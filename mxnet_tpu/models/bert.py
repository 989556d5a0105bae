"""BERT (capability target: GluonNLP BERT-base — SURVEY.md §2.6
"External zoos"; BASELINE config #3 "BERT-base pretraining
samples/sec/chip").

``BERTModel`` = embeddings (word + position + token-type) → N transformer
encoder layers (fused SDPA, flash on TPU) → pooler; ``BERTForPretrain``
adds the masked-LM head (decoder tied to word embeddings) and
next-sentence head, returning the summed pretraining loss.  The whole
pretraining step hybridizes/jits to one XLA program; data parallelism
comes from ``mx.parallel.DataParallelTrainer`` unchanged.
"""
from __future__ import annotations

import numpy as np

from ..base import MXNetError
from ..gluon.block import HybridBlock
from ..gluon import nn
from ..gluon.contrib.nn import TransformerEncoder
from ..profiler import device_scope

__all__ = ["BERTModel", "BERTForPretrain", "bert_base", "bert_small",
           "bert_large", "get_bert"]


class BERTModel(HybridBlock):
    def __init__(self, vocab_size=30522, units=768, hidden_size=3072,
                 num_layers=12, num_heads=12, max_length=512,
                 type_vocab_size=2, dropout=0.1, remat=False,
                 scan_layers=False, **kwargs):
        super().__init__(**kwargs)
        self._units = units
        self.vocab_size = vocab_size
        with self.name_scope():
            self.word_embed = nn.Embedding(vocab_size, units,
                                           prefix="word_embed_")
            self.token_type_embed = nn.Embedding(type_vocab_size, units,
                                                 prefix="type_embed_")
            self.position_embed = self.params.get(
                "position_embed", shape=(max_length, units),
                init="normal")
            self.embed_layer_norm = nn.LayerNorm(in_channels=units)
            self.embed_dropout = nn.Dropout(dropout) if dropout else None
            self.encoder = TransformerEncoder(
                units, hidden_size, num_layers, num_heads,
                dropout=dropout, activation="gelu", remat=remat,
                scan_layers=scan_layers, prefix="enc_")
            self.pooler = nn.Dense(units, activation="tanh",
                                   in_units=units, flatten=False,
                                   prefix="pooler_")

    def hybrid_forward(self, F, inputs, token_types, valid_length=None,
                       position_embed=None):
        b, s = inputs.shape[0], inputs.shape[1]
        with device_scope("mxtpu.embed"):
            x = self.word_embed(inputs) \
                + self.token_type_embed(token_types)
            x = x + F.expand_dims(
                F.slice_axis(position_embed, axis=0, begin=0, end=s),
                axis=0)
            x = self.embed_layer_norm(x)
            if self.embed_dropout is not None:
                x = self.embed_dropout(x)
        mask = None
        if valid_length is not None:
            # (B, 1, 1, S) key-padding mask broadcast over heads & queries
            with device_scope("mxtpu.mixer.full"):
                steps = F.arange(0, s, ctx=inputs.context)
                mask = F.broadcast_lesser(
                    F.expand_dims(steps, axis=0),
                    F.expand_dims(valid_length.astype("float32"), axis=1))
                mask = F.expand_dims(F.expand_dims(mask, axis=1), axis=1)
        seq = self.encoder(x, mask)
        with device_scope("mxtpu.head"):
            pooled = self.pooler(F.slice_axis(seq, axis=1, begin=0,
                                              end=1).reshape((b, -1)))
        return seq, pooled


class BERTForPretrain(HybridBlock):
    """MLM + NSP pretraining heads over BERTModel.

    ``decode_mlm=False`` skips the tied decode matmul and returns the
    pre-decode MLM hidden plus the tied weight and bias instead of
    logits, so the caller can fuse decode+CE with
    ``nd.chunked_softmax_ce_bias`` — the (B·M, V) logits (156 MB at
    bert_base b64/m20) are then never materialized.  The r5 on-chip
    ablation measured the decoded-logits MLM head at 18.6 ms of an
    81.3 ms step, far above its ~1 ms of matmul FLOPs — the gap is
    logits HBM traffic, which the fused path removes.
    """

    def __init__(self, bert: BERTModel, decode_mlm=True, **kwargs):
        super().__init__(**kwargs)
        units = bert._units
        self._decode_mlm = bool(decode_mlm)
        with self.name_scope():
            self.bert = bert
            self.mlm_dense = nn.Dense(units, activation=None,
                                      in_units=units, flatten=False,
                                      prefix="mlm_dense_")
            self.mlm_norm = nn.LayerNorm(in_channels=units)
            self.mlm_bias = self.params.get("mlm_bias",
                                            shape=(bert.vocab_size,),
                                            init="zeros")
            self.nsp_classifier = nn.Dense(2, in_units=units,
                                           prefix="nsp_")

    def hybrid_forward(self, F, inputs, token_types, valid_length,
                       masked_positions, mlm_bias=None):
        seq, pooled = self.bert(inputs, token_types, valid_length)
        with device_scope("mxtpu.head"):
            mlm_in = _gather_positions(F, seq, masked_positions)
            h = self.mlm_dense(mlm_in)
            h = F.LeakyReLU(h, act_type="gelu")
            h = self.mlm_norm(h)
            # decode with TIED word-embedding weights: under CachedOp
            # tracing the weight's buffer holds the trace-time tracer,
            # so gradients flow to the embedding from both uses
            word_w = self.bert.word_embed.weight.data(h.context)
            nsp_scores = self.nsp_classifier(pooled)
            h2 = h.reshape((-1, h.shape[-1]))
            if not self._decode_mlm:
                # fused-CE contract: (hidden, nsp, tied weight, bias) —
                # feed the first/last two to chunked_softmax_ce_bias
                return h2, nsp_scores, word_w, mlm_bias
            mlm_scores = F.dot(h2, word_w, transpose_b=True) + mlm_bias
            return mlm_scores, nsp_scores


def _gather_positions(F, seq, positions):
    """seq (B,S,U), positions (B,M) → (B,M,U)."""
    b, s, u = seq.shape
    m = positions.shape[1]
    flat = seq.reshape((b * s, u))
    offset = F.arange(0, b, ctx=seq.context).reshape((b, 1)) * s
    idx = (positions.astype("float32") + offset).reshape((-1,))
    out = F.take(flat, idx, axis=0, mode="clip")
    return out.reshape((b, m, u))


_BERT_SPECS = {
    "bert_small": dict(units=256, hidden_size=1024, num_layers=4,
                       num_heads=4),
    "bert_base": dict(units=768, hidden_size=3072, num_layers=12,
                      num_heads=12),
    "bert_large": dict(units=1024, hidden_size=4096, num_layers=24,
                       num_heads=16),
}


def get_bert(name, vocab_size=30522, max_length=512, dropout=0.1,
             **kwargs):
    if name not in _BERT_SPECS:
        raise MXNetError(f"unknown bert config {name!r}; options "
                         f"{sorted(_BERT_SPECS)}")
    spec = dict(_BERT_SPECS[name])
    spec.update(kwargs)
    return BERTModel(vocab_size=vocab_size, max_length=max_length,
                     dropout=dropout, **spec)


def bert_base(**kwargs):
    return get_bert("bert_base", **kwargs)


def bert_small(**kwargs):
    return get_bert("bert_small", **kwargs)


def bert_large(**kwargs):
    return get_bert("bert_large", **kwargs)
