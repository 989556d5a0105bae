"""Transformer NMT + beam search (capability target: GluonNLP
``transformer_en_de_512`` and ``BeamSearchSampler`` — SURVEY.md §2.6
"External zoos", upstream example/gluon NMT scripts).

TPU-first design notes:
- The whole teacher-forcing step (encoder + decoder + label-smoothed
  loss) hybridizes to ONE XLA program; attention is the fused SDPA op
  (flash kernel on chip).
- Incremental translation mirrors ``LlamaForCausalLM``: per-layer
  self-attention KV caches written in place at a dynamic offset, so
  every decode step reuses one compiled program regardless of position.
  Cross-attention K/V are projected from the encoder memory ONCE at
  decode init — the classic inference-time transformer optimization.
- ``BeamSearchSampler`` keeps all heavy math on device: candidate
  scores and the (K·V)-wide top-k run as device programs; only the
  (B, K) winner bookkeeping happens on host.  Beam-reordering of the
  cached decoder state is a batched ``take`` along axis 0.
"""
from __future__ import annotations

import numpy as np

from ..base import MXNetError
from ..gluon.block import HybridBlock
from ..gluon import nn
from ..gluon.contrib.nn import TransformerEncoder

__all__ = ["TransformerNMT", "BeamSearchScorer", "BeamSearchSampler",
           "get_nmt", "nmt_tiny", "transformer_en_de_512"]


def _sinusoid_table(max_len, units):
    """Vaswani-style fixed position encodings (max_len, units)."""
    pos = np.arange(max_len, dtype=np.float64)[:, None]
    dim = np.arange(units // 2, dtype=np.float64)[None, :]
    ang = pos / np.power(10000.0, 2.0 * dim / units)
    table = np.zeros((max_len, units), dtype=np.float32)
    table[:, 0::2] = np.sin(ang)
    table[:, 1::2] = np.cos(ang)
    return table


class _DecoderAttention(HybridBlock):
    """Self- or cross-attention with explicit projections so the decode
    path can cache K/V (MultiHeadAttention hides its projections and has
    no incremental step)."""

    def __init__(self, units, num_heads, **kwargs):
        super().__init__(**kwargs)
        if units % num_heads:
            raise MXNetError(f"units {units} % num_heads {num_heads}")
        self._h = num_heads
        self._d = units // num_heads
        self._units = units
        with self.name_scope():
            self.q_proj = nn.Dense(units, flatten=False, in_units=units,
                                   prefix="q_")
            self.k_proj = nn.Dense(units, flatten=False, in_units=units,
                                   prefix="k_")
            self.v_proj = nn.Dense(units, flatten=False, in_units=units,
                                   prefix="v_")
            self.o_proj = nn.Dense(units, flatten=False, in_units=units,
                                   prefix="o_")

    def _split(self, F, x):
        b, s = x.shape[0], x.shape[1]
        return x.reshape((b, s, self._h, self._d))

    def hybrid_forward(self, F, query, key, value, mask=None,
                       causal=False):
        b, s_q = query.shape[0], query.shape[1]
        q = self._split(F, self.q_proj(query))
        k = self._split(F, self.k_proj(key))
        v = self._split(F, self.v_proj(value))
        if mask is not None:
            out = F.dot_product_attention(q, k, v, mask, causal=causal,
                                          use_mask=True)
        else:
            out = F.dot_product_attention(q, k, v, causal=causal)
        return self.o_proj(out.reshape((b, s_q, self._units)))

    def project_kv(self, memory):
        """Encoder memory → (K, V) in (B, S, H, D), computed once per
        translation instead of once per step."""
        k = self._split(None, self.k_proj(memory))
        v = self._split(None, self.v_proj(memory))
        return k, v

    def step_self(self, x, cache_k, cache_v, offset, mask):
        """One-token self-attention against the in-place KV cache."""
        from .. import ndarray as nd
        b = x.shape[0]
        q = self._split(None, self.q_proj(x))
        k_t = self._split(None, self.k_proj(x))
        v_t = self._split(None, self.v_proj(x))
        nd._cache_update(cache_k, k_t, offset=offset, out=cache_k)
        nd._cache_update(cache_v, v_t, offset=offset, out=cache_v)
        out = nd.dot_product_attention(q, cache_k, cache_v, mask,
                                       use_mask=True)
        return self.o_proj(out.reshape((b, 1, self._units)))

    def step_cross(self, x, mem_k, mem_v, mask=None):
        """One-token cross-attention against pre-projected memory."""
        from .. import ndarray as nd
        b = x.shape[0]
        q = self._split(None, self.q_proj(x))
        if mask is not None:
            out = nd.dot_product_attention(q, mem_k, mem_v, mask,
                                           use_mask=True)
        else:
            out = nd.dot_product_attention(q, mem_k, mem_v)
        return self.o_proj(out.reshape((b, 1, self._units)))


class TransformerDecoderCell(HybridBlock):
    """Post-LN decoder layer: self-attn → cross-attn → FFN, residual
    around each (Vaswani layout, as the reference transformer)."""

    def __init__(self, units, hidden_size, num_heads, dropout=0.0,
                 activation="relu", **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.self_attn = _DecoderAttention(units, num_heads,
                                               prefix="self_")
            self.cross_attn = _DecoderAttention(units, num_heads,
                                                prefix="cross_")
            self.ffn_1 = nn.Dense(hidden_size, flatten=False,
                                  in_units=units, prefix="ffn1_")
            self.ffn_2 = nn.Dense(units, flatten=False,
                                  in_units=hidden_size, prefix="ffn2_")
            self.norm_self = nn.LayerNorm(in_channels=units)
            self.norm_cross = nn.LayerNorm(in_channels=units)
            self.norm_ffn = nn.LayerNorm(in_channels=units)
            self.drop = nn.Dropout(dropout) if dropout else None
        self._activation = activation

    def _ffn(self, F, x):
        h = self.ffn_1(x)
        h = F.Activation(h, act_type=self._activation)
        h = self.ffn_2(h)
        if self.drop is not None:
            h = self.drop(h)
        return h

    def hybrid_forward(self, F, x, memory, tgt_mask=None,
                       mem_mask=None):
        att = self.self_attn(x, x, x, tgt_mask, True)
        if self.drop is not None:
            att = self.drop(att)
        x = self.norm_self(x + att)
        att = self.cross_attn(x, memory, memory, mem_mask, False)
        if self.drop is not None:
            att = self.drop(att)
        x = self.norm_cross(x + att)
        return self.norm_ffn(x + self._ffn(F, x))

    def step(self, x, cache_k, cache_v, offset, self_mask, mem_k,
             mem_v, mem_mask):
        from .. import ndarray as nd
        att = self.self_attn.step_self(x, cache_k, cache_v, offset,
                                       self_mask)
        x = self.norm_self(x + att)
        att = self.cross_attn.step_cross(x, mem_k, mem_v, mem_mask)
        x = self.norm_cross(x + att)
        return self.norm_ffn(x + self._ffn(nd, x))


class TransformerNMT(HybridBlock):
    """Encoder-decoder transformer for sequence-to-sequence tasks.

    Conventions (GluonNLP NMT): token 0 usable as PAD, the caller
    supplies BOS/EOS ids; ``hybrid_forward`` is the teacher-forcing
    pass returning (B, T, tgt_vocab) logits; ``translate`` runs beam
    search through the cached incremental decoder.
    """

    def __init__(self, src_vocab_size, tgt_vocab_size=None, units=512,
                 hidden_size=2048, num_layers=6, num_heads=8,
                 max_length=512, dropout=0.1, activation="relu",
                 share_embed=False, tie_output=True, **kwargs):
        super().__init__(**kwargs)
        if share_embed and tgt_vocab_size not in (None, src_vocab_size):
            raise MXNetError("share_embed requires equal vocabularies")
        tgt_vocab_size = tgt_vocab_size or src_vocab_size
        self.src_vocab_size = src_vocab_size
        self.tgt_vocab_size = tgt_vocab_size
        self._units = units
        self._scale = float(np.sqrt(units))
        self._tied = tie_output
        self._num_layers = num_layers
        self._heads = num_heads
        with self.name_scope():
            self.src_embed = nn.Embedding(src_vocab_size, units,
                                          prefix="src_embed_")
            self.tgt_embed = (self.src_embed if share_embed else
                              nn.Embedding(tgt_vocab_size, units,
                                           prefix="tgt_embed_"))
            self.pos_table = self.params.get_constant(
                "pos_table", _sinusoid_table(max_length, units))
            self.encoder = TransformerEncoder(
                units, hidden_size, num_layers, num_heads,
                dropout=dropout, activation=activation, prefix="enc_")
            self.decoder_cells = []
            for i in range(num_layers):
                cell = TransformerDecoderCell(
                    units, hidden_size, num_heads, dropout=dropout,
                    activation=activation, prefix=f"dec{i}_")
                self.register_child(cell)
                self.decoder_cells.append(cell)
            if not tie_output:
                self.out_proj = nn.Dense(tgt_vocab_size, flatten=False,
                                         use_bias=False, in_units=units,
                                         prefix="out_")

    # ---- masks -------------------------------------------------------

    @staticmethod
    def _key_mask(F, valid_length, s, ctx):
        """(B,) valid lengths → (B, 1, 1, S) boolean key mask."""
        steps = F.arange(0, s, ctx=ctx)
        m = F.broadcast_lesser(
            F.expand_dims(steps, axis=0),
            F.expand_dims(valid_length.astype("float32"), axis=1))
        return F.expand_dims(F.expand_dims(m, axis=1), axis=1)

    # ---- teacher-forcing path ---------------------------------------

    def _embed(self, F, embed, tokens, pos_table=None):
        s = tokens.shape[1]
        if pos_table is None:
            pos_table = self.pos_table.data(tokens.context)
        pos = F.slice_axis(pos_table, axis=0, begin=0, end=s)
        return embed(tokens) * self._scale + F.expand_dims(pos, axis=0)

    def _head(self, F, h):
        if self._tied:
            w = self.tgt_embed.weight.data(h.context)
            b, s, u = h.shape
            return F.dot(h.reshape((b * s, u)), w,
                         transpose_b=True).reshape(
                             (b, s, self.tgt_vocab_size))
        return self.out_proj(h)

    def encode(self, src, src_valid=None):
        from .. import ndarray as nd
        x = self._embed(nd, self.src_embed, src)
        mask = None
        if src_valid is not None:
            mask = self._key_mask(nd, src_valid, src.shape[1],
                                  src.context)
        return self.encoder(x, mask)

    def hybrid_forward(self, F, src, tgt, src_valid=None,
                       tgt_valid=None, pos_table=None):
        s_src, s_tgt = src.shape[1], tgt.shape[1]
        x = self._embed(F, self.src_embed, src, pos_table)
        src_mask = None
        if src_valid is not None:
            src_mask = self._key_mask(F, src_valid, s_src, src.context)
        memory = self.encoder(x, src_mask)

        y = self._embed(F, self.tgt_embed, tgt, pos_table)
        tgt_mask = None
        if tgt_valid is not None:
            tgt_mask = self._key_mask(F, tgt_valid, s_tgt, tgt.context)
        for cell in self.decoder_cells:
            y = cell(y, memory, tgt_mask, src_mask)
        return self._head(F, y)

    def loss(self, src, tgt_in, tgt_out, src_valid=None, tgt_valid=None,
             label_smoothing=0.1):
        """Label-smoothed cross entropy (Vaswani ε=0.1), masked to the
        valid target positions; returns a scalar."""
        from .. import ndarray as nd
        logits = self(src, tgt_in, src_valid, tgt_valid)
        b, t, v = logits.shape
        logp = nd.log_softmax(logits.reshape((b * t, v)), axis=-1)
        lbl = tgt_out.reshape((-1,)).astype("int32")
        nll = -nd.pick(logp, lbl, axis=-1)
        smooth = -nd.mean(logp, axis=-1)
        per_tok = ((1.0 - label_smoothing) * nll
                   + label_smoothing * smooth)
        if tgt_valid is not None:
            steps = nd.arange(0, t, ctx=src.context).reshape((1, t))
            keep = (steps < tgt_valid.astype("float32").reshape(
                (b, 1))).astype("float32").reshape((-1,))
            return nd.sum(per_tok * keep) / nd.sum(keep)
        return nd.mean(per_tok)

    # ---- incremental decode (beam/greedy) ---------------------------

    def init_decode(self, memory, max_len, src_valid=None):
        """Build decode state: per-layer empty self-attn caches
        (``states`` — the part beam search reorders), pre-projected
        cross K/V (``mem_kvs`` — invariant across steps, kept OUT of
        the reordered state so beams never re-gather it), and the
        memory key mask."""
        from .. import ndarray as nd
        if max_len > self.pos_table.shape[0]:
            raise MXNetError(
                f"max_len {max_len} exceeds the position table "
                f"({self.pos_table.shape[0]} rows; raise max_length)")
        b = memory.shape[0]
        h, d = self._heads, self._units // self._heads
        states, mem_kvs = [], []
        for cell in self.decoder_cells:
            ck = nd.zeros((b, max_len, h, d), ctx=memory.context)
            cv = nd.zeros((b, max_len, h, d), ctx=memory.context)
            states.append([ck, cv])
            mem_kvs.append(cell.cross_attn.project_kv(memory))
        mem_mask = None
        if src_valid is not None:
            mem_mask = self._key_mask(nd, src_valid, memory.shape[1],
                                      memory.context)
        return states, mem_kvs, mem_mask

    def decode_step(self, tok, states, mem_kvs, offset, mem_mask=None):
        """tok (B, 1) → log-probs (B, tgt_vocab); states updated in
        place.  One compiled program for every position: the position
        row is fetched with a dynamic ``take`` (a static slice at
        ``offset`` would bake the position into the program and compile
        anew each step)."""
        from .. import ndarray as nd
        pos_idx = nd.array(np.array([offset], np.float32),
                           ctx=tok.context)
        pos = nd.take(self.pos_table.data(tok.context), pos_idx, axis=0)
        x = (self.tgt_embed(tok) * self._scale
             + nd.expand_dims(pos, axis=0))
        max_len = states[0][0].shape[1]
        # mask on the token's device, not the default context's
        self_mask = (nd.arange(max_len, ctx=tok.context)
                     <= float(offset)).reshape((1, 1, 1, max_len))
        for cell, (ck, cv), (mk, mv) in zip(self.decoder_cells, states,
                                            mem_kvs):
            x = cell.step(x, ck, cv, offset, self_mask, mk, mv,
                          mem_mask)
        logits = self._head(nd, x).reshape((x.shape[0],
                                            self.tgt_vocab_size))
        return nd.log_softmax(logits, axis=-1)

    def translate(self, src, bos_id, eos_id, src_valid=None,
                  beam_size=4, max_len=None, alpha=1.0):
        """Beam-search translation → (samples (B, K, L), scores (B, K),
        lengths (B, K)); samples start with BOS and include EOS when
        produced."""
        max_len = min(max_len or (2 * src.shape[1] + 8),
                      self.pos_table.shape[0])
        memory = self.encode(src, src_valid)
        sampler = BeamSearchSampler(
            beam_size=beam_size, eos_id=eos_id,
            scorer=BeamSearchScorer(alpha=alpha), max_length=max_len)

        from .. import ndarray as nd
        b = src.shape[0]
        mem_t = _tile_rows(memory, beam_size)
        sv_t = None
        if src_valid is not None:
            sv_t = _tile_rows(src_valid, beam_size)
        states, mem_kvs, mem_mask = self.init_decode(mem_t, max_len,
                                                     sv_t)

        def decoder(tok, step_idx, st):
            return (self.decode_step(tok, st, mem_kvs, step_idx,
                                     mem_mask), st)

        start = nd.full((b * beam_size, 1), float(bos_id),
                        ctx=src.context)
        return sampler(decoder, start, states, batch_size=b)


def _tile_rows(x, k):
    """(B, ...) → (B*K, ...) with each row repeated K times."""
    from .. import ndarray as nd
    return nd.repeat(x, repeats=k, axis=0)


class BeamSearchScorer:
    """Google-NMT length-penalized score (Wu et al. 2016), the
    GluonNLP default: score = logprob_sum / ((5 + len) / 6) ** alpha."""

    def __init__(self, alpha=1.0, K=5.0):
        self.alpha = float(alpha)
        self.K = float(K)

    def __call__(self, log_probs, length):
        lp = ((self.K + length) / (self.K + 1.0)) ** self.alpha
        return log_probs / lp


class BeamSearchSampler:
    """Generic beam search over an incremental decoder.

    ``decoder(tok, step_idx, states) -> (log_probs (B*K, V), states)``
    with states any nest of NDArrays whose leading axis is the flat
    beam axis B*K — after each step the sampler reorders that axis by
    the surviving beams' parent indices (a device ``take``).

    Device/host split: per-step score expansion and the (K·V)-wide
    top-k run on device; only the (B, 2K) winner indices come to host
    for the EOS/finished bookkeeping.
    """

    def __init__(self, beam_size, eos_id, scorer=None, max_length=64):
        self.beam_size = int(beam_size)
        self.eos_id = int(eos_id)
        self.scorer = scorer or BeamSearchScorer()
        self.max_length = int(max_length)

    def __call__(self, decoder, start_tokens, states, batch_size):
        from .. import ndarray as nd
        b, k = batch_size, self.beam_size
        if start_tokens.shape[0] != b * k:
            raise MXNetError(
                f"start_tokens leading axis {start_tokens.shape[0]} != "
                f"batch_size*beam_size {b * k}")
        ctx = start_tokens.context
        # beam 0 of each batch row is live; the rest start at -inf so
        # the first expansion seeds distinct hypotheses from beam 0
        logp_sum = np.full((b, k), -np.inf, np.float64)
        logp_sum[:, 0] = 0.0
        hist = start_tokens.asnumpy().astype(np.int64).reshape(b, k, 1)
        alive = np.ones((b, k), bool)
        lengths = np.ones((b, k), np.int64)   # counts BOS
        cur = start_tokens
        finished = [[] for _ in range(b)]     # (score, token_list)

        for step in range(self.max_length - 1):
            logp, states = decoder(cur, step, states)  # (B*K, V)
            v = logp.shape[-1]
            # dead/unfilled beams carry -inf sums; clamp to a finite
            # floor so the device-side add never produces NaN (the
            # -1e29 host filter below then discards their children —
            # -inf * 0 tricks would leave NaN, whose top_k order is
            # unspecified)
            cand = logp + nd.array(
                np.maximum(logp_sum, -1e30).reshape(-1, 1)
                .astype(np.float32), ctx=ctx)
            # (B, K*V) top-2K on device; 2K so EOS picks never starve
            # the live-beam quota
            cand = cand.reshape((b, k * v))
            n_top = min(2 * k, k * v)
            top_scores, top_idx = nd.topk(
                cand, k=n_top, axis=-1, ret_typ="both")
            ts = top_scores.asnumpy().astype(np.float64)
            ti = top_idx.asnumpy().astype(np.int64)

            new_logp = np.full((b, k), -np.inf, np.float64)
            new_alive = np.zeros((b, k), bool)
            new_len = np.ones((b, k), np.int64)
            parent = np.zeros((b, k), np.int64)
            next_tok = np.zeros((b, k), np.int64)
            for i in range(b):
                slot = 0
                for j in range(n_top):
                    if slot == k:
                        break
                    if ts[i, j] <= -1e29:
                        continue
                    pj, tj = divmod(int(ti[i, j]), v)
                    seq_len = lengths[i, pj] + 1
                    if tj == self.eos_id:
                        seq = np.concatenate(
                            [hist[i, pj], [self.eos_id]])
                        sc = self.scorer(ts[i, j], float(seq_len))
                        finished[i].append((sc, seq))
                        continue
                    new_logp[i, slot] = ts[i, j]
                    new_alive[i, slot] = True
                    new_len[i, slot] = seq_len
                    parent[i, slot] = pj
                    next_tok[i, slot] = tj
                    slot += 1
            logp_sum, alive, lengths = new_logp, new_alive, new_len
            if not alive.any():
                break
            # reorder the beam axis of every state by parent index
            flat_parent = (parent
                           + np.arange(b)[:, None] * k).reshape(-1)
            hist = np.concatenate(
                [hist[np.arange(b)[:, None], parent],
                 next_tok[:, :, None]], axis=-1)
            if step < self.max_length - 2:
                # the final iteration's gather/upload would never be
                # consumed — only the host-side close-out remains
                idx_nd = nd.array(flat_parent.astype(np.float32),
                                  ctx=ctx)
                states = _gather_states(states, idx_nd)
                cur = nd.array(next_tok.reshape(b * k, 1).astype(
                    np.float32), ctx=ctx)

        # close out still-alive beams without EOS at max length
        for i in range(b):
            for j in range(k):
                if alive[i, j]:
                    sc = self.scorer(logp_sum[i, j],
                                     float(lengths[i, j]))
                    finished[i].append((sc, hist[i, j]))
            if not finished[i]:   # degenerate: everything pruned
                finished[i].append((-np.inf, hist[i, 0]))

        # pad + sort per batch row, best first
        max_out = max(len(s) for row in finished for _, s in row)
        samples = np.full((b, k, max_out), self.eos_id, np.int64)
        scores = np.full((b, k), -np.inf, np.float64)
        lens = np.zeros((b, k), np.int64)
        for i in range(b):
            best = sorted(finished[i], key=lambda t: -t[0])[:k]
            for j, (sc, seq) in enumerate(best):
                samples[i, j, :len(seq)] = seq
                scores[i, j] = sc
                lens[i, j] = len(seq)
        return (nd.array(samples.astype(np.float32), ctx=ctx),
                nd.array(scores.astype(np.float32), ctx=ctx),
                nd.array(lens.astype(np.float32), ctx=ctx))


def _gather_states(states, idx_nd):
    """Reorder the leading (flat beam) axis of every NDArray in a nest."""
    from .. import ndarray as nd
    if hasattr(states, "context"):   # NDArray leaf
        return nd.take(states, idx_nd, axis=0)
    if isinstance(states, (list, tuple)):
        out = [_gather_states(s, idx_nd) for s in states]
        return out if isinstance(states, list) else tuple(out)
    return states


_NMT_SPECS = {
    # test-size config (trains in seconds on the CPU backend)
    "nmt_tiny": dict(units=32, hidden_size=64, num_layers=2,
                     num_heads=2, max_length=64, dropout=0.0),
    # the GluonNLP WMT en-de base config
    "transformer_en_de_512": dict(units=512, hidden_size=2048,
                                  num_layers=6, num_heads=8,
                                  max_length=512, dropout=0.1),
}


def get_nmt(name, src_vocab_size, tgt_vocab_size=None, **kwargs):
    if name not in _NMT_SPECS:
        raise MXNetError(f"unknown nmt config {name!r}; options "
                         f"{sorted(_NMT_SPECS)}")
    spec = dict(_NMT_SPECS[name])
    spec.update(kwargs)
    return TransformerNMT(src_vocab_size, tgt_vocab_size, **spec)


def nmt_tiny(src_vocab_size, **kwargs):
    return get_nmt("nmt_tiny", src_vocab_size, **kwargs)


def transformer_en_de_512(src_vocab_size, **kwargs):
    return get_nmt("transformer_en_de_512", src_vocab_size, **kwargs)
