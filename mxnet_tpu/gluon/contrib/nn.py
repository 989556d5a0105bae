"""Transformer building blocks (capability target: GluonNLP's
``gluonnlp.model.transformer``/BERT blocks — SURVEY.md §2.6 "External
zoos" and §5 "Long-context").

Built on the fused ``dot_product_attention`` op (Pallas flash path on
TPU): one op per attention instead of the reference's interleaved-matmul
chains.
"""
from __future__ import annotations

import math

import numpy as np

from ...base import MXNetError
from ...profiler import device_scope
from ..block import HybridBlock
from .. import nn

__all__ = ["MultiHeadAttention", "PositionwiseFFN",
           "TransformerEncoderCell", "TransformerEncoder", "MoEFFN",
           "SyncBatchNorm"]


class MultiHeadAttention(HybridBlock):
    """Multi-head self/cross attention (units == num_heads * head_dim)."""

    def __init__(self, units, num_heads, dropout=0.0, use_bias=True,
                 **kwargs):
        super().__init__(**kwargs)
        if units % num_heads:
            raise MXNetError(f"units {units} not divisible by num_heads "
                             f"{num_heads}")
        self._units = units
        self._num_heads = num_heads
        self._dropout = dropout
        with self.name_scope():
            self.query_proj = nn.Dense(units, flatten=False,
                                       use_bias=use_bias, prefix="query_")
            self.key_proj = nn.Dense(units, flatten=False,
                                     use_bias=use_bias, prefix="key_")
            self.value_proj = nn.Dense(units, flatten=False,
                                       use_bias=use_bias, prefix="value_")
            self.out_proj = nn.Dense(units, flatten=False,
                                     use_bias=use_bias, prefix="out_")
            self.drop = nn.Dropout(dropout) if dropout else None

    def hybrid_forward(self, F, query, key=None, value=None, mask=None):
        if key is None:
            key = query
        if value is None:
            value = key
        b, s_q = query.shape[0], query.shape[1]
        s_k = key.shape[1]
        h = self._num_heads
        d = self._units // h
        with device_scope("mxtpu.mixer.full"):
            q = self.query_proj(query).reshape((b, s_q, h, d))
            k = self.key_proj(key).reshape((b, s_k, h, d))
            v = self.value_proj(value).reshape((b, s_k, h, d))
            if mask is not None:
                out = F.dot_product_attention(q, k, v, mask,
                                              use_mask=True)
            else:
                out = F.dot_product_attention(q, k, v)
            out = out.reshape((b, s_q, self._units))
            out = self.out_proj(out)
            if self.drop is not None:
                out = self.drop(out)
            return out


class PositionwiseFFN(HybridBlock):
    def __init__(self, units, hidden_size, dropout=0.0, activation="gelu",
                 **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.ffn_1 = nn.Dense(hidden_size, flatten=False,
                                  prefix="ffn1_")
            self.ffn_2 = nn.Dense(units, flatten=False, prefix="ffn2_")
            self.drop = nn.Dropout(dropout) if dropout else None
        self._activation = activation

    def hybrid_forward(self, F, x):
        with device_scope("mxtpu.mlp"):
            h = self.ffn_1(x)
            if self._activation == "gelu":
                h = F.LeakyReLU(h, act_type="gelu")
            else:
                h = F.Activation(h, act_type=self._activation)
            h = self.ffn_2(h)
            if self.drop is not None:
                h = self.drop(h)
            return h


# trace-time count of rematerialized encoder stacks (tests assert the
# checkpoint branch actually fired, not merely that numerics matched)
_REMAT_APPLICATIONS = 0

# trace-time count of scan-over-layers encoder stacks (same contract)
_SCAN_APPLICATIONS = 0


class TransformerEncoderCell(HybridBlock):
    """Pre/post-LN encoder layer (BERT uses post-LN, the default)."""

    def __init__(self, units, hidden_size, num_heads, dropout=0.0,
                 activation="gelu", pre_norm=False, **kwargs):
        super().__init__(**kwargs)
        self._pre_norm = pre_norm
        with self.name_scope():
            self.attention = MultiHeadAttention(units, num_heads,
                                                dropout=dropout)
            self.ffn = PositionwiseFFN(units, hidden_size,
                                       dropout=dropout,
                                       activation=activation)
            self.layer_norm_att = nn.LayerNorm(in_channels=units)
            self.layer_norm_ffn = nn.LayerNorm(in_channels=units)
            self.drop = nn.Dropout(dropout) if dropout else None

    def hybrid_forward(self, F, x, mask=None):
        # Block.__call__ is positional: (query, key, value, mask).  A
        # sub-layer's norm and residual add carry the sub-layer's
        # device scope: they are passes over its output
        if self._pre_norm:
            with device_scope("mxtpu.mixer.full"):
                x = x + self.attention(self.layer_norm_att(x), None, None,
                                       mask)
            with device_scope("mxtpu.mlp"):
                return x + self.ffn(self.layer_norm_ffn(x))
        with device_scope("mxtpu.mixer.full"):
            att = self.attention(x, None, None, mask)
            if self.drop is not None:
                att = self.drop(att)
            x = self.layer_norm_att(x + att)
        with device_scope("mxtpu.mlp"):
            return self.layer_norm_ffn(x + self.ffn(x))


class TransformerEncoder(HybridBlock):
    """Stack of encoder cells.

    ``remat=True`` wraps each layer in ``jax.checkpoint`` when running
    inside a jitted trace (the fused trainer, hybridized forward):
    activations are recomputed during backward instead of stored, so
    batch x seq configurations that would overflow HBM fit — the
    standard FLOPs-for-memory trade on TPU.  Numerically identical to
    the uncheckpointed stack (same program, different schedule).

    ``scan_layers=True`` runs the stack as ONE ``lax.scan`` over
    stacked per-layer weights instead of unrolling N layers into the
    program.  Same math, same parameters (stacked at trace time, so
    gradients flow to each layer's own tensors) — but the compiled
    program contains ONE layer body, cutting XLA compile time ~N-fold.
    The TPU-first shape for deep transformers: the reference unrolls
    because graph-per-layer is how imperative frameworks work; under a
    tracing compiler the loop belongs in the IR (``lax.scan``), not the
    Python. Composes with ``remat`` (the scan body is checkpointed).
    Dropout draws a distinct folded key per layer, matching the
    unrolled stack's per-layer independence."""

    def __init__(self, units, hidden_size, num_layers, num_heads,
                 dropout=0.0, activation="gelu", pre_norm=False,
                 remat=False, scan_layers=False, **kwargs):
        super().__init__(**kwargs)
        self._remat = remat
        self._scan_layers = scan_layers
        with self.name_scope():
            self.layers = []
            for i in range(num_layers):
                cell = TransformerEncoderCell(
                    units, hidden_size, num_heads, dropout=dropout,
                    activation=activation, pre_norm=pre_norm,
                    prefix=f"layer{i}_")
                self.register_child(cell, f"layer{i}")
                self.layers.append(cell)

    def _cell_param_refs(self, cell):
        """(suffix, NDArray) pairs in a deterministic order shared by
        every cell — suffixes are the param names with the per-layer
        prefix stripped."""
        pfx = cell.prefix
        items = []
        for name, p in cell.collect_params().items():
            suffix = name[len(pfx):] if name.startswith(pfx) else name
            items.append((suffix, p.data()))
        items.sort(key=lambda kv: kv[0])
        return items

    def _scan_forward(self, x, mask):
        import jax
        import jax.numpy as jnp
        from ... import random as _rnd
        from ...ndarray.ndarray import NDArray

        global _SCAN_APPLICATIONS
        _SCAN_APPLICATIONS += 1
        ctx = x.context
        cell0 = self.layers[0]
        ref_items = self._cell_param_refs(cell0)
        refs = [nd for _, nd in ref_items]
        order = [s for s, _ in ref_items]

        layer_bufs = []
        for cell in self.layers:
            items = dict(self._cell_param_refs(cell))
            if sorted(items) != sorted(order):
                raise MXNetError(
                    "scan_layers=True needs structurally identical "
                    f"cells; {cell.prefix} params differ from "
                    f"{cell0.prefix}")
            layer_bufs.append([items[s]._buf for s in order])
        stacked = tuple(
            jnp.stack([bufs[i] for bufs in layer_bufs])
            for i in range(len(order)))

        # one ambient key, folded per layer INSIDE the scan so each
        # layer's dropout masks are independent (as in the unrolled
        # stack); nested fold_in inside the body separates multiple
        # dropout sites within a layer
        base = _rnd._next_key_nd(ctx)._data
        layer_keys = jnp.stack([
            jax.random.key_data(jax.random.fold_in(
                jax.random.wrap_key_data(base), i))
            for i in range(len(self.layers))])

        def body(carry, xs):
            sliced, kraw = xs[:-1], xs[-1]
            counter = [0]

            def provider(_ctx):
                k = jax.random.fold_in(
                    jax.random.wrap_key_data(kraw), counter[0])
                counter[0] += 1
                return NDArray(jax.random.key_data(k), ctx=ctx)

            saved = [(r._buf, r._version) for r in refs]
            _rnd._push_key_provider(provider)
            try:
                for r, s in zip(refs, sliced):
                    r._buf = s
                out = cell0(NDArray(carry, ctx=ctx), mask)
            finally:
                _rnd._pop_key_provider()
                for r, (b, v) in zip(refs, saved):
                    r._buf = b
                    r._version = v
            return out._data, None

        if self._remat:
            # the remat-fired counter must also reflect this path — a
            # checkpointed scan body IS the remat contract applying
            global _REMAT_APPLICATIONS
            _REMAT_APPLICATIONS += 1
            body = jax.checkpoint(body)
        out, _ = jax.lax.scan(body, x._data, stacked + (layer_keys,))
        return NDArray(out, ctx=ctx)

    def hybrid_forward(self, F, x, mask=None):
        from ..block import _is_tracing
        if self._scan_layers and len(self.layers) > 1 and _is_tracing():
            return self._scan_forward(x, mask)
        if self._remat and _is_tracing():
            import jax
            from ...ndarray.ndarray import NDArray
            global _REMAT_APPLICATIONS
            _REMAT_APPLICATIONS += 1
            ctx = x.context
            for layer in self.layers:
                def body(xv, mv, _layer=layer):
                    m = NDArray(mv, ctx=ctx) if mv is not None else None
                    return _layer(NDArray(xv, ctx=ctx), m)._data

                if mask is None:
                    x = NDArray(jax.checkpoint(
                        lambda xv, _l=layer: body(xv, None, _l))(
                            x._data), ctx=ctx)
                else:
                    x = NDArray(jax.checkpoint(body)(
                        x._data, mask._data), ctx=ctx)
            return x
        for layer in self.layers:
            x = layer(x, mask)
        return x


class MoEFFN(HybridBlock):
    """Mixture-of-experts feed-forward layer (beyond-reference; see
    ops/moe.py).  Input (B, S, d) or (T, d); top-k routing with static
    capacity; expert weights live as (E, ...) tensors so an ``ep`` mesh
    axis can shard them (``parallel.moe_param_rule``).

    ``forward`` returns ``(out, aux_loss)``; add ``aux_weight *
    aux_loss`` to the training loss for load balancing.
    """

    def __init__(self, units, hidden_size, num_experts, k=1,
                 capacity_factor=1.25, activation="relu", prefix=None,
                 params=None):
        super().__init__(prefix=prefix, params=params)
        self._units = units
        self._e = num_experts
        self._kwargs = {"num_experts": num_experts, "k": k,
                        "capacity_factor": capacity_factor,
                        "activation": activation}
        with self.name_scope():
            self.gate_weight = self.params.get(
                "gate_weight", shape=(units, num_experts))
            self.expert_w1 = self.params.get(
                "expert_w1", shape=(num_experts, units, hidden_size))
            self.expert_b1 = self.params.get(
                "expert_b1", shape=(num_experts, hidden_size),
                init="zeros")
            self.expert_w2 = self.params.get(
                "expert_w2", shape=(num_experts, hidden_size, units))
            self.expert_b2 = self.params.get(
                "expert_b2", shape=(num_experts, units), init="zeros")

    def hybrid_forward(self, F, x, gate_weight, expert_w1, expert_b1,
                       expert_w2, expert_b2):
        shape = x.shape
        flat = x.reshape((-1, self._units)) if len(shape) > 2 else x
        out, aux = F._contrib_MoEFFN(flat, gate_weight, expert_w1,
                                     expert_b1, expert_w2, expert_b2,
                                     **self._kwargs)
        if len(shape) > 2:
            out = out.reshape(shape)
        return out, aux


class SyncBatchNorm(nn.BatchNorm):
    """Cross-device synchronized BatchNorm (parity: reference
    ``gluon.contrib.nn.SyncBatchNorm``).

    The reference implements this with a dedicated cross-GPU allreduce
    of batch statistics (``sync_batch_norm.cc``).  Under this
    framework's SPMD execution model it needs NO extra communication
    code: inside a mesh-jitted step (``DataParallelTrainer``) the batch
    dim is sharded but the BatchNorm reduction is over the GLOBAL batch
    — XLA inserts the cross-device reduction automatically, which IS
    sync-BN semantics (verified bit-exact in tests/test_parallel.py).
    The class exists so reference code importing SyncBatchNorm ports
    unchanged; ``num_devices``/``ndev`` is accepted and ignored.
    """

    def __init__(self, in_channels=0, num_devices=None, momentum=0.9,
                 epsilon=1e-5, **kwargs):
        # positional layout matches the reference exactly so ported
        # SyncBatchNorm(64, 4, 0.99) keeps its momentum
        kwargs.pop("ndev", None)
        kwargs.pop("key", None)
        super().__init__(in_channels=in_channels, momentum=momentum,
                         epsilon=epsilon, **kwargs)
