"""AFMoE decoder LMs (Arcee Trinity; ``model_type`` ``afmoe``): gated,
QK-normed grouped-query attention with RoPE on the sliding-window layers
and NO positional encoding on the full ones, sandwich norms, and a
sigmoid-routed expert layer that is TOLD which experts it holds.

Every layer is ``h + RMS_2(Attn(RMS_1(h)))`` then ``h + RMS_4(FFN(RMS_3(
h)))``.  ``Attn``: ``q``, ``k`` RMS-normed per head, rotated on a
``sliding_attention`` layer only, SDPA, the output times ``sigmoid(Wg a)``
before ``Wo``.  ``FFN``: SwiGLU on the first ``num_dense_layers`` layers,
then ``Shared(m) + sum_{e in top_k(s + b), held} g_e Expert_e(m)``
(``ops/moe.py`` ``routed_experts``).  The embedding is scaled by
``sqrt(units)``, the head is untied.  The residual stream, the norms and
the router's scores are float32; the matrix products run in the weights'
dtype.  The equations, and every departure from the published model, are
in ``afmoe_reference.py``.

**A share of an expert-parallel deployment**: ``experts_held = (first,
count)`` gives this model ``count`` of the ``num_experts`` routed experts
of every expert layer.  The router keeps its width, its top-k and its
normalisation over everything it picked; the sum runs over the held
experts; the shared expert is added on every chip.  What the absent
experts would have added is left out, and that partial result goes on to
the next layer.  There is no exchange and nothing stands in for one.
``vocab_size`` is what is held of the vocabulary: ids, logits and
sampling are over ``[0, vocab_size)``.

The serving contract (docs/serving.md, "State kinds") is ``sambay.py``'s:
``state_spec`` / ``prefill`` / ``decode_step`` over a flat state list,
``kv_window`` rows (rolling, ROTATED keys, ``min(window, cache_len)``
positions) and ``kv_full`` rows side by side.  Beside it the model hands
the server ``statistics`` (docs/serving.md, "Model statistics"): after
each ``prefill`` / ``decode_step``, ``last_statistics`` holds what the
expert layers counted in that call and, behind the counts, the experts
each row of the call picked, layer by layer (a pick of 4 in 256 is a
discrete choice that two bfloat16 computations make differently at a few
rows in a hundred; whoever holds a served request to another computation
needs the picks that WERE made).
"""
from __future__ import annotations

import math
from functools import partial

import numpy as np

from .. import initializer as init
from ..base import MXNetError
from ..gluon import nn
from ..gluon.block import HybridBlock
from ..profiler import device_scope
from .sambay import _MLP, _dense, _rows, _split

__all__ = ["AfmoeModel", "AfmoeForCausalLM", "get_afmoe", "afmoe_tiny",
           "trinity_large_ep8"]

SLIDING, FULL = "sliding_attention", "full_attention"


class _RMSNorm(HybridBlock):
    """``x / sqrt(mean(x^2) + eps) * g`` over the last axis, in float32
    whatever the gain's dtype."""

    def __init__(self, units, eps, **kwargs):
        super().__init__(**kwargs)
        self._eps = eps
        with self.name_scope():
            self.gamma = self.params.get("gamma", shape=(units,),
                                         init="ones")

    def hybrid_forward(self, F, x, gamma=None):
        return F.RMSNorm(x.astype("float32"), gamma.astype("float32"),
                         eps=self._eps)


class _GatedAttention(HybridBlock):
    """Grouped-query attention with per-head RMS norms on q and k, RoPE
    only where ``rotary``, and a sigmoid gate on the heads' output."""

    def __init__(self, units, num_heads, num_kv_heads, head_dim, eps,
                 rope_base, window=None, **kwargs):
        super().__init__(**kwargs)
        if num_heads % num_kv_heads:
            raise MXNetError(
                f"grouped-query attention: {num_heads} query heads over "
                f"{num_kv_heads} K/V heads")
        self._h, self._kv, self._d = num_heads, num_kv_heads, head_dim
        self._base = float(rope_base)
        self.window = window                 # None: a full layer, no RoPE
        #: the device scope; the layer's norms around it carry it too
        self.scope = "mxtpu.mixer.swa" if window else "mxtpu.mixer.full"
        h, kv, d = num_heads, num_kv_heads, head_dim
        with self.name_scope():
            # [q, k, v, gate] rows of ONE projection
            self.qkvg_proj = _dense((2 * h + 2 * kv) * d, units, False,
                                    "qkvg_")
            self.q_norm = _RMSNorm(d, eps, prefix="qnorm_")
            self.k_norm = _RMSNorm(d, eps, prefix="knorm_")
            self.o_proj = _dense(units, h * d, False, "o_")

    def _qkvg(self, a, offset):
        """a (B, S, units) -> q (B, S, H, d), k, v (B, S, KV, d) and the
        gate (B, S, H d); q and k normed, and rotated at ``offset`` (a
        number, or (B,) positions) on a window layer.  Norm and rotation
        run in float32 and round once."""
        from .. import ndarray as nd
        b, s = a.shape[0], a.shape[1]
        h, kv, d = self._h, self._kv, self._d
        q, k, v, g = _split(self.qkvg_proj(a),
                            (h * d, kv * d, kv * d, h * d))
        q = self.q_norm(q.reshape((b, s, h, d)))
        k = self.k_norm(k.reshape((b, s, kv, d)))
        if self.window:
            q = nd.rope(q, offset=offset, base=self._base)
            k = nd.rope(k, offset=offset, base=self._base)
        return (q.astype(a.dtype), k.astype(a.dtype),
                v.reshape((b, s, kv, d)), g)

    def _out(self, o, g):
        from .. import ndarray as nd
        b, s = o.shape[0], o.shape[1]
        return self.o_proj(o.reshape((b, s, -1)) * nd.sigmoid(g))

    def seq(self, a):
        """Self-attention over a whole (right-padded) sequence -> (out,
        k, v) with k as stored (rotated on a window layer); causal,
        banded by the layer's window."""
        from .. import ndarray as nd
        with device_scope(self.scope):
            q, k, v, g = self._qkvg(a, 0)
            o = nd.dot_product_attention(q, k, v, causal=True,
                                         window=self.window)
            return self._out(o, g), k, v

    def step(self, a, cache_k, cache_v, offset, slot, mask):
        """One token a row at its own ``offset`` (B,): write K,V at
        ``slot`` (B,), attend the buffer under the key mask."""
        from .. import ndarray as nd
        with device_scope(self.scope):
            q, k, v, g = self._qkvg(a, offset)
            nd._cache_update(cache_k, k, offset=slot, out=cache_k)
            nd._cache_update(cache_v, v, offset=slot, out=cache_v)
            o = nd.dot_product_attention(q, cache_k, cache_v, mask,
                                         use_mask=True)
            return self._out(o, g)


class _RoutedFFN(HybridBlock):
    """``Shared(m) + sum_{e in S, held} g_e Expert_e(m)``: the router over
    all ``num_experts``, the ``count`` experts held here, the shared
    expert whole."""

    def __init__(self, units, hidden, num_experts, experts_held, top_k,
                 route_scale, selection_bias=True, **kwargs):
        super().__init__(**kwargs)
        first, count = experts_held
        self._attrs = dict(k=int(top_k), route_scale=float(route_scale),
                           first_held=int(first))
        sigma = math.sqrt(2.0 / (units + hidden))
        with self.name_scope():
            self.router = self.params.get(
                "router_weight", shape=(num_experts, units),
                init=init.Normal(math.sqrt(2.0 / (units + num_experts))))
            # picks, never weighs: a buffer the training loop's balancer
            # moves, no gradient; a family without one selects on the
            # scores alone
            self.bias = self.params.get(
                "router_bias", shape=(num_experts,), init="zeros",
                grad_req="null") if selection_bias else None
            self.gate = self.params.get(
                "experts_gate_weight", shape=(count, units, hidden),
                init=init.Normal(sigma))
            self.up = self.params.get(
                "experts_up_weight", shape=(count, units, hidden),
                init=init.Normal(sigma))
            self.down = self.params.get(
                "experts_down_weight", shape=(count, hidden, units),
                init=init.Normal(sigma))
            self.shared = _MLP(units, hidden, scope="mxtpu.moe.shared",
                               prefix="shared_")

    def route(self, m, valid=None):
        """m (B, S, units), ``valid`` (B, S) or None (every row routed)
        -> (out (B, S, units) float32, [held assignments, held experts
        touched] int32 scalars, selected (B, S, k) int32)."""
        from .. import ndarray as nd
        ctx = m.context
        b, s, u = m.shape
        extra = [] if valid is None else [valid.reshape((b * s,))]
        bias = nd.zeros(self.router.shape[:1], ctx=ctx) \
            if self.bias is None else self.bias.data(ctx)
        # the op names its own halves mxtpu.moe.router / mxtpu.moe.experts.
        # The TPU compiler gives a grouped product an ``op_name`` of its
        # own (``ragged-dot-none``) and keeps only the CALL SITE's
        # prefix: the call says ``.experts`` so that they do
        with device_scope("mxtpu.moe.experts"):
            out, held, touched, selected = nd._contrib_RoutedExperts(
                m.reshape((b * s, u)), self.router.data(ctx),
                bias, self.gate.data(ctx), self.up.data(ctx),
                self.down.data(ctx), *extra, use_valid=valid is not None,
                **self._attrs)
        out = out.reshape((b, s, u)) + self.shared(m).astype("float32")
        return out, [held, touched], selected.reshape((b, s, -1))


class _Layer(HybridBlock):
    """``h + RMS_2(Mixer(RMS_1(h)))`` then ``h + RMS_4(FFN(RMS_3(h)))``;
    ``mixer(prefix=...)`` builds the attention block (``pangu_moe.py``
    gives another), ``moe`` the arguments of an expert layer's
    ``_RoutedFFN``."""

    def __init__(self, mixer, dense, units, hidden, moe_hidden, eps, moe,
                 **kwargs):
        super().__init__(**kwargs)
        self.dense = dense
        with self.name_scope():
            self.ln1 = _RMSNorm(units, eps, prefix="ln1_")
            self.attn = mixer(prefix="attn_")
            self.ln2 = _RMSNorm(units, eps, prefix="ln2_")
            self.ln3 = _RMSNorm(units, eps, prefix="ln3_")
            if dense:
                self.ffn = _MLP(units, hidden, prefix="mlp_")
            else:
                self.ffn = _RoutedFFN(units, moe_hidden, prefix="moe_",
                                      **moe)
            self.ln4 = _RMSNorm(units, eps, prefix="ln4_")

    def pre(self, h, wdt):
        """The mixer's input: the first norm, in the weights' dtype."""
        with device_scope(self.attn.scope):
            return self.ln1(h).astype(wdt)

    def finish(self, h, mix, wdt, valid, stats, picked=None):
        """The attention branch's residual add, then the FFN sublayer;
        an expert layer adds what it counted to ``stats`` (the order of
        ``AfmoeForCausalLM.statistics``) and the experts its rows picked
        to ``picked``.  The norms and adds around a sub-layer carry its
        device scope (``mxtpu.moe`` around an expert layer, whose router,
        experts and shared expert name themselves inside it)."""
        with device_scope(self.attn.scope):
            h = h + self.ln2(mix)
        with device_scope("mxtpu.mlp" if self.dense else "mxtpu.moe"):
            m = self.ln3(h).astype(wdt)
            if self.dense:
                return h + self.ln4(self.ffn(m))
            out, counted, selected = self.ffn.route(m, valid)
            rows = m.shape[0] * m.shape[1] if valid is None \
                else valid.sum().astype("int32")
            for i, c in enumerate(counted + [rows, 1]):
                stats[i] = stats[i] + c
            if picked is not None:
                picked.append(selected)
            return h + self.ln4(out)


class AfmoeModel(HybridBlock):
    def __init__(self, vocab_size, units, hidden, moe_hidden, layer_types,
                 num_dense_layers, num_heads, num_kv_heads, head_dim,
                 num_experts, top_k, route_scale, sliding_window,
                 experts_held=None, rms_norm_eps=1e-5, rope_base=10000.0,
                 **kwargs):
        super().__init__(**kwargs)
        bad = [t for t in layer_types if t not in (SLIDING, FULL)]
        if bad:
            raise MXNetError(f"layer_types holds {bad[0]!r}; a layer is "
                             f"{SLIDING!r} or {FULL!r}")
        held = (0, num_experts) if experts_held is None \
            else tuple(int(x) for x in experts_held)
        if held[0] < 0 or held[1] < 1 or sum(held) > num_experts:
            raise MXNetError(
                f"experts_held {held} = (first, count) must lie within "
                f"the router's {num_experts} experts")
        self._units = units
        self.vocab_size = vocab_size
        self.sliding_window = int(sliding_window)
        self.num_kv_heads, self.head_dim = num_kv_heads, head_dim
        self.num_experts, self.experts_held = num_experts, held
        moe = dict(num_experts=num_experts, experts_held=held, top_k=top_k,
                   route_scale=route_scale)
        with self.name_scope():
            self.embed = nn.Embedding(vocab_size, units, prefix="embed_")
            self.layers = []
            for i, kind in enumerate(layer_types):
                mixer = partial(
                    _GatedAttention, units, num_heads, num_kv_heads,
                    head_dim, rms_norm_eps, rope_base,
                    window=sliding_window if kind == SLIDING else None)
                layer = _Layer(mixer, i < num_dense_layers, units, hidden,
                               moe_hidden, rms_norm_eps, moe,
                               prefix=f"layer{i}_")
                layer.kind = kind
                self.register_child(layer, f"layer{i}")
                self.layers.append(layer)
            self.final_norm = _RMSNorm(units, rms_norm_eps,
                                       prefix="finalnorm_")

    def compute_dtype(self):
        """What enters the matrix products: the weights' dtype."""
        return self.embed.weight.dtype

    def embed_scaled(self, tokens):
        """``E[token] * sqrt(units)`` (``mup_enabled``), float32."""
        with device_scope("mxtpu.embed"):
            return self.embed(tokens).astype("float32") \
                * math.sqrt(self._units)

    def new_statistics(self, ctx):
        from .. import ndarray as nd
        return [nd.zeros((), ctx=ctx, dtype="int32")
                for _ in AfmoeForCausalLM.statistics]

    def run(self, tokens, stats):
        """Every layer over the whole sequence: (B, S) -> (B, S, units)
        before the final norm."""
        h = self.embed_scaled(tokens)
        wdt = self.compute_dtype()
        for layer in self.layers:
            mix, _k, _v = layer.attn.seq(layer.pre(h, wdt))
            h = layer.finish(h, mix, wdt, None, stats)
        return h

    def hybrid_forward(self, F, tokens):
        h = self.run(tokens, self.new_statistics(tokens.context))
        with device_scope("mxtpu.head"):
            return self.final_norm(h)


class AfmoeForCausalLM(HybridBlock):
    """Untied LM head over :class:`AfmoeModel`, with the model-zoo
    decoder contract the serving plane drives."""

    # (counter, help) rows: what ``last_statistics`` holds first after
    # each ``prefill`` / ``decode_step``, which ``serving.Server`` adds to
    # the counters of these names as it reads the call's tokens; behind
    # them the rows' picks (``_picks``), which go to the server's
    # ``statistics_listener`` and to no counter
    statistics = (
        ("mxtpu_moe_assignments_held_total",
         "token-to-expert assignments that landed on experts held here"),
        ("mxtpu_moe_experts_touched_total",
         "held experts that received at least one token, summed over "
         "expert-layer calls"),
        ("mxtpu_moe_routed_rows_total",
         "rows (tokens) routed, summed over expert-layer calls"),
        ("mxtpu_moe_layer_calls_total", "expert-layer calls"),
    )

    def __init__(self, model: AfmoeModel, **kwargs):
        super().__init__(**kwargs)
        from .. import telemetry
        with self.name_scope():
            self.model = model
            self.lm_head = _dense(model.vocab_size, model._units, False,
                                  "head_")
        self.last_statistics = None
        telemetry.gauge(
            "mxtpu_moe_experts_held",
            "routed experts of each expert layer held by this process"
            ).set(model.experts_held[1])

    # -- head ------------------------------------------------------------
    def _head(self, h):
        from .. import ndarray as nd
        m = self.model
        with device_scope("mxtpu.head"):
            return nd._head_logits(
                m.final_norm(h).reshape((-1, m._units))
                .astype(m.compute_dtype()),
                self.lm_head.weight.data(h.context))

    def hybrid_forward(self, F, tokens):
        b, s = tokens.shape
        stats = self.model.new_statistics(tokens.context)
        h = self.model.run(tokens, stats)
        self.last_statistics = stats
        return self._head(h).reshape((b, s, self.model.vocab_size))

    # -- state -----------------------------------------------------------
    def state_spec(self, slots, cache_len, dtype="float32"):
        """One K and one V buffer a layer, in the order ``prefill`` /
        ``decode_step`` take them: ``kv_window`` rows of ``min(window,
        cache_len)`` positions (rolling; the keys are stored ROTATED) on
        a sliding layer, ``kv_full`` rows of ``cache_len`` on a full
        one."""
        import jax.numpy as jnp
        if not jnp.issubdtype(jnp.dtype(dtype), jnp.floating):
            raise MXNetError(
                f"KV cache dtype must be floating, got {dtype!r} "
                "(an int cache truncates every K/V write)")
        m = self.model
        rows = []
        for i, layer in enumerate(m.layers):
            if layer.kind == SLIDING:
                kind, n = "kv_window", min(m.sliding_window, cache_len)
            else:
                kind, n = "kv_full", cache_len
            shape = (slots, n, m.num_kv_heads, m.head_dim)
            rows += [(f"layer{i}_k", kind, shape, str(dtype)),
                     (f"layer{i}_v", kind, shape, str(dtype))]
        return rows

    def init_cache(self, batch_size, max_len, ctx=None, dtype="float32"):
        """Zeroed state buffers, flat, in ``state_spec`` order."""
        from .. import ndarray as nd
        return [nd.zeros(shape, ctx=ctx, dtype=dt) for _n, _k, shape, dt
                in self.state_spec(batch_size, max_len, dtype)]

    # -- prefill ----------------------------------------------------------
    def prefill(self, tokens, state, last_pos=None):
        """Right-padded prompts (B, S) -> each row's logits at its own
        ``last_pos`` (B, vocab); ``state`` (flat, ``state_spec`` order) is
        filled in place.  Rows past ``last_pos`` are routed to no expert
        and counted nowhere."""
        from .. import ndarray as nd
        m = self.model
        b, s = tokens.shape
        ctx = tokens.context
        if last_pos is None:
            last_pos = _rows(b, s - 1, ctx)
        wdt = m.compute_dtype()
        with device_scope("mxtpu.moe"):
            pos = nd.arange(s, ctx=ctx).reshape((1, s))
            valid = pos <= last_pos.reshape((-1, 1))
            stats = m.new_statistics(ctx)
        h = m.embed_scaled(tokens)
        bufs = iter(state)
        picked = []
        for layer in m.layers:
            mix, k, v = layer.attn.seq(layer.pre(h, wdt))
            with device_scope(layer.attn.scope):
                for buf, new in ((next(bufs), k), (next(bufs), v)):
                    if buf.shape[1] < s:    # a window shorter than the prompt
                        new = nd._rolling_window_fill(
                            new, last_pos, length=buf.shape[1])
                    nd._cache_update(buf, new, offset=0, out=buf)
            h = layer.finish(h, mix, wdt, valid, stats, picked)
        self.last_statistics = stats + self._picks(picked, valid)
        with device_scope("mxtpu.head"):
            return self._head(nd._take_positions(h, last_pos))

    @staticmethod
    def _picks(picked, valid=None):
        """What rides out behind the counts: the experts every row of the
        call picked, (B, S, k) a layer -> one (B, S, expert layers x k)
        int32, layer by layer; a padded row (``valid`` 0) picked nothing
        and reads -1."""
        from .. import ndarray as nd
        if not picked:
            return []
        with device_scope("mxtpu.moe"):
            rows = nd.concat(*picked, dim=2)
            if valid is not None:
                keep = valid.reshape(valid.shape + (1,)).astype("int32")
                rows = rows * keep + (keep - 1)
        return [rows]

    # -- decode -----------------------------------------------------------
    def decode_step(self, token, state, offset):
        """One token a row: token (B, 1), ``offset`` a number or a (B,)
        NDArray of absolute positions -> logits (B, vocab); ``state`` is
        advanced in place.  Every row is routed (the contract names no
        idle rows)."""
        from .. import ndarray as nd
        m = self.model
        b = token.shape[0]
        ctx = token.context
        if not isinstance(offset, nd.NDArray):
            offset = _rows(b, offset, ctx)
        elif offset.ndim == 0:
            offset = offset.reshape((1,)) + nd.zeros((b,), ctx=ctx)
        offv = offset.reshape((-1, 1))
        wdt = m.compute_dtype()
        with device_scope("mxtpu.moe"):
            stats = m.new_statistics(ctx)
        h = m.embed_scaled(token)
        masks = {}

        def key_mask(n):
            # slot j of an n-slot buffer is live once written: j <= offset
            # (a rolling buffer holds only positions inside the window)
            if n not in masks:
                pos = nd.arange(n, ctx=ctx).reshape((1, n))
                masks[n] = (pos <= offv).reshape((b, 1, 1, n))
            return masks[n]

        bufs = iter(state)
        picked = []
        for layer in m.layers:
            ck, cv = next(bufs), next(bufs)
            n = ck.shape[1]
            with device_scope(layer.attn.scope):
                slot = offset % float(n) if layer.kind == SLIDING \
                    else offset
                mask = key_mask(n)
            mix = layer.attn.step(layer.pre(h, wdt), ck, cv, offset, slot,
                                  mask)
            h = layer.finish(h, mix, wdt, None, stats, picked)
        self.last_statistics = stats + self._picks(picked)
        return self._head(h)

    def generate(self, tokens, max_new_tokens, cache_dtype="float32"):
        """Greedy generation through the cache: (B, S) -> (B, S + new)."""
        from .. import ndarray as nd
        b, s = tokens.shape
        state = self.init_cache(b, s + max_new_tokens, ctx=tokens.context,
                                dtype=cache_dtype)
        out = [tokens.asnumpy()]
        logits = self.prefill(tokens, state)
        for i in range(max_new_tokens):
            nxt = logits.asnumpy().argmax(-1).astype("float32") \
                .reshape(b, 1)
            out.append(nxt)
            if i < max_new_tokens - 1:
                logits = self.decode_step(
                    nd.array(nxt, ctx=tokens.context), state, s + i)
        return nd.array(np.concatenate(out, axis=1), ctx=tokens.context)


_PERIOD = (SLIDING, SLIDING, SLIDING, FULL)
_AFMOE_SPECS = {
    # test size: one dense layer and one whole period of expert layers
    # (window, window, full, window after it), a window the tests cross,
    # heads whose width is not units / heads
    "afmoe_tiny": dict(units=64, hidden=128, moe_hidden=32,
                       layer_types=(_PERIOD + _PERIOD)[:5],
                       num_dense_layers=1, num_heads=4, num_kv_heads=2,
                       head_dim=32, num_experts=16, top_k=4,
                       route_scale=2.448, sliding_window=8),
    # arcee-ai/Trinity-Large-Preview config.json (model_type afmoe) as ONE
    # CHIP OF AN 8-WAY EXPERT-PARALLEL DEPLOYMENT holds it: every width
    # published; 5 of 60 layers (1 of the 6 dense ones + one period), 32 of
    # each layer's 256 experts (the router stays 256 wide); vocabulary
    # 25,024 of 200,192 (chipbench/configs/trinity_large_ep8.json)
    "trinity_large_ep8": dict(units=3072, hidden=12288, moe_hidden=3072,
                              layer_types=(_PERIOD + _PERIOD)[:5],
                              num_dense_layers=1, num_heads=48,
                              num_kv_heads=8, head_dim=128,
                              num_experts=256, experts_held=(0, 32),
                              top_k=4, route_scale=2.448,
                              sliding_window=4096),
}


def get_afmoe(name, vocab_size=25024, **kwargs):
    if name not in _AFMOE_SPECS:
        raise MXNetError(f"unknown afmoe config {name!r}; options "
                         f"{sorted(_AFMOE_SPECS)}")
    spec = dict(_AFMOE_SPECS[name])
    spec.update(kwargs)
    return AfmoeModel(vocab_size=vocab_size, **spec)


def afmoe_tiny(**kwargs):
    return get_afmoe("afmoe_tiny", **kwargs)


def trinity_large_ep8(**kwargs):
    return get_afmoe("trinity_large_ep8", **kwargs)
