"""Solar Open 2 decoder LMs (Solar-Open2-250B; ``model_type``
``solar_open2``): gated delta-rule LINEAR attention with a matrix state a
head (Kimi Delta Attention, arXiv:2510.26692) on three layers of four,
gated grouped-query attention with no positional encoding on the fourth,
and a sigmoid-routed expert layer after every mixer.

Every layer is the pre-norm ``h + Mixer(RMS_1(h))`` then ``h +
FFN(RMS_3(h))`` (``afmoe.py``'s ``_Layer`` without its post-norms).
``FFN``: ``Shared(m) + sum_{e in top_k(s), held} g_e Expert_e(m)``
(``afmoe.py``'s ``_RoutedFFN``, no selection bias).  The GQA layer is
``afmoe.py``'s gated attention with no q/k norm and no rotation.  The
linear-attention mixer, per head (``ops/delta_rule.py``)::

    q, k, v = SiLU(conv4(x W_{q,k,v}))      q, k L2-normed per head
    log a_t = -exp(A_log_h) softplus(x W_fa W_fb + dt_bias)   (d_k,)
    b_t = 2 sigmoid(x W_b)                   (the 2: negative eigenvalues
                                              of I - b k k^T allowed)
    S_t = Diag(a_t) S_{t-1} + b_t k_t (v_t - (Diag(a_t) S_{t-1})^T k_t)^T
    o_t = d_k^-0.5 S_t^T q_t
    out = W_o (RMS_head(o) * sigmoid(x W_ga W_gb))

The residual stream, the norms, the state and the router's scores are
float32; the matrix products run in the weights' dtype.  The equations,
and every departure from the published model, are in
``solar_open2_reference.py``.

``experts_held`` and ``vocab_size`` are ONE CHIP'S SHARE of an
expert-parallel deployment, as in ``afmoe.py``.  The serving contract
(docs/serving.md, "State kinds", "Model statistics") is that model's: a
GQA layer holds ``kv_full`` K and V, a linear-attention layer a ``conv``
tail and its matrix state as an ``ssm`` row ``(slots, heads, d_k,
d_v)`` float32, which prefill freezes at each row's ``last_pos``.  Beside
the expert layers' counts the linear-attention layers count their calls
and the state rows they wrote.
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
from .afmoe import AfmoeForCausalLM, _GatedAttention, _Layer, _RMSNorm
from .sambay import _dense, _rows, _split

__all__ = ["SolarOpen2Model", "SolarOpen2ForCausalLM", "get_solar_open2",
           "solar_open2_tiny", "solar_open2_ep8"]

FULL, DELTA = "full", "delta"
L2_EPS = 1e-6


class _DeltaAttention(HybridBlock):
    """The gated delta-rule mixer.  ``seq`` scans a right-padded batch
    from a given state and returns the state at each row's ``last_pos``;
    ``step`` advances one token."""

    def __init__(self, units, num_heads, head_dim, conv_width, gate_rank,
                 eps, **kwargs):
        super().__init__(**kwargs)
        self._h, self._d = num_heads, head_dim
        #: the device scope; the layer's norm and residual add carry it
        self.scope = "mxtpu.mixer.delta"
        width = num_heads * head_dim
        self._w = width
        # KDA's own initial values: A spread over [1, 16], dt's bias the
        # inverse softplus of steps spread over [1e-3, 1e-1]
        dt = np.exp(np.linspace(math.log(1e-3), math.log(1e-1), width,
                                dtype="f4"))
        with self.name_scope():
            # [q, k, v] rows of ONE projection, and ONE depthwise conv
            self.qkv_proj = _dense(3 * width, units, False, "qkv_")
            self.conv_weight = self.params.get(
                "conv_weight", shape=(conv_width, 3 * width),
                init=init.Uniform(1.0 / math.sqrt(conv_width)))
            self.fa_proj = _dense(gate_rank, units, False, "fa_")
            self.fb_proj = _dense(width, gate_rank, False, "fb_")
            self.a_log = self.params.get(
                "a_log", shape=(num_heads,), init=init.Constant(
                    np.log(np.linspace(1.0, 16.0, num_heads, dtype="f4"))))
            self.dt_bias = self.params.get(
                "dt_bias", shape=(width,),
                init=init.Constant(dt + np.log(-np.expm1(-dt))))
            self.b_proj = _dense(num_heads, units, False, "b_")
            self.ga_proj = _dense(gate_rank, units, False, "ga_")
            self.gb_proj = _dense(width, gate_rank, False, "gb_")
            self.o_norm = _RMSNorm(head_dim, eps, prefix="onorm_")
            self.o_proj = _dense(units, width, False, "o_")

    def _gates(self, a):
        """a (B, S, units) -> the conv's input (B, S, 3 W), log_a (B, S,
        H, d_k), b (B, S, H), the output gate's logits (B, S, W)."""
        from .. import ndarray as nd
        ctx = a.context
        log_a, b = nd._delta_gates(
            self.fb_proj(self.fa_proj(a)), self.a_log.data(ctx),
            self.dt_bias.data(ctx), self.b_proj(a))
        return self.qkv_proj(a), log_a, b, self.gb_proj(self.ga_proj(a))

    def _heads(self, x, lead):
        """The conv's output (..., 3 W) -> q, k (lead + (H, d)) float32
        L2-normed per head, and v (lead + (H, d))."""
        from .. import ndarray as nd
        h, d = self._h, self._d
        q, k, v = _split(x, (self._w, self._w, self._w))

        def unit(t):
            return nd.L2Normalization(t.astype("float32").reshape((-1, d)),
                                      eps=L2_EPS).reshape(lead + (h, d))
        return unit(q), unit(k), v.reshape(lead + (h, d))

    def _zero_bias(self, ctx):
        from .. import ndarray as nd
        # ASSUMED: the short convolution has no bias
        return nd.zeros((3 * self._w,), ctx=ctx)

    def _out(self, o, g, wdt):
        """``W_o (RMS_head(o) * sigmoid(g))``: o (B, S, H, d) float32."""
        from .. import ndarray as nd
        b, s = o.shape[0], o.shape[1]
        with device_scope(self.scope + ".out"):
            o = self.o_norm(o) * nd.sigmoid(
                g.astype("float32")).reshape((b, s, self._h, self._d))
            return self.o_proj(o.reshape((b, s, self._w)).astype(wdt))

    def seq(self, a, last_pos, state):
        """a (B, S, units), ``state`` (B, H, d, d) the state before the
        prompt -> (out (B, S, units), conv tail (B, K-1, 3 W), the state
        at each row's ``last_pos`` (B, H, d, d) float32)."""
        from .. import ndarray as nd
        ctx = a.context
        b, s = a.shape[0], a.shape[1]
        with device_scope(self.scope + ".project"):
            x, log_a, beta, g = self._gates(a)
            x, tail = nd._causal_conv1d(x, self.conv_weight.data(ctx),
                                        self._zero_bias(ctx), last_pos)
            q, k, v = self._heads(x, (b, s))
        with device_scope(self.scope + ".scan"):
            o, state = nd._delta_scan(q, k, v, log_a, beta, state, last_pos)
        return self._out(o, g, a.dtype), tail, state

    def step(self, a, tail, state):
        """a (B, 1, units); ``tail`` and ``state`` are advanced in
        place."""
        from .. import ndarray as nd
        ctx = a.context
        b = a.shape[0]
        h, d = self._h, self._d
        with device_scope(self.scope + ".project"):
            x, log_a, beta, g = self._gates(a)
            x, new_tail = nd._causal_conv1d_step(
                x.reshape((b, 3 * self._w)), self.conv_weight.data(ctx),
                self._zero_bias(ctx), tail)
            tail._set_data(new_tail._data)
            q, k, v = self._heads(x, (b,))
        with device_scope(self.scope + ".update"):
            o, new_state = nd._delta_step(q, k, v, log_a.reshape((b, h, d)),
                                          beta.reshape((b, h)), state)
            state._set_data(new_state._data)
        return self._out(o.reshape((b, 1, h, d)), g, a.dtype)


class SolarOpen2Model(HybridBlock):
    def __init__(self, vocab_size, units, moe_hidden, num_layers, gqa_layers,
                 num_heads, num_kv_heads, head_dim, delta_heads,
                 delta_head_dim, conv_width, gate_rank, num_experts, top_k,
                 route_scale=1.0, experts_held=None, rms_norm_eps=1e-5,
                 **kwargs):
        super().__init__(**kwargs)
        held = (0, num_experts) if experts_held is None \
            else tuple(int(x) for x in experts_held)
        if held[0] < 0 or held[1] < 1 or sum(held) > num_experts:
            raise MXNetError(
                f"experts_held {held} = (first, count) must lie within "
                f"the router's {num_experts} experts")
        bad = [i for i in gqa_layers if not 0 <= int(i) < num_layers]
        if bad:
            raise MXNetError(f"gqa_layers {bad} lie outside the "
                             f"{num_layers} layers")
        self._units = units
        self.vocab_size = vocab_size
        self.num_kv_heads, self.head_dim = num_kv_heads, head_dim
        self.delta_shape = (delta_heads, delta_head_dim, delta_head_dim)
        self.conv_width = conv_width
        self.num_experts, self.experts_held = num_experts, held
        moe = dict(num_experts=num_experts, experts_held=held, top_k=top_k,
                   route_scale=route_scale, selection_bias=False)
        gqa = set(int(i) for i in gqa_layers)
        with self.name_scope():
            self.embed = nn.Embedding(vocab_size, units, prefix="embed_")
            self.layers = []
            for i in range(num_layers):
                kind = FULL if i in gqa else DELTA
                # ASSUMED: no q/k norm, no rotation (use_rope false)
                mixer = partial(
                    _GatedAttention, units, num_heads, num_kv_heads,
                    head_dim, rms_norm_eps, 0.0, qk_norm=False) \
                    if kind == FULL else partial(
                    _DeltaAttention, units, delta_heads, delta_head_dim,
                    conv_width, gate_rank, rms_norm_eps)
                layer = _Layer(mixer, False, units, moe_hidden, moe_hidden,
                               rms_norm_eps, moe, sandwich=False,
                               prefix=f"layer{i}_")
                layer.kind = kind
                self.register_child(layer, f"layer{i}")
                self.layers.append(layer)
            self.final_norm = _RMSNorm(units, rms_norm_eps,
                                       prefix="finalnorm_")

    def compute_dtype(self):
        """What enters the matrix products: the weights' dtype."""
        return self.embed.weight.dtype

    def embedded(self, tokens):
        """``E[token]``, float32 (the config has no key for a scale)."""
        with device_scope("mxtpu.embed"):
            return self.embed(tokens).astype("float32")

    def new_statistics(self, ctx):
        from .. import ndarray as nd
        return [nd.zeros((), ctx=ctx, dtype="int32")
                for _ in SolarOpen2ForCausalLM.statistics]

    @staticmethod
    def count_delta(stats, rows):
        """One linear-attention call that wrote ``rows`` states (the last
        two rows of ``SolarOpen2ForCausalLM.statistics``)."""
        with device_scope("mxtpu.mixer.delta"):
            for i, c in zip((-2, -1), (rows, 1)):
                stats[i] = stats[i] + c

    def run(self, tokens, stats):
        """Every layer over the whole sequence from an empty state: (B, S)
        -> (B, S, units) before the final norm."""
        from .. import ndarray as nd
        b, s = tokens.shape
        ctx = tokens.context
        last = _rows(b, s - 1, ctx)
        h = self.embedded(tokens)
        wdt = self.compute_dtype()
        for layer in self.layers:
            u = layer.pre(h, wdt)
            if layer.kind == FULL:
                mix, _k, _v = layer.attn.seq(u)
            else:
                mix, _tail, _state = layer.attn.seq(
                    u, last, nd.zeros((b,) + self.delta_shape, ctx=ctx))
                self.count_delta(stats, b)
            h = layer.finish(h, mix, wdt, None, stats)
        return h

    def hybrid_forward(self, F, tokens):
        h = self.run(tokens, self.new_statistics(tokens.context))
        with device_scope("mxtpu.head"):
            return self.final_norm(h)


class SolarOpen2ForCausalLM(AfmoeForCausalLM):
    """Untied LM head over :class:`SolarOpen2Model` with the model-zoo
    decoder contract: ``AfmoeForCausalLM``'s head, counts, picks and
    ``generate``, over K,V pages, conv tails and matrix states."""

    statistics = AfmoeForCausalLM.statistics + (
        ("mxtpu_delta_state_rows_total",
         "matrix states a linear-attention layer wrote (one a row of the "
         "call: in decode every slot of the bucket), summed over "
         "linear-attention layer calls"),
        ("mxtpu_delta_layer_calls_total", "linear-attention layer calls"),
    )

    # -- state -----------------------------------------------------------
    def state_spec(self, slots, cache_len, dtype="float32"):
        """In layer order: a GQA layer's ``kv_full`` K and V of
        ``cache_len`` positions; a linear-attention layer's ``conv`` tail
        ``(slots, K-1, 3 W)`` and its matrix state, an ``ssm`` row
        ``(slots, heads, d_k, d_v)`` float32 whatever ``dtype`` says."""
        import jax.numpy as jnp
        if not jnp.issubdtype(jnp.dtype(dtype), jnp.floating):
            raise MXNetError(
                f"KV cache dtype must be floating, got {dtype!r} "
                "(an int cache truncates every K/V write)")
        m = self.model
        rows = []
        for i, layer in enumerate(m.layers):
            if layer.kind == FULL:
                shape = (slots, cache_len, m.num_kv_heads, m.head_dim)
                rows += [(f"layer{i}_k", "kv_full", shape, str(dtype)),
                         (f"layer{i}_v", "kv_full", shape, str(dtype))]
            else:
                heads, dk, _dv = m.delta_shape
                rows += [(f"layer{i}_conv", "conv",
                          (slots, m.conv_width - 1, 3 * heads * dk),
                          str(dtype)),
                         (f"layer{i}_state", "ssm",
                          (slots,) + m.delta_shape, "float32")]
        return rows

    # -- prefill ----------------------------------------------------------
    def prefill(self, tokens, state, last_pos=None):
        """Right-padded prompts (B, S) -> each row's logits at its own
        ``last_pos`` (B, vocab); ``state`` (flat, ``state_spec`` order) is
        filled in place from the prompts alone: a linear-attention
        layer's scan starts from a zero state, as its convolution from
        zero inputs, whatever the buffers held.  Rows past ``last_pos``
        write K,V that decode masks, leave the recurrent state and conv
        tail where the row's last token put them, are routed to no expert
        and are counted nowhere."""
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
        h = m.embedded(tokens)
        bufs = iter(state)
        picked = []
        for layer in m.layers:
            u = layer.pre(h, wdt)
            first, second = next(bufs), next(bufs)
            if layer.kind == FULL:
                mix, k, v = layer.attn.seq(u)
                with device_scope(layer.attn.scope):
                    for buf, new in ((first, k), (second, v)):
                        nd._cache_update(buf, new, offset=0, out=buf)
            else:
                mix, tail, st = layer.attn.seq(
                    u, last_pos, nd.zeros(second.shape, ctx=ctx))
                with device_scope(layer.attn.scope):
                    first._set_data(tail._data.astype(first.dtype))
                    second._set_data(st._data)
                m.count_delta(stats, b)
            h = layer.finish(h, mix, wdt, valid, stats, picked)
        self.last_statistics = stats + self._picks(picked, valid)
        with device_scope("mxtpu.head"):
            return self._head(nd._take_positions(h, last_pos))

    # -- decode -----------------------------------------------------------
    def decode_step(self, token, state, offset):
        """One token a row: token (B, 1), ``offset`` a number or a (B,)
        NDArray of absolute positions -> logits (B, vocab); ``state`` is
        advanced in place.  Every row is routed and every row's matrix
        state is moved (the contract names no idle rows)."""
        from .. import ndarray as nd
        m = self.model
        b = token.shape[0]
        ctx = token.context
        if not isinstance(offset, nd.NDArray):
            offset = _rows(b, offset, ctx)
        elif offset.ndim == 0:
            offset = offset.reshape((1,)) + nd.zeros((b,), ctx=ctx)
        wdt = m.compute_dtype()
        with device_scope("mxtpu.moe"):
            stats = m.new_statistics(ctx)
        h = m.embedded(token)
        mask = None
        bufs = iter(state)
        picked = []
        for layer in m.layers:
            u = layer.pre(h, wdt)
            first, second = next(bufs), next(bufs)
            if layer.kind == FULL:
                with device_scope(layer.attn.scope):
                    if mask is None:
                        # position j of the page is live once written
                        n = first.shape[1]
                        pos = nd.arange(n, ctx=ctx).reshape((1, n))
                        mask = (pos <= offset.reshape((-1, 1))) \
                            .reshape((b, 1, 1, n))
                mix = layer.attn.step(u, first, second, offset, offset, mask)
            else:
                mix = layer.attn.step(u, first, second)
                m.count_delta(stats, b)
            h = layer.finish(h, mix, wdt, None, stats, picked)
        self.last_statistics = stats + self._picks(picked)
        return self._head(h)


_SOLAR_SPECS = {
    # test size: two whole periods (GQA, delta x 3), heads whose width is
    # not units / heads, a low rank narrower than a head
    "solar_open2_tiny": dict(units=64, moe_hidden=32, num_layers=8,
                             gqa_layers=(0, 4), num_heads=4, num_kv_heads=2,
                             head_dim=16, delta_heads=4, delta_head_dim=8,
                             conv_width=4, gate_rank=8, num_experts=16,
                             top_k=4),
    # upstage/Solar-Open2-250B config.json as ONE CHIP OF AN 8-WAY
    # EXPERT-PARALLEL DEPLOYMENT holds it: every width published; 4 of 48
    # layers (one whole period), 40 of each layer's 320 experts (the
    # router stays 320 wide); vocabulary 24,576 of 196,608
    # (chipbench/configs/solar_open2_ep8.json)
    "solar_open2_ep8": dict(units=4096, moe_hidden=1280, num_layers=4,
                            gqa_layers=(0,), num_heads=64,
                            num_kv_heads=8, head_dim=128, delta_heads=64,
                            delta_head_dim=128, conv_width=4, gate_rank=128,
                            num_experts=320, experts_held=(0, 40),
                            top_k=8),
}


def get_solar_open2(name, vocab_size=24576, **kwargs):
    if name not in _SOLAR_SPECS:
        raise MXNetError(f"unknown solar_open2 config {name!r}; options "
                         f"{sorted(_SOLAR_SPECS)}")
    spec = dict(_SOLAR_SPECS[name])
    spec.update(kwargs)
    return SolarOpen2Model(vocab_size=vocab_size, **spec)


def solar_open2_tiny(**kwargs):
    return get_solar_open2("solar_open2_tiny", **kwargs)


def solar_open2_ep8(**kwargs):
    return get_solar_open2("solar_open2_ep8", **kwargs)
