"""SambaY decoder-hybrid-decoder LMs (Phi-4-mini-flash-reasoning,
arXiv:2507.06607): five kinds of layer in one decoder.

* layers ``0 .. n/2 + 1`` are the SELF-decoder: Mamba-1 on even layers,
  differential attention (arXiv:2410.05258) with a sliding window on odd
  ones, ONE full-attention layer last (layer ``n/2 + 1``) whose K,V are
  kept;
* layers ``n/2 + 2 .. n - 1`` are the CROSS-decoder: gated memory units
  on the memory ``m`` of the last Mamba layer (even layers) and cross
  attention over the full layer's K,V (odd layers).  Neither holds state.

No positional encoding anywhere.  Every layer is ``h + Mixer(LN1(h))``
then ``h + W2 (up * silu(gate))``; the residual stream is float32, the
matrix products run in the weights' dtype.  The equations, and every
departure from the published model, are in ``sambay_reference.py``.

The serving contract (docs/serving.md, "State kinds"):
``state_spec(slots, cache_len, dtype)`` names the buffers a slot holds,
``prefill(tokens, state, last_pos)`` fills a batch of them from
right-padded prompts, ``decode_step(token, state, offset)`` advances
every row one token at its own offset.  PREFILL runs the self-decoder
over the whole prompt and the cross-decoder at ONE position, each row's
``last_pos``: prefill cost linear in the prompt is the architecture's
point.  A recurrent state IS exposed to the next step, so the scan and
the conv tail freeze past ``last_pos`` (``ops/ssm.py``).
"""
from __future__ import annotations

import math

import numpy as np

from .. import initializer as init
from ..base import MXNetError
from ..gluon import nn
from ..gluon.block import HybridBlock
from ..profiler import device_scope

__all__ = ["SambaYModel", "SambaYForCausalLM", "get_sambay",
           "sambay_tiny", "phi4_mini_flash", "layer_kind"]


def layer_kind(l, n):
    """``mamba`` | ``swa`` | ``full`` | ``cross`` | ``gmu``."""
    if l % 2 == 0:
        return "mamba" if l <= n // 2 else "gmu"
    if l < n // 2:
        return "swa"
    return "full" if l == n // 2 + 1 else "cross"


def _rows(b, value, ctx):
    """(B,) float32 NDArray holding ``value``: a position every row
    shares, as an array INPUT (a static attr would compile a program per
    position)."""
    from .. import ndarray as nd
    return nd.array(np.full((b,), float(value), "float32"), ctx=ctx)


def _split(t, sizes):
    """``t`` cut along its last axis into pieces of ``sizes``."""
    from .. import ndarray as nd
    out, at = [], 0
    for n in sizes:
        out.append(nd.slice_axis(t, axis=-1, begin=at, end=at + n))
        at += n
    return out


def _dense(out, inp, bias, prefix):
    return nn.Dense(out, flatten=False, use_bias=bias, in_units=inp,
                    prefix=prefix)


class _LayerNorm(HybridBlock):
    """LayerNorm with gain and bias; statistics in float32 whatever the
    parameters' dtype."""

    def __init__(self, units, eps, **kwargs):
        super().__init__(**kwargs)
        self._eps = eps
        with self.name_scope():
            self.gamma = self.params.get("gamma", shape=(units,),
                                         init="ones")
            self.beta = self.params.get("beta", shape=(units,),
                                        init="zeros")

    def hybrid_forward(self, F, x, gamma=None, beta=None):
        return F.LayerNorm(x.astype("float32"), gamma.astype("float32"),
                           beta.astype("float32"), eps=self._eps)


class _MLP(HybridBlock):
    """``W2 (up * silu(gate))``, ``[gate, up] = W1 x``, no bias."""

    def __init__(self, units, hidden, scope="mxtpu.mlp", **kwargs):
        super().__init__(**kwargs)
        self._hidden = hidden
        self._scope_name = scope
        with self.name_scope():
            self.gateup = _dense(2 * hidden, units, False, "gateup_")
            self.down = _dense(units, hidden, False, "down_")

    def hybrid_forward(self, F, x):
        with device_scope(self._scope_name):
            gate, up = _split(self.gateup(x), (self._hidden, self._hidden))
            return self.down(up * F.silu(gate))


class _Mamba(HybridBlock):
    """Mamba-1 mixer.  ``seq`` scans a right-padded batch and returns the
    state at each row's ``last_pos``; ``step`` advances one token."""

    def __init__(self, units, d_inner, d_state, d_conv, dt_rank, **kwargs):
        super().__init__(**kwargs)
        self._di, self._n, self._r = d_inner, d_state, dt_rank
        # Mamba's own initial values: A = -(1..N) on every channel, dt's
        # bias the inverse softplus of steps spread over [1e-3, 1e-1]
        a_log = np.log(np.arange(1, d_state + 1, dtype="f4"))[:, None] \
            * np.ones((1, d_inner), "f4")
        dt = np.exp(np.linspace(math.log(1e-3), math.log(1e-1), d_inner,
                                dtype="f4"))
        with self.name_scope():
            self.in_proj = _dense(2 * d_inner, units, False, "in_")
            self.conv_weight = self.params.get(
                "conv_weight", shape=(d_conv, d_inner),
                init=init.Uniform(1.0 / math.sqrt(d_conv)))
            self.conv_bias = self.params.get(
                "conv_bias", shape=(d_inner,), init="zeros")
            self.x_proj = _dense(dt_rank + 2 * d_state, d_inner, False,
                                 "x_")
            self.dt_proj = _dense(d_inner, dt_rank, False, "dt_")
            self.dt_bias = self.params.get(
                "dt_bias", shape=(d_inner,),
                init=init.Constant(dt + np.log(-np.expm1(-dt))))
            self.a_log = self.params.get(
                "a_log", shape=(d_state, d_inner),
                init=init.Constant(a_log))
            self.d_skip = self.params.get("d", shape=(d_inner,),
                                          init="ones")
            self.out_proj = _dense(units, d_inner, False, "out_")

    def _ssm_params(self, ctx):
        return (self.a_log.data(ctx), self.d_skip.data(ctx),
                self.dt_bias.data(ctx))

    def seq(self, u, last_pos):
        """u (B, S, h) -> (out (B, S, h), memory y (B, S, Di), conv tail
        (B, K-1, Di), state (B, N, Di) float32)."""
        from .. import ndarray as nd
        ctx = u.context
        with device_scope("mxtpu.mixer.mamba"):
            x, z = _split(self.in_proj(u), (self._di, self._di))
            x, tail = nd._causal_conv1d(x, self.conv_weight.data(ctx),
                                        self.conv_bias.data(ctx), last_pos)
            dt, bm, cm = _split(self.x_proj(x),
                                     (self._r, self._n, self._n))
            y, state = nd._selective_scan(x, self.dt_proj(dt), bm, cm,
                                          *self._ssm_params(ctx), last_pos)
            return self.out_proj(y * nd.silu(z)), y, tail, state

    def step(self, u, tail, state):
        """u (B, 1, h); ``tail`` and ``state`` are advanced in place."""
        from .. import ndarray as nd
        ctx = u.context
        b = u.shape[0]
        with device_scope("mxtpu.mixer.mamba"):
            x, z = _split(self.in_proj(u).reshape((b, 2 * self._di)),
                               (self._di, self._di))
            x, new_tail = nd._causal_conv1d_step(
                x, self.conv_weight.data(ctx), self.conv_bias.data(ctx),
                tail)
            tail._set_data(new_tail._data)
            dt, bm, cm = _split(self.x_proj(x),
                                     (self._r, self._n, self._n))
            y, new_state = nd._selective_scan_step(
                x, self.dt_proj(dt), bm, cm, *self._ssm_params(ctx), state)
            state._set_data(new_state._data)
            out = self.out_proj(y * nd.silu(z))
            return out.reshape((b, 1, -1)), y.reshape((b, 1, self._di))

class _DiffAttention(HybridBlock):
    """Differential attention: a window or full self-attention layer
    (``cross=False``: own ``Wqkv``) or a cross layer (own ``Wq`` only,
    the full layer's K,V as stored)."""

    def __init__(self, units, num_heads, num_kv_heads, depth, window=None,
                 cross=False, **kwargs):
        super().__init__(**kwargs)
        if units % num_heads or num_heads % num_kv_heads \
                or num_kv_heads % 2:
            raise MXNetError(
                f"differential attention pairs heads: units {units}, "
                f"heads {num_heads}, kv heads {num_kv_heads} (even, "
                "dividing)")
        self._h, self._kv = num_heads, num_kv_heads
        self._d = units // num_heads
        self.window = window
        self._lam0 = 0.8 - 0.6 * math.exp(-0.3 * depth)
        d = self._d
        with self.name_scope():
            if cross:
                self.q_proj = _dense(num_heads * d, units, True, "q_")
            else:
                self.qkv_proj = _dense((num_heads + 2 * num_kv_heads) * d,
                                       units, True, "qkv_")
            self.o_proj = _dense(units, num_heads * d, True, "o_")
            self.lambdas = [
                self.params.get(f"lambda_{n}", shape=(d,),
                                init=init.Normal(0.1))
                for n in ("q1", "k1", "q2", "k2")]
            self.subln_gamma = self.params.get(
                "subln_gamma", shape=(2 * d,), init="ones")

    def _lam(self, ctx):
        from .. import ndarray as nd
        q1, k1, q2, k2 = (p.data(ctx).astype("float32")
                          for p in self.lambdas)
        return (nd.exp((q1 * k1).sum()) - nd.exp((q2 * k2).sum())
                + self._lam0).reshape((1,))

    def _attend(self, q, k, v, mask=None, causal=False):
        from .. import ndarray as nd
        ctx = q.context
        out = nd._diff_attention(
            q, k, v, self._lam(ctx), self.subln_gamma.data(ctx),
            *([mask] if mask is not None else []),
            lambda_init=self._lam0, causal=causal,
            window=self.window if causal else None,
            use_mask=mask is not None)
        return self.o_proj(out)

    def qkv(self, u):
        b, s = u.shape[0], u.shape[1]
        h, kv, d = self._h, self._kv, self._d
        q, k, v = _split(self.qkv_proj(u), (h * d, kv * d, kv * d))
        return (q.reshape((b, s, h, d)), k.reshape((b, s, kv, d)),
                v.reshape((b, s, kv, d)))

    def seq(self, u):
        """Self-attention over a whole (right-padded) sequence ->
        (out, k, v); causal, banded by the layer's window."""
        with device_scope("mxtpu.mixer.swa" if self.window else
                    "mxtpu.mixer.full"):
            q, k, v = self.qkv(u)
            return self._attend(q, k, v, causal=True), k, v

    def step(self, u, cache_k, cache_v, slot, mask):
        """One token: write K,V at ``slot`` (B,), attend the buffer under
        the key mask (B, 1, 1, C)."""
        from .. import ndarray as nd
        with device_scope("mxtpu.mixer.swa" if self.window else
                    "mxtpu.mixer.full"):
            q, k, v = self.qkv(u)
            nd._cache_update(cache_k, k, offset=slot, out=cache_k)
            nd._cache_update(cache_v, v, offset=slot, out=cache_v)
            return self._attend(q, cache_k, cache_v, mask=mask)

    def cross(self, u, k, v, mask=None, causal=False):
        """Cross layer: Q from ``u``, the full layer's K,V as given."""
        b, s = u.shape[0], u.shape[1]
        with device_scope("mxtpu.mixer.cross"):
            q = self.q_proj(u).reshape((b, s, self._h, self._d))
            return self._attend(q, k, v, mask=mask, causal=causal)


class _GMU(HybridBlock):
    """Gated memory unit: ``W_o2 (silu(W_i u) * m)``; no state."""

    def __init__(self, units, d_inner, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.in_proj = _dense(d_inner, units, False, "in_")
            self.out_proj = _dense(units, d_inner, False, "out_")

    def hybrid_forward(self, F, u, m):
        with device_scope("mxtpu.mixer.gmu"):
            return self.out_proj(F.silu(self.in_proj(u)) * m)


class _Layer(HybridBlock):
    def __init__(self, kind, depth, units, hidden, num_heads, num_kv_heads,
                 window, eps, mamba, **kwargs):
        super().__init__(**kwargs)
        self.kind = kind
        #: the mixer's device scope; its norm and residual add carry it
        self.scope = "mxtpu.mixer." + kind
        with self.name_scope():
            self.ln1 = _LayerNorm(units, eps, prefix="ln1_")
            if kind == "mamba":
                self.mixer = _Mamba(units, prefix="mamba_", **mamba)
            elif kind == "gmu":
                self.mixer = _GMU(units, mamba["d_inner"], prefix="gmu_")
            else:
                self.mixer = _DiffAttention(
                    units, num_heads, num_kv_heads, depth,
                    window=window if kind == "swa" else None,
                    cross=kind == "cross", prefix="attn_")
            self.ln2 = _LayerNorm(units, eps, prefix="ln2_")
            self.mlp = _MLP(units, hidden, prefix="mlp_")

    def pre(self, h, dtype):
        """The mixer's input: the first norm, in the weights' dtype."""
        with device_scope(self.scope):
            return self.ln1(h).astype(dtype)

    def finish(self, h, mix):
        """Residual add of the mixer's output, then the MLP sublayer."""
        with device_scope(self.scope):
            h = h + mix.astype("float32")
        with device_scope("mxtpu.mlp"):
            return h + self.mlp(self.ln2(h).astype(mix.dtype)) \
                .astype("float32")


class SambaYModel(HybridBlock):
    def __init__(self, vocab_size, units, hidden, num_layers, num_heads,
                 num_kv_heads, sliding_window, layer_norm_eps=1e-5,
                 d_state=16, d_conv=4, dt_rank=None, d_inner=None,
                 **kwargs):
        super().__init__(**kwargs)
        if num_layers < 8 or num_layers % 4:
            raise MXNetError(
                "a SambaY decoder needs num_layers a multiple of 4, at "
                f"least 8 (every kind of layer present), got {num_layers}")
        self._units = units
        self.vocab_size = vocab_size
        self.sliding_window = int(sliding_window)
        self.num_kv_heads = num_kv_heads
        self.head_dim = units // num_heads
        self.mamba = dict(d_inner=d_inner or 2 * units, d_state=d_state,
                          d_conv=d_conv,
                          dt_rank=dt_rank or math.ceil(units / 16))
        with self.name_scope():
            self.embed = nn.Embedding(vocab_size, units, prefix="embed_")
            self.layers = []
            for i in range(num_layers):
                layer = _Layer(layer_kind(i, num_layers), i, units, hidden,
                               num_heads, num_kv_heads, sliding_window,
                               layer_norm_eps, self.mamba,
                               prefix=f"layer{i}_")
                self.register_child(layer, f"layer{i}")
                self.layers.append(layer)
            self.final_norm = _LayerNorm(units, layer_norm_eps,
                                         prefix="finalnorm_")
        # layers run at ONE position by prefill (the cross-decoder)
        self.tail_from = num_layers // 2 + 2

    def compute_dtype(self):
        """What enters the matrix products: the weights' dtype."""
        return self.embed.weight.dtype

    def embed_tokens(self, tokens):
        with device_scope("mxtpu.embed"):
            return self.embed(tokens).astype("float32")

    def hybrid_forward(self, F, tokens):
        """Every layer over the whole sequence: (B, S) -> (B, S, h)."""
        from .. import ndarray as nd
        h = self.embed_tokens(tokens)
        last = _rows(tokens.shape[0], tokens.shape[1] - 1, tokens.context)
        wdt = self.compute_dtype()
        mem = k = v = None
        for layer in self.layers:
            u = layer.pre(h, wdt)
            if layer.kind == "mamba":
                mix, mem, _, _ = layer.mixer.seq(u, last)
            elif layer.kind == "gmu":
                mix = layer.mixer(u, mem)
            elif layer.kind == "cross":
                mix = layer.mixer.cross(u, k, v, causal=True)
            else:
                mix, k_l, v_l = layer.mixer.seq(u)
                if layer.kind == "full":
                    k, v = k_l, v_l
            h = layer.finish(h, mix)
        with device_scope("mxtpu.head"):
            return self.final_norm(h)


class SambaYForCausalLM(HybridBlock):
    """LM head (the tied embedding) over :class:`SambaYModel`, with the
    model-zoo decoder contract the serving plane drives."""

    def __init__(self, model: SambaYModel, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.model = model

    # -- head ------------------------------------------------------------
    def _head(self, h):
        from .. import ndarray as nd
        w = self.model.embed.weight.data(h.context)
        with device_scope("mxtpu.head"):
            return nd._head_logits(
                h.reshape((-1, self.model._units))
                .astype(self.model.compute_dtype()), w)

    def hybrid_forward(self, F, tokens):
        h = self.model(tokens)
        b, s = tokens.shape
        return self._head(h).reshape((b, s, self.model.vocab_size))

    # -- state -----------------------------------------------------------
    def state_spec(self, slots, cache_len, dtype="float32"):
        """The buffers ``slots`` requests of up to ``cache_len`` positions
        hold, in the order ``prefill`` / ``decode_step`` take them:
        ``(name, kind, shape, dtype)`` rows.  The SSM state is float32
        whatever ``dtype`` says; cross and GMU layers hold nothing."""
        import jax.numpy as jnp
        if not jnp.issubdtype(jnp.dtype(dtype), jnp.floating):
            raise MXNetError(
                f"KV cache dtype must be floating, got {dtype!r} "
                "(an int cache truncates every K/V write)")
        m = self.model
        kv = (m.num_kv_heads, m.head_dim)
        mb = m.mamba
        rows = []
        for i, layer in enumerate(m.layers):
            if layer.kind == "mamba":
                rows.append((f"layer{i}_conv", "conv",
                             (slots, mb["d_conv"] - 1, mb["d_inner"]),
                             str(dtype)))
                rows.append((f"layer{i}_ssm", "ssm",
                             (slots, mb["d_state"], mb["d_inner"]),
                             "float32"))
            elif layer.kind in ("swa", "full"):
                n = cache_len if layer.kind == "full" else \
                    min(m.sliding_window, cache_len)
                kind = "kv_full" if layer.kind == "full" else "kv_window"
                rows.append((f"layer{i}_k", kind, (slots, n) + kv,
                             str(dtype)))
                rows.append((f"layer{i}_v", kind, (slots, n) + kv,
                             str(dtype)))
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
        filled in place.  Layers up to the full-attention one run over the
        whole prompt, the cross-decoder at the one position ``last_pos``."""
        from .. import ndarray as nd
        m = self.model
        b, s = tokens.shape
        ctx = tokens.context
        if last_pos is None:
            last_pos = _rows(b, s - 1, ctx)
        wdt = m.compute_dtype()
        h = m.embed_tokens(tokens)
        bufs = iter(state)
        mem = k = v = None
        for layer in m.layers[:m.tail_from]:
            u = layer.pre(h, wdt)
            if layer.kind == "mamba":
                mix, mem, tail, ssm = layer.mixer.seq(u, last_pos)
                for buf, new in ((next(bufs), tail), (next(bufs), ssm)):
                    buf._set_data(new._data.astype(buf.dtype))
            else:
                mix, k, v = layer.mixer.seq(u)
                for buf, new in ((next(bufs), k), (next(bufs), v)):
                    if layer.kind == "swa":
                        new = nd._rolling_window_fill(
                            new, last_pos, length=buf.shape[1])
                    nd._cache_update(buf, new, offset=0, out=buf)
            h = layer.finish(h, mix)
        # the cross-decoder sees one position a row: its own last token
        with device_scope("mxtpu.mixer.cross"):
            h = nd._take_positions(h, last_pos)
            pos = nd.arange(s, ctx=ctx).reshape((1, s))
            mask = (pos <= last_pos.reshape((-1, 1))) \
                .reshape((b, 1, 1, s))
        with device_scope("mxtpu.mixer.gmu"):
            mem = nd._take_positions(mem, last_pos)
        for layer in m.layers[m.tail_from:]:
            u = layer.pre(h, wdt)
            if layer.kind == "gmu":
                mix = layer.mixer(u, mem)
            else:
                mix = layer.mixer.cross(u, k, v, mask=mask)
            h = layer.finish(h, mix)
        with device_scope("mxtpu.head"):
            return self._head(m.final_norm(h))

    # -- decode -----------------------------------------------------------
    def decode_step(self, token, state, offset):
        """One token a row: token (B, 1), ``offset`` a number or a (B,)
        NDArray of absolute positions -> logits (B, vocab); ``state`` is
        advanced in place."""
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
        h = m.embed_tokens(token)
        masks = {}

        def key_mask(n, scope):
            # slot j of an n-slot buffer is live once written: j <= offset
            # (a rolling window buffer holds only positions inside the
            # window, so every written slot is visible)
            if n not in masks:
                with device_scope(scope):
                    pos = nd.arange(n, ctx=ctx).reshape((1, n))
                    masks[n] = (pos <= offv).reshape((b, 1, 1, n))
            return masks[n]

        bufs = iter(state)
        mem = k = v = None
        for layer in m.layers:
            u = layer.pre(h, wdt)
            if layer.kind == "mamba":
                mix, mem = layer.mixer.step(u, next(bufs), next(bufs))
            elif layer.kind == "gmu":
                mix = layer.mixer(u, mem)
            elif layer.kind == "cross":
                mix = layer.mixer.cross(
                    u, k, v, mask=key_mask(k.shape[1], layer.scope))
            else:
                ck, cv = next(bufs), next(bufs)
                n = ck.shape[1]
                with device_scope(layer.scope):
                    slot = offset % float(n) if layer.kind == "swa" \
                        else offset
                mix = layer.mixer.step(u, ck, cv, slot,
                                       key_mask(n, layer.scope))
                if layer.kind == "full":
                    k, v = ck, cv
            h = layer.finish(h, mix)
        with device_scope("mxtpu.head"):
            return self._head(m.final_norm(h))

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


_SAMBAY_SPECS = {
    # test size: every kind of layer (mamba 0 2 4, swa 1 3, full 5, gmu 6,
    # cross 7), a window the tests cross
    "sambay_tiny": dict(units=64, hidden=128, num_layers=8, num_heads=8,
                        num_kv_heads=4, sliding_window=8, d_state=4,
                        dt_rank=4),
    # microsoft/Phi-4-mini-flash-reasoning config.json (model_type
    # phi4flash); vocabulary 200,064
    "phi4_mini_flash": dict(units=2560, hidden=10240, num_layers=32,
                            num_heads=40, num_kv_heads=20,
                            sliding_window=512, d_state=16, d_conv=4,
                            dt_rank=160, d_inner=5120),
}


def get_sambay(name, vocab_size=200064, **kwargs):
    if name not in _SAMBAY_SPECS:
        raise MXNetError(f"unknown sambay config {name!r}; options "
                         f"{sorted(_SAMBAY_SPECS)}")
    spec = dict(_SAMBAY_SPECS[name])
    spec.update(kwargs)
    return SambaYModel(vocab_size=vocab_size, **spec)


def sambay_tiny(**kwargs):
    return get_sambay("sambay_tiny", **kwargs)


def phi4_mini_flash(**kwargs):
    return get_sambay("phi4_mini_flash", **kwargs)
