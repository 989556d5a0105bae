"""Pangu Ultra MoE decoder LMs (openPangu-Ultra-MoE-718B; ``model_type``
``pangu_ultra_moe``): multi-head LATENT attention (MLA, DeepSeek-V2
section 2.1) with a low-rank query, sandwich norms, leading dense layers
and then sigmoid-routed top-k experts beside one shared expert.

Every layer is ``h + RMS_2(MLA(RMS_1(h)))`` then ``h + RMS_4(FFN(RMS_3(
h)))`` (``afmoe.py``'s ``_Layer`` with another mixer).  ``MLA``: ``c_q =
RMS_q(a W_dq)``, ``[q_n ; q_r] = c_q W_uq`` per head, ``[c_kv ; k_r] = a
W_dkv``, ``c = RMS_kv(c_kv)``, ``q_r`` and the ONE ``k_r`` all heads
share rotated at the position; head ``i`` has keys ``[W_uk,i c ; k_r]``
and values ``W_uv,i c``.  WHAT A POSITION LEAVES BEHIND is ``[c ; k_r]``,
normed and rotated: one ``kv_latent`` row of ``kv_rank + rope_dim``
numbers a layer, where per-head K,V would be ``heads x (nope + rope +
v)``.  Prefill expands keys and values per head from the prompt's rows;
decode absorbs ``W_uk`` into the query and ``W_uv`` into the output and
attends the page as it is stored: the two modes of ONE op over the same
``W_ukv`` (``ops/latent_attention.py``).  ``FFN``: SwiGLU on the first
``num_dense_layers`` layers, then ``Shared(m) + sum_{e in top_k(s),
held} g_e Expert_e(m)`` (``ops/moe.py`` ``routed_experts``, no selection
bias).  The embedding is not scaled, the head is untied.  The residual
stream, the norms, softmax and the router's scores are float32; the
matrix products run in the weights' dtype.  The equations, and every
departure from the published model, are in ``pangu_moe_reference.py``;
the published next-token-prediction module is a training objective and
is not built.

``experts_held`` and ``vocab_size`` are ONE CHIP'S SHARE of an
expert-parallel deployment, as in ``afmoe.py``.  The serving contract
(docs/serving.md, "State kinds", "Model statistics") is that model's
too: beside the expert layers' counts the latent attention counts the
positions it attended and how many of them a request had written.
"""
from __future__ import annotations

from functools import partial

from ..base import MXNetError
from ..gluon import nn
from ..gluon.block import HybridBlock
from ..profiler import device_scope
from .afmoe import AfmoeForCausalLM, _Layer, _RMSNorm
from .sambay import _dense, _rows, _split

__all__ = ["PanguMoeModel", "PanguMoeForCausalLM", "get_pangu_moe",
           "pangu_moe_tiny", "pangu_ultra_moe_ep16"]


class _LatentAttention(HybridBlock):
    """MLA: the five projections and the two norms; the attention itself
    is ``_contrib_LatentAttention``."""

    def __init__(self, units, num_heads, q_rank, kv_rank, nope_dim,
                 rope_dim, v_dim, eps, rope_base, **kwargs):
        super().__init__(**kwargs)
        self._h, self._rkv = num_heads, kv_rank
        self._dn, self._dr, self._dv = nope_dim, rope_dim, v_dim
        self._base = float(rope_base)
        #: the device scope; the layer's norms around it carry it too
        self.scope = "mxtpu.mixer.mla"
        h = num_heads
        with self.name_scope():
            self.dq_proj = _dense(q_rank, units, False, "dq_")
            self.q_norm = _RMSNorm(q_rank, eps, prefix="qnorm_")
            self.uq_proj = _dense(h * (nope_dim + rope_dim), q_rank, False,
                                  "uq_")
            self.dkv_proj = _dense(kv_rank + rope_dim, units, False, "dkv_")
            self.kv_norm = _RMSNorm(kv_rank, eps, prefix="kvnorm_")
            # head i's rows [W_uk,i ; W_uv,i]: expanded by prefill,
            # absorbed by decode, never copied
            self.ukv = self.params.get(
                "ukv_weight", shape=(h * (nope_dim + v_dim), kv_rank))
            self.o_proj = _dense(units, h * v_dim, False, "o_")

    def _inputs(self, a, offset):
        """a (B, S, units) -> q (B, S, H, dn + dr) and the positions'
        rows ``[c ; k_r]`` (B, S, rkv + dr): ``c`` normed, ``q_r`` and
        ``k_r`` rotated at ``offset`` (a number, or (B,) positions).
        Norms and rotations run in float32 and round once."""
        from .. import ndarray as nd
        b, s = a.shape[0], a.shape[1]
        h, dn, dr = self._h, self._dn, self._dr
        with device_scope("mxtpu.mixer.mla.project"):
            c_q = self.q_norm(self.dq_proj(a)).astype(a.dtype)
            q_n, q_r = _split(
                self.uq_proj(c_q).reshape((b, s, h, dn + dr)), (dn, dr))
            c, k_r = _split(self.dkv_proj(a), (self._rkv, dr))
            c = self.kv_norm(c)
        q_r = nd.rope(q_r.astype("float32"), offset=offset, base=self._base)
        k_r = nd.rope(k_r.astype("float32").reshape((b, s, 1, dr)),
                      offset=offset, base=self._base).reshape((b, s, dr))
        return (nd.concat(q_n, q_r.astype(a.dtype), dim=3),
                nd.concat(c, k_r, dim=2).astype(a.dtype))

    def _attend(self, q, rows, *offset):
        from .. import ndarray as nd
        o = nd._contrib_LatentAttention(
            q, rows, self.ukv.data(q.context), *offset, nope_dim=self._dn,
            v_dim=self._dv, use_offset=bool(offset))
        with device_scope("mxtpu.mixer.mla.project"):
            return self.o_proj(o)

    def seq(self, a):
        """Self-attention over a whole (right-padded) sequence, EXPANDED
        -> (out, the positions' rows as a page stores them)."""
        with device_scope(self.scope):
            q, rows = self._inputs(a, 0)
            return self._attend(q, rows), rows

    def step(self, a, page, offset):
        """One token a row at its own ``offset`` (B,): write the row
        into ``page``, attend the page ABSORBED."""
        from .. import ndarray as nd
        with device_scope(self.scope):
            q, row = self._inputs(a, offset)
            nd._cache_update(page, row, offset=offset, out=page)
            return self._attend(q, page, offset)


class PanguMoeModel(HybridBlock):
    def __init__(self, vocab_size, units, hidden, moe_hidden, num_layers,
                 num_dense_layers, num_heads, q_rank, kv_rank, nope_dim,
                 rope_dim, v_dim, num_experts, top_k, route_scale,
                 experts_held=None, rms_norm_eps=1e-5, rope_base=25.6e6,
                 **kwargs):
        super().__init__(**kwargs)
        held = (0, num_experts) if experts_held is None \
            else tuple(int(x) for x in experts_held)
        if held[0] < 0 or held[1] < 1 or sum(held) > num_experts:
            raise MXNetError(
                f"experts_held {held} = (first, count) must lie within "
                f"the router's {num_experts} experts")
        if rope_dim % 2:
            raise MXNetError(f"rope_dim {rope_dim} must be even: RoPE "
                             "turns feature pairs")
        self._units = units
        self.vocab_size = vocab_size
        self.row_width = kv_rank + rope_dim
        self.num_experts, self.experts_held = num_experts, held
        moe = dict(num_experts=num_experts, experts_held=held, top_k=top_k,
                   route_scale=route_scale, selection_bias=False)
        mixer = partial(_LatentAttention, units, num_heads, q_rank, kv_rank,
                        nope_dim, rope_dim, v_dim, rms_norm_eps, rope_base)
        with self.name_scope():
            self.embed = nn.Embedding(vocab_size, units, prefix="embed_")
            self.layers = []
            for i in range(num_layers):
                layer = _Layer(mixer, i < num_dense_layers, units, hidden,
                               moe_hidden, rms_norm_eps, moe,
                               prefix=f"layer{i}_")
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
                for _ in PanguMoeForCausalLM.statistics]

    @staticmethod
    def count_attention(stats, live, attended):
        """One latent-attention call: ``live`` positions a request had
        written among the ``attended`` ones the call ran over (the last
        three rows of ``PanguMoeForCausalLM.statistics``)."""
        with device_scope("mxtpu.mixer.mla"):
            for i, c in zip((-3, -2, -1), (live, attended, 1)):
                stats[i] = stats[i] + c

    def run(self, tokens, stats):
        """Every layer over the whole sequence: (B, S) -> (B, S, units)
        before the final norm."""
        b, s = tokens.shape
        h = self.embedded(tokens)
        wdt = self.compute_dtype()
        for layer in self.layers:
            mix, _rows = layer.attn.seq(layer.pre(h, wdt))
            self.count_attention(stats, b * s, b * s)
            h = layer.finish(h, mix, wdt, None, stats)
        return h

    def hybrid_forward(self, F, tokens):
        h = self.run(tokens, self.new_statistics(tokens.context))
        with device_scope("mxtpu.head"):
            return self.final_norm(h)


class PanguMoeForCausalLM(AfmoeForCausalLM):
    """Untied LM head over :class:`PanguMoeModel` with the model-zoo
    decoder contract: ``AfmoeForCausalLM``'s head, counts, picks and
    ``generate``, over latent pages."""

    statistics = AfmoeForCausalLM.statistics + (
        ("mxtpu_mla_live_positions_total",
         "positions a request had written among those its latent "
         "attention ran over, summed over attention-layer calls"),
        ("mxtpu_mla_page_positions_total",
         "positions the latent attention ran over (a prompt's length a "
         "row; in decode the blocks its kernel walked, or rows x page "
         "where the dense lowering ran), summed over attention-layer "
         "calls"),
        ("mxtpu_mla_layer_calls_total", "latent-attention layer calls"),
    )

    # -- state -----------------------------------------------------------
    def state_spec(self, slots, cache_len, dtype="float32"):
        """ONE ``kv_latent`` buffer a layer, in layer order: ``cache_len``
        rows ``[c ; k_r]`` of ``kv_rank + rope_dim`` numbers (``c``
        normed, ``k_r`` rotated).  Nothing per head."""
        import jax.numpy as jnp
        if not jnp.issubdtype(jnp.dtype(dtype), jnp.floating):
            raise MXNetError(
                f"KV cache dtype must be floating, got {dtype!r} "
                "(an int cache truncates every latent write)")
        m = self.model
        return [(f"layer{i}_latent", "kv_latent",
                 (slots, cache_len, m.row_width), str(dtype))
                for i in range(len(m.layers))]

    # -- prefill ----------------------------------------------------------
    def prefill(self, tokens, state, last_pos=None):
        """Right-padded prompts (B, S) -> each row's logits at its own
        ``last_pos`` (B, vocab), through the EXPANDED path; the prompt's
        latent rows are written to ``state`` (flat, ``state_spec``
        order) at offset 0.  Rows past ``last_pos`` are written and
        never read (causal; decode masks them), are routed to no expert
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
            live = valid.sum().astype("int32")
            stats = m.new_statistics(ctx)
        h = m.embedded(tokens)
        picked = []
        for layer, page in zip(m.layers, state):
            mix, rows = layer.attn.seq(layer.pre(h, wdt))
            with device_scope(layer.attn.scope):
                nd._cache_update(page, rows, offset=0, out=page)
            m.count_attention(stats, live, b * s)
            h = layer.finish(h, mix, wdt, valid, stats, picked)
        self.last_statistics = stats + self._picks(picked, valid)
        with device_scope("mxtpu.head"):
            return self._head(nd._take_positions(h, last_pos))

    # -- decode -----------------------------------------------------------
    def decode_step(self, token, state, offset):
        """One token a row through the ABSORBED path: token (B, 1),
        ``offset`` a number or a (B,) NDArray of absolute positions ->
        logits (B, vocab); ``state`` is advanced in place.  A row
        attends its page's rows ``[0, offset]``: what an evicted
        request left past them is never read."""
        from .. import ndarray as nd
        m = self.model
        b = token.shape[0]
        ctx = token.context
        if not isinstance(offset, nd.NDArray):
            offset = _rows(b, offset, ctx)
        elif offset.ndim == 0:
            offset = offset.reshape((1,)) + nd.zeros((b,), ctx=ctx)
        wdt = m.compute_dtype()
        with device_scope("mxtpu.mixer.mla"):
            stats = m.new_statistics(ctx)
            live = (offset + 1).sum().astype("int32")
        h = m.embedded(token)
        picked = []
        for layer, page in zip(m.layers, state):
            mix = layer.attn.step(layer.pre(h, wdt), page, offset)
            with device_scope(layer.attn.scope):
                walked = nd._contrib_LatentAttentionWalked(page, offset)
            m.count_attention(stats, live, walked)
            h = layer.finish(h, mix, wdt, None, stats, picked)
        self.last_statistics = stats + self._picks(picked)
        return self._head(h)


_PANGU_SPECS = {
    # test size: one dense layer and four expert layers, heads whose key
    # (16 + 8) and value (16) widths differ and do not divide the hidden
    # size, a rope part narrower than the rest
    "pangu_moe_tiny": dict(units=64, hidden=128, moe_hidden=32,
                           num_layers=5, num_dense_layers=1, num_heads=4,
                           q_rank=24, kv_rank=16, nope_dim=16, rope_dim=8,
                           v_dim=16, num_experts=16, top_k=4,
                           route_scale=2.5),
    # FreedomIntelligence/openPangu-Ultra-MoE-718B config.json as ONE CHIP
    # OF A 16-WAY EXPERT-PARALLEL DEPLOYMENT holds it: every width
    # published; 5 of 61 layers (1 of the 3 dense ones + 4 expert layers),
    # 16 of each layer's 256 experts (the router stays 256 wide);
    # vocabulary 19,200 of 153,600; the next-token-prediction module not
    # built (chipbench/configs/pangu_ultra_moe_ep16.json)
    "pangu_ultra_moe_ep16": dict(units=7680, hidden=18432, moe_hidden=2048,
                                 num_layers=5, num_dense_layers=1,
                                 num_heads=128, q_rank=1536, kv_rank=512,
                                 nope_dim=128, rope_dim=64, v_dim=128,
                                 num_experts=256, experts_held=(0, 16),
                                 top_k=8, route_scale=2.5),
}


def get_pangu_moe(name, vocab_size=19200, **kwargs):
    if name not in _PANGU_SPECS:
        raise MXNetError(f"unknown pangu_moe config {name!r}; options "
                         f"{sorted(_PANGU_SPECS)}")
    spec = dict(_PANGU_SPECS[name])
    spec.update(kwargs)
    return PanguMoeModel(vocab_size=vocab_size, **spec)


def pangu_moe_tiny(**kwargs):
    return get_pangu_moe("pangu_moe_tiny", **kwargs)


def pangu_ultra_moe_ep16(**kwargs):
    return get_pangu_moe("pangu_ultra_moe_ep16", **kwargs)
