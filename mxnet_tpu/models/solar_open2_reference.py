"""Plain reference of the Solar Open 2 decoder (Solar-Open2-250B,
``model_type`` ``solar_open2``): ONE full-sequence forward in
straightforward ``jax.numpy``, the gated delta rule as its PER-TOKEN
recurrence (a ``lax.scan`` over the positions, one state update a token,
exactly as written below: no chunks, no triangular solve), a Python loop
over experts, no cache, no batching, no kernels, no sorting of tokens.
It imports nothing from ``mxnet_tpu.models`` or ``mxnet_tpu.ops``: what
the program is held to shares no code with it.
``chipbench/models/solar_open2_server.py`` carries a copy (the benchmark's
tree must stand alone).

A linear-attention layer (``kda_use_full_proj`` false,
``kda_allow_neg_eigval`` true: the channel-gated delta rule of Kimi
Linear, arXiv:2510.26692), per head of ``dk`` = ``dv`` features::

    q, k, v = SiLU(conv4(a W_{q,k,v}))       q, k L2-normed per head
    a_t = exp(-exp(A_log_h) softplus(a W_fa W_fb + dt_bias))     (dk,)
    b_t = 2 sigmoid(a W_b)
    S' = Diag(a_t) S_{t-1};  S_t = S' + b_t k_t (v_t - S'^T k_t)^T
    o_t = S_t^T q_t dk^-0.5
    out = W_o (RMS_head(o) * sigmoid(a W_ga W_gb))

A GQA layer: ``q, k, v, g = a W_{q,k,v,g}``, causal softmax over every
position with no positional encoding, ``W_o ((softmax(q k^T dh^-0.5) v)
* sigmoid(g))``.  Every layer: ``h += Mixer(RMS_1(h))``, ``h +=
Shared(m) + sum_{e in top_8(sigmoid(m W_r)), held} g_e Expert_e(m)`` with
``m = RMS_3(h)``, the gates normalised over the 8 picked; a final RMS
norm and an untied head.

``precision`` names WHAT IS ROUNDED, never how it is computed: every
sum, the residual stream, the norms, softmax, the recurrent state, the
router's scores and the gates are float32 under each (``PRECISIONS``).
``"float32"`` is the mathematics (products at ``Precision.HIGHEST``);
``"stated"`` is what a served configuration states (bfloat16 into every
matrix product, in the K,V a page keeps and in the conv's inputs a tail
keeps, float32 accumulation and state); ``"float8"`` is the control a
limit of ``correct`` is set against: weights, K,V and conv inputs in
float8 (4 exponent and 3 mantissa bits, one scale a tensor) besides.
Three more controls, each the stated precision with one thing of the
mechanism wrong, must each fail the comparison: ``"beta_sigmoid"`` (``b =
sigmoid``: no negative eigenvalues), ``"no_correction"`` (the ``- b k k^T
S'`` term dropped: plain gated linear attention) and ``"state_bfloat16"``
(the matrix state held in bfloat16 from token to token).

``weights`` maps the names below to arrays of any float type.  A dense
weight is ``(out, in)``: ``y = x W^T``; an expert's is ``(in, out)``,
stacked over the experts HELD (``y = x W_e``).  No bias anywhere.  ``H``
query / ``KV`` key-value heads of ``dh`` on a GQA layer; ``Hd`` heads of
``dk`` on a linear-attention layer, ``W = Hd dk``; ``r`` the gates' rank.

    embed_weight (V, d)       head_weight (V, d)       finalnorm_gamma (d,)
    layer{l}_ln1_gamma, layer{l}_ln3_gamma (d,)
    GQA:    layer{l}_attn_qkvg_weight ((2 H + 2 KV) dh, d) [q, k, v, gate rows],
            _attn_o_weight (d, H dh)
    linear: layer{l}_attn_qkv_weight (3 W, d) [q, k, v rows],
            _attn_conv_weight (K, 3 W) [tap K-1 takes the current position],
            _attn_fa_weight (r, d), _attn_fb_weight (W, r), _attn_a_log (Hd,),
            _attn_dt_bias (W,), _attn_b_weight (Hd, d), _attn_ga_weight (r, d),
            _attn_gb_weight (W, r), _attn_onorm_gamma (dk,), _attn_o_weight (d, W)
    expert: layer{l}_moe_router_weight (E, d),
            _moe_experts_gate_weight, _moe_experts_up_weight (N, d, f),
            _moe_experts_down_weight (N, f, d),
            _moe_shared_gateup_weight (2 f, d), _moe_shared_down_weight (d, f)

What the source's ``config.json`` has no key for is the convention of the
family it follows (Kimi Linear for the linear-attention layer, DeepSeek-V3
for the router) or a matter of this being one chip's share; the
configuration file lists each under ``assumed`` and each is marked
ASSUMED at its line below.
"""
import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

VOCAB_BLOCK = 16384
L2_EPS = 1e-6

# (exponent, mantissa) bits a value is rounded to; None leaves it float32
BFLOAT16, FLOAT8 = (8, 7), (4, 3)
_STATED = {"matmul": "bfloat16", "kv": BFLOAT16, "weights": None,
           "state": None, "beta": 2.0, "correction": True}
# the type a matrix product takes its inputs in; what the stored K,V and
# conv inputs, the weight matrices and the recurrent state are rounded
# to; the factor on the step's sigmoid; whether the delta rule subtracts
# what the state already holds
PRECISIONS = {
    "float32": dict(_STATED, matmul="float32", kv=None),
    "stated": _STATED,
    "float8": dict(_STATED, kv=FLOAT8, weights=FLOAT8),
    "beta_sigmoid": dict(_STATED, beta=1.0),
    "no_correction": dict(_STATED, correction=False),
    "state_bfloat16": dict(_STATED, state=BFLOAT16),
}
EXACT = dict(precision=jax.lax.Precision.HIGHEST,
             preferred_element_type=jnp.float32)


def _f32(w):
    return jnp.asarray(w).astype(jnp.float32)


def _rounded(x, bits):
    """``x`` in float32, holding only numbers a format of ``bits`` =
    (exponent, mantissa) holds.  ``lax.reduce_precision``, not a pair of
    casts: the TPU compiler drops a cast to bfloat16 and back (excess
    precision is allowed), and a control that rounds nothing proves
    nothing.  A format with a narrow exponent has one scale an array (its
    largest |value| on the format's largest), as a deployment in float8
    would: a scale a matrix, an expert, a block of the head's rows, a
    layer's K, V or conv inputs."""
    x = _f32(x)
    if bits is None:
        return x
    exponent, mantissa = bits
    if exponent == 8:                       # float32's own range
        return jax.lax.reduce_precision(x, exponent, mantissa)
    top = (2.0 - 2.0 ** -mantissa) * 2.0 ** (2 ** (exponent - 1) - 1)
    scale = jnp.max(jnp.abs(x)) / top
    return jax.lax.reduce_precision(x / scale, exponent, mantissa) * scale


def _mm(x, w, p):
    """``x W^T`` for a weight matrix ``(out, in)``: both rounded to what
    enters the product, the sum in float32."""
    dt = jnp.dtype(p["matmul"])
    return jnp.dot(x.astype(dt), _rounded(w, p["weights"]).astype(dt).T,
                   **EXACT)


def _rms(x, gain, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + eps) * _f32(gain)


def _silu(x):
    return x / (1.0 + jnp.exp(-x))


def _sigmoid(x):
    return 1.0 / (1.0 + jnp.exp(-x))


def _softplus(x):
    return jnp.logaddexp(x, 0.0)


def _unit(x):
    """Each head's vector over its L2 norm (eps ``L2_EPS`` under the
    root)."""
    return x / jnp.sqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True)
                        + L2_EPS)


@partial(jax.jit, static_argnames=("heads", "kv_heads", "precision"))
def _gqa(a, w, *, heads, kv_heads, precision):
    """The GQA mixer over every position: a (S, d) -> (S, d).  ASSUMED:
    no q/k norm, no positional encoding (``use_rope`` false), scores
    scaled by ``dh^-0.5``, the gate (``use_gqa_gate``) an elementwise
    sigmoid of q's width on the heads' output before ``W_o``."""
    p = PRECISIONS[precision]
    dt = jnp.dtype(p["matmul"])
    s = a.shape[0]
    dh = w["attn_o_weight"].shape[1] // heads
    proj = _mm(a, w["attn_qkvg_weight"], p)
    q, k, v, g = jnp.split(proj, np.cumsum(
        [heads * dh, kv_heads * dh, kv_heads * dh]), axis=-1)
    q = q.reshape(s, heads, dh)
    # what the page keeps
    k = _rounded(k, p["kv"]).reshape(s, kv_heads, dh)
    v = _rounded(v, p["kv"]).reshape(s, kv_heads, dh)
    group = heads // kv_heads
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    logits = jnp.einsum("thd,ihd->hti", q.astype(dt), k.astype(dt),
                        **EXACT) / math.sqrt(dh)
    keep = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    logits = jnp.where(keep[None], logits, -jnp.inf)
    e = jnp.exp(logits - jnp.max(logits, axis=-1, keepdims=True))
    probs = e / jnp.sum(e, axis=-1, keepdims=True)
    o = jnp.einsum("hti,ihd->thd", probs.astype(dt), v.astype(dt), **EXACT)
    return _mm(o.reshape(s, heads * dh) * _sigmoid(g), w["attn_o_weight"], p)


@partial(jax.jit, static_argnames=("heads", "precision"))
def _delta(a, w, eps, *, heads, precision):
    """The linear-attention mixer over every position, from a zero state,
    its recurrence one token at a time: a (S, d) -> ((S, d), the state the
    last position left (Hd, dk, dv))."""
    p = PRECISIONS[precision]
    s = a.shape[0]
    width = w["attn_o_weight"].shape[1]
    dk = width // heads
    # what a conv tail keeps: the projection's output as the cache type
    x = _rounded(_mm(a, w["attn_qkv_weight"], p), p["kv"])
    conv = _f32(w["attn_conv_weight"])                        # (K, 3 W)
    taps = conv.shape[0]
    # ASSUMED: the short convolution has no bias; tap K-1 takes the
    # current position, tap K-1-j the one j positions back
    y = sum(conv[taps - 1 - j] * jnp.pad(x, ((j, 0), (0, 0)))[:s]
            for j in range(taps))
    q, k, v = jnp.split(_silu(y), 3, axis=-1)
    q = _unit(q.reshape(s, heads, dk))
    k = _unit(k.reshape(s, heads, dk))
    v = v.reshape(s, heads, dk)
    # ASSUMED: low-rank gates of rank r (``kda_use_full_proj`` false)
    f = _mm(_mm(a, w["attn_fa_weight"], p), w["attn_fb_weight"], p)
    rate = _softplus(f + _f32(w["attn_dt_bias"])).reshape(s, heads, dk)
    decay = jnp.exp(-jnp.exp(_f32(w["attn_a_log"]))[None, :, None] * rate)
    # ``kda_allow_neg_eigval``: b in (0, 2)
    b = p["beta"] * _sigmoid(_mm(a, w["attn_b_weight"], p))    # (S, Hd)

    def token(state, xs):
        q_t, k_t, v_t, a_t, b_t = xs
        held = a_t[:, :, None] * state                       # S' (Hd, dk, dv)
        old = jnp.einsum("hk,hkv->hv", k_t, held, **EXACT) \
            if p["correction"] else 0.0
        new = held + b_t[:, None, None] * k_t[:, :, None] \
            * (v_t - old)[:, None, :]
        new = _rounded(new, p["state"])
        return new, jnp.einsum("hk,hkv->hv", q_t, new, **EXACT) / math.sqrt(dk)

    state, o = jax.lax.scan(token, jnp.zeros((heads, dk, dk), jnp.float32),
                            (q, k, v, decay, b))
    gate = _sigmoid(_mm(_mm(a, w["attn_ga_weight"], p),
                        w["attn_gb_weight"], p))
    o = _rms(o, w["attn_onorm_gamma"], eps).reshape(s, width) * gate
    return _mm(o, w["attn_o_weight"], p), state


@partial(jax.jit, static_argnames=("k", "precision"))
def _router(m, w, *, k, precision):
    """m (S, d) -> (scores (S, E) float32, the k experts each row picks,
    the margin between its k-th and (k+1)-th score).  ASSUMED: sigmoid
    scores, no selection bias, no expert groups (the config has no
    ``scoring_func``, ``n_group`` or bias key)."""
    s = _sigmoid(_mm(m, w["moe_router_weight"], PRECISIONS[precision]))
    order = jnp.argsort(-s, axis=-1, stable=True)
    ranked = jnp.take_along_axis(s, order, axis=-1)
    return s, order[:, :k].astype(jnp.int32), ranked[:, k - 1] - ranked[:, k]


@partial(jax.jit, static_argnames=("precision",))
def _one_expert(m, gate_w, up_w, down_w, weight, *, precision):
    """``weight[:, None] * Expert(m)`` for ONE expert, over every row
    (``weight`` is 0 on the rows that did not pick it).  The expert's
    matrices are (in, out)."""
    p = PRECISIONS[precision]
    mid = _silu(_mm(m, gate_w.T, p)) * _mm(m, up_w.T, p)
    return weight[:, None] * _mm(mid, down_w.T, p)


@partial(jax.jit, static_argnames=("precision",))
def _shared(m, gateup, down, *, precision):
    """``W2 (silu(W1g m) * (W1u m))``, ``gateup`` (2 f, d), gate first."""
    p = PRECISIONS[precision]
    gate, up = jnp.split(_mm(m, gateup, p), 2, axis=-1)
    return _mm(_silu(gate) * up, down, p)


@partial(jax.jit, static_argnames=("precision",))
def _head_block(h, e, *, precision):
    return _mm(h, e, PRECISIONS[precision])


_norm = jax.jit(_rms)


def _routed(m, w, cfg, held, chosen, precision):
    """The expert layer's routed part over the experts ``held`` = (first,
    count): (partial sum (S, d), picked (S, k), margin (S,)).  ``chosen``
    (S, k), if given, takes the place of the reference's own pick in the
    sum (its own is still returned)."""
    k = int(cfg["num_experts_per_tok"])
    scores, picked, margin = _router(m, w, k=k, precision=precision)
    use = picked if chosen is None else jnp.asarray(chosen, jnp.int32)
    taken = jnp.take_along_axis(scores, use, axis=-1)            # (S, k)
    # ``norm_topk_prob``: normalised over ALL it picked, held here or
    # not, then scaled by ``routed_scaling_factor``
    gates = taken / (jnp.sum(taken, axis=-1, keepdims=True) + 1e-20) \
        * float(cfg["routed_scaling_factor"])
    first, count = held
    out = jnp.zeros((m.shape[0], w["moe_experts_down_weight"].shape[2]),
                    jnp.float32)
    for j in range(count):              # experts held here, one by one
        # ASSUMED (the share): an expert that is not held adds nothing
        weight = jnp.sum(jnp.where(use == first + j, gates, 0.0), axis=-1)
        out = out + _one_expert(
            m, w["moe_experts_gate_weight"][j],
            w["moe_experts_up_weight"][j], w["moe_experts_down_weight"][j],
            weight, precision=precision)
    return out, picked, margin


def forward_logits(weights, tokens, cfg, precision="float32",
                   experts_held=None, selections=None, routing=None,
                   states=None):
    """(S, V) float32 logits of ``tokens`` (S,).

    ``cfg``: the source's keys ``num_hidden_layers``, ``gqa_layers``,
    ``num_attention_heads``, ``num_key_value_heads``, ``linear_heads``
    (``linear_attn_config.num_heads``), ``n_routed_experts`` (the
    router's width), ``num_experts_per_tok``, ``routed_scaling_factor``,
    ``rms_norm_eps`` (the other sizes are the weights' shapes).
    ``precision``: a key of ``PRECISIONS``.  ``experts_held`` = (first,
    count): the share of every expert layer that ``weights`` holds
    (default: all).  ``selections`` (S, expert layers, k) int32, if given,
    are the experts the SUM uses in place of the reference's own pick.
    ``routing``, if a dict, receives ``picked`` (S, expert layers, k), the
    reference's own pick, and ``margin`` (S, expert layers), its k-th
    score less its (k+1)-th.  ``states``, if a dict, receives each
    linear-attention layer's matrix state after the last token, by layer
    index ((Hd, dk, dv) float32)."""
    p = PRECISIONS[precision]
    eps = float(cfg["rms_norm_eps"])
    held = (0, int(cfg["n_routed_experts"])) if experts_held is None \
        else tuple(int(x) for x in experts_held)
    gqa = set(int(i) for i in cfg["gqa_layers"])
    tokens = np.asarray(tokens).astype(np.int32)
    # ASSUMED: the embedding is not scaled (the config has no key for it)
    h = _rounded(jnp.take(jnp.asarray(weights["embed_weight"]),
                          jnp.asarray(tokens), axis=0), p["weights"])
    picked, margins = [], []
    for l in range(int(cfg["num_hidden_layers"])):
        prefix = f"layer{l}_"
        w = {k[len(prefix):]: v for k, v in weights.items()
             if k.startswith(prefix)}
        # ASSUMED: pre-norm layers, every gain 1 at the start
        a = _norm(h, w["ln1_gamma"], eps)
        if l in gqa:
            h = h + _gqa(a, w, heads=int(cfg["num_attention_heads"]),
                         kv_heads=int(cfg["num_key_value_heads"]),
                         precision=precision)
        else:
            mix, state = _delta(a, w, eps, heads=int(cfg["linear_heads"]),
                                precision=precision)
            h = h + mix
            if states is not None:
                states[l] = np.asarray(state)
        m = _norm(h, w["ln3_gamma"], eps)
        chosen = None if selections is None \
            else np.asarray(selections)[:, len(picked)]
        ffn, pick, margin = _routed(m, w, cfg, held, chosen, precision)
        picked.append(pick)
        margins.append(margin)
        # the shared expert is whole on every chip
        h = h + ffn + _shared(m, w["moe_shared_gateup_weight"],
                              w["moe_shared_down_weight"],
                              precision=precision)
    if routing is not None:
        routing["picked"] = np.stack([np.asarray(x) for x in picked], 1)
        routing["margin"] = np.stack([np.asarray(x) for x in margins], 1)
    h = _norm(h, weights["finalnorm_gamma"], eps)
    # ASSUMED (the share): the head is a SLICE of the vocabulary's rows
    head = jnp.asarray(weights["head_weight"])
    v = head.shape[0]
    out = np.empty((len(tokens), v), np.float32)
    for v0 in range(0, v, VOCAB_BLOCK):
        out[:, v0:v0 + VOCAB_BLOCK] = np.asarray(_head_block(
            h, head[v0:v0 + VOCAB_BLOCK], precision=precision))
    return out


def weights_of(net, ctx=None):
    """{structural name: the parameter's array} of a Gluon net whose
    parameters end in the names above (the net's own prefix is cut)."""
    import re
    out = {}
    for name, p in net.collect_params().items():
        m = re.search(r"(embed_|finalnorm_|head_|layer\d+_).*$", name)
        out[m.group(0)] = p.data(ctx)._data
    return out


def config_of(net):
    """The ``cfg`` and ``experts_held`` of ``forward_logits`` that
    describe a built ``SolarOpen2ForCausalLM``."""
    m = net.model
    full = next(layer.attn for layer in m.layers if layer.kind == "full")
    delta = next(layer.attn for layer in m.layers if layer.kind == "delta")
    moe = m.layers[0].ffn
    cfg = {"num_hidden_layers": len(m.layers),
           "gqa_layers": [i for i, layer in enumerate(m.layers)
                          if layer.kind == "full"],
           "num_attention_heads": full._h, "num_key_value_heads": full._kv,
           "linear_heads": delta._h, "n_routed_experts": m.num_experts,
           "num_experts_per_tok": moe._attrs["k"],
           "routed_scaling_factor": moe._attrs["route_scale"],
           "rms_norm_eps": m.final_norm._eps}
    return cfg, m.experts_held
