"""Plain reference of the AFMoE decoder (Arcee Trinity, ``model_type``
``afmoe``): ONE full-sequence forward in straightforward ``jax.numpy``, a
Python loop over heads and over experts, no cache, no batching, no
kernels, no sorting of tokens.  It imports nothing from
``mxnet_tpu.models`` or ``mxnet_tpu.ops``: what the program is held to
shares no code with it.  ``chipbench/models/afmoe_server.py`` carries a
copy (the benchmark's tree must stand alone).

``precision`` names WHAT IS ROUNDED, never how it is computed: every sum,
the residual stream, the norms, softmax, the router's scores and the
gates are float32 under each (``PRECISIONS``).  ``"float32"`` is the
mathematics (products at ``Precision.HIGHEST``); ``"stated"`` is what a
served configuration states (bfloat16 into every matrix product and in
the K,V, float32 accumulation); ``"float8"`` is the control a limit of
``correct`` is set against: weights and K,V in float8 (4 exponent and 3
mantissa bits, one scale a tensor) besides.

``weights`` maps the names below to arrays of any float type.  A dense
weight is ``(out, in)``: ``y = x W^T``; an expert's is ``(in, out)``,
stacked over the experts HELD (``y = x W_e``).  No bias anywhere.

    embed_weight (V, d)       head_weight (V, d)       finalnorm_gamma (d,)
    layer{l}_ln1_gamma .. layer{l}_ln4_gamma (d,)
    layer{l}_attn_qkvg_weight ((2 H + 2 KV) dh, d) [q, k, v, gate rows],
            _attn_qnorm_gamma, _attn_knorm_gamma (dh,), _attn_o_weight (d, H dh)
    dense:  layer{l}_mlp_gateup_weight (2 f, d) [gate rows first], _mlp_down_weight (d, f)
    expert: layer{l}_moe_router_weight (E, d), _moe_router_bias (E,),
            _moe_experts_gate_weight, _moe_experts_up_weight (N, d, f),
            _moe_experts_down_weight (N, f, d),
            _moe_shared_gateup_weight (2 f, d), _moe_shared_down_weight (d, f)

Departures from the published model, each because the source's
``config.json`` has no key for it or because this is one chip's share
(the configuration file lists them under ``assumed``); each is marked
DEPARTURE at its line below.
"""
import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

VOCAB_BLOCK = 16384
SLIDING = "sliding_attention"

# (exponent, mantissa) bits a value is rounded to; None leaves it float32
BFLOAT16, FLOAT8 = (8, 7), (4, 3)
# the type a matrix product takes its inputs in, and what the stored K,V
# and the weight matrices are rounded to
PRECISIONS = {
    "float32": {"matmul": "float32", "kv": None, "weights": None},
    "stated": {"matmul": "bfloat16", "kv": BFLOAT16, "weights": None},
    "float8": {"matmul": "bfloat16", "kv": FLOAT8, "weights": FLOAT8},
}


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
    layer's K or V."""
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
                   precision=jax.lax.Precision.HIGHEST,
                   preferred_element_type=jnp.float32)


def _rms(x, gain, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + eps) * _f32(gain)


def _silu(x):
    return x / (1.0 + jnp.exp(-x))


def _sigmoid(x):
    return 1.0 / (1.0 + jnp.exp(-x))


def _swiglu(x, gateup, down, p):
    """``W2 (silu(W1g x) * (W1u x))``, ``gateup`` (2 f, d), gate first."""
    gate, up = jnp.split(_mm(x, gateup, p), 2, axis=-1)
    return _mm(_silu(gate) * up, down, p)


def _rope(x, base):
    """x (S, heads, dh) rotated at positions 0..S-1 over the whole ``dh``.
    DEPARTURE: feature pairs (2i, 2i+1) turn together (this repository's
    convention), where the published code pairs (i, i + dh/2): the same
    function up to a fixed permutation of each head's features, which
    random weights cannot tell apart."""
    s, _, d = x.shape
    inv = jnp.power(jnp.float32(base),
                    -jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv       # (S, dh/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     axis=-1).reshape(x.shape)


@partial(jax.jit, static_argnames=("heads", "kv_heads", "rotary",
                                   "precision"))
def _attention_inputs(a, w, eps, base, *, heads, kv_heads, rotary,
                      precision):
    """a (S, d) -> q (S, H, dh), k, v (S, KV, dh) as stored, gate (S, H dh).
    DEPARTURE: the gate is ``sigmoid(Wg a)``, one value a head FEATURE
    (width H dh), applied to the heads' output before ``Wo``: the config
    has no key for it, the model card says "gated".  DEPARTURE: q and k
    are RMS-normed over ``dh`` with one gain shared by the heads, BEFORE
    the rotation.  DEPARTURE: rotation on sliding layers only; a full
    layer has no positional encoding."""
    p = PRECISIONS[precision]
    dh = w["attn_qnorm_gamma"].shape[0]
    q, k, v, g = jnp.split(
        _mm(a, w["attn_qkvg_weight"], p),
        np.cumsum([heads * dh, kv_heads * dh, kv_heads * dh]).tolist(),
        axis=-1)
    q = _rms(q.reshape(-1, heads, dh), w["attn_qnorm_gamma"], eps)
    k = _rms(k.reshape(-1, kv_heads, dh), w["attn_knorm_gamma"], eps)
    if rotary:
        q, k = _rope(q, base), _rope(k, base)
    return (q, _rounded(k, p["kv"]),
            _rounded(v.reshape(-1, kv_heads, dh), p["kv"]), g)


@partial(jax.jit, static_argnames=("window", "precision"))
def _one_head(q, k, v, *, window, precision):
    """q, k, v (S, dh) of ONE query head and its K/V head -> (S, dh):
    causal, position i sees (i - window, i]."""
    p = PRECISIONS[precision]
    dt = jnp.dtype(p["matmul"])
    exact = dict(precision=jax.lax.Precision.HIGHEST,
                 preferred_element_type=jnp.float32)
    s, dh = q.shape
    i = jnp.arange(s)[:, None]
    j = jnp.arange(s)[None, :]
    keep = j <= i
    if window is not None:
        keep &= j > i - window
    logits = jnp.dot(q.astype(dt), k.astype(dt).T, **exact) / math.sqrt(dh)
    logits = jnp.where(keep, logits, -jnp.inf)
    e = jnp.exp(logits - jnp.max(logits, axis=-1, keepdims=True))
    probs = e / jnp.sum(e, axis=-1, keepdims=True)
    return jnp.dot(probs.astype(dt), v.astype(dt), **exact)


@partial(jax.jit, static_argnames=("precision",))
def _attention_output(h, o, g, w, eps, *, precision):
    """``h + RMS_2((o * sigmoid(g)) Wo^T)``: the gate, the output
    projection, the post-norm, the residual add."""
    mix = _mm(o * _sigmoid(g), w["attn_o_weight"], PRECISIONS[precision])
    return h + _rms(mix, w["ln2_gamma"], eps)


@partial(jax.jit, static_argnames=("k", "precision"))
def _router(m, w, *, k, route_scale, precision):
    """m (S, d) -> (scores (S, E) float32, the k experts each row picks,
    the margin between its k-th and (k+1)-th biased score).  The bias
    picks and never weighs."""
    s = _sigmoid(_mm(m, w["moe_router_weight"], PRECISIONS[precision]))
    biased = s + _f32(w["moe_router_bias"])
    order = jnp.argsort(-biased, axis=-1, stable=True)
    ranked = jnp.take_along_axis(biased, order, axis=-1)
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
def _swiglu_jit(m, gateup, down, *, precision):
    return _swiglu(m, gateup, down, PRECISIONS[precision])


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
    scores, picked, margin = _router(
        m, w, k=k, route_scale=float(cfg["route_scale"]),
        precision=precision)
    use = picked if chosen is None else jnp.asarray(chosen, jnp.int32)
    taken = jnp.take_along_axis(scores, use, axis=-1)            # (S, k)
    # normalised over ALL it picked, held here or not, then scaled
    gates = taken / (jnp.sum(taken, axis=-1, keepdims=True) + 1e-20) \
        * float(cfg["route_scale"])
    first, count = held
    out = jnp.zeros((m.shape[0], w["moe_experts_down_weight"].shape[2]),
                    jnp.float32)
    for j in range(count):              # experts held here, one by one
        # DEPARTURE (the share): an expert that is not held adds nothing
        weight = jnp.sum(jnp.where(use == first + j, gates, 0.0), axis=-1)
        out = out + _one_expert(
            m, w["moe_experts_gate_weight"][j],
            w["moe_experts_up_weight"][j], w["moe_experts_down_weight"][j],
            weight, precision=precision)
    return out, picked, margin


def forward_logits(weights, tokens, cfg, precision="float32",
                   experts_held=None, selections=None, routing=None):
    """(S, V) float32 logits of ``tokens`` (S,).

    ``cfg``: the source's keys ``layer_types``, ``num_dense_layers``,
    ``num_attention_heads``, ``num_key_value_heads``, ``num_experts`` (the
    router's width), ``num_experts_per_tok``, ``route_scale``,
    ``sliding_window``, ``rms_norm_eps``, ``rope_theta``.  ``precision``:
    a key of ``PRECISIONS``.  ``experts_held`` = (first, count): the share
    of every expert layer that ``weights`` holds (default: all).
    ``selections`` (S, expert layers, k) int32, if given, are the experts
    the SUM uses in place of the reference's own pick.  ``routing``, if a
    dict, receives ``picked`` (S, expert layers, k), the reference's own
    pick, and ``margin`` (S, expert layers), its k-th biased score less
    its (k+1)-th."""
    p = PRECISIONS[precision]
    kinds = list(cfg["layer_types"])
    heads, kv_heads = (int(cfg["num_attention_heads"]),
                       int(cfg["num_key_value_heads"]))
    eps, base = float(cfg["rms_norm_eps"]), float(cfg["rope_theta"])
    held = (0, int(cfg["num_experts"])) if experts_held is None \
        else tuple(int(x) for x in experts_held)
    tokens = np.asarray(tokens).astype(np.int32)
    embed = jnp.asarray(weights["embed_weight"])
    # DEPARTURE: sqrt(d) on the embedding is the one place ``mup_enabled``
    # acts in a forward pass
    h = _rounded(jnp.take(embed, jnp.asarray(tokens), axis=0),
                 p["weights"]) * math.sqrt(embed.shape[1])
    picked, margins = [], []
    for l, kind in enumerate(kinds):
        prefix = f"layer{l}_"
        w = {k[len(prefix):]: v for k, v in weights.items()
             if k.startswith(prefix)}
        # sandwich norms: a = RMS_1(h); h += RMS_2(Attn(a)); m = RMS_3(h);
        # h += RMS_4(FFN(m)).  DEPARTURE: every gain is 1 at the start
        # (the published depth-scaled initial gains are a training matter)
        q, k, v, g = _attention_inputs(
            _norm(h, w["ln1_gamma"], eps), w, eps, base, heads=heads,
            kv_heads=kv_heads, rotary=kind == SLIDING, precision=precision)
        window = int(cfg["sliding_window"]) if kind == SLIDING else None
        o = jnp.concatenate(
            [_one_head(q[:, i], k[:, i // (heads // kv_heads)],
                       v[:, i // (heads // kv_heads)], window=window,
                       precision=precision) for i in range(heads)], axis=-1)
        h = _attention_output(h, o, g, w, eps, precision=precision)
        m = _norm(h, w["ln3_gamma"], eps)
        if l < int(cfg["num_dense_layers"]):
            ffn = _swiglu_jit(m, w["mlp_gateup_weight"],
                              w["mlp_down_weight"], precision=precision)
        else:
            chosen = None if selections is None \
                else np.asarray(selections)[:, len(picked)]
            ffn, pick, margin = _routed(m, w, cfg, held, chosen, precision)
            picked.append(pick)
            margins.append(margin)
            # the shared expert is whole on every chip
            ffn = ffn + _swiglu_jit(m, w["moe_shared_gateup_weight"],
                                    w["moe_shared_down_weight"],
                                    precision=precision)
        h = h + _norm(ffn, w["ln4_gamma"], eps)
    if routing is not None and picked:
        routing["picked"] = np.stack([np.asarray(x) for x in picked], 1)
        routing["margin"] = np.stack([np.asarray(x) for x in margins], 1)
    h = _norm(h, weights["finalnorm_gamma"], eps)
    # DEPARTURE (the share): the head is a SLICE of the vocabulary's rows
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
    describe a built ``AfmoeForCausalLM``."""
    m = net.model
    attn = m.layers[0].attn
    moe = next(layer.ffn for layer in m.layers if not layer.dense)
    cfg = {"layer_types": [layer.kind for layer in m.layers],
           "num_dense_layers": sum(1 for layer in m.layers if layer.dense),
           "num_attention_heads": attn._h, "num_key_value_heads": attn._kv,
           "num_experts": m.num_experts,
           "num_experts_per_tok": moe._attrs["k"],
           "route_scale": moe._attrs["route_scale"],
           "sliding_window": m.sliding_window,
           "rms_norm_eps": m.final_norm._eps, "rope_theta": attn._base}
    return cfg, m.experts_held
