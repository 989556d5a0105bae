"""Plain reference of the Pangu Ultra MoE decoder (openPangu-Ultra-MoE-718B,
``model_type`` ``pangu_ultra_moe``): ONE full-sequence forward in
straightforward ``jax.numpy``, a Python loop over heads and over experts,
the EXPANDED definition of latent attention only (it never absorbs an
up-projection), no cache, no batching, no kernels, no sorting of tokens.
It imports nothing from ``mxnet_tpu.models`` or ``mxnet_tpu.ops``: what
the program is held to shares no code with it.
``chipbench/models/pangu_moe_server.py`` carries a copy (the benchmark's
tree must stand alone).

``precision`` names WHAT IS ROUNDED, never how it is computed: every sum,
the residual stream, the norms, softmax, the router's scores and the
gates are float32 under each (``PRECISIONS``).  ``"float32"`` is the
mathematics (products at ``Precision.HIGHEST``); ``"stated"`` is what a
served configuration states (bfloat16 into every matrix product and in
the latent rows a page keeps, float32 accumulation); ``"float8"`` is the
control a limit of ``correct`` is set against: weights and latent rows in
float8 (4 exponent and 3 mantissa bits, one scale a tensor) besides;
``"softmax_bfloat16"`` is a second control: the stated precision with
attention's scores and probabilities held in bfloat16.

``weights`` maps the names below to arrays of any float type.  A dense
weight is ``(out, in)``: ``y = x W^T``; an expert's is ``(in, out)``,
stacked over the experts HELD (``y = x W_e``).  No bias anywhere.
``H`` heads of ``dn`` (no position) + ``dr`` (rotated) query and key
features and ``dv`` value features; ``rq``, ``rkv`` the ranks of the
query's and of the keys-and-values' latent.

    embed_weight (V, d)       head_weight (V, d)       finalnorm_gamma (d,)
    layer{l}_ln1_gamma .. layer{l}_ln4_gamma (d,)
    layer{l}_attn_dq_weight (rq, d), _attn_qnorm_gamma (rq,),
            _attn_uq_weight (H (dn + dr), rq) [head i: dn rows, then dr],
            _attn_dkv_weight (rkv + dr, d) [latent rows, then the shared key's],
            _attn_kvnorm_gamma (rkv,),
            _attn_ukv_weight (H (dn + dv), rkv) [head i: W_uk,i (dn rows), then W_uv,i],
            _attn_o_weight (d, H dv)
    dense:  layer{l}_mlp_gateup_weight (2 f, d) [gate rows first], _mlp_down_weight (d, f)
    expert: layer{l}_moe_router_weight (E, d),
            _moe_experts_gate_weight, _moe_experts_up_weight (N, d, f),
            _moe_experts_down_weight (N, f, d),
            _moe_shared_gateup_weight (2 f, d), _moe_shared_down_weight (d, f)

What the source's ``config.json`` has no key for is the convention of the
family it follows (DeepSeek-V2 section 2.1, DeepSeek-V3 for the router)
or a matter of this being one chip's share; the configuration file lists
each under ``assumed`` and each is marked ASSUMED at its line below.
"""
import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

VOCAB_BLOCK = 16384

# (exponent, mantissa) bits a value is rounded to; None leaves it float32
BFLOAT16, FLOAT8 = (8, 7), (4, 3)
# the type a matrix product takes its inputs in, and what the latent rows
# a page keeps, the weight matrices and attention's scores and
# probabilities are rounded to
PRECISIONS = {
    "float32": {"matmul": "float32", "kv": None, "weights": None,
                "softmax": None},
    "stated": {"matmul": "bfloat16", "kv": BFLOAT16, "weights": None,
               "softmax": None},
    "float8": {"matmul": "bfloat16", "kv": FLOAT8, "weights": FLOAT8,
               "softmax": None},
    "softmax_bfloat16": {"matmul": "bfloat16", "kv": BFLOAT16,
                         "weights": None, "softmax": BFLOAT16},
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
    layer's latent rows."""
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


def _swiglu(x, gateup, down, p):
    """``W2 (silu(W1g x) * (W1u x))``, ``gateup`` (2 f, d), gate first."""
    gate, up = jnp.split(_mm(x, gateup, p), 2, axis=-1)
    return _mm(_silu(gate) * up, down, p)


def _rope(x, base):
    """x (S, heads, dr) rotated at positions 0..S-1 over the whole ``dr``,
    no scaling of the angles.  ASSUMED: feature pairs (2i, 2i+1) turn
    together (this repository's convention), where the family's code
    pairs (i, i + dr/2): the same function up to a fixed permutation of
    the rotated features, which random weights cannot tell apart."""
    s, _, d = x.shape
    inv = jnp.power(jnp.float32(base),
                    -jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv       # (S, dr/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     axis=-1).reshape(x.shape)


@partial(jax.jit, static_argnames=("heads", "nope", "precision"))
def _attention_inputs(a, w, eps, base, *, heads, nope, precision):
    """a (S, d) -> q_n (S, H, dn), q_r (S, H, dr) rotated, and what a
    position leaves behind: c (S, rkv) normed and k_r (S, dr) rotated,
    both as a page keeps them.  ASSUMED: an RMS norm on the query's
    latent ``c_q`` and on ``c_kv``, none on ``k_r``; ``k_r`` is ONE
    vector a position, shared by all heads."""
    p = PRECISIONS[precision]
    rkv = w["attn_kvnorm_gamma"].shape[0]
    c_q = _rms(_mm(a, w["attn_dq_weight"], p), w["attn_qnorm_gamma"], eps)
    q = _mm(c_q, w["attn_uq_weight"], p).reshape(a.shape[0], heads, -1)
    down = _mm(a, w["attn_dkv_weight"], p)
    c = _rms(down[:, :rkv], w["attn_kvnorm_gamma"], eps)
    k_r = _rope(down[:, None, rkv:], base)[:, 0]
    return (q[..., :nope], _rope(q[..., nope:], base),
            _rounded(c, p["kv"]), _rounded(k_r, p["kv"]))


@partial(jax.jit, static_argnames=("precision",))
def _one_head(q_n, q_r, c, k_r, w_uk, w_uv, *, precision):
    """ONE head, the definition: keys ``[W_uk c ; k_r]`` and values ``W_uv
    c`` EXPANDED for every position, causal softmax in float32.  q_n (S,
    dn), q_r (S, dr), c (S, rkv), k_r (S, dr), w_uk (dn, rkv), w_uv (dv,
    rkv) -> (S, dv).  ASSUMED: the scores are scaled by ``(dn + dr)^-0.5``,
    the width of a key."""
    p = PRECISIONS[precision]
    dt = jnp.dtype(p["matmul"])
    k_n, v = _mm(c, w_uk, p), _mm(c, w_uv, p)
    s = q_n.shape[0]
    logits = (jnp.dot(q_n.astype(dt), k_n.astype(dt).T, **EXACT)
              + jnp.dot(q_r.astype(dt), k_r.astype(dt).T, **EXACT)) \
        / math.sqrt(q_n.shape[1] + q_r.shape[1])
    keep = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    logits = _rounded(jnp.where(keep, logits, -jnp.inf), p["softmax"])
    e = _rounded(jnp.exp(logits - jnp.max(logits, axis=-1, keepdims=True)),
                 p["softmax"])
    probs = _rounded(e / jnp.sum(e, axis=-1, keepdims=True), p["softmax"])
    return jnp.dot(probs.astype(dt), v.astype(dt), **EXACT)


@partial(jax.jit, static_argnames=("precision",))
def _attention_output(h, o, w, eps, *, precision):
    """``h + RMS_2(o Wo^T)``: the output projection, the post-norm, the
    residual add."""
    mix = _mm(o, w["attn_o_weight"], PRECISIONS[precision])
    return h + _rms(mix, w["ln2_gamma"], eps)


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
                   experts_held=None, selections=None, routing=None):
    """(S, V) float32 logits of ``tokens`` (S,).

    ``cfg``: the source's keys ``num_hidden_layers``,
    ``first_k_dense_replace``, ``num_attention_heads``,
    ``qk_nope_head_dim``, ``v_head_dim``, ``n_routed_experts`` (the
    router's width), ``num_experts_per_tok``, ``routed_scaling_factor``,
    ``rms_norm_eps``, ``rope_theta`` (the ranks and ``qk_rope_head_dim``
    are the weights' shapes).  ``precision``: a key of ``PRECISIONS``.
    ``experts_held`` = (first, count): the share of every expert layer
    that ``weights`` holds (default: all).  ``selections`` (S, expert
    layers, k) int32, if given, are the experts the SUM uses in place of
    the reference's own pick.  ``routing``, if a dict, receives ``picked``
    (S, expert layers, k), the reference's own pick, and ``margin`` (S,
    expert layers), its k-th score less its (k+1)-th."""
    p = PRECISIONS[precision]
    heads = int(cfg["num_attention_heads"])
    nope, dv = int(cfg["qk_nope_head_dim"]), int(cfg["v_head_dim"])
    eps, base = float(cfg["rms_norm_eps"]), float(cfg["rope_theta"])
    held = (0, int(cfg["n_routed_experts"])) if experts_held is None \
        else tuple(int(x) for x in experts_held)
    tokens = np.asarray(tokens).astype(np.int32)
    # ASSUMED: the embedding is not scaled (the config has no key for it)
    h = _rounded(jnp.take(jnp.asarray(weights["embed_weight"]),
                          jnp.asarray(tokens), axis=0), p["weights"])
    picked, margins = [], []
    # ASSUMED: the next-token-prediction module (``num_nextn_predict_layers``)
    # is a training objective and no part of the main model's logits
    for l in range(int(cfg["num_hidden_layers"])):
        prefix = f"layer{l}_"
        w = {k[len(prefix):]: v for k, v in weights.items()
             if k.startswith(prefix)}
        # ASSUMED (``sandwich_norm``): a = RMS_1(h); h += RMS_2(MLA(a));
        # m = RMS_3(h); h += RMS_4(FFN(m)); every gain 1 at the start
        q_n, q_r, c, k_r = _attention_inputs(
            _norm(h, w["ln1_gamma"], eps), w, eps, base, heads=heads,
            nope=nope, precision=precision)
        ukv = jnp.asarray(w["attn_ukv_weight"]).reshape(heads, nope + dv, -1)
        o = jnp.concatenate(
            [_one_head(q_n[:, i], q_r[:, i], c, k_r, ukv[i, :nope],
                       ukv[i, nope:], precision=precision)
             for i in range(heads)], axis=-1)
        h = _attention_output(h, o, w, eps, precision=precision)
        m = _norm(h, w["ln3_gamma"], eps)
        if l < int(cfg["first_k_dense_replace"]):
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
    describe a built ``PanguMoeForCausalLM``."""
    m = net.model
    attn = m.layers[0].attn
    moe = next(layer.ffn for layer in m.layers if not layer.dense)
    cfg = {"num_hidden_layers": len(m.layers),
           "first_k_dense_replace": sum(1 for layer in m.layers
                                        if layer.dense),
           "num_attention_heads": attn._h, "qk_nope_head_dim": attn._dn,
           "v_head_dim": attn._dv, "n_routed_experts": m.num_experts,
           "num_experts_per_tok": moe._attrs["k"],
           "routed_scaling_factor": moe._attrs["route_scale"],
           "rms_norm_eps": m.final_norm._eps, "rope_theta": attn._base}
    return cfg, m.experts_held
