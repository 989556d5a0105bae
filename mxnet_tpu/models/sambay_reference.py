"""Plain reference of the SambaY decoder (Phi-4-mini-flash-reasoning,
arXiv:2507.06607; Differential Attention arXiv:2410.05258; Mamba-1
arXiv:2312.00752): ONE full-sequence forward in straightforward
``jax.numpy``, a sequential scan, no cache, no batching, no kernels.  It
imports nothing from ``mxnet_tpu.models`` or ``mxnet_tpu.ops``: what the
program is held to shares no code with it.
``chipbench/models/sambay_server.py`` carries a copy (the benchmark's
tree must stand alone).

``precision`` names WHAT IS ROUNDED, never how it is computed: every sum,
the residual stream, LayerNorm, softmax and the scan are float32 under
each (``PRECISIONS``).  ``"float32"`` is the mathematics (products at
``Precision.HIGHEST``); ``"stated"`` is what a served configuration
states (bfloat16 into every matrix product and in the K,V, float32
accumulation and SSM state); the two below it are the controls a limit
of ``correct`` is set against: the SSM state carried in bfloat16, and
weights and K,V in float8 (4 exponent and 3 mantissa bits, one scale a
tensor) besides.

``weights`` maps the names below to arrays of any float type (the served
bfloat16 weights are taken as they are and upcast one layer at a time,
the head in vocabulary blocks, so the reference fits beside the model).
A dense weight is ``(out, in)``: ``y = x W^T + b``.

    embed_weight (V, h)                         finalnorm_gamma/_beta (h,)
    layer{l}_ln1_gamma/_beta, layer{l}_ln2_gamma/_beta (h,)
    layer{l}_mlp_gateup_weight (2 f, h) [gate rows first], layer{l}_mlp_down_weight (h, f)
    mamba:  layer{l}_mamba_in_weight (2 Di, h) [x rows first, then z],
            _conv_weight (K, Di) [tap k multiplies x[t - (K-1) + k]], _conv_bias (Di,),
            _x_weight (R + 2 N, Di) [dt, B, C], _dt_weight (Di, R), _dt_bias (Di,),
            _a_log (N, Di), _d (Di,), _out_weight (h, Di)
    attention (window, full): layer{l}_attn_qkv_weight ((H + 2 KV) d, h) [q, k, v],
            _qkv_bias, _o_weight (h, H d), _o_bias, _lambda_q1/_k1/_q2/_k2 (d,),
            _subln_gamma (2 d,)
    cross:  layer{l}_attn_q_weight (H d, h), _q_bias, and _o_*, _lambda_*, _subln_gamma as above
    gmu:    layer{l}_gmu_in_weight (Di, h), layer{l}_gmu_out_weight (h, Di)

Departures from the published model, each because the source's
``config.json`` has no key for it (the configuration file lists them under
``assumed``): Mamba sizes ``d_state`` 16, ``d_conv`` 4, ``dt_rank`` =
hidden / 16, ``d_inner`` = 2 x hidden, conv bias on, in/out projection
bias off; biases on ``Wqkv`` and ``Wo``; query heads ``2j, 2j+1`` form
pair ``j`` and K/V heads ``2c, 2c+1`` K/V pair ``c`` = ``j // (H / KV)``;
the state and the conv weight are stored ``(N, Di)`` / ``(K, Di)`` (the
transpose of the textbook layout; same numbers).
"""
import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

VOCAB_BLOCK = 16384

# (exponent, mantissa) bits a value is rounded to; None leaves it float32
BFLOAT16, FLOAT8 = (8, 7), (4, 3)
# the type a matrix product takes its inputs in, and what the stored K,V,
# the carried SSM state and the weight matrices are rounded to
PRECISIONS = {
    "float32": {"matmul": "float32", "kv": None, "state": None,
                "weights": None},
    "stated": {"matmul": "bfloat16", "kv": BFLOAT16, "state": None,
               "weights": None},
    "state_bfloat16": {"matmul": "bfloat16", "kv": BFLOAT16,
                       "state": BFLOAT16, "weights": None},
    "float8": {"matmul": "bfloat16", "kv": FLOAT8, "state": BFLOAT16,
               "weights": FLOAT8},
}


def layer_kind(l, n):
    """``mamba`` | ``swa`` | ``full`` | ``cross`` | ``gmu`` for layer ``l``
    of ``n``: the self-decoder is layers 0..n/2+1 (Mamba on even layers,
    window attention on odd ones, ONE full-attention layer last), the
    cross-decoder alternates gated memory units and cross attention."""
    if l % 2 == 0:
        return "mamba" if l <= n // 2 else "gmu"
    if l < n // 2:
        return "swa"
    return "full" if l == n // 2 + 1 else "cross"


def lambda_init(l):
    return 0.8 - 0.6 * math.exp(-0.3 * l)


def _f32(w):
    return jnp.asarray(w).astype(jnp.float32)


def _rounded(x, bits):
    """``x`` in float32, holding only numbers a format of ``bits`` =
    (exponent, mantissa) holds.  ``lax.reduce_precision``, not a pair of
    casts: the TPU compiler drops a cast to bfloat16 and back (excess
    precision is allowed), and a control that rounds nothing proves
    nothing.  A format with a narrow exponent has one scale an array (its
    largest |value| on the format's largest), as a deployment in float8
    would: a scale a matrix, a block of the head's rows, a layer's K or V."""
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


def _layer_norm(x, gamma, beta, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * gamma + beta


def _silu(x):
    return x / (1.0 + jnp.exp(-x))


def _softplus(x):
    return jnp.logaddexp(x, 0.0)


def _mlp(x, w, p):
    gate, up = jnp.split(_mm(x, w["mlp_gateup_weight"], p), 2, axis=-1)
    return _mm(up * _silu(gate), w["mlp_down_weight"], p)


def _mamba(u, w, p):
    """u (S, h) -> (mixer output (S, h), memory y before the gate (S, Di))."""
    s = u.shape[0]
    x, z = jnp.split(_mm(u, w["mamba_in_weight"], p), 2, axis=-1)
    cw = _f32(w["mamba_conv_weight"])                       # (K, Di)
    k = cw.shape[0]
    xp = jnp.concatenate([jnp.zeros((k - 1, x.shape[1])), x], axis=0)
    x = _silu(sum(cw[j] * xp[j:j + s] for j in range(k))
              + _f32(w["mamba_conv_bias"]))
    a = -jnp.exp(_f32(w["mamba_a_log"]))                    # (N, Di)
    n = a.shape[0]
    dbc = _mm(x, w["mamba_x_weight"], p)
    r = dbc.shape[1] - 2 * n
    dt, bm, cm = dbc[:, :r], dbc[:, r:r + n], dbc[:, r + n:]
    delta = _softplus(_mm(dt, w["mamba_dt_weight"], p)
                      + _f32(w["mamba_dt_bias"]))           # (S, Di)

    def step(state, t):
        d_t, x_t, b_t, c_t = t
        state = _rounded(jnp.exp(d_t[None, :] * a) * state
                         + (d_t * x_t)[None, :] * b_t[:, None], p["state"])
        return state, jnp.sum(c_t[:, None] * state, axis=0)

    _, y = jax.lax.scan(step, jnp.zeros_like(a), (delta, x, bm, cm))
    y = y + _f32(w["mamba_d"]) * x
    return _mm(y * _silu(z), w["mamba_out_weight"], p), y


def _diff_attention(q, k, v, w, lam_init, keep, heads, kv_heads, p):
    """q (Sq, H d), k / v (Sk, KV d) as stored, keep (Sq, Sk) bool ->
    (Sq, H d).  Query heads 2j, 2j+1 are pair j; K/V heads 2c, 2c+1 are
    K/V pair c, read by the query pairs j with j // (H / KV) == c."""
    d = q.shape[1] // heads
    pairs, group = heads // 2, heads // kv_heads
    dt = jnp.dtype(p["matmul"])
    exact = dict(precision=jax.lax.Precision.HIGHEST,
                 preferred_element_type=jnp.float32)
    q = q.reshape(-1, pairs, 2, d).astype(dt)
    k = jnp.repeat(k.reshape(-1, kv_heads // 2, 2, d), group, axis=1)
    v = jnp.repeat(v.reshape(-1, kv_heads // 2, 2 * d), group, axis=1)
    lam = jnp.exp(jnp.sum(_f32(w["attn_lambda_q1"]) * _f32(w["attn_lambda_k1"]))) \
        - jnp.exp(jnp.sum(_f32(w["attn_lambda_q2"]) * _f32(w["attn_lambda_k2"]))) \
        + lam_init
    logits = jnp.einsum("qjid,kjid->jiqk", q, k.astype(dt), **exact) \
        / math.sqrt(d)
    logits = jnp.where(keep, logits, -jnp.inf)
    e = jnp.exp(logits - jnp.max(logits, axis=-1, keepdims=True))
    probs = e / jnp.sum(e, axis=-1, keepdims=True)          # (P, 2, Sq, Sk)
    o = jnp.einsum("jqk,kje->qje", (probs[:, 0] - lam * probs[:, 1])
                   .astype(dt), v.astype(dt), **exact)      # (Sq, P, 2 d)
    o = o / jnp.sqrt(jnp.mean(jnp.square(o), axis=-1, keepdims=True)
                     + 1e-5) * _f32(w["attn_subln_gamma"]) * (1.0 - lam_init)
    return _mm(o.reshape(o.shape[0], -1), w["attn_o_weight"], p) \
        + _f32(w["attn_o_bias"])


def _causal(s, window):
    i = jnp.arange(s)[:, None]
    j = jnp.arange(s)[None, :]
    keep = j <= i
    if window is not None:
        keep &= j > i - window             # position i sees (i - W, i]
    return keep


@partial(jax.jit, static_argnames=("kind", "heads", "kv_heads", "window",
                                   "precision"))
def _layer(h, w, memory, shared_k, shared_v, lam_init, eps, *, kind, heads,
           kv_heads, window, precision):
    """One decoder layer over the whole sequence h (S, hidden).  Returns
    (h, memory, shared_k, shared_v): a Mamba layer replaces ``memory`` with
    its own, the full-attention layer replaces the shared K, V."""
    p = PRECISIONS[precision]
    u = _layer_norm(h, _f32(w["ln1_gamma"]), _f32(w["ln1_beta"]), eps)
    s = h.shape[0]
    if kind == "mamba":
        mix, memory = _mamba(u, w, p)
    elif kind == "gmu":
        mix = _mm(_silu(_mm(u, w["gmu_in_weight"], p)) * memory,
                  w["gmu_out_weight"], p)
    elif kind == "cross":
        q = _mm(u, w["attn_q_weight"], p) + _f32(w["attn_q_bias"])
        mix = _diff_attention(q, shared_k, shared_v, w, lam_init,
                              _causal(s, None), heads, kv_heads, p)
    else:
        qkv = _mm(u, w["attn_qkv_weight"], p) + _f32(w["attn_qkv_bias"])
        d = qkv.shape[1] // (heads + 2 * kv_heads)
        q, k, v = jnp.split(qkv, [heads * d, (heads + kv_heads) * d], axis=-1)
        k, v = _rounded(k, p["kv"]), _rounded(v, p["kv"])    # as stored
        mix = _diff_attention(q, k, v, w, lam_init,
                              _causal(s, window if kind == "swa" else None),
                              heads, kv_heads, p)
        if kind == "full":
            shared_k, shared_v = k, v
    h = h + mix
    h = h + _mlp(_layer_norm(h, _f32(w["ln2_gamma"]), _f32(w["ln2_beta"]),
                             eps), w, p)
    return h, memory, shared_k, shared_v


@partial(jax.jit, static_argnames=("precision",))
def _head_block(h, e, *, precision):
    return _mm(h, e, PRECISIONS[precision])


def forward_logits(weights, tokens, cfg, precision="float32"):
    """(S, V) float32 logits of ``tokens`` (S,).  ``cfg``: the source's
    keys ``num_hidden_layers``, ``num_attention_heads``,
    ``num_key_value_heads``, ``sliding_window``, ``layer_norm_eps``;
    ``precision``: a key of ``PRECISIONS``."""
    p = PRECISIONS[precision]
    n = int(cfg["num_hidden_layers"])
    heads, kv_heads = (int(cfg["num_attention_heads"]),
                       int(cfg["num_key_value_heads"]))
    eps = float(cfg["layer_norm_eps"])
    tokens = np.asarray(tokens).astype(np.int32)
    embed = jnp.asarray(weights["embed_weight"])
    h = _rounded(jnp.take(embed, jnp.asarray(tokens), axis=0), p["weights"])
    hd = h.shape[1] // heads
    memory = jnp.zeros((len(tokens), 1), jnp.float32)
    sk = sv = jnp.zeros((len(tokens), kv_heads * hd), jnp.float32)
    for l in range(n):
        prefix = f"layer{l}_"
        w = {k[len(prefix):]: v for k, v in weights.items()
             if k.startswith(prefix)}
        h, memory, sk, sv = _layer(
            h, w, memory, sk, sv, lambda_init(l), eps,
            kind=layer_kind(l, n), heads=heads, kv_heads=kv_heads,
            window=int(cfg["sliding_window"]), precision=precision)
    h = _layer_norm(h, _f32(weights["finalnorm_gamma"]),
                    _f32(weights["finalnorm_beta"]), eps)
    v = embed.shape[0]
    out = np.empty((len(tokens), v), np.float32)
    for v0 in range(0, v, VOCAB_BLOCK):
        out[:, v0:v0 + VOCAB_BLOCK] = np.asarray(_head_block(
            h, embed[v0:v0 + VOCAB_BLOCK], precision=precision))
    return out


def weights_of(net, ctx=None):
    """{structural name: the parameter's array} of a Gluon net whose
    parameters end in the names above (the net's own prefix is cut)."""
    import re
    out = {}
    for name, p in net.collect_params().items():
        m = re.search(r"(embed_|finalnorm_|layer\d+_).*$", name)
        out[m.group(0)] = p.data(ctx)._data
    return out
