"""Phi-4-mini-flash-reasoning (SambaY) behind ``serving.Server``, through
the program's normal entry points: ``get_sambay(preset, ...)`` ->
``SambaYForCausalLM`` -> ``Server(net, buckets=..., max_new_tokens=...,
cache_dtype=...)``, the weights made ON THE DEVICE from the seed in the
type they are served in and installed through the parameters' load path
(as ``llama_server.py`` does).

Three things live here beside the builder: the PLAIN REFERENCE that
``correct`` holds a served request to (a copy of
``mxnet_tpu/models/sambay_reference.py``, so that the benchmark's tree
stands alone: straightforward ``jax.numpy``, every sum in float32,
sequential scan, no cache, no batching, no call into ``mxnet_tpu.models``
or ``mxnet_tpu.ops``; ``correct`` takes it at the precision the
configuration states, and the same code one precision lower is the
control the limit was set against; ``chipbench/tests`` holds the copy to
the original), and the two functions the per-layer metrics take
their operations and bytes from, ``decode_bytes_per_round`` and
``flops_per_token``.  No Pallas kernel is on this model's path (composed
XLA ops), so there is no kernel roofline function.
"""
import json
import math
import re
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

# -- the builder ---------------------------------------------------------------

_DT_RANGE = (1e-3, 1e-1)      # Mamba-1's own range of initial step sizes


def _rule(name, shape):
    """How a parameter is drawn, by the end of its name (the
    configuration file's ``assumed.weights``)."""
    if name.endswith("_gamma") or name.endswith("mamba_d"):
        return "ones"
    if name.endswith("_beta"):
        return "zeros"
    if name.endswith("mamba_a_log"):
        return "a_log"
    if name.endswith("mamba_dt_bias"):
        return "dt_bias"
    if name.endswith("_bias"):
        return 0.02
    if "_lambda_" in name:
        return 0.1
    if name.endswith("mamba_conv_weight"):
        return 1.0 / math.sqrt(shape[0])
    return math.sqrt(2.0 / (shape[0] + shape[1]))


@partial(jax.jit, static_argnames=("shape", "dtype", "rule"))
def _make(key, *, shape, dtype, rule):
    if rule == "ones":
        return jnp.ones(shape, dtype)
    if rule == "zeros":
        return jnp.zeros(shape, dtype)
    if rule == "a_log":       # A = -(1..N) on every channel
        return jnp.broadcast_to(jnp.log(jnp.arange(
            1, shape[0] + 1, dtype=jnp.float32))[:, None], shape) \
            .astype(dtype)
    if rule == "dt_bias":     # inverse softplus of log-spaced steps
        dt = jnp.exp(jnp.linspace(math.log(_DT_RANGE[0]),
                                  math.log(_DT_RANGE[1]), shape[0]))
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
    return (rule * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


def build_server(shapes, seed, device, max_queue):
    """(net, server, ctx).  ``shapes`` is the configuration file's
    content, or its ``rehearsal`` group."""
    import mxnet_tpu as mx
    from mxnet_tpu import nd
    from mxnet_tpu.models import SambaYForCausalLM, get_sambay
    from mxnet_tpu.serving import Server

    prog, serving, mamba = (shapes["program"], shapes["serving"],
                            shapes["mamba"])
    ctx = mx.Context(device.platform, 0)
    mx.random.seed(seed % (2 ** 31 - 1))
    net = SambaYForCausalLM(
        get_sambay(prog["preset"], vocab_size=int(shapes["vocab_size"]),
                   # the file's sizes are what runs, whatever the preset holds
                   units=int(shapes["hidden_size"]),
                   hidden=int(shapes["intermediate_size"]),
                   num_layers=int(shapes["num_hidden_layers"]),
                   num_heads=int(shapes["num_attention_heads"]),
                   num_kv_heads=int(shapes["num_key_value_heads"]),
                   sliding_window=int(shapes["sliding_window"]),
                   layer_norm_eps=float(shapes["layer_norm_eps"]),
                   **{k: int(v) for k, v in mamba.items()}))
    net.cast(serving["weight_dtype"])
    key = jax.device_put(jax.random.PRNGKey(seed % (2 ** 31 - 1)), device)
    for i, p in enumerate(net.collect_params().values()):
        p.grad_req = "null"
        shape = tuple(p.shape)
        value = _make(jax.random.fold_in(key, i), shape=shape,
                      dtype=serving["weight_dtype"],
                      rule=_rule(p.name, shape))
        p._load_init(nd.NDArray(value, ctx=ctx), ctx=ctx)
    srv = Server(net, buckets=[tuple(b) for b in serving["buckets"]],
                 max_new_tokens=int(serving["max_new_tokens"]), ctx=ctx,
                 cache_dtype=serving["cache_dtype"], max_queue=max_queue)
    return net, srv, ctx


def n_params(net):
    return sum(int(np.prod(p.shape)) for p in net.collect_params().values())


def shapes_of_run(slots):
    """The shapes a run of this configuration with ``slots`` slots used:
    its file's, or the file's ``rehearsal`` group; None for neither (a
    metric file has only ``obs`` to tell them apart by)."""
    import json
    import os
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "configs", "phi4_mini_flash.json")
    with open(path) as f:
        cfg = json.load(f)
    for shapes in (cfg, cfg["rehearsal"]):
        if sum(b[0] for b in shapes["serving"]["buckets"]) == slots:
            return shapes
    return None


# -- operations and bytes, from the shapes alone ------------------------------

def _kinds(shapes):
    n = int(shapes["num_hidden_layers"])
    return [layer_kind(l, n) for l in range(n)]


def param_counts(shapes):
    """{"matrices": parameters that sit in a matrix product (the tied
    embedding once, as the head), "vectors": gains, biases, lambdas, conv
    taps, A, D}: together ``n_params`` of the built net."""
    h, f = int(shapes["hidden_size"]), int(shapes["intermediate_size"])
    heads, kv = (int(shapes["num_attention_heads"]),
                 int(shapes["num_key_value_heads"]))
    d = h // heads
    m = shapes["mamba"]
    di, n, k, r = (int(m["d_inner"]), int(m["d_state"]), int(m["d_conv"]),
                   int(m["dt_rank"]))
    mat = int(shapes["vocab_size"]) * h
    vec = 2 * h                                        # final norm
    for kind in _kinds(shapes):
        mat += 3 * h * f                               # gate, up, down
        vec += 4 * h                                   # two norms
        if kind == "mamba":
            mat += 2 * di * h + (r + 2 * n) * di + di * r + h * di
            vec += k * di + di + di + n * di + di      # conv, dt_b, A, D
        elif kind == "gmu":
            mat += 2 * di * h
        else:
            qkv = heads * d if kind == "cross" else (heads + 2 * kv) * d
            mat += qkv * h + h * heads * d
            vec += qkv + h + 4 * d + 2 * d             # biases, lambdas, subln
    return {"matrices": mat, "vectors": vec}


def state_bytes_per_slot(shapes):
    """{kind: bytes one slot holds} at the configuration's one bucket."""
    h = int(shapes["hidden_size"])
    kv, d = (int(shapes["num_key_value_heads"]),
             h // int(shapes["num_attention_heads"]))
    m = shapes["mamba"]
    item = jnp.dtype(shapes["serving"]["cache_dtype"]).itemsize
    (_slots, prompt), = shapes["serving"]["buckets"]
    cache_len = prompt + int(shapes["serving"]["max_new_tokens"])
    window = min(int(shapes["sliding_window"]), cache_len)
    kinds = _kinds(shapes)
    return {
        "kv_full": kinds.count("full") * 2 * cache_len * kv * d * item,
        "kv_window": kinds.count("swa") * 2 * window * kv * d * item,
        "ssm": kinds.count("mamba") * int(m["d_state"])
        * int(m["d_inner"]) * 4,
        "conv": kinds.count("mamba") * (int(m["d_conv"]) - 1)
        * int(m["d_inner"]) * item,
    }


def decode_bytes_per_round(shapes, active, positions):
    """Bytes ONE decode round has to move, whatever implements it: every
    weight once, and for each of the ``active`` slots its LIVE state:
    the full layer's K,V up to the slot's ``positions`` written so far,
    read by that layer and by every cross layer; each window layer's K,V
    up to the window; the SSM and conv state read AND written.
    ``positions``: one number for every slot, or one a slot.  Dense pages
    read past a slot's offset are the program's waste, not work, and the
    one new K,V row a layer writes is left out."""
    pos = [float(positions)] * int(active) if np.ndim(positions) == 0 \
        else [float(p) for p in positions]
    per = state_bytes_per_slot(shapes)
    (_slots, prompt), = shapes["serving"]["buckets"]
    cache_len = prompt + int(shapes["serving"]["max_new_tokens"])
    window = min(int(shapes["sliding_window"]), cache_len)
    readers = 1 + _kinds(shapes).count("cross")
    weights = sum(param_counts(shapes).values()) \
        * jnp.dtype(shapes["serving"]["weight_dtype"]).itemsize
    state = sum(
        readers * per["kv_full"] * min(p, cache_len) / cache_len
        + per["kv_window"] * min(p, window) / window
        + 2 * (per["ssm"] + per["conv"]) for p in pos)
    return weights + state


def flops_per_token(shapes):
    """Operations one generated token needs in the matrix products: two a
    weight (the tied embedding once, as the head).  Attention's own
    products grow with the position and are left out, so the share of the
    peak this gives is a little low."""
    return 2 * param_counts(shapes)["matrices"]


# -- the plain reference (copy of mxnet_tpu/models/sambay_reference.py) -------

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


def full_forward_logits(net, tokens, ctx, precision="stated"):
    """The plain reference ``correct`` holds a served request to: one
    full-sequence forward of the served weights at the precision the
    configuration states, (S, V) float32.  It runs inside the harness's
    set-up phase ``first_calls_probe``: its seconds are printed, so that
    ``setup_s`` and ``compile_s`` can be read without it."""
    import time
    t0 = time.perf_counter()
    weights = {}
    for name, p in net.collect_params().items():
        m = re.search(r"(embed_|finalnorm_|layer\d+_).*$", name)
        weights[m.group(0)] = p.data(ctx)._data
    model = net.model
    cfg = {"num_hidden_layers": len(model.layers),
           "num_attention_heads": model._units // model.head_dim,
           "num_key_value_heads": model.num_kv_heads,
           "sliding_window": model.sliding_window,
           "layer_norm_eps": model.final_norm._eps}
    logits = forward_logits(weights, tokens, cfg, precision)
    print(json.dumps({"reference": precision, "tokens": len(tokens),
                      "reference_s": time.perf_counter() - t0}), flush=True)
    return logits
