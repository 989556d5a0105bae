"""Trinity-Large-Preview (AFMoE) as ONE CHIP OF AN 8-WAY EXPERT-PARALLEL
DEPLOYMENT behind ``serving.Server``, through the program's normal entry
points: ``get_afmoe(preset, ...)`` -> ``AfmoeForCausalLM`` ->
``Server(net, buckets=..., max_new_tokens=..., cache_dtype=...)``, the
weights made ON THE DEVICE from the seed in the type they are served in
and installed through the parameters' load path (as ``sambay_server.py``
does).  The configuration file's ``num_experts`` is how many experts are
HELD here; the router's width is its ``published.num_experts``.

Beside the builder: the PLAIN REFERENCE that ``correct`` holds a served
request to (a copy of ``mxnet_tpu/models/afmoe_reference.py``, so that
the benchmark's tree stands alone: straightforward ``jax.numpy``, every
sum in float32, a Python loop over heads and experts, no cache, no
batching, no call into ``mxnet_tpu.models`` or ``mxnet_tpu.ops``; it is
given the SAME share of the experts; ``chipbench/tests`` holds the copy
to the original), and the functions the per-layer metrics take their
operations and bytes from, ``decode_bytes_per_round`` and
``flops_per_token``.  The routed products are ``jax.lax.ragged_dot``,
which the TPU compiler turns into its own grouped-matmul custom call: no
kernel of this repository is on the path, so there is no kernel roofline
function and the step's roofline (``decode_hbm_share.moe``) is the bound.
"""
import importlib
import json
import math
import os
import re
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

CONFIG = "trinity_large_ep8.json"

# -- the builder ---------------------------------------------------------------


def _rule(name, shape):
    """How a parameter is drawn, by the end of its name (the
    configuration file's ``assumed``): norm gains 1, the selection bias
    N(0, 0.02^2), a matrix N(0, 2 / (fan_in + fan_out)) (an expert's
    matrices are stacked over the experts held)."""
    if name.endswith("_gamma"):
        return "ones"
    if name.endswith("router_bias"):
        return 0.02
    return math.sqrt(2.0 / (shape[-2] + shape[-1]))


@partial(jax.jit, static_argnames=("shape", "dtype", "rule"))
def _make(key, *, shape, dtype, rule):
    if rule == "ones":
        return jnp.ones(shape, dtype)
    return (rule * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


def draw_weights(net, seed, device, dtype):
    """Every parameter of ``net`` from ``seed``, on ``device``, in the
    order of ``collect_params``, ONE AT A TIME (a second draw into a
    built net must not hold two models)."""
    key = jax.device_put(jax.random.PRNGKey(seed % (2 ** 31 - 1)), device)
    for i, p in enumerate(net.collect_params().values()):
        yield _make(jax.random.fold_in(key, i), shape=tuple(p.shape),
                    dtype=dtype, rule=_rule(p.name, tuple(p.shape)))


def build_server(shapes, seed, device, max_queue):
    """(net, server, ctx).  ``shapes`` is the configuration file's
    content, or its ``rehearsal`` group."""
    import mxnet_tpu as mx
    from mxnet_tpu import nd
    from mxnet_tpu.models import AfmoeForCausalLM, get_afmoe
    from mxnet_tpu.serving import Server

    prog, serving = shapes["program"], shapes["serving"]
    ctx = mx.Context(device.platform, 0)
    mx.random.seed(seed % (2 ** 31 - 1))
    net = AfmoeForCausalLM(get_afmoe(
        prog["preset"], vocab_size=int(shapes["vocab_size"]),
        # the file's sizes are what runs, whatever the preset holds
        units=int(shapes["hidden_size"]),
        hidden=int(shapes["intermediate_size"]),
        moe_hidden=int(shapes["moe_intermediate_size"]),
        layer_types=tuple(shapes["layer_types"]),
        num_dense_layers=int(shapes["num_dense_layers"]),
        num_heads=int(shapes["num_attention_heads"]),
        num_kv_heads=int(shapes["num_key_value_heads"]),
        head_dim=int(shapes["head_dim"]),
        # the router keeps its published width; ``num_experts`` are held
        num_experts=int(shapes["published"]["num_experts"]),
        experts_held=(int(prog["first_expert_held"]),
                      int(shapes["num_experts"])),
        top_k=int(shapes["num_experts_per_tok"]),
        route_scale=float(shapes["route_scale"]),
        sliding_window=int(shapes["sliding_window"]),
        rms_norm_eps=float(shapes["rms_norm_eps"]),
        rope_base=float(shapes["rope_theta"])))
    net.cast(serving["weight_dtype"])
    values = draw_weights(net, seed, device, serving["weight_dtype"])
    for p, value in zip(net.collect_params().values(), values):
        p.grad_req = "null"
        p._load_init(nd.NDArray(value, ctx=ctx), ctx=ctx)
    srv = Server(net, buckets=[tuple(b) for b in serving["buckets"]],
                 max_new_tokens=int(serving["max_new_tokens"]), ctx=ctx,
                 cache_dtype=serving["cache_dtype"], max_queue=max_queue)
    _shared().CALLS = srv.statistics_listener = Calls(
        [name for name, _doc in net.statistics])
    return net, srv, ctx


def _shared():
    """The ONE copy of this module that a process imports by name.  The
    harness loads this file anew for the builder and for every reader;
    what the run's listener logged is kept where all of them find it."""
    return importlib.import_module("chipbench.models.afmoe_server")


CALLS = None        # of ``_shared()``: the listener of the last server built


class Calls:
    """``Server.statistics_listener`` of a run (docs/serving.md, "Model
    statistics"): what the served programs counted, call by call, with
    the time the call's tokens were read; and, until ``served_picks``
    takes them, what the rows of each call picked."""

    def __init__(self, names):
        self.names = list(names)
        self.log = []       # (read at, kind, columns with a request, counts)
        self.arm()

    def arm(self):
        """Keep the picks of the request that is served next, alone."""
        self.picks = []     # (kind, columns, rows); None: kept no longer
        self.served = None  # what ``served_picks`` made of them

    def __call__(self, kind, columns, counts, rows):
        self.log.append((time.perf_counter(), kind, len(columns),
                         [float(c) for c in counts]))
        if self.picks is not None:
            self.picks.append((kind, list(columns),
                               np.asarray(rows).astype(np.int32)))


def decode_calls(obs):
    """What the DECODE dispatches whose tokens were read inside the run's
    window counted, summed: {counter: sum} and ``dispatches``, ``rows``
    (slots that held a request, summed over them); None for a run whose
    program has no such counts (the parent of the PR that added them).
    Prefills, the lone-row probe, the ramp and the drain are outside: an
    expert layer's load in a decode round is a property of a FULL round.
    A slot that holds no request is routed like any other (the decoder
    contract names no idle rows): ``rows`` over ``dispatches`` says how
    many there were."""
    calls = _shared().CALLS
    t0, t1 = obs["window"]
    inside = [(n, c) for t, kind, n, c in (calls.log if calls else ())
              if kind == "decode" and t0 <= t <= t1]
    if not inside:
        return None
    out = {name: sum(c[i] for _n, c in inside)
           for i, name in enumerate(calls.names)}
    out["dispatches"] = len(inside)
    out["rows"] = sum(n for n, _c in inside)
    return out


def per_held_expert_call(obs, counter):
    """The program's count ``counter`` over expert-layer calls x the
    experts held: its mean for ONE held expert in ONE call of an expert
    layer, over the window's decode dispatches; None without them."""
    got = decode_calls(obs)
    shapes = shapes_of_run(obs["slots"])
    if got is None or shapes is None:
        return None
    return got[counter] / (got["mxtpu_moe_layer_calls_total"]
                           * int(shapes["num_experts"]))


def n_params(net):
    return sum(int(np.prod(p.shape)) for p in net.collect_params().values())


def _shapes_where(holds):
    """The configuration file's content or its ``rehearsal`` group,
    whichever ``holds``; None for neither."""
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "configs", CONFIG)
    with open(path) as f:
        cfg = json.load(f)
    return next((s for s in (cfg, cfg["rehearsal"]) if holds(s)), None)


def shapes_of_run(slots):
    """The shapes a run of this configuration with ``slots`` slots used
    (a metric file has only ``obs`` to tell them apart by)."""
    return _shapes_where(
        lambda s: sum(b[0] for b in s["serving"]["buckets"]) == slots)


# -- operations and bytes, from the shapes alone ------------------------------

def _sizes(shapes):
    d, f, fe = (int(shapes["hidden_size"]), int(shapes["intermediate_size"]),
                int(shapes["moe_intermediate_size"]))
    heads, kv, dh = (int(shapes["num_attention_heads"]),
                     int(shapes["num_key_value_heads"]),
                     int(shapes["head_dim"]))
    layers = len(shapes["layer_types"])
    dense = int(shapes["num_dense_layers"])
    return d, f, fe, heads, kv, dh, layers, dense


def param_counts(shapes):
    """Parameters by what a decode round does with them; together
    ``n_params`` of the built net.  ``embedding``: a row a token is
    looked up; ``experts``: the routed experts HELD, read only where
    touched; ``matrices``: every other matrix (attention with its gate,
    the dense layer's MLP, shared experts, routers, the head), applied to
    every token; ``vectors``: norm gains and selection biases."""
    d, f, fe, heads, kv, dh, layers, dense = _sizes(shapes)
    held, routed = int(shapes["num_experts"]), \
        int(shapes["published"]["num_experts"])
    vocab = int(shapes["vocab_size"])
    attention = (2 * heads + 2 * kv) * dh * d + heads * dh * d
    return {
        "embedding": vocab * d,
        "experts": (layers - dense) * held * 3 * d * fe,
        "matrices": vocab * d + layers * attention + dense * 3 * d * f
        + (layers - dense) * (3 * d * fe + routed * d),
        "vectors": d + layers * (4 * d + 2 * dh) + (layers - dense) * routed,
    }


def _cache_lens(shapes):
    """(positions a full layer's page holds, positions a window's)."""
    (_slots, prompt), = shapes["serving"]["buckets"]
    cache_len = prompt + int(shapes["serving"]["max_new_tokens"])
    return cache_len, min(int(shapes["sliding_window"]), cache_len)


def state_bytes_per_slot(shapes):
    """{kind: bytes one slot holds} at the configuration's one bucket."""
    _d, _f, _fe, _heads, kv, dh, _layers, _dense = _sizes(shapes)
    item = jnp.dtype(shapes["serving"]["cache_dtype"]).itemsize
    cache_len, window = _cache_lens(shapes)
    kinds = list(shapes["layer_types"])
    return {
        "kv_full": kinds.count("full_attention") * 2 * cache_len * kv * dh
        * item,
        "kv_window": kinds.count("sliding_attention") * 2 * window * kv * dh
        * item,
    }


def decode_bytes_per_round(shapes, active, positions, experts_touched):
    """Bytes ONE decode round has to move, whatever implements it: every
    matrix that is applied to every token once (attention, the dense
    layer, shared experts, routers, the head) and the vectors, ONE
    embedding row a slot that decodes, the three matrices of each routed
    expert TOUCHED (``experts_touched``: held experts with at least one
    token, summed over the expert layers), and the LIVE K,V of the
    ``active`` slots: each layer's page up to the slot's ``positions``
    written so far, a window's up to the window.  ``positions``: one
    number for every slot, or one a slot.  Dense pages read past a slot's
    offset and experts that no token picked are the program's waste, not
    work, and the one new K,V row a layer writes is left out."""
    pos = [float(positions)] * int(active) if np.ndim(positions) == 0 \
        else [float(p) for p in positions]
    d, _f, fe, _heads, _kv, _dh, _layers, _dense = _sizes(shapes)
    item = jnp.dtype(shapes["serving"]["weight_dtype"]).itemsize
    counts = param_counts(shapes)
    per = state_bytes_per_slot(shapes)
    cache_len, window = _cache_lens(shapes)
    weights = (counts["matrices"] + counts["vectors"] + len(pos) * d
               + float(experts_touched) * 3 * d * fe) * item
    state = sum(per["kv_full"] * min(p, cache_len) / cache_len
                + per["kv_window"] * min(p, window) / window for p in pos)
    return weights + state


def flops_per_token(shapes):
    """Operations one generated token needs in the matrix products THIS
    CHIP applies to it: two a weight of ``matrices`` (the embedding is a
    lookup, the untied head a product) and two a weight of the routed
    experts it is expected to reach here: ``num_experts_per_tok`` x held /
    routed of them an expert layer.  Attention's own products grow with
    the position and are left out, so the share of the peak this gives is
    a little low."""
    d, _f, fe, _heads, _kv, _dh, layers, dense = _sizes(shapes)
    counts = param_counts(shapes)
    reached = int(shapes["num_experts_per_tok"]) * int(shapes["num_experts"]) \
        / int(shapes["published"]["num_experts"])
    return 2 * (counts["matrices"]
                + (layers - dense) * reached * 3 * d * fe)


# -- the plain reference (copy of mxnet_tpu/models/afmoe_reference.py) --------

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


def _weights_and_config(net, ctx):
    """What ``forward_logits`` takes, from the built net: its weights by
    structural name, ``cfg`` and the share of the experts it holds."""
    weights = {}
    for name, p in net.collect_params().items():
        m = re.search(r"(embed_|finalnorm_|head_|layer\d+_).*$", name)
        weights[m.group(0)] = p.data(ctx)._data
    model = net.model
    attn = model.layers[0].attn
    moe = next(layer.ffn for layer in model.layers if not layer.dense)
    cfg = {"layer_types": [layer.kind for layer in model.layers],
           "num_dense_layers": sum(1 for layer in model.layers
                                   if layer.dense),
           "num_attention_heads": attn._h, "num_key_value_heads": attn._kv,
           "num_experts": model.num_experts,
           "num_experts_per_tok": moe._attrs["k"],
           "route_scale": moe._attrs["route_scale"],
           "sliding_window": model.sliding_window,
           "rms_norm_eps": model.final_norm._eps, "rope_theta": attn._base}
    return weights, cfg, model.experts_held


def served_picks(net, n):
    """The experts the SERVED programs picked at the first ``n`` positions
    of the one request served since the listener was armed (the probe,
    alone, one decode step a dispatch): its prefill's rows, then its row
    of every decode dispatch, from what the programs hand back behind
    their counts: (n, expert layers, k) int32.  The first call puts them
    together, and the listener keeps no further picks (``arm`` again for
    another probe)."""
    calls = _shared().CALLS
    if calls.picks is not None:
        kept, calls.picks = calls.picks, None
        k = next(layer.ffn for layer in net.model.layers
                 if not layer.dense)._attrs["k"]
        width = k * sum(1 for layer in net.model.layers if not layer.dense)
        alone = [kind for kind, _c, _r in kept].count("prefill") == 1 \
            and all(len(columns) == 1 for _k, columns, _r in kept)
        found = []
        for kind, columns, rows in kept if alone else ():
            rows = rows.reshape(-1, width)
            found += list(rows[(rows >= 0).all(axis=1)]) \
                if kind == "prefill" else [rows[columns[0]]]
        calls.served = np.stack(found).reshape(len(found), -1, k) \
            if found else None
    if calls.served is None or len(calls.served) != n:
        raise RuntimeError(
            f"no picks of {n} positions were kept: the probe has to be "
            "served alone, by the Server that build_server made last")
    return calls.served


def full_forward_logits(net, tokens, ctx, precision="stated"):
    """The plain reference ``correct`` holds a served request to: one
    full-sequence forward of the served weights at the precision the
    configuration states, with the SAME share of the experts, (S, V)
    float32.

    THE RULE (PERF.md section 6, PR 33; fixed before the first chip run).
    Picking 4 of 256 is a discrete choice: two computations that round at
    bfloat16 in another order pick differently wherever a row's 4th and
    5th biased scores lie within that noise, and one other expert moves
    the row's logits by percents, as much as a precision lower does.  So
    the reference's SUM takes the experts the SERVED programs picked
    (``served_picks``: the programs hand them back behind their counts;
    the gates are the reference's own scores of them), the harness
    holds every served token to those logits by its
    ``gap_share``, and the picks themselves are held to the reference's
    own: where the two differ, the reference's margin between its 4th and
    5th biased score has to be under ``probe.route_margin_tau`` of the
    configuration file, and such excused decisions may be at most
    ``probe.excused_share_cap`` of all.  ONE unexcused difference at ANY
    position, prompt or generated, or a share past the cap, and EVERY row
    comes back NEGATED: each served token, near its row's top, is then
    near its bottom, and the harness's own check, which reads the rows of
    the generated tokens alone, refuses the run.  The counts are printed.

    It runs inside the harness's set-up phase ``first_calls_probe``: its
    seconds are printed, so that ``setup_s`` and ``compile_s`` can be read
    without it."""
    t0 = time.perf_counter()
    weights, cfg, held = _weights_and_config(net, ctx)
    limits = _shapes_where(
        lambda s: int(s["hidden_size"]) == net.model._units)["probe"]
    tau, cap = (float(limits["route_margin_tau"]),
                float(limits["excused_share_cap"]))
    chosen = served_picks(net, len(tokens))
    routing = {}
    logits = forward_logits(weights, tokens, cfg, precision, held,
                            selections=chosen, routing=routing)
    differ = (np.sort(chosen, -1) != np.sort(routing["picked"], -1)).any(-1)
    excused = differ & (routing["margin"] < tau)
    refused = differ & ~excused
    share = float(excused.mean())
    sound = not refused.any() and share <= cap
    if not sound:
        logits *= -1.0
    print(json.dumps({
        "reference": precision, "tokens": len(tokens),
        "experts_held": list(held), "decisions": int(differ.size),
        "picks_differ": int(differ.sum()), "excused": int(excused.sum()),
        "refused": int(refused.sum()), "excused_share": share,
        "rows_negated": 0 if sound else len(logits),
        "largest_margin_where_they_differ": float(
            routing["margin"][differ].max()) if differ.any() else 0.0,
        "route_margin_tau": tau, "excused_share_cap": cap,
        "reference_s": time.perf_counter() - t0}), flush=True)
    return logits
