"""openPangu-Ultra-MoE-718B as ONE CHIP OF A 16-WAY EXPERT-PARALLEL
DEPLOYMENT behind ``serving.Server``, through the program's normal entry
points: ``get_pangu_moe(preset, ...)`` -> ``PanguMoeForCausalLM`` ->
``Server(net, buckets=..., max_new_tokens=..., cache_dtype=...)``, the
weights made ON THE DEVICE from the seed in the type they are served in
(``afmoe_server.draw_weights``: the same rule) and installed through the
parameters' load path.  The configuration file's ``n_routed_experts`` is
how many experts are HELD here; the router's width is its
``published.n_routed_experts``.  The run's listener is
``afmoe_server.Calls`` (one expert layer, one listener: what the served
programs count rides out behind every dispatch's tokens, the latent
attention's counts beside the expert layers').

Beside the builder: the PLAIN REFERENCE that ``correct`` holds a served
request to (a copy of ``mxnet_tpu/models/pangu_moe_reference.py``, so
that the benchmark's tree stands alone: straightforward ``jax.numpy``,
every sum in float32, a Python loop over heads and experts, the EXPANDED
definition of latent attention only, no cache, no call into
``mxnet_tpu.models`` or ``mxnet_tpu.ops``; ``chipbench/tests`` holds the
copy to the original), and the functions the per-layer metrics take
their operations and bytes from: ``decode_bytes_per_round``,
``decode_flops_per_round`` and ``flops_per_token``.  The absorbed decode
is composed XLA ops and the routed products are ``jax.lax.ragged_dot``:
no kernel of this repository is on the path, so there is no kernel
roofline function and the step's two rooflines (``decode_hbm_share.mla``,
``decode_mxu_share.mla``) are the bound.
"""
import json
import math
import os
import re
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.models import afmoe_server as routed

CONFIG = "pangu_ultra_moe_ep16.json"

# -- the builder ---------------------------------------------------------------


def build_server(shapes, seed, device, max_queue):
    """(net, server, ctx).  ``shapes`` is the configuration file's
    content, or its ``rehearsal`` group."""
    import mxnet_tpu as mx
    from mxnet_tpu import nd
    from mxnet_tpu.models import PanguMoeForCausalLM, get_pangu_moe
    from mxnet_tpu.serving import Server

    prog, serving = shapes["program"], shapes["serving"]
    ctx = mx.Context(device.platform, 0)
    mx.random.seed(seed % (2 ** 31 - 1))
    net = PanguMoeForCausalLM(get_pangu_moe(
        prog["preset"], vocab_size=int(shapes["vocab_size"]),
        # the file's sizes are what runs, whatever the preset holds
        units=int(shapes["hidden_size"]),
        hidden=int(shapes["intermediate_size"]),
        moe_hidden=int(shapes["moe_intermediate_size"]),
        num_layers=int(shapes["num_hidden_layers"]),
        num_dense_layers=int(shapes["first_k_dense_replace"]),
        num_heads=int(shapes["num_attention_heads"]),
        q_rank=int(shapes["q_lora_rank"]),
        kv_rank=int(shapes["kv_lora_rank"]),
        nope_dim=int(shapes["qk_nope_head_dim"]),
        rope_dim=int(shapes["qk_rope_head_dim"]),
        v_dim=int(shapes["v_head_dim"]),
        # the router keeps its published width; ``n_routed_experts`` are held
        num_experts=int(shapes["published"]["n_routed_experts"]),
        experts_held=(int(prog["first_expert_held"]),
                      int(shapes["n_routed_experts"])),
        top_k=int(shapes["num_experts_per_tok"]),
        route_scale=float(shapes["routed_scaling_factor"]),
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
    routed.CALLS = srv.statistics_listener = routed.Calls(
        [name for name, _doc in net.statistics])
    return net, srv, ctx


n_params = routed.n_params
draw_weights = routed.draw_weights


def decode_calls(obs):
    """``afmoe_server.decode_calls`` of a run of THIS configuration: what
    the decode dispatches read inside the window counted, summed; None
    for a run of another configuration or a program without the latent
    attention's counts."""
    got = routed.decode_calls(obs) \
        if shapes_of_run(obs.get("slots")) is not None else None
    return got if got and "mxtpu_mla_layer_calls_total" in got else None


def per_held_expert_call(obs, counter):
    """The program's count ``counter`` over expert-layer calls x the
    experts held: its mean for ONE held expert in ONE call of an expert
    layer, over the window's decode dispatches; None without them."""
    got = decode_calls(obs)
    if got is None:
        return None
    return got[counter] / (got["mxtpu_moe_layer_calls_total"] * int(
        shapes_of_run(obs["slots"])["n_routed_experts"]))


def mean_live_positions(obs):
    """Positions a decoding row had written (its own included), averaged
    over the rows and attention calls of the window's decode dispatches,
    from the program's own counts; None without them."""
    got = decode_calls(obs)
    if got is None:
        return None
    return got["mxtpu_mla_live_positions_total"] / (
        got["mxtpu_mla_layer_calls_total"] * obs["slots"])


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
    """(d, f, fe, H, rq, rkv, dn, dr, dv, layers, dense layers)."""
    return tuple(int(shapes[k]) for k in (
        "hidden_size", "intermediate_size", "moe_intermediate_size",
        "num_attention_heads", "q_lora_rank", "kv_lora_rank",
        "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
        "num_hidden_layers", "first_k_dense_replace"))


def param_counts(shapes):
    """Parameters by what a decode round does with them; together
    ``n_params`` of the built net.  ``embedding``: a row a token is looked
    up; ``experts``: the routed experts HELD, read only where touched;
    ``matrices``: every other matrix (the five of latent attention, the
    dense layer's MLP, shared experts, routers, the head), applied to
    every token; ``vectors``: norm gains."""
    d, f, fe, h, rq, rkv, dn, dr, dv, layers, dense = _sizes(shapes)
    held, routed_n = int(shapes["n_routed_experts"]), \
        int(shapes["published"]["n_routed_experts"])
    vocab = int(shapes["vocab_size"])
    attention = d * rq + rq * h * (dn + dr) + d * (rkv + dr) \
        + rkv * h * (dn + dv) + h * dv * d
    return {
        "embedding": vocab * d,
        "experts": (layers - dense) * held * 3 * d * fe,
        "matrices": vocab * d + layers * attention + dense * 3 * d * f
        + (layers - dense) * (3 * d * fe + routed_n * d),
        "vectors": d + layers * (4 * d + rq + rkv),
    }


def _cache_len(shapes):
    (_slots, prompt), = shapes["serving"]["buckets"]
    return prompt + int(shapes["serving"]["max_new_tokens"])


def state_bytes_per_slot(shapes):
    """{kind: bytes one slot holds} at the configuration's one bucket:
    one row of ``kv_lora_rank + qk_rope_head_dim`` numbers a position a
    layer, nothing per head."""
    _d, _f, _fe, _h, _rq, rkv, _dn, dr, _dv, layers, _dense = _sizes(shapes)
    item = jnp.dtype(shapes["serving"]["cache_dtype"]).itemsize
    return {"kv_latent": layers * _cache_len(shapes) * (rkv + dr) * item}


def _live(shapes, active, positions):
    """Positions written so far, one a decoding slot, none past a page."""
    pos = [float(positions)] * int(active) if np.ndim(positions) == 0 \
        else [float(p) for p in positions]
    return [min(p, _cache_len(shapes)) for p in pos]


def decode_bytes_per_round(shapes, active, positions, experts_touched):
    """Bytes ONE decode round has to move, whatever implements it: every
    matrix that is applied to every token once (latent attention's five,
    the dense layer, shared experts, routers, the head) and the vectors,
    ONE embedding row a slot that decodes, the three matrices of each
    routed expert TOUCHED (``experts_touched``: held experts with at least
    one token, summed over the expert layers), and the LIVE latent rows of
    the ``active`` slots: each layer's page up to the slot's ``positions``
    written so far (one number for every slot, or one a slot).  Page rows
    read past a slot's offset and experts that no token picked are the
    program's waste, not work, and the one new row a layer writes is left
    out."""
    pos = _live(shapes, active, positions)
    d, _f, fe = _sizes(shapes)[:3]
    item = jnp.dtype(shapes["serving"]["weight_dtype"]).itemsize
    counts = param_counts(shapes)
    weights = (counts["matrices"] + counts["vectors"] + len(pos) * d
               + float(experts_touched) * 3 * d * fe) * item
    return weights + state_bytes_per_slot(shapes)["kv_latent"] \
        * sum(pos) / _cache_len(shapes)


def attention_flops_per_position(shapes):
    """Operations of the ABSORBED attention for one cached position of
    one row in one layer: every head's score over the ``rkv + dr`` row
    and its weighted sum over the ``rkv`` latent, two an element."""
    _d, _f, _fe, h, _rq, rkv, _dn, dr, _dv, _layers, _dense = _sizes(shapes)
    return 2 * h * ((rkv + dr) + rkv)


def decode_flops_per_round(shapes, active, positions, assignments_held):
    """Operations ONE decode round needs: two a weight of ``matrices``
    for each slot that decodes (absorbed, ``W_ukv`` is applied once a
    row like any other matrix), two a weight of a routed expert for each
    of the ``assignments_held`` (token, held expert) pairs of the round,
    and the absorbed attention over the LIVE positions of each slot in
    each layer.  Idle slots' rows, positions past a slot's offset and
    the padding of a grouped product are the program's waste."""
    pos = _live(shapes, active, positions)
    d, _f, fe = _sizes(shapes)[:3]
    layers = int(shapes["num_hidden_layers"])
    return 2 * param_counts(shapes)["matrices"] * len(pos) \
        + 2 * 3 * d * fe * float(assignments_held) \
        + attention_flops_per_position(shapes) * layers * sum(pos)


def flops_per_token(shapes, live_positions=0.0):
    """Operations one generated token needs in the products THIS CHIP
    applies to it: two a weight of ``matrices`` (the embedding is a
    lookup, the untied head a product), two a weight of the routed experts
    it is expected to reach here (``num_experts_per_tok`` x held / routed
    of them an expert layer), and the absorbed attention over
    ``live_positions`` cached positions in every layer."""
    d, _f, fe, _h, _rq, _rkv, _dn, _dr, _dv, layers, dense = _sizes(shapes)
    reached = int(shapes["num_experts_per_tok"]) \
        * int(shapes["n_routed_experts"]) \
        / int(shapes["published"]["n_routed_experts"])
    return 2 * (param_counts(shapes)["matrices"]
                + (layers - dense) * reached * 3 * d * fe) \
        + attention_flops_per_position(shapes) * layers \
        * float(live_positions)


def decode_round_counts(obs):
    """What the step's two rooflines divide: (median bytes, median
    operations) a decode-only round of the window has to move and do,
    from the run's requests (which slots decoded, each at how many
    positions) and the program's own counts of the window's decode
    dispatches (experts touched, assignments on held experts, a mean a
    dispatch); None where either is missing."""
    import bisect
    from chipbench.harness import stats
    got = decode_calls(obs)
    if got is None:
        return None
    shapes = shapes_of_run(obs["slots"])
    touched = got["mxtpu_moe_experts_touched_total"] / got["dispatches"]
    assigned = got["mxtpu_moe_assignments_held_total"] / got["dispatches"]
    t0, t1 = obs["window"]
    live = [r for r in obs["requests"] if r["stamps"]]
    byts, flops = [], []
    for rnd in obs["rounds"]:
        if rnd["admitted"] or not (t0 <= rnd["t0"] and rnd["t1"] <= t1):
            continue
        # a slot decoding in this round has its first token and is not done
        pos = [r["prompt_len"] + bisect.bisect_right(r["stamps"], rnd["t0"])
               for r in live if r["stamps"][0] <= rnd["t0"]
               and (r["done"] is None or r["done"] > rnd["t0"])]
        if pos:
            byts.append(decode_bytes_per_round(shapes, len(pos), pos, touched))
            flops.append(decode_flops_per_round(shapes, len(pos), pos,
                                                assigned))
    return (stats.median(byts), stats.median(flops)) if byts else None


def decode_roofline_share(obs, which, peak):
    """100 x (the round's bytes (``which`` 0) or operations (1)) over a
    decode-only round's device-busy seconds x the peak ``peak`` of
    ``obs["peaks"]``: the median, over the decode-only rounds of the
    traced seconds (``program_spans``), of the round's length less the
    device's idle time in it.  None without such a trace, the peaks or
    the counts."""
    from chipbench.harness import program_spans, stats
    red = program_spans.of(obs)
    busy = [length - idle for length, idle in
            (red["decode_only_rounds"] if red else ())]
    counts = decode_round_counts(obs) if busy and obs.get("peaks") else None
    if counts is None:
        return None
    return 100.0 * counts[which] / (stats.median(busy) * obs["peaks"][peak])


# -- the plain reference (copy of mxnet_tpu/models/pangu_moe_reference.py) ----

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
    cfg = {"num_hidden_layers": len(model.layers),
           "first_k_dense_replace": sum(1 for layer in model.layers
                                        if layer.dense),
           "num_attention_heads": attn._h, "qk_nope_head_dim": attn._dn,
           "v_head_dim": attn._dv, "n_routed_experts": model.num_experts,
           "num_experts_per_tok": moe._attrs["k"],
           "routed_scaling_factor": moe._attrs["route_scale"],
           "rms_norm_eps": model.final_norm._eps, "rope_theta": attn._base}
    return weights, cfg, model.experts_held


served_picks = routed.served_picks


def full_forward_logits(net, tokens, ctx, precision="stated"):
    """The plain reference ``correct`` holds a served request to: one
    full-sequence forward of the served weights at the precision the
    configuration states, EXPANDED at every position (the served request
    was prefilled expanded and decoded absorbed, out of a bfloat16 latent
    page), with the SAME share of the experts, (S, V) float32.

    THE RULE is ``afmoe_server.full_forward_logits``'s (PERF.md section 6,
    PR 33): picking 8 of 256 is a discrete choice that two bfloat16
    computations make differently wherever a row's 8th and 9th scores lie
    within their noise, so the reference's SUM takes the experts the
    SERVED programs picked (``served_picks``), the harness holds every
    served token to those logits by its ``gap_share``, and the picks
    themselves are held to the reference's own: where the two differ, the
    reference's margin between its 8th and 9th score has to be under
    ``probe.route_margin_tau`` of the configuration file, and such excused
    decisions may be at most ``probe.excused_share_cap`` of all.  ONE
    unexcused difference at ANY position, or a share past the cap, and
    EVERY row comes back NEGATED, which the harness's own check refuses.
    The counts are printed; its seconds too (it runs inside the harness's
    set-up phase ``first_calls_probe``)."""
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
