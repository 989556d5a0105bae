"""Solar-Open2-250B as ONE CHIP OF AN 8-WAY EXPERT-PARALLEL DEPLOYMENT
behind ``serving.Server``, through the program's normal entry points:
``get_solar_open2(preset, ...)`` -> ``SolarOpen2ForCausalLM`` ->
``Server(net, buckets=..., max_new_tokens=..., cache_dtype=...)``, the
weights made ON THE DEVICE from the seed in the type they are served in
and installed through the parameters' load path (``afmoe_server``'s way,
with the linear-attention layer's own initial values, ``_rule``).  The
configuration file's ``n_routed_experts`` is how many experts are HELD
here; the router's width is its ``published.n_routed_experts``.  The
run's listener is ``afmoe_server.Calls``: what the served programs count
rides out behind every dispatch's tokens, the linear-attention layers'
counts beside the expert layers'.

A parent whose program has no ``SolarOpen2ForCausalLM`` fails at
``build_server``'s import, within seconds: a clean refusal of the cell.

Beside the builder: the PLAIN REFERENCE that ``correct`` holds a served
request to (a copy of ``mxnet_tpu/models/solar_open2_reference.py``, so
that the benchmark's tree stands alone: straightforward ``jax.numpy``,
every sum in float32, the gated delta rule as its per-token recurrence, a
Python loop over experts, no cache, no call into ``mxnet_tpu.models`` or
``mxnet_tpu.ops``; ``chipbench/tests`` holds the copy to the original),
and the functions the per-layer metrics take their operations and bytes
from: ``decode_bytes_per_round``, ``decode_flops_per_round``,
``flops_per_token``, ``recurrence_flops_per_token`` and
``scan_bytes_per_token``.  The delta rule is composed XLA ops and the
routed products are ``jax.lax.ragged_dot``: no kernel of this repository
is on the path, so the rule's two shares (``delta_update_roofline_share``,
``delta_scan_roofline_share``) read its device SCOPES, whatever
implements them.
"""
import json
import math
import re
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.harness import decode_rounds
from chipbench.models import afmoe_server as routed

CONFIG = "solar_open2_ep8.json"

# -- the builder ---------------------------------------------------------------


def _rule(name, shape):
    """How a parameter is drawn, by the end of its name (the configuration
    file's ``assumed``): norm gains 1; the linear-attention layer's own
    initial values (``A`` uniform over [1, 16] a head, dt log-uniform over
    [1e-3, 1e-1] a channel with its bias the inverse softplus of it, the
    short conv uniform over +-1/sqrt(width)); every matrix N(0, 2 /
    (fan_in + fan_out)) (an expert's matrices are stacked over the experts
    held)."""
    if name.endswith("_gamma"):
        return "ones"
    for end in ("a_log", "dt_bias", "conv_weight"):
        if name.endswith(end):
            return end
    return math.sqrt(2.0 / (shape[-2] + shape[-1]))


@partial(jax.jit, static_argnames=("shape", "dtype", "rule"))
def _make(key, *, shape, dtype, rule):
    if rule == "ones":
        return jnp.ones(shape, dtype)
    u = jax.random.uniform(key, shape, jnp.float32)
    if rule == "a_log":
        return jnp.log(1.0 + 15.0 * u).astype(dtype)
    if rule == "dt_bias":
        dt = jnp.exp(math.log(1e-3) + u * (math.log(1e-1) - math.log(1e-3)))
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
    if rule == "conv_weight":
        return ((2.0 * u - 1.0) / math.sqrt(shape[0])).astype(dtype)
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
    from mxnet_tpu.models import SolarOpen2ForCausalLM, get_solar_open2
    from mxnet_tpu.serving import Server

    prog, serving = shapes["program"], shapes["serving"]
    linear = shapes["linear_attn_config"]
    if shapes["kda_allow_neg_eigval"] is not True:
        raise ValueError("the model's step is 2 sigmoid: "
                         "kda_allow_neg_eigval must be true")
    ctx = mx.Context(device.platform, 0)
    mx.random.seed(seed % (2 ** 31 - 1))
    net = SolarOpen2ForCausalLM(get_solar_open2(
        prog["preset"], vocab_size=int(shapes["vocab_size"]),
        # the file's sizes are what runs, whatever the preset holds
        units=int(shapes["hidden_size"]),
        moe_hidden=int(shapes["moe_intermediate_size"]),
        num_layers=int(shapes["num_hidden_layers"]),
        gqa_layers=tuple(int(i) for i in shapes["gqa_layers"]),
        num_heads=int(shapes["num_attention_heads"]),
        num_kv_heads=int(shapes["num_key_value_heads"]),
        head_dim=int(shapes["head_dim"]),
        delta_heads=int(linear["num_heads"]),
        delta_head_dim=int(linear["head_dim"]),
        conv_width=int(linear["short_conv_kernel_size"]),
        gate_rank=int(prog["gate_rank"]),
        # the router keeps its published width; ``n_routed_experts`` are held
        num_experts=int(shapes["published"]["n_routed_experts"]),
        experts_held=(int(prog["first_expert_held"]),
                      int(shapes["n_routed_experts"])),
        top_k=int(shapes["num_experts_per_tok"]),
        route_scale=float(shapes["routed_scaling_factor"]),
        rms_norm_eps=float(shapes["rms_norm_eps"])))
    net.cast(serving["weight_dtype"])
    values = draw_weights(net, seed, device, serving["weight_dtype"])
    for p, value in zip(net.collect_params().values(), values):
        p.grad_req = "null"
        p._load_init(nd.NDArray(value, ctx=ctx), ctx=ctx)
    srv = Server(net, buckets=[tuple(b) for b in serving["buckets"]],
                 max_new_tokens=int(serving["max_new_tokens"]), ctx=ctx,
                 cache_dtype=serving["cache_dtype"], max_queue=max_queue)
    routed.CALLS = srv.statistics_listener = Calls(
        [name for name, _doc in net.statistics], srv)
    return net, srv, ctx


class Calls(routed.Calls):
    """``afmoe_server.Calls`` that also knows its server and the slot the
    armed request decoded in, so that ``served_states`` can read what that
    request left in the pool."""

    def __init__(self, names, server):
        self.server = server
        super().__init__(names)

    def arm(self):
        super().arm()
        self.slots = set()      # the columns the armed request decoded in
        self.states = None      # what ``served_states`` read from them

    def __call__(self, kind, columns, counts, rows):
        if self.picks is not None and kind == "decode":
            self.slots.update(columns)
        super().__call__(kind, columns, counts, rows)


def served_states():
    """{linear-attention layer: its matrix state (Hd, dk, dv) float32} in
    the slot of the one request served since the listener was armed (the
    probe, alone: prefilled by the chunked scan, then one update a decode
    dispatch, so the state of every position but the last generated
    token).  Read at the first call, which the harness makes right after
    the probe's last token, before anything else is dispatched."""
    calls = routed.CALLS
    if calls.states is None:
        if len(calls.slots) != 1:
            raise RuntimeError(
                f"the served matrix states are read from ONE request served "
                f"alone since the listener was armed; it decoded in slots "
                f"{sorted(calls.slots)}")
        slot, = calls.slots
        pool, = calls.server._pools.values()
        calls.states = {
            int(name[len("layer"):-len("_state")]): np.asarray(buf[slot])
            for (name, kind, _shape, _dtype), buf in zip(pool.spec,
                                                         pool.flat())
            if kind == "ssm"}
    return calls.states


n_params = routed.n_params


def _calls(obs, kind):
    """``decode_rounds.calls`` of a run of THIS configuration; None for a
    run of another configuration, or a program without the
    linear-attention layers' counts (the parent of the PR that added
    them)."""
    if shapes_of_run(obs.get("slots")) is None:
        return None
    got = decode_rounds.calls(routed.CALLS, obs, kind)
    return got if got and "mxtpu_delta_layer_calls_total" in got else None


def decode_calls(obs):
    """The window's DECODE dispatches' counts: ``rows`` are the slots that
    held a request, summed over them."""
    return _calls(obs, "decode")


def prefill_calls(obs):
    """The window's PREFILL dispatches' counts (admissions)."""
    return _calls(obs, "prefill")


def per_held_expert_call(obs, counter):
    """The program's count ``counter`` over expert-layer calls x the
    experts held: its mean for ONE held expert in ONE call of an expert
    layer, over the window's decode dispatches; None without them."""
    got = decode_calls(obs)
    if got is None:
        return None
    return got[counter] / (got["mxtpu_moe_layer_calls_total"] * int(
        shapes_of_run(obs["slots"])["n_routed_experts"]))


def active_state_rows(obs):
    """Matrix states a decode dispatch of the window moved FOR A REQUEST,
    on average: the program's ``mxtpu_delta_state_rows_total`` (every
    slot, each linear-attention layer) times the share of slots that held
    one (the listener's columns); None without the counts."""
    got = decode_calls(obs)
    if got is None or not got["mxtpu_delta_state_rows_total"]:
        return None
    d = got["dispatches"]
    return got["mxtpu_delta_state_rows_total"] / d \
        * got["rows"] / (d * obs["slots"])


def shapes_of_run(slots):
    """The shapes a run of this configuration with ``slots`` slots used
    (a metric file has only ``obs`` to tell them apart by)."""
    return decode_rounds.of_slots(CONFIG, slots)


# -- operations and bytes, from the shapes alone ------------------------------

def _sizes(shapes):
    """(d, fe, H, KV, dh, Hd, dk, K, r, layers, GQA layers)."""
    lin = shapes["linear_attn_config"]
    return (int(shapes["hidden_size"]), int(shapes["moe_intermediate_size"]),
            int(shapes["num_attention_heads"]),
            int(shapes["num_key_value_heads"]), int(shapes["head_dim"]),
            int(lin["num_heads"]), int(lin["head_dim"]),
            int(lin["short_conv_kernel_size"]),
            int(shapes["program"]["gate_rank"]),
            int(shapes["num_hidden_layers"]), len(shapes["gqa_layers"]))


def param_counts(shapes):
    """Parameters by what a decode round does with them; together
    ``n_params`` of the built net.  ``embedding``: a row a token is looked
    up; ``experts``: the routed experts HELD, read only where touched;
    ``matrices``: every other matrix (GQA's four and ``W_o``, the linear
    layers' seven, shared experts, routers, the head), applied to every
    token; ``vectors``: norm gains, the short convs' taps, ``A_log`` and
    ``dt_bias``."""
    d, fe, h, kv, dh, hd, dk, taps, r, layers, gqa = _sizes(shapes)
    delta = layers - gqa
    held, width_r = int(shapes["n_routed_experts"]), \
        int(shapes["published"]["n_routed_experts"])
    vocab = int(shapes["vocab_size"])
    w = hd * dk
    full = (2 * h + 2 * kv) * dh * d + h * dh * d
    linear = 3 * w * d + 2 * (d * r + r * w) + d * hd + w * d
    return {
        "embedding": vocab * d,
        "experts": layers * held * 3 * d * fe,
        "matrices": vocab * d + gqa * full + delta * linear
        + layers * (3 * d * fe + width_r * d),
        "vectors": d + layers * 2 * d
        + delta * (taps * 3 * w + hd + w + dk),
    }


def _cache_len(shapes):
    (_slots, prompt), = shapes["serving"]["buckets"]
    return prompt + int(shapes["serving"]["max_new_tokens"])


def state_bytes_per_slot(shapes):
    """{kind: bytes one slot holds} at the configuration's one bucket: the
    GQA layers' K,V over every position, the linear layers' conv tails
    (in the cache type) and float32 matrix states, neither of which grows
    with the request."""
    d, fe, h, kv, dh, hd, dk, taps, r, layers, gqa = _sizes(shapes)
    item = jnp.dtype(shapes["serving"]["cache_dtype"]).itemsize
    delta = layers - gqa
    return {"kv_full": gqa * 2 * _cache_len(shapes) * kv * dh * item,
            "conv": delta * (taps - 1) * 3 * hd * dk * item,
            "ssm": delta * hd * dk * dk * 4}


def recurrence_flops_per_token(shapes):
    """Operations of the gated delta rule for one token in one layer, as
    written: per head the decay (dk dv), ``S'^T k`` (2 dk dv), the update
    (3 dv), ``k u^T`` added (2 dk dv), ``S^T q`` (2 dk dv) and its scale
    (dv)."""
    _d, _fe, _h, _kv, _dh, hd, dk, _taps, _r, _l, _g = _sizes(shapes)
    return hd * (7 * dk * dk + 4 * dk)


def scan_bytes_per_token(shapes):
    """Bytes the prefill scan of one layer has to read and write for one
    real token, whatever implements it: q, k, the decay (dk each), v (dv)
    and the step in, the output (dv) out, float32."""
    _d, _fe, _h, _kv, _dh, hd, dk, _taps, _r, _l, _g = _sizes(shapes)
    return hd * (3 * dk + 2 * dk + 1) * 4


def attention_flops_per_position(shapes):
    """Operations of GQA for one cached position of one row in one layer:
    every query head's score and weighted sum, two an element."""
    _d, _fe, h, _kv, dh, _hd, _dk, _taps, _r, _l, _g = _sizes(shapes)
    return 2 * 2 * h * dh


def _live(shapes, active, positions):
    return decode_rounds.live(active, positions, _cache_len(shapes))


def decode_bytes_per_round(shapes, active, positions, experts_touched):
    """Bytes ONE decode round has to move, whatever implements it: every
    matrix that is applied to every token once and the vectors, ONE
    embedding row a slot that decodes, the three matrices of each routed
    expert TOUCHED (``experts_touched``: held experts with at least one
    token, summed over the expert layers), the LIVE K,V of the ``active``
    slots (each GQA layer's page up to the slot's ``positions`` written so
    far: one number for every slot, or one a slot), and each active slot's
    matrix states and conv tails read AND written.  Page rows past a
    slot's offset, idle slots' states and experts that no token picked are
    the program's waste, not work; the one new K,V row is left out."""
    pos = _live(shapes, active, positions)
    d, fe = _sizes(shapes)[:2]
    item = jnp.dtype(shapes["serving"]["weight_dtype"]).itemsize
    counts = param_counts(shapes)
    per = state_bytes_per_slot(shapes)
    weights = (counts["matrices"] + counts["vectors"] + len(pos) * d
               + float(experts_touched) * 3 * d * fe) * item
    return weights + per["kv_full"] * sum(pos) / _cache_len(shapes) \
        + 2 * (per["ssm"] + per["conv"]) * len(pos)


def decode_flops_per_round(shapes, active, positions, assignments_held):
    """Operations ONE decode round needs: two a weight of ``matrices``
    for each slot that decodes, two a weight of a routed expert for each
    of the ``assignments_held`` (token, held expert) pairs of the round,
    GQA over the LIVE positions of each slot, and the gated delta rule of
    each slot in each linear layer.  Idle slots' rows, positions past a
    slot's offset and the padding of a grouped product are the program's
    waste."""
    pos = _live(shapes, active, positions)
    d, fe = _sizes(shapes)[:2]
    layers, gqa = _sizes(shapes)[-2:]
    return 2 * param_counts(shapes)["matrices"] * len(pos) \
        + 2 * 3 * d * fe * float(assignments_held) \
        + attention_flops_per_position(shapes) * gqa * sum(pos) \
        + recurrence_flops_per_token(shapes) * (layers - gqa) * len(pos)


def flops_per_token(shapes, live_positions=0.0):
    """Operations one generated token needs in what THIS CHIP applies to
    it: two a weight of ``matrices`` (the embedding is a lookup, the
    untied head a product), two a weight of the routed experts it is
    expected to reach here (``num_experts_per_tok`` x held / routed of
    them an expert layer), GQA over ``live_positions`` cached positions
    and the gated delta rule in every linear layer."""
    d, fe = _sizes(shapes)[:2]
    layers, gqa = _sizes(shapes)[-2:]
    reached = int(shapes["num_experts_per_tok"]) \
        * int(shapes["n_routed_experts"]) \
        / int(shapes["published"]["n_routed_experts"])
    return 2 * (param_counts(shapes)["matrices"]
                + layers * reached * 3 * d * fe) \
        + attention_flops_per_position(shapes) * gqa * float(live_positions) \
        + recurrence_flops_per_token(shapes) * (layers - gqa)


def mean_live_positions(obs):
    """Positions a decoding row of the window had written (its own
    included), averaged over the window's decode-only rounds' requests;
    None without them."""
    live = [p for _rnd, pos in decode_rounds.decoding_positions(obs)
            for p in pos]
    return sum(live) / len(live) if live else None


def decode_round_counts(obs):
    """What the step's roofline divides: (median bytes, median
    operations) a decode-only round of the window has to move and do,
    from the run's requests and the program's own counts of the window's
    decode dispatches (experts touched, assignments on held experts, a
    mean a dispatch); None where either is missing."""
    got = decode_calls(obs)
    if got is None:
        return None
    shapes = shapes_of_run(obs["slots"])
    touched = got["mxtpu_moe_experts_touched_total"] / got["dispatches"]
    assigned = got["mxtpu_moe_assignments_held_total"] / got["dispatches"]
    return decode_rounds.round_counts(
        obs, lambda n, pos: decode_bytes_per_round(shapes, n, pos, touched),
        lambda n, pos: decode_flops_per_round(shapes, n, pos, assigned))


def decode_roofline_share(obs, which, peak):
    """``decode_rounds.roofline_share`` of this model's decode round."""
    return decode_rounds.roofline_share(
        obs, lambda: decode_round_counts(obs), which, peak)


def bound_ms(obs, byts, flops):
    """The least time, in ms, that moving ``byts`` and doing ``flops``
    take on the chip: the larger of the two over its peak."""
    peaks = obs["peaks"]
    return 1e3 * max(byts / peaks["hbm_bytes_per_s"],
                     flops / peaks["bf16_flops_per_s"])


# -- the plain reference (copy of mxnet_tpu/models/solar_open2_reference.py) --

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


def _weights_and_config(net, ctx):
    """What ``forward_logits`` takes, from the built net: its weights by
    structural name, ``cfg`` and the share of the experts it holds."""
    weights = {}
    for name, p in net.collect_params().items():
        m = re.search(r"(embed_|finalnorm_|head_|layer\d+_).*$", name)
        weights[m.group(0)] = p.data(ctx)._data
    model = net.model
    full = next(layer.attn for layer in model.layers if layer.kind == "full")
    delta = next(layer.attn for layer in model.layers
                 if layer.kind == "delta")
    moe = model.layers[0].ffn
    cfg = {"num_hidden_layers": len(model.layers),
           "gqa_layers": [i for i, layer in enumerate(model.layers)
                          if layer.kind == "full"],
           "num_attention_heads": full._h, "num_key_value_heads": full._kv,
           "linear_heads": delta._h, "n_routed_experts": model.num_experts,
           "num_experts_per_tok": moe._attrs["k"],
           "routed_scaling_factor": moe._attrs["route_scale"],
           "rms_norm_eps": model.final_norm._eps}
    return weights, cfg, model.experts_held


served_picks = routed.served_picks


def _bfloat16_share(arrays):
    """The share of the elements of ``arrays`` that bfloat16 holds
    exactly: about 2^-16 of a float32 computation's, all of one rounded
    to bfloat16."""
    x = np.concatenate([np.ravel(a).astype(np.float32) for a in arrays])
    return float(np.mean(x == x.astype(jnp.bfloat16).astype(np.float32)))


def full_forward_logits(net, tokens, ctx, precision="stated"):
    """The plain reference ``correct`` holds a served request to: one
    full-sequence forward of the served weights at the precision the
    configuration states, the gated delta rule one token at a time from a
    zero state (the served request was prefilled by the chunked scan and
    decoded by the one-token update, its matrix state float32, its K,V
    and conv tails bfloat16), with the SAME share of the experts, (S, V)
    float32.

    THE RULE is ``afmoe_server.full_forward_logits``'s (PERF.md section
    6): picking 8 of 320 is a discrete choice that two bfloat16
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

    The STATE is compared too: each linear-attention layer's matrix
    state that the served request left in its slot (``served_states``:
    the chunked scan, then one update a decode step) against the
    reference's after the same tokens, twice.  Its VALUES: ``|S_served -
    S_ref| / |S_ref|`` (Frobenius norms), the largest over the layers,
    within ``probe.state_gap`` of the configuration file (a wrong rule
    reads 0.7 and more).  Its PRECISION: the share of the states'
    elements that bfloat16 holds exactly, served and reference, at most
    ``probe.state_precision_gap`` apart (a float32 state ~0, one rounded
    to bfloat16 1).  The values alone cannot see the precision: with
    random weights a bfloat16 state moves them less than the bfloat16
    noise of the products before the rule does (PERF.md section 6).
    Past either limit every row comes back negated as well.  The
    counts and the readings are printed; its seconds too (it runs inside
    the harness's set-up phase ``first_calls_probe``)."""
    t0 = time.perf_counter()
    served = served_states()
    weights, cfg, held = _weights_and_config(net, ctx)
    limits = decode_rounds.config_shapes(
        CONFIG, lambda s: int(s["hidden_size"]) == net.model._units)["probe"]
    tau, cap = (float(limits["route_margin_tau"]),
                float(limits["excused_share_cap"]))
    chosen = served_picks(net, len(tokens))
    routing, states = {}, {}
    logits = forward_logits(weights, tokens, cfg, precision, held,
                            selections=chosen, routing=routing, states=states)
    differ = (np.sort(chosen, -1) != np.sort(routing["picked"], -1)).any(-1)
    excused = differ & (routing["margin"] < tau)
    refused = differ & ~excused
    share = float(excused.mean())
    gaps = [float(np.linalg.norm(served[l] - states[l])
                  / np.linalg.norm(states[l])) for l in sorted(states)]
    exact = [_bfloat16_share(list(x.values())) for x in (served, states)]
    sound = not refused.any() and share <= cap \
        and max(gaps) <= float(limits["state_gap"]) \
        and abs(exact[0] - exact[1]) <= float(limits["state_precision_gap"])
    if not sound:
        logits *= -1.0
    print(json.dumps({
        "reference": precision, "tokens": len(tokens),
        "experts_held": list(held), "decisions": int(differ.size),
        "picks_differ": int(differ.sum()), "excused": int(excused.sum()),
        "refused": int(refused.sum()), "excused_share": share,
        "state_gap_by_layer": gaps, "state_gap": limits["state_gap"],
        "state_bfloat16_exact_served_reference": exact,
        "state_precision_gap": limits["state_precision_gap"],
        "rows_negated": 0 if sound else len(logits),
        "largest_margin_where_they_differ": float(
            routing["margin"][differ].max()) if differ.any() else 0.0,
        "route_margin_tau": tau, "excused_share_cap": cap,
        "reference_s": time.perf_counter() - t0}), flush=True)
    return logits
