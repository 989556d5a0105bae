"""The ``solar_open2_ep8`` configuration and its cell: found by name, the
source's numbers kept, the cut stated, the byte and operation counts tied
to the model the program builds, the copied reference held to the
original, the new readers on a hand-built run.

Run by hand: ``JAX_PLATFORMS=cpu python -m pytest chipbench/tests -q``.
"""
import inspect
import os
import subprocess
import sys

import numpy as np
import pytest

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
sys.path.insert(0, ROOT)

from chipbench.harness import resolve  # noqa: E402

CELL = "solar_open2_ep8.bulkgen_closed"
BENCH = resolve.load_benchmark()
# the catalog row's ``config`` (model-configs guide, architectures.jsonl)
CATALOG = {
    "model_type": "solar_open2", "partial_rotary_factor": 1,
    "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 128,
                           "num_heads": 64, "num_kv_heads": None},
    "hidden_size": 4096, "num_hidden_layers": 48, "num_attention_heads": 64,
    "head_dim": 128, "num_key_value_heads": 8, "vocab_size": 196608,
    "intermediate_size": 10240, "moe_intermediate_size": 1280,
    "rms_norm_eps": 1e-05, "rope_theta": 10000, "tie_word_embeddings": False,
    "max_position_embeddings": 1048576, "first_k_dense_replace": 0,
    "use_rope": False, "gqa_interval": 3,
    "gqa_layers": [0, 4, 8, 12, 16, 20, 24, 28, 32, 36, 40, 44],
    "use_gqa_gate": True, "kda_use_full_proj": False,
    "kda_allow_neg_eigval": True, "n_routed_experts": 320,
    "n_shared_experts": 1, "norm_topk_prob": True,
    "routed_scaling_factor": 1, "num_experts_per_tok": 8}
REDUCED = {"num_hidden_layers": 4, "gqa_layers": [0],
           "n_routed_experts": 40, "vocab_size": 24576}
DELTA = [name + ".delta" for name in (
    "delta_update_roofline_share", "delta_scan_roofline_share",
    "decode_hbm_share", "mfu", "expert_tokens_per_round",
    "experts_touched_share")]


@pytest.fixture(scope="module")
def cell():
    return resolve.cell(BENCH, CELL)


@pytest.fixture(scope="module")
def builder(cell):
    return resolve.load_module("models", cell[1]["builder"])


@pytest.fixture(scope="module")
def built(cell, builder):
    """The rehearsal shapes, built as a run builds them."""
    import jax
    shapes = cell[1]["rehearsal"]
    net, srv, ctx = builder.build_server(shapes, 11, jax.devices()[0], 8)
    yield shapes, net, srv, ctx
    # the state gauges are the process's: a later file's server of the
    # same toy bucket must not find this one's bytes beside its own
    from mxnet_tpu import telemetry
    for name in telemetry.snapshot()["gauges"]:
        if name.startswith("mxtpu_serving_state_bytes_b4x16"):
            telemetry.gauge(name, "").set(0)


def read(name, obs):
    return resolve.load_module("layer_metrics", name).read(obs)


def test_the_cell_its_files_and_its_metrics_resolve_by_name(
        cell, builder, bench):
    workload, config, traffic = cell
    assert workload["chips"] == 1 and len(workload["why"]) <= 200
    assert traffic["driver"] == "serve_loop"
    # 336 clients over 256 slots: 1.3 a slot
    assert traffic["arrivals"] == {"kind": "closed", "clients": 336}
    assert config["serving"]["buckets"] == [[256, 1024]]
    assert config["serving"]["max_new_tokens"] == 1024
    assert traffic["prompt_len"] == {
        "kind": "lognormal", "median": 384, "sigma": 0.8, "min": 32,
        "max": 1024, "round": True}
    assert traffic["output_len"] == {
        "kind": "lognormal", "median": 768, "sigma": 0.5, "min": 128,
        "max": 1024, "round": True}
    assert (traffic["length_block"], traffic["ramp_s"], traffic["drain_s"]) \
        == (128, 12, 120)
    assert (traffic["probe"]["prompt_len"], traffic["probe"]["new_tokens"]) \
        == (448, 128)
    for fn in ("build_server", "n_params", "shapes_of_run", "param_counts",
               "state_bytes_per_slot", "flops_per_token",
               "full_forward_logits", "decode_bytes_per_round",
               "decode_flops_per_round", "recurrence_flops_per_token",
               "scan_bytes_per_token"):
        assert callable(getattr(builder, fn))
    assert resolve.cell(bench, CELL) == cell
    names = {m["name"] for g in ("end_to_end", "per_layer")
             for m in resolve.metrics_of(bench, g, CELL)}
    # a closed cell's reductions are the accepted cells' own entries with
    # this cell appended.  A floor: whatever later PRs add to it
    assert names >= {
        "serve_tokens_per_s", "setup_s", "compile_s",
        "decode_round_ms.offline", "occupancy.offline",
        "steady_tokens_per_s.offline", "device_idle_share.offline",
        "decode_mixer_ms.offline", "decode_ffn_ms.offline",
        "state_bytes_per_slot.reason"} | set(DELTA)
    for g, sub in resolve.GROUP_DIRS.items():
        for m in resolve.metrics_of(bench, g, CELL):
            assert callable(resolve.load_module(sub, m["name"]).read)
    # the entries this cell brought, by NAME, one after the other in the
    # order it brought them, whatever follows them
    listed = [m["name"] for m in bench["per_layer"]]
    first = listed.index(DELTA[0])
    new = bench["per_layer"][first:first + len(DELTA)]
    assert [m["name"] for m in new] == DELTA
    assert [(m["workloads"][0], m["moves"], m["layer"], m["better"])
            for m in new] == [(CELL, "serve_tokens_per_s", "ops / kernels",
                               "higher")] * len(DELTA)
    assert config["name"] in [c["name"] for c in bench["configs"]]
    assert CELL in [w["name"] for w in bench["workloads"]]


def test_the_file_holds_the_source_and_states_the_cut(cell, bench):
    config = cell[1]
    entry = next(c for c in bench["configs"] if c["name"] == config["name"])
    assert len(entry["why"]) <= 200
    assert sorted(entry["reduced"]) == sorted(config["reduced"]) \
        == sorted(REDUCED)
    # every key of the source, unchanged unless listed; never a width
    assert {k: config[k] for k in CATALOG if k not in REDUCED} \
        == {k: v for k, v in CATALOG.items() if k not in REDUCED}
    assert {k: config[k] for k in REDUCED} == REDUCED
    assert config["published"] == {k: CATALOG[k] for k in REDUCED}
    assert config["vocab_size"] * 8 == CATALOG["vocab_size"]
    assert config["n_routed_experts"] * 8 == CATALOG["n_routed_experts"]
    # one whole period: the first GQA layer and the three after it
    assert config["gqa_layers"] == CATALOG["gqa_layers"][:1]
    assert config["num_hidden_layers"] == CATALOG["gqa_layers"][1]
    assert config["source"] == entry["source"] and len(entry["source"]) <= 200
    assert "8 chips share each layer" in config["deployment"]
    assert "6.4 tokens an expert" in config["deployment"]
    assert "8x" in config["deployment"]
    for key in ("linear_attention", "gate_rank", "conv", "gqa", "layers",
                "router", "intermediate_size", "norm_gains", "weights",
                "experts_held", "ids", "activations"):
        assert config["assumed"][key]


def test_counts_at_the_published_shapes(cell, builder):
    config = cell[1]
    counts = builder.param_counts(config)
    # the issue's table: 3,308.4 M, routed experts 76% of it
    assert round(sum(counts.values()) / 1e5) == 33084
    assert counts["experts"] == 4 * 40 * 3 * 4096 * 1280
    assert 0.75 < counts["experts"] / sum(counts.values()) < 0.77
    linear = 3 * 8192 * 4096 + 2 * (4096 * 128 + 128 * 8192) \
        + 4096 * 64 + 8192 * 4096
    gqa = (2 * 64 + 2 * 8) * 128 * 4096 + 64 * 128 * 4096
    assert counts["matrices"] == 24576 * 4096 + gqa + 3 * linear \
        + 4 * (3 * 4096 * 1280 + 320 * 4096)
    assert counts["vectors"] == 4096 + 4 * 2 * 4096 \
        + 3 * (4 * 3 * 8192 + 64 + 8192 + 128)
    per = builder.state_bytes_per_slot(config)
    assert per == {"kv_full": 8_388_608, "conv": 442_368,
                   "ssm": 12_582_912}
    assert sum(per.values()) == 21_413_888
    slots = config["serving"]["buckets"][0][0]
    # every expert touched, every slot full: every parameter once but the
    # embedding's rows that no slot looks up, the K,V, and each state twice
    full = builder.decode_bytes_per_round(config, slots, 2048, 4 * 40)
    assert full == (sum(counts.values())
                    - (config["vocab_size"] - slots) * 4096) * 2 \
        + slots * (8_388_608 + 2 * (442_368 + 12_582_912))
    assert builder.decode_bytes_per_round(config, slots, 9000, 160) == full
    assert full - builder.decode_bytes_per_round(config, slots, 2048, 159) \
        == 3 * 4096 * 1280 * 2
    # BY HAND, two rows at 100 and 300 written positions, 3 experts
    # touched, 7 assignments: live K,V 400 x 4 KiB, two rows' states
    assert builder.decode_bytes_per_round(config, 2, [100, 300], 3) == \
        (counts["matrices"] + counts["vectors"] + 2 * 4096
         + 3 * 3 * 4096 * 1280) * 2 + 400 * 4096 \
        + 2 * 2 * (442_368 + 12_582_912)
    rule = 64 * (7 * 128 * 128 + 4 * 128)
    assert builder.recurrence_flops_per_token(config) == rule
    assert builder.attention_flops_per_position(config) == 4 * 64 * 128
    assert builder.decode_flops_per_round(config, 2, [100, 300], 7) == \
        2 * counts["matrices"] * 2 + 2 * 3 * 4096 * 1280 * 7 \
        + 4 * 64 * 128 * 400 + rule * 3 * 2
    # two a matrix weight this chip applies to a token, 1/8 of top-8 here
    assert builder.flops_per_token(config) == 2 * (
        counts["matrices"] + 4 * 1.0 * 3 * 4096 * 1280) + 3 * rule
    assert builder.scan_bytes_per_token(config) == 64 * (5 * 128 + 1) * 4


def test_counts_are_the_built_models(built, builder):
    shapes, net, srv, _ctx = built
    assert sum(builder.param_counts(shapes).values()) == \
        builder.n_params(net)
    assert net.model.experts_held == (4, 4) and net.model.num_experts == 16
    pool, = srv._pools.values()
    slots = pool.slots
    by = pool.bytes_by_kind()
    assert {k: v * slots for k, v in
            builder.state_bytes_per_slot(shapes).items()} == by
    held = sum(int(np.prod(p.shape)) for n, p in
               net.collect_params().items() if "_experts_" in n)
    assert builder.param_counts(shapes)["experts"] == held
    assert builder.shapes_of_run(slots) == shapes
    assert builder.shapes_of_run(slots + 1) is None


def test_the_weights_are_drawn_as_the_file_assumes(built, builder):
    _shapes, net, _srv, ctx = built
    got = {n: np.asarray(p.data(ctx)._data, np.float32)
           for n, p in net.collect_params().items()}
    a_log = [v for n, v in got.items() if n.endswith("a_log")]
    dt_bias = [v for n, v in got.items() if n.endswith("dt_bias")]
    conv = [v for n, v in got.items() if n.endswith("conv_weight")]
    assert a_log and all((np.exp(v) >= 0.99).all()
                         and (np.exp(v) <= 16.1).all() for v in a_log)
    dt = np.log1p(np.exp(np.concatenate(dt_bias)))        # softplus
    assert dt.min() > 0.9e-3 and dt.max() < 0.11
    assert all(np.abs(v).max() <= 0.5 for v in conv)
    assert all((v == 1).all() for n, v in got.items() if n.endswith("gamma"))


def test_the_copied_reference_is_the_original(built, builder):
    from mxnet_tpu.models import solar_open2_reference as original
    src, orig = inspect.getsource(builder), inspect.getsource(original)
    copied = src[src.index("VOCAB_BLOCK = "):src.index(
        "def _weights_and_config")]
    assert copied.strip() == orig[orig.index("VOCAB_BLOCK = "):orig.index(
        "def weights_of")].strip()
    assert "mxnet_tpu" not in copied
    assert builder.PRECISIONS == original.PRECISIONS
    _shapes, net, _srv, ctx = built
    tokens = np.random.default_rng(3).integers(1, 256, 20)
    cfg, held = original.config_of(net)
    weights, cfg_b, held_b = builder._weights_and_config(net, ctx)
    assert (cfg_b, held_b) == (cfg, held) and held == (4, 4)
    for precision in ("stated", "float32"):
        np.testing.assert_array_equal(
            builder.forward_logits(weights, tokens, cfg, precision, held),
            original.forward_logits(original.weights_of(net, ctx), tokens,
                                    cfg, precision, held))
    # ``correct`` takes the picks the SERVED programs made: with no
    # request served alone the listener has none to give
    with pytest.raises(RuntimeError, match="served alone"):
        builder.full_forward_logits(net, tokens, ctx)


def _hand_built_run(srv):
    slots = sum(b.slots for b in srv.sched.buckets)
    peaks = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
    stamps = [1.0, 1.1, 1.2, 1.3]
    requests = [{"prompt_len": 10, "stamps": stamps, "done": 1.3,
                 "counted": True, "due": 0.9, "submit": 0.9},
                {"prompt_len": 4, "stamps": stamps, "done": 1.3,
                 "counted": True, "due": 0.9, "submit": 0.9}]
    rounds = [{"t0": t, "t1": t + 0.1, "admitted": 0, "tokens": 2,
               "active": 2} for t in (1.0, 1.1, 1.2)]
    return {"peaks": peaks, "chips": 1, "slots": slots, "window": (0.5, 2.0),
            "requests": requests, "rounds": rounds, "trace": None}


def test_the_new_readers_on_a_hand_built_run(built, builder, monkeypatch):
    shapes, net, srv, _ctx = built
    obs = _hand_built_run(srv)
    slots = obs["slots"]
    # what the run's listener logged: the window's decode dispatches are
    # read, 3 of them with 2, 2 and 1 slots holding a request; a prefill,
    # the probe's decodes before the window and the drain's after it are
    # not.  Columns: held assignments, experts touched, rows routed,
    # expert-layer calls, state rows, linear-attention calls
    calls = srv.statistics_listener
    assert calls.names == [n for n, _doc in net.statistics]
    monkeypatch.setattr(calls, "log", [
        (0.2, "decode", 1, [1., 1., 16., 4., 12., 3.]),
        (0.3, "prefill", 1, [9., 3., 40., 4., 3., 3.]),
        (1.1, "decode", 2, [8., 10., 16., 4., 12., 3.]),
        (1.2, "prefill", 1, [7., 4., 28., 4., 3., 3.]),
        (1.2, "decode", 2, [8., 10., 16., 4., 12., 3.]),
        (1.3, "decode", 1, [9., 10., 16., 4., 12., 3.]),
        (2.4, "decode", 1, [0., 0., 16., 4., 12., 3.])])
    got = builder.decode_calls(obs)
    assert (got["dispatches"], got["rows"],
            got["mxtpu_delta_state_rows_total"]) == (3, 5, 36.)
    assert read("expert_tokens_per_round.delta", obs) == pytest.approx(
        25 / (12 * 4))
    # 30 held experts touched over 12 expert-layer calls x 4 held
    assert read("experts_touched_share.delta", obs) == pytest.approx(
        100.0 * 30 / (12 * 4))
    # 12 state rows a dispatch, 5 of the 3 x 4 slot-dispatches held one
    assert builder.active_state_rows(obs) == pytest.approx(12 * 5 / 12)
    live = builder.mean_live_positions(obs)
    # each round k = 1, 2, 3: both rows decoding, at 10 + k and 4 + k
    assert live == pytest.approx((11 + 5 + 12 + 6 + 13 + 7) / 6)
    assert read("mfu.delta", obs) == pytest.approx(
        100.0 * (8 / 1.5) * builder.flops_per_token(shapes, live) / 197e12)
    # a device's roofline has no host-clock reading: untraced, nothing
    for name in DELTA[:3]:
        assert read(name, obs) is None, name
    from chipbench.harness import device_scopes, program_spans
    table = {"busy_ms": 1.0, "programs": {
        "jit_decode_b4x16": {"runs": 3, "ms_per_run": 2.0, "scopes": {
            "mxtpu.mixer.delta.update": {"ms_per_run": 0.5}}},
        "jit_prefill_b4x16": {"runs": 2, "ms_per_run": 3.0, "scopes": {
            "mxtpu.mixer.delta.scan": {"ms_per_run": 0.25}}}}}
    traced = dict(obs, trace={"window_s": 3.0}, **{
        program_spans.KEY: {"decode_only_rounds": [(0.1, 0.02)] * 3},
        device_scopes.KEY: table})
    rows = 12 * 5 / 12
    state = 4 * 8 * 8 * 4
    want = 100.0 * max(rows * 2 * state / 819e9, rows * 4 * (
        7 * 64 + 32) / 197e12) * 1e3 / 0.5
    assert read("delta_update_roofline_share.delta", traced) \
        == pytest.approx(want)
    # the window's one prefill: 28 / 4 routed rows an expert-layer call =
    # 7 real tokens, one state row and three layer calls
    tokens = 7.0
    byts = tokens * builder.scan_bytes_per_token(shapes) + 1 * state
    flops = tokens * builder.recurrence_flops_per_token(shapes)
    assert read("delta_scan_roofline_share.delta", traced) == pytest.approx(
        100.0 * 3 * max(byts / 819e9, flops / 197e12) * 1e3 / 0.25)
    assert read("decode_hbm_share.delta", traced) == pytest.approx(
        100.0 * builder.decode_bytes_per_round(shapes, 2, [12, 6], 10.0)
        / (0.08 * 819e9))
    # a run of another configuration's size, or without peaks: nothing
    for name in DELTA:
        assert read(name, dict(traced, slots=slots + 1)) is None, name
        assert read(name, dict(traced, window=(3.0, 4.0))) is None, name
    for name in DELTA[:4]:
        assert read(name, dict(traced, peaks=None)) is None, name


def test_a_program_without_the_counts_reads_nothing(builder, monkeypatch):
    """The parent of the PR that added them builds no such server: there
    is no listener, or one whose calls carry the expert layers' counts
    alone.  The readers return None and do not raise."""
    from chipbench.models import afmoe_server
    obs = {"trace": None, "slots": 256, "peaks": None, "window": (0.0, 1.0),
           "requests": [], "rounds": [], "chips": 1}
    monkeypatch.setattr(afmoe_server, "CALLS", None)
    for name in DELTA:
        assert read(name, obs) is None, name
    other = afmoe_server.Calls(["mxtpu_moe_assignments_held_total",
                                "mxtpu_moe_experts_touched_total",
                                "mxtpu_moe_routed_rows_total",
                                "mxtpu_moe_layer_calls_total"])
    other.log.append((0.5, "decode", 256, [256., 40., 1024., 4.]))
    monkeypatch.setattr(afmoe_server, "CALLS", other)
    for name in DELTA:
        assert read(name, dict(obs, peaks={"hbm_bytes_per_s": 1.0,
                                           "bf16_flops_per_s": 1.0})) \
            is None, name


def _probed(cell, built, builder, precision="stated"):
    """The harness's own comparison (``serve_loop._probe``) over the
    rehearsal shapes -> (the run, what the probe returned)."""
    import types
    from chipbench.drivers import serve_loop
    from chipbench.harness import runtime
    workload, config, traffic = cell
    _shapes, net, srv, ctx = built
    run = runtime.Run(
        types.SimpleNamespace(seed=11, seconds=0.0, trace=0,
                              rehearse=True, sweep=None),
        workload, config, traffic, None, 0.0)
    srv.statistics_listener.arm()           # this probe's picks
    probe = serve_loop._probe(
        run, net, srv, ctx, types.SimpleNamespace(
            full_forward_logits=lambda n, t, c: builder.full_forward_logits(
                n, t, c, precision=precision)),
        serve_loop.Loop(srv, run.spans))
    return run, probe


def test_the_limit_sits_between_the_stated_precision_and_the_controls(
        cell, built, builder):
    """What the cell compares with passes its ``gap_share``; float8
    weights and K,V, ``b = sigmoid``, the rule without its ``- b k k^T
    S'`` term and a bfloat16 matrix state are refused (the last by the
    served states' comparison alone: ``tools/afmoe_chip_check.py --cell``
    reads the same on the chip)."""
    worst = {}
    controls = ("float8", "beta_sigmoid", "no_correction", "state_bfloat16")
    for name in ("stated",) + controls:
        run, probe = _probed(cell, built, builder, name)
        worst[name] = probe["probe_worst_regret_share"]
        assert bool(run.checks.failed) == (name != "stated"), worst
    limit = cell[2]["rehearsal"]["probe"]["gap_share"]
    assert worst["stated"] <= limit < min(worst[c] for c in controls)
    # the states are read once a probe, from the slot it decoded in
    served = builder.served_states()
    assert sorted(served) == [1, 2, 3]
    assert builder._bfloat16_share(served.values()) < 0.01


def test_the_cell_rehearses_on_the_cpu():
    """``--rehearse`` runs the cell's own code at the toy shapes to its
    end: exit 3 (never a number), nothing failed."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH_DIR, "run.py"), "--workload",
         CELL, "--rehearse", "--seed", "2147493203", "--seconds", "2"],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=600)
    assert out.returncode == 3, out.stderr[-2000:]
    assert '"checks_failed": []' in out.stdout
    assert "expert_tokens_per_round.delta" in out.stdout
