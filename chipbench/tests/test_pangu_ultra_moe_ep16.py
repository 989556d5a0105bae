"""The ``pangu_ultra_moe_ep16`` configuration and its cell: found by name,
the source's numbers kept, the cut stated, the byte and operation counts
tied to the model the program builds, the copied reference held to the
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

CELL = "pangu_ultra_moe_ep16.longgen_closed"
BENCH = resolve.load_benchmark()
# the catalog row's ``config`` (model-configs guide, architectures.jsonl)
CATALOG = {
    "attention_bias": False, "first_k_dense_replace": 3, "hidden_act": "silu",
    "hidden_size": 7680, "intermediate_size": 18432, "kv_lora_rank": 512,
    "max_position_embeddings": 131072, "model_type": "pangu_ultra_moe",
    "moe_intermediate_size": 2048, "n_routed_experts": 256,
    "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 61, "num_key_value_heads": 128,
    "num_nextn_predict_layers": 1, "q_lora_rank": 1536,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05,
    "rope_theta": 25600000, "routed_scaling_factor": 2.5,
    "sandwich_norm": True, "tie_word_embeddings": False, "v_head_dim": 128,
    "vocab_size": 153600}
REDUCED = {"num_hidden_layers": 5, "first_k_dense_replace": 1,
           "n_routed_experts": 16, "vocab_size": 19200,
           "num_nextn_predict_layers": 0}
MLA = [name + ".mla" for name in (
    "latent_live_share", "expert_tokens_per_round", "experts_touched_share",
    "decode_hbm_share", "decode_mxu_share", "mfu")]


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
        if name.startswith("mxtpu_serving_state_bytes_b") \
                and name.endswith("kv_latent"):
            telemetry.gauge(name, "").set(0)


def read(name, obs):
    return resolve.load_module("layer_metrics", name).read(obs)


def test_the_cell_its_files_and_its_metrics_resolve_by_name(
        cell, builder, bench):
    workload, config, traffic = cell
    assert workload["chips"] == 1 and len(workload["why"]) <= 200
    assert traffic["driver"] == "serve_loop"
    # 1.3 clients a slot; the memory rule: 160 slots, or fewer in steps of
    # 16 down to 128
    slots = config["serving"]["buckets"][0][0]
    assert traffic["arrivals"] == {"kind": "closed",
                                   "clients": round(1.3 * slots)}
    assert config["serving"]["buckets"] == [[slots, 1024]]
    assert slots % 16 == 0 and 128 <= slots <= 160
    assert config["serving"]["max_new_tokens"] == 2048
    assert traffic["prompt_len"] == {
        "kind": "lognormal", "median": 384, "sigma": 0.8, "min": 32,
        "max": 1024, "round": True}
    assert traffic["output_len"] == {
        "kind": "lognormal", "median": 1024, "sigma": 0.5, "min": 128,
        "max": 2048, "round": True}
    assert (traffic["length_block"], traffic["ramp_s"], traffic["drain_s"]) \
        == (128, 12, 120)
    assert (traffic["probe"]["prompt_len"], traffic["probe"]["new_tokens"]) \
        == (448, 128)
    for fn in ("build_server", "n_params", "shapes_of_run", "param_counts",
               "state_bytes_per_slot", "flops_per_token",
               "full_forward_logits", "decode_bytes_per_round",
               "decode_flops_per_round"):
        assert callable(getattr(builder, fn))
    assert resolve.cell(bench, CELL) == cell
    names = {m["name"] for g in ("end_to_end", "per_layer")
             for m in resolve.metrics_of(bench, g, CELL)}
    # the closed-loop reductions are the accepted cells' own entries, with
    # this cell appended: one name a reduction, no twin files.  A floor:
    # what the cell reports at least, whatever later PRs add to it
    assert names >= {
        "serve_tokens_per_s", "setup_s", "compile_s",
        "decode_round_ms.offline", "occupancy.offline",
        "steady_tokens_per_s.offline", "device_idle_share.offline",
        "state_bytes_per_slot.reason"} | set(MLA)
    for g, sub in resolve.GROUP_DIRS.items():
        for m in resolve.metrics_of(bench, g, CELL):
            assert callable(resolve.load_module(sub, m["name"]).read)
    # the six entries this cell brought, by NAME (a later entry may end
    # ``.mla`` too): one after the other and in the order it brought them,
    # whatever follows them
    listed = [m["name"] for m in bench["per_layer"]]
    first = listed.index(MLA[0])
    new = bench["per_layer"][first:first + len(MLA)]
    assert [m["name"] for m in new] == MLA
    assert [(m["workloads"][0], m["moves"], m["layer"], m["better"])
            for m in new] == [(CELL, "serve_tokens_per_s", "ops / kernels",
                               "higher")] * 6
    # additions only: the configuration and the cell are in their lists
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
    assert config["n_routed_experts"] * 16 == CATALOG["n_routed_experts"]
    assert config["source"] == entry["source"] and len(entry["source"]) <= 200
    assert "16 chips share each layer" in config["deployment"]
    assert "5 tokens an expert" in config["deployment"]
    assert "16x" in config["deployment"]
    for key in ("latent_attention", "positions", "sandwich_norm", "router",
                "norm_gains", "weights", "experts_held", "ids", "mtp",
                "activations"):
        assert config["assumed"][key]


def test_counts_at_the_published_shapes(cell, builder):
    config = cell[1]
    counts = builder.param_counts(config)
    # the issue's table: every matrix, norm gains beside them
    assert sum(counts.values()) - counts["vectors"] == 4_918_968_320
    assert counts["vectors"] == 7680 + 5 * (4 * 7680 + 1536 + 512)
    assert counts["experts"] == 4 * 16 * 3 * 7680 * 2048      # 6.04 GB
    per = builder.state_bytes_per_slot(config)
    # 1,152 bytes a token a layer, where per-head K,V would be 81,920
    assert per == {"kv_latent": 5 * 3072 * (512 + 64) * 2} \
        == {"kv_latent": 17_694_720}
    assert 128 * (128 + 64 + 128) * 2 == 81_920
    slots = config["serving"]["buckets"][0][0]
    # every expert touched, every slot full: every parameter once but the
    # embedding's rows that no slot looks up, and the whole state
    full = builder.decode_bytes_per_round(config, slots, 3072, 4 * 16)
    assert full == (sum(counts.values())
                    - (config["vocab_size"] - slots) * 7680) * 2 \
        + slots * 17_694_720
    assert builder.decode_bytes_per_round(config, slots, 9000, 64) == full
    assert builder.decode_bytes_per_round(config, slots, 600, 64) < full
    assert full - builder.decode_bytes_per_round(config, slots, 3072, 63) \
        == 3 * 7680 * 2048 * 2
    assert builder.decode_bytes_per_round(config, 40, [600] * 40, 50) == \
        builder.decode_bytes_per_round(config, 40, 600, 50)
    # BY HAND, two rows at 100 and 300 written positions, 3 experts
    # touched, 7 assignments on them: the live rows are 400 x 5 x 1,152 B
    assert builder.decode_bytes_per_round(config, 2, [100, 300], 3) == \
        (counts["matrices"] + counts["vectors"] + 2 * 7680
         + 3 * 3 * 7680 * 2048) * 2 + 400 * 5 * 1152
    # the absorbed attention: 128 heads x ((512 + 64) + 512) x 2
    assert builder.attention_flops_per_position(config) == 278_528
    assert builder.decode_flops_per_round(config, 2, [100, 300], 7) == \
        2 * counts["matrices"] * 2 + 2 * 3 * 7680 * 2048 * 7 \
        + 278_528 * 5 * 400
    # two a matrix weight this chip applies to a token, 1/16 of top-8
    # here, and the attention at the positions given
    assert builder.flops_per_token(config) == 2 * (
        counts["matrices"] + 4 * 0.5 * 3 * 7680 * 2048)
    assert builder.flops_per_token(config, 1000) \
        - builder.flops_per_token(config) == 278_528 * 5 * 1000


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
    assert list(by) == ["kv_latent"]
    held = sum(int(np.prod(p.shape)) for n, p in
               net.collect_params().items() if "_experts_" in n)
    assert builder.param_counts(shapes)["experts"] == held
    want = (builder.n_params(net) - (shapes["vocab_size"] - slots) * 64) \
        * 2 + by["kv_latent"]
    assert builder.decode_bytes_per_round(
        shapes, slots, pool.cache_len, 4 * 4) == want
    assert builder.shapes_of_run(slots) == shapes
    assert builder.shapes_of_run(slots + 1) is None


def test_the_copied_reference_is_the_original(built, builder):
    from mxnet_tpu.models import pangu_moe_reference as original
    src, orig = inspect.getsource(builder), inspect.getsource(original)
    copied = src[src.index("VOCAB_BLOCK = "):src.index(
        "def _weights_and_config")]
    assert copied.strip() == orig[orig.index("VOCAB_BLOCK = "):orig.index(
        "def weights_of")].strip()
    # and it calls nothing of the program's models or ops
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


def test_the_new_readers_on_a_hand_built_run(built, builder, monkeypatch):
    shapes, net, srv, _ctx = built
    slots = sum(b.slots for b in srv.sched.buckets)
    peaks = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
    stamps = [1.0, 1.1, 1.2, 1.3]
    requests = [{"prompt_len": 10, "stamps": stamps, "done": 1.3,
                 "counted": True, "due": 0.9, "submit": 0.9},
                {"prompt_len": 4, "stamps": stamps, "done": 1.3,
                 "counted": True, "due": 0.9, "submit": 0.9}]
    rounds = [{"t0": t, "t1": t + 0.1, "admitted": 0, "tokens": 2,
               "active": 2} for t in (1.0, 1.1, 1.2)]
    obs = {"peaks": peaks, "chips": 1, "slots": slots, "window": (0.5, 2.0),
           "requests": requests, "rounds": rounds, "trace": None}
    # what the run's listener logged: the window's decode dispatches are
    # read, 3 of them = 12 expert-layer and 15 attention-layer calls; a
    # prefill, the probe's decodes before the window and the drain's
    # after it are not.  Columns: held assignments, experts touched, rows
    # routed, expert-layer calls, live positions, attended, attention calls
    calls = srv.statistics_listener
    assert calls.names == [n for n, _doc in net.statistics]
    monkeypatch.setattr(calls, "log", [
        (0.2, "decode", 1, [1., 1., 4., 4., 10., 800., 5.]),
        (0.3, "prefill", 1, [9., 3., 40., 4., 50., 80., 5.]),
        (1.1, "decode", 2, [8., 10., 8., 4., 100., 800., 5.]),
        (1.2, "prefill", 1, [7., 4., 28., 4., 35., 80., 5.]),
        (1.2, "decode", 2, [8., 10., 8., 4., 120., 800., 5.]),
        (1.3, "decode", 1, [9., 10., 8., 4., 140., 800., 5.]),
        (2.4, "decode", 1, [0., 0., 4., 4., 5., 800., 5.])])
    got = builder.decode_calls(obs)
    assert (got["dispatches"], got["mxtpu_mla_live_positions_total"],
            got["mxtpu_mla_page_positions_total"],
            got["mxtpu_mla_layer_calls_total"]) == (3, 360., 2400., 15.)
    assert read("latent_live_share.mla", obs) == pytest.approx(15.0)
    assert read("expert_tokens_per_round.mla", obs) == pytest.approx(
        25 / (12 * 4))
    assert read("experts_touched_share.mla", obs) == pytest.approx(
        100.0 * 30 / (12 * 4))
    # the mean row of those calls had written 360 / (15 x 4 slots)
    assert builder.mean_live_positions(obs) == pytest.approx(6.0)
    assert read("mfu.mla", obs) == pytest.approx(
        100.0 * (8 / 1.5) * builder.flops_per_token(shapes, 6.0) / 197e12)
    # a device's roofline has no host-clock reading: untraced, nothing
    assert read("decode_hbm_share.mla", obs) is None
    assert read("decode_mxu_share.mla", obs) is None
    from chipbench.harness import program_spans
    traced = dict(obs, trace={"window_s": 3.0}, **{program_spans.KEY: {
        "decode_only_rounds": [(0.1, 0.02)] * 3}})
    # 10 touched and 25 / 3 assignments a dispatch; the middle round's
    # two slots at 12 and 6 positions
    assert read("decode_hbm_share.mla", traced) == pytest.approx(
        100.0 * builder.decode_bytes_per_round(shapes, 2, [12, 6], 10.0)
        / (0.08 * 819e9))
    assert read("decode_mxu_share.mla", traced) == pytest.approx(
        100.0 * builder.decode_flops_per_round(shapes, 2, [12, 6], 25 / 3)
        / (0.08 * 197e12))
    # a run of another configuration's size, or without peaks: nothing
    for name in MLA:
        assert read(name, dict(traced, slots=slots + 1)) is None, name
    for name in ("decode_hbm_share.mla", "decode_mxu_share.mla", "mfu.mla"):
        assert read(name, dict(traced, peaks=None)) is None
    # nor from a window in which no decode dispatch was read
    for name in MLA:
        assert read(name, dict(traced, window=(3.0, 4.0))) is None, name


def test_a_program_without_the_counts_reads_nothing(builder, monkeypatch):
    """The parent of the PR that added them builds no such server: there is
    no listener, or one whose calls carry the expert layers' counts alone.
    The readers return None and do not raise."""
    from chipbench.models import afmoe_server
    obs = {"trace": None, "slots": 160, "peaks": None, "window": (0.0, 1.0),
           "requests": [], "rounds": [], "chips": 1}
    monkeypatch.setattr(afmoe_server, "CALLS", None)
    for name in MLA:
        assert read(name, obs) is None, name
    other = afmoe_server.Calls(["mxtpu_moe_assignments_held_total",
                                "mxtpu_moe_experts_touched_total",
                                "mxtpu_moe_routed_rows_total",
                                "mxtpu_moe_layer_calls_total"])
    other.log.append((0.5, "decode", 160, [80., 60., 640., 4.]))
    monkeypatch.setattr(afmoe_server, "CALLS", other)
    for name in MLA:
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


def test_the_limit_sits_between_the_stated_precision_and_the_one_below(
        cell, built, builder):
    """What the cell compares with passes its ``gap_share``, float8
    weights and latent rows are refused (``tools/afmoe_chip_check.py
    --cell`` reads the same on the chip)."""
    worst = {}
    for name in ("stated", "float8"):
        run, probe = _probed(cell, built, builder, name)
        worst[name] = probe["probe_worst_regret_share"]
        assert bool(run.checks.failed) == (name == "float8"), worst
    limit = cell[2]["rehearsal"]["probe"]["gap_share"]
    assert worst["stated"] <= limit < worst["float8"]


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
    assert "latent_live_share.mla" in out.stdout
