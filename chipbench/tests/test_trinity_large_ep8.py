"""The ``trinity_large_ep8`` configuration and its cell: found by name, the
source's numbers kept, the cut stated, the byte and operation counts tied
to the model the program builds, and the copied reference held to the
original.

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

CELL = "trinity_large_ep8.decode_closed"
BENCH = resolve.load_benchmark()
PERIOD = ["sliding_attention"] * 3 + ["full_attention"]
# the catalog row's ``config`` (model-configs guide, architectures.jsonl)
CATALOG = {
    "global_attn_every_n_layers": 4, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 3072, "intermediate_size": 12288,
    "layer_types": PERIOD * 15, "load_balance_coeff": 5e-05,
    "max_position_embeddings": 262144, "model_type": "afmoe",
    "moe_intermediate_size": 3072, "mup_enabled": True, "n_group": 1,
    "num_attention_heads": 48, "num_dense_layers": 6,
    "num_expert_groups": 1, "num_experts": 256, "num_experts_per_tok": 4,
    "num_hidden_layers": 60, "num_key_value_heads": 8,
    "num_limited_groups": 1, "num_shared_experts": 1, "rms_norm_eps": 1e-05,
    "rope_scaling": None, "rope_theta": 10000, "route_norm": True,
    "route_scale": 2.448, "score_func": "sigmoid", "sliding_window": 4096,
    "tie_word_embeddings": False, "topk_group": 1, "use_grouped_mm": True,
    "vocab_size": 200192}
REDUCED = {"num_hidden_layers": 5, "num_dense_layers": 1,
           "layer_types": (PERIOD * 2)[:5], "num_experts": 32,
           "vocab_size": 25024}
MOE = [name + ".moe" for name in (
    "expert_tokens_per_round", "experts_touched_share", "decode_hbm_share",
    "mfu")]


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
    return shapes, net, srv, ctx


def test_the_cell_its_files_and_its_metrics_resolve_by_name(
        cell, builder, bench):
    workload, config, traffic = cell
    assert workload["chips"] == 1
    assert traffic["driver"] == "serve_loop"
    # 1.3 clients a slot
    slots = config["serving"]["buckets"][0][0]
    assert traffic["arrivals"] == {"kind": "closed",
                                   "clients": round(1.3 * slots)}
    assert config["serving"]["buckets"] == [[slots, 512]]
    assert slots % 16 == 0 and config["serving"]["max_new_tokens"] == 1024
    for fn in ("build_server", "n_params", "shapes_of_run", "param_counts",
               "state_bytes_per_slot", "flops_per_token",
               "full_forward_logits", "decode_bytes_per_round"):
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
        "state_bytes_per_slot.reason"} | set(MOE)
    for g, sub in resolve.GROUP_DIRS.items():
        for m in resolve.metrics_of(bench, g, CELL):
            assert callable(resolve.load_module(sub, m["name"]).read)
    # the four entries this cell brought, by NAME (a later cell's entry
    # may end ``.moe`` too), in the order it brought them
    listed = [m["name"] for m in bench["per_layer"]]
    at = [listed.index(name) for name in MOE]
    assert at == sorted(at)
    assert [(m["workloads"][0], m["moves"], m["layer"])
            for m in (bench["per_layer"][i] for i in at)] == [
        (CELL, "serve_tokens_per_s", "ops / kernels")] * 4


def test_the_file_holds_the_source_and_states_the_cut(cell, bench):
    config = cell[1]
    entry = next(c for c in bench["configs"] if c["name"] == config["name"])
    assert sorted(entry["reduced"]) == sorted(config["reduced"]) \
        == sorted(REDUCED)
    # every key of the source, unchanged unless listed; never a width
    assert {k: config[k] for k in CATALOG if k not in REDUCED} \
        == {k: v for k, v in CATALOG.items() if k not in REDUCED}
    assert {k: config[k] for k in REDUCED} == REDUCED
    assert config["layer_types"] == CATALOG["layer_types"][:5]
    assert config["published"] == {
        k: CATALOG[k] for k in ("num_experts", "vocab_size",
                                "num_hidden_layers", "num_dense_layers")}
    assert config["vocab_size"] * 8 == CATALOG["vocab_size"]
    assert config["num_experts"] * 8 == CATALOG["num_experts"]
    assert config["source"] in entry["source"] and len(entry["source"]) <= 200
    assert "8 chips share each layer" in config["deployment"]
    for key in ("attention_gate", "qk_norm", "positions", "mup_enabled",
                "norm_gains", "selection_bias", "weights", "experts_held",
                "ids"):
        assert config["assumed"][key]


def test_counts_at_the_published_shapes(cell, builder):
    config = cell[1]
    counts = builder.param_counts(config)
    assert round((sum(counts.values()) - counts["vectors"]) / 1e6, 1) \
        == 4321.8
    assert round(counts["experts"] * 2 / 1e9, 2) == 7.25
    per = builder.state_bytes_per_slot(config)
    assert per == {"kv_full": 6291456, "kv_window": 25165824}
    assert sum(per.values()) == 31457280
    slots = config["serving"]["buckets"][0][0]
    # every expert touched, every slot full: every parameter once but the
    # embedding's rows that no slot looks up, and the whole state
    full = builder.decode_bytes_per_round(config, slots, 1536, 4 * 32)
    assert full == (sum(counts.values())
                    - (config["vocab_size"] - slots) * 3072) * 2 \
        + slots * 31457280
    # live bytes never pass the dense pages' bytes, grow with position
    # and with the experts touched
    assert builder.decode_bytes_per_round(config, slots, 4000, 128) == full
    assert builder.decode_bytes_per_round(config, slots, 600, 128) < full
    assert full - builder.decode_bytes_per_round(config, slots, 1536, 127) \
        == 3 * 3072 * 3072 * 2
    assert builder.decode_bytes_per_round(config, 40, [600] * 40, 100) == \
        builder.decode_bytes_per_round(config, 40, 600, 100)
    # two a matrix weight this chip applies to a token, 1/8 of top-4 here
    assert builder.flops_per_token(config) == 2 * (
        counts["matrices"] + 4 * 0.5 * 3 * 3072 * 3072)
    assert round(builder.flops_per_token(config) / 1e9, 2) == 1.36


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
    want = (builder.n_params(net) - (shapes["vocab_size"] - slots) * 64) \
        * 2 + by["kv_full"] + by["kv_window"]
    assert builder.decode_bytes_per_round(
        shapes, slots, pool.cache_len, 4 * 4) == want
    assert builder.shapes_of_run(slots) == shapes
    assert builder.shapes_of_run(slots + 1) is None


def test_the_copied_reference_is_the_original(built, builder):
    from mxnet_tpu.models import afmoe_reference as original
    for name in ("_f32", "_rounded", "_mm", "_rms", "_silu", "_sigmoid",
                 "_swiglu", "_rope", "_routed", "forward_logits"):
        assert inspect.getsource(getattr(builder, name)) == \
            inspect.getsource(getattr(original, name)), name
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
    # the same numbers, at the precision the configuration states and in
    # float32, with the share the program holds
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
    shapes, _net, srv, _ctx = built
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

    def read(name, o):
        return resolve.load_module("layer_metrics", name).read(o)

    # what the run's listener logged: the window's decode dispatches are
    # read, 3 of them = 12 expert-layer calls over 4 held experts, 25
    # assignments on them, 30 touched; a prefill, the probe's decodes
    # before the window and the drain's after it are not
    calls = srv.statistics_listener
    assert calls.names == [n for n, _doc in _net.statistics]
    monkeypatch.setattr(calls, "log", [
        (0.2, "decode", 1, [1., 1., 4., 4.]),
        (0.3, "prefill", 1, [9., 3., 40., 4.]),
        (1.1, "decode", 2, [8., 10., 8., 4.]),
        (1.2, "prefill", 1, [7., 4., 28., 4.]),
        (1.2, "decode", 2, [8., 10., 8., 4.]),
        (1.3, "decode", 1, [9., 10., 8., 4.]),
        (2.4, "decode", 1, [0., 0., 4., 4.])])
    assert builder.decode_calls(obs) == {
        "mxtpu_moe_assignments_held_total": 25.,
        "mxtpu_moe_experts_touched_total": 30.,
        "mxtpu_moe_routed_rows_total": 24., "mxtpu_moe_layer_calls_total": 12.,
        "dispatches": 3, "rows": 5}
    assert read("expert_tokens_per_round.moe", obs) == pytest.approx(
        25 / (12 * 4))
    share = 100.0 * 30 / (12 * 4)
    assert read("experts_touched_share.moe", obs) == pytest.approx(share)
    # a device's roofline has no host-clock reading: untraced, nothing
    assert read("decode_hbm_share.moe", obs) is None
    from chipbench.harness import program_spans
    traced = dict(obs, trace={"window_s": 3.0}, **{program_spans.KEY: {
        "decode_only_rounds": [(0.1, 0.02)] * 3}})
    want_bytes = builder.decode_bytes_per_round(
        shapes, 2, [12, 6], share / 100.0 * 4 * 4)
    assert read("decode_hbm_share.moe", traced) == pytest.approx(
        100.0 * want_bytes / (0.08 * 819e9))
    assert read("mfu.moe", obs) == pytest.approx(
        100.0 * (8 / 1.5) * builder.flops_per_token(shapes) / 197e12)
    # a run of another configuration's size, or without peaks: nothing
    assert read("decode_hbm_share.moe", dict(traced, slots=slots + 1)) is None
    assert read("mfu.moe", dict(obs, peaks=None)) is None
    assert read("mfu.moe", dict(obs, slots=slots + 1)) is None
    # nor from a window in which no decode dispatch was read
    assert read("experts_touched_share.moe",
                dict(obs, window=(3.0, 4.0))) is None


def test_a_program_without_the_counts_reads_nothing(builder, monkeypatch):
    """The parent of the PR that added them builds no such server and has
    no listener: the readers return None and do not raise."""
    monkeypatch.setattr(builder._shared(), "CALLS", None)
    obs = {"trace": None, "slots": 160, "peaks": None, "window": (0.0, 1.0)}
    for name in ("expert_tokens_per_round.moe", "experts_touched_share.moe",
                 "decode_hbm_share.moe"):
        assert resolve.load_module("layer_metrics", name).read(obs) is None


def _probed(cell, built, builder, precision="stated"):
    """The harness's own comparison (``serve_loop._probe``) over the
    rehearsal shapes -> (the run, what the probe returned, the tokens the
    reference was given)."""
    import types
    from chipbench.drivers import serve_loop
    from chipbench.harness import runtime
    workload, config, traffic = cell
    _shapes, net, srv, ctx = built
    run = runtime.Run(
        types.SimpleNamespace(seed=11, seconds=0.0, trace=0,
                              rehearse=True, sweep=None),
        workload, config, traffic, None, 0.0)
    seen = {}

    def reference(net_, toks, ctx_):
        seen["tokens"] = np.asarray(toks)
        return builder.full_forward_logits(net_, toks, ctx_,
                                           precision=precision)

    srv.statistics_listener.arm()           # this probe's picks
    probe = serve_loop._probe(
        run, net, srv, ctx,
        types.SimpleNamespace(full_forward_logits=reference),
        serve_loop.Loop(srv, run.spans))
    return run, probe, seen["tokens"]


def test_the_limit_sits_between_the_stated_precision_and_the_one_below(
        cell, built, builder):
    """What the cell compares with passes its ``gap_share``, float8
    weights and K,V are refused (``tools/afmoe_chip_check.py`` reads the
    same on the chip)."""
    worst = {}
    for name in ("stated", "float8"):
        run, probe, _tokens = _probed(cell, built, builder, name)
        worst[name] = probe["probe_worst_regret_share"]
        assert bool(run.checks.failed) == (name == "float8"), worst
    limit = cell[2]["rehearsal"]["probe"]["gap_share"]
    assert worst["stated"] <= limit < worst["float8"]


@pytest.mark.parametrize("where", ["prompt", "generated"])
def test_one_refused_pick_anywhere_fails_the_run(cell, built, builder,
                                                 monkeypatch, capsys, where):
    """A served pick that differs from the reference's own where the
    reference's margin is at least tau is refused WHEREVER it lies: the
    harness reads the rows of the generated tokens alone, so one refused
    decision at a prompt position has to turn every row."""
    import json
    _shapes, net, _srv, ctx = built
    prompt_len = cell[2]["rehearsal"]["probe"]["prompt_len"]
    tau = cell[1]["rehearsal"]["probe"]["route_margin_tau"]
    real = builder.served_picks
    planted = {}

    def one_wrong(net_, n):
        chosen = real(net_, n).copy()
        weights, cfg, held = builder._weights_and_config(net_, ctx)
        routing = {}
        builder.forward_logits(weights, planted["tokens"][:n], cfg,
                               "stated", held, selections=chosen,
                               routing=routing)
        rows = slice(0, prompt_len - 1) if where == "prompt" \
            else slice(prompt_len - 1, n)
        # the decision the reference is surest of, among those rows
        margin = routing["margin"][rows]
        pos, layer = np.unravel_index(np.argmax(margin), margin.shape)
        assert margin[pos, layer] >= tau
        pos += rows.start
        row = chosen[pos, layer]
        row[0] = next(e for e in range(16) if e not in row)
        planted["at"] = int(pos)
        return chosen

    # the sound run first: the tokens the planted run will serve again
    run, _probe, planted["tokens"] = _probed(cell, built, builder)
    assert not run.checks.failed
    capsys.readouterr()
    monkeypatch.setattr(builder, "served_picks", one_wrong)
    run, probe, tokens = _probed(cell, built, builder)
    assert (tokens == planted["tokens"]).all()      # greedy, same weights
    said = [json.loads(line) for line in capsys.readouterr().out.splitlines()
            if '"refused"' in line][-1]
    assert said["refused"] == 1 and said["rows_negated"] == len(tokens)
    assert (planted["at"] < prompt_len - 1) == (where == "prompt")
    assert run.checks.failed, probe


def test_the_cell_rehearses_on_the_cpu():
    """``--rehearse`` runs the cell's own code at the toy shapes to its
    end: exit 3 (never a number), nothing failed."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH_DIR, "run.py"), "--workload",
         CELL, "--rehearse", "--seed", "2147491203", "--seconds", "2"],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=600)
    assert out.returncode == 3, out.stderr[-2000:]
    assert '"checks_failed": []' in out.stdout
    assert "expert_tokens_per_round.moe" in out.stdout
