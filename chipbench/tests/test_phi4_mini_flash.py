"""The ``phi4_mini_flash`` configuration and its cell: found by name, the
source's numbers kept, the byte and operation counts tied to the model
the program builds, and the copied reference held to the original.

Run by hand: ``JAX_PLATFORMS=cpu python -m pytest chipbench/tests -q``.
"""
import inspect
import os
import sys

import numpy as np
import pytest

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
sys.path.insert(0, ROOT)

from chipbench.harness import resolve  # noqa: E402

CELL = "phi4_mini_flash.reason_closed"
BENCH = resolve.load_benchmark()
# the catalog row's ``config`` (model-configs guide, architectures.jsonl)
CATALOG = {"embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
           "intermediate_size": 10240, "layer_norm_eps": 1e-05,
           "max_position_embeddings": 262144, "mb_per_layer": 2,
           "model_type": "phi4flash", "num_attention_heads": 40,
           "num_hidden_layers": 32, "num_key_value_heads": 20,
           "resid_pdrop": 0, "sliding_window": 512,
           "tie_word_embeddings": True, "mlp_bias": False,
           "lm_head_bias": False, "vocab_size": 200064}


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
    assert workload["chips"] == 1 and config["reduced"] == []
    assert traffic["driver"] == "serve_loop"
    assert traffic["arrivals"] == {"kind": "closed", "clients": 128}
    assert config["serving"]["buckets"] == [[96, 512]]
    for fn in ("build_server", "n_params", "full_forward_logits",
               "decode_bytes_per_round", "flops_per_token"):
        assert callable(getattr(builder, fn))
    assert resolve.cell(bench, CELL) == cell
    names = {m["name"] for g in ("end_to_end", "per_layer")
             for m in resolve.metrics_of(bench, g, CELL)}
    # the closed-loop reductions are the accepted cell's own entries, with
    # this cell appended: one name a reduction, no twin files.  A floor:
    # what the cell reports at least, whatever later PRs add to it
    assert names >= {
        "serve_tokens_per_s", "setup_s", "compile_s",
        "decode_round_ms.offline", "occupancy.offline",
        "steady_tokens_per_s.offline", "device_idle_share.offline",
        "state_bytes_per_slot.reason", "decode_hbm_share.reason",
        "mfu.reason"}
    for g, sub in resolve.GROUP_DIRS.items():
        for m in resolve.metrics_of(bench, g, CELL):
            assert callable(resolve.load_module(sub, m["name"]).read)
    by = {m["name"]: m for m in bench["per_layer"]}
    # the state gauge's list began with this cell; its two own entries
    for name in ("decode_hbm_share.reason", "mfu.reason"):
        assert (by[name]["workloads"][0], by[name]["moves"],
                by[name]["layer"]) == (
            CELL, "serve_tokens_per_s", "ops / kernels")
    assert by["state_bytes_per_slot.reason"]["workloads"][0] == CELL


def test_the_file_holds_every_number_of_the_source(cell, bench):
    config = cell[1]
    assert {k: config[k] for k in CATALOG} == CATALOG
    entry = next(c for c in bench["configs"] if c["name"] == config["name"])
    assert entry["reduced"] == [] and entry["file"].endswith(
        "phi4_mini_flash.json")
    assert config["source"] in entry["source"]


def test_counts_at_the_published_shapes(cell, builder):
    config = cell[1]
    counts = builder.param_counts(config)
    assert round(sum(counts.values()) / 1e9, 2) == 3.85
    per = builder.state_bytes_per_slot(config)
    assert per == {"kv_full": 7864320, "kv_window": 20971520,
                   "ssm": 2949120, "conv": 276480}
    full = builder.decode_bytes_per_round(config, 96, 1536)
    assert round(full / 1e9, 1) == 16.4
    # live bytes never pass the dense pages' bytes, and grow with position
    assert builder.decode_bytes_per_round(config, 96, 4000) == full
    assert builder.decode_bytes_per_round(config, 96, 600) < full
    assert builder.decode_bytes_per_round(config, 40, [600] * 40) == \
        builder.decode_bytes_per_round(config, 40, 600)
    assert builder.flops_per_token(config) == 2 * counts["matrices"]


def test_counts_are_the_built_models(built, builder):
    shapes, net, srv, _ctx = built
    assert sum(builder.param_counts(shapes).values()) == \
        builder.n_params(net)
    pool, = srv._pools.values()
    slots = pool.slots
    by = pool.bytes_by_kind()
    assert {k: v * slots for k, v in
            builder.state_bytes_per_slot(shapes).items()} == by
    # every slot active and full: layer n/2+1's K,V once for itself and
    # once for each cross layer, SSM and conv read and written, the
    # weights once
    cross = sum(1 for layer in net.model.layers if layer.kind == "cross")
    want = builder.n_params(net) * 2 + by["kv_full"] * (1 + cross) \
        + by["kv_window"] + 2 * (by["ssm"] + by["conv"])
    assert builder.decode_bytes_per_round(
        shapes, slots, pool.cache_len) == want


def test_the_copied_reference_is_the_original(built, builder):
    from mxnet_tpu.models import sambay_reference as original
    for name in ("layer_kind", "lambda_init", "_rounded", "_mm",
                 "_layer_norm", "_silu", "_softplus", "_mlp", "_mamba",
                 "_diff_attention", "_causal", "forward_logits"):
        assert inspect.getsource(getattr(builder, name)) == \
            inspect.getsource(getattr(original, name)), name
    assert builder.PRECISIONS == original.PRECISIONS
    _shapes, net, _srv, ctx = built
    tokens = np.random.default_rng(3).integers(1, 256, 20)
    cfg = {"num_hidden_layers": 8, "num_attention_heads": 8,
           "num_key_value_heads": 4, "sliding_window": 8,
           "layer_norm_eps": 1e-5}
    # ``correct`` compares at the precision the configuration states
    for args, precision in (((), "stated"), (("float32",), "float32")):
        got = builder.full_forward_logits(net, tokens, ctx, *args)
        want = original.forward_logits(original.weights_of(net, ctx),
                                       tokens, cfg, precision)
        np.testing.assert_array_equal(got, want)
    # and it calls nothing of the program's models or ops
    src = inspect.getsource(builder)
    ref_part = src[src.index("VOCAB_BLOCK = "):]
    assert "mxnet_tpu" not in ref_part.split("def full_forward_logits")[0]


def test_the_new_readers_on_a_hand_built_run(built, builder):
    shapes, _net, srv, _ctx = built
    slots = sum(b.slots for b in srv.sched.buckets)
    peaks = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
    # two requests decode side by side through three decode-only rounds
    stamps = [1.0, 1.1, 1.2, 1.3]
    requests = [{"prompt_len": 10, "stamps": stamps, "done": 1.3,
                 "counted": True, "due": 0.9, "submit": 0.9},
                {"prompt_len": 4, "stamps": stamps, "done": 1.3,
                 "counted": True, "due": 0.9, "submit": 0.9}]
    rounds = [{"t0": t, "t1": t + 0.1, "admitted": 0, "tokens": 2,
               "active": 2} for t in (1.0, 1.1, 1.2)]
    obs = {"peaks": peaks, "chips": 1, "slots": slots, "window": (0.5, 2.0),
           "requests": requests, "rounds": rounds, "trace": None}
    hbm = resolve.load_module("layer_metrics", "decode_hbm_share.reason")
    # a device's roofline has no host-clock reading: untraced, nothing
    assert hbm.read(obs) is None
    # traced: three decode-only rounds of 100 ms, the device idle 20 of them
    from chipbench.harness import program_spans
    traced = dict(obs, trace={"window_s": 3.0}, **{program_spans.KEY: {
        "decode_only_rounds": [(0.1, 0.02)] * 3}})
    want_bytes = builder.decode_bytes_per_round(shapes, 2, [12, 6])
    assert hbm.read(traced) == pytest.approx(
        100.0 * want_bytes / (0.08 * 819e9))
    mfu = resolve.load_module("layer_metrics", "mfu.reason")
    assert mfu.read(obs) == pytest.approx(
        100.0 * (8 / 1.5) * builder.flops_per_token(shapes) / 197e12)
    # a run of another configuration's size, or without peaks: nothing
    assert hbm.read(dict(traced, slots=slots + 1)) is None
    assert mfu.read(dict(obs, peaks=None)) is None
    gauge = resolve.load_module("layer_metrics",
                                "state_bytes_per_slot.reason")
    pool, = srv._pools.values()
    assert gauge.read(obs) == pool.nbytes() / slots


def test_the_limit_sits_between_the_stated_precision_and_the_one_below(
        cell, built, builder):
    """The harness's own comparison (``serve_loop._probe``) over the
    rehearsal shapes, once a precision of the reference: what the cell
    compares with passes its ``gap_share``, float8 weights and K,V are
    refused (``tools/sambay_chip_check.py`` reads the same on the chip)."""
    import types
    from functools import partial
    from chipbench.drivers import serve_loop
    from chipbench.harness import runtime
    workload, config, traffic = cell
    shapes, net, srv, ctx = built
    worst = {}
    for name in ("stated", "float8"):
        run = runtime.Run(
            types.SimpleNamespace(seed=11, seconds=0.0, trace=0,
                                  rehearse=True, sweep=None),
            workload, config, traffic, None, 0.0)
        shim = types.SimpleNamespace(full_forward_logits=partial(
            builder.full_forward_logits, precision=name))
        probe = serve_loop._probe(run, net, srv, ctx, shim,
                                  serve_loop.Loop(srv, run.spans))
        worst[name] = probe["probe_worst_regret_share"]
        assert bool(run.checks.failed) == (name == "float8"), worst
    limit = traffic["rehearsal"]["probe"]["gap_share"]
    assert worst["stated"] <= limit < worst["float8"]
