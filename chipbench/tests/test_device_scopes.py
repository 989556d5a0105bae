"""The readers of device time by scope, on a hand-built table, and that
every metric that reads it is found by name in ``BENCHMARK.json`` (the
15 entries PR 37 wrote and PR 39 moved in), each held to its layer, its
end-to-end metric and AT LEAST the cells it began with.

Run by hand: ``JAX_PLATFORMS=cpu python -m pytest chipbench/tests -q``.
"""
import json
import os
import sys

import pytest

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
sys.path.insert(0, ROOT)

from chipbench import run_held                                # noqa: E402
from chipbench.harness import device_scopes as ds, resolve  # noqa: E402

TRAIN = "bert_base.pretrain_s128"
CHAT = "mistral_7b_l8.chat_steady"
PANGU = "pangu_ultra_moe_ep16.longgen_closed"
CLOSED = ["mistral_7b_l8.offline_closed", "phi4_mini_flash.reason_closed",
          "trinity_large_ep8.decode_closed", PANGU]
# name -> (layer, the end-to-end metric it moves, the cells its list of
# ``workloads`` BEGINS with: a later closed cell appends itself)
NEW = {
    "step_device_ms.train": ("trainer", "train_tokens_per_s", [TRAIN]),
    "attention_device_ms.train":
        ("ops / kernels", "train_tokens_per_s", [TRAIN]),
    "mlp_device_ms.train": ("ops / kernels", "train_tokens_per_s", [TRAIN]),
    "embed_head_device_ms.train":
        ("ops / kernels", "train_tokens_per_s", [TRAIN]),
    "optimizer_device_ms.train": ("trainer", "train_tokens_per_s", [TRAIN]),
    "planes_device_ms.train": ("trainer", "train_tokens_per_s", [TRAIN]),
    "unscoped_device_share.train":
        ("device", "train_tokens_per_s", [TRAIN]),
    "unscoped_device_share.offline":
        ("device", "serve_tokens_per_s", [CHAT] + CLOSED),
    "prefill_busy_share.chat": ("serving", "serve_tokens_per_s", [CHAT]),
    "prefill_busy_share.offline": ("serving", "serve_tokens_per_s", CLOSED),
    "prefill_device_ms.offline": ("serving", "serve_tokens_per_s", CLOSED),
    "decode_device_ms.offline": ("serving", "serve_tokens_per_s", CLOSED),
    "decode_mixer_ms.offline":
        ("ops / kernels", "serve_tokens_per_s", CLOSED),
    "decode_ffn_ms.offline": ("ops / kernels", "serve_tokens_per_s", CLOSED),
    "attend_roofline_share.mla":
        ("ops / kernels", "serve_tokens_per_s", [PANGU]),
}


def read(name, obs):
    return resolve.load_module("layer_metrics", name).read(obs)


def row(ms, fwd=None):
    fwd = ms if fwd is None else fwd
    return {"ms_per_run": ms, "forward_ms": fwd, "backward_ms": ms - fwd,
            "ops": 1, "top": []}


def program(runs, ms_per_run, busy_share, scopes):
    return {"runs": runs, "ms_per_run": ms_per_run,
            "busy_ms_per_run": sum(r["ms_per_run"] for r in scopes.values()),
            "busy_share": busy_share, "scopes": scopes}


def traced(table):
    return {"trace": {"window_s": 3.0}, ds.KEY: table}


def test_the_15_entries_name_cells_layers_and_metrics_that_exist(bench):
    listed = [m["name"] for m in bench["per_layer"]]
    cells = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    # a layer that the benchmark named before these entries came
    layers = {m["layer"] for m in bench["per_layer"] if m["name"] not in NEW}
    # all 15, once each, in the order PR 37 wrote them
    at = [listed.index(name) for name in NEW]
    assert len(NEW) == 15 and at == sorted(at)
    assert all(listed.count(name) == 1 for name in NEW)
    for (name, (layer, moves, workloads)), i in zip(NEW.items(), at):
        m = bench["per_layer"][i]
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert (m["layer"], m["moves"]) == (layer, moves), name
        assert m["workloads"][:len(workloads)] == workloads, name
        assert layer in layers and set(m["workloads"]) <= cells
        # each cell listed reports the metric this one moves
        assert set(m["workloads"]) <= set(e2e[moves]["workloads"])
        assert m["source"] == "device_trace"
        assert m["better"] == ("higher" if "roofline" in name else "lower")
        assert m["unit"] == ("%" if "share" in name else "ms")
        assert callable(resolve.load_module("layer_metrics", name).read)


def test_each_cell_reads_its_part_of_the_15(bench):
    """At least: train 7, the open loop 2, a closed loop 6, and the
    latent attention's roofline beside them in its cell."""
    def mine(cell):
        return [m["name"] for m in resolve.metrics_of(
            bench, "per_layer", cell) if m["name"] in NEW]
    for cell, want in ((TRAIN, 7), (CHAT, 2), (CLOSED[0], 6), (PANGU, 7)):
        assert len(mine(cell)) >= want, (cell, mine(cell))
    # every cell that reads a decode program's time reads its split too
    by = {m["name"]: m for m in bench["per_layer"]}
    for cell in by["decode_device_ms.offline"]["workloads"]:
        assert len(mine(cell)) >= 6, (cell, mine(cell))


def test_the_former_side_entry_adds_nothing_to_the_benchmark(bench):
    """``run_held.py`` and ``held_per_layer.json`` stay for the documents
    that name them: each entry once held is an entry of the benchmark,
    equal but for the cells that joined its ``workloads`` since, so the
    side entry runs a cell as ``run.py`` does."""
    held = resolve.load_json(BENCH_DIR, "held_per_layer.json")
    by = {m["name"]: m for m in bench["per_layer"]}
    assert [m["name"] for m in held] == list(NEW)
    for m in held:
        n = len(m["workloads"])
        assert dict(by[m["name"]],
                    workloads=by[m["name"]]["workloads"][:n]) == m
    assert run_held.with_held(bench) == bench
    # an entry that the benchmark lacks would still be read
    less = dict(bench, per_layer=[m for m in bench["per_layer"]
                                  if m["name"] != held[0]["name"]])
    assert run_held.with_held(less)["per_layer"] == \
        less["per_layer"] + held[:1]


def test_an_untraced_run_opens_no_file_and_prints_none_of_them(
        monkeypatch, capsys):
    def no_read(*_a, **_k):
        raise AssertionError("an untraced run asked for the device table")
    monkeypatch.setattr(ds, "read_table", no_read)
    obs = {"trace": None, "window": (0.0, 1.0), "slots": 160,
           "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}}
    assert ds.of(obs) is None
    for name in NEW:
        assert read(name, obs) is None, name
    assert capsys.readouterr().out == ""


def test_a_program_without_the_reader_reads_none(monkeypatch, capsys):
    """The parent of the PR that added ``profiler.device_dumps``."""
    from mxnet_tpu import profiler
    monkeypatch.delattr(profiler, "device_dumps")
    obs = {"trace": {"window_s": 3.0}, "window": (0.0, 1.0)}
    assert ds.of(obs) is None
    for name in NEW:
        assert read(name, obs) is None, name
    assert capsys.readouterr().out == ""


def test_the_table_is_read_once_and_printed_once(monkeypatch, capsys):
    from mxnet_tpu import profiler
    calls = []

    def dumps(logdir=None, format_="json"):
        calls.append(logdir)
        return json.dumps({"busy_ms": 1.0, "programs": {}})
    monkeypatch.setattr(profiler, "device_dumps", dumps)
    obs = {"trace": {"window_s": 3.0}}
    assert ds.of(obs) == ds.of(obs) == {"busy_ms": 1.0, "programs": {}}
    assert len(calls) == 1 and calls[0].endswith(
        os.path.join(".chipbench", "trace"))
    lines = capsys.readouterr().out.splitlines()
    assert len(lines) == 1 and "device_scopes" in json.loads(lines[0])

    # a reader that is there and raises fails the traced run: it must
    # not pass for a run that was not traced
    def broken(logdir=None, format_="json"):
        raise RuntimeError("no trace")
    monkeypatch.setattr(profiler, "device_dumps", broken)
    with pytest.raises(RuntimeError, match="no trace"):
        ds.of({"trace": {"window_s": 3.0}})


def test_the_train_readers_split_one_step():
    step = program(60, 45.0, 0.99, {
        "mxtpu.mixer.full": row(16.0, 5.0), "mxtpu.mlp": row(17.0, 6.0),
        "mxtpu.embed": row(1.0), "mxtpu.head": row(3.0),
        "mxtpu.loss": row(0.5), "mxtpu.step.optimizer": row(4.0),
        "mxtpu.step.health": row(0.25),
        "mxtpu.step.integrity": row(0.25), "(no scope)": row(2.0)})
    obs = traced({"busy_ms": 60 * 44.0 / 0.99, "programs": {
        "jit_full_step": step,
        # a variant that ran once (a sampled health step): not the step
        "jit_full_step_other": program(1, 99.0, 0.0, {}),
        "jit__threefry_split": program(60, 0.01, 0.01, {
            "(unknown program)": row(0.44 / 0.99)})}})
    assert read("step_device_ms.train", obs) == pytest.approx(45.0)
    assert read("attention_device_ms.train", obs) == pytest.approx(16.0)
    assert read("mlp_device_ms.train", obs) == pytest.approx(17.0)
    assert read("embed_head_device_ms.train", obs) == pytest.approx(4.5)
    assert read("optimizer_device_ms.train", obs) == pytest.approx(4.0)
    assert read("planes_device_ms.train", obs) == pytest.approx(0.5)
    # unnamed: the step's 2 ms and the helper program's 0.44 a step
    assert read("unscoped_device_share.train", obs) == \
        pytest.approx(100.0 * (2.0 + 0.44 / 0.99) / (44.0 / 0.99))
    # a serving reader finds no program of its kind here
    assert read("decode_device_ms.offline", obs) is None
    assert read("prefill_busy_share.offline", obs) == pytest.approx(0.0)


def test_the_serving_readers_weigh_two_buckets_by_their_runs():
    def decode(runs, ms, share):
        return program(runs, ms, share, {
            "mxtpu.mixer.swa": row(0.4 * ms), "mxtpu.mlp": row(0.3 * ms),
            "mxtpu.moe.experts": row(0.1 * ms), "mxtpu.moe": row(0.05 * ms),
            "mxtpu.head": row(0.1 * ms), "(no scope)": row(0.05 * ms)})
    obs = traced({"busy_ms": 3000.0, "programs": {
        "jit_decode_b48x256": decode(100, 6.0, 0.2),
        "jit_decode_b24x1024": decode(300, 8.0, 0.4),
        "jit_prefill_b48x256": program(50, 4.0, 0.1, {
            "mxtpu.mixer.swa": row(4.0)}),
        "jit_prefill_b24x1024": program(50, 20.0, 0.3, {
            "mxtpu.mixer.swa": row(20.0)})}})
    assert read("decode_device_ms.offline", obs) == pytest.approx(7.5)
    assert read("prefill_device_ms.offline", obs) == pytest.approx(12.0)
    assert read("prefill_busy_share.offline", obs) == pytest.approx(40.0)
    assert read("prefill_busy_share.chat", obs) == pytest.approx(40.0)
    assert read("decode_mixer_ms.offline", obs) == pytest.approx(3.0)
    assert read("decode_ffn_ms.offline", obs) == pytest.approx(0.45 * 7.5)
    named = 100 * 6.0 * 0.95 + 300 * 8.0 * 0.95 + 50 * 4.0 + 50 * 20.0
    assert read("unscoped_device_share.offline", obs) == \
        pytest.approx(100.0 * (1.0 - named / 3000.0))
    assert read("step_device_ms.train", obs) is None
    assert read("attend_roofline_share.mla", obs) is None   # no such scope


def test_scope_prefixes_take_what_lies_below_and_nothing_beside():
    p = program(1, 1.0, 1.0, {
        "mxtpu.mixer.mla": row(1.0), "mxtpu.mixer.mla.attend": row(2.0),
        "mxtpu.mlp": row(4.0), "mxtpu.mlpx": row(8.0)})
    assert ds.scope_ms(p, "mxtpu.mixer") == pytest.approx(3.0)
    assert ds.scope_ms(p, "mxtpu.mixer.mla.attend") == pytest.approx(2.0)
    assert ds.scope_ms(p, "mxtpu.mlp", "mxtpu.moe") == pytest.approx(4.0)


def test_the_attention_roofline_is_the_live_rows_bound_over_the_scope(
        monkeypatch):
    """160 rows, 1,047 live positions each, five layer calls a run: the
    bound is 5 x 0.237 ms (HBM and MXU within 1% of one another), and a
    measured 3.13 ms under the scope reads 38%."""
    server = resolve.load_module("models", "pangu_moe_server")
    shapes = server.shapes_of_run(160)
    live = 160 * 1047
    calls = {"dispatches": 100, "mxtpu_mla_layer_calls_total": 500,
             "mxtpu_mla_live_positions_total": 500 * live}
    monkeypatch.setattr(server.routed, "decode_calls", lambda obs: calls)
    # ``read`` loads the builder anew by path: patch what it will see
    monkeypatch.setattr(
        resolve, "load_module",
        lambda sub, name, _real=resolve.load_module:
        server if name == "pangu_moe_server" else _real(sub, name))
    obs = traced({"busy_ms": 3000.0, "programs": {
        "jit_decode_b160x1024": program(100, 22.4, 0.7, {
            "mxtpu.mixer.mla.attend": row(3.13),
            "mxtpu.mixer.mla.project": row(2.4)})}})
    obs.update(slots=160, window=(0.0, 51.0),
               peaks={"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12})
    flops = live * server.attention_flops_per_position(shapes) / 197e12
    hbm = live * 1152 / 819e9
    assert hbm == pytest.approx(0.2356e-3, rel=1e-3)
    assert flops == pytest.approx(0.2369e-3, rel=1e-3)
    got = resolve.load_module(
        "layer_metrics", "attend_roofline_share.mla").read(obs)
    assert got == pytest.approx(100 * 5 * flops * 1e3 / 3.13)
    assert 35 < got < 41
