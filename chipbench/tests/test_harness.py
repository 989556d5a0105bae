"""The yardstick's own arithmetic, and that cells are found by name.

Run by hand: ``JAX_PLATFORMS=cpu python -m pytest chipbench/tests -q``
(not under ``tests/``: the tier-1 count is the program's, not the
benchmark's).
"""
import hashlib
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
sys.path.insert(0, ROOT)

from chipbench.harness import (  # noqa: E402
    readers, resolve, stats, traffic, xplane)

BENCH = resolve.load_benchmark()
CELLS = [(w["name"], w["chips"]) for w in BENCH["workloads"]]


# -- the trace reduction, on a hand-built event list --------------------------

OPS = [("fusion.1", 0.0, 1.0), ("fusion.2", 0.5, 1.5),      # overlap
       ("all-reduce.3", 3.0, 4.0), ("fusion.1", 4.0, 4.5),  # abutting
       ("copy.9", 7.0, 7.25), ("inner", 7.1, 7.2)]          # nested
HOST = [("bench.trace_window", 0.0, 10.0),
        ("bench.make_batch", 1.4, 2.0), ("bench.step_call", 2.0, 3.2),
        ("bench.step_call", 4.4, 9.0), ("bench.loss_read", 5.0, 6.5)]


def test_busy_union_counts_overlap_and_nesting_once():
    merged, busy = xplane.busy_union(OPS)
    assert merged == [[0.0, 1.5], [3.0, 4.5], [7.0, 7.25]]
    assert busy == pytest.approx(3.25)


def test_idle_gaps_and_their_host_spans():
    merged, _ = xplane.busy_union(OPS)
    gaps = xplane.idle_gaps(merged, 0.0, 10.0)
    assert gaps == [(1.5, 3.0), (4.5, 7.0), (7.25, 10.0)]
    # midpoints 2.25 (step_call), 5.75 (loss_read, the innermost of two
    # open spans) and 8.625 (step_call again)
    named = xplane.name_gaps(gaps, HOST, longest=2)
    assert named[:2] == [["bench.step_call", 2.75], ["bench.loss_read", 2.5]]
    sums = dict((n, d) for n, d in named[2:])
    assert sums["sum:bench.step_call"] == pytest.approx(4.25)
    assert sums["sum:bench.loss_read"] == pytest.approx(2.5)
    assert xplane.span_at(HOST, 9.5) == "bench.(none)"


def test_collective_sum_top_ops_and_the_whole_reduction():
    assert xplane.collective_seconds(OPS) == pytest.approx(1.0)
    assert xplane.top_ops(OPS, 2) == [["fusion.1", 1.5], ["fusion.2", 1.0]]
    trace = {"devices": {"/device:TPU:0": {"XLA Ops": OPS,
                                           "XLA Modules": [("m", 0, 9)]},
                         "/device:TPU:1": {"XLA Ops": OPS[:1]}},
             "host": HOST}
    red = xplane.reduce_trace(trace, chips=2)
    assert red["window_s"] == 10.0
    assert red["busy_s_per_chip"] == pytest.approx([3.25, 1.0])
    assert red["busy_s"] == pytest.approx(2.125)
    assert red["idle_share_dev0"] == pytest.approx(0.675)
    assert red["host_span_counts"]["bench.step_call"] == 2
    assert red["modules"] == [["m", 9]] and red["module_runs"] == {"m": 1}
    # clipped to the window span: an op that starts before it counts
    # only from its edge
    trace["host"] = [("bench.trace_window", 0.5, 4.25)]
    red = xplane.reduce_trace(trace, chips=1)
    assert red["busy_s"] == pytest.approx(1.0 + 1.25)
    assert xplane.reduce_trace({"devices": {}, "host": []}, 1) is None
    assert xplane.op_name("%fusion.3 = (bf16[2]{0}) fusion(%p)") == "fusion.3"


# -- percentiles and gaps, on a made-up token log -----------------------------

def _obs():
    def req(due, stamps, counted=True):
        return {"due": due, "submit": due + 0.001, "stamps": stamps,
                "counted": counted, "asked": len(stamps), "done": 1.0}
    reqs = [req(0.0, [0.10, 0.12, 0.15]), req(1.0, [1.30, 1.31]),
            req(2.0, [2.05, 2.45, 2.50, 2.55]),
            req(-1.0, [0.01, 0.02], counted=False),   # sent in the ramp
            req(3.0, [])]                             # never got a token
    return {"requests": reqs, "window": (0.0, 4.0), "t_end": 14.0}


def _e2e(name, obs):
    return resolve.load_module("end_to_end", name).read(obs)


def _layer(name, obs):
    return resolve.load_module("layer_metrics", name).read(obs)


def test_percentile_rule():
    assert stats.percentile([1, 2, 3, 4], 50) == 2.5
    assert stats.percentile([10], 95) == 10
    assert stats.percentile([0, 10], 95) == pytest.approx(9.5)
    assert stats.percentile([], 95) is None


def test_ttft_is_from_the_due_time_and_counts_the_starved():
    # waits: 0.10, 0.30, 0.05 and 11.0 for the request with no token
    want = stats.percentile([0.10, 0.30, 0.05, 11.0], 95) * 1e3
    assert _layer("ttft_p95_ms.chat", _obs()) == pytest.approx(want)


def test_itl_takes_every_gap_of_counted_requests():
    gaps = [0.02, 0.03, 0.01, 0.40, 0.05, 0.05]
    assert _layer("itl_p95_ms.chat", _obs()) \
        == pytest.approx(stats.percentile(gaps, 95) * 1e3)


def test_serve_tokens_counts_what_arrived_in_the_window():
    # 3 + 2 + 4 of the counted requests, + 2 of the ramp's request whose
    # tokens arrived inside the window; over 4 s
    assert _e2e("serve_tokens_per_s", _obs()) == pytest.approx(11 / 4.0)


def test_the_steady_rate_is_a_median_over_chunks_of_rounds(monkeypatch):
    # rounds of 1 s that each give 3 tokens and admit 1 request; the
    # fourth is held up for 9 s.  Chunks of 2 rounds: 8 tokens over 2, 10
    # and 2 s; the last two rounds only close the third chunk
    monkeypatch.setattr(readers, "STEADY_CHUNK", 2)
    t, rounds = 0.0, []
    for d in (1, 1, 1, 9, 1, 1, 1, 1):
        rounds.append({"t0": t, "t1": t + d, "tokens": 3, "admitted": 1})
        t += d
    obs = {"window": (0.0, 20.0), "rounds": rounds}
    assert _layer("steady_tokens_per_s.offline", obs) \
        == pytest.approx(stats.median([8 / 2, 8 / 10, 8 / 2]))
    assert _layer("steady_tokens_per_s.offline",
                  {"window": (0.0, 20.0), "rounds": rounds[:2]}) is None


# -- traffic from a seed ------------------------------------------------------

TRAFFIC = resolve.load_json(BENCH_DIR, "traffic", "chat_steady.json")


def _lengths(seed, n=300):
    s = traffic.request_stream(TRAFFIC, 32000, seed)
    return [(len(p), o, float(p[:4].sum())) for p, o in
            (next(s) for _ in range(n))]


def test_requests_reproduce_from_a_seed_and_differ_across_seeds():
    assert _lengths(2 ** 31 + 77) == _lengths(2 ** 31 + 77)
    a, b = _lengths(1), _lengths(2)
    assert a != b
    # ...but every seed offers the same sizes in another order: one block
    # is one permutation of the same strata
    n = TRAFFIC["length_block"]
    assert sorted(x[0] for x in a[:n]) == sorted(x[0] for x in b[:n])
    assert sorted(x[1] for x in a[:n]) == sorted(x[1] for x in b[:n])
    lens = [x[0] for x in a]
    assert min(lens) >= 16 and max(lens) <= 1024


def test_arrivals_reproduce_keep_their_rate_and_differ():
    arr = TRAFFIC["arrivals"]

    def times(seed):
        return traffic.arrival_times(10.0, arr["gaps"], arr["block"],
                                     traffic.rng_for(seed, 4), 0.0, 60.0)
    assert times(5) == times(5)
    assert times(5) != times(6)
    assert len(times(5)) == pytest.approx(600, rel=0.03)
    assert len(times(6)) == pytest.approx(600, rel=0.03)
    g = np.diff(times(5)[:arr["block"]])
    assert g.std() / g.mean() == pytest.approx(1.0, abs=0.15)  # Poisson


def test_strata_of_a_numpy_distribution_need_no_new_code():
    g = traffic.strata({"kind": "gamma", "args": {"shape": 0.25,
                                                  "scale": 4.0}}, 256)
    assert g.mean() == pytest.approx(1.0, rel=0.1)
    assert g.std() / g.mean() == pytest.approx(2.0, rel=0.15)


def test_bert_batch_labels_are_the_true_tokens():
    cdf = traffic.zipf_cdf(30522, 1.0)
    (tok, typ, pos), label = traffic.bert_batch(
        traffic.rng_for(3, 0), cdf, 4, 128, 20)
    assert tok.shape == typ.shape == (4, 128) and pos.shape == (4, 20)
    assert label.shape == (4, 21)
    for r in range(4):
        assert len(set(pos[r])) == 20
        assert (tok[r, pos[r].astype(int)] == label[r, :20]).all()
    assert tok.max() < 30522 and set(np.unique(typ)) <= {0.0, 1.0}


# -- cells are found by name; the command fails where it should --------------

def _run(argv, cwd=ROOT, devices=1, tmp=None, root=ROOT):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    env.pop("MXTPU_COMPILE_CACHE_DIR", None)
    if tmp is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp)
    if devices > 1:
        env["XLA_FLAGS"] = \
            f"--xla_force_host_platform_device_count={devices}"
    return subprocess.run(
        [sys.executable, os.path.join(root, "chipbench", "run.py")] + argv,
        cwd=cwd, env=env, capture_output=True, text=True, timeout=900)


def _lines(proc):
    return [json.loads(x) for x in proc.stdout.splitlines()
            if x.startswith("{")]


@pytest.mark.parametrize("cell,chips", CELLS)
def test_rehearsal_of_every_cell_runs_and_is_never_a_result(
        cell, chips, tmp_path):
    p = _run(["--workload", cell, "--seed", str(2 ** 31 + 5), "--seconds",
              "3", "--trace", "0", "--rehearse"], devices=chips,
             tmp=tmp_path)
    assert p.returncode == 3, p.stderr[-2000:]
    lines = _lines(p)
    assert not any("correct" in x for x in lines)       # no result line
    last = lines[-1]
    assert last["checks_failed"] == []
    want = {m["name"] for m in resolve.metrics_of(BENCH, "end_to_end", cell)}
    assert set(last["end_to_end"]) == want


def test_without_a_chip_the_command_fails_before_building_anything(
        tmp_path):
    p = _run(["--workload", CELLS[0][0], "--seed", "1", "--seconds", "1",
              "--trace", "0"], tmp=tmp_path)
    assert p.returncode == 2
    lines = _lines(p)
    assert len(lines) == 1 and "start" in lines[0]      # nothing was built
    assert "not a TPU" in p.stderr


def _digest(root):
    out = {}
    for d, _dirs, files in os.walk(root):
        if "__pycache__" in d:
            continue
        for f in files:
            path = os.path.join(d, f)
            with open(path, "rb") as fh:
                out[os.path.relpath(path, root)] = \
                    hashlib.sha256(fh.read()).hexdigest()
    return out


def test_a_cell_is_added_with_new_files_and_entries_only(tmp_path):
    """A later PR's cell: a new configuration file, a new traffic file, a
    new per-layer reader, and entries in BENCHMARK.json — no file that is
    there is edited, and the harness finds all three by name."""
    root = tmp_path / "checkout"
    shutil.copytree(BENCH_DIR, root / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = _digest(root / "chipbench")

    cfg = resolve.load_json(BENCH_DIR, "configs", "bert_base.json")
    cfg["name"] = "bert_new"
    (root / "chipbench/configs/bert_new.json").write_text(json.dumps(cfg))
    tr = resolve.load_json(BENCH_DIR, "traffic", "pretrain_s128.json")
    tr["rehearsal"]["seq"] = 16
    (root / "chipbench/traffic/pretrain_new.json").write_text(json.dumps(tr))
    (root / "chipbench/layer_metrics/make_batch_ms.new.py").write_text(
        "from chipbench.harness import readers\n\n\n"
        "def read(obs):\n"
        "    return readers.window_span_ms(obs, 'bench.make_batch')\n")

    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append({
        "name": "bert_new", "source": cfg["source"], "reduced": [],
        "file": "chipbench/configs/bert_new.json", "why": "a newcomer"})
    bench["workloads"].append({
        "name": "bert_new.pretrain_new", "config": "bert_new",
        "traffic": "pretrain_new", "chips": 1, "why": "a newcomer"})
    bench["end_to_end"][0]["workloads"].append("bert_new.pretrain_new")
    bench["per_layer"].append({
        "name": "make_batch_ms.new", "unit": "ms", "better": "lower",
        "source": "host_clock", "layer": "entry points",
        "moves": "train_tokens_per_s",
        "workloads": ["bert_new.pretrain_new"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    p = _run(["--workload", "bert_new.pretrain_new", "--seed", "7",
              "--seconds", "2", "--trace", "0", "--rehearse"],
             cwd=str(root), tmp=tmp_path / "cache", root=str(root))
    assert p.returncode == 3, p.stderr[-2000:]
    last = _lines(p)[-1]
    assert last["per_layer"]["make_batch_ms.new"]["value"] > 0
    assert "train_tokens_per_s" in last["end_to_end"]
    after = _digest(root / "chipbench")
    assert {k: v for k, v in after.items() if k in before} == before
    assert sorted(set(after) - set(before)) == [
        "configs/bert_new.json", "layer_metrics/make_batch_ms.new.py",
        "traffic/pretrain_new.json"]


def test_an_unknown_name_is_an_error_that_names_it():
    with pytest.raises(resolve.ResolveError, match="no_such_cell"):
        resolve.cell(BENCH, "no_such_cell")
    with pytest.raises(resolve.ResolveError, match="no_such_metric"):
        resolve.load_module("layer_metrics", "no_such_metric")


def test_every_metric_and_cell_of_benchmark_json_resolves():
    for w in BENCH["workloads"]:
        _w, _cfg, tr = resolve.cell(BENCH, w["name"])
        resolve.load_module("drivers", tr["driver"])
        resolve.load_module("models", _cfg["builder"])
    names = {m["name"] for m in BENCH["end_to_end"]}
    for group, sub in resolve.GROUP_DIRS.items():
        for m in BENCH[group]:
            assert callable(resolve.load_module(sub, m["name"]).read)
            if group == "per_layer":
                assert m["moves"] in names
