"""The reduction of the program's own spans, on hand-built lists, and that
every metric that reads them is found by name.

Run by hand: ``JAX_PLATFORMS=cpu python -m pytest chipbench/tests -q``.
"""
import json
import os
import sys

import pytest

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
sys.path.insert(0, ROOT)

from chipbench.harness import program_spans as ps, resolve  # noqa: E402

T, S, E = "mxtpu.trainer.", "mxtpu.serving.", "mxtpu.engine."

# one train step on thread 0, one helper span on thread 1, and a step
# that the window's edge (10.0) cuts
TRAIN = [
    (T + "step", 0, 1.0, 9.0), (T + "place_batch", 0, 1.5, 2.0),
    (T + "dispatch", 0, 3.0, 8.0), (T + "aval_sig", 0, 3.0, 4.0),
    (T + "execute", 0, 4.5, 7.5), (T + "write_back", 0, 8.0, 8.75),
    ("mxtpu.io.fetch", 1, 2.5, 6.0),
    (T + "step", 0, 9.5, 12.0), (T + "place_batch", 0, 9.6, 9.9)]


def test_parents_and_self_times():
    spans = ps.inside(TRAIN, 0.0, 10.0)
    assert len(spans) == 8          # the cut step went, its child stayed
    par = ps.parents(spans)
    assert par == [None, 0, 0, 2, 2, 0, None, None]
    kids = ps.children(par)
    assert kids[0] == [1, 2, 5] and kids[2] == [3, 4]
    selfs = ps.by_name(spans, ps.self_times(spans, kids))
    assert selfs[T + "step"] == [pytest.approx(8.0 - 0.5 - 5.0 - 0.75)]
    assert selfs[T + "dispatch"] == [pytest.approx(5.0 - 1.0 - 3.0)]
    assert selfs[T + "execute"] == [pytest.approx(3.0)]
    assert selfs[T + "place_batch"] == [pytest.approx(0.5),
                                        pytest.approx(0.3)]


def test_gaps_go_to_the_innermost_span_and_only_leaves_attribute():
    spans = ps.inside(TRAIN, 0.0, 10.0)
    par = ps.parents(spans)
    kids = ps.children(par)
    inner = ps.Innermost(spans, par)
    assert spans[inner.at(1.75)][0] == T + "place_batch"
    assert spans[inner.at(2.25)][0] == T + "step"      # between children
    assert spans[inner.at(4.25)][0] == T + "dispatch"  # fetch began sooner
    assert spans[inner.at(5.0)][0] == T + "execute"
    assert spans[inner.at(2.75)][0] == "mxtpu.io.fetch"    # other thread
    assert inner.at(0.5) is None and inner.at(9.25) is None
    # midpoints 1.75 (leaf), 2.25 (the root), 5.0 (leaf), 9.25 (nothing)
    gaps = [(1.5, 2.0), (2.0, 2.5), (4.0, 6.0), (9.0, 9.5)]
    idle, share = ps.name_gaps(gaps, spans, par, kids)
    assert idle == {T + "place_batch": 0.5, T + "step": 0.5,
                    T + "execute": 2.0, "(none)": 0.5}
    assert share == pytest.approx(2.5 / 3.5)
    assert ps.name_gaps([], spans, par, kids) == ({}, None)
    assert ps.overlap(gaps, 1.75, 4.5) == pytest.approx(0.25 + 0.5 + 0.5)


# two serving rounds: the first admits and decodes, the second only
# decodes, over two buckets; device ops leave gaps inside the second
SERVE = [
    (S + "round", 0, 0.0, 4.0), (S + "admit", 0, 0.25, 2.0),
    (S + "token_read", 0, 1.0, 1.75), (S + "decode", 0, 2.0, 4.0),
    (S + "token_read", 0, 3.0, 3.5),
    (S + "round", 0, 4.0, 9.0),
    (S + "decode", 0, 4.5, 6.5), (S + "build_inputs", 0, 4.5, 5.0),
    (S + "dispatch", 0, 5.0, 5.75), (E + "lookup", 0, 5.0, 5.25),
    (S + "token_read", 0, 5.75, 6.25), (S + "bookkeeping", 0, 6.25, 6.5),
    (S + "decode", 0, 6.5, 8.5), (S + "token_read", 0, 7.0, 8.0)]
OPS = [("", 0.0, 4.75), ("", 5.5, 6.375), ("", 7.0, 11.0)]


def test_the_whole_reduction_of_a_serving_trace():
    red = ps.reduce_spans(SERVE, (0.0, 9.0), OPS)
    assert red["window_s"] == 9.0
    assert red["admit_host_s"] == [pytest.approx(1.75 - 0.75)]
    # only the second round is decode-only: (2.0 - 0.5) + (2.0 - 1.0)
    assert red["decode_host_s"] == [pytest.approx(2.5)]
    # its device idle: 4.75-5.5 and 6.375-7.0
    assert red["decode_only_rounds"] == [
        (pytest.approx(5.0), pytest.approx(0.75 + 0.625))]
    assert red["durations"][E + "lookup"] == [pytest.approx(0.25)]
    # gap midpoints: 5.125 in engine.lookup (a leaf), 6.6875 in the last
    # decode before its token_read (not a leaf)
    assert red["idle_s"] == {E + "lookup": pytest.approx(0.75),
                             S + "decode": pytest.approx(0.625)}
    assert red["idle_named_share"] == pytest.approx(0.75 / 1.375)
    line = ps.summary(red)
    assert line["spans"][S + "decode"]["n"] == 3
    assert line["spans"][S + "round"]["self_median_ms"] == \
        pytest.approx((0.25 + 1.0) / 2 * 1e3)
    assert line["decode_only_rounds"]["host_median_ms"] == \
        pytest.approx(2500.0)
    # a program without spans, or a window that holds none whole
    assert ps.reduce_spans([], (0.0, 9.0), OPS) is None
    assert ps.reduce_spans(SERVE, (0.1, 3.9), OPS) is not None
    assert ps.reduce_spans(SERVE[:1], (0.1, 3.9), OPS) is None


# one admission a request since the program enqueues a prefill and reads
# its first token later: two ``admit`` spans that carry one ``req`` id
ADMITS = [
    (S + "round", 0, 0.0, 4.0, {"round": 1}),
    (S + "admit", 0, 0.25, 1.0, {"req": 7, "slot": 3}),     # enqueue
    (S + "admit", 0, 1.0, 1.5, {"req": 8, "slot": 4}),
    (S + "decode", 0, 1.5, 2.0, {}),
    (S + "admit", 0, 2.0, 3.0, {"req": 7}),                 # its read
    (S + "token_read", 0, 2.25, 2.75, {"req": 7}),
    (S + "admit", 0, 3.0, 3.25, {"req": 8}),
    (S + "admit", 0, 3.5, 3.75, {}),        # a program before ``req`` ids
    (S + "admit", 0, 3.75, 4.0),            # a recorded list without ids
    (S + "round", 0, 4.0, 9.0, {"round": 2}),
    (S + "admit", 0, 4.0, 6.0, {"req": 9}),     # enqueued inside ...
    (S + "admit", 0, 8.5, 9.5, {"req": 9})]     # ... read past the edge


def test_an_admission_is_the_sum_of_its_spans_by_request():
    red = ps.reduce_spans(ADMITS, (0.0, 9.0), [])
    # 7: 0.75 + (1.0 - 0.5 of waiting); 8: 0.5 + 0.25; two of their own;
    # 9 is cut by the window's edge and counts for nothing
    assert sorted(red["admit_host_s"]) == [
        pytest.approx(0.25), pytest.approx(0.25), pytest.approx(0.75),
        pytest.approx(1.25)]
    assert ps.summary(red)["admit_host_median_ms"] == pytest.approx(500.0)
    # a span before the window counts as one cut, like one past it
    late = ps.reduce_spans(ADMITS, (0.5, 9.0), [])
    assert sorted(late["admit_host_s"]) == [
        pytest.approx(0.25), pytest.approx(0.25), pytest.approx(0.75)]


# PR 27's metrics that read the program's spans and are in the benchmark
# today: at least these, each in at least one cell
SPAN_METRICS = [
    "step_place_batch_ms.train", "step_gather_args_ms.train",
    "step_execute_ms.train", "step_write_back_ms.train",
    "step_unattributed_ms.train", "decode_host_ms.chat",
    "decode_host_ms.offline", "admit_host_ms.chat", "admit_host_ms.offline",
    "queue_wait_p95_ms.chat", "engine_lookup_us.chat",
    "idle_named_share.train"]


def test_an_untraced_run_reads_no_file_and_prints_none_of_them(
        monkeypatch, capsys):
    def no_file(*_a, **_k):
        raise AssertionError("an untraced run opened a trace file")
    monkeypatch.setattr(ps, "newest_xplane", no_file)
    monkeypatch.setattr(ps, "read_spans", no_file)
    obs = {"trace": None, "requests": [], "window": (0.0, 1.0)}
    assert ps.of(obs) is None
    for name in SPAN_METRICS:
        read = resolve.load_module("layer_metrics", name).read
        assert read(obs) is None, name
    assert capsys.readouterr().out == ""


def test_every_span_metric_names_cells_that_report_what_it_moves(bench):
    layers = {"trainer", "serving", "engine + tiers", "device"}
    cells = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    by = {m["name"]: m for m in bench["per_layer"]}
    for m in (by[name] for name in SPAN_METRICS):
        assert m["layer"] in layers and m["source"] in (
            "program_counter", "device_trace")
        # one cell or several, each of which reports the metric it moves
        assert m["workloads"] and set(m["workloads"]) <= cells
        assert set(m["workloads"]) <= set(
            e2e[m["moves"]].get("workloads", cells)), m["name"]
    # ``Server.step`` enters the same spans for every model: every cell
    # that reports a closed loop's round reports the host's side of it,
    # and of an admission
    for name in ("decode_host_ms.offline", "admit_host_ms.offline"):
        assert by[name]["workloads"] == \
            by["decode_round_ms.offline"]["workloads"]


def test_every_span_metric_reads_its_span_from_the_reduction(capsys):

    def read(name, obs):
        return resolve.load_module("layer_metrics", name).read(obs)

    serve = {"trace": {"window_s": 9.0},
             ps.KEY: ps.reduce_spans(SERVE, (0.0, 9.0), OPS)}
    assert read("decode_host_ms.chat", serve) == pytest.approx(2500.0)
    assert read("decode_host_ms.offline", serve) == pytest.approx(2500.0)
    assert read("admit_host_ms.chat", serve) == pytest.approx(1000.0)
    assert read("engine_lookup_us.chat", serve) == pytest.approx(250e3)
    assert read("admit_host_ms.offline", serve) == pytest.approx(1000.0)
    # the share of the idle time a leaf span names is a metric of the
    # train cell alone; a serving run keeps it in its ``program_spans`` line
    assert read("idle_named_share.train", serve) == \
        pytest.approx(100 * 0.75 / 1.375)
    assert ps.summary(serve[ps.KEY])["idle_named_share"] == \
        pytest.approx(0.75 / 1.375)
    assert read("step_execute_ms.train", serve) is None     # no such span
    train = {"trace": {"window_s": 10.0},
             ps.KEY: ps.reduce_spans(TRAIN, (0.0, 10.0), [])}
    assert read("step_place_batch_ms.train", train) == pytest.approx(400.0)
    assert read("step_execute_ms.train", train) == pytest.approx(3000.0)
    assert read("step_write_back_ms.train", train) == pytest.approx(750.0)
    assert read("step_gather_args_ms.train", train) is None
    assert read("step_unattributed_ms.train", train) == \
        pytest.approx(1750.0)
    assert read("idle_named_share.train", train) is None    # no device op

    # the wait for a slot needs no trace: the program's own stamps, and
    # nothing from a program whose requests keep no ``admit_t``
    class Req:
        def __init__(self, submit_t, admit_t):
            self.submit_t, self.admit_t = submit_t, admit_t

    class OldReq:
        submit_t = 0.0

    obs = {"trace": None, "window": (0.0, 9.0), "requests": [
        {"counted": True, "req": Req(1.0, 1.5)},
        {"counted": True, "req": Req(2.0, 2.25)},
        {"counted": False, "req": Req(0.0, 8.0)},      # sent in the ramp
        {"counted": True, "req": Req(3.0, None)},      # never admitted
        {"counted": True, "req": None}]}               # refused
    assert read("queue_wait_p95_ms.chat", obs) == pytest.approx(487.5)
    obs["requests"] = [{"counted": True, "req": OldReq()}]
    assert read("queue_wait_p95_ms.chat", obs) is None
    assert capsys.readouterr().out == ""     # ``of`` found its result kept


def test_spans_of_a_real_profiler_session(tmp_path, monkeypatch, capsys):
    """A CPU round trip: the program's spans and the window's, with their
    threads and ids, out of an ``.xplane.pb`` that ``of`` finds as the
    newest under the trace root."""
    import jax
    from mxnet_tpu import profiler
    from chipbench.harness import xplane
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path / "cell"), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(xplane.WINDOW_SPAN):
            with profiler.span(S + "round", "serving", step_num=4, round=4):
                with profiler.span(S + "decode", "serving", bucket=256):
                    with profiler.span(S + "token_read", "serving"):
                        pass
    finally:
        jax.profiler.stop_trace()
    monkeypatch.setattr(ps, "TRACE_ROOT", str(tmp_path))
    trace = ps.read_spans(ps.newest_xplane())
    assert [s[0] for s in trace["spans"]] == [
        S + "round", S + "decode", S + "token_read"]
    assert len({s[1] for s in trace["spans"]}) == 1
    assert trace["spans"][0][4] == {"step_num": 4, "round": 4}
    assert trace["spans"][1][4] == {"bucket": 256}
    t0, t1 = trace["window"]
    assert t0 <= trace["spans"][0][2] and trace["spans"][0][3] <= t1
    assert trace["device_ops"] == []         # a CPU trace has no TPU plane
    obs = {"trace": {"window_s": t1 - t0}}
    red = ps.of(obs)
    assert len(red["decode_host_s"]) == 1 and red["idle_s"] == {}
    assert ps.of(obs) is red                 # read once, kept in obs
    lines = capsys.readouterr().out.splitlines()
    assert len(lines) == 1
    table = json.loads(lines[0])["program_spans"]["spans"]
    assert table[S + "decode"]["n"] == 1
