"""Profiler (mxnet_tpu/profiler.py) — direct tier-1 coverage.

Until PR 4 the profiler was only incidentally exercised through
``test_aux_subsystems.py``; this module owns its contract:

* op spans recorded while ``set_state('run')`` (engine hook wired and
  unwired), pause/resume gating;
* ``record_scope`` ranges and ``Marker`` instant events;
* ``span``: the one class behind every framework seam — both sinks
  (chrome events while running, ``jax.profiler`` annotations while a
  session is live), nothing at all with both off, and the phases of a
  ``DataParallelTrainer.step`` nested under their root;
* ``dump()`` chrome-trace JSON round-trip;
* ``dumps()`` aggregate table AND the (previously silently ignored)
  ``format_="json"`` mode; unknown formats raise.
"""
import glob
import json
import os

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import gluon, nd, parallel, profiler
from mxnet_tpu.base import MXNetError
from mxnet_tpu.gluon import nn


@pytest.fixture(autouse=True)
def _stopped():
    """Leave the profiler stopped and drained around every test."""
    yield
    profiler.set_state("stop")
    profiler.resume()
    with profiler._lock:
        profiler._events.clear()


def _run_some_ops():
    x = nd.array(np.random.rand(8, 8).astype("f4"))
    y = nd.dot(x, x) + x
    y.wait_to_read()
    return y


def test_op_spans_recorded_under_run(tmp_path):
    fname = str(tmp_path / "prof.json")
    profiler.set_config(filename=fname)
    assert profiler.state() == "stop"
    profiler.set_state("run")
    assert profiler.state() == "run" and profiler.active()
    _run_some_ops()
    profiler.set_state("stop")
    _run_some_ops()                       # after stop: NOT recorded
    profiler.dump()
    with open(fname) as f:
        trace = json.load(f)
    ops = [e for e in trace["traceEvents"] if e.get("cat") == "operator"]
    names = {e["name"] for e in ops}
    assert "dot" in names and "broadcast_add" in names
    # exactly one run's worth: the post-stop ops did not double it
    assert sum(1 for e in ops if e["name"] == "dot") == 1
    for e in ops:
        assert e["ph"] == "X" and e["dur"] >= 0


def test_pause_resume_gate():
    profiler.set_state("run")
    profiler.pause()
    _run_some_ops()
    assert not profiler.active()
    profiler.resume()
    _run_some_ops()
    profiler.set_state("stop")
    table = profiler.dumps(reset=True)
    # the paused window's ops are absent; the resumed window's present
    assert table.count("dot") == 1


def test_record_scope_and_marker(tmp_path):
    fname = str(tmp_path / "prof.json")
    profiler.set_config(filename=fname)
    profiler.set_state("run")
    with profiler.record_scope("my_step"):
        _run_some_ops()
    profiler.Marker("hit").mark()
    profiler.set_state("stop")
    profiler.dump()
    with open(fname) as f:
        events = json.load(f)["traceEvents"]
    scopes = [e for e in events if e.get("cat") == "scope"]
    assert [e["name"] for e in scopes] == ["my_step"]
    assert scopes[0]["ph"] == "X" and scopes[0]["dur"] > 0
    markers = [e for e in events if e.get("cat") == "marker"]
    assert [e["name"] for e in markers] == ["hit"]
    assert markers[0]["ph"] == "i"


def test_dump_chrome_trace_round_trip(tmp_path):
    fname = str(tmp_path / "trace.json")
    profiler.set_config(filename=fname)
    profiler.set_state("run")
    _run_some_ops()
    profiler.set_state("stop")
    profiler.dump()                       # finished=True drains
    with open(fname) as f:
        trace = json.load(f)
    assert trace["displayTimeUnit"] == "ms"
    assert all({"name", "ph", "ts", "pid"} <= set(e)
               for e in trace["traceEvents"])
    # drained: a second dump writes an empty trace
    profiler.dump()
    with open(fname) as f:
        assert json.load(f)["traceEvents"] == []


def test_dumps_table_and_json():
    profiler.set_state("run")
    _run_some_ops()
    profiler.Marker("m").mark()           # instant event: no duration
    profiler.set_state("stop")
    table = profiler.dumps()
    header = table.splitlines()[0]
    for col in ("Name", "Calls", "Total(us)", "Min(us)", "Max(us)",
                "Avg(us)"):
        assert col in header
    assert "dot" in table

    payload = json.loads(profiler.dumps(format_="json"))
    ops = payload["ops"]
    assert ops["dot"]["calls"] == 1
    assert ops["dot"]["total_us"] >= ops["dot"]["min_us"] >= 0
    assert "m" not in ops                 # markers carry no span
    # table and json aggregate the SAME events
    assert set(ops) == {line.split()[0]
                        for line in table.splitlines()[1:]}


def test_dumps_unknown_format_raises():
    with pytest.raises(MXNetError, match="unknown dumps format"):
        profiler.dumps(format_="xml")


# -- spans (docs/observability.md, "Spans") ----------------------------------

def _tiny_trainer():
    net = nn.HybridSequential()
    net.add(nn.Dense(32, in_units=16, activation="relu"), nn.Dense(1))
    net.initialize(mx.init.Xavier())
    loss_fn = gluon.loss.L2Loss()
    dpt = parallel.DataParallelTrainer(
        net, lambda o, l: loss_fn(o, l).mean(), "adam",
        {"learning_rate": 0.01}, mesh=parallel.make_mesh({"dp": 1}),
        fuse_step=True)
    data, label = nd.ones((8, 16)), nd.ones((8, 1))
    for _ in range(2):                    # trace + compile, then warm
        dpt.step(data, label).wait_to_read()
    return dpt, data, label


def _inside(child, parent):
    return parent["ts"] <= child["ts"] and \
        child["ts"] + child["dur"] <= parent["ts"] + parent["dur"]


@pytest.mark.time_limit(120)
def test_trainer_step_spans_nest_under_their_root():
    """Two steps give two ``mxtpu.trainer.step`` roots; each root's
    phases lie inside it on its thread, carry its ``step`` id, and
    leave under a fifth of it to no span."""
    dpt, data, label = _tiny_trainer()
    profiler.set_state("run")
    for _ in range(2):
        dpt.step(data, label).wait_to_read()
    profiler.set_state("stop")
    with profiler._lock:
        spans = [e for e in profiler._events
                 if e["name"].startswith("mxtpu.trainer.")]
    roots = [e for e in spans if e["name"] == "mxtpu.trainer.step"]
    assert len(roots) == 2 and all(e["cat"] == "spmd_step" for e in roots)
    assert roots[1]["args"]["step"] == roots[0]["args"]["step"] + 1
    top = {"prologue", "place_batch", "rng_key", "gather_args",
           "dispatch", "write_back"}
    covered = []
    for root in roots:
        mine = [e for e in spans if e is not root
                and e["args"]["step"] == root["args"]["step"]]
        assert {e["name"].rsplit(".", 1)[1] for e in mine} == \
            top | {"aval_sig", "execute"}
        assert all(_inside(e, root) and e["tid"] == root["tid"]
                   and e["cat"] == "trainer" for e in mine)
        dispatch, = [e for e in mine if e["name"].endswith(".dispatch")]
        assert all(_inside(e, dispatch) for e in mine
                   if e["name"].rsplit(".", 1)[1] in ("aval_sig",
                                                      "execute"))
        covered.append(sum(e["dur"] for e in mine
                           if e["name"].rsplit(".", 1)[1] in top)
                       / root["dur"])
    assert max(covered) >= 0.8, covered


@pytest.mark.time_limit(120)
def test_step_arg_gauges_count_what_a_step_hands_over():
    """Set once per step variant: every array leaf of one fused-step
    call, and those of them that are host numpy, which jax copies to
    the device on every step: the optimizer's scalars as ONE vector,
    whatever the number of parameters, and the health flag."""
    from mxnet_tpu import telemetry
    telemetry.reset()
    dpt, _data, _label = _tiny_trainer()
    gauges = telemetry.snapshot()["gauges"]
    n_params = len(dpt._params)
    assert n_params * len(dpt._rule.scalars(dpt.optimizer, 0, 1)) > 1
    # the health plane's sampling flag rides as one more host scalar
    host = 1 + (dpt._health_spec is not None)
    assert gauges["mxtpu_trainer_step_host_args"] == host
    # parameters, Adam's two moments each, data, label, rng key
    assert gauges["mxtpu_trainer_step_args"] == \
        n_params + 2 * n_params + host + 3


@pytest.mark.time_limit(120)
def test_spans_record_nothing_with_both_sinks_off():
    """Profiler stopped and no jax session: a step appends nothing."""
    dpt, data, label = _tiny_trainer()
    assert profiler.state() == "stop" and not profiler.recording()
    with profiler._lock:
        profiler._events.clear()
    dpt.step(data, label).wait_to_read()
    _run_some_ops()
    with profiler.span("user.scope", req=1):
        pass
    assert profiler._events == []


def test_record_scope_is_span_and_both_annotate(monkeypatch):
    """One class; while a jax session is live it enters the profiler's
    own annotation with its ids, a step root the step annotation."""
    assert profiler.record_scope is profiler.span
    made = []

    class Spy:
        def __init__(self, name, **kw):
            made.append((type(self).__name__, name, kw))

        def __enter__(self):
            made.append("enter")

        def __exit__(self, *exc):
            made.append("exit")

    monkeypatch.setattr(profiler, "_tracing", lambda: True)
    monkeypatch.setattr(profiler, "TraceAnnotation", Spy)
    monkeypatch.setattr(profiler, "StepTraceAnnotation",
                        type("StepSpy", (Spy,), {}))
    with profiler.record_scope("my_step"):
        pass
    with profiler.span("mxtpu.serving.admit", "serving", req=7, slot=1):
        pass
    with profiler.span("mxtpu.trainer.step", "spmd_step", step_num=3,
                       step=3):
        pass
    assert made == [
        ("Spy", "my_step", {}), "enter", "exit",
        ("Spy", "mxtpu.serving.admit", {"req": 7, "slot": 1}),
        "enter", "exit",
        ("StepSpy", "mxtpu.trainer.step", {"step_num": 3, "step": 3}),
        "enter", "exit"]
    assert profiler._events == []         # that sink stayed off


@pytest.mark.time_limit(180)
def test_spans_land_in_a_jax_profiler_trace(tmp_path):
    """One round trip through a real ``jax.profiler`` session on the
    CPU: the ``/host:CPU`` plane of the XPlane file holds the
    ``mxtpu.*`` spans with their ids — what a ``--trace 1`` run of the
    benchmark reads on the chip."""
    import jax
    dpt, data, label = _tiny_trainer()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        assert profiler.recording()
        dpt.step(data, label).wait_to_read()
        _run_some_ops()
    finally:
        jax.profiler.stop_trace()
    assert not profiler.recording()
    assert profiler._events == []         # chrome sink needs set_state
    path, = glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    host, = [p for p in jax.profiler.ProfileData.from_file(path).planes
             if p.name == "/host:CPU"]
    seen = {}
    for line in host.lines:
        for e in line.events:
            if e.name.startswith("mxtpu."):
                seen.setdefault(e.name, []).append(
                    {k: v for k, v in e.stats})
    assert {"mxtpu.trainer.step", "mxtpu.trainer.gather_args",
            "mxtpu.trainer.execute", "mxtpu.trainer.write_back",
            "mxtpu.engine.lookup", "mxtpu.engine.execute"} <= set(seen)
    root, = seen["mxtpu.trainer.step"]
    assert root["step_num"] == root["step"] == dpt._span_step
    assert seen["mxtpu.trainer.execute"][0]["step"] == root["step"]
    assert {s["op"] for s in seen["mxtpu.engine.execute"]} >= {"dot"}
