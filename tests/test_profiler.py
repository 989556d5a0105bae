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
  ``format_="json"`` mode; unknown formats raise;
* the device half: ``scope_of`` / ``scopes_of_text`` read the
  ``mxtpu.*`` scope out of an ``op_name``, ``reduce_device`` (the pure
  part of ``device_dumps``) sums device time by program and scope on a
  hand-built event list.
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
    """Six steps give six ``mxtpu.trainer.step`` roots; each root's
    phases lie inside it on its thread, carry its ``step`` id, and
    leave under a fifth of it to no span (in the best of the six: a warm
    toy step is under a millisecond, and on a loaded host one pause of
    the process between two phases is a fifth of that)."""
    dpt, data, label = _tiny_trainer()
    profiler.set_state("run")
    for _ in range(6):
        dpt.step(data, label).wait_to_read()
    profiler.set_state("stop")
    with profiler._lock:
        spans = [e for e in profiler._events
                 if e["name"].startswith("mxtpu.trainer.")]
    roots = [e for e in spans if e["name"] == "mxtpu.trainer.step"]
    assert len(roots) == 6 and all(e["cat"] == "spmd_step" for e in roots)
    assert [e["args"]["step"] for e in roots] == \
        list(range(roots[0]["args"]["step"], roots[0]["args"]["step"] + 6))
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


# -- device time by scope (the pure parts) ------------------------------------

@pytest.mark.parametrize("op_name, want", [
    ("jit(f)/mxtpu.mlp/jit(<unknown>)/dot_general", ("mxtpu.mlp", False)),
    # the backward twin that value_and_grad makes
    ("jit(f)/transpose(jvp(mxtpu.mlp))/dot_general", ("mxtpu.mlp", True)),
    ("jit(f)/jvp(mxtpu.mlp)/dot_general", ("mxtpu.mlp", False)),
    # the innermost scope wins
    ("jit(f)/mxtpu.mixer.mla/mxtpu.mixer.mla.attend/exp",
     ("mxtpu.mixer.mla.attend", False)),
    # a fusion's op_name may join several paths: the first one counts
    ("jit(f)/mxtpu.head/add;jit(f)/transpose(jvp(mxtpu.mlp))/mul",
     ("mxtpu.head", False)),
    ("jit(f)/transpose(jvp(mxtpu.loss))/mul;jit(f)/mxtpu.mlp/add",
     ("mxtpu.loss", True)),
    ("jit(f)/broadcast_in_dim", None),
])
def test_scope_of_an_op_name(op_name, want):
    assert profiler.scope_of(op_name) == want


def test_scopes_of_text_reads_module_and_instructions():
    text = """HloModule jit_decode_b48x256, is_scheduled=true, entry={...}

%fused_computation (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  ROOT %add.7 = f32[4]{0} add(%p, %p), metadata={op_name="jit(decode_b48x256)/mxtpu.mlp/add"}
}

ENTRY %main (x: f32[4], w: f32[4]) -> f32[4] {
  %x = f32[4]{0} parameter(0), metadata={op_name="x"}
  %w = f32[4]{0} parameter(1), metadata={op_name="w"}
  %copy-start.2 = (f32[4]{0}, f32[4]{0}, u32[]) copy-start(%w)
  %copy.1 = f32[4]{0} copy(%x)
  %copy-done.2 = f32[4]{0} copy-done(%copy-start.2)
  %plain.4 = f32[4]{0} add(%x, %x), metadata={op_name="jit(decode_b48x256)/add"}
  %fusion.3 = f32[4]{0} fusion(%x, %copy-done.2), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(decode_b48x256)/mxtpu.mlp/add;jit(decode_b48x256)/mxtpu.head/mul"}
  ROOT %while.56 = f32[4]{0} while(%fusion.3), condition=%c, body=%b, metadata={op_name="jit(decode_b48x256)/mxtpu.mixer.mamba/while"}
}
"""
    module, table = profiler.scopes_of_text(text)
    assert module == "jit_decode_b48x256"
    mlp, inherited = ("mxtpu.mlp", False, False), ("mxtpu.mlp", False, True)
    assert table == {
        "add.7": mlp, "p": inherited, "fusion.3": mlp,
        "while.56": ("mxtpu.mixer.mamba", False, False),
        # what the compiler inserted FOR the fusion (no op_name of its
        # own) counts with it, through the start / done chain, and says
        # that the name is its consumer's
        "copy-done.2": inherited, "copy-start.2": inherited}
    # inserted and consumed by nothing scoped; named, but under no scope
    assert "copy.1" not in table and "plain.4" not in table


# two programs that both hold ``fusion.1``, under different scopes; an
# enclosing ``while`` whose body ops are events of their own; a module
# the process holds no executable of
_OPS = [("fusion.1", 0.0, 1.0), ("while.2", 1.0, 4.0),
        ("fusion.3", 1.5, 2.0), ("fusion.3", 2.5, 3.0),
        ("copy.9", 4.0, 4.5),
        ("fusion.1", 10.0, 11.0), ("fusion.4", 11.0, 11.5),
        ("fusion.1", 20.0, 21.0), ("while.2", 21.0, 24.0),
        ("fusion.3", 21.5, 22.0), ("fusion.3", 22.5, 23.0),
        ("copy.9", 24.0, 24.5),
        ("fusion.1", 30.0, 32.0)]
_RUNS = [("jit_full_step(123)", 0.0, 5.0), ("jit_decode_b2x8(9)", 10.0, 12.0),
         ("jit_full_step(123)", 20.0, 25.0), ("jit_other(7)", 30.0, 32.0)]
_MAP = {"jit_full_step": {"fusion.1": ("mxtpu.mlp", False, False),
                          "while.2": ("mxtpu.mixer.mamba", False, False),
                          "fusion.3": ("mxtpu.mixer.mamba", True, False)},
        "jit_decode_b2x8": {"fusion.1": ("mxtpu.head", False, False)}}


@pytest.fixture(scope="module")
def reduced():
    return profiler.reduce_device(_OPS, _RUNS, _MAP)


def test_reduce_device_joins_an_op_to_the_map_of_its_own_program(reduced):
    progs = reduced["programs"]
    assert set(progs) == {"jit_full_step", "jit_decode_b2x8", "jit_other"}
    step, decode = progs["jit_full_step"], progs["jit_decode_b2x8"]
    assert (step["runs"], decode["runs"]) == (2, 1)
    assert step["ms_per_run"] == pytest.approx(5000.0)
    # ``fusion.1`` is the MLP in one program and the head in the other
    assert step["scopes"]["mxtpu.mlp"]["ms_per_run"] == pytest.approx(1000.0)
    assert decode["scopes"]["mxtpu.head"]["ms_per_run"] == \
        pytest.approx(1000.0)
    assert "mxtpu.mlp" not in decode["scopes"]
    # an op the map does not name
    assert step["scopes"][profiler.NO_SCOPE]["ms_per_run"] == \
        pytest.approx(500.0)
    assert decode["scopes"][profiler.NO_SCOPE]["top"] == \
        [["fusion.4", pytest.approx(500.0)]]


def test_reduce_device_counts_an_enclosing_op_by_its_self_time(reduced):
    mamba = reduced["programs"]["jit_full_step"]["scopes"][
        "mxtpu.mixer.mamba"]
    # the while is 3 s of which its two body ops take 1: self 2 (forward,
    # by its own op_name), the body ops backward
    assert mamba["forward_ms"] == pytest.approx(2000.0)
    assert mamba["backward_ms"] == pytest.approx(1000.0)
    assert mamba["ms_per_run"] == pytest.approx(3000.0)
    assert mamba["ops"] == 2
    assert mamba["top"][0] == ["while.2", pytest.approx(2000.0)]
    # what the loop is, bodies included: the trace's flat ``while.2``
    assert mamba["enclosing"] == [["while.2", pytest.approx(3000.0)]]
    assert reduced["programs"]["jit_full_step"]["scopes"][
        "mxtpu.mlp"]["enclosing"] == []


def test_reduce_device_names_a_program_without_a_map(reduced):
    other = reduced["programs"]["jit_other"]
    assert list(other["scopes"]) == [profiler.UNKNOWN_PROGRAM]
    assert other["scopes"][profiler.UNKNOWN_PROGRAM]["ms_per_run"] == \
        pytest.approx(2000.0)


def test_reduce_device_scopes_sum_to_the_busy_time(reduced):
    for prog in reduced["programs"].values():
        assert sum(s["ms_per_run"] for s in prog["scopes"].values()) == \
            pytest.approx(prog["busy_ms_per_run"])
        for s in prog["scopes"].values():
            assert s["forward_ms"] + s["backward_ms"] == \
                pytest.approx(s["ms_per_run"])
    # nested events count once: 4.5 + 1.5 + 4.5 + 2 s of ops
    assert reduced["busy_ms"] == pytest.approx(12500.0)
    assert sum(p["busy_share"] for p in reduced["programs"].values()) == \
        pytest.approx(1.0)
    assert reduced["programs"]["jit_full_step"]["busy_share"] == \
        pytest.approx(9.0 / 12.5)


def test_reduce_device_keeps_inherited_time_apart_within_a_scope(reduced):
    """A ``copy-done`` the compiler inserted carries no name: the map
    gives it its consumer's, and the table says how much of a scope's
    time is of that kind, so the named part can be read alone."""
    red = profiler.reduce_device(
        [("fusion.1", 0.0, 1.0), ("copy-done.5", 1.0, 1.5),
         ("copy-done.6", 1.5, 1.75)],
        [("jit_full_step(123)", 0.0, 2.0)],
        {"jit_full_step": {"fusion.1": ("mxtpu.mlp", False, False),
                           "copy-done.5": ("mxtpu.mlp", True, True)}})
    mlp = red["programs"]["jit_full_step"]["scopes"]["mxtpu.mlp"]
    assert mlp["ms_per_run"] == pytest.approx(1500.0)
    assert mlp["inherited_ms"] == pytest.approx(500.0)
    assert (mlp["forward_ms"], mlp["backward_ms"]) == \
        (pytest.approx(1000.0), pytest.approx(500.0))
    # consumed by nothing scoped: under no scope, inherited from nobody
    bare = red["programs"]["jit_full_step"]["scopes"][profiler.NO_SCOPE]
    assert (bare["ms_per_run"], bare["inherited_ms"]) == \
        (pytest.approx(250.0), 0.0)
    assert all(s["inherited_ms"] == 0.0 for p in reduced["programs"].values()
               for s in p["scopes"].values())


def test_reduce_device_keeps_an_op_outside_every_run_apart():
    red = profiler.reduce_device([("fusion.1", 6.0, 7.0)], _RUNS, _MAP)
    assert list(red["programs"]) == [profiler.OUTSIDE_RUNS]


def test_device_dumps_wants_a_trace_and_a_known_format(tmp_path):
    with pytest.raises(MXNetError, match="unknown dumps format"):
        profiler.device_dumps(str(tmp_path), format_="yaml")
    with pytest.raises(MXNetError, match="no .xplane.pb"):
        profiler.device_dumps(str(tmp_path))


def test_device_dumps_reads_a_trace_without_a_device_plane(tmp_path):
    """A CPU trace has no ``/device:`` plane with ``XLA Ops``: the table
    is empty, not an error (device numbers come from a chip)."""
    import jax
    jax.profiler.start_trace(str(tmp_path))
    _run_some_ops()
    jax.profiler.stop_trace()
    out = json.loads(profiler.device_dumps(str(tmp_path)))
    assert out["path"].endswith(".xplane.pb")
    assert out["programs"] == {} and out["busy_ms"] == 0
    assert set(out["seconds"]) == {"map", "read", "reduce"}
    assert out["shadowed"] == []
    table = profiler.device_dumps(str(tmp_path), format_="table")
    assert table.startswith("Program / scope")
