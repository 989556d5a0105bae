"""Runtime telemetry plane (docs/observability.md).

Tier-1 coverage for ``mxnet_tpu.telemetry``:

* metrics registry: counter/gauge/histogram semantics, fixed buckets,
  snapshot shape;
* exporters: Prometheus text and JSONL both round-trip the snapshot;
* disabled plane: no events, no metric mutations (the near-zero
  contract is behavioral — a disabled process records NOTHING);
* retrace-cause attribution: engine-level shape/attr diffs, and the
  CompiledStep momentum-drift case naming the exact changed attr;
* flight recorder: ring bounded by MXTPU_FLIGHT_RECORDER_SIZE, dump
  artifact produced on a poisoned CompiledStep and on demand;
* step-level wiring: dispatches-per-step == 1 through the compiled
  path, prefetch stall ratio from the DataLoader pipeline;
* mxlint runtime pass: MXL306 carries the attributed cause, MXL307
  fires on a stalling loader.
"""
import json
import os
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import engine, gluon, nd, telemetry
from mxnet_tpu.base import MXNetError


@pytest.fixture(autouse=True)
def _clean_telemetry():
    """Each test starts with an enabled, empty plane and leaves it
    enabled (other test modules record through module-level state)."""
    telemetry.enable()
    telemetry.reset()
    yield
    telemetry.enable()
    telemetry.reset()


def _mlp(dropout=0.0):
    net = gluon.nn.HybridSequential()
    with net.name_scope():
        net.add(gluon.nn.Dense(8, activation="relu", in_units=6),
                gluon.nn.Dense(3, in_units=8))
    net.initialize(mx.init.Xavier())
    net.hybridize()
    return net


def _data(n=4):
    rng = np.random.RandomState(0)
    return (nd.array(rng.randn(n, 6).astype("f4")),
            nd.array(rng.randn(n, 3).astype("f4")))


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------


def test_counter_gauge_histogram_semantics():
    c = telemetry.counter("t_c", "doc")
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    with pytest.raises(ValueError):
        c.inc(-1)
    assert telemetry.counter("t_c") is c  # idempotent registration
    with pytest.raises(TypeError):
        telemetry.gauge("t_c")            # kind mismatch is an error

    g = telemetry.gauge("t_g")
    g.set(7)
    g.dec(3)
    assert g.value == 4.0

    h = telemetry.histogram("t_h", buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.5, 5.0, 50.0):
        h.observe(v)
    s = h.summary()
    assert s["count"] == 4 and s["min"] == 0.05 and s["max"] == 50.0
    # cumulative bucket counts over the FIXED boundaries
    assert s["buckets"] == [(0.1, 1), (1.0, 2), (10.0, 3)]
    with pytest.raises(ValueError):
        telemetry.histogram("t_bad", buckets=(1.0, 1.0))

    snap = telemetry.snapshot()
    assert snap["counters"]["t_c"] == 3.5
    assert snap["gauges"]["t_g"] == 4.0
    assert snap["histograms"]["t_h"]["count"] == 4


def test_prometheus_round_trip():
    telemetry.counter("rt_ops_total").inc(5)
    telemetry.gauge("rt_depth").set(3)
    h = telemetry.histogram("rt_lat", buckets=(0.5, 2.0))
    h.observe(0.1)
    h.observe(1.0)
    h.observe(9.0)
    text = telemetry.to_prometheus()
    parsed = telemetry.parse_prometheus(text)
    # counters keep the _total convention without doubling the suffix
    assert parsed["rt_ops_total"] == 5.0
    assert parsed["rt_depth"] == 3.0
    assert parsed["rt_lat_bucket"]["0.5"] == 1.0
    assert parsed["rt_lat_bucket"]["2"] == 2.0
    assert parsed["rt_lat_bucket"]["+Inf"] == 3.0
    assert parsed["rt_lat_count"] == 3.0
    assert abs(parsed["rt_lat_sum"] - 10.1) < 1e-9


def test_jsonl_round_trip(tmp_path):
    telemetry.counter("jl_c").inc(2)
    telemetry.histogram("jl_h", buckets=(1.0,)).observe(0.5)
    path = str(tmp_path / "metrics.jsonl")
    n = telemetry.write_jsonl(path)
    rows = telemetry.read_jsonl(path)
    assert len(rows) == n
    by_name = {r["name"]: r for r in rows}
    assert by_name["jl_c"]["type"] == "counter"
    assert by_name["jl_c"]["value"] == 2.0
    assert by_name["jl_h"]["count"] == 1
    # append semantics: a second export adds a second generation
    telemetry.counter("jl_c").inc()
    telemetry.write_jsonl(path)
    rows2 = telemetry.read_jsonl(path)
    assert len(rows2) == 2 * n
    gens = [r["value"] for r in rows2 if r["name"] == "jl_c"]
    assert gens == [2.0, 3.0]


# ---------------------------------------------------------------------------
# disabled plane
# ---------------------------------------------------------------------------


def test_disabled_records_nothing():
    telemetry.disable()
    try:
        telemetry.counter("dis_c").inc(5)
        telemetry.gauge("dis_g").set(9)
        telemetry.histogram("dis_h").observe(1.0)
        telemetry.record_event("retrace", op="x")
        x = nd.ones((3, 3))
        y = (x + x) * 2          # engine dispatches while disabled
        y.wait_to_read()
        snap = telemetry.snapshot()
        assert snap["counters"].get("dis_c", 0.0) == 0.0
        assert snap["gauges"].get("dis_g", 0.0) == 0.0
        assert snap["histograms"].get(
            "dis_h", {"count": 0})["count"] == 0
        assert snap["counters"].get(
            "mxtpu_engine_dispatches_total", 0.0) == 0.0
        assert telemetry.events() == []
    finally:
        telemetry.enable()


# ---------------------------------------------------------------------------
# engine-level attribution + dispatch events
# ---------------------------------------------------------------------------


def test_engine_dispatch_events_and_counters():
    x = nd.ones((5, 5))
    (x * 3).wait_to_read()
    evs = telemetry.events("dispatch")
    assert any(e["op"] == "_mul_scalar" for e in evs)
    assert telemetry.snapshot()["counters"][
        "mxtpu_engine_dispatches_total"] >= 2


def test_shape_retrace_attribution():
    # a dedicated op name: builtin elemwise ops accumulate aval history
    # from every other test module in a full-suite run, which would
    # swallow the retrace (both shapes already seen)
    def fc(x):
        return x * 2
    engine.invoke_compiled("telem_shape_op", fc, {},
                           nd.ones((4, 4))._data)
    telemetry.clear_events()
    engine.invoke_compiled("telem_shape_op", fc, {},
                           nd.ones((6, 4))._data)  # new shape: retrace
    evs = [e for e in telemetry.events("retrace")
           if e["op"] == "telem_shape_op"]
    assert evs, "shape change must emit a retrace event"
    ev = evs[0]
    assert ev["cause"] == "shapes"
    assert ev["changed"]["arg0.shape"] == [[4, 4], [6, 4]]


def test_attr_retrace_attribution():
    # same op name, drifting numeric attr: the retrace event names it
    import jax.numpy as jnp

    def fc(x, k=0):
        return x + k
    arr = nd.ones((2, 2))._data
    engine.invoke_compiled("telem_attr_op", fc, {"k": 1}, arr)
    telemetry.clear_events()
    engine.invoke_compiled("telem_attr_op", fc, {"k": 2}, arr)
    evs = telemetry.events("retrace")
    assert evs and evs[0]["cause"] == "attrs"
    assert evs[0]["changed"]["k"] == ["1", "2"]


# ---------------------------------------------------------------------------
# CompiledStep wiring: 1-dispatch contract + momentum-drift attribution
# ---------------------------------------------------------------------------


def test_compiled_step_records_one_dispatch():
    X, Y = _data()
    net = _mlp()
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 0.05})
    cs = tr.compile_step(net, gluon.loss.L2Loss())
    for _ in range(3):
        cs.step(X, Y, 4)
    snap = telemetry.snapshot()
    assert snap["gauges"]["mxtpu_last_step_dispatches"] == 1.0
    assert snap["counters"]["mxtpu_steps_total"] == 3.0
    assert snap["histograms"]["mxtpu_compiled_step_seconds"]["count"] == 3
    assert snap["counters"]["mxtpu_examples_total"] == 12.0
    steps = [e for e in telemetry.events("step")
             if e.get("path") == "compiled"]
    assert steps and steps[-1]["dispatches"] == 1


def test_steady_window_across_health_samples_is_one_dispatch_no_retrace():
    """The steady-state window an operator watches: warmed past the
    first health-sampled step (every 10th by default; its program
    variant traces once), 20 more steps cross two sampled steps and
    still count ONE dispatch each, no retrace event, and a stall ratio
    of 0 with no loader in the loop."""
    X, Y = _data()
    net = _mlp()
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 0.05})
    cs = tr.compile_step(net, gluon.loss.L2Loss())
    for _ in range(11):
        cs.step(X, Y, 4)
    telemetry.clear_events()
    d0 = engine.cache_info()["dispatches"]
    for _ in range(20):
        cs.step(X, Y, 4)
    assert engine.cache_info()["dispatches"] - d0 == 20
    assert telemetry.events("retrace") == []
    assert telemetry.snapshot()["gauges"][
        "mxtpu_last_step_dispatches"] == 1.0
    assert telemetry.prefetch_stall_ratio() == 0.0
    assert telemetry.health.sentinels()[cs.name].samples == 3


def test_momentum_drift_retrace_names_the_attr():
    X, Y = _data()
    net = _mlp()
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 0.05, "momentum": 0.9})
    cs = tr.compile_step(net, gluon.loss.L2Loss())
    cs.step(X, Y, 4)
    cs.step(X, Y, 4)
    telemetry.clear_events()
    tr._optimizer.momentum = 0.5          # forced static-attr drift
    cs.step(X, Y, 4)
    evs = telemetry.events("retrace")
    assert evs, "momentum drift must emit an attributed retrace event"
    ev = evs[0]
    assert ev["source"] == "compiled_step" and ev["cause"] == "attrs"
    assert ev["changed"]["momentum"] == ["0.9", "0.5"]
    # the eviction that followed is on the timeline too
    assert any(e["op"].startswith("gluon_train_step")
               for e in telemetry.events("evict"))
    # drift recompiles ONCE; the next step is clean
    telemetry.clear_events()
    cs.step(X, Y, 4)
    assert telemetry.events("retrace") == []


def test_fallback_event_recorded():
    from mxnet_tpu.gluon import compiled_step as cs_mod

    class Weird(gluon.HybridBlock):
        def __init__(self):
            super().__init__()
            with self.name_scope():
                self.d = gluon.nn.Dense(3, in_units=6)

        def hybrid_forward(self, F, x):
            # host-dependent control flow: untraceable, forces the
            # transparent eager fallback
            if float(x.sum().asnumpy()) > 1e9:
                return self.d(x) * 2
            return self.d(x)

    cs_mod.clear_fallback_reports()
    X, Y = _data()
    net = Weird()
    net.initialize(mx.init.Xavier())
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 0.05})
    cs = tr.compile_step(net, gluon.loss.L2Loss())
    cs.step(X, Y, 4)
    assert cs.last_path == "eager"
    evs = telemetry.events("fallback")
    assert evs and evs[0]["where"] == "compiled_step"
    assert telemetry.snapshot()["counters"][
        "mxtpu_fallbacks_total"] >= 1.0


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------


def test_ring_bounded_by_env(monkeypatch):
    monkeypatch.setenv("MXTPU_FLIGHT_RECORDER_SIZE", "16")
    telemetry.clear_events()          # re-reads capacity on next use
    # a rare event recorded BEFORE a flood of dispatches must survive:
    # the forensic kinds live in a retained ring of their own
    telemetry.record_event("retrace", op="precious", cause="attrs",
                           changed={})
    for i in range(50):
        telemetry.record_event("dispatch", op=f"op{i}")
    evs = telemetry.events()
    assert len(evs) == 17             # 16 newest dispatches + retrace
    assert evs[0]["op"] == "precious"
    assert evs[-1]["op"] == "op49"    # newest survive, oldest dropped
    assert telemetry.events("retrace")[0]["op"] == "precious"


def test_dump_on_demand(tmp_path):
    telemetry.counter("dump_c").inc(3)
    telemetry.record_event("retrace", op="x", cause="attrs",
                           changed={"k": ["1", "2"]})
    path = telemetry.dump_flight_recorder(
        path=str(tmp_path / "flight.json"), reason="test")
    with open(path) as f:
        art = json.load(f)
    assert art["reason"] == "test"
    assert art["metrics"]["counters"]["dump_c"] == 3.0
    kinds = [e["kind"] for e in art["events"]]
    assert "retrace" in kinds
    assert telemetry.last_dump() == path


def test_poisoned_compiled_step_dumps_flight_recorder(
        monkeypatch, tmp_path):
    """Post-donation failure = training state lost; the flight
    recorder must land on disk with the poison event in it."""
    monkeypatch.setenv("MXTPU_TELEMETRY_EXPORT", str(tmp_path))
    X, Y = _data()
    net = _mlp()
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 0.05})
    cs = tr.compile_step(net, gluon.loss.L2Loss())
    cs.step(X, Y, 4)                      # healthy step compiles

    real_invoke = engine.invoke_compiled

    def consume_then_boom(name, fn, attrs, *arrays, **kw):
        for a in arrays:
            if hasattr(a, "delete"):
                a.delete()                # what donation does on TPU
        raise RuntimeError("transient device error")

    monkeypatch.setattr(engine, "invoke_compiled", consume_then_boom)
    with pytest.raises(MXNetError, match="donated"):
        cs.step(X, Y, 4)
    monkeypatch.setattr(engine, "invoke_compiled", real_invoke)

    dump = telemetry.last_dump()
    assert dump is not None and os.path.dirname(dump) == str(tmp_path)
    with open(dump) as f:
        art = json.load(f)
    assert art["reason"].startswith("compiled_step_poisoned")
    poisons = [e for e in art["events"] if e["kind"] == "poison"]
    assert poisons and poisons[0]["where"] == "compiled_step"
    assert telemetry.snapshot()["counters"][
        "mxtpu_poisons_total"] == 1.0


# ---------------------------------------------------------------------------
# DataLoader pipeline + stall ratio + profiler mirroring
# ---------------------------------------------------------------------------


def test_dataloader_prefetch_metrics_and_stall_ratio():
    from mxnet_tpu.gluon.data import DataLoader, Dataset

    class Slow(Dataset):
        """Fetch slower than the consumer: guaranteed stalls."""

        def __len__(self):
            return 12

        def __getitem__(self, i):
            time.sleep(0.01)
            return np.full((2,), i, "f4")

    dl = DataLoader(Slow(), batch_size=4, num_workers=1, prefetch=1)
    for _ in dl:
        pass
    snap = telemetry.snapshot()
    assert snap["counters"]["mxtpu_dataloader_batches_total"] == 3.0
    assert snap["histograms"][
        "mxtpu_dataloader_consumer_wait_seconds"]["count"] == 3
    assert snap["histograms"][
        "mxtpu_dataloader_fetch_seconds"]["count"] == 3
    # a 10ms/sample dataset against an instant consumer MUST stall
    assert telemetry.prefetch_stall_ratio() > 0.0
    assert telemetry.events("prefetch_stall")


def test_events_mirror_into_profiler_stream(tmp_path):
    from mxnet_tpu import profiler
    fname = str(tmp_path / "prof.json")
    profiler.set_config(filename=fname)
    profiler.set_state("run")
    try:
        telemetry.record_event("retrace", op="mirrored_op",
                               cause="attrs", changed={})
    finally:
        profiler.set_state("stop")
    profiler.dump()
    with open(fname) as f:
        trace = json.load(f)
    mirrored = [e for e in trace["traceEvents"]
                if e["name"] == "telemetry:retrace"]
    assert mirrored and mirrored[0]["cat"] == "telemetry"
    assert mirrored[0]["args"]["op"] == "mirrored_op"


# ---------------------------------------------------------------------------
# mxlint runtime pass
# ---------------------------------------------------------------------------


def test_mxl306_retrace_after_warmup_carries_cause():
    from mxnet_tpu import analysis
    # before any recorded steps: a retrace at step 0 is warm-up noise
    telemetry.record_event("retrace", op="warm", cause="attrs",
                           changed={"k": ["1", "2"]})
    assert analysis.analyze_telemetry(warmup_steps=2) == []
    telemetry.note_step()
    telemetry.note_step()
    # note_step advances at step END, so this event is stamped 2 ==
    # "emitted DURING step 3", the FIRST post-warm-up step — the
    # boundary the filter must keep
    telemetry.record_event("retrace", op="hot_op", cause="attrs",
                           changed={"momentum": ["0.9", "0.5"]})
    findings = analysis.analyze_telemetry(warmup_steps=2)
    assert len(findings) == 1
    f = findings[0]
    assert f.rule == "MXL306"
    assert "hot_op" in f.message and "during step 3" in f.message
    assert "momentum: 0.9 -> 0.5" in f.message


def test_mxl307_prefetch_stall_ratio():
    from mxnet_tpu import analysis
    telemetry.counter("mxtpu_dataloader_batches_total").inc(10)
    telemetry.counter("mxtpu_prefetch_stalls_total").inc(6)
    findings = analysis.analyze_telemetry(stall_threshold=0.25)
    assert [f.rule for f in findings] == ["MXL307"]
    assert "0.60" in findings[0].message
    # below threshold: clean
    assert analysis.analyze_telemetry(stall_threshold=0.8) == []
