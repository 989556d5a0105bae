"""Training-health observatory (docs/observability.md, "Training
health").

Tier-1 coverage for `telemetry.health` + its splice into the step
stacks:

* contract: with health ON at K=1, a compiled gluon step and a fused
  SPMD step are STILL exactly one dispatch (single and `step_multi`),
  and health-on vs health-off training is bit-identical (warn mode
  adds outputs, never touches the update math);
* a fault-injected nonfinite gradient (`nonfinite_grad` point)
  produces a `health_anomaly` event with subtree attribution, a
  skipped update under `MXTPU_HEALTH_ACTION=skip` (params bit-exact
  through the poisoned step), and a bit-exact resume from the last
  committed checkpoint under `rollback`;
* the sentinel's anomaly taxonomy (nonfinite / loss spike / grad
  explosion / update-ratio collapse), patience escalation, and
  attribution, unit-tested on crafted vectors;
* retained-ring round-trip: `health_anomaly` events survive dispatch
  floods and ride the JSONL + Prometheus exporters and
  `dump_flight_recorder()` artifacts;
* `metric.py` NaN-robustness (`nonfinite_updates`), mxlint MXL311
  (seeded corpus + suppression) and MXL312 (runtime sibling), and the
  `tools/mxhealth.py` CLI.
"""
import json
import os

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import engine, gluon, nd, telemetry
from mxnet_tpu.elastic import faults
from mxnet_tpu.telemetry import health


@pytest.fixture(autouse=True)
def _health_env(monkeypatch):
    """Health at K=1 by default for this module (tests override), and
    a clean telemetry plane per test."""
    monkeypatch.setenv("MXTPU_HEALTH", "1")
    monkeypatch.setenv("MXTPU_HEALTH_EVERY", "1")
    monkeypatch.delenv("MXTPU_HEALTH_ACTION", raising=False)
    telemetry.reset()
    faults.clear()
    yield
    faults.clear()
    telemetry.reset()


def _mlp(seed=0):
    mx.random.seed(seed)
    np.random.seed(seed)
    net = gluon.nn.HybridSequential()
    with net.name_scope():
        net.add(gluon.nn.Dense(8, activation="relu", in_units=6),
                gluon.nn.Dense(3, in_units=8))
    net.initialize(mx.init.Xavier())
    net.hybridize()
    return net


def _trainer(net, opt="sgd", **kw):
    kw.setdefault("learning_rate", 0.05)
    return gluon.Trainer(net.collect_params(), opt, kw, kvstore=None)


def _data(seed=3, n=4):
    rng = np.random.RandomState(seed)
    return (nd.array(rng.rand(n, 6).astype("f4")),
            nd.array(rng.rand(n, 3).astype("f4")))


def _params_np(net):
    return {i: p.data().asnumpy()
            for i, p in enumerate(net.collect_params().values())}


def _one_sentinel():
    sents = telemetry.health.sentinels()
    assert len(sents) >= 1
    return list(sents.values())[-1]


# ---------------------------------------------------------------------------
# in-graph stats + dispatch contract
# ---------------------------------------------------------------------------


def test_health_vector_fields_and_values():
    """The sampled vector carries loss / norms / nonfinite per
    top-level subtree, and the loss slot matches the step's actual
    loss."""
    net = _mlp()
    cs = _trainer(net).compile_step(net, gluon.loss.L2Loss())
    X, Y = _data()
    loss = cs.step(X, Y, 4)
    assert cs.last_path == "compiled"
    sent = _one_sentinel()
    assert sent.spec.subtrees == ["dense0", "dense1"]
    assert sent.spec.fields()[:3] == ["loss", "grad_norm", "nonfinite"]
    row = sent.snapshot()["history"][-1]
    np.testing.assert_allclose(row["loss"],
                               float(loss.asnumpy().mean()), rtol=1e-5)
    assert row["nonfinite"] == 0
    for s in ("dense0", "dense1"):
        sub = row["subtrees"][s]
        assert sub["param_norm"] > 0 and sub["grad_norm"] > 0
        assert sub["update_norm"] > 0


def test_one_dispatch_with_health_on():
    """Health ON at K=1: the gluon train step is still EXACTLY one
    dispatch (single and step_multi), and steady state compiles
    nothing."""
    net = _mlp()
    cs = _trainer(net, "adam", learning_rate=0.01).compile_step(
        net, gluon.loss.L2Loss())
    X, Y = _data()
    for _ in range(2):
        cs.step(X, Y, 4)
    d0 = engine.cache_info()["dispatches"]
    cs.step(X, Y, 4)
    assert engine.cache_info()["dispatches"] - d0 == 1
    K = 3
    rng = np.random.RandomState(7)
    Xk = nd.array(rng.rand(K, 4, 6).astype("f4"))
    Yk = nd.array(rng.rand(K, 4, 3).astype("f4"))
    cs.step_multi(Xk, Yk)
    d0 = engine.cache_info()["dispatches"]
    cs.step_multi(Xk, Yk)
    assert engine.cache_info()["dispatches"] - d0 == 1
    m0 = engine.cache_info()["misses"]
    cs.step(X, Y, 4)
    cs.step_multi(Xk, Yk)
    assert engine.cache_info()["misses"] == m0
    # every real step sampled at K=1
    assert _one_sentinel().samples >= 3 + 2 * K


def test_health_on_off_bit_identical(monkeypatch):
    """Warn-mode monitoring must not perturb training: N steps with
    health sampling every step == N steps with the plane off,
    bit-for-bit."""
    X, Y = _data()
    results = {}
    for mode in ("1", "0"):
        monkeypatch.setenv("MXTPU_HEALTH", mode)
        net = _mlp(seed=11)
        cs = _trainer(net).compile_step(net, gluon.loss.L2Loss())
        for _ in range(4):
            cs.step(X, Y, 4)
        assert cs.last_path == "compiled"
        results[mode] = _params_np(net)
    for i in results["1"]:
        np.testing.assert_array_equal(results["1"][i], results["0"][i])


def test_compiled_vs_eager_parity_with_health_spliced(monkeypatch):
    """Fused-vs-eager parity with health outputs spliced in at K=1:
    the compiled step (every dispatch carrying the stats vector)
    matches the eager record/backward/step path on the MLP — to
    ``_FMA_ULP`` (tests/test_compiled_step.py: the whole-step program
    contracts the last bias's gradient reduction into FMAs, one
    rounding where the eager per-op chain takes two; both correct)."""
    from test_compiled_step import _FMA_ULP
    from mxnet_tpu import autograd
    X, Y = _data()
    l2 = gluon.loss.L2Loss()

    net_c = _mlp(seed=21)
    cs = _trainer(net_c).compile_step(net_c, l2)
    for _ in range(4):
        cs.step(X, Y, 4)
    assert cs.last_path == "compiled"
    assert _one_sentinel().samples == 4

    net_e = _mlp(seed=21)
    tr_e = _trainer(net_e)
    for _ in range(4):
        with autograd.record():
            loss = l2(net_e(X), Y)
        autograd.backward([loss])
        tr_e.step(4)

    pc, pe = _params_np(net_c), _params_np(net_e)
    for i in pc:
        np.testing.assert_array_max_ulp(pc[i], pe[i], maxulp=_FMA_ULP)


def test_sampling_cadence(monkeypatch):
    monkeypatch.setenv("MXTPU_HEALTH_EVERY", "3")
    net = _mlp()
    cs = _trainer(net).compile_step(net, gluon.loss.L2Loss())
    X, Y = _data()
    for _ in range(9):
        cs.step(X, Y, 4)
    assert _one_sentinel().samples == 3


def test_toggle_emits_attributed_retrace(monkeypatch):
    """Flipping the health config mid-run evicts the stale program
    with an attributed retrace event, like any other baked-attr
    drift."""
    net = _mlp()
    cs = _trainer(net).compile_step(net, gluon.loss.L2Loss())
    X, Y = _data()
    cs.step(X, Y, 4)
    monkeypatch.setenv("MXTPU_HEALTH_ACTION", "skip")
    cs.step(X, Y, 4)
    evs = [e for e in telemetry.events("retrace")
           if "health" in (e.get("changed") or {})]
    assert evs and evs[-1]["op"] == cs.name


def test_config_flip_clears_stale_manifest_rows(monkeypatch):
    """A health-config flip must drop the recorded warm-start variant
    rows: they bake the pre-flip program's output arity / call
    signature, and a save_signature after the flip would otherwise
    hand a fresh process a manifest that contradicts the config."""
    net = _mlp()
    cs = _trainer(net).compile_step(net, gluon.loss.L2Loss())
    X, Y = _data()
    cs.step(X, Y, 4)
    assert any(v.get("health_out") and v["suffix"].endswith("_hs")
               for v in cs._variants.values())
    monkeypatch.setenv("MXTPU_HEALTH_ACTION", "skip")
    cs.step(X, Y, 4)
    # only post-flip rows survive, all consistent with skip mode
    # (health outputs in the BASE variant, no _hs suffix)
    assert cs._variants
    for v in cs._variants.values():
        assert v["health_out"] and not v["suffix"].endswith("_hs")


@pytest.mark.needs_mesh
def test_spmd_config_flip_clears_stale_var_avals(monkeypatch):
    from conftest import needs_devices
    needs_devices(8)
    from mxnet_tpu import parallel
    monkeypatch.setenv("MXTPU_HEALTH", "0")
    net = _mlp()
    mesh = parallel.make_mesh({"dp": 8})
    dpt = parallel.DataParallelTrainer(
        net, gluon.loss.L2Loss(), "sgd", {"learning_rate": 0.05},
        mesh=mesh, fuse_step=True)
    rng = np.random.RandomState(0)
    X = nd.array(rng.rand(16, 6).astype("f4"))
    Y = nd.array(rng.rand(16, 3).astype("f4"))
    dpt.step(X, Y)
    assert "extra" not in dpt._var_avals[(0, False)]
    monkeypatch.setenv("MXTPU_HEALTH", "1")
    dpt.step(X, Y)
    # the flip dropped the health-off row; the re-recorded one
    # carries the due-flag extra aval the health-on signature needs
    assert "extra" in dpt._var_avals[(0, False)]
    assert [e for e in telemetry.events("retrace")
            if "health" in (e.get("changed") or {})]


def test_disabled_plane_is_inert(monkeypatch):
    monkeypatch.setenv("MXTPU_HEALTH", "0")
    net = _mlp()
    cs = _trainer(net).compile_step(net, gluon.loss.L2Loss())
    X, Y = _data()
    cs.step(X, Y, 4)
    assert cs._health_spec is None
    assert telemetry.health.sentinels() == {}
    # telemetry master switch also kills it
    monkeypatch.setenv("MXTPU_HEALTH", "1")
    monkeypatch.setenv("MXTPU_TELEMETRY", "0")
    telemetry.disable()
    try:
        assert not health.enabled()
        assert health.trace_signature() is None
    finally:
        telemetry.enable()


# ---------------------------------------------------------------------------
# fault-injected nonfinite gradient: warn / skip / rollback
# ---------------------------------------------------------------------------


def test_nonfinite_injection_warn_event_and_attribution():
    net = _mlp()
    cs = _trainer(net).compile_step(net, gluon.loss.L2Loss())
    X, Y = _data()
    cs.step(X, Y, 4)
    faults.configure("nonfinite_grad:nth=1")
    loss = cs.step(X, Y, 4)
    assert np.isnan(loss.asnumpy()).any()
    evs = telemetry.events("health_anomaly")
    assert len(evs) == 1
    ev = evs[0]
    assert ev["anomaly"] == "nonfinite" and ev["count"] > 0
    # a NaN input poisons every subtree's gradients — attribution
    # must name them
    assert ev["subtrees"] == ["dense0", "dense1"]
    assert not ev["skipped"]
    assert [f for f in faults.fired()
            if f.startswith("nonfinite_grad")]
    snap = telemetry.snapshot()["counters"]
    assert snap["mxtpu_health_nonfinite_total"] > 0
    assert snap["mxtpu_health_anomalies_total"] == 1


def test_nonfinite_injection_skip_keeps_params_bit_exact(monkeypatch):
    monkeypatch.setenv("MXTPU_HEALTH_ACTION", "skip")
    net = _mlp()
    cs = _trainer(net).compile_step(net, gluon.loss.L2Loss())
    X, Y = _data()
    cs.step(X, Y, 4)
    cs.step(X, Y, 4)
    before = _params_np(net)
    faults.configure("nonfinite_grad:nth=1")
    loss = cs.step(X, Y, 4)
    # the loss output still reports the poisoned step...
    assert np.isnan(loss.asnumpy()).any()
    after = _params_np(net)
    # ...but the in-graph gate made the update a no-op, bit-exact
    for i in before:
        np.testing.assert_array_equal(before[i], after[i])
    ev = telemetry.events("health_anomaly")[-1]
    assert ev["anomaly"] == "nonfinite" and ev["skipped"]
    # the next healthy step trains again
    cs.step(X, Y, 4)
    trained = _params_np(net)
    assert any(not np.array_equal(after[i], trained[i])
               for i in after)
    assert not any(np.isnan(v).any() for v in trained.values())


def test_nonfinite_injection_rollback_bit_exact_resume(monkeypatch,
                                                       tmp_path):
    from mxnet_tpu.elastic import CheckpointManager
    monkeypatch.setenv("MXTPU_HEALTH_ACTION", "rollback")
    net = _mlp()
    cs = _trainer(net).compile_step(net, gluon.loss.L2Loss())
    X, Y = _data()
    mgr = CheckpointManager(str(tmp_path / "ck"), trainer=cs, keep=2)
    try:
        cs.health_manager = mgr
        cs.step(X, Y, 4)
        cs.step(X, Y, 4)
        mgr.save(block=True)
        committed = _params_np(net)
        faults.configure("nonfinite_grad:nth=1")
        cs.step(X, Y, 4)
        restored = _params_np(net)
        for i in committed:
            np.testing.assert_array_equal(committed[i], restored[i])
        assert len(telemetry.events("recovery")) == 1
        snap = telemetry.snapshot()["counters"]
        assert snap["mxtpu_health_rollbacks_total"] == 1
        # training continues from the committed state
        cs.step(X, Y, 4)
        assert not any(np.isnan(v).any()
                       for v in _params_np(net).values())
    finally:
        mgr.close()


def test_rollback_before_first_commit_degrades_gracefully(
        monkeypatch, tmp_path):
    """Armed rollback with NOTHING committed yet must not crash the
    training loop: the verdict records a rollback_failed event (no
    rollback counted) and the sentinel retries once a save commits."""
    from mxnet_tpu.elastic import CheckpointManager
    monkeypatch.setenv("MXTPU_HEALTH_ACTION", "rollback")
    net = _mlp()
    cs = _trainer(net).compile_step(net, gluon.loss.L2Loss())
    X, Y = _data()
    mgr = CheckpointManager(str(tmp_path / "ck"), trainer=cs, keep=2)
    try:
        cs.health_manager = mgr
        cs.step(X, Y, 4)
        faults.configure("nonfinite_grad:nth=1")
        cs.step(X, Y, 4)           # must NOT raise
        faults.clear()
        kinds = [e.get("anomaly")
                 for e in telemetry.events("health_anomaly")]
        assert "rollback_failed" in kinds
        snap = telemetry.snapshot()["counters"]
        assert snap.get("mxtpu_health_rollbacks_total", 0) == 0
    finally:
        mgr.close()


def test_rollback_without_manager_records_unarmed(monkeypatch):
    monkeypatch.setenv("MXTPU_HEALTH_ACTION", "rollback")
    net = _mlp()
    cs = _trainer(net).compile_step(net, gluon.loss.L2Loss())
    X, Y = _data()
    cs.step(X, Y, 4)
    faults.configure("nonfinite_grad:nth=1")
    cs.step(X, Y, 4)       # verdict fires, no manager attached
    kinds = [e.get("anomaly")
             for e in telemetry.events("health_anomaly")]
    assert "rollback_unarmed" in kinds


# ---------------------------------------------------------------------------
# sentinel unit tests (crafted vectors)
# ---------------------------------------------------------------------------


def _spec2():
    return health.HealthSpec(["g1", "g2"], [[0], [1]], skip=False)


def _vec(spec, loss=1.0, gnorm=1.0, nonfinite=0.0, subs=None):
    subs = subs or {}
    v = [loss, gnorm, nonfinite]
    for s in spec.subtrees:
        row = subs.get(s, {})
        v += [row.get("param_norm", 1.0), row.get("grad_norm", 0.5),
              row.get("update_norm", 1e-3),
              row.get("nonfinite", 0.0)]
    return np.asarray(v, np.float32)


def test_sentinel_nonfinite_attribution_unit():
    spec = _spec2()
    sent = health.Sentinel(spec, "unit")
    v = _vec(spec, loss=0.5, nonfinite=1.0,
             subs={"g2": {"nonfinite": 1.0}})
    verdict = sent.observe(v, step=7)
    assert verdict["kind"] == "nonfinite" and verdict["step"] == 7
    ev = telemetry.events("health_anomaly")[-1]
    assert ev["subtrees"] == ["g2"]
    assert sent.last_verdict["kind"] == "nonfinite"


def test_sentinel_loss_spike_patience_and_divergence(monkeypatch):
    monkeypatch.setenv("MXTPU_HEALTH_PATIENCE", "2")
    monkeypatch.setenv("MXTPU_HEALTH_ACTION", "rollback")
    spec = _spec2()
    sent = health.Sentinel(spec, "unit")
    rng = np.random.RandomState(0)
    for i in range(10):
        assert sent.observe(_vec(
            spec, loss=1.0 + 0.01 * rng.rand(),
            gnorm=1.0 + 0.01 * rng.rand()), step=i) is None
    # first spike: anomaly, but below patience -> no verdict yet
    assert sent.observe(_vec(spec, loss=100.0), step=10) is None
    assert [e["anomaly"] for e in
            telemetry.events("health_anomaly")] == ["loss_spike"]
    # second consecutive spike escalates
    verdict = sent.observe(_vec(spec, loss=120.0), step=11)
    assert verdict["kind"] == "divergence" and verdict["streak"] == 2

    class _Owner:
        health_manager = object()
        rolled = 0

        def recover(self, manager):
            _Owner.rolled += 1

    assert health.handle_verdict(_Owner(), verdict)
    assert _Owner.rolled == 1
    # spikes never contaminated the baseline: a healthy sample is
    # healthy again
    assert sent.observe(_vec(spec, loss=1.0), step=12) is None


def test_sentinel_grad_explosion_and_ratio_collapse():
    spec = _spec2()
    sent = health.Sentinel(spec, "unit")
    for i in range(10):
        sent.observe(_vec(spec, gnorm=1.0 + 0.001 * i), step=i)
    sent.observe(_vec(spec, gnorm=50.0,
                      subs={"g2": {"grad_norm": 49.0}}), step=10)
    ev = telemetry.events("health_anomaly")[-1]
    assert ev["anomaly"] == "grad_explosion"
    assert ev["subtrees"] == ["g2"]      # largest grad norm
    sent.observe(_vec(spec, subs={
        "g1": {"update_norm": 1e-9}, "g2": {"update_norm": 1e-9}}),
        step=11)
    kinds = [e["anomaly"] for e in telemetry.events("health_anomaly")]
    assert "update_ratio_collapse" in kinds


# ---------------------------------------------------------------------------
# retained ring + exporters round-trip
# ---------------------------------------------------------------------------


def test_health_anomaly_survives_dispatch_flood(monkeypatch, tmp_path):
    monkeypatch.setenv("MXTPU_FLIGHT_RECORDER_SIZE", "64")
    telemetry.clear_events()        # re-read capacity
    telemetry.record_event("health_anomaly", where="t",
                           anomaly="nonfinite", count=1,
                           subtrees=["dense0"], detail="drill")
    for _ in range(500):
        telemetry.record_event("dispatch", op="flood")
    evs = telemetry.events("health_anomaly")
    assert len(evs) == 1 and evs[0]["detail"] == "drill"
    # the dump artifact carries it too
    path = telemetry.dump_flight_recorder(
        str(tmp_path / "dump.json"), reason="test")
    with open(path) as f:
        artifact = json.load(f)
    kinds = [e["kind"] for e in artifact["events"]]
    assert "health_anomaly" in kinds


def test_health_metrics_export_round_trip(tmp_path):
    spec = _spec2()
    sent = health.Sentinel(spec, "unit")
    sent.observe(_vec(spec, loss=2.5, gnorm=1.5), step=1)
    parsed = telemetry.parse_prometheus(telemetry.to_prometheus())
    assert parsed["mxtpu_health_loss"] == 2.5
    assert parsed["mxtpu_health_grad_norm"] == 1.5
    sent.observe(_vec(spec, nonfinite=1.0,
                      subs={"g1": {"nonfinite": 1.0}}), step=2)
    # Prometheus text exposition round-trips the health instruments
    parsed = telemetry.parse_prometheus(telemetry.to_prometheus())
    assert parsed["mxtpu_health_samples_total"] == 2.0
    assert parsed["mxtpu_health_anomalies_total"] == 1.0
    # JSONL exporter round-trips them too
    p = str(tmp_path / "m.jsonl")
    telemetry.write_jsonl(p)
    names = {r["name"] for r in telemetry.read_jsonl(p)}
    assert {"mxtpu_health_loss", "mxtpu_health_update_ratio",
            "mxtpu_health_nonfinite_total"} <= names


# ---------------------------------------------------------------------------
# metric.py NaN-robustness
# ---------------------------------------------------------------------------


def test_metric_loss_nonfinite_update_does_not_corrupt():
    from mxnet_tpu import metric
    m = metric.Loss()
    m.update(None, nd.array(np.asarray([1.0, 3.0], np.float32)))
    m.update(None, nd.array(np.asarray([np.nan, 2.0], np.float32)))
    m.update(None, nd.array(np.asarray([2.0, 2.0], np.float32)))
    name, value = m.get()
    assert np.isfinite(value)
    np.testing.assert_allclose(value, 8.0 / 4.0)
    assert m.nonfinite_updates == 1
    m.update(None, nd.array(np.asarray([np.inf], np.float32)))
    assert m.nonfinite_updates == 2
    m.reset()
    assert m.nonfinite_updates == 0


def test_metric_custom_nonfinite_robust():
    from mxnet_tpu import metric
    m = metric.CustomMetric(lambda l, p: float(np.sum(p)))
    m.update([nd.array(np.ones(2))], [nd.array(np.ones(2))])
    m.update([nd.array(np.ones(2))],
             [nd.array(np.asarray([np.nan, 1.0], np.float32))])
    assert m.get()[1] == 2.0
    assert m.nonfinite_updates == 1
    # F1/MCC override reset(); the counter must exist there too
    assert metric.F1().nonfinite_updates == 0
    assert metric.MCC().nonfinite_updates == 0


# ---------------------------------------------------------------------------
# mxlint MXL311 / MXL312
# ---------------------------------------------------------------------------


_LOSS_READ_LOOP = '''
def train(net, data, trainer, metric):
    for x, y in data:
        with mx.autograd.record():
            loss = net(x)
        loss.backward()
        trainer.step(1)
        log(loss.item())
        lr = float(loss)
        m = metric.asnumpy()
'''


def test_mxl311_seeded_corpus():
    from mxnet_tpu import analysis
    rules = [f.rule for f in analysis.analyze_source(_LOSS_READ_LOOP)]
    assert rules.count("MXL311") == 3
    assert "MXL301" not in rules
    f = [x for x in analysis.analyze_source(_LOSS_READ_LOOP)
         if x.rule == "MXL311"][0]
    assert "MXTPU_HEALTH_EVERY" in f.message


def test_mxl311_suppression_and_clean_loop():
    from mxnet_tpu import analysis
    src = _LOSS_READ_LOOP.replace(
        "log(loss.item())",
        "log(loss.item())  # mxlint: disable=MXL311")
    rules = [f.rule for f in analysis.analyze_source(src)]
    assert rules.count("MXL311") == 2
    # a loop that never reads the loss to the host is quiet
    clean = '''
def train(net, data, trainer):
    for x, y in data:
        with mx.autograd.record():
            loss = net(x)
        loss.backward()
        trainer.step(1)
'''
    assert not [f for f in analysis.analyze_source(clean)
                if f.rule in ("MXL301", "MXL311")]


def test_mxl312_runtime_pass_reports_recorded_anomalies():
    from mxnet_tpu import analysis
    assert analysis.analyze_health() == []     # fresh process: quiet
    spec = _spec2()
    sent = health.get_sentinel("unit312", spec)
    sent.observe(_vec(spec, nonfinite=1.0,
                      subs={"g1": {"nonfinite": 1.0}}), step=1)
    findings = analysis.analyze_health()
    assert len(findings) == 1
    f = findings[0]
    assert f.rule == "MXL312" and "nonfinite" in f.message
    assert "unit312" in f.location
    # and it rides self_check
    all_f, _ok = analysis.self_check()
    assert any(x.rule == "MXL312" for x in all_f)


# ---------------------------------------------------------------------------
# CLI + report
# ---------------------------------------------------------------------------


def test_report_and_render_table():
    net = _mlp()
    cs = _trainer(net).compile_step(net, gluon.loss.L2Loss())
    X, Y = _data()
    for _ in range(3):
        cs.step(X, Y, 4)
    rep = health.report()
    assert rep["kind"] == "mxtpu_health_report"
    owner = list(rep["owners"].values())[0]
    assert owner["samples"] == 3 and len(owner["history"]) == 3
    text = health.render_table(rep)
    assert "dense0" in text and "last verdict: healthy" in text


def test_mxhealth_cli_smoke_render_and_malformed(tmp_path, capsys):
    import sys
    sys.modules.pop("tools.mxhealth", None)
    from tools import mxhealth
    out = str(tmp_path / "health.json")
    rc = mxhealth.main(["smoke", "--steps", "4", "--out", out])
    assert rc == 0
    text = capsys.readouterr().out
    assert "STEP" in text and "LOSS" in text
    # the CI gate spelling (next to mxlint/mxmem's --self-check)
    assert mxhealth.main(["--self-check"]) == 0
    assert "sample(s)" in capsys.readouterr().out
    rc = mxhealth.main(["render", out])
    assert rc == 0
    assert "last verdict" in capsys.readouterr().out
    # malformed artifact -> exit 1
    bad = tmp_path / "bad.json"
    bad.write_text("{not json")
    assert mxhealth.main(["render", str(bad)]) == 1
    other = tmp_path / "other.json"
    other.write_text(json.dumps({"foo": 1}))
    assert mxhealth.main(["render", str(other)]) == 1
    capsys.readouterr()
    # a flight-recorder dump renders its retained health events
    telemetry.record_event("health_anomaly", where="cli",
                           anomaly="nonfinite", count=1,
                           subtrees=["dense0"], detail="drill")
    dump = telemetry.dump_flight_recorder(
        str(tmp_path / "flight.json"), reason="test")
    assert mxhealth.main(["render", dump]) == 0
    assert "nonfinite" in capsys.readouterr().out


# ---------------------------------------------------------------------------
# SPMD trainer
# ---------------------------------------------------------------------------


@pytest.mark.needs_mesh
def test_spmd_health_one_dispatch_and_samples():
    from conftest import needs_devices
    needs_devices(8)
    from mxnet_tpu import parallel
    net = _mlp()
    mesh = parallel.make_mesh({"dp": 8})
    dpt = parallel.DataParallelTrainer(
        net, gluon.loss.L2Loss(), "sgd", {"learning_rate": 0.05},
        mesh=mesh, fuse_step=True)
    rng = np.random.RandomState(0)
    X = nd.array(rng.rand(16, 6).astype("f4"))
    Y = nd.array(rng.rand(16, 3).astype("f4"))
    dpt.step(X, Y)
    # the fused SPMD step is ONE counted dispatch (the retry seam);
    # health must not add any
    d0 = engine.cache_info()["dispatches"]
    dpt.step(X, Y)
    assert engine.cache_info()["dispatches"] - d0 == 1
    Xk = nd.array(rng.rand(2, 16, 6).astype("f4"))
    Yk = nd.array(rng.rand(2, 16, 3).astype("f4"))
    dpt.step_multi(Xk, Yk)
    d0 = engine.cache_info()["dispatches"]
    dpt.step_multi(Xk, Yk)
    assert engine.cache_info()["dispatches"] - d0 == 1
    sent = telemetry.health.sentinels()[f"spmd:{net.name}"]
    assert sent.samples == 2 + 2 * 2
    row = sent.snapshot()["history"][-1]
    assert row["grad_norm"] > 0 and row["nonfinite"] == 0


@pytest.mark.needs_mesh
def test_spmd_nonfinite_injection_skip(monkeypatch):
    from conftest import needs_devices
    needs_devices(8)
    monkeypatch.setenv("MXTPU_HEALTH_ACTION", "skip")
    from mxnet_tpu import parallel
    net = _mlp()
    mesh = parallel.make_mesh({"dp": 8})
    dpt = parallel.DataParallelTrainer(
        net, gluon.loss.L2Loss(), "sgd", {"learning_rate": 0.05},
        mesh=mesh, fuse_step=True)
    rng = np.random.RandomState(0)
    X = nd.array(rng.rand(16, 6).astype("f4"))
    Y = nd.array(rng.rand(16, 3).astype("f4"))
    dpt.step(X, Y)
    dpt.step(X, Y)
    before = _params_np(net)
    faults.configure("nonfinite_grad:nth=1")
    dpt.step(X, Y)
    after = _params_np(net)
    for i in before:
        np.testing.assert_array_equal(before[i], after[i])
    ev = telemetry.events("health_anomaly")[-1]
    assert ev["anomaly"] == "nonfinite" and ev["skipped"]
    assert ev["where"] == f"spmd:{net.name}"


@pytest.mark.needs_mesh
def test_spmd_rollback_bit_exact(monkeypatch, tmp_path):
    from conftest import needs_devices
    needs_devices(8)
    from mxnet_tpu import parallel
    from mxnet_tpu.elastic import CheckpointManager
    monkeypatch.setenv("MXTPU_HEALTH_ACTION", "rollback")
    net = _mlp()
    mesh = parallel.make_mesh({"dp": 8})
    dpt = parallel.DataParallelTrainer(
        net, gluon.loss.L2Loss(), "sgd", {"learning_rate": 0.05},
        mesh=mesh, fuse_step=True)
    rng = np.random.RandomState(0)
    X = nd.array(rng.rand(16, 6).astype("f4"))
    Y = nd.array(rng.rand(16, 3).astype("f4"))
    mgr = CheckpointManager(str(tmp_path / "ck"), trainer=dpt, keep=2)
    try:
        dpt.health_manager = mgr
        dpt.step(X, Y)
        dpt.step(X, Y)
        mgr.save(block=True)
        committed = _params_np(net)
        faults.configure("nonfinite_grad:nth=1")
        dpt.step(X, Y)
        restored = _params_np(net)
        for i in committed:
            np.testing.assert_array_equal(committed[i], restored[i])
        assert len(telemetry.events("recovery")) == 1
    finally:
        mgr.close()
