"""Memory & communication observatory (docs/observability.md,
"Device memory & comms").

Tier-1 coverage for ``telemetry.memory`` and its surfaces:

* per-program memory block present after a compiled step (peak/temp/
  argument bytes via ``compiled.memory_analysis()`` on the tiered AOT
  seam), visible through ``engine.cache_info()["memory"]``;
* donation-savings math == the donate tuple's aval bytes;
* per-param HBM attribution sums to the census total;
* SPMD collective byte counts for ``DataParallelTrainer``'s implicit
  gradient psum match the analytic grad-size expectation on the
  8-device virtual mesh;
* MXL308 (large updated buffer not donated) and MXL309 (large tensor
  replicated across a multi-device mesh) fire on seeded defects, stay
  quiet on the donated/sharded twins, and are suppressible;
* ``MXTPU_TELEMETRY=0``: harvesting records NOTHING;
* ``memory_analysis`` unavailable: analytic aval fallback + ONE
  ``mem_analysis_unavailable`` event per process;
* ``engine.cache_info()["live_bytes"]`` (the cheap always-on census),
  oom-risk events against a (monkeypatched) device capacity, and the
  mxcache/mxmem tool surfaces.
"""
import json
import os

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import analysis, engine, gluon, nd, telemetry
from mxnet_tpu.telemetry import memory as memobs

_TOOLS = os.path.join(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))), "tools")


def _tool(name):
    import sys
    if _TOOLS not in sys.path:
        sys.path.insert(0, _TOOLS)
    import importlib
    return importlib.import_module(name)


@pytest.fixture(autouse=True)
def _clean_plane():
    telemetry.enable()
    telemetry.reset()
    yield
    telemetry.enable()
    telemetry.reset()


def _mlp(hidden=16, in_units=8, out_units=4):
    net = gluon.nn.HybridSequential()
    with net.name_scope():
        net.add(gluon.nn.Dense(hidden, activation="relu",
                               in_units=in_units),
                gluon.nn.Dense(out_units, in_units=hidden))
    net.initialize(mx.init.Xavier())
    net.hybridize()
    return net


def _compiled_step(net, momentum=0.9):
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 0.1, "momentum": momentum},
                       kvstore=None)
    return tr.compile_step(net, gluon.loss.L2Loss())


def _batch(n=8, in_units=8, out_units=4):
    rng = np.random.RandomState(0)
    return (nd.array(rng.rand(n, in_units).astype("f4")),
            nd.array(rng.rand(n, out_units).astype("f4")))


def _param_bytes(net):
    return sum(int(np.prod(p.shape)) * 4
               for p in net.collect_params().values())


# ---------------------------------------------------------------------------
# per-program harvest
# ---------------------------------------------------------------------------

def test_memory_block_present_after_compile():
    net = _mlp()
    cs = _compiled_step(net)
    x, y = _batch()
    cs.step(x, y, 8).wait_to_read()
    assert cs.last_path == "compiled"
    mem = engine.cache_info()["memory"]
    assert mem["programs"] >= 1
    rec = mem["per_program"][cs.name]
    for field in ("peak_bytes", "argument_bytes", "output_bytes",
                  "temp_bytes", "donation_saved_bytes"):
        assert field in rec
    # this backend supports memory_analysis, so the numbers are XLA's
    assert rec["analytic"] is False
    assert rec["peak_bytes"] >= rec["donation_saved_bytes"] > 0
    assert mem["max_peak_bytes"] >= rec["peak_bytes"]
    # full records (with avals) via the module API
    full = memobs.programs()[cs.name]
    assert full["in_avals"] and full["out_avals"]


def test_donation_savings_match_donate_tuple():
    net = _mlp()
    cs = _compiled_step(net, momentum=0.9)
    x, y = _batch()
    cs.step(x, y, 8).wait_to_read()
    rec = memobs.programs()[cs.name]
    # CompiledStep donates trainable weights + momentum states: for an
    # all-trainable SGD-momentum net that is exactly 2x param bytes
    expected = 2 * _param_bytes(net)
    assert rec["donation_saved_bytes"] == expected
    # and the donated flat indices really are the donate tuple's
    assert len(rec["donated_idx"]) == 2 * len(net.collect_params())


def test_param_census_sums_to_total():
    net = _mlp(hidden=32)
    net(_batch(in_units=8)[0]).wait_to_read()
    pc = memobs.param_census(net.collect_params())
    assert pc["count"] == 4
    assert pc["total_bytes"] == sum(r["nbytes"] for r in pc["params"])
    assert pc["total_bytes"] == _param_bytes(net)
    # rows are sorted largest-first and carry the attribution fields
    sizes = [r["nbytes"] for r in pc["params"]]
    assert sizes == sorted(sizes, reverse=True)
    assert all({"name", "shape", "dtype", "sharding",
                "replicated"} <= set(r) for r in pc["params"])


def test_live_bytes_census():
    import gc
    gc.collect()        # an earlier file's garbage goes now, not mid-test
    info0 = engine.cache_info()
    a = nd.array(np.ones((64, 64), np.float32))
    b = a + 1.0
    b.wait_to_read()
    info = engine.cache_info()
    # op OUTPUTS are tracked (host-created arrays only enter the set
    # once an op writes them back): b's buffer at least
    assert info["live_bytes"] >= info0["live_bytes"] + 64 * 64 * 4
    c = memobs.census()
    assert c["total_bytes"] == info["live_bytes"]
    # a buffer a donation deleted stays in the live set while anything
    # holds it (an earlier file's Server): counted there, skipped here
    deleted = sum(1 for arr in engine.live_arrays()
                  if getattr(arr, "is_deleted", lambda: False)())
    assert c["count"] == info["live_buffers"] - deleted
    assert sum(c["by_device"].values()) >= c["total_bytes"]


# ---------------------------------------------------------------------------
# SPMD collectives
# ---------------------------------------------------------------------------

@pytest.mark.needs_mesh
def test_spmd_collective_bytes_match_grads():
    from conftest import needs_devices
    needs_devices(8)
    from mxnet_tpu import parallel
    net = _mlp(hidden=32, in_units=16, out_units=4)
    mesh = parallel.make_mesh({"dp": 8})
    dpt = parallel.DataParallelTrainer(
        net, gluon.loss.L2Loss(), "sgd", {"learning_rate": 0.1},
        mesh=mesh, fuse_step=True)
    rng = np.random.RandomState(0)
    x = nd.array(rng.rand(16, 16).astype("f4"))
    y = nd.array(rng.rand(16, 4).astype("f4"))
    dpt.step(x, y).wait_to_read()
    rec = memobs.programs()["spmd_full_step"]
    coll = rec["collectives"]
    assert "all-reduce" in coll
    grad_bytes = _param_bytes(net)
    payload = coll["all-reduce"]["payload_bytes"]
    # the dp gradient psum moves every trainable grad (replicated
    # params -> full-size grads per device) plus a few scalar reduces
    # (the global-batch loss mean)
    assert grad_bytes <= payload <= grad_bytes + 4096
    # ring all-reduce wire bytes: 2*N*(k-1)/k per device (int-per-
    # instruction rounding allows a few bytes of slack)
    assert coll["all-reduce"]["wire_bytes"] == pytest.approx(
        2 * payload * 7 / 8, abs=64)
    assert rec["collective_wire_bytes"] >= coll["all-reduce"]["wire_bytes"]
    # the roll-up reaches report() and the gauge
    rep = memobs.report()
    assert rep["collectives"]["all-reduce"]["payload_bytes"] >= payload
    snap = telemetry.snapshot()["gauges"]
    assert snap.get("mxtpu_collective_bytes_per_step", 0) > 0


# ---------------------------------------------------------------------------
# mxlint rules
# ---------------------------------------------------------------------------

def test_mxl308_seeded_defect_and_donated_twin():
    big = np.ones((256, 256), np.float32)          # 256 KiB

    def sgd_like(w, g):
        return w - 0.1 * g

    # seeded defect: hand-rolled train step updating a large weight
    # WITHOUT donating it (persist_name routes it through the tiered
    # seam, like any step-class program)
    engine.invoke_compiled("mxl308_bad_step", sgd_like, {}, big, big,
                           persist_name="mxl308_bad_step")
    findings = [f for f in analysis.analyze_memory(
        large_buffer_bytes=1 << 16) if f.rule == "MXL308"]
    assert any("mxl308_bad_step" in f.location for f in findings)
    bad = [f for f in findings if "mxl308_bad_step" in f.location][0]
    assert "donate" in bad.message
    assert bad.severity == "warning"

    # the donated twin is clean
    engine.invoke_compiled("mxl308_good_step", sgd_like, {}, big, big,
                           donate=(0,), persist_name="mxl308_good_step")
    findings = analysis.analyze_memory(large_buffer_bytes=1 << 16)
    assert not any("mxl308_good_step" in f.location for f in findings)

    # suppressible like every rule
    left = analysis.filter_findings(
        analysis.analyze_memory(large_buffer_bytes=1 << 16),
        {"MXL308"})
    assert not any(f.rule == "MXL308" for f in left)


@pytest.mark.needs_mesh
def test_mxl309_replicated_tensor_and_sharded_twin():
    from conftest import needs_devices
    needs_devices(8)
    from mxnet_tpu import parallel
    from jax.sharding import PartitionSpec as P

    def build():
        net = gluon.nn.HybridSequential()
        with net.name_scope():
            net.add(gluon.nn.Dense(64, in_units=4096))   # 1 MiB weight
        net.initialize(mx.init.Xavier())
        return net

    mesh = parallel.make_mesh({"dp": 8})
    rng = np.random.RandomState(0)
    x = nd.array(rng.rand(16, 4096).astype("f4"))
    y = nd.array(rng.rand(16, 64).astype("f4"))

    dpt = parallel.DataParallelTrainer(
        build(), gluon.loss.L2Loss(), "sgd", {"learning_rate": 0.1},
        mesh=mesh, fuse_step=True)
    dpt.step(x, y).wait_to_read()
    findings = [f for f in analysis.analyze_memory(
        replicated_bytes=1 << 20) if f.rule == "MXL309"]
    assert any("dense0_weight" in f.location for f in findings)
    assert "param_sharding" in findings[0].message

    # the sharded twin is clean (row-sharded over dp)
    telemetry.reset()
    dpt2 = parallel.DataParallelTrainer(
        build(), gluon.loss.L2Loss(), "sgd", {"learning_rate": 0.1},
        mesh=mesh, fuse_step=False,
        param_sharding=lambda name, shape:
            P("dp", None) if "weight" in name else None)
    dpt2.step(x, y).wait_to_read()
    findings = [f for f in analysis.analyze_memory(
        replicated_bytes=1 << 20) if f.rule == "MXL309"]
    assert not any("dense0_weight" in f.location for f in findings)
    # default threshold (64 MiB) keeps ordinary nets quiet
    assert not any(f.rule == "MXL309" for f in analysis.analyze_memory())


# ---------------------------------------------------------------------------
# degradation paths
# ---------------------------------------------------------------------------

def test_disabled_telemetry_harvests_nothing():
    telemetry.disable()
    try:
        net = _mlp()
        cs = _compiled_step(net)
        x, y = _batch()
        cs.step(x, y, 8).wait_to_read()
        assert cs.last_path == "compiled"     # the step itself runs
        assert memobs.programs() == {}
        assert engine.cache_info()["memory"] == {
            "programs": 0, "per_program": {}}
        assert telemetry.events() == []
        snap = telemetry.snapshot()["gauges"]
        assert snap.get("mxtpu_program_peak_bytes", 0) == 0
        assert snap.get("mxtpu_donation_saved_bytes", 0) == 0
        # note_param_tree is inert too
        memobs.note_param_tree("t", net.collect_params())
        assert memobs.param_trees() == {}
    finally:
        telemetry.enable()


def test_unavailable_analysis_degrades_to_analytic(monkeypatch):
    # a backend whose memory_analysis raises (older jaxlib / exotic
    # PJRT): the harvest must degrade to aval estimates, record ONE
    # event for the whole process, and never raise
    monkeypatch.setattr(
        memobs, "_memory_stats",
        lambda name, compiled: memobs._note_unavailable(
            name, "memory_analysis", "Boom()") or None)
    big = np.ones((64, 64), np.float32)
    engine.invoke_compiled("degraded_step_a", lambda w: w * 2.0, {},
                           big, persist_name="degraded_step_a")
    engine.invoke_compiled("degraded_step_b", lambda w: w * 3.0, {},
                           big, persist_name="degraded_step_b")
    rec = memobs.programs()["degraded_step_a"]
    assert rec["analytic"] is True
    assert rec["argument_bytes"] == 64 * 64 * 4
    assert rec["peak_bytes"] == rec["argument_bytes"]
    assert rec["output_bytes"] is None and rec["temp_bytes"] is None
    # ONE event despite two degraded programs
    evs = telemetry.events("mem_analysis_unavailable")
    assert len(evs) == 1


def test_oom_risk_event_against_capacity(monkeypatch):
    # CPU reports no capacity, so fake one just above the live bytes:
    # any nontrivial program then crosses the 92% line
    a = nd.array(np.ones((128, 128), np.float32))
    a.wait_to_read()
    monkeypatch.setattr(memobs, "device_capacity",
                        lambda: engine.live_bytes() + 1024)
    big = np.ones((64, 64), np.float32)
    engine.invoke_compiled("oomy_step", lambda w: w + 1.0, {}, big,
                           persist_name="oomy_step")
    evs = telemetry.events("oom_risk")
    assert evs and evs[-1]["op"] == "oomy_step"
    assert evs[-1]["ratio"] > memobs.OOM_RISK_RATIO
    assert evs[-1]["capacity_bytes"] == evs[-1]["live_bytes"] + 1024 \
        or evs[-1]["capacity_bytes"] > 0


# ---------------------------------------------------------------------------
# tool surfaces
# ---------------------------------------------------------------------------

def test_mxcache_verify_reports_payload_bytes(tmp_path, monkeypatch):
    cache = tmp_path / "cc"
    monkeypatch.setenv("MXTPU_COMPILE_CACHE_DIR", str(cache))
    big = np.ones((32, 32), np.float32)
    engine.invoke_compiled("persisted_step", lambda w: w * 2.0, {},
                           big, persist_name="persisted_step")
    rows = engine.persist.verify(str(cache))
    assert rows and all(r["payload_bytes"] > 0 for r in rows)
    ls_rows = engine.persist.ls(str(cache))
    assert all(r["payload_bytes"] > 0 for r in ls_rows)
    # the writer embedded the harvest in the header: peak visible
    # offline (ls), no payload read needed
    assert all((r.get("memory") or {}).get("peak_bytes", 0) > 0
               for r in ls_rows)
    # the CLI totals serialized-executable bytes and exits 0
    mxcache = _tool("mxcache")
    assert mxcache.main(["--dir", str(cache), "ls"]) == 0
    assert mxcache.main(["--dir", str(cache), "verify"]) == 0
    assert mxcache.main(
        ["--dir", str(cache), "--format", "json", "verify"]) == 0
    engine.drop_cached("persisted_step", persistent=True)


def test_mxmem_render_report(tmp_path):
    net = _mlp()
    cs = _compiled_step(net)
    x, y = _batch()
    cs.step(x, y, 8).wait_to_read()
    path = str(tmp_path / "memrep.json")
    memobs.dump_report(path, params=net.collect_params())
    rep = json.loads(open(path).read())
    assert rep["n_programs"] >= 1
    mxmem = _tool("mxmem")
    text = mxmem.render_report(rep)
    assert "programs by peak footprint" in text
    assert cs.name[:44] in text
    assert "param HBM attribution" in text
    assert "live buffers" in text
    assert mxmem.main(["render", path]) == 0
    # top-N honors the env knob
    assert len(memobs.report(top_n=0)["programs"]) == 0


def test_report_top_n_env(monkeypatch):
    net = _mlp()
    cs = _compiled_step(net)
    x, y = _batch()
    cs.step(x, y, 8).wait_to_read()
    monkeypatch.setenv("MXTPU_MEM_REPORT_TOP_N", "1")
    rep = memobs.report()
    assert len(rep["programs"]) <= 1
    assert rep["n_programs"] >= 1


def test_report_collectives_not_double_counted_across_variants():
    # step_multi bulking harvests `<base>_k{K}[r]` variants of the SAME
    # train step; the report's per-step collective table must count
    # each logical program once (most recent variant wins), not sum
    # the base with its bulk variants
    def _rec(name, seq, wire):
        return {"name": name, "kind": "program", "source": "fresh",
                "analytic": False, "peak_bytes": 1, "harvests": 1,
                "seq": seq, "donation_saved_bytes": wire * 2,
                "collectives": {"all-reduce": {
                    "count": 1, "payload_bytes": wire // 2,
                    "wire_bytes": wire}},
                "collective_wire_bytes": wire}
    with memobs._lock:
        memobs._programs["spmd_full_step"] = _rec(
            "spmd_full_step", 1, 1000)
        memobs._programs["spmd_full_step_k8"] = _rec(
            "spmd_full_step_k8", 2, 1024)
        memobs._programs["spmd_full_step_k4r"] = _rec(
            "spmd_full_step_k4r", 3, 1040)
        memobs._programs["other_step"] = _rec("other_step", 4, 100)
    try:
        rep = memobs.report()
        ar = rep["collectives"]["all-reduce"]
        # latest spmd variant (seq 3) + the distinct other_step
        assert ar["wire_bytes"] == 1040 + 100
        assert ar["count"] == 2
        blk = memobs.cache_info_block()
        assert blk["collective_wire_bytes"] == 1040 + 100
        # donation roll-up dedups the same way (a bulk variant's
        # donation is the same buffers as its base's)
        assert blk["donation_saved_bytes"] == (1040 + 100) * 2
    finally:
        memobs.reset()


def test_collective_stats_parser():
    hlo = """
  %all-reduce = f32[1024]{0} all-reduce(f32[1024]{0} %p), replica_groups=[1,8]<=[8], to_apply=%add
  %rs = f32[128]{0} reduce-scatter(f32[1024]{0} %p), replica_groups=[1,8]<=[8]
  %ag = f32[1024]{0} all-gather(f32[128]{0} %p), replica_groups={{0,1,2,3,4,5,6,7}}
"""
    stats = memobs.collective_stats(hlo)
    k = stats["kinds"]
    assert k["all-reduce"]["count"] == 1
    assert k["all-reduce"]["payload_bytes"] == 4096
    assert k["all-reduce"]["wire_bytes"] == int(2 * 4096 * 7 / 8)
    assert k["reduce-scatter"]["payload_bytes"] == 512
    assert k["reduce-scatter"]["wire_bytes"] == 512 * 7
    assert k["all-gather"]["payload_bytes"] == 4096
    assert k["all-gather"]["wire_bytes"] == int(4096 * 7 / 8)
    assert stats["total_wire_bytes"] == sum(
        row["wire_bytes"] for row in k.values())


def test_collective_stats_async_pairs():
    # TPU's latency-hiding scheduler emits start/done pairs whose START
    # tuple interleaves operand and result shapes: counting the start
    # would overcount the payload by the operand, so the pair counts
    # ONCE — at the done, with the group size carried over from the
    # start (replica_groups only appears there)
    hlo = """
  %ag-start.1 = (f32[128]{0}, f32[1024]{0}) all-gather-start(f32[128]{0} %p), replica_groups=[1,8]<=[8]
  %ag-done.1 = f32[1024]{0} all-gather-done((f32[128]{0}, f32[1024]{0}) %ag-start.1)
  %ar-start = (f32[256]{0}, f32[256]{0}) all-reduce-start(f32[256]{0} %q), replica_groups=[2,4]<=[8]
  %ar-done = f32[256]{0} all-reduce-done((f32[256]{0}, f32[256]{0}) %ar-start)
"""
    stats = memobs.collective_stats(hlo)
    k = stats["kinds"]
    assert k["all-gather"]["count"] == 1
    assert k["all-gather"]["payload_bytes"] == 4096   # result, not +shard
    assert k["all-gather"]["wire_bytes"] == int(4096 * 7 / 8)
    assert k["all-reduce"]["count"] == 1
    assert k["all-reduce"]["payload_bytes"] == 1024
    # group size 4 came from the -start line
    assert k["all-reduce"]["wire_bytes"] == int(2 * 1024 * 3 / 4)


def test_self_check_includes_memory_pass():
    # the pass is wired into the CI gate and free on a clean registry
    telemetry.reset()
    findings, ok = analysis.self_check()
    assert ok
    assert not any(f.rule in ("MXL308", "MXL309") for f in findings)


# ---------------------------------------------------------------------------
# the scope map: executables kept weakly at the harvest seam, HLO text
# rendered by ``profiler.device_scopes()`` and by nothing before it
# ---------------------------------------------------------------------------

def _toy_step():
    """A step with the fused step's own pieces: the optimizer rule and
    the health plane's reductions, each under its scope."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu import optimizer as opt_mod
    from mxnet_tpu.parallel import trainer as trainer_mod
    from mxnet_tpu.telemetry import health

    opt = opt_mod.create("sgd", learning_rate=0.1, momentum=0.9)
    rule = trainer_mod._FUSED_RULES["SGD"]
    spec = health.build_spec("toy_", ["toy_w"], [0])

    def toy_step(w, mom, x, scal):
        def loss_of(w):
            with mx.profiler.device_scope("mxtpu.mlp"):
                return jnp.sum(jnp.tanh(x @ w) ** 2)

        loss, g = jax.value_and_grad(loss_of)(w)
        (new_w,), (new_mom,) = trainer_mod._apply_rule(
            rule, opt, 1, 2, lambda j: w, ((mom,),), (g,), scal)
        hvec = health.compute(spec, loss, (w,), (g,), (new_w,))
        return new_w, new_mom[0], hvec

    rng = np.random.RandomState(0)
    args = (rng.rand(8, 8).astype("f4"), np.zeros((8, 8), "f4"),
            rng.rand(4, 8).astype("f4"),
            np.asarray([0.1, 0.0], "f4"))
    return toy_step, args


def _scopes_found(module):
    table = mx.profiler.device_scopes().get(module, {})
    return {scope for scope, _bwd, _inh in table.values()}, table


def test_device_scopes_maps_the_step_pieces_fresh_and_reloaded(
        tmp_path, monkeypatch):
    monkeypatch.setenv("MXTPU_COMPILE_CACHE_DIR", str(tmp_path / "cc"))
    fn, args = _toy_step()
    engine.invoke_compiled("toy_scoped_step", fn, {}, *args,
                           persist_name="toy_scoped_step")
    assert any(n == "toy_scoped_step" for n, _c in memobs.executables())
    found, table = _scopes_found("jit_toy_step")
    assert {"mxtpu.mlp", "mxtpu.step.optimizer",
            "mxtpu.step.health"} <= found
    # the forward / backward split of one scope
    assert {bwd for scope, bwd, _inh in table.values()
            if scope == "mxtpu.mlp"} == {False, True}
    # a second call renders nothing again: cached on the executable
    def boom(_self):
        raise AssertionError("HLO text rendered twice")
    import jax
    with monkeypatch.context() as m:
        m.setattr(jax.stages.Compiled, "as_text", boom)
        assert _scopes_found("jit_toy_step")[0] == found
    # a fresh memory tier: the executable comes back from the persist
    # tier and still says where its ops came from
    h0 = engine.persist.counters()["hits"]
    engine.clear_cache()
    telemetry.reset()
    assert memobs.executables() == []
    engine.invoke_compiled("toy_scoped_step", fn, {}, *args,
                           persist_name="toy_scoped_step")
    assert engine.persist.counters()["hits"] == h0 + 1
    assert _scopes_found("jit_toy_step")[0] == found
    engine.drop_cached("toy_scoped_step", persistent=True)


def test_no_hlo_text_is_rendered_until_device_scopes_is_called(
        tmp_path, monkeypatch):
    """``setup_s`` is an end-to-end metric: an untraced run must not
    render a program's text, neither when it compiles nor when it
    reloads (on the one-chip machine; a process with several devices
    renders it once, at the fresh compile, for the collectives)."""
    import jax
    monkeypatch.setenv("MXTPU_COMPILE_CACHE_DIR", str(tmp_path / "cc"))
    monkeypatch.setattr(memobs, "_single_device", lambda: True)

    def boom(_self):
        raise AssertionError("an untraced run rendered HLO text")
    monkeypatch.setattr(jax.stages.Compiled, "as_text", boom)
    fn, args = _toy_step()
    engine.invoke_compiled("toy_untraced_step", fn, {}, *args,
                           persist_name="toy_untraced_step")
    engine.clear_cache()
    engine.invoke_compiled("toy_untraced_step", fn, {}, *args,
                           persist_name="toy_untraced_step")
    assert telemetry.events("persist_error") == []
    assert [n for n, _c in memobs.executables()
            if n == "toy_untraced_step"]
    engine.drop_cached("toy_untraced_step", persistent=True)


def test_the_map_outlives_the_owner_of_a_fresh_executable():
    """The benchmark's train driver drops its trainer, and with it the
    freshly compiled step, before the trace is read (the chip showed a
    weak reference dead by then): the record keeps the newest
    executable of a name until ``telemetry.reset()``."""
    import gc
    fn, args = _toy_step()
    engine.invoke_compiled("toy_owned_step", fn, {}, *args,
                           persist_name="toy_owned_step")
    engine.drop_cached("toy_owned_step")      # the engine lets go of it
    gc.collect()
    assert "mxtpu.step.optimizer" in _scopes_found("jit_toy_step")[0]
    # not in the JSON face of the record
    assert "executable" not in \
        engine.cache_info()["memory"]["per_program"]["toy_owned_step"]
    telemetry.reset()
    assert mx.profiler.device_scopes() == {}


def test_two_executables_of_one_module_name_are_never_merged(tmp_path):
    """``fusion.N`` of two compiles of one function name mean different
    things: the module's map is the NEWEST executable's alone, and the
    reader says that the name is shared."""
    import jax
    import jax.numpy as jnp

    def twin(scope):
        def toy_twin(w):
            with mx.profiler.device_scope(scope):
                return jnp.tanh(w) * 2.0
        return toy_twin

    for name, scope in (("toy_twin_a", "mxtpu.mlp"),
                        ("toy_twin_b", "mxtpu.head")):
        engine.invoke_compiled(name, twin(scope), {},
                               np.ones((4, 4), "f4"), persist_name=name)
    try:
        found, _table = _scopes_found("jit_toy_twin")
        assert found == {"mxtpu.head"}
        jax.profiler.start_trace(str(tmp_path))
        jnp.ones((2,)).block_until_ready()
        jax.profiler.stop_trace()
        out = json.loads(mx.profiler.device_dumps(str(tmp_path)))
        assert out["shadowed"] == ["jit_toy_twin"]
    finally:
        for name in ("toy_twin_a", "toy_twin_b"):
            engine.drop_cached(name, persistent=True)


def test_disabled_telemetry_keeps_no_executable():
    telemetry.disable()
    try:
        engine.invoke_compiled("toy_dark_step", lambda w: w * 2.0, {},
                               np.ones((4, 4), "f4"),
                               persist_name="toy_dark_step")
        assert memobs.executables() == []
        assert mx.profiler.device_scopes() == {}
    finally:
        telemetry.enable()
        engine.drop_cached("toy_dark_step")
