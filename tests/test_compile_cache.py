"""Persistent compile cache + AOT warm-start (docs/compile_cache.md).

Tier-1 coverage for the engine's second cache tier and the whole-step
warm-start path:

* CPU round-trip: a simulated process restart (memory tier cleared)
  serves the executable from disk — 0 fresh compiles, asserted via the
  engine/telemetry compile counters;
* invalidation: a library-salt (version) bump misses cleanly;
* corruption tolerance: a truncated/garbage entry falls back to a
  fresh compile (never a crash) and is reported by mxlint's MXL402 /
  ``tools/mxcache.py verify``;
* donation is still honored after an executable reload;
* ``CompiledStep.save_signature`` / ``Trainer.warm_start`` precompile
  the whole fused train step from a manifest: 0 fresh compiles in the
  warm process and a bit-identical first step;
* the ``DataParallelTrainer`` equivalent records the mesh layout and
  rejects a mismatched mesh;
* ``cache_info()`` exposes the persistent hit/miss/seconds-saved
  counters; LRU pruning bounds the dir.
"""
import json
import os

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import engine, gluon, nd, telemetry
from mxnet_tpu.engine import persist


@pytest.fixture(autouse=True)
def _preserve_engine_cache():
    """These tests clear the PROCESS-WIDE jit cache to simulate
    restarts; snapshot it and put the pre-existing warm entries back so
    the rest of the suite doesn't re-pay every shared-op compile (the
    870 s tier-1 budget is real)."""
    saved = dict(engine._jit_cache)
    yield
    engine.clear_cache()           # drops tiered wrappers w/ tmp dirs
    engine._jit_cache.update(saved)
    engine.reset_counters()


@pytest.fixture
def cache_dir(monkeypatch, tmp_path):
    d = str(tmp_path / "mxcache")
    monkeypatch.setenv("MXTPU_COMPILE_CACHE_DIR", d)
    engine.clear_cache()
    engine.reset_counters()
    telemetry.reset()
    yield d


def _fresh_compiles():
    return engine.cache_info()["fresh_compiles"]


def _restart():
    """Simulate a process restart for the engine: the memory tier dies
    with the process, the persistent tier does not."""
    engine.clear_cache()
    engine.reset_counters()


# ---------------------------------------------------------------------------
# engine tier
# ---------------------------------------------------------------------------


def test_roundtrip_second_process_compiles_nothing(cache_dir):
    def f(a, b):
        return a * b + 1.0

    x = nd.array(np.full((4,), 3.0, "f4"))
    y = nd.array(np.full((4,), 2.0, "f4"))
    out1 = np.asarray(engine.invoke_compiled("cc_demo", f, {},
                                             x._data, y._data))
    info = engine.cache_info()
    assert info["fresh_compiles"] == 1
    assert info["persist"]["enabled"]
    assert info["persist"]["misses"] == 1

    _restart()
    out2 = np.asarray(engine.invoke_compiled("cc_demo", f, {},
                                             x._data, y._data))
    info = engine.cache_info()
    assert info["fresh_compiles"] == 0, \
        "second process must load, not compile"
    assert info["persist"]["hits"] == 1
    assert info["persist"]["seconds_saved"] > 0
    np.testing.assert_array_equal(out1, out2)
    # the telemetry plane sees the same story
    snap = telemetry.snapshot()["counters"]
    assert snap.get("mxtpu_persist_hits_total") == 1


def test_distinct_attrs_and_shapes_get_distinct_entries(cache_dir):
    def f(a, *, k=1.0):
        return a * k

    x = nd.array(np.ones((4,), "f4"))
    engine.invoke_compiled("cc_attrs", f, {"k": 2.0}, x._data)
    engine.invoke_compiled("cc_attrs", f, {"k": 3.0}, x._data)
    x8 = nd.array(np.ones((8,), "f4"))
    engine.invoke_compiled("cc_attrs", f, {"k": 2.0}, x8._data)
    assert len(os.listdir(cache_dir)) == 3
    _restart()
    out = np.asarray(engine.invoke_compiled("cc_attrs", f, {"k": 3.0},
                                            x._data))
    np.testing.assert_array_equal(out, np.full((4,), 3.0, "f4"))
    assert _fresh_compiles() == 0


def test_version_salt_invalidation(cache_dir, monkeypatch):
    def f(a):
        return a + 1.0

    x = nd.array(np.zeros((3,), "f4"))
    engine.invoke_compiled("cc_salt", f, {}, x._data)
    assert _fresh_compiles() == 1

    _restart()
    # nested context: undo must not strip the fixture's cache-dir env
    with monkeypatch.context() as m:
        m.setattr(persist, "LIBRARY_SALT", "bumped-by-test")
        persist._reset_fingerprint()
        engine.invoke_compiled("cc_salt", f, {}, x._data)
        info = engine.cache_info()
        assert info["fresh_compiles"] == 1, \
            "a salt bump must invalidate every prior entry"
        assert info["persist"]["hits"] == 0
    persist._reset_fingerprint()


def test_corrupted_entry_falls_back_to_fresh_compile(cache_dir):
    def f(a):
        return a * 10.0

    x = nd.array(np.ones((5,), "f4"))
    engine.invoke_compiled("cc_corrupt", f, {}, x._data)
    (entry,) = os.listdir(cache_dir)
    path = os.path.join(cache_dir, entry)
    with open(path, "rb") as fh:
        blob = fh.read()
    with open(path, "wb") as fh:          # truncate mid-payload
        fh.write(blob[:len(blob) // 2])

    _restart()
    out = np.asarray(engine.invoke_compiled("cc_corrupt", f, {},
                                            x._data))
    np.testing.assert_array_equal(out, np.full((5,), 10.0, "f4"))
    info = engine.cache_info()
    assert info["fresh_compiles"] == 1          # recovered by compiling
    assert info["persist"]["hits"] == 0
    # the bad entry was evicted and rewritten by the fresh compile
    assert all(r["ok"] for r in persist.verify())


def test_garbage_entry_never_crashes_and_mxl402_flags_it(cache_dir):
    os.makedirs(cache_dir, exist_ok=True)
    bad = os.path.join(cache_dir, "cc_garbage-deadbeef.mxc")
    with open(bad, "wb") as fh:
        fh.write(b"not a cache entry at all")
    rows = persist.verify()
    assert [r for r in rows if not r["ok"]]
    from mxnet_tpu import analysis
    findings = analysis.analyze_compile_cache()
    assert len(findings) == 1
    assert findings[0].rule == "MXL402"
    assert findings[0].severity == "error"
    assert "cc_garbage" in findings[0].message


def test_donation_honored_after_reload(cache_dir):
    def f(a):
        return a + 5.0

    x = nd.array(np.ones((3,), "f4"))
    engine.invoke_compiled("cc_donate", f, {}, x._data, donate=(0,))
    assert x._data.is_deleted()

    _restart()
    x2 = nd.array(np.ones((3,), "f4"))
    out = np.asarray(engine.invoke_compiled("cc_donate", f, {},
                                            x2._data, donate=(0,)))
    assert _fresh_compiles() == 0, "reload, not recompile"
    assert x2._data.is_deleted(), \
        "the reloaded executable must keep the donation contract"
    np.testing.assert_array_equal(out, np.full((3,), 6.0, "f4"))


class _RefusingJit:
    """A jitted fn whose AOT ``lower`` fails while a plain call would
    still work — what a quiet demotion to jit used to hide."""

    def __init__(self, jitted):
        self._jitted = jitted

    def lower(self, *args):
        raise RuntimeError("compiler refused this program")

    def __call__(self, *args):
        return self._jitted(*args)


@pytest.mark.parametrize("donate", [(0,), ()], ids=["donating", "per_op"])
def test_aot_lower_failure_raises_not_demotes(cache_dir, monkeypatch,
                                              donate):
    """Any tiered entry — a donating step or a per-op entry tiered
    because the persistent tier is on — raises what its AOT lower
    raises, on every call, and counts no demotion."""
    real_jit = engine._TieredFn._jit
    monkeypatch.setattr(engine._TieredFn, "_jit",
                        lambda self: _RefusingJit(real_jit(self)))

    def f(a):
        return a + 5.0

    x = nd.array(np.ones((3,), "f4"))
    for _ in range(2):
        with pytest.raises(RuntimeError, match="compiler refused"):
            engine.invoke_compiled("cc_refused", f, {}, x._data,
                                   donate=donate)
    assert not x._data.is_deleted(), "nothing ran, nothing was donated"
    assert engine.cache_info()["aot_demotions"] == 0
    assert telemetry.events("persist_error") == []


def test_aval_drift_demotion_is_counted(cache_dir):
    """The one demotion left: an AOT executable that rejects a drift the
    (shape, dtype) signature cannot see (here tuple -> list) with
    ``TypeError`` hands that signature to plain jit, once, counted."""
    def f(a, pair):
        return a * pair[0] + pair[1]

    pair = (np.float32(2.0), np.float32(1.0))
    x = nd.array(np.ones((3,), "f4"))
    engine.invoke_compiled("cc_drift", f, {}, x._data, pair, donate=(0,))
    assert engine.cache_info()["aot_demotions"] == 0

    for _ in range(2):
        x = nd.array(np.ones((3,), "f4"))
        out = np.asarray(engine.invoke_compiled(
            "cc_drift", f, {}, x._data, list(pair), donate=(0,)))
        np.testing.assert_array_equal(out, np.full((3,), 3.0, "f4"))
        assert x._data.is_deleted(), "the jit path keeps the donation"
    assert engine.cache_info()["aot_demotions"] == 1
    events = telemetry.events("persist_error")
    assert len(events) == 1 and events[0]["op"] == "cc_drift"
    assert "aot demoted" in events[0]["error"]
    engine.reset_counters()
    assert engine.cache_info()["aot_demotions"] == 0


def test_export_fallback_when_executable_serialization_unavailable(
        cache_dir, monkeypatch):
    """Backends without executable serialization fall back to the
    jax.export (StableHLO) payload: reload still skips the Python
    trace."""
    from jax.experimental import serialize_executable as se

    def boom(*a, **k):
        raise RuntimeError("serialization unavailable on this backend")

    def f(a):
        return a - 1.5

    x = nd.array(np.ones((4,), "f4"))
    # nested context: a bare undo would also strip the fixture's
    # cache-dir env and silently disable the tier
    with monkeypatch.context() as m:
        m.setattr(se, "serialize", boom)
        engine.invoke_compiled("cc_export", f, {}, x._data)
    rows = persist.ls()
    assert [r for r in rows if r["kind"] == "export"]

    _restart()
    out = np.asarray(engine.invoke_compiled("cc_export", f, {},
                                            x._data))
    np.testing.assert_array_equal(out, np.full((4,), -0.5, "f4"))
    info = engine.cache_info()
    assert info["persist"]["hits"] == 1
    assert info["fresh_compiles"] == 0


def test_clear_and_drop_persistent_scope(cache_dir):
    def f(a):
        return a * 2.0

    x = nd.array(np.ones((2,), "f4"))
    engine.invoke_compiled("cc_keep", f, {}, x._data)
    engine.invoke_compiled("cc_drop", f, {}, x._data)
    assert len(os.listdir(cache_dir)) == 2
    engine.drop_cached("cc_drop", persistent=True)
    names = os.listdir(cache_dir)
    assert len(names) == 1 and names[0].startswith("cc_keep")
    engine.clear_cache(persistent=True)
    assert os.listdir(cache_dir) == []


def test_lru_prune_bounds_the_dir(cache_dir):
    def f(a):
        return a + 2.0

    for n in range(4):
        x = nd.array(np.ones((4 + n,), "f4"))
        engine.invoke_compiled("cc_lru", f, {}, x._data)
    assert len(os.listdir(cache_dir)) == 4
    sizes = [os.path.getsize(os.path.join(cache_dir, p))
             for p in os.listdir(cache_dir)]
    # bound to roughly two entries: the two oldest must go
    removed = persist.prune(limit=sum(sizes) - min(sizes) - 1)
    assert removed >= 1
    assert len(os.listdir(cache_dir)) == 4 - removed
    assert persist.prune(limit=0) == 4 - removed
    assert os.listdir(cache_dir) == []


# ---------------------------------------------------------------------------
# AOT warm-start: CompiledStep / Trainer
# ---------------------------------------------------------------------------


def _mlp(prefix):
    mx.random.seed(0)
    np.random.seed(0)
    net = gluon.nn.HybridSequential(prefix=prefix)
    with net.name_scope():
        net.add(gluon.nn.Dense(8, activation="relu", in_units=6),
                gluon.nn.Dropout(0.2),
                gluon.nn.Dense(3, in_units=8))
    net.initialize(mx.init.Xavier())
    net.hybridize()
    tr = gluon.Trainer(net.collect_params(), "adam",
                       {"learning_rate": 0.01}, kvstore=None)
    return net, tr


def _batch():
    X = nd.array(np.random.RandomState(2).rand(4, 6).astype("f4"))
    Y = nd.array(np.random.RandomState(3).rand(4, 3).astype("f4"))
    return X, Y


def test_warm_start_precompiles_compiled_step_manifest(cache_dir,
                                                       tmp_path):
    l2 = gluon.loss.L2Loss()
    X, Y = _batch()
    net, tr = _mlp("cc_cold_")
    cs = tr.compile_step(net, l2)
    loss_cold = cs.step(X, Y, 4).asnumpy()
    assert cs.last_path == "compiled"
    manifest = str(tmp_path / "step.json")
    cs.save_signature(manifest)
    m = json.loads(open(manifest).read())
    assert m["kind"] == "gluon_compiled_step" and m["variants"]

    _restart()
    net2, tr2 = _mlp("cc_warm_")
    cs2 = tr2.warm_start(net2, l2, manifest)
    assert cs2.warm_started
    assert _fresh_compiles() == 0, \
        "warm start must reload, not compile"
    loss_warm = cs2.step(X, Y, 4).asnumpy()
    assert cs2.last_path == "compiled"
    info = engine.cache_info()
    assert info["fresh_compiles"] == 0
    assert info["persist"]["hits"] >= 1
    # same seed + same program => the warm process's first step is the
    # cold process's first step, bit for bit
    np.testing.assert_array_equal(loss_cold, loss_warm)


def test_warm_start_step_multi_variant(cache_dir, tmp_path):
    l2 = gluon.loss.L2Loss()
    X, Y = _batch()
    net, tr = _mlp("cc_multi_cold_")
    cs = tr.compile_step(net, l2)
    losses_cold = cs.step_multi(X, Y, 4, repeat=3).asnumpy()
    manifest = str(tmp_path / "step.json")
    cs.save_signature(manifest)

    _restart()
    net2, tr2 = _mlp("cc_multi_warm_")
    cs2 = tr2.warm_start(net2, l2, manifest)
    assert cs2.warm_started and _fresh_compiles() == 0
    losses_warm = cs2.step_multi(X, Y, 4, repeat=3).asnumpy()
    assert _fresh_compiles() == 0
    np.testing.assert_array_equal(losses_cold, losses_warm)


def test_warm_start_rejects_mismatched_manifest(cache_dir, tmp_path):
    l2 = gluon.loss.L2Loss()
    X, Y = _batch()
    net, tr = _mlp("cc_mm_a_")
    cs = tr.compile_step(net, l2)
    cs.step(X, Y, 4)
    manifest = str(tmp_path / "step.json")
    cs.save_signature(manifest)

    # different architecture: structural hash must reject, harmlessly
    mx.random.seed(0)
    np.random.seed(0)
    other = gluon.nn.HybridSequential(prefix="cc_mm_b_")
    with other.name_scope():
        other.add(gluon.nn.Dense(16, activation="relu", in_units=6),
                  gluon.nn.Dense(3, in_units=16))
    other.initialize(mx.init.Xavier())
    other.hybridize()
    tr2 = gluon.Trainer(other.collect_params(), "adam",
                        {"learning_rate": 0.01}, kvstore=None)
    cs2 = tr2.warm_start(other, l2, manifest)
    assert not cs2.warm_started
    # unreadable manifests are equally harmless
    bad = str(tmp_path / "bad.json")
    with open(bad, "w") as f:
        f.write("{truncated")
    net3, tr3 = _mlp("cc_mm_c_")
    cs3 = tr3.compile_step(net3, l2)
    assert cs3.warm_start(bad) is False
    # ...and the step still trains via the normal cold path
    cs3.step(X, Y, 4)
    assert cs3.last_path == "compiled"


def test_warm_start_without_cache_dir_still_precompiles(tmp_path):
    """No MXTPU_COMPILE_CACHE_DIR: the manifest alone still drives an
    AOT precompile (compile moved BEFORE the first batch, overlapping
    DataLoader spin-up), just without cross-process reuse."""
    engine.clear_cache()
    engine.reset_counters()
    l2 = gluon.loss.L2Loss()
    X, Y = _batch()
    net, tr = _mlp("cc_nodir_a_")
    cs = tr.compile_step(net, l2)
    loss_cold = cs.step(X, Y, 4).asnumpy()
    manifest = str(tmp_path / "step.json")
    cs.save_signature(manifest)

    engine.clear_cache()
    engine.reset_counters()
    net2, tr2 = _mlp("cc_nodir_b_")
    cs2 = tr2.warm_start(net2, l2, manifest)
    assert cs2.warm_started
    assert _fresh_compiles() >= 1          # compiled at warm_start...
    pre_step = _fresh_compiles()
    loss_warm = cs2.step(X, Y, 4).asnumpy()
    assert _fresh_compiles() == pre_step   # ...not at the first batch
    np.testing.assert_array_equal(loss_cold, loss_warm)


def _bn_net(prefix):
    mx.random.seed(0)
    np.random.seed(0)
    net = gluon.nn.HybridSequential(prefix=prefix)
    with net.name_scope():
        net.add(gluon.nn.Dense(8, in_units=6),
                gluon.nn.BatchNorm(),
                gluon.nn.Dense(3, in_units=8))
    net.initialize(mx.init.Xavier())
    net.hybridize()
    return net


def _running_stats(net):
    return {i: p.data().asnumpy()
            for i, (k, p) in enumerate(
                sorted(net.collect_params().items()))
            if "running" in k}


def test_warm_start_batchnorm_aux_written_back(cache_dir, tmp_path):
    """A persist hit skips the trace that discovers mutated_idx; the
    manifest must restore the aux routing or BatchNorm running stats
    silently freeze.  Two warm steps must match two cold steps bit for
    bit, running stats included."""
    l2 = gluon.loss.L2Loss()
    X, Y = _batch()
    net, = (_bn_net("cc_bn_a_"),)
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 0.05, "momentum": 0.9},
                       kvstore=None)
    cs = tr.compile_step(net, l2)
    cs.step(X, Y, 4)
    assert cs.last_path == "compiled"
    assert cs._mutated_idx, "BN net must report mutated aux params"
    manifest = str(tmp_path / "bn.json")
    cs.save_signature(manifest)
    cs.step(X, Y, 4)
    cold_stats = _running_stats(net)

    _restart()
    net2 = _bn_net("cc_bn_b_")
    tr2 = gluon.Trainer(net2.collect_params(), "sgd",
                        {"learning_rate": 0.05, "momentum": 0.9},
                        kvstore=None)
    cs2 = tr2.warm_start(net2, l2, manifest)
    assert cs2.warm_started and _fresh_compiles() == 0
    assert cs2._mutated_idx == cs._mutated_idx
    cs2.step(X, Y, 4)
    cs2.step(X, Y, 4)
    assert _fresh_compiles() == 0
    warm_stats = _running_stats(net2)
    assert cold_stats, "test net must actually carry running stats"
    for i in cold_stats:
        np.testing.assert_array_equal(cold_stats[i], warm_stats[i])
        # and they moved away from init (0 mean / 1 var)
    assert any(np.abs(v).sum() > 0 for v in warm_stats.values())


# ---------------------------------------------------------------------------
# AOT warm-start: DataParallelTrainer (mesh layout in the manifest)
# ---------------------------------------------------------------------------


def _spmd(prefix, n_dev=1):
    from mxnet_tpu import parallel
    mx.random.seed(0)
    np.random.seed(0)
    net = gluon.nn.HybridSequential(prefix=prefix)
    with net.name_scope():
        net.add(gluon.nn.Dense(8, activation="relu", in_units=6),
                gluon.nn.Dense(3, in_units=8))
    net.initialize(mx.init.Xavier())
    net.hybridize()
    mesh = parallel.make_mesh({"dp": n_dev})
    dpt = parallel.DataParallelTrainer(
        net, gluon.loss.L2Loss(), "adam", {"learning_rate": 0.01},
        mesh=mesh, fuse_step=True)
    return net, dpt


def test_spmd_warm_start_records_and_checks_mesh(cache_dir, tmp_path):
    X, Y = _batch()
    net, dpt = _spmd("cc_spmd_a_")
    l1 = dpt.step(X, Y).asnumpy()
    manifest = str(tmp_path / "spmd.json")
    dpt.save_signature(manifest)
    m = json.loads(open(manifest).read())
    assert m["kind"] == "spmd_full_step"
    assert m["mesh"] == {"dp": 1} and m["dp_axis"] == "dp"
    assert len(m["param_shardings"]) == 4      # 2 dense layers * (W, b)

    _restart()
    net2, dpt2 = _spmd("cc_spmd_b_")
    assert dpt2.warm_start(manifest)
    assert _fresh_compiles() == 0
    l2_ = dpt2.step(X, Y).asnumpy()
    assert _fresh_compiles() == 0
    np.testing.assert_array_equal(l1, l2_)

    # a mesh-SIZE change is no longer a hard reject: the manifest's
    # avals re-AOT on the new layout (reshard + re-AOT, fresh compiles
    # expected — the serialized executable baked the OLD mesh and must
    # not be reused; docs/elasticity.md)
    from conftest import needs_devices
    needs_devices(2)
    net3, dpt3 = _spmd("cc_spmd_c_", n_dev=2)
    assert dpt3.warm_start(manifest) is True
    assert _fresh_compiles() > 0, \
        "a resharded warm start must re-AOT, never adopt the old " \
        "mesh's executable"
    l3 = dpt3.step(X, Y).asnumpy()
    np.testing.assert_array_equal(l1, l3)

    # a different AXIS STRUCTURE (dp axis missing from the manifest's
    # mesh) is still a hard reject
    net4, dpt4 = _spmd("cc_spmd_d_", n_dev=2)
    m2 = dict(m)
    m2["mesh"] = {"tp": 1}
    bad = str(tmp_path / "spmd_bad_mesh.json")
    open(bad, "w").write(json.dumps(m2))
    assert dpt4.warm_start(bad) is False

    # a resharded manifest from a DIFFERENT model is also rejected —
    # the persist-name hash bakes mesh sizes so it cannot carry the
    # check across a reshard; the mesh-independent struct hash does
    net4b, dpt4b = _spmd("cc_spmd_db_", n_dev=2)
    m3 = dict(m)
    m3["struct"] = "0" * 16
    bad_struct = str(tmp_path / "spmd_bad_struct.json")
    open(bad_struct, "w").write(json.dumps(m3))
    assert dpt4b.warm_start(bad_struct) is False

    # the manifest round-trips the NEW layout: after the resharded
    # process re-saves its signature, a second restart on that mesh
    # warm-starts with 0 fresh compiles (docs/elasticity.md)
    manifest2 = str(tmp_path / "spmd2.json")
    dpt3.save_signature(manifest2)
    assert json.loads(open(manifest2).read())["mesh"] == {"dp": 2}
    _restart()
    net5, dpt5 = _spmd("cc_spmd_e_", n_dev=2)
    assert dpt5.warm_start(manifest2) is True
    assert _fresh_compiles() == 0
    l5 = dpt5.step(X, Y).asnumpy()
    assert _fresh_compiles() == 0
    np.testing.assert_array_equal(l1, l5)


@pytest.mark.parametrize("variant", ["k0", "k3"])
def test_spmd_warm_start_round_trips_the_scalar_vector(
        cache_dir, tmp_path, variant):
    """The manifest records the optimizer scalars as ONE row, the
    ``(S,)`` vector of ``step()`` or the ``(K, S)`` stack of
    ``step_multi``; a fresh trainer warm-started from it pays no
    compile on its first step."""
    X, Y = _batch()
    Xk, Yk = (nd.array(np.stack([a.asnumpy()] * 3)) for a in (X, Y))

    def first_step(dpt):
        if variant == "k0":
            return dpt.step(X, Y).asnumpy()
        return dpt.step_multi(Xk, Yk).asnumpy()

    net, dpt = _spmd(f"cc_vec_{variant}_a_")
    l1 = first_step(dpt)
    manifest = str(tmp_path / "spmd.json")
    dpt.save_signature(manifest)
    row, = json.loads(open(manifest).read())["variants"]
    n_scalars = 2 * len(dpt._tr_idx)               # Adam: lr, wd
    assert row["scalars"] == [[[n_scalars] if variant == "k0"
                               else [3, n_scalars], "float32"]]

    _restart()
    net2, dpt2 = _spmd(f"cc_vec_{variant}_b_")
    assert dpt2.warm_start(manifest) is True
    assert _fresh_compiles() == 0
    np.testing.assert_array_equal(first_step(dpt2), l1)
    assert _fresh_compiles() == 0


def test_spmd_warm_start_refuses_one_leaf_per_scalar_manifest(
        cache_dir, tmp_path):
    """A manifest from before the scalars travelled as one vector
    lists one 0-d row per scalar for the single-step variant.  It
    fails open: a failure record, nothing pre-compiled for a call
    shape no step makes, and the next step runs cold and correct."""
    X, Y = _batch()
    net, dpt = _spmd("cc_old_a_")
    l1 = dpt.step(X, Y).asnumpy()
    manifest = str(tmp_path / "spmd.json")
    dpt.save_signature(manifest)
    m = json.loads(open(manifest).read())
    (n_scalars,), dtype = m["variants"][0]["scalars"][0]
    m["variants"][0]["scalars"] = [[[], dtype]] * n_scalars
    old = str(tmp_path / "spmd_old.json")
    open(old, "w").write(json.dumps(m))

    _restart()
    telemetry.reset()
    net2, dpt2 = _spmd("cc_old_b_")
    assert dpt2.warm_start(old) is False
    ev = telemetry.events("warm_start")[-1]
    assert ev["ok"] is False and "one vector" in ev["reason"]
    assert not dpt2.warm_started and dpt2._full_exec is None
    assert _fresh_compiles() == 0
    np.testing.assert_array_equal(dpt2.step(X, Y).asnumpy(), l1)
    np.testing.assert_array_equal(dpt2.step(X, Y).asnumpy(),
                                  dpt.step(X, Y).asnumpy())


def test_spmd_warm_start_batchnorm_aux(cache_dir, tmp_path):
    """The SPMD twin of the gluon BN test: a persist hit never traces,
    so the manifest's mutated_idx must survive _build_fwd_bwd's list
    rebind — otherwise running stats freeze silently."""
    from mxnet_tpu import parallel

    def build(prefix):
        net = _bn_net(prefix)
        mesh = parallel.make_mesh({"dp": 1})
        return net, parallel.DataParallelTrainer(
            net, gluon.loss.L2Loss(), "adam",
            {"learning_rate": 0.01}, mesh=mesh, fuse_step=True)

    X, Y = _batch()
    net, dpt = build("cc_spmd_bn_a_")
    dpt.step(X, Y)
    assert dpt._mutated_idx
    manifest = str(tmp_path / "spmd_bn.json")
    dpt.save_signature(manifest)
    dpt.step(X, Y)
    cold_stats = _running_stats(net)

    _restart()
    net2, dpt2 = build("cc_spmd_bn_b_")
    assert dpt2.warm_start(manifest)
    assert dpt2._mutated_idx == dpt._mutated_idx
    dpt2.step(X, Y)
    dpt2.step(X, Y)
    assert _fresh_compiles() == 0
    warm_stats = _running_stats(net2)
    assert cold_stats
    for i in cold_stats:
        np.testing.assert_array_equal(cold_stats[i], warm_stats[i])


# ---------------------------------------------------------------------------
# introspection / CLI
# ---------------------------------------------------------------------------


def test_cache_info_persist_counters(cache_dir):
    def f(a):
        return a * 4.0

    x = nd.array(np.ones((2,), "f4"))
    engine.invoke_compiled("cc_info", f, {}, x._data)
    info = engine.cache_info()["persist"]
    assert info == {"enabled": True, "dir": cache_dir, "hits": 0,
                    "misses": 1, "seconds_saved": 0.0}
    _restart()
    engine.invoke_compiled("cc_info", f, {}, x._data)
    info = engine.cache_info()["persist"]
    assert info["hits"] == 1 and info["misses"] == 0
    assert info["seconds_saved"] > 0
    engine.reset_counters()
    info = engine.cache_info()["persist"]
    assert info["hits"] == 0 and info["seconds_saved"] == 0.0


def test_mxcache_cli_ls_verify_prune(cache_dir, capsys):
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "tools"))
    import mxcache

    def f(a):
        return a / 2.0

    x = nd.array(np.ones((6,), "f4"))
    engine.invoke_compiled("cc_cli", f, {}, x._data)

    assert mxcache.main(["ls"]) == 0
    out = capsys.readouterr().out
    assert "cc_cli" in out and "1 entries" in out
    assert mxcache.main(["verify"]) == 0

    # corrupt it: verify must exit nonzero (the CI contract)
    (entry,) = os.listdir(cache_dir)
    with open(os.path.join(cache_dir, entry), "wb") as fh:
        fh.write(b"garbage")
    assert mxcache.main(["verify"]) == 1
    out = capsys.readouterr().out
    assert "CORRUPT" in out
    assert mxcache.main(["prune", "--all"]) == 0
    assert os.listdir(cache_dir) == []
