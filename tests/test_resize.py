"""Live elastic resize tests (docs/elasticity.md, "Live resize").

The ISSUE 11 acceptance criteria under test: dp shrink (8->4) and grow
(4->8) complete IN-JOB without losing a committed step and with 0
fresh compiles on the first post-swap step (the pre-warm contract);
every ``resize_*`` fault-injection point recovers to a consistent mesh
(old or new, never poisoned without a recovery path); ZeRO stage-2
``(dp, chunk)`` slices reshard fp32-exact; and the serving plane's
slot grow/shrink keeps steady-state 0 retraces under admit/evict
churn, with resident requests keeping their progress bit-for-bit.
"""
import os
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import engine, nd, parallel, telemetry
from mxnet_tpu.base import MXNetError
from mxnet_tpu.elastic import (CheckpointManager, ResizeController,
                               ServingAutoscaler, faults)
from mxnet_tpu.elastic import resize as resize_mod
from mxnet_tpu.elastic.faults import FaultError
from mxnet_tpu.gluon import nn
from mxnet_tpu.gluon.loss import L2Loss
from mxnet_tpu.parallel.trainer import _flatten


@pytest.fixture(autouse=True)
def _clean():
    faults.clear()
    resize_mod._reset()
    yield
    faults.clear()
    resize_mod._reset()


def _batch(n=16):
    rng = np.random.RandomState(0)
    return (nd.array(rng.randn(n, 8).astype("f4")),
            nd.array(rng.randn(n, 4).astype("f4")))


def _mlp(seed=7):
    mx.random.seed(seed)
    np.random.seed(seed)
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(16, activation="relu", in_units=8),
                nn.Dense(4, in_units=16))
    net.initialize(mx.init.Xavier())
    return net


def _spmd(mesh, seed=7, opt="adam"):
    net = _mlp(seed=seed)
    dpt = parallel.DataParallelTrainer(
        net, L2Loss(), opt, {"learning_rate": 0.01}, mesh=mesh,
        fuse_step=True)
    return net, dpt


def _params_of(net):
    return [v.data().asnumpy()
            for v in net.collect_params().values()]


def _assert_params_equal(a, b):
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)


def _assert_close_ulp(a, b):
    """1-2 ulp slack: a different dp size regroups the global-batch
    mean's reduction (float reassociation), the same slack the
    fused-vs-eager conv/transformer parity tests carry.  The resize
    ITSELF is bit-exact (params compare with assert_array_equal);
    only post-resize arithmetic on the new mesh picks up ulps."""
    np.testing.assert_allclose(a, b, rtol=3e-7, atol=1e-7)


@pytest.fixture
def mesh8():
    from conftest import needs_devices
    needs_devices(8)
    return parallel.make_mesh({"dp": 8})


# ---------------------------------------------------------------------------
# tentpole: in-job shrink/grow, bit-exact continuation, 0 fresh compiles
# ---------------------------------------------------------------------------


def test_live_shrink_bit_exact_continuation(mesh8, tmp_path):
    """dp 8 -> 4 in-job: params fp32-EXACT across the transition (a
    layout move never touches element values), the loss trajectory
    continues vs an unresized 8-dev run to 1-2 ulp (the new mesh
    regroups the global-batch mean's reduction), the step counter
    never rewinds, and the first post-swap step pays 0 fresh compiles
    (finalized into the registry record)."""
    x, y = _batch()
    mx.random.seed(11)
    net_a, dpt_a = _spmd(mesh8)
    losses_a = [dpt_a.step(x, y).asnumpy() for _ in range(6)]

    mx.random.seed(11)
    net_b, dpt_b = _spmd(parallel.make_mesh({"dp": 8}))
    mgr = CheckpointManager(str(tmp_path / "ck"), trainer=dpt_b,
                            async_save=False)
    losses_b = [dpt_b.step(x, y).asnumpy() for _ in range(3)]
    pre = _params_of(net_b)
    rc = ResizeController(dpt_b, mgr)
    stats = rc.resize(parallel.make_mesh({"dp": 4}))
    assert stats["healed"] is False
    assert stats["committed_step"] == stats["drain_step"] == 3
    # the reshard is a layout move: element values untouched
    _assert_params_equal(pre, _params_of(net_b))
    m0, f0 = engine.compile_counts()
    losses_b += [dpt_b.step(x, y).asnumpy() for _ in range(3)]
    m1, f1 = engine.compile_counts()
    assert (m1 - m0, f1 - f0) == (0, 0)
    for la, lb in zip(losses_a[:3], losses_b[:3]):
        np.testing.assert_array_equal(la, lb)   # pre-resize: bitwise
    for la, lb in zip(losses_a[3:], losses_b[3:]):
        _assert_close_ulp(la, lb)
    for pa, pb in zip(_params_of(net_a), _params_of(net_b)):
        _assert_close_ulp(pa, pb)
    # the first post-swap step finalized the pre-warm contract numbers
    rec = resize_mod.resizes()[-1]
    assert rec["post_swap_fresh_compiles"] == 0
    assert rec["post_swap_misses"] == 0
    # and the step counter continued where the old mesh left off
    assert max(dpt_b.optimizer._index_update_count.values()) == 6
    from mxnet_tpu.analysis import analyze_elasticity
    assert [f for f in analyze_elasticity()
            if f.rule == "MXL503"] == []


def test_live_grow_bit_exact_with_step_multi(mesh8, tmp_path):
    """dp 4 -> 8 in-job, with a bulked step_multi(K) variant in the
    recorded set: both variants are pre-warmed for the target mesh,
    the post-swap single + bulked steps pay 0 fresh compiles, and the
    trajectory matches an unresized dp-4 run to reduction-order
    ulps."""
    x, y = _batch()
    mx.random.seed(13)
    net_a, dpt_a = _spmd(parallel.make_mesh({"dp": 4}))
    dpt_a.step(x, y)
    dpt_a.step_multi(x, y, repeat=2)
    la = [dpt_a.step_multi(x, y, repeat=2).asnumpy(),
          dpt_a.step(x, y).asnumpy()]

    mx.random.seed(13)
    net_b, dpt_b = _spmd(parallel.make_mesh({"dp": 4}))
    mgr = CheckpointManager(str(tmp_path / "ck"), trainer=dpt_b,
                            async_save=False)
    dpt_b.step(x, y)
    # a variant is pre-warmed iff it was DISPATCHED at least once —
    # run the bulked shape before resizing so the swap covers it
    dpt_b.step_multi(x, y, repeat=2)
    rc = ResizeController(dpt_b, mgr)
    rc.resize(parallel.make_mesh({"dp": 8}))
    m0, f0 = engine.compile_counts()
    lb = [dpt_b.step_multi(x, y, repeat=2).asnumpy(),
          dpt_b.step(x, y).asnumpy()]
    m1, f1 = engine.compile_counts()
    assert (m1 - m0, f1 - f0) == (0, 0)
    for a, b in zip(la, lb):
        _assert_close_ulp(a, b)
    for pa, pb in zip(_params_of(net_a), _params_of(net_b)):
        _assert_close_ulp(pa, pb)
    rec = resize_mod.resizes()[-1]
    assert rec["post_swap_fresh_compiles"] == 0


def test_resize_prewarms_every_dispatched_batch_shape(mesh8,
                                                      tmp_path):
    """A workload that dispatched MORE than one batch size records
    only the first shape in its variant row, but the per-signature
    exec caches hold them all — the pre-warm must cover the union, so
    EVERY post-swap shape is compile-free (the contract MXL503
    audits)."""
    x16, y16 = _batch(16)
    x32, y32 = _batch(32)
    net, dpt = _spmd(mesh8)
    mgr = CheckpointManager(str(tmp_path / "ck"), trainer=dpt,
                            async_save=False)
    dpt.step(x16, y16)
    dpt.step(x32, y32)                 # second shape: no new row
    rc = ResizeController(dpt, mgr)
    rc.resize(parallel.make_mesh({"dp": 4}))
    m0, f0 = engine.compile_counts()
    dpt.step(x32, y32)                 # the NON-recorded shape first
    dpt.step(x16, y16)
    m1, f1 = engine.compile_counts()
    assert (m1 - m0, f1 - f0) == (0, 0)
    assert resize_mod.resizes()[-1]["post_swap_fresh_compiles"] == 0


def test_post_swap_probe_ignores_foreign_compiles(mesh8, tmp_path):
    """The contract probe brackets the FIRST post-swap step itself —
    another owner compiling between swap and that step must not be
    attributed to the resize (no false MXL503)."""
    import jax
    x, y = _batch()
    net, dpt = _spmd(mesh8)
    mgr = CheckpointManager(str(tmp_path / "ck"), trainer=dpt,
                            async_save=False)
    dpt.step(x, y)
    rc = ResizeController(dpt, mgr)
    rc.resize(parallel.make_mesh({"dp": 4}))
    # a foreign fresh compile lands in the swap->first-step window
    from mxnet_tpu import engine as _eng
    _eng.invoke_compiled("resize_foreign_probe_op",
                         lambda a: a * 2, {},
                         nd.array(np.ones((3,), "f4"))._data)
    dpt.step(x, y)
    rec = resize_mod.resizes()[-1]
    assert rec["post_swap_fresh_compiles"] == 0
    from mxnet_tpu.analysis import analyze_elasticity
    assert [f for f in analyze_elasticity()
            if f.rule == "MXL503"] == []


def test_prepare_resize_leaves_trainer_untouched(mesh8):
    """The pre-warm runs while the old mesh still trains: a trainer
    that prepared (but never applied) a resize continues BIT-identical
    to one that never prepared."""
    x, y = _batch()
    mx.random.seed(17)
    net_a, dpt_a = _spmd(mesh8)
    dpt_a.step(x, y)
    la = [dpt_a.step(x, y).asnumpy() for _ in range(2)]

    mx.random.seed(17)
    net_b, dpt_b = _spmd(parallel.make_mesh({"dp": 8}))
    dpt_b.step(x, y)
    staged = dpt_b.prepare_resize(parallel.make_mesh({"dp": 4}))
    assert staged["n_dp"] == 4
    assert resize_mod.mesh_desc(dpt_b.mesh) == {"dp": 8}
    lb = [dpt_b.step(x, y).asnumpy() for _ in range(2)]
    for a, b in zip(la, lb):
        np.testing.assert_array_equal(a, b)
    _assert_params_equal(_params_of(net_a), _params_of(net_b))


def test_resize_eligibility_and_divisibility(mesh8, tmp_path):
    x, y = _batch(12)        # 12 divides 4, not 8
    net, dpt = _spmd(parallel.make_mesh({"dp": 4}))
    mgr = CheckpointManager(str(tmp_path / "ck"), trainer=dpt,
                            async_save=False)
    with pytest.raises(MXNetError, match="run at least one"):
        dpt.prepare_resize(parallel.make_mesh({"dp": 2}))
    dpt.step(x, y)
    with pytest.raises(MXNetError, match="does not divide"):
        dpt.prepare_resize(parallel.make_mesh({"dp": 8}))
    with pytest.raises(MXNetError, match="CheckpointManager"):
        ResizeController(dpt, None)
    # non-fused trainers cannot swap compiled entries
    net2, dpt2 = _spmd(parallel.make_mesh({"dp": 4}), seed=8)
    dpt2.step(x, y)
    dpt2._fuse_step = False
    with pytest.raises(MXNetError, match="fuse_step"):
        dpt2.prepare_resize(parallel.make_mesh({"dp": 2}))


# ---------------------------------------------------------------------------
# fault matrix: every resize_* point recovers to a consistent mesh
# ---------------------------------------------------------------------------


def test_fault_pre_drain_aborts_on_old_mesh(mesh8, tmp_path):
    """resize_prewarm / resize_drain faults fire BEFORE the drain
    checkpoint commits: the resize raises and the trainer is untouched
    on the OLD mesh, still training."""
    x, y = _batch()
    net, dpt = _spmd(mesh8)
    mgr = CheckpointManager(str(tmp_path / "ck"), trainer=dpt,
                            async_save=False)
    for _ in range(2):
        dpt.step(x, y)
    rc = ResizeController(dpt, mgr)
    for point in ("resize_prewarm", "resize_drain"):
        pre = _params_of(net)
        faults.configure(point)
        with pytest.raises(FaultError, match=point):
            rc.resize(parallel.make_mesh({"dp": 4}))
        faults.clear()
        assert resize_mod.mesh_desc(dpt.mesh) == {"dp": 8}
        _assert_params_equal(pre, _params_of(net))
        loss = dpt.step(x, y)
        assert np.isfinite(loss.asnumpy()).all()
        evs = telemetry.events("resize_failed")
        assert evs and evs[-1]["still_on"] == "old_mesh"
    assert resize_mod.resizes() == []       # nothing completed


def test_fault_post_drain_heals_onto_new_mesh(mesh8, tmp_path):
    """resize_reshard / resize_swap faults land AFTER the drain
    checkpoint committed: the controller restores it INTO the
    pre-warmed mesh-B bindings — cleanly on the NEW mesh, exactly at
    the drain boundary, with `recovery` telemetry."""
    x, y = _batch()
    net, dpt = _spmd(mesh8)
    mgr = CheckpointManager(str(tmp_path / "ck"), trainer=dpt,
                            async_save=False)
    sizes = [(4, "resize_reshard"), (8, "resize_swap"),
             (4, None)]                     # and one clean hop back
    for _ in range(2):
        dpt.step(x, y)
    rc = ResizeController(dpt, mgr)
    for target, point in sizes:
        drained = _params_of(net)
        if point is not None:
            faults.configure(point)
        stats = rc.resize(parallel.make_mesh({"dp": target}))
        faults.clear()
        assert resize_mod.mesh_desc(dpt.mesh) == {"dp": target}
        if point is not None:
            assert stats["healed"] is True
            evs = telemetry.events("recovery")
            assert evs and evs[-1]["where"] == "resize_heal"
        else:
            assert stats["healed"] is False
        # on mesh B at exactly the drain boundary, and trains on
        _assert_params_equal(drained, _params_of(net))
        loss = dpt.step(x, y)
        assert np.isfinite(loss.asnumpy()).all()
        assert dpt._donation_poisoned is None


def test_resize_points_registered():
    for p in ("resize_drain", "resize_prewarm", "resize_reshard",
              "resize_swap"):
        assert p in faults.POINTS
    # unknown points still parse with a warning (import never bricks)
    with pytest.warns(RuntimeWarning, match="unknown fault point"):
        faults.configure("resize_nonsense")
    faults.clear()


# ---------------------------------------------------------------------------
# ZeRO stage-2 slices reshard fp32-exact
# ---------------------------------------------------------------------------


def _gathered_states(dpt):
    from mxnet_tpu.parallel import zero as zmod
    out = []
    for i in dpt._tr_idx:
        leaves = []
        _flatten(dpt._states[i], leaves)
        pshape = tuple(dpt._params[i].data().shape)
        out.append([zmod.gather_host(np.asarray(l._data), pshape)
                    for l in leaves])
    return out


def test_zero_stage2_slices_reshard_exact(mesh8, tmp_path,
                                          monkeypatch):
    monkeypatch.setenv("MXTPU_ZERO_STAGE", "2")
    x, y = _batch()
    net, dpt = _spmd(parallel.make_mesh({"dp": 8}), seed=9)
    assert dpt._zero_stage == 2
    mgr = CheckpointManager(str(tmp_path / "ck"), trainer=dpt,
                            async_save=False)
    for _ in range(3):
        dpt.step(x, y)
    want = _gathered_states(dpt)
    pre = _params_of(net)
    rc = ResizeController(dpt, mgr)
    rc.resize(parallel.make_mesh({"dp": 4}))
    # slices landed in the target (4, chunk) P(dp) layout, fp32-exact
    from jax.sharding import PartitionSpec as P
    from mxnet_tpu.parallel.zero import param_slice
    for i in dpt._tr_idx:
        leaves = []
        _flatten(dpt._states[i], leaves)
        _s, _p, chunk = param_slice(dpt._params[i].data().shape, 4)
        for leaf in leaves:
            assert leaf._data.shape == (4, chunk)
            assert leaf._data.sharding.spec == P("dp")
    for wl, gl in zip(want, _gathered_states(dpt)):
        for w, g in zip(wl, gl):
            np.testing.assert_array_equal(w, g)
    _assert_params_equal(pre, _params_of(net))
    m0, f0 = engine.compile_counts()
    loss = dpt.step(x, y)
    m1, f1 = engine.compile_counts()
    assert (m1 - m0, f1 - f0) == (0, 0)
    assert np.isfinite(loss.asnumpy()).all()
    assert resize_mod.resizes()[-1]["post_swap_fresh_compiles"] == 0


# ---------------------------------------------------------------------------
# serving: slot grow/shrink under churn, steady-state 0 retraces
# ---------------------------------------------------------------------------

V = 61


@pytest.fixture(scope="module")
def lm():
    from mxnet_tpu.models import LlamaForCausalLM, llama_tiny
    mx.random.seed(0)
    np.random.seed(0)
    net = LlamaForCausalLM(llama_tiny(vocab_size=V))
    net.initialize(mx.init.Xavier())
    return net


def _prompt(seed, n):
    return np.random.RandomState(seed).randint(0, V, n).astype("f4")


def test_serving_slot_grow_shrink_churn(lm):
    from mxnet_tpu.serving import Server
    ref = Server(lm, buckets=[(2, 8)], max_new_tokens=6)
    ref_out = ref.generate([_prompt(0, 5), _prompt(1, 7)])

    srv = Server(lm, buckets=[(2, 8)], max_new_tokens=6)
    r1 = srv.submit(_prompt(0, 5))
    r2 = srv.submit(_prompt(1, 7))
    srv.step()
    srv.step()
    gen_before = (list(r1.generated), list(r2.generated))
    assert srv._owed                          # a decode is outstanding
    rec = srv.resize_slots(4)
    assert (rec["slots_from"], rec["slots_to"]) == (2, 4)
    assert rec["migrated"] == 2 and rec["requeued"] == 0
    assert rec["prewarmed_variants"] == 2     # prefill + decode
    # migrated residents kept their progress, and the drain read the
    # one token a request the device still owed...
    assert not srv._owed
    assert (r1.generated[:-1], r2.generated[:-1]) == gen_before
    # ...and finish bit-identical to the unresized run, under churn,
    # with ZERO compiles post-swap (the pre-warm contract)
    m0, f0 = engine.compile_counts()
    r3 = srv.submit(_prompt(2, 4))
    srv.step()
    srv.evict(r3, reason="churn")
    srv.submit(_prompt(3, 6))
    srv.run()
    m1, f1 = engine.compile_counts()
    assert (m1 - m0, f1 - f0) == (0, 0)
    np.testing.assert_array_equal(r1.tokens(), ref_out[0])
    np.testing.assert_array_equal(r2.tokens(), ref_out[1])
    st = srv.stats()["buckets"]["4x8"]
    assert st["steady_dispatches"] > 0
    assert st["steady_misses"] == 0
    assert st["steady_fresh_compiles"] == 0

    # shrink below the resident count: overflow evicts-with-requeue
    reqs = [srv.submit(_prompt(10 + i, 5)) for i in range(4)]
    srv.step()
    rec = srv.resize_slots(2)
    assert (rec["slots_from"], rec["slots_to"]) == (4, 2)
    assert rec["migrated"] == 2 and rec["requeued"] == 2
    srv.run()
    assert all(r.state == "done" for r in reqs)
    m0, f0 = engine.compile_counts()
    srv.generate([_prompt(30, 6)])
    m1, f1 = engine.compile_counts()
    assert (m1 - m0, f1 - f0) == (0, 0)
    assert len(resize_mod.resizes()) == 2


@pytest.mark.time_limit(300)
def test_serving_resize_carries_every_rung_of_the_prefill_ladder(lm):
    """A bucket's prefill ladder (docs/serving.md, "Buckets") is part of
    what a resize pre-warms: every rung is a recorded variant, so after
    the swap a prompt of any length compiles nothing, with a resident
    that was admitted through a short rung keeping its progress."""
    from mxnet_tpu.serving import Server
    ref = Server(lm, buckets=[(2, 512)], max_new_tokens=6)
    ref_out = ref.generate([_prompt(0, 100), _prompt(1, 300)])

    srv = Server(lm, buckets=[(2, 512)], max_new_tokens=6)
    r1 = srv.submit(_prompt(0, 100))            # rung 256; 512 is made
    srv.step()
    srv.step()
    rec = srv.resize_slots(3)
    assert rec["prewarmed_variants"] == 3       # two rungs + decode
    assert sorted(srv._variants) == [
        "_b3x512_decode", "_b3x512_prefill", "_b3x512_prefill256"]
    assert [v["k"] for _s, v in sorted(srv._variants.items())] \
        == [0, 0, 256]
    bucket, = srv.sched.buckets
    assert (bucket.slots, bucket.rungs) == (3, (256, 512))
    m0, f0 = engine.compile_counts()
    r2 = srv.submit(_prompt(1, 300))            # the rung never run
    srv.run()
    assert tuple(b - a for a, b in zip((m0, f0), engine.compile_counts())) \
        == (0, 0)
    np.testing.assert_array_equal(r1.tokens(), ref_out[0])
    np.testing.assert_array_equal(r2.tokens(), ref_out[1])
    st = srv.stats()["buckets"]["3x512"]
    assert st["steady_dispatches"] > 0
    assert st["steady_misses"] == st["steady_fresh_compiles"] == 0


def test_serving_resize_fault_matrix(lm):
    from mxnet_tpu.serving import Server
    srv = Server(lm, buckets=[(2, 8)], max_new_tokens=6)
    srv.generate([_prompt(20, 5)])            # warm programs
    # pre-migration fault: clean abort on the old slot count
    faults.configure("resize_prewarm")
    with pytest.raises(FaultError):
        srv.resize_slots(4)
    faults.clear()
    assert max(b.slots for b in srv.sched.buckets) == 2
    evs = telemetry.events("resize_failed")
    assert evs and evs[-1]["phase"] == "prewarm"
    assert evs[-1]["still_on"] == "old_config"
    srv.generate([_prompt(21, 5)])            # still serves
    # post-migration fault: heal onto the NEW slot count, residents
    # requeued and replayed exactly from their host-owned prompts
    live = srv.submit(_prompt(22, 5))
    srv.step()
    faults.configure("resize_reshard")
    rec = srv.resize_slots(4)
    faults.clear()
    assert rec["healed"] is True
    assert max(b.slots for b in srv.sched.buckets) == 4
    srv.run()
    assert live.state == "done"
    ref = Server(lm, buckets=[(2, 8)],
                 max_new_tokens=6).generate([_prompt(22, 5)])[0]
    np.testing.assert_array_equal(live.tokens(), ref)
    evs = telemetry.events("recovery")
    assert evs and evs[-1]["where"] == "resize_heal"
    # a shrink that faults AFTER its overflow evictions must count
    # BOTH populations in `requeued` (overflow already in the queue +
    # the residents the heal sweeps out of the bucket tables)
    reqs = [srv.submit(_prompt(25 + i, 5)) for i in range(4)]
    srv.step()                                # fill all 4 slots
    faults.configure("resize_swap")
    rec = srv.resize_slots(2)
    faults.clear()
    assert rec["healed"] is True
    assert rec["requeued"] == 4
    # heal evictions leave the SAME audit trail as every other
    # eviction: retained request_evicted events + the counter
    heal_evs = [e for e in telemetry.events("request_evicted")
                if e.get("reason") == "resize_heal"]
    assert len(heal_evs) >= 2            # the swept residents
    srv.run()
    assert all(r.state == "done" for r in reqs)


def test_autoscaler_hysteresis_and_cooldown(lm):
    from mxnet_tpu.serving import Server
    srv = Server(lm, buckets=[(1, 8)], max_new_tokens=4,
                 max_queue=32)
    srv.generate([_prompt(40, 4)])            # warm programs
    auto = ServingAutoscaler(srv, min_slots=1, max_slots=8,
                             up_queue=2, down_occupancy=0.3,
                             patience=2, cooldown_s=0.0)
    for i in range(6):
        srv.submit(_prompt(41 + i, 4))
    srv.step()
    assert auto.observe() is None             # patience 1 of 2
    rec = auto.observe()                      # fires: 1 -> 2
    assert rec is not None and rec["slots_to"] == 2
    assert "queue_depth" in rec["autoscale_reason"]
    srv.run()
    assert auto.observe() is None
    rec = auto.observe()                      # idle: 2 -> 1
    assert rec is not None and rec["slots_to"] == 1
    # cooldown: a breach inside the window never fires
    cold = ServingAutoscaler(srv, min_slots=1, max_slots=8,
                             up_queue=1, down_occupancy=0.3,
                             patience=1, cooldown_s=3600.0)
    cold._last_resize = __import__("time").monotonic()
    srv.submit(_prompt(50, 4))
    assert cold.observe() is None
    srv.run()
    # env-default construction reads the registry
    auto_env = ServingAutoscaler(srv)
    from mxnet_tpu import envs
    assert auto_env.patience == envs.get("MXTPU_RESIZE_PATIENCE")
    assert auto_env.max_slots == envs.get("MXTPU_RESIZE_MAX_SLOTS")


# ---------------------------------------------------------------------------
# MXL503 + telemetry + CLI + env registry
# ---------------------------------------------------------------------------


def test_mxl503_seeded_corpus():
    from mxnet_tpu.analysis import analyze_elasticity
    assert [f for f in analyze_elasticity()
            if f.rule == "MXL503"] == []      # fresh registry: quiet
    # seeded defect: a resize whose first post-swap step compiled
    resize_mod._note_completed({
        "kind": "train", "mesh_from": {"dp": 8}, "mesh_to": {"dp": 4},
        "drain_step": 5, "committed_step": 5, "healed": False,
        "downtime_seconds": 0.1, "post_swap_fresh_compiles": 2,
        "post_swap_misses": 2})
    # seeded defect: a drain that committed behind the trainer's step
    resize_mod._note_completed({
        "kind": "train", "mesh_from": {"dp": 4}, "mesh_to": {"dp": 8},
        "drain_step": 9, "committed_step": 7, "healed": False,
        "downtime_seconds": 0.1, "post_swap_fresh_compiles": 0,
        "post_swap_misses": 0})
    # clean twin + a pending record (probe not fired yet): quiet
    resize_mod._note_completed({
        "kind": "train", "mesh_from": {"dp": 8}, "mesh_to": {"dp": 4},
        "drain_step": 3, "committed_step": 3, "healed": False,
        "downtime_seconds": 0.1, "post_swap_fresh_compiles": 0,
        "post_swap_misses": 0})
    resize_mod._note_completed({
        "kind": "serving", "slots_from": 2, "slots_to": 4,
        "healed": False, "downtime_seconds": 0.1,
        "post_swap_fresh_compiles": None})
    found = [f for f in analyze_elasticity() if f.rule == "MXL503"]
    assert len(found) == 2
    assert "fresh compile" in found[0].message
    assert "resize:0" == found[0].location
    assert "lose" in found[1].message and "2 committed step" in \
        found[1].message
    # rides self_check (warning severity: informs, does not gate)
    from mxnet_tpu import analysis
    findings, ok = analysis.self_check()
    assert [f for f in findings if f.rule == "MXL503"]
    resize_mod._reset()
    assert [f for f in analyze_elasticity()
            if f.rule == "MXL503"] == []


def test_resize_events_survive_dispatch_flood():
    resize_mod._note_completed({
        "kind": "train", "mesh_from": {"dp": 8}, "mesh_to": {"dp": 4},
        "drain_step": 1, "committed_step": 1, "healed": False,
        "downtime_seconds": 0.05, "post_swap_fresh_compiles": 0})
    resize_mod._note_failed("train", "prewarm", "boom")
    for i in range(1200):                    # >> both ring capacities
        telemetry.record_event("dispatch", op=f"flood{i}")
    evs = telemetry.events("resize")
    assert evs and evs[-1]["resize_kind"] == "train"
    assert telemetry.events("resize_failed")
    snap = telemetry.snapshot()
    assert snap["counters"].get("mxtpu_resizes_total", 0) >= 1
    assert snap["histograms"][
        "mxtpu_resize_downtime_seconds"]["count"] >= 1
    telemetry.clear_events()


def test_resize_env_knobs_registered():
    from mxnet_tpu import envs
    reg = envs.registry()
    for name, typ in (("MXTPU_RESIZE_UP_QUEUE", int),
                      ("MXTPU_RESIZE_DOWN_OCCUPANCY", float),
                      ("MXTPU_RESIZE_PATIENCE", int),
                      ("MXTPU_RESIZE_COOLDOWN_S", float),
                      ("MXTPU_RESIZE_MIN_SLOTS", int),
                      ("MXTPU_RESIZE_MAX_SLOTS", int)):
        assert name in reg and reg[name].type is typ
    doc = open(os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "docs", "env_vars.md")).read()
    assert "MXTPU_RESIZE_UP_QUEUE" in doc


def test_mxresize_cli(tmp_path, capsys):
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "tools"))
    import mxresize
    resize_mod._note_completed({
        "kind": "train", "mesh_from": {"dp": 8}, "mesh_to": {"dp": 4},
        "drain_step": 2, "committed_step": 2, "healed": True,
        "heal_error": "FaultError('x')", "downtime_seconds": 0.07,
        "post_swap_fresh_compiles": 0, "post_swap_misses": 0})
    resize_mod._note_completed({
        "kind": "serving", "slots_from": 2, "slots_to": 4,
        "buckets": ["4x8"], "migrated": 2, "requeued": 0,
        "prewarmed_variants": 2, "healed": False,
        "downtime_seconds": 0.02, "autoscale_reason": "queue_depth"})
    out = mxresize.render(resize_mod.report())
    assert "mesh dp:8 -> dp:4" in out
    assert "HEALED" in out
    assert "OK (0 fresh compiles)" in out
    assert "slots 2 -> 4" in out and "autoscale: queue_depth" in out
    # render a flight-recorder dump artifact
    dump = telemetry.dump_flight_recorder(
        str(tmp_path / "dump.json"), reason="test")
    assert mxresize.main(["render", dump]) == 0
    assert "resize" in capsys.readouterr().out
    # status --json round-trips
    assert mxresize.main(["status", "--json"]) == 0
    import json
    rep = json.loads(capsys.readouterr().out)
    assert len(rep["resizes"]) == 2
    # malformed artifact exits 1
    bad = str(tmp_path / "bad.json")
    with open(bad, "w") as f:
        f.write("[1, 2]")
    assert mxresize.main(["render", bad]) == 1
    telemetry.clear_events()
