"""ZeRO-sharded weight update inside the one-dispatch SPMD step
(docs/zero.md, arXiv 2004.13336; ISSUE 10).

Tier-1 coverage:

* ``collectives.reduce_scatter`` psum parity (RS + all-gather == psum,
  exact) and ``quantized_reduce_scatter`` (int8 wire, fp32 local
  accumulate) accuracy + lowered-HLO wire check;
* fp32-parity of stage 1 and stage 2 training vs the unsharded stage-0
  path over >= 5 steps for SGD-momentum and Adam on the 8-device mesh
  (single step AND ``step_multi``), with the health plane on;
* optimizer state really lives 1/dp per device (census + gauge), and
  the stage-2 wire is reduce-scatter + all-gather, not a gradient
  all-reduce;
* steady state stays 1 fused dispatch with 0 retraces/misses;
* checkpoint portability matrix: ZeRO dp8 -> ZeRO dp4, -> ZeRO-off,
  -> stage 2, and a stage-0 checkpoint -> ZeRO trainer — all
  fp32-exact; ``save_states``/``load_states`` round-trip the portable
  full layout;
* warm start: 0 fresh compiles through the persistent tier, stage/
  slice mismatches fail open;
* MXL310 fires on the ineligible-fallback misconfiguration and stays
  quiet on a properly sharded trainer; ``CompiledStep`` records the
  one-shot ``zero_inapplicable`` event.
"""
import os
import tempfile

import numpy as np
import pytest

pytestmark = pytest.mark.needs_mesh(8)

import mxnet_tpu as mx
from mxnet_tpu import analysis, engine, nd, parallel, telemetry
from mxnet_tpu.base import MXNetError
from mxnet_tpu.gluon import nn
from mxnet_tpu.gluon.loss import SoftmaxCrossEntropyLoss
from mxnet_tpu.parallel import zero as zmod
from mxnet_tpu.parallel.trainer import _flatten


@pytest.fixture(autouse=True)
def _zero_env():
    """Every test leaves the env unset (stage 0) behind."""
    prev = os.environ.pop("MXTPU_ZERO_STAGE", None)
    telemetry.enable()
    telemetry.reset()
    yield
    if prev is None:
        os.environ.pop("MXTPU_ZERO_STAGE", None)
    else:
        os.environ["MXTPU_ZERO_STAGE"] = prev
    telemetry.reset()


def _mlp(seed=7):
    np.random.seed(seed)
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(16, activation="relu", in_units=8),
                nn.Dense(4, in_units=16))
    net.initialize(mx.init.Xavier())
    return net


_X = np.random.RandomState(0).randn(16, 8).astype("f4")
_Y = np.random.RandomState(1).randint(0, 4, 16).astype("f4")


def _make(stage, dp=8, seed=7, opt="adam",
          opt_args=None, **trainer_kw):
    os.environ["MXTPU_ZERO_STAGE"] = str(stage)
    np.random.seed(0)
    mx.random.seed(0)
    net = _mlp(seed)
    dpt = parallel.DataParallelTrainer(
        net, SoftmaxCrossEntropyLoss(), opt,
        dict(opt_args or {"learning_rate": 1e-2}),
        mesh=parallel.make_mesh({"dp": dp}), fuse_step=True,
        **trainer_kw)
    return net, dpt


def _run(dpt, steps=5):
    return [float(dpt.step(nd.array(_X), nd.array(_Y)).asnumpy())
            for _ in range(steps)]


def _weights(net):
    return [p.data().asnumpy() for p in net.collect_params().values()]


def _state_leaves(dpt):
    out = []
    for i in dpt._tr_idx:
        leaves = []
        _flatten(dpt._states[i], leaves)
        out.append((i, [np.asarray(x._data) for x in leaves]))
    return out


def _full_states(dpt):
    """State leaves gathered to the portable full layout."""
    out = []
    for i, leaves in _state_leaves(dpt):
        shape = tuple(dpt._params[i].data().shape)
        out.append([zmod.gather_host(h, shape)
                    if h.shape != shape else h for h in leaves])
    return out


# -- collectives -------------------------------------------------------------

def test_reduce_scatter_psum_parity():
    """RS member i == slice i of the psum, and all-gathering the RS
    results reassembles the psum exactly."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from jax import shard_map
    from mxnet_tpu.parallel import collectives as C

    mesh = parallel.make_mesh({"dp": 8})
    x = np.random.RandomState(2).randn(8, 8, 16).astype("f4")

    def member(v):
        v = v[0]                              # (8, 16) local
        rs = C.reduce_scatter(v, "dp")        # (16,) summed slice
        full = C.all_gather(rs, "dp", axis=0, tiled=True)
        return rs[None], full[None]

    rs, full = jax.jit(shard_map(
        member, mesh=mesh, in_specs=P("dp"),
        out_specs=(P("dp"), P("dp", None)), check_vma=False))(
            jnp.asarray(x))
    want = x.sum(axis=0)                      # (8, 16) psum
    np.testing.assert_array_equal(np.asarray(rs), want)
    for row in np.asarray(full):
        np.testing.assert_array_equal(row.reshape(8, 16), want)


def test_quantized_reduce_scatter_accuracy_and_wire():
    """quantize -> scatter -> fp32 accumulate: gathered slices track
    the exact psum within int8 chunk-quantization error, and the wire
    carries int8 all_to_all lanes (checked in the lowered HLO)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from jax import shard_map
    from mxnet_tpu.parallel import collectives as C

    mesh = parallel.make_mesh({"dp": 8})
    x = np.random.RandomState(3).randn(8, 100).astype("f4")  # padded

    def member(v):
        rs = C.quantized_reduce_scatter(v[0], "dp")   # (chunk,)
        return C.all_gather(rs, "dp", axis=0, tiled=True)[None]

    fn = jax.jit(shard_map(member, mesh=mesh, in_specs=P("dp"),
                           out_specs=P("dp", None), check_vma=False))
    got = np.asarray(fn(jnp.asarray(x)))[0][:100]
    want = x.sum(axis=0)
    # one rounding stage against per-chunk absmax/127 scales
    scale = np.abs(x).max() / 127.0
    np.testing.assert_allclose(got, want, atol=8 * scale * 1.01)

    txt = fn.lower(jnp.asarray(x)).as_text()
    assert "all-to-all" in txt.replace("_", "-") and "i8" in txt, \
        txt[:500]
    with pytest.raises(MXNetError, match="bits"):
        C.quantized_reduce_scatter(jnp.ones((4,)), "dp", bits=4)


def test_sharded_weight_update_grad_reduce_modes():
    """'local' (pre-reduced grads) and a callable leg agree with the
    default scatter leg."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from jax import shard_map
    from mxnet_tpu.parallel import collectives as C
    import jax.lax as lax

    mesh = parallel.make_mesh({"dp": 4})
    p0 = np.random.RandomState(4).randn(6, 5).astype("f4")
    gs = np.random.RandomState(5).randn(4, 6, 5).astype("f4")

    def run(mode):
        def member(p, g):
            g = g[0]
            if mode == "local":
                new_p, _ = C.sharded_weight_update(
                    p, lax.psum(g, "dp"), (),
                    lambda ps, gsl: (ps - 0.1 * gsl, ()), "dp",
                    grad_reduce="local")
            else:
                new_p, _ = C.sharded_weight_update(
                    p, g, (), lambda ps, gsl: (ps - 0.1 * gsl, ()),
                    "dp", grad_reduce=mode)
            return new_p
        return np.asarray(jax.jit(shard_map(
            member, mesh=mesh, in_specs=(P(), P("dp")),
            out_specs=P(), check_vma=False))(
                jnp.asarray(p0), jnp.asarray(gs)))

    base = run("scatter")
    np.testing.assert_array_equal(run("local"), base)
    with pytest.raises(MXNetError, match="grad_reduce"):
        run("bogus")


# -- training parity ---------------------------------------------------------

@pytest.mark.parametrize("opt_name,opt_args", [
    ("sgd", {"learning_rate": 0.05, "momentum": 0.9}),
    ("adam", {"learning_rate": 1e-2}),
])
@pytest.mark.parametrize("stage", [1, 2])
def test_zero_training_parity(stage, opt_name, opt_args):
    """>= 5 steps of ZeRO training match the unsharded path fp32-close
    for SGD-momentum and Adam (acceptance criterion)."""
    net0, d0 = _make(0, opt=opt_name, opt_args=opt_args)
    l0 = _run(d0)
    netz, dz = _make(stage, opt=opt_name, opt_args=opt_args)
    lz = _run(dz)
    assert dz._zero_stage == stage
    np.testing.assert_allclose(lz, l0, rtol=2e-5, atol=1e-6)
    for a, b in zip(_weights(net0), _weights(netz)):
        np.testing.assert_allclose(b, a, rtol=2e-5, atol=1e-5)
    # momentum/m/v agree too (gathered from the shards)
    for sa, sb in zip(_full_states(d0), _full_states(dz)):
        for a, b in zip(sa, sb):
            np.testing.assert_allclose(
                np.asarray(b, "f4"), np.asarray(a, "f4"),
                rtol=2e-5, atol=1e-6)


def test_zero_step_multi_parity_and_single_program():
    """K bulked ZeRO steps == K single steps numerically, as ONE
    program (no per-inner-step engine work)."""
    Xk = np.stack([_X] * 3)
    Yk = np.stack([_Y] * 3)
    net0, d0 = _make(0)
    l0 = np.asarray(d0.step_multi(nd.array(Xk),
                                  nd.array(Yk)).asnumpy())
    net1, d1 = _make(1)
    l1 = np.asarray(d1.step_multi(nd.array(Xk),
                                  nd.array(Yk)).asnumpy())
    np.testing.assert_allclose(l1, l0, rtol=2e-5, atol=1e-6)
    # singles continue bit-consistently after a bulk
    ls0 = _run(d0, steps=2)
    ls1 = _run(d1, steps=2)
    np.testing.assert_allclose(ls1, ls0, rtol=2e-5, atol=1e-6)
    # repeat= variant
    net2, d2 = _make(2)
    lr2 = np.asarray(d2.step_multi(nd.array(_X), nd.array(_Y),
                                   repeat=3).asnumpy())
    np.testing.assert_allclose(lr2, l0, rtol=2e-5, atol=1e-6)


def test_zero_state_bytes_drop_and_gauge():
    """Measured, not asserted: per-device optimizer-state bytes drop
    >= (dp-1)/dp at dp=8, visible in the census AND the gauge."""
    net0, d0 = _make(0)
    d0.step(nd.array(_X), nd.array(_Y))
    t0 = telemetry.memory.opt_state_trees()[f"spmd:{net0.name}"]
    net1, d1 = _make(1)
    d1.step(nd.array(_X), nd.array(_Y))
    t1 = telemetry.memory.opt_state_trees()[f"spmd:{net1.name}"]
    assert t0["per_device_bytes"] == t0["total_bytes"]
    assert t0["sharded_bytes_per_device"] == 0
    assert t1["replicated_bytes"] == 0
    assert t1["zero_stage"] == 1
    # padding may add a few bytes; the drop must still be >= 7/8 of
    # the replicated footprint
    assert t1["per_device_bytes"] <= t0["per_device_bytes"] / 8 + 64, \
        (t0, t1)
    snap = telemetry.snapshot()
    assert snap["gauges"]["mxtpu_optimizer_state_bytes"] == \
        t1["per_device_bytes"]
    # physical layout: (8, chunk) rows sharded on dp
    for i, leaves in _state_leaves(d1):
        size, padded, chunk = zmod.param_slice(
            d1._params[i].data().shape, 8)
        for h in leaves:
            assert h.shape == (8, chunk)


def test_zero2_wire_is_reduce_scatter_plus_all_gather():
    """The stage-2 program's gradient wire: reduce-scatter + weight
    all-gather; any residual all-reduce carries only scalars (loss +
    health stats), even with the health plane ON (compute_sharded)."""
    telemetry.memory.reset()
    net, d2 = _make(2)
    d2.step(nd.array(_X), nd.array(_Y))
    rec = telemetry.memory.programs()["spmd_full_step"]
    coll = rec["collectives"]
    assert "reduce-scatter" in coll and "all-gather" in coll, coll
    grad_bytes = sum(
        int(np.prod(d2._params[i].data().shape)) * 4
        for i in d2._tr_idx)
    ar = coll.get("all-reduce", {"payload_bytes": 0})
    assert ar["payload_bytes"] < grad_bytes / 2, coll
    # the weight gather moves the full param set once
    assert coll["all-gather"]["payload_bytes"] >= grad_bytes, coll


def test_zero_steady_state_zero_retrace():
    """After warm-up, each ZeRO step()/step_multi() is exactly one
    counted dispatch with no cache misses, no fresh compiles, and no
    retrace events — the 1-dispatch/0-retrace contract (acceptance
    criterion)."""
    net, d1 = _make(1)
    for _ in range(2):
        d1.step(nd.array(_X), nd.array(_Y))
    d1.step_multi(nd.array(_X), nd.array(_Y), repeat=2)
    telemetry.clear_events()
    info0 = engine.cache_info()
    for _ in range(3):
        d1.step(nd.array(_X), nd.array(_Y))
    d1.step_multi(nd.array(_X), nd.array(_Y), repeat=2)
    info1 = engine.cache_info()
    assert info1["dispatches"] == info0["dispatches"] + 4
    assert info1["misses"] == info0["misses"]
    assert info1["fresh_compiles"] == info0["fresh_compiles"]
    assert telemetry.events("retrace") == []


# -- checkpoint portability --------------------------------------------------

def test_zero_checkpoint_restore_matrix(tmp_path):
    """A ZeRO dp8 checkpoint restores fp32-EXACT onto ZeRO dp4,
    a ZeRO-off trainer, and a stage-2 trainer (acceptance
    criterion), then trains on."""
    from mxnet_tpu.elastic import CheckpointManager
    net_a, dpt_a = _make(1)
    m = CheckpointManager(str(tmp_path / "ck"), trainer=dpt_a,
                          async_save=False)
    for _ in range(3):
        dpt_a.step(nd.array(_X), nd.array(_Y))
    m.save()
    want_w = _weights(net_a)
    want_s = _full_states(dpt_a)
    for stage_b, dp_b in ((1, 4), (0, 8), (2, 8)):
        net_b, dpt_b = _make(stage_b, dp=dp_b, seed=99)
        mb = CheckpointManager(str(tmp_path / "ck"), trainer=dpt_b,
                               async_save=False)
        assert mb.restore() == 3
        for a, b in zip(want_w, _weights(net_b)):
            np.testing.assert_array_equal(a, b)
        for sa, sb in zip(want_s, _full_states(dpt_b)):
            for a, b in zip(sa, sb):
                np.testing.assert_array_equal(
                    np.asarray(a, "f4"), np.asarray(b, "f4"))
        assert dpt_b.optimizer.num_update == dpt_a.optimizer.num_update
        loss = dpt_b.step(nd.array(_X), nd.array(_Y))
        assert np.isfinite(loss.asnumpy()).all()


def test_nonzero_checkpoint_restores_sharded(tmp_path):
    """A pre-ZeRO (stage 0) checkpoint restores onto a ZeRO trainer:
    state re-shards exactly."""
    from mxnet_tpu.elastic import CheckpointManager
    net_a, dpt_a = _make(0)
    m = CheckpointManager(str(tmp_path / "ck"), trainer=dpt_a,
                          async_save=False)
    for _ in range(2):
        dpt_a.step(nd.array(_X), nd.array(_Y))
    m.save()
    want_s = _full_states(dpt_a)
    net_b, dpt_b = _make(2, seed=99)
    mb = CheckpointManager(str(tmp_path / "ck"), trainer=dpt_b,
                           async_save=False)
    mb.restore()
    for i, leaves in _state_leaves(dpt_b):       # physically sharded
        assert all(h.ndim == 2 and h.shape[0] == 8 for h in leaves)
    for sa, sb in zip(want_s, _full_states(dpt_b)):
        for a, b in zip(sa, sb):
            np.testing.assert_array_equal(
                np.asarray(a, "f4"), np.asarray(b, "f4"))


def test_save_load_states_portable_layout(tmp_path):
    """save_states always writes the FULL layout; load_states
    re-shards into the target trainer's layout."""
    net_a, dpt_a = _make(2)
    for _ in range(2):
        dpt_a.step(nd.array(_X), nd.array(_Y))
    f = str(tmp_path / "opt.states")
    dpt_a.save_states(f)
    want = _full_states(dpt_a)

    net_b, dpt_b = _make(0, seed=99)
    dpt_b.step(nd.array(_X), nd.array(_Y))
    dpt_b.load_states(f)
    for sa, sb in zip(want, _full_states(dpt_b)):
        for a, b in zip(sa, sb):
            np.testing.assert_array_equal(
                np.asarray(a, "f4"), np.asarray(b, "f4"))
    assert dpt_b.optimizer.num_update == dpt_a.optimizer.num_update

    net_c, dpt_c = _make(1, seed=98)
    dpt_c.step(nd.array(_X), nd.array(_Y))
    dpt_c.load_states(f)
    for sa, sc in zip(want, _full_states(dpt_c)):
        for a, c in zip(sa, sc):
            np.testing.assert_array_equal(
                np.asarray(a, "f4"), np.asarray(c, "f4"))

    net_d, dpt_d = _make(1, seed=97, opt="sgd",
                         opt_args={"learning_rate": 0.1,
                                   "momentum": 0.9})
    dpt_d.step(nd.array(_X), nd.array(_Y))
    with pytest.raises(MXNetError, match="optimizer mismatch"):
        dpt_d.load_states(f)


# -- warm start --------------------------------------------------------------

def test_zero_warm_start_and_mismatch_fail_open(tmp_path,
                                                monkeypatch):
    """ZeRO variants warm-start through the persistent tier with 0
    fresh compiles; a stage mismatch fails open (False + event)."""
    monkeypatch.setenv("MXTPU_COMPILE_CACHE_DIR",
                       str(tmp_path / "cache"))
    net_a, dpt_a = _make(1)
    dpt_a.step(nd.array(_X), nd.array(_Y))
    dpt_a.step_multi(nd.array(_X), nd.array(_Y), repeat=2)
    man = str(tmp_path / "manifest.json")
    dpt_a.save_signature(man)
    import json
    rec = json.load(open(man))
    assert rec["zero"]["stage"] == 1 and rec["zero"]["dp"] == 8
    assert all(len(row) == 4 for row in rec["zero"]["slices"])

    engine.clear_cache()
    engine.reset_counters()
    telemetry.clear_events()
    net_b, dpt_b = _make(1)
    ok = dpt_b.warm_start(man)
    # baseline AFTER warm_start: tiny init/probe ops (_zeros) may
    # compile freshly during setup when an earlier in-process test
    # already held them in the (non-persisted) memory tier; the claim
    # is about the STEP programs, asserted as persist hits below
    base = engine.cache_info()["fresh_compiles"]
    assert ok is True
    dpt_b.step(nd.array(_X), nd.array(_Y))
    dpt_b.step_multi(nd.array(_X), nd.array(_Y), repeat=2)
    assert engine.cache_info()["fresh_compiles"] == base
    hits = [e.get("op", "") for e in telemetry.events("persist_hit")]
    assert any(h.startswith("spmd_full_step") and not h.endswith("r")
               for h in hits), hits
    assert any(h.endswith("_k2r") for h in hits), hits

    net_c, dpt_c = _make(2)
    assert dpt_c.warm_start(man) is False
    net_d, dpt_d = _make(0)
    assert dpt_d.warm_start(man) is False
    reasons = [e.get("reason", "") for e in
               telemetry.events("warm_start") if not e.get("ok")]
    assert any("zero" in r for r in reasons), reasons


# -- misconfiguration / lint -------------------------------------------------

def test_ineligible_warns_and_mxl310_fires():
    """A TP-ruled trainer cannot shard its update: construction warns,
    runs stage 0, and analyze_memory() raises MXL310 while the env is
    set; the properly sharded twin stays quiet."""
    from jax.sharding import PartitionSpec as P
    os.environ["MXTPU_ZERO_STAGE"] = "1"
    np.random.seed(0)
    mx.random.seed(0)
    net = _mlp()
    with pytest.warns(UserWarning, match="cannot shard"):
        dpt = parallel.DataParallelTrainer(
            net, SoftmaxCrossEntropyLoss(), "adam",
            {"learning_rate": 1e-2},
            mesh=parallel.make_mesh({"dp": 4, "tp": 2}),
            fuse_step=True,
            param_sharding=lambda n, s:
                P("tp", None) if n.endswith("dense0_weight") else None)
    assert dpt._zero_stage == 0
    dpt.step(nd.array(_X), nd.array(_Y))
    findings = [f for f in analysis.analyze_memory()
                if f.rule == "MXL310"]
    assert findings and "stage 0" in findings[0].message
    assert findings[0].severity == "warning"

    # the sharded twin is clean
    telemetry.reset()
    net2, dpt2 = _make(1)
    dpt2.step(nd.array(_X), nd.array(_Y))
    assert not any(f.rule == "MXL310"
                   for f in analysis.analyze_memory())

    # env unset: rule inert even on a replicated layout
    telemetry.reset()
    net3, dpt3 = _make(0)
    dpt3.step(nd.array(_X), nd.array(_Y))
    assert not any(f.rule == "MXL310"
                   for f in analysis.analyze_memory())


def test_env_validation_and_registry():
    from mxnet_tpu import envs
    var = envs.registry()["MXTPU_ZERO_STAGE"]
    assert var.type is int and var.default == 0
    os.environ["MXTPU_ZERO_STAGE"] = "5"
    with pytest.raises(MXNetError, match="MXTPU_ZERO_STAGE"):
        _make(5)


def test_compiled_step_records_inapplicable_event():
    """The single-context gluon path says WHY the env did nothing —
    one retained event, and the compiled path still runs."""
    from mxnet_tpu import gluon
    os.environ["MXTPU_ZERO_STAGE"] = "1"
    np.random.seed(0)
    net = _mlp()
    net.hybridize()
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 0.1}, kvstore=None)
    cs = tr.compile_step(net, gluon.loss.L2Loss())
    y = np.random.RandomState(0).rand(16, 4).astype("f4")
    for _ in range(3):
        cs.step(nd.array(_X), nd.array(y), 16)
    assert cs.last_path == "compiled"
    evs = telemetry.events("zero_inapplicable")
    assert len(evs) == 1 and "dp mesh axis" in evs[0]["reason"]


# -- composition -------------------------------------------------------------

def test_int8_composes_with_zero_and_step_multi():
    """int8 compression rides the ZeRO gradient leg (quantize ->
    scatter -> fp32 accumulate): training converges, step_multi works
    (plain compressed training never supported it), and the grad wire
    carries no fp32 all-reduce."""
    telemetry.memory.reset()
    net, dpt = _make(2, opt="adam", opt_args={"learning_rate": 5e-3},
                     compression={"type": "int8"})
    assert dpt._zero_stage == 2
    losses = _run(dpt, steps=8)
    assert losses[-1] < losses[0], losses
    losses_k = np.asarray(dpt.step_multi(
        nd.array(_X), nd.array(_Y), repeat=3).asnumpy())
    assert np.isfinite(losses_k).all()
    rec = telemetry.memory.programs()["spmd_full_step"]
    coll = rec["collectives"]
    assert "all-to-all" in coll, coll           # the int8 scatter leg
    assert "reduce-scatter" not in coll, coll   # replaced by quantized


def test_int8_stage1_keeps_quantized_wire():
    """Stage 1's all-reduce gradient leg must keep the int8 exchange
    (quantized_psum) when compression is configured — composing
    zero+int8 never silently widens the wire back to fp32."""
    telemetry.memory.reset()
    net, dpt = _make(1, opt="adam", opt_args={"learning_rate": 5e-3},
                     compression={"type": "int8"})
    assert dpt._zero_stage == 1
    losses = _run(dpt, steps=5)
    assert losses[-1] < losses[0], losses
    coll = telemetry.memory.programs()["spmd_full_step"]["collectives"]
    assert "all-to-all" in coll, coll           # the quantized phases
    grad_bytes = sum(
        int(np.prod(dpt._params[i].data().shape)) * 4
        for i in dpt._tr_idx)
    ar = coll.get("all-reduce", {"payload_bytes": 0})
    assert ar["payload_bytes"] < grad_bytes / 2, coll


def test_stage0_hashes_unchanged_by_release():
    """A stage-0 trainer's persist/struct hashes must not change just
    because the ZeRO field exists — the stage is appended only when
    nonzero.  (The integrity sentry's signature DOES ride the tuple on
    a >1-dp mesh — its fingerprint rows widen the program's outputs,
    so pre-integrity executables legitimately cannot serve — but a
    zero stage of 0 still adds nothing on top.)"""
    import hashlib
    from mxnet_tpu import telemetry as _t
    net, dpt = _make(0)
    dpt.step(nd.array(_X), nd.array(_Y))
    # the pre-ZeRO parts tuple + the integrity component, reproduced
    # verbatim — NO zero component
    parts = (type(dpt.optimizer).__name__,
             tuple((tuple(p.data().shape), str(p.data().dtype))
                   for p in dpt._params),
             tuple(dpt._tr_idx),
             tuple((str(k), int(v))
                   for k, v in dpt.mesh.shape.items()),
             dpt.dp_axis,
             _t.health.trace_signature()) + (
                 (dpt._integrity_sig(),)
                 if dpt._integrity_sig() is not None else ())
    want = hashlib.sha256(repr(parts).encode()).hexdigest()[:16]
    assert dpt._persist_name().endswith(want)


def test_2bit_compression_stays_stage0():
    """2bit error-feedback residuals are incompatible: construction
    warns and runs the (unsharded) compressed path."""
    os.environ["MXTPU_ZERO_STAGE"] = "1"
    np.random.seed(0)
    mx.random.seed(0)
    net = _mlp()
    with pytest.warns(UserWarning, match="2bit"):
        dpt = parallel.DataParallelTrainer(
            net, SoftmaxCrossEntropyLoss(), "adam",
            {"learning_rate": 5e-3},
            mesh=parallel.make_mesh({"dp": 8}), fuse_step=True,
            compression={"type": "2bit", "threshold": 0.05})
    assert dpt._zero_stage == 0
    losses = _run(dpt, steps=3)
    assert np.isfinite(losses).all()


def test_health_sampling_composes_with_zero():
    """A sampled health vector from the stage-2 step (grad stats from
    the scattered slices) matches the stage-0 vector."""
    from mxnet_tpu.telemetry import health
    net0, d0 = _make(0)
    net2, d2 = _make(2)
    ev = health.every()
    for _ in range(ev):
        d0.step(nd.array(_X), nd.array(_Y))
        d2.step(nd.array(_X), nd.array(_Y))
    rep = health.report()["owners"]
    h0 = [v for k, v in rep.items() if net0.name in k][0]
    h2 = [v for k, v in rep.items() if net2.name in k][0]
    assert h0["samples"] >= 1 and h2["samples"] >= 1
    s0, s2 = h0["history"][-1], h2["history"][-1]
    np.testing.assert_allclose(s2["grad_norm"], s0["grad_norm"],
                               rtol=1e-4)
    np.testing.assert_allclose(s2["loss"], s0["loss"], rtol=1e-5)
    assert s2["nonfinite"] == 0.0
