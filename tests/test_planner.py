"""Unified sharding planner (docs/parallelism.md, "The sharding
planner"; ISSUE 13).

Tier-1 coverage of the acceptance criteria:

* rule grammar + validation (bad regex / unknown axis / bad stage /
  malformed JSON all raise), first-match-wins ORDERING determinism;
* the shipped megatron rule set resolves the llama and BERT block
  families to the documented row/column layout;
* canonical serialization round-trips (``to_json``/``from_json``/
  ``save``/``load``) with a stable struct hash, and ``diff_records``
  names the exact diverging rule;
* ONE plan object drives the trainer: ``plan=`` vs legacy args is
  loss-BIT-identical at 1 fused dispatch/step with 0 retraces (single
  step AND ``step_multi``), the plan's ``zero_stage`` shards the
  optimizer state ``(dp, chunk)`` ``P(dp)``, and the plan's rules
  shard params like the equivalent callable;
* plan<->plan reshard matrix, fp32-EXACT: dp-only <-> dp x tp, ZeRO
  on/off, across dp sizes — both the live ``redistribute_plan`` round
  trip and the checkpoint portability path;
* warm-start manifests pin the plan: unchanged plan warm-restarts
  with 0 fresh compiles through the persistent tier; a diverging rule
  fail-opens naming that rule;
* pipeline/ring attention consume the plan's axes (``pp_axis``/
  ``sp_axis``) instead of ad-hoc names;
* serving: the plan's decode spec shards the KV pages on the plan
  mesh with token parity vs an unplanned server, and the serving
  manifest rejects a diverging plan naming the rule;
* MXL313 seeded-defect corpus: uncovered param, shadowed rule, big
  replicated tensor (rule-attributed) — caught; covered twin quiet;
  rides ``analyze_memory``/``self_check`` and stays quiet fresh;
* ``tools/mxplan.py`` show/diff/lint + malformed-plan exit 1.
"""
import json
import os
import subprocess
import sys
import tempfile

import numpy as np
import pytest

pytestmark = pytest.mark.needs_mesh(8)

import mxnet_tpu as mx
from mxnet_tpu import analysis, engine, nd, parallel, telemetry
from mxnet_tpu.base import MXNetError
from mxnet_tpu.elastic import reshard
from mxnet_tpu.gluon import nn
from mxnet_tpu.gluon.loss import SoftmaxCrossEntropyLoss
from mxnet_tpu.parallel import ShardingPlan, megatron_rules, planner
from mxnet_tpu.parallel.trainer import _flatten

_X = np.random.RandomState(0).randn(16, 8).astype("f4")
_Y = np.random.RandomState(1).randint(0, 4, 16).astype("f4")


@pytest.fixture(autouse=True)
def _clean():
    prev = os.environ.pop("MXTPU_SHARDING_PLAN", None)
    prev_z = os.environ.pop("MXTPU_ZERO_STAGE", None)
    telemetry.enable()
    telemetry.reset()
    planner._reset()
    yield
    planner._reset()
    telemetry.reset()
    for k, v in (("MXTPU_SHARDING_PLAN", prev),
                 ("MXTPU_ZERO_STAGE", prev_z)):
        if v is None:
            os.environ.pop(k, None)
        else:
            os.environ[k] = v


def _mlp(seed=7):
    np.random.seed(seed)
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(16, activation="relu", in_units=8),
                nn.Dense(4, in_units=16))
    net.initialize(mx.init.Xavier())
    return net


def _trainer(plan=None, seed=7, **kw):
    np.random.seed(0)
    mx.random.seed(0)
    net = _mlp(seed)
    t = parallel.DataParallelTrainer(
        net, SoftmaxCrossEntropyLoss(), "adam",
        {"learning_rate": 1e-2}, fuse_step=True, plan=plan, **kw)
    return net, t


def _weights(net):
    return [p.data().asnumpy() for p in net.collect_params().values()]


# MLP-shaped tensor-parallel rules (dense0 column, dense1 row) — the
# megatron move on the test net's (out, in) weights
def _mlp_rules():
    return [(r"dense0_weight$", ("tp", None)),
            (r"dense0_bias$", ("tp",)),
            (r"dense1_weight$", (None, "tp")),
            (r".", ())]


def _mlp_rule_fn():
    from jax.sharding import PartitionSpec as P

    def rule(name, shape):
        if name.endswith("dense0_weight"):
            return P("tp", None)
        if name.endswith("dense0_bias"):
            return P("tp")
        if name.endswith("dense1_weight"):
            return P(None, "tp")
        return None

    return rule


# -- grammar / validation ----------------------------------------------------

def test_rule_grammar_validation():
    with pytest.raises(MXNetError, match="does not compile"):
        ShardingPlan({"dp": 2}, [("([bad", ())])
    with pytest.raises(MXNetError, match="names mesh axis"):
        ShardingPlan({"dp": 2}, [(".*", ("tp", None))])
    with pytest.raises(MXNetError, match="zero_stage"):
        ShardingPlan({"dp": 2}, zero_stage=3)
    with pytest.raises(MXNetError, match="dp_axis"):
        ShardingPlan({"x": 2}, dp_axis="dp")
    with pytest.raises(MXNetError, match="decode"):
        ShardingPlan({"dp": 2}, decode=("nope",))
    with pytest.raises(MXNetError, match="at least one mesh axis"):
        ShardingPlan({})
    with pytest.raises(MXNetError, match="stage rule"):
        ShardingPlan({"dp": 2, "pp": 2}, stage_rules=[(".*", 5)])
    # a rule naming more dims than the param has is a resolution error
    p = ShardingPlan({"dp": 2, "tp": 2},
                     [(r"w$", ("tp", None, "dp"))])
    with pytest.raises(MXNetError, match="names 3 dims"):
        p.spec_for("my_w", (4, 4))


def test_rule_ordering_first_match_wins():
    """Determinism: the FIRST matching rule claims the param, so two
    orderings of overlapping rules resolve differently — and each
    resolution is stable across calls."""
    names = [("net_attn_q_weight", (8, 8))]
    a = ShardingPlan({"dp": 2, "tp": 2},
                     [(r"attn_q", ("tp", None)), (r"weight$", ())])
    b = ShardingPlan({"dp": 2, "tp": 2},
                     [(r"weight$", ()), (r"attn_q", ("tp", None))])
    ra = a.resolve(names)["net_attn_q_weight"]
    rb = b.resolve(names)["net_attn_q_weight"]
    assert ra["spec"] == ("tp",) and ra["rule"] == 0
    assert rb["spec"] == () and rb["rule"] == 0
    for _ in range(3):
        assert a.resolve(names)["net_attn_q_weight"] == ra
    # scalars are never partitioned, whatever the rules say
    assert a.spec_for("net_attn_q_weight", (1,)) == ((), planner.SCALAR)


def test_megatron_rules_llama_bert_layout():
    rules = megatron_rules()
    p = ShardingPlan({"dp": 2, "tp": 2}, rules)
    llama = {
        "m0_layer0_attn_q_weight": ("tp",),
        "m0_layer0_attn_k_weight": ("tp",),
        "m0_layer0_attn_v_weight": ("tp",),
        "m0_layer0_mlp_gate_weight": ("tp",),
        "m0_layer0_mlp_up_weight": ("tp",),
        "m0_layer0_attn_o_weight": (None, "tp"),
        "m0_layer0_mlp_down_weight": (None, "tp"),
        "m0_embed_weight": ("tp",),
        "m0_layer0_innorm_gamma": (),
    }
    bert = {
        "b0_enc_layer0_multiheadattention0_query_weight": ("tp",),
        "b0_enc_layer0_multiheadattention0_out_weight": (None, "tp"),
        "b0_enc_layer0_positionwiseffn0_ffn1_weight": ("tp",),
        "b0_enc_layer0_positionwiseffn0_ffn2_weight": (None, "tp"),
        "b0_enc_layer0_layernorm0_gamma": (),
        "b0_word_embed_weight": ("tp",),
    }
    for name, want in {**llama, **bert}.items():
        spec, idx = p.spec_for(name, (64, 64))
        assert spec == want, (name, spec, want)
        assert idx is not None     # full coverage via the catch-all
    # every param covered: the coverage audit is clean by construction
    cov = p.coverage([(n, (64, 64)) for n in {**llama, **bert}])
    assert cov == {"uncovered": [], "shadowed": [],
                   "replicated_big": [], "demoted": []}


def test_serialization_round_trip_and_diff():
    p = ShardingPlan({"dp": 4, "tp": 2}, megatron_rules(),
                     zero_stage=2, decode=("dp",),
                     stage_rules=[(r"embed", 0)])
    q = ShardingPlan.from_json(p.to_json())
    assert q == p and q.struct_hash() == p.struct_hash()
    with tempfile.TemporaryDirectory() as d:
        path = p.save(os.path.join(d, "plan.json"))
        r = ShardingPlan.load(path)
        assert r == p and r.struct_hash() == p.struct_hash()
    assert planner.diff_records(p.to_record(), q.to_record()) is None
    # a single diverging rule is NAMED (index + both sides)
    rules = megatron_rules()
    rules[1] = (rules[1][0], (None, None))   # row -> replicated
    alt = ShardingPlan({"dp": 4, "tp": 2}, rules, zero_stage=2,
                       decode=("dp",), stage_rules=[(r"embed", 0)])
    msg = planner.diff_records(p.to_record(), alt.to_record())
    assert msg is not None and "rule #1" in msg
    # field-level divergence named too
    alt2 = ShardingPlan.from_record(
        dict(p.to_record(), zero_stage=0))
    assert "zero_stage" in planner.diff_records(p.to_record(),
                                                alt2.to_record())
    # malformed JSON raises MXNetError (the CLI exit-1 contract)
    with pytest.raises(MXNetError, match="malformed"):
        ShardingPlan.from_json("{not json")
    with pytest.raises(MXNetError, match="format"):
        ShardingPlan.from_record({"format": 99})


# -- one plan drives the trainer --------------------------------------------

def test_plan_vs_legacy_args_bit_identical_one_dispatch():
    """``plan=`` vs mesh/dp_axis legacy args: bit-identical losses
    and weights, 1 fused dispatch per steady step, 0 retraces — on
    step() AND step_multi()."""
    net1, t1 = _trainer(mesh=parallel.make_mesh({"dp": 8}))
    net2, t2 = _trainer(plan=ShardingPlan({"dp": 8}))
    l1 = [float(t1.step(nd.array(_X), nd.array(_Y)).asnumpy())
          for _ in range(3)]
    l2 = [float(t2.step(nd.array(_X), nd.array(_Y)).asnumpy())
          for _ in range(3)]
    assert l1 == l2
    for a, b in zip(_weights(net1), _weights(net2)):
        assert np.array_equal(a, b)
    # steady-state contract, same assertion style as
    # test_zero_steady_state_zero_retrace: the fused-AOT step is ONE
    # counted dispatch with no misses/fresh compiles and no retrace
    # events
    telemetry.clear_events()
    info0 = engine.cache_info()
    t2.step(nd.array(_X), nd.array(_Y))
    info1 = engine.cache_info()
    assert info1["dispatches"] == info0["dispatches"] + 1
    assert info1["misses"] == info0["misses"]
    assert info1["fresh_compiles"] == info0["fresh_compiles"]
    assert telemetry.events("retrace") == []
    t1.step(nd.array(_X), nd.array(_Y))   # keep the twins in lockstep
    # bulked parity: same losses, still compile-free
    m1 = t1.step_multi(nd.array(_X), nd.array(_Y), repeat=2)
    m2 = t2.step_multi(nd.array(_X), nd.array(_Y), repeat=2)
    assert np.array_equal(m1.asnumpy(), m2.asnumpy())
    for a, b in zip(_weights(net1), _weights(net2)):
        assert np.array_equal(a, b)
    assert telemetry.events("retrace") == []


def test_plan_rules_match_callable_param_sharding():
    """The plan's regex rules place params exactly like the
    equivalent callable rule — and training stays bit-identical."""
    mesh = parallel.make_mesh({"dp": 4, "tp": 2})
    net1, t1 = _trainer(mesh=mesh, param_sharding=_mlp_rule_fn(),
                        dp_axis="dp")
    net2, t2 = _trainer(plan=ShardingPlan({"dp": 4, "tp": 2},
                                          _mlp_rules()))
    l1 = [float(t1.step(nd.array(_X), nd.array(_Y)).asnumpy())
          for _ in range(3)]
    l2 = [float(t2.step(nd.array(_X), nd.array(_Y)).asnumpy())
          for _ in range(3)]
    assert l1 == l2
    for (n, p1), p2 in zip(net1.collect_params().items(),
                           net2.collect_params().values()):
        assert np.array_equal(p1.data().asnumpy(),
                              p2.data().asnumpy())
        s1 = p1.data()._data.sharding
        s2 = p2.data()._data.sharding
        # P('tp') and P('tp', None) are the same placement — compare
        # equivalence, not spelling
        assert s1.is_equivalent_to(s2, p1.data().ndim), n
    w0 = net2.collect_params()[
        [k for k in net2.collect_params()
         if k.endswith("dense0_weight")][0]]
    assert "tp" in str(w0.data()._data.sharding.spec)


def test_plan_zero_stage_drives_sharded_states():
    """plan.zero_stage=2 (env UNSET) shards optimizer state (dp,
    chunk) P(dp) and keeps stage-0 loss parity — the plan, not the
    env, is the source of truth."""
    assert "MXTPU_ZERO_STAGE" not in os.environ
    net0, t0 = _trainer(mesh=parallel.make_mesh({"dp": 8}))
    netz, tz = _trainer(plan=ShardingPlan({"dp": 8}, zero_stage=2))
    assert tz._zero_stage == 2
    l0 = [float(t0.step(nd.array(_X), nd.array(_Y)).asnumpy())
          for _ in range(4)]
    lz = [float(tz.step(nd.array(_X), nd.array(_Y)).asnumpy())
          for _ in range(4)]
    assert np.allclose(l0, lz, rtol=0, atol=0)   # pointwise: exact
    leaves = []
    _flatten(tz._states[tz._tr_idx[0]], leaves)
    assert tuple(leaves[0].shape)[0] == 8        # (dp, chunk) rows
    assert "dp" in str(leaves[0]._data.sharding.spec)
    # plan stage conflicts with an ineligible config the usual way:
    # param_sharding rules + ZeRO -> warn + stage 0 (MXL310 path)
    with pytest.warns(UserWarning, match="cannot shard"):
        _net, t_bad = _trainer(
            plan=ShardingPlan({"dp": 4, "tp": 2}, _mlp_rules(),
                              zero_stage=1))
    assert t_bad._zero_stage == 0


def test_plan_mesh_conflicts_rejected():
    plan = ShardingPlan({"dp": 8})
    with pytest.raises(MXNetError, match="not both"):
        _trainer(plan=plan, param_sharding=_mlp_rule_fn())
    with pytest.raises(MXNetError, match="do not match the"):
        _trainer(plan=plan, mesh=parallel.make_mesh({"dp": 4}))
    with pytest.raises(MXNetError, match="dp_axis"):
        _trainer(plan=plan, dp_axis="batch")
    with pytest.raises(MXNetError, match="ShardingPlan"):
        _trainer(plan={"dp": 8})


def test_plan_from_env_file():
    """MXTPU_SHARDING_PLAN points construction at a plan file; a
    malformed file raises loudly."""
    with tempfile.TemporaryDirectory() as d:
        path = ShardingPlan({"dp": 8}, zero_stage=1).save(
            os.path.join(d, "plan.json"))
        os.environ["MXTPU_SHARDING_PLAN"] = path
        _net, t = _trainer()
        assert t.plan is not None and t.plan.axes == {"dp": 8}
        assert t._zero_stage == 1
        # the env plan is AMBIENT: explicit legacy layout args win —
        # a pre-planner call site must never start raising because
        # the env var appeared (review finding, regression)
        _net_l, t_l = _trainer(mesh=parallel.make_mesh({"dp": 8}),
                               param_sharding=_mlp_rule_fn(),
                               dp_axis="dp")
        assert t_l.plan is None
        with pytest.warns(UserWarning, match="ignoring the env plan"):
            _net_m, t_m = _trainer(mesh=parallel.make_mesh({"dp": 4}))
        assert t_m.plan is None
        bad = os.path.join(d, "bad.json")
        with open(bad, "w") as f:
            f.write("{oops")
        os.environ["MXTPU_SHARDING_PLAN"] = bad
        with pytest.raises(MXNetError, match="malformed"):
            _trainer()


# -- plan <-> plan reshard matrix -------------------------------------------

def test_redistribute_plan_round_trip_exact():
    """Live plan->plan->plan round trip over the matrix corner
    (dp-only <-> dp x tp) is fp32-EXACT, and the flat-layout
    arithmetic has ONE definition (zero.param_slice ==
    planner.flat_rows)."""
    from mxnet_tpu.parallel import zero as zmod
    net = _mlp()
    # materialize params on the default device
    _ = [p.data() for p in net.collect_params().values()]
    named = [(p.name, p.data()._data)
             for p in net.collect_params().values()]
    before = [np.asarray(a) for _n, a in named]
    plan_a = ShardingPlan({"dp": 8})
    plan_b = ShardingPlan({"dp": 4, "tp": 2}, _mlp_rules())
    on_a = reshard.redistribute_plan(named, plan_a)
    names = [n for n, _a in named]
    on_b = reshard.redistribute_plan(list(zip(names, on_a)), plan_b)
    back = reshard.redistribute_plan(list(zip(names, on_b)), plan_a)
    for b0, a in zip(before, back):
        assert np.array_equal(b0, np.asarray(a))
    # the move report names per-param collectives + bytes
    shapes = [(n, tuple(int(d) for d in b.shape))
              for n, b in zip(names, before)]
    moves = reshard.plan_moves(shapes, plan_a, plan_b)
    w0 = [n for n in names if n.endswith("dense0_weight")][0]
    assert any("slice" in m for m in moves[w0]["moves"])
    assert zmod.param_slice((16, 8), 8) == planner.flat_rows((16, 8),
                                                             8)


def test_plan_moves_lists_only_changed_params_with_global_bytes():
    """The move report is a count from shapes and the two plans: on ONE
    mesh it lists exactly the params whose spec changes, each with its
    GLOBAL tensor bytes; identical plans move nothing; across a change
    of mesh axes every param is listed, a replicated one as a
    re-replication."""
    shapes = [("n_dense0_weight", (16, 8)), ("n_dense0_bias", (16,)),
              ("n_dense1_weight", (4, 16)), ("n_dense1_bias", (4,))]
    nbytes = {n: 4 * int(np.prod(s)) for n, s in shapes}
    flat = ShardingPlan({"dp": 4, "tp": 2})
    ruled = ShardingPlan({"dp": 4, "tp": 2}, _mlp_rules())
    same_mesh = reshard.plan_moves(shapes, flat, ruled)
    assert sorted(same_mesh) == ["n_dense0_bias", "n_dense0_weight",
                                 "n_dense1_weight"]
    assert {n: r["nbytes"] for n, r in same_mesh.items()} == \
        {n: nbytes[n] for n in same_mesh}
    assert same_mesh["n_dense1_weight"]["moves"] == ["slice(dim=1, tp:2)"]
    assert reshard.plan_moves(shapes, ruled, ruled) == {}
    other_mesh = reshard.plan_moves(shapes, ShardingPlan({"dp": 8}), ruled)
    assert sorted(other_mesh) == sorted(nbytes)
    assert other_mesh["n_dense1_bias"]["moves"] == ["replicate(dp:4xtp:2)"]
    assert sum(r["nbytes"] for r in other_mesh.values()) == \
        sum(nbytes.values())


def test_checkpoint_matrix_across_plans_fp32_exact():
    """Checkpoint portability THROUGH plans: save under (dp8, ZeRO-2)
    plan, restore into a (dp4 x tp2, ZeRO-off) plan trainer and back —
    params fp32-exact both ways (the reshard path routed through the
    plan's resolution)."""
    from mxnet_tpu.elastic import CheckpointManager
    net_a, t_a = _trainer(plan=ShardingPlan({"dp": 8}, zero_stage=2))
    for _ in range(3):
        t_a.step(nd.array(_X), nd.array(_Y))
    w_a = _weights(net_a)
    with tempfile.TemporaryDirectory() as d:
        mgr = CheckpointManager(d, trainer=t_a, async_save=False)
        step = mgr.save(block=True)
        # manifest pins the plan record
        mpath = os.path.join(d, f"step-{step:08d}", "manifest.json")
        with open(mpath) as f:
            m = json.load(f)
        assert m["plan"]["zero_stage"] == 2
        assert m["plan"]["axes"] == [["dp", 8]]
        # restore into a DIFFERENT plan: dp4 x tp2, rules, no ZeRO
        net_b, t_b = _trainer(
            plan=ShardingPlan({"dp": 4, "tp": 2}, _mlp_rules()))
        t_b.step(nd.array(_X), nd.array(_Y))   # divergent state
        mgr.restore(into=t_b)
        for a, b in zip(w_a, _weights(net_b)):
            assert np.array_equal(a, b)
        # and back across dp sizes onto a fresh ZeRO plan trainer
        net_c, t_c = _trainer(plan=ShardingPlan({"dp": 4},
                                                zero_stage=1))
        mgr2 = CheckpointManager(tempfile.mkdtemp(), trainer=t_b,
                                 async_save=False)
        mgr2.save(block=True)
        mgr2.restore(into=t_c)
        for a, c in zip(w_a, _weights(net_c)):
            assert np.array_equal(a, c)


def test_live_resize_to_target_plan():
    """ResizeController.resize(plan): dp8 -> dp4 x tp2 IN-JOB — the
    swap adopts the target plan, params stay fp32-exact across the
    transition, and the step counter continues."""
    from mxnet_tpu.elastic import CheckpointManager, ResizeController
    net, t = _trainer(plan=ShardingPlan({"dp": 8}))
    for _ in range(3):
        t.step(nd.array(_X), nd.array(_Y))
    w_before = _weights(net)
    step_before = max(t.optimizer._index_update_count.values())
    with tempfile.TemporaryDirectory() as d:
        mgr = CheckpointManager(d, trainer=t, async_save=False)
        rc = ResizeController(t, mgr)
        target = ShardingPlan({"dp": 4, "tp": 2}, _mlp_rules())
        # a ZeRO trainer must reject a TP-ruled target plan (the same
        # exclusion construction enforces; the prewarmed zero body
        # would otherwise bake layouts the reshard contradicts —
        # found driving the surface)
        _netz, tz = _trainer(plan=ShardingPlan({"dp": 8},
                                               zero_stage=2))
        tz.step(nd.array(_X), nd.array(_Y))
        with pytest.raises(MXNetError, match="ZeRO"):
            tz.prepare_resize(ShardingPlan({"dp": 4, "tp": 2},
                                           _mlp_rules(),
                                           zero_stage=2))
        rec = rc.resize(target)
        assert rec["mesh_to"] == {"dp": 4, "tp": 2}
        assert rec["plan_to"] == target.struct_hash()
        assert t.plan == target
        for a, b in zip(w_before, _weights(net)):
            assert np.array_equal(a, b)
        w0 = [p for p in net.collect_params().values()
              if p.name.endswith("dense0_weight")][0]
        assert "tp" in str(w0.data()._data.sharding.spec)
        t.step(nd.array(_X), nd.array(_Y))
        assert max(t.optimizer._index_update_count.values()) == \
            step_before + 1
        # rule-LOSING direction (review finding): TP-ruled plan ->
        # rule-free pure-DP plan must resolve "explicitly replicate",
        # not fall back to the old TP rule (whose axis the new mesh
        # lacks) — drained path, no crash-heal
        w_mid = _weights(net)
        rec2 = rc.resize(ShardingPlan({"dp": 8}))
        assert not rec2["healed"]
        assert t.plan == ShardingPlan({"dp": 8})
        for a, b in zip(w_mid, _weights(net)):
            assert np.array_equal(a, b)
        for p in net.collect_params().values():
            assert "tp" not in str(p.data()._data.sharding.spec)
        t.step(nd.array(_X), nd.array(_Y))


# -- warm-start manifest pin -------------------------------------------------

def test_warm_start_unchanged_plan_zero_fresh_compiles():
    """Same plan in a 'fresh process' (fresh trainer + persist tier):
    warm_start adopts, and the first step + step_multi pay 0 fresh
    compiles; a plan-vs-no-plan manifest is rejected naming the
    mismatch.

    NOTE: exactly ONE engine.clear_cache() here (the restart
    simulation), same recipe as test_zero's warm-start test.
    Bracketing this test with extra clear_cache() calls makes jaxlib
    segfault/abort nondeterministically later in the process (CPU
    backend, deserialized sharded executables + a cleared tier) — do
    not "clean" that back in."""
    with tempfile.TemporaryDirectory() as d:
        os.environ["MXTPU_COMPILE_CACHE_DIR"] = os.path.join(d, "cc")
        try:
            plan = ShardingPlan({"dp": 8}, zero_stage=1)
            net1, t1 = _trainer(plan=plan)
            t1.step(nd.array(_X), nd.array(_Y))
            t1.step_multi(nd.array(_X), nd.array(_Y), repeat=2)
            sig = t1.save_signature(os.path.join(d, "sig.json"))
            with open(sig) as f:
                m = json.load(f)
            assert m["plan"]["zero_stage"] == 1
            engine.clear_cache()        # memory tier gone, disk stays
            net2, t2 = _trainer(plan=ShardingPlan({"dp": 8},
                                                  zero_stage=1))
            assert t2.warm_start(sig)
            c0 = engine.cache_info()["fresh_compiles"]
            t2.step(nd.array(_X), nd.array(_Y))
            t2.step_multi(nd.array(_X), nd.array(_Y), repeat=2)
            assert engine.cache_info()["fresh_compiles"] == c0
            # a legacy-args trainer must NOT adopt a plan manifest
            net3, t3 = _trainer(mesh=parallel.make_mesh({"dp": 8}))
            os.environ["MXTPU_ZERO_STAGE"] = "1"
            try:
                net3b, t3b = _trainer(
                    mesh=parallel.make_mesh({"dp": 8}))
            finally:
                os.environ.pop("MXTPU_ZERO_STAGE", None)
            assert not t3b.warm_start(sig)
            ev = [e for e in telemetry.events("warm_start")
                  if not e.get("ok")]
            assert any("sharding-plan mismatch" in str(e.get("reason"))
                       for e in ev)
        finally:
            os.environ.pop("MXTPU_COMPILE_CACHE_DIR", None)


def test_warm_start_diverging_rule_rejected_by_name():
    """A manifest whose plan differs in ONE rule fail-opens, and the
    warm_start event names that rule."""
    with tempfile.TemporaryDirectory() as d:
        net1, t1 = _trainer(
            plan=ShardingPlan({"dp": 4, "tp": 2}, _mlp_rules()))
        t1.step(nd.array(_X), nd.array(_Y))
        sig = t1.save_signature(os.path.join(d, "sig.json"))
        rules = _mlp_rules()
        rules[2] = (rules[2][0], ("tp", None))    # row -> column
        net2, t2 = _trainer(
            plan=ShardingPlan({"dp": 4, "tp": 2}, rules))
        assert not t2.warm_start(sig)
        ev = [e for e in telemetry.events("warm_start")
              if not e.get("ok")]
        assert any("rule #2" in str(e.get("reason")) for e in ev), ev


# -- plan axes drive pipeline + ring attention ------------------------------

def test_pipeline_and_ring_consume_plan_axes():
    import jax
    import jax.numpy as jnp
    plan = ShardingPlan({"dp": 1, "pp": 4, "sp": 2},
                        pp_axis="pp", sp_axis="sp")
    mesh = plan.build_mesh()

    def stage_fn(params, x):
        return jnp.tanh(x @ params["w"])

    rng = np.random.RandomState(0)
    params = {"w": jnp.asarray(rng.randn(4, 8, 8).astype("f4"))}
    x = jnp.asarray(rng.randn(8, 8).astype("f4"))
    y_plan = parallel.pipeline_apply(stage_fn, params, x, 4,
                                     plan=plan)
    y_mesh = parallel.pipeline_apply(stage_fn, params, x, 4,
                                     mesh=mesh, axis="pp")
    assert np.array_equal(np.asarray(y_plan), np.asarray(y_mesh))
    q = jnp.asarray(rng.randn(1, 8, 4, 8).astype("f4"))
    k = jnp.asarray(rng.randn(1, 8, 4, 8).astype("f4"))
    v = jnp.asarray(rng.randn(1, 8, 4, 8).astype("f4"))
    o_plan = parallel.ring_attention(q, k, v, plan=plan)
    o_mesh = parallel.ring_attention(q, k, v, mesh=mesh, axis="sp")
    assert np.array_equal(np.asarray(o_plan), np.asarray(o_mesh))
    # a custom sp axis NAME rides the plan, no ad-hoc strings
    plan2 = ShardingPlan({"dp": 1, "seq": 2}, sp_axis="seq")
    o2 = parallel.ring_attention(q, k, v, plan=plan2)
    assert np.allclose(np.asarray(o_plan), np.asarray(o2), atol=1e-6)


# -- serving decode sharding -------------------------------------------------

V = 61


def _tiny_lm():
    from mxnet_tpu.models import LlamaForCausalLM, llama_tiny
    mx.random.seed(0)
    np.random.seed(0)
    lm = LlamaForCausalLM(llama_tiny(vocab_size=V))
    lm.initialize(mx.init.Xavier())
    return lm


def _serve(server, seeds=(1, 2, 3)):
    def prompt(s):
        return np.random.RandomState(s).randint(0, V, 5).astype("f4")
    reqs = [server.submit(prompt(s), max_new_tokens=6) for s in seeds]
    for _ in range(40):
        if all(r.state == "done" for r in reqs):
            break
        server.step()
    return [list(r.tokens()) for r in reqs]


def test_serving_decode_sharding_from_plan():
    """plan.decode shards the KV pages over the plan mesh; tokens are
    IDENTICAL to an unplanned server, and the serving manifest pins
    the plan (diverging rule named on reject)."""
    from mxnet_tpu.serving import Server
    t1 = _serve(Server(_tiny_lm(), buckets=[(8, 8)],
                       max_new_tokens=6))
    plan = ShardingPlan({"dp": 8}, decode=("dp",))
    srv = Server(_tiny_lm(), buckets=[(8, 8)], max_new_tokens=6,
                 plan=plan)
    t2 = _serve(srv)
    assert t1 == t2
    k0 = list(srv._pools.values())[0].buffers()[0]._data
    assert "dp" in str(k0.sharding.spec)
    assert len(k0.sharding.device_set) == 8
    with tempfile.TemporaryDirectory() as d:
        sig = srv.save_signature(os.path.join(d, "serve.json"))
        with open(sig) as f:
            m = json.load(f)
        assert m["plan"]["decode"] == ["dp"]
        # a diverging plan (decode spec) rejects naming the field
        srv2 = Server(_tiny_lm(), buckets=[(8, 8)], max_new_tokens=6,
                      plan=ShardingPlan({"dp": 8}))
        assert not srv2.warm_start(sig)
        ev = [e for e in telemetry.events("warm_start")
              if not e.get("ok")]
        assert any("decode" in str(e.get("reason")) for e in ev), ev
    # the serving leg registers its plan for the MXL313 audit
    assert any(k.startswith("serving:") for k in planner.plans()), \
        list(planner.plans())
    # a slot resize keeps the planned page layout (migration adopt
    # bypasses the pool's build path — review finding, regression)
    srv.resize_slots(16, reason="test")
    k1 = list(srv._pools.values())[0].buffers()[0]._data
    assert "dp" in str(k1.sharding.spec)
    assert len(k1.sharding.device_set) == 8
    # slot counts must divide the decode fan-out
    with pytest.raises(MXNetError, match="divisible"):
        Server(_tiny_lm(), buckets=[(3, 8)], max_new_tokens=6,
               plan=plan)
    with pytest.raises(MXNetError, match="multiple"):
        srv.resize_slots(12)


# -- MXL313 coverage audit ---------------------------------------------------

def _big_names():
    # 32 M f32 elements = 128 MiB >= the 64 MiB threshold
    return [("net_embed_weight", (32768, 1024)),
            ("net_layer0_attn_q_weight", (64, 64)),
            ("net_norm_gamma", (64,))]


def test_mxl313_seeded_defect_corpus():
    """Three seeded defects caught with rule attribution; the covered
    twin is quiet; findings ride analyze_memory()."""
    # (a) uncovered param: no catch-all, embed matches nothing
    p_unc = ShardingPlan({"dp": 8},
                         [(r"attn_q_weight$", ()),
                          (r"norm", ())])
    f = analysis.analyze_parallel(plan=p_unc,
                                  named_shapes=_big_names())
    assert any("matches NO plan rule" in x.message and
               "net_embed_weight" in x.message for x in f)
    # (b) shadowed rule: broad rule first, specific rule unreachable
    p_shad = ShardingPlan({"dp": 8, "tp": 1},
                          [(r"weight$", ()),
                           (r"attn_q_weight$", ()),
                           (r".", ())])
    f = analysis.analyze_parallel(plan=p_shad,
                                  named_shapes=_big_names())
    assert any("rule #1" in x.message and "unreachable" in x.message
               for x in f)
    # (c) big tensor replicated BY an attributed rule on a >1 mesh
    p_big = ShardingPlan({"dp": 8}, [(r".", ())])
    f = analysis.analyze_parallel(plan=p_big,
                                  named_shapes=_big_names())
    hits = [x for x in f if "fully replicated" in x.message]
    assert any("net_embed_weight" in x.message and "rule #0" in
               x.message for x in hits)
    assert all(x.rule == "MXL313" for x in f)
    # covered twin: embed sharded, catch-all present -> quiet
    p_ok = ShardingPlan({"dp": 4, "tp": 2},
                        [(r"embed_weight$", ("tp", None)),
                         (r".", ())])
    assert analysis.analyze_parallel(plan=p_ok,
                                     named_shapes=_big_names()) == []
    # a SCALAR param matching a rule's regex must not mark that rule
    # shadowed (scalars resolve before any regex runs — review
    # finding, regression)
    p_scal = ShardingPlan({"dp": 4, "tp": 2},
                          [(r"scale$", ("tp",)), (r".", ())])
    f = analysis.analyze_parallel(
        plan=p_scal, named_shapes=[("net_attn_scale", (1,)),
                                   ("net_w", (8, 8))])
    assert [x for x in f if "unreachable" in x.message] == []
    # (d) a non-divisible dim DEMOTES to replication (placement would
    # crash otherwise) and the audit names the rule — found driving an
    # odd-vocab embed under the tp-sharded megatron rule
    p_dem = ShardingPlan({"dp": 4, "tp": 2},
                         [(r"embed_weight$", ("tp", None)), (r".", ())])
    spec, idx = p_dem.spec_for("net_embed_weight", (61, 64))
    assert spec == () and idx == 0       # demoted, rule kept
    f = analysis.analyze_parallel(
        plan=p_dem, named_shapes=[("net_embed_weight", (61, 64))])
    assert any("cannot honor" in x.message and "rule #0" in x.message
               for x in f)
    # and the demoted layout actually TRAINS (replicated embed):
    net_d, t_d = _trainer(
        plan=ShardingPlan({"dp": 4, "tp": 2},
                          [(r"dense0_weight$", ("tp", None)),
                           (r"dense0_bias$", ("tp",)), (r".", ())]))
    # dense0 out dim 16 divides tp=2 — sanity that the clean path still
    # shards while a 61-wide rule would have demoted
    t_d.step(nd.array(_X), nd.array(_Y))


def test_mxl313_rides_live_registry_and_memory_pass():
    """A live plan-driven trainer registers its resolved tree; the
    audit rides analyze_memory()/self_check() and a fresh registry is
    quiet."""
    assert analysis.analyze_parallel() == []      # fresh: quiet
    # a dp8 plan whose only rule replicates a big (>=1 MiB w/ small
    # threshold) tensor — use the real trainer registration, custom
    # threshold keeps the test model tiny
    net, t = _trainer(plan=ShardingPlan({"dp": 8}, [(r".", ())]))
    t.step(nd.array(_X), nd.array(_Y))
    assert f"spmd:{net.name}" in planner.plans()
    # the tiny MLP's biggest tensor is dense0_weight (512 B) — a 256 B
    # threshold makes it "big" for the audit
    f = analysis.analyze_parallel(big_bytes=256)
    assert any(x.rule == "MXL313" and "fully replicated" in x.message
               for x in f)
    # the default 64 MiB threshold keeps the tiny MLP quiet — and so
    # does analyze_memory / the self_check ride-along
    assert [x for x in analysis.analyze_memory()
            if x.rule == "MXL313"] == []


# -- CLI ---------------------------------------------------------------------

def _mxplan(*argv):
    tool = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "mxplan.py")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, tool, *argv],
                          capture_output=True, text=True, timeout=240,
                          env=env)


def test_mxplan_cli():
    with tempfile.TemporaryDirectory() as d:
        a = os.path.join(d, "a.json")
        b = os.path.join(d, "b.json")
        ShardingPlan({"dp": 4, "tp": 2}, megatron_rules(),
                     zero_stage=1).save(a)
        ShardingPlan({"dp": 8}).save(b)
        res = _mxplan("show", a)
        assert res.returncode == 0 and "rule #0" in res.stdout
        res = _mxplan("diff", a, b)
        assert res.returncode == 0 and "record diff" in res.stdout
        res = _mxplan("lint", a)
        assert res.returncode == 0
        bad = os.path.join(d, "bad.json")
        with open(bad, "w") as f:
            f.write("{nope")
        for args in (("show", bad), ("lint", bad),
                     ("diff", bad, b)):
            res = _mxplan(*args)
            assert res.returncode == 1
            assert "malformed plan" in res.stderr
