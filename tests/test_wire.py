"""mxwire — the jaxpr-level wire-leg auditor (MXL8xx;
docs/static_analysis.md, "The wire auditor").

Tier-1 coverage for ISSUE 16: the seeded-defect corpus for every
MXL801-804 rule (defect caught red->green with leg attribution, clean
twin quiet), fresh-process quiet, the ``ShardingPlan.precision``
serialization contract (round-trip, legacy fail-open, stable legacy
``struct_hash``), the MXL313 decode-only-plan case, the dense-dp8
static-vs-observatory reconciliation (within MXL804's 10%), the ZeRO-2
explicit-leg walk, and the llama_tiny dp x tp demo-trainer self-lint.
"""
import collections
import json
import os
import tempfile

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd, parallel, telemetry
from mxnet_tpu import analysis
from mxnet_tpu.analysis import wire_passes
from mxnet_tpu.analysis.corpus import wire_defect_corpus
from mxnet_tpu.base import MXNetError
from mxnet_tpu.gluon import nn
from mxnet_tpu.gluon.loss import SoftmaxCrossEntropyLoss
from mxnet_tpu.parallel.planner import (ShardingPlan, WIRE_LEG_KINDS,
                                        wire_dtype_itemsize)

# every test here builds the 8-device virtual mesh — auto-skip on fewer
pytestmark = pytest.mark.needs_mesh(8)


@pytest.fixture(autouse=True)
def _clean_wire():
    """Every test leaves the wire registry empty and the ZeRO env
    unset: registered variants feed the process-global ``self_check``
    gate, and MXL801/802 are error severity — a leaked variant would
    fail a later module's ``--self-check``."""
    prev = os.environ.pop("MXTPU_ZERO_STAGE", None)
    wire_passes._reset()
    yield
    wire_passes._reset()
    if prev is None:
        os.environ.pop("MXTPU_ZERO_STAGE", None)
    else:
        os.environ["MXTPU_ZERO_STAGE"] = prev


def _mlp(seed=0, units=256):
    np.random.seed(seed)
    mx.random.seed(seed)
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(units, activation="relu", in_units=64),
                nn.Dense(10, in_units=units))
    net.initialize(mx.init.Xavier())
    return net


def _step_a_trainer(dpt, steps=3, b=32, d=64):
    X = np.random.RandomState(0).randn(b, d).astype("f4")
    Y = np.random.RandomState(1).randint(0, 10, b).astype("f4")
    for _ in range(steps):
        loss = dpt.step(nd.array(X), nd.array(Y))
    loss.wait_to_read()
    return loss


# ---------------------------------------------------------------------------
# fresh-process quiet + the seeded-defect corpus
# ---------------------------------------------------------------------------


def test_fresh_registry_is_quiet():
    """No registered variants -> analyze_wire() is free and empty (the
    --self-check CI gate's fresh half)."""
    assert wire_passes.variants() == {}
    assert analysis.analyze_wire() == []


def test_corpus_defects_caught_and_twins_quiet():
    """Every seeded wire defect is caught by EXACTLY its rule; every
    clean twin is silent (red->green for MXL801-804)."""
    seen = set()
    for e in wire_defect_corpus():
        findings = analysis.analyze_wire(
            jaxpr=e["jaxpr"], plan=e["plan"],
            owner=f"corpus:{e['name']}", **e["kwargs"])
        if e["clean"]:
            assert findings == [], (e["name"],
                                    [f.format() for f in findings])
        else:
            assert [f.rule for f in findings] == [e["rule"]], \
                (e["name"], [f.format() for f in findings])
            seen.add(e["rule"])
    assert seen == {"MXL801", "MXL802", "MXL803", "MXL804"}


def test_mxl801_names_leg_axis_and_widened_dtype():
    """ISSUE 16 acceptance: the fp32-widened int8 leg finding carries
    the leg kind, the wire axis, and the widened dtype."""
    e = [x for x in wire_defect_corpus()
         if x["name"] == "fp32_widened_int8_leg"][0]
    (f,) = analysis.analyze_wire(jaxpr=e["jaxpr"], plan=e["plan"])
    assert f.rule == "MXL801" and f.severity == "error"
    assert "dp_grad" in f.message          # the leg kind
    assert "'dp'" in f.message             # the wire axis
    assert "float32" in f.message          # the widened on-wire dtype
    assert "int8" in f.message             # the declared precision
    assert "4x" in f.message               # the widening factor
    assert f.location.startswith("wire:")


def test_mxl802_and_mxl803_attribution():
    c = {e["name"]: e for e in wire_defect_corpus()}
    e = c["psum_on_zero2_grad_leg"]
    (f,) = analysis.analyze_wire(jaxpr=e["jaxpr"], plan=e["plan"],
                                 **e["kwargs"])
    assert f.rule == "MXL802" and f.severity == "error"
    assert "reduce-scatter" in f.message and "'dp'" in f.message
    e = c["ungated_fingerprint_row"]
    (f,) = analysis.analyze_wire(jaxpr=e["jaxpr"], plan=e["plan"],
                                 **e["kwargs"])
    assert f.rule == "MXL803" and f.severity == "warning"
    assert "all_gather" in f.message and "sampl" in f.message


# ---------------------------------------------------------------------------
# ShardingPlan.precision — serialization contract
# ---------------------------------------------------------------------------


def test_precision_round_trips_record_save_load_hash(tmp_path):
    plan = ShardingPlan({"dp": 8}, zero_stage=2,
                        precision={"zero_scatter": "int8",
                                   "zero_gather": "float32"})
    rec = plan.to_record()
    assert rec["precision"] == {"zero_scatter": "int8",
                                "zero_gather": "float32"}
    path = os.path.join(str(tmp_path), "plan.json")
    plan.save(path)
    back = ShardingPlan.load(path)
    assert back.precision == plan.precision
    assert back.struct_hash() == plan.struct_hash()
    # precision is structural: declaring it changes the identity
    bare = ShardingPlan({"dp": 8}, zero_stage=2)
    assert bare.struct_hash() != plan.struct_hash()


def test_legacy_precision_free_record_loads_fail_open(tmp_path):
    """A pre-precision plan file (no ``precision`` key) loads with
    ``precision=None`` and keeps its legacy struct_hash — the
    warm-start manifests of existing checkpoints stay valid."""
    bare = ShardingPlan({"dp": 8})
    rec = bare.to_record()
    assert "precision" not in rec       # only-when-set serialization
    path = os.path.join(str(tmp_path), "legacy.json")
    with open(path, "w") as f:
        json.dump(rec, f)
    back = ShardingPlan.load(path)
    assert back.precision is None
    assert back.struct_hash() == bare.struct_hash()


def test_precision_validation_rejects_junk():
    with pytest.raises(MXNetError, match="leg"):
        ShardingPlan({"dp": 8}, precision={"warp_drive": "int8"})
    with pytest.raises(MXNetError, match="dtype"):
        ShardingPlan({"dp": 8}, precision={"dp_grad": "float99"})
    assert wire_dtype_itemsize("int8") == 1
    assert wire_dtype_itemsize("bfloat16") == 2
    assert set(WIRE_LEG_KINDS) >= {"dp_grad", "zero_scatter",
                                   "zero_gather", "tp_act", "decode"}


# ---------------------------------------------------------------------------
# MXL313 — a decode-only plan audited for trainable coverage
# ---------------------------------------------------------------------------


def test_mxl313_decode_only_plan_replicated_big_tensor():
    """A serving-style decode-only plan (KV pages sharded over dp, NO
    param rules — the deliberate pure-DP idiom, so ``uncovered`` stays
    quiet) still gets the big-tensor audit: a weight over the
    threshold replicates 8x and analyze_parallel names it with
    ``no rule matched`` attribution (ISSUE 16 satellite)."""
    plan = ShardingPlan({"dp": 8}, decode=("dp",))
    named = [("lm0_embed_weight", (1024, 512)),     # 2 MiB, over
             ("lm0_attn_q_weight", (64, 64))]       # 16 KiB, under
    findings = analysis.analyze_parallel(plan=plan, named_shapes=named,
                                         owner="decode_only",
                                         big_bytes=1 << 20)
    assert len(findings) == 1
    (f,) = findings
    assert f.rule == "MXL313"
    assert "lm0_embed_weight" in f.message
    assert "no rule matched" in f.message
    assert "8-device" in f.message
    # sharding the embed (vocab over dp) makes the same plan quiet
    covered = ShardingPlan({"dp": 8},
                           [("embed", ("dp", None)), (".", ())],
                           decode=("dp",))
    assert analysis.analyze_parallel(plan=covered, named_shapes=named,
                                     owner="decode_only",
                                     big_bytes=1 << 20) == []


# ---------------------------------------------------------------------------
# the live trainer paths: registration, reconciliation, self-lint
# ---------------------------------------------------------------------------


def _dp8_report(zero_stage=0):
    """Three fused Adam steps of the MLP on dp=8 at ``zero_stage``; the
    variant's row of ``wire_report()``."""
    os.environ["MXTPU_ZERO_STAGE"] = str(zero_stage)
    net = _mlp()
    dpt = parallel.DataParallelTrainer(
        net, SoftmaxCrossEntropyLoss(), "adam",
        {"learning_rate": 1e-3}, mesh=parallel.make_mesh({"dp": 8}),
        fuse_step=True)
    _step_a_trainer(dpt)
    return wire_passes.wire_report()[f"spmd:{net.name}"]


def test_dense_dp8_reconciles_within_ten_percent():
    """ISSUE 16 acceptance: on the dense dp8 fused step the derived
    static wire model lands within MXL804's 10% of the memory
    observatory's runtime accounting — and the audit is quiet."""
    rep = _dp8_report()
    assert rep["derived"] and rep["reconciled"]
    assert rep["trace_error"] is None
    assert rep["measured_wire_bytes"] is not None
    assert rep["drift"] <= 0.10, rep
    # the implicit model is per-param attributed
    grads = [leg for leg in rep["legs"] if leg["implicit"]]
    assert grads and all(leg.get("param") for leg in grads)
    # the health plane's fingerprint row walked out of the jaxpr:
    # gated, obs-only, classified stats
    stats = [leg for leg in rep["legs"] if leg["kind"] == "stats"]
    assert stats and all(leg["gated"] and leg["obs_only"]
                         for leg in stats)
    assert analysis.analyze_wire() == []


def test_zero2_walks_explicit_contract_legs():
    """The ZeRO-2 fused step's jaxpr carries the stage-2 wire contract
    EXPLICITLY — reduce-scatter (zero_scatter) + all-gather
    (zero_gather) — and reconciles exactly; no MXL802."""
    rep = _dp8_report(zero_stage=2)
    kinds = {leg["kind"] for leg in rep["legs"]}
    assert "zero_scatter" in kinds and "zero_gather" in kinds
    assert not rep["derived"] and rep["reconciled"]
    assert rep["drift"] <= 0.10, rep
    assert analysis.analyze_wire() == []


@pytest.mark.parametrize("stage", [0, 2], ids=["dense_dp8", "zero2_dp8"])
def test_static_legs_sum_to_the_ring_model(stage):
    """Bytes on the wire per leg are a count from shapes.  Dense dp8:
    the per-param gradient legs sum to the ring all-reduce of every
    trainable byte, 2 (n-1)/n.  ZeRO-2: each leg of the pair moves
    (n-1)/n of a weight matrix, reduce-scatter in and all-gather out
    (both matrices gather; only the first is large enough to count as a
    scatter leg, the rest ride the scalar legs), and the static total
    equals what the observatory measured."""
    rep = _dp8_report(zero_stage=stage)
    by_kind = collections.Counter()
    for leg in rep["legs"]:
        by_kind[leg["kind"]] += leg["wire_bytes"]
    w0, w1 = 4 * 256 * 64, 4 * 10 * 256          # the two weight matrices
    param_bytes = w0 + 4 * 256 + w1 + 4 * 10
    if stage == 0:
        implicit = sum(leg["wire_bytes"] for leg in rep["legs"]
                       if leg["implicit"])
        assert implicit == 2 * 7 * param_bytes // 8 == 134470
        assert by_kind["dp_grad"] == 2 * 7 * (w0 + w1) // 8
    else:
        assert by_kind["zero_scatter"] == 7 * w0 // 8
        assert by_kind["zero_gather"] == 7 * (w0 + w1) // 8
        assert rep["static_wire_bytes"] == rep["measured_wire_bytes"]


def test_declared_precision_fires_mxl801_on_dense_leg():
    """Registry path red->green: a dp-only plan declaring
    dp_grad=int8 makes the dense fp32 grad legs MXL801 findings with
    per-param attribution; float32 declaration is quiet."""
    net = _mlp()
    dpt = parallel.DataParallelTrainer(
        net, SoftmaxCrossEntropyLoss(), "adam",
        {"learning_rate": 1e-3}, fuse_step=True,
        plan=ShardingPlan({"dp": 8}, [(".", ())],
                          precision={"dp_grad": "int8"}))
    _step_a_trainer(dpt)
    findings = analysis.analyze_wire()
    assert findings and all(f.rule == "MXL801" for f in findings)
    assert any(f"{net.name}_dense0_weight" in f.message
               for f in findings)
    # green twin: same trainer shape, truthful declaration
    wire_passes._reset()
    net2 = _mlp(seed=1)
    dpt2 = parallel.DataParallelTrainer(
        net2, SoftmaxCrossEntropyLoss(), "adam",
        {"learning_rate": 1e-3}, fuse_step=True,
        plan=ShardingPlan({"dp": 8}, [(".", ())],
                          precision={"dp_grad": "float32"}))
    _step_a_trainer(dpt2)
    assert analysis.analyze_wire() == []


def test_llama_tiny_dp_tp_demo_self_lint():
    """ISSUE 16 satellite: the wire audit AND the plan coverage audit
    are both clean over a built llama_tiny dp x tp demo trainer (the
    megatron rule set; fused step registered and walked)."""
    from mxnet_tpu.models import LlamaForCausalLM, llama_tiny
    from mxnet_tpu.parallel import planner as _planner
    np.random.seed(0)
    mx.random.seed(0)
    net = LlamaForCausalLM(llama_tiny(vocab_size=64))
    net.initialize(mx.init.Xavier())
    plan = ShardingPlan({"dp": 2, "tp": 4}, parallel.megatron_rules())
    sce = SoftmaxCrossEntropyLoss()

    def lm_loss(logits, toks):
        v = logits.shape[-1]
        return sce(logits[:, :-1].reshape((-1, v)),
                   toks[:, 1:].reshape((-1,))).mean()

    dpt = parallel.DataParallelTrainer(
        net, lm_loss, "adam", {"learning_rate": 1e-3},
        fuse_step=True, plan=plan)
    toks = nd.array(np.random.RandomState(2)
                    .randint(0, 64, (4, 8)).astype("f4"))
    for _ in range(2):
        loss = dpt.step(toks, toks)
    loss.wait_to_read()
    key = f"spmd:{net.name}"
    rep = wire_passes.wire_report()[key]
    assert rep["trace_error"] is None
    # dense tp>1: GSPMD traffic is unmodelable, so no derived model
    # and no MXL804 reconciliation claim — and NO findings
    assert not rep["derived"] and not rep["reconciled"]
    assert analysis.analyze_wire() == []
    assert [f for f in analysis.analyze_parallel()
            if key in f.location] == []


def test_wire_audit_env_kill_switch():
    """MXTPU_WIRE_AUDIT=0 disables registration entirely."""
    os.environ["MXTPU_WIRE_AUDIT"] = "0"
    try:
        net = _mlp()
        dpt = parallel.DataParallelTrainer(
            net, SoftmaxCrossEntropyLoss(), "adam",
            {"learning_rate": 1e-3},
            mesh=parallel.make_mesh({"dp": 8}), fuse_step=True)
        _step_a_trainer(dpt, steps=1)
        assert wire_passes.variants() == {}
    finally:
        os.environ.pop("MXTPU_WIRE_AUDIT")


def test_registration_stores_avals_not_arrays():
    """The registry must hold abstract signatures only — a registered
    variant pinning live device buffers would defeat donation."""
    import jax
    net = _mlp()
    dpt = parallel.DataParallelTrainer(
        net, SoftmaxCrossEntropyLoss(), "adam",
        {"learning_rate": 1e-3}, mesh=parallel.make_mesh({"dp": 8}),
        fuse_step=True)
    _step_a_trainer(dpt, steps=1)
    (rec,) = wire_passes.variants().values()
    leaves = jax.tree_util.tree_leaves(rec["avals"])
    assert leaves
    assert all(not isinstance(x, jax.Array) for x in leaves)
