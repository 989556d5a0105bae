"""One-dispatch compiled Gluon train step (docs/compiled_step.md).

Tier-1 coverage for CompiledStep:

* acceptance: a compiled train step is EXACTLY 1 engine dispatch
  (``cache_info()["dispatches"]``), and ``step_multi(K)`` is 1 dispatch
  whose results are bit-identical to K eager record/backward/step calls;
* fused-vs-eager equivalence of loss, params, and optimizer states over
  5 steps for an MLP with dropout (bit-exact, RNG parity), a model-zoo
  conv net with BatchNorm (running-stat aux updates through the donated
  step), and the BERT-small builder;
* dynamic-input hygiene: lr schedule / wd / batch size / dropout keys
  enter as array inputs — stepping 5 times with all of them varying
  compiles nothing new (regression via ``cache_info()``, as PR 2 did
  for ``rescale_grad``);
* static-attr drift (momentum change) recompiles ONCE and stays
  correct instead of applying a stale baked value;
* ``MXTPU_COMPILED_STEP=0`` escape hatch and the transparent eager
  fallbacks (non-fused optimizer, non-hybridizable forward), with the
  fallback registry feeding mxlint's MXL305;
* save/load_states round-trip across compiled/eager paths.
"""
import os

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, engine, gluon, nd


def _mlp(dropout=0.2):
    net = gluon.nn.HybridSequential()
    with net.name_scope():
        net.add(gluon.nn.Dense(8, activation="relu", in_units=6),
                gluon.nn.Dropout(dropout),
                gluon.nn.Dense(3, in_units=8))
    net.initialize(mx.init.Xavier())
    net.hybridize()
    return net


def _data(rng_seed=2):
    X = nd.array(np.random.RandomState(rng_seed).rand(4, 6).astype("f4"))
    Y = nd.array(
        np.random.RandomState(rng_seed + 1).rand(4, 3).astype("f4"))
    return X, Y


def _params_np(net):
    # positional: block-scope prefixes differ between instances
    return {i: p.data().asnumpy() for i, p in
            enumerate(net.collect_params().values())}


def _states_np(trainer):
    out = {}
    for k, s in trainer._updaters[0].states.items():
        leaves = s if isinstance(s, (list, tuple)) else [s]
        out[k] = [x.asnumpy() for x in leaves if x is not None]
    return out


#: params/states of the whole-step program vs the eager per-op chain.
#: XLA:CPU (jaxlib 0.9.0) fuses the loss backward INTO the last bias's
#: gradient reduction and contracts its multiply with the accumulate
#: into one FMA (one rounding); the eager chain dispatches the multiply
#: and the reduce as separate programs (two roundings).  Same operation
#: order, both correct roundings, the fused one the more exact (checked
#: against a float64 FMA emulation); measured 1 ulp after 5 momentum
#: steps.  Losses stay asserted bit-identical.
_FMA_ULP = 4


def _assert_same(a, b, atol=0.0, maxulp=None):
    assert sorted(a) == sorted(b)
    for k in a:
        xs, ys = (a[k], b[k]) if isinstance(a[k], list) \
            else ([a[k]], [b[k]])
        for x, y in zip(xs, ys):
            if maxulp is not None:
                np.testing.assert_array_max_ulp(x, y, maxulp=maxulp)
            else:
                np.testing.assert_allclose(x, y, rtol=0, atol=atol)


def _eager_steps(net, trainer, loss_fn, batches, batch_size=4):
    losses = []
    for X, Y in batches:
        with autograd.record():
            loss = loss_fn(net(X), Y)
        autograd.backward([loss])
        trainer.step(batch_size)
        losses.append(loss.asnumpy())
    return losses


# ---------------------------------------------------------------------------
# acceptance: dispatch contracts
# ---------------------------------------------------------------------------


def test_one_dispatch_per_step():
    """A compiled Gluon train step executes as exactly ONE device
    dispatch, and steady state is a cache hit, not a compile."""
    net = _mlp()
    tr = gluon.Trainer(net.collect_params(), "adam",
                       {"learning_rate": 0.01})
    cs = tr.compile_step(net, gluon.loss.L2Loss())
    X, Y = _data()
    for _ in range(2):
        cs.step(X, Y, 4)
    assert cs.last_path == "compiled"
    d0 = engine.cache_info()["dispatches"]
    cs.step(X, Y, 4)
    assert engine.cache_info()["dispatches"] - d0 == 1
    m0 = engine.cache_info()["misses"]
    cs.step(X, Y, 4)
    assert engine.cache_info()["misses"] == m0


def test_step_multi_one_dispatch_bitident_to_k_eager_steps():
    """step_multi(K) executes K optimizer steps in ONE dispatch with
    loss bit-identical to K eager steps, params/states to
    ``_FMA_ULP``."""
    K = 3
    rng = np.random.RandomState(7)
    Xk = rng.rand(K, 4, 6).astype("f4")
    Yk = rng.rand(K, 4, 3).astype("f4")
    l2 = gluon.loss.L2Loss()

    mx.random.seed(0)
    np.random.seed(0)
    net_a = _mlp()
    tr_a = gluon.Trainer(net_a.collect_params(), "adam",
                         {"learning_rate": 0.01})
    la = _eager_steps(net_a, tr_a, l2,
                      [(nd.array(Xk[k]), nd.array(Yk[k]))
                       for k in range(K)])

    mx.random.seed(0)
    np.random.seed(0)
    net_b = _mlp()
    tr_b = gluon.Trainer(net_b.collect_params(), "adam",
                         {"learning_rate": 0.01})
    cs = tr_b.compile_step(net_b, l2)
    lb = cs.step_multi(nd.array(Xk), nd.array(Yk), 4)
    assert cs.last_path == "compiled"
    np.testing.assert_array_equal(np.stack(la), lb.asnumpy())
    _assert_same(_params_np(net_a), _params_np(net_b), maxulp=_FMA_ULP)
    _assert_same(_states_np(tr_a), _states_np(tr_b), maxulp=_FMA_ULP)

    # and it was ONE dispatch (warm bracket)
    d0 = engine.cache_info()["dispatches"]
    cs.step_multi(nd.array(Xk), nd.array(Yk), 4)
    assert engine.cache_info()["dispatches"] - d0 == 1


def test_step_multi_repeat_matches_k_steps_on_same_batch():
    """repeat=K reuses one batch for K inner steps without K host
    copies — bit-identical to K step() calls on that batch."""
    K = 4
    X, Y = _data(11)
    l2 = gluon.loss.L2Loss()

    mx.random.seed(0)
    np.random.seed(0)
    net_a = _mlp()
    tr_a = gluon.Trainer(net_a.collect_params(), "sgd",
                         {"learning_rate": 0.05, "momentum": 0.9})
    la = _eager_steps(net_a, tr_a, l2, [(X, Y)] * K)

    mx.random.seed(0)
    np.random.seed(0)
    net_b = _mlp()
    tr_b = gluon.Trainer(net_b.collect_params(), "sgd",
                         {"learning_rate": 0.05, "momentum": 0.9})
    cs = tr_b.compile_step(net_b, l2)
    lb = cs.step_multi(X, Y, 4, repeat=K)
    assert cs.last_path == "compiled"
    np.testing.assert_array_equal(np.stack(la), lb.asnumpy())
    _assert_same(_params_np(net_a), _params_np(net_b))


# ---------------------------------------------------------------------------
# fused-vs-eager equivalence
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("optname,opt_kw", [
    ("sgd", {"learning_rate": 0.05, "momentum": 0.9, "wd": 0.01}),
    ("adam", {"learning_rate": 0.01, "wd": 0.001}),
    ("lamb", {"learning_rate": 0.01, "wd": 0.01}),
])
def test_compiled_matches_eager_mlp_dropout(optname, opt_kw):
    """5 steps, dropout active: loss bit-identical, params/states to
    ``_FMA_ULP`` —
    covering dropout RNG parity with the eager hybridized path."""
    X, Y = _data()
    l2 = gluon.loss.L2Loss()

    mx.random.seed(0)
    np.random.seed(0)
    net_a = _mlp()
    tr_a = gluon.Trainer(net_a.collect_params(), optname, dict(opt_kw))
    la = _eager_steps(net_a, tr_a, l2, [(X, Y)] * 5)

    mx.random.seed(0)
    np.random.seed(0)
    net_b = _mlp()
    tr_b = gluon.Trainer(net_b.collect_params(), optname, dict(opt_kw))
    cs = tr_b.compile_step(net_b, l2)
    lb = [cs.step(X, Y, 4).asnumpy() for _ in range(5)]
    assert cs.last_path == "compiled" and cs.fallback_reason is None
    np.testing.assert_array_equal(np.stack(la), np.stack(lb))
    _assert_same(_params_np(net_a), _params_np(net_b), maxulp=_FMA_ULP)
    _assert_same(_states_np(tr_a), _states_np(tr_b), maxulp=_FMA_ULP)


@pytest.mark.slow
def test_compiled_matches_eager_model_zoo_convnet():
    """Model-zoo conv net (BatchNorm everywhere): 5 compiled steps match
    eager including the running-stat AUX updates flowing through the
    donated step.  Conv/BN kernels fused into the whole-step program may
    differ from the eager per-op chain by 1-2 ulp (reduction order), so
    the bound is tight-but-nonzero; see docs/compiled_step.md."""
    from mxnet_tpu.gluon.model_zoo import get_model
    rng = np.random.RandomState(0)
    X = nd.array(rng.rand(2, 3, 32, 32).astype("f4"))
    Y = nd.array(rng.randint(0, 4, (2,)).astype("f4"))
    sce = gluon.loss.SoftmaxCrossEntropyLoss()

    def train(compiled):
        mx.random.seed(0)
        np.random.seed(0)
        net = get_model("resnet18_v1", classes=4, thumbnail=True)
        net.initialize(mx.init.Xavier())
        net.hybridize()
        tr = gluon.Trainer(net.collect_params(), "sgd",
                           {"learning_rate": 0.05, "momentum": 0.9})
        if compiled:
            cs = tr.compile_step(net, sce)
            for _ in range(5):
                cs.step(X, Y, 2)
            assert cs.last_path == "compiled"
        else:
            _eager_steps(net, tr, sce, [(X, Y)] * 5, batch_size=2)
        return net, tr

    net_a, tr_a = train(False)
    net_b, tr_b = train(True)
    _assert_same(_params_np(net_a), _params_np(net_b), atol=2e-6)
    _assert_same(_states_np(tr_a), _states_np(tr_b), atol=2e-6)
    # the BN aux state REALLY moved (not left at init) through the
    # donated compiled step
    moved = [k for k, p in net_b.collect_params().items()
             if "running_mean" in k and
             np.abs(p.data().asnumpy()).max() > 0]
    assert moved


def test_compiled_matches_eager_bert_small():
    """The BERT-small builder (embeddings, transformer encoder, dropout,
    LayerNorm) trains identically through the compiled step."""
    from mxnet_tpu import models
    from mxnet_tpu.gluon.block import HybridBlock

    class Pooled(HybridBlock):
        def __init__(self, bert, **kw):
            super().__init__(**kw)
            with self.name_scope():
                self.bert = bert

        def hybrid_forward(self, F, tokens, types):
            _seq, pooled = self.bert(tokens, types, None)
            return pooled

    rng = np.random.RandomState(3)
    X = nd.array(rng.randint(0, 32, (2, 8)).astype("f4"))
    T = nd.array(rng.randint(0, 2, (2, 8)).astype("f4"))
    Y = nd.array(rng.rand(2, 256).astype("f4"))
    l2 = gluon.loss.L2Loss()

    def train(compiled):
        mx.random.seed(0)
        np.random.seed(0)
        net = Pooled(models.bert_small(vocab_size=32, max_length=8,
                                       dropout=0.1))
        net.initialize(mx.init.Xavier())
        net.hybridize()
        # momentum-SGD: linear in the gradients, so the 1-2 ulp fusion
        # noise stays 1-2 ulp (Adam's divisive update amplifies it on
        # near-zero-grad embedding rows; Adam bit-exactness is covered
        # by the MLP test)
        tr = gluon.Trainer(net.collect_params(), "sgd",
                           {"learning_rate": 0.05, "momentum": 0.9})
        if compiled:
            cs = tr.compile_step(net, l2)
            losses = [cs.step([X, T], Y, 2).asnumpy()
                      for _ in range(5)]
            assert cs.last_path == "compiled", cs.fallback_reason
        else:
            losses = _eager_steps(net, tr, l2, [([X, T], Y)] * 5,
                                  batch_size=2)

            # _eager_steps calls net(X) with a list; unpack instead
        return net, tr, losses

    # eager reference needs multi-input call: run inline
    mx.random.seed(0)
    np.random.seed(0)
    net_a = Pooled(models.bert_small(vocab_size=32, max_length=8,
                                     dropout=0.1))
    net_a.initialize(mx.init.Xavier())
    net_a.hybridize()
    tr_a = gluon.Trainer(net_a.collect_params(), "sgd",
                         {"learning_rate": 0.05, "momentum": 0.9})
    la = []
    for _ in range(5):
        with autograd.record():
            loss = l2(net_a(X, T), Y)
        autograd.backward([loss])
        tr_a.step(2)
        la.append(loss.asnumpy())

    net_b, tr_b, lb = train(True)
    np.testing.assert_allclose(np.stack(la), np.stack(lb), rtol=0,
                               atol=2e-6)
    _assert_same(_params_np(net_a), _params_np(net_b), atol=2e-6)
    _assert_same(_states_np(tr_a), _states_np(tr_b), atol=2e-6)


# ---------------------------------------------------------------------------
# dynamic-input hygiene
# ---------------------------------------------------------------------------


def test_no_retrace_across_lr_wd_batchsize_dropout():
    """lr schedule, wd, batch size (rescale_grad), and the dropout key
    are ARRAY inputs of the compiled step: varying all of them over 5
    steps compiles nothing new and never re-dispatches more than once."""
    net = _mlp(dropout=0.3)
    tr = gluon.Trainer(net.collect_params(), "adam",
                       {"learning_rate": 0.01, "wd": 0.001})
    cs = tr.compile_step(net, gluon.loss.L2Loss())
    X, Y = _data()
    cs.step(X, Y, 4)                         # warm (trace + compile)
    before = engine.cache_size()
    m0 = engine.cache_info()["misses"]
    for k, bs in enumerate((2, 3, 5, 7, 11)):
        tr.set_learning_rate(0.01 / (k + 1))     # scheduler analog
        d0 = engine.cache_info()["dispatches"]
        cs.step(X, Y, bs)
        assert engine.cache_info()["dispatches"] - d0 == 1
    assert engine.cache_size() == before, "fresh programs compiled"
    assert engine.cache_info()["misses"] == m0
    # second witness, as PR 2: the mxlint runtime pass sees no blowup
    # attributable to the step program
    from mxnet_tpu.analysis import analyze_cache
    bad = [f for f in analyze_cache(threshold=4)
           if "gluon_train_step" in f.message]
    assert not bad, [f.message for f in bad]


def test_lr_scheduler_object_no_retrace():
    """A real LRScheduler drives the compiled step without retracing."""
    from mxnet_tpu.lr_scheduler import FactorScheduler
    net = _mlp(dropout=0.0)
    tr = gluon.Trainer(
        net.collect_params(), "sgd",
        {"learning_rate": 0.05, "momentum": 0.9,
         "lr_scheduler": FactorScheduler(step=1, factor=0.7)})
    cs = tr.compile_step(net, gluon.loss.L2Loss())
    X, Y = _data()
    cs.step(X, Y, 4)
    before = engine.cache_size()
    for _ in range(4):
        cs.step(X, Y, 4)
    assert engine.cache_size() == before


def test_momentum_change_recompiles_once_and_stays_correct():
    """Static attrs (momentum) are baked; changing one mid-run evicts
    the stale executable and matches a fresh eager run — never silently
    applies the old value."""
    X, Y = _data()
    l2 = gluon.loss.L2Loss()

    def train(compiled):
        mx.random.seed(0)
        np.random.seed(0)
        net = _mlp(dropout=0.0)
        tr = gluon.Trainer(net.collect_params(), "sgd",
                           {"learning_rate": 0.05, "momentum": 0.9})
        cs = tr.compile_step(net, l2) if compiled else None
        for k in range(4):
            if k == 2:
                tr._optimizer.momentum = 0.5
            if compiled:
                cs.step(X, Y, 4)
            else:
                _eager_steps(net, tr, l2, [(X, Y)])
        return net

    net_a = train(False)
    net_b = train(True)
    _assert_same(_params_np(net_a), _params_np(net_b))


# ---------------------------------------------------------------------------
# escape hatch + fallbacks
# ---------------------------------------------------------------------------


def test_escape_hatch_env_matches_compiled():
    X, Y = _data()
    l2 = gluon.loss.L2Loss()

    def train(env):
        mx.random.seed(0)
        np.random.seed(0)
        net = _mlp()
        tr = gluon.Trainer(net.collect_params(), "adam",
                           {"learning_rate": 0.01})
        cs = tr.compile_step(net, l2)
        os.environ["MXTPU_COMPILED_STEP"] = env
        try:
            for _ in range(3):
                cs.step(X, Y, 4)
        finally:
            os.environ.pop("MXTPU_COMPILED_STEP", None)
        return net, cs

    net_a, cs_a = train("0")
    assert cs_a.last_path == "eager"
    # the env hatch is explicit, not a silent fallback
    assert cs_a.fallback_reason is None
    net_b, cs_b = train("1")
    assert cs_b.last_path == "compiled"
    _assert_same(_params_np(net_a), _params_np(net_b))


def test_fallback_unfused_optimizer_reported():
    """NAG has no fused program: the step transparently runs eager,
    matches a plain eager run, and the silent fallback is recorded for
    mxlint (MXL305 carries the reason)."""
    from mxnet_tpu.gluon import compiled_step as csmod
    from mxnet_tpu.analysis import analyze_compiled_steps
    csmod.clear_fallback_reports()
    X, Y = _data()
    l2 = gluon.loss.L2Loss()

    mx.random.seed(0)
    np.random.seed(0)
    net_a = _mlp()
    tr_a = gluon.Trainer(net_a.collect_params(), "nag",
                         {"learning_rate": 0.05, "momentum": 0.9})
    _eager_steps(net_a, tr_a, l2, [(X, Y)] * 3)

    mx.random.seed(0)
    np.random.seed(0)
    net_b = _mlp()
    tr_b = gluon.Trainer(net_b.collect_params(), "nag",
                         {"learning_rate": 0.05, "momentum": 0.9})
    cs = tr_b.compile_step(net_b, l2)
    for _ in range(3):
        cs.step(X, Y, 4)
    assert cs.last_path == "eager"
    assert "NAG" in cs.fallback_reason
    _assert_same(_params_np(net_a), _params_np(net_b))

    findings = analyze_compiled_steps()
    assert any(f.rule == "MXL305" and "NAG" in f.message
               for f in findings)
    csmod.clear_fallback_reports()
    assert analyze_compiled_steps() == []


def test_fallback_non_hybridizable_forward():
    """A host sync inside hybrid_forward kills the trace; the SAME call
    transparently completes on the eager path (host bookkeeping rewound
    first) and the reason lands in the registry."""
    from mxnet_tpu.gluon import compiled_step as csmod

    class Bad(gluon.HybridBlock):
        def __init__(self, **kw):
            super().__init__(**kw)
            with self.name_scope():
                self.d = gluon.nn.Dense(3, in_units=6)

        def hybrid_forward(self, F, x):
            _ = float(x.asnumpy().sum())  # mxlint: disable=MXL302
            return self.d(x)

    csmod.clear_fallback_reports()
    X, Y = _data()
    l2 = gluon.loss.L2Loss()

    mx.random.seed(0)
    np.random.seed(0)
    net_a = Bad()
    net_a.initialize(mx.init.Xavier())
    tr_a = gluon.Trainer(net_a.collect_params(), "sgd",
                         {"learning_rate": 0.05})
    la = _eager_steps(net_a, tr_a, l2, [(X, Y)] * 2)

    mx.random.seed(0)
    np.random.seed(0)
    net_b = Bad()
    net_b.initialize(mx.init.Xavier())
    tr_b = gluon.Trainer(net_b.collect_params(), "sgd",
                         {"learning_rate": 0.05})
    cs = tr_b.compile_step(net_b, l2)
    lb = [cs.step(X, Y, 4).asnumpy() for _ in range(2)]
    assert cs.last_path == "eager"
    assert "trace/compile failed" in cs.fallback_reason
    np.testing.assert_array_equal(np.stack(la), np.stack(lb))
    _assert_same(_params_np(net_a), _params_np(net_b))
    assert any(n == cs.name for n, _ in csmod.fallback_reports())
    csmod.clear_fallback_reports()


# ---------------------------------------------------------------------------
# state serialization across paths
# ---------------------------------------------------------------------------


def test_save_load_states_roundtrip_across_paths(tmp_path):
    """States written by the compiled step serialize identically to the
    eager path's, and an eager trainer continues a compiled run
    bit-for-bit after load_states (and vice versa the compiled step
    re-resolves the swapped state objects)."""
    fname = str(tmp_path / "opt.states")
    X, Y = _data()
    l2 = gluon.loss.L2Loss()

    mx.random.seed(0)
    np.random.seed(0)
    net_a = _mlp(dropout=0.0)
    tr_a = gluon.Trainer(net_a.collect_params(), "adam",
                         {"learning_rate": 0.01})
    cs_a = tr_a.compile_step(net_a, l2)
    for _ in range(3):
        cs_a.step(X, Y, 4)
    assert cs_a.last_path == "compiled"
    tr_a.save_states(fname)

    mx.random.seed(0)
    np.random.seed(0)
    net_b = _mlp(dropout=0.0)
    tr_b = gluon.Trainer(net_b.collect_params(), "adam",
                         {"learning_rate": 0.01})
    _eager_steps(net_b, tr_b, l2, [(X, Y)] * 3)
    tr_b.load_states(fname)
    _assert_same(_states_np(tr_a), _states_np(tr_b))

    # continue BOTH on their own path; trajectories stay identical.
    # (Copy through the host: set_data(p_a.data()) would ALIAS the jax
    # buffer, which the next compiled step donates — the documented
    # donation contract, docs/compiled_step.md.)
    for p_a, p_b in zip(net_a.collect_params().values(),
                        net_b.collect_params().values()):
        p_b.set_data(p_a.data().asnumpy())
    cs_a.step(X, Y, 4)
    _eager_steps(net_b, tr_b, l2, [(X, Y)])
    _assert_same(_params_np(net_a), _params_np(net_b))

    # and the compiled step survives ITS OWN load_states (fresh state
    # NDArray objects must be picked up, not stale cached leaves)
    tr_a.load_states(fname)
    cs_a.step(X, Y, 4)
    assert cs_a.last_path == "compiled"


def test_batch_size_defaults_to_label_dim():
    net = _mlp(dropout=0.0)
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 0.05})
    cs = tr.compile_step(net, gluon.loss.L2Loss())
    X, Y = _data()
    cs.step(X, Y)       # batch_size inferred = 4
    assert tr._optimizer.rescale_grad == pytest.approx(0.25)
