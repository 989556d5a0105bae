"""The fused step's optimizer scalars travel as ONE float32 vector.

``DataParallelTrainer.step`` hands its fused program the per-step
optimizer scalars (bias-corrected lr, wd, ...) as one ``(S,)`` host
vector, ``S = n_scalars x trainable params``, the scalars of trainable
param ``j`` at ``[j * n_scalars + k]`` — the layout ``step_multi`` has
always stacked K of.  Every case runs on the 8-way CPU mesh, over the
four single-step call shapes {dense, ZeRO-1, ZeRO-2, int8-compressed}
and three rules {Adam, AdamW with three scalars, SGD with momentum}:

* three ``step()``s are handed bit for bit the rows ``step_multi``
  stacks for the same three batches, land within a few ulp of it, and
  within the suite's fused-vs-eager tolerance of the eager
  per-parameter ``Optimizer.update``;
* distinct ``lr_mult`` / ``wd_mult`` on two parameters land on those
  parameters;
* a learning-rate change between steps changes the update and costs
  no compile, no retrace and no second dispatch.
"""
import os

import numpy as np
import pytest

pytestmark = pytest.mark.needs_mesh(8)

import mxnet_tpu as mx
from mxnet_tpu import engine, lr_scheduler, nd, parallel, telemetry
from mxnet_tpu.base import MXNetError
from mxnet_tpu.gluon import nn
from mxnet_tpu.gluon.loss import SoftmaxCrossEntropyLoss
from mxnet_tpu.parallel import zero as zmod
from mxnet_tpu.parallel.trainer import _flatten

MODES = ["dense", "zero1", "zero2", "int8"]
OPTS = {
    "adam": ("adam", {"learning_rate": 1e-2, "wd": 1e-2}),
    "adamw": ("adamw", {"learning_rate": 1e-2, "wd": 1e-2}),
    "sgd_momentum": ("sgd", {"learning_rate": 0.05, "momentum": 0.9,
                             "wd": 1e-2}),
}
# fused-vs-eager tolerance of tests/test_parallel.py and test_zero.py.
# The int8 wire quantizes every gradient, and an Adam update has size
# lr whatever the gradient's: a lane the wire rounds to zero moves a
# weight by up to lr a step, so K steps of 1e-2 bound the distance
TOL = {m: dict(rtol=2e-5, atol=1e-5) for m in MODES}
TOL["int8"] = dict(rtol=0, atol=3e-2)
# step() against step_multi: the same arithmetic compiled as a scan
# body; XLA may fuse it differently, so it is held to a few ulp and
# not to the bit (AdamW under ZeRO-2 differs by 1 ulp in one moment,
# before this change as after)
SCAN_TOL = dict(rtol=2e-6, atol=1e-8)

K, B = 3, 16
_RNG = np.random.RandomState(0)
X = _RNG.randn(K, B, 8).astype("f4")
Y = _RNG.randint(0, 4, (K, B)).astype("f4")
# params in collect_params() order: dense0 weight, bias, dense1 weight,
# bias.  The first is frozen outright, the third runs hot.
LR_MULT = {0: 0.0, 2: 3.0}
WD_MULT = {0: 0.0, 2: 2.0}

grid = pytest.mark.parametrize("opt", list(OPTS))
modes = pytest.mark.parametrize("mode", MODES)


@pytest.fixture(autouse=True)
def _zero_env():
    prev = os.environ.pop("MXTPU_ZERO_STAGE", None)
    telemetry.enable()
    yield
    os.environ.pop("MXTPU_ZERO_STAGE", None)
    if prev is not None:
        os.environ["MXTPU_ZERO_STAGE"] = prev


def _net():
    np.random.seed(7)
    mx.random.seed(7)
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(16, activation="relu", in_units=8),
                nn.Dense(4, in_units=16))
    net.initialize(mx.init.Xavier())
    return net


def _optimizer(opt, mults=False, schedule=False):
    name, args = OPTS[opt]
    args = dict(args)
    if schedule:
        args["lr_scheduler"] = lr_scheduler.FactorScheduler(
            step=1, factor=0.5, base_lr=args["learning_rate"])
    o = mx.optimizer.create(name, **args)
    if mults:
        o.set_lr_mult(LR_MULT)
        o.set_wd_mult(WD_MULT)
    return o


def _fused(mode, opt, **okw):
    os.environ["MXTPU_ZERO_STAGE"] = {"zero1": "1", "zero2": "2"}.get(
        mode, "0")
    net = _net()
    dpt = parallel.DataParallelTrainer(
        net, SoftmaxCrossEntropyLoss(), _optimizer(opt, **okw),
        mesh=parallel.make_mesh({"dp": 8}), fuse_step=True,
        compression={"type": "int8"} if mode == "int8" else None)
    return net, dpt


def _weights(net):
    return [p.data().asnumpy() for p in net.collect_params().values()]


def _states(dpt):
    """Optimizer-state leaves in the full (unsharded) layout."""
    out = []
    for i in dpt._tr_idx:
        leaves = []
        _flatten(dpt._states[i], leaves)
        shape = tuple(dpt._params[i].data().shape)
        for leaf in leaves:
            h = np.asarray(leaf._data)
            out.append(zmod.gather_host(h, shape)
                       if h.shape != shape else h)
    return out


def _eager(opt, steps, lr_after_first=None, **okw):
    """The reference the fused rules are held to: autograd + one
    ``Optimizer.update`` per parameter, no fused program."""
    net = _net()
    o = _optimizer(opt, **okw)
    params = list(net.collect_params().values())
    states = [o.create_state(i, p.data()) for i, p in enumerate(params)]
    loss_fn = SoftmaxCrossEntropyLoss()
    for k in range(steps):
        if k == 1 and lr_after_first is not None:
            o.set_learning_rate(lr_after_first)
        with mx.autograd.record():
            l = loss_fn(net(nd.array(X[k])), nd.array(Y[k])).mean()
        l.backward()
        for i, p in enumerate(params):
            o.update(i, p.data(), p.grad(), states[i])
    leaves = []
    for s in states:
        _flatten(s, leaves)
    return _weights(net), [x.asnumpy() for x in leaves]


def _step(dpt, k):
    return dpt.step(nd.array(X[k]), nd.array(Y[k]))


@modes
@grid
def test_three_steps_equal_step_multi_and_eager(mode, opt):
    net_s, dpt_s = _fused(mode, opt)
    handed = []
    for k in range(K):
        _step(dpt_s, k).wait_to_read()
        handed.append(dpt_s._step_scalars())
    assert dpt_s._zero_stage == {"zero1": 1, "zero2": 2}.get(mode, 0)

    net_m, dpt_m = _fused(mode, opt)
    dpt_m._setup([nd.array(X[0])])
    # what step k was handed == row k of what step_multi stacks
    np.testing.assert_array_equal(
        np.stack(handed),
        np.stack([dpt_m._step_scalars(k + 1) for k in range(K)]))
    if mode == "int8":
        # the stage-0 compressed wire has no bulked program
        with pytest.raises(MXNetError, match="compression"):
            dpt_m.step_multi(nd.array(X), nd.array(Y))
    else:
        dpt_m.step_multi(nd.array(X), nd.array(Y)).wait_to_read()
        for a, b in zip(_weights(net_s), _weights(net_m)):
            np.testing.assert_allclose(a, b, **SCAN_TOL)
        for a, b in zip(_states(dpt_s), _states(dpt_m)):
            np.testing.assert_allclose(a, b, **SCAN_TOL)

    w_ref, s_ref = _eager(opt, K)
    for a, b in zip(_weights(net_s), w_ref):
        np.testing.assert_allclose(a, b, **TOL[mode])
    for a, b in zip(_states(dpt_s), s_ref):
        np.testing.assert_allclose(a, b, **TOL[mode])


@modes
@grid
def test_lr_and_wd_mults_land_on_their_parameters(mode, opt):
    net, dpt = _fused(mode, opt, mults=True)
    w0 = _weights(net)
    _step(dpt, 0).wait_to_read()

    # the vector the step handed over: row j is param tr_idx[j]'s
    o, rule = dpt.optimizer, dpt._rule
    n = len(rule.scalars(o, 0, 1))
    vec = dpt._step_scalars()
    assert vec.dtype == np.float32 and vec.shape == (n * len(w0),)
    for j, i in enumerate(dpt._tr_idx):
        np.testing.assert_array_equal(
            vec[j * n:(j + 1) * n],
            np.asarray(rule.scalars(o, i, 1), np.float32))
    assert vec[0] == 0.0                          # lr of the frozen one
    np.testing.assert_allclose(vec[2 * n], 3.0 * vec[n], rtol=1e-6)

    w1 = _weights(net)
    np.testing.assert_array_equal(w1[0], w0[0])   # lr_mult = wd_mult = 0
    assert all(np.abs(a - b).max() > 0 for a, b in zip(w1[1:], w0[1:]))
    w_ref, _ = _eager(opt, 1, mults=True)
    for a, b in zip(w1, w_ref):
        np.testing.assert_allclose(a, b, **TOL[mode])
    # and the hot parameter really moved by its own multiplier: three
    # times as far as the eager twin without multipliers moves it
    w_plain, _ = _eager(opt, 1)
    moved, plain = np.abs(w1[2] - w0[2]), np.abs(w_plain[2] - w0[2])
    assert moved.mean() > 2.0 * plain.mean()


@modes
@grid
def test_lr_change_between_steps_costs_no_compile(mode, opt):
    # SGD ticks a FactorScheduler (lr halves every update); the Adams
    # get set_learning_rate between the first and the second step
    schedule = opt == "sgd_momentum"
    lr = OPTS[opt][1]["learning_rate"]
    net, dpt = _fused(mode, opt, schedule=schedule)
    _step(dpt, 0).wait_to_read()
    telemetry.reset()
    before = engine.cache_info()
    jit_sizes = dpt._full_step._cache_size()
    if not schedule:
        dpt.optimizer.set_learning_rate(lr * 0.5)
    _step(dpt, 1).wait_to_read()
    after = engine.cache_info()
    assert after["dispatches"] - before["dispatches"] == 1
    assert after["fresh_compiles"] == before["fresh_compiles"]
    assert after["misses"] == before["misses"]
    assert dpt._full_step._cache_size() == jit_sizes
    assert telemetry.events("retrace") == []

    # the new rate is in the update
    w_ref, s_ref = _eager(opt, 2, lr_after_first=None if schedule
                          else lr * 0.5, schedule=schedule)
    for a, b in zip(_weights(net), w_ref):
        np.testing.assert_allclose(a, b, **TOL[mode])
    for a, b in zip(_states(dpt), s_ref):
        np.testing.assert_allclose(a, b, **TOL[mode])
    w_same, _ = _eager(opt, 2)          # had the rate stayed
    assert max(np.abs(a - b).max()
               for a, b in zip(_weights(net), w_same)) > 1e-4
