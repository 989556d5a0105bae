"""SPMD parallel-trainer tests over the 8-virtual-device CPU mesh.

The reference tested dist training without a cluster via
``launch.py --launcher local`` (SURVEY.md §4); the rebuild's analog is a
multi-device mesh in one process, asserting the SPMD step matches
single-device eager training bit-for-bit (same math, same init).
"""
import numpy as np
import pytest

# every test here builds the 8-device virtual mesh — auto-skip on fewer
pytestmark = pytest.mark.needs_mesh(8)

import mxnet_tpu as mx
from mxnet_tpu import nd, parallel
from mxnet_tpu.gluon import nn, Trainer
from mxnet_tpu.gluon.loss import SoftmaxCrossEntropyLoss, L2Loss


def _mlp(seed=7, ctx=None):
    np.random.seed(seed)
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(16, activation="relu", in_units=8),
                nn.Dense(4, in_units=16))
    net.initialize(mx.init.Xavier(), ctx=ctx or mx.cpu(0))
    return net


def test_mesh_lifecycle():
    mesh = parallel.make_mesh({"dp": 4, "tp": 2})
    assert parallel.mesh_shape(mesh) == {"dp": 4, "tp": 2}
    parallel.set_mesh(mesh)
    assert parallel.current_mesh() is mesh
    parallel.set_mesh(None)
    assert parallel.mesh_shape(parallel.current_mesh()) == {"dp": 8}


def test_mesh_too_big():
    with pytest.raises(mx.MXNetError, match="needs 16 devices"):
        parallel.make_mesh({"dp": 16})


@pytest.mark.parametrize("opt_name,opt_args", [
    ("sgd", {"learning_rate": 0.1}),
    ("sgd", {"learning_rate": 0.1, "momentum": 0.9}),
    ("adam", {"learning_rate": 0.01}),
])
def test_dp_trainer_matches_eager(opt_name, opt_args):
    """One fused SPMD step == eager autograd.record + Trainer.step."""
    mesh = parallel.make_mesh({"dp": 8})

    x = np.random.rand(16, 8).astype("float32")
    y = np.random.randint(0, 4, 16).astype("float32")
    loss_fn = SoftmaxCrossEntropyLoss()

    # eager reference
    net_e = _mlp()
    tr = Trainer(net_e.collect_params(), opt_name, dict(opt_args),
                 kvstore=None)
    for _ in range(3):
        with mx.autograd.record():
            l = loss_fn(net_e(nd.array(x)), nd.array(y))
            l = l.mean()
        l.backward()
        tr.step(batch_size=1)  # loss already meaned

    # SPMD
    net_s = _mlp()
    dpt = parallel.DataParallelTrainer(net_s, loss_fn, opt_name,
                                       dict(opt_args), mesh=mesh)
    for _ in range(3):
        loss = dpt.step(nd.array(x), nd.array(y))
    assert np.isfinite(loss.asnumpy()).all()

    for (n1, p1), (n2, p2) in zip(net_e.collect_params().items(),
                                  net_s.collect_params().items()):
        np.testing.assert_allclose(p1.data().asnumpy(),
                                   p2.data().asnumpy(),
                                   rtol=2e-5, atol=1e-5,
                                   err_msg=f"{n1} vs {n2} ({opt_name})")


def test_dp_trainer_batchnorm_aux():
    """BatchNorm running stats update inside the jitted SPMD step."""
    mesh = parallel.make_mesh({"dp": 4})
    np.random.seed(3)
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(8, in_units=4), nn.BatchNorm(axis=1),
                nn.Dense(2, in_units=8))
    net.initialize(ctx=mx.cpu(0))
    dpt = parallel.DataParallelTrainer(net, L2Loss(), "sgd",
                                       {"learning_rate": 0.05}, mesh=mesh)
    x = np.random.rand(8, 4).astype("float32")
    y = np.random.rand(8, 2).astype("float32")
    net(nd.array(x))  # resolve deferred init (inference mode: no mutation)
    params = net.collect_params()
    rm = [p for n, p in params.items() if "running_mean" in n][0]
    before = rm.data().asnumpy().copy()
    dpt.step(nd.array(x), nd.array(y))
    after = rm.data().asnumpy()
    assert not np.allclose(before, after), \
        "running_mean must move under training"


def test_dp_trainer_generic_optimizer_fallback():
    """An optimizer without a fused rule goes down the eager path."""
    mesh = parallel.make_mesh({"dp": 2})
    net = _mlp(seed=11)
    dpt = parallel.DataParallelTrainer(net, L2Loss(), "adagrad",
                                       {"learning_rate": 0.05}, mesh=mesh)
    x = np.random.rand(4, 8).astype("float32")
    y = np.random.rand(4, 4).astype("float32")
    w_before = list(net.collect_params().values())[0].data().asnumpy().copy()
    dpt.step(nd.array(x), nd.array(y))
    w_after = list(net.collect_params().values())[0].data().asnumpy()
    assert not np.allclose(w_before, w_after)


def test_tp_param_sharding():
    """Tensor-parallel param layout via a sharding rule (the capability
    the reference lacked — SURVEY.md §2.3 checklist 'Tensor parallel')."""
    from jax.sharding import PartitionSpec as P
    mesh = parallel.make_mesh({"dp": 2, "tp": 4})

    def rule(name, shape):
        # shard Dense weights' output dim over tp
        if name.endswith("weight") and len(shape) == 2 and \
                shape[0] % 4 == 0:
            return P("tp", None)
        return None

    net = _mlp(seed=13)
    dpt = parallel.DataParallelTrainer(net, L2Loss(), "sgd",
                                       {"learning_rate": 0.1}, mesh=mesh,
                                       param_sharding=rule)
    x = np.random.rand(4, 8).astype("float32")
    y = np.random.rand(4, 4).astype("float32")
    loss = dpt.step(nd.array(x), nd.array(y))
    assert np.isfinite(loss.asnumpy()).all()
    # params stay sharded after the step
    p0 = list(net.collect_params().values())[0].data()
    assert len({d.id for d in p0._data.sharding.device_set}) == 8


def test_quantized_psum_accuracy_and_grad():
    """int8 quantized allreduce: result within quantization error of the
    exact psum; straight-through gradient equals the psum vjp."""
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    from mxnet_tpu import parallel
    sm = shard_map

    mesh = parallel.make_mesh({"dp": 8})
    rng = np.random.RandomState(0)
    shards = rng.randn(8, 256).astype("float32")

    def body(x):
        return parallel.quantized_psum(x[0], "dp")[None]

    f = jax.jit(shard_map(body, mesh=mesh, in_specs=P("dp"),
                          out_specs=P("dp")))
    got = np.asarray(f(jnp.asarray(shards)))[0]
    exact = shards.sum(axis=0)
    # two-stage int8 bound: per-shard chunk quantization + the
    # requantized partial sum (each rounding ≤ scale/2 = absmax/254)
    bound = (sum(np.abs(shards[i]).max() / 254 for i in range(8))
             + np.abs(exact).max() / 254 + 1e-5)
    assert np.abs(got - exact).max() <= bound, (
        np.abs(got - exact).max(), bound)
    # relative accuracy sanity
    assert np.abs(got - exact).max() / np.abs(exact).max() < 0.05

    def loss(x):
        y = sm(body, mesh=mesh, in_specs=P("dp"),
               out_specs=P("dp"))(x)
        return jnp.sum(y * y)

    g = np.asarray(jax.grad(loss)(jnp.asarray(shards)))
    # straight-through == the EXACT psum's gradient (quantization only
    # perturbs the forward value inside the cotangent)
    import jax.lax as lax

    def body_exact(x):
        return lax.psum(x[0], "dp")[None]

    def loss_exact(x):
        y = sm(body_exact, mesh=mesh, in_specs=P("dp"),
               out_specs=P("dp"))(x)
        return jnp.sum(y * y)

    g_exact = np.asarray(jax.grad(loss_exact)(jnp.asarray(shards)))
    assert np.isfinite(g).all()
    # cotangents carry the quantized forward value, so small
    # entries wobble by the quantization error
    np.testing.assert_allclose(g, g_exact, rtol=0.05, atol=1.0)


def test_quantized_psum_rejects_bad_bits():
    import pytest as _pytest
    import jax.numpy as jnp
    from mxnet_tpu import parallel
    with _pytest.raises(mx.MXNetError, match="bits"):
        parallel.quantized_psum(jnp.ones((4,)), "dp", bits=4)


def test_sync_batchnorm_global_stats():
    """SyncBatchNorm semantics come free under SPMD: BN statistics in
    a DataParallelTrainer step reduce over the GLOBAL batch, matching
    the reference's cross-device sync-BN (bit-exact check)."""
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon.contrib.nn import SyncBatchNorm
    from mxnet_tpu.gluon.loss import L2Loss
    np.random.seed(0)
    net = gluon.nn.HybridSequential()
    with net.name_scope():
        net.add(gluon.nn.Dense(4, in_units=3),
                SyncBatchNorm(num_devices=8))
    net.initialize(mx.init.Xavier())
    mesh = parallel.make_mesh({"dp": 8})
    dpt = parallel.DataParallelTrainer(net, L2Loss(), "sgd",
                                       {"learning_rate": 0.0},
                                       mesh=mesh)
    rng = np.random.RandomState(0)
    X = rng.randn(16, 3).astype("f4")
    Y = rng.randn(16, 4).astype("f4")
    dpt.step(nd.array(X), nd.array(Y))
    bn = net[1]
    W = net[0].weight.data().asnumpy()
    b = net[0].bias.data().asnumpy()
    want = 0.1 * (X @ W.T + b).mean(axis=0)   # global-batch mean
    np.testing.assert_allclose(bn.running_mean.data().asnumpy(), want,
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("opt_name,opt_args", [
    ("sgd", {"learning_rate": 0.05, "momentum": 0.9}),
    ("adam", {"learning_rate": 1e-3}),
    ("adamw", {"learning_rate": 1e-3, "wd": 0.01}),
    ("adagrad", {"learning_rate": 0.05}),
])
def test_fuse_step_matches_two_phase(opt_name, opt_args):
    """fuse_step=True (one program: fwd+bwd+update, donated states)
    must be numerically identical to the two-phase trainer."""
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon.loss import SoftmaxCrossEntropyLoss

    rng = np.random.RandomState(0)
    X = rng.randn(8, 6).astype("f4")
    Y = rng.randint(0, 3, 8).astype("f4")

    def run(fuse):
        np.random.seed(0)
        mx.random.seed(0)
        net = gluon.nn.HybridSequential()
        with net.name_scope():
            net.add(gluon.nn.Dense(16, activation="relu", in_units=6),
                    gluon.nn.Dense(3, in_units=16))
        net.initialize(mx.init.Xavier())
        mesh = parallel.make_mesh({"dp": 4})
        dpt = parallel.DataParallelTrainer(
            net, SoftmaxCrossEntropyLoss(), opt_name, dict(opt_args),
            mesh=mesh, fuse_step=fuse)
        losses = [float(dpt.step(nd.array(X), nd.array(Y)).asnumpy())
                  for _ in range(5)]
        w = net[0].weight.data().asnumpy()
        return losses, w

    l_fused, w_fused = run(True)
    l_two, w_two = run(False)
    np.testing.assert_allclose(l_fused, l_two, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(w_fused, w_two, rtol=1e-5, atol=1e-6)


def test_fuse_step_with_tensor_parallel_rule():
    """fuse_step under a TP param-sharding rule: losses match the
    two-phase TP run and the weight sharding stays pinned."""
    import jax
    from jax.sharding import PartitionSpec as P
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon.loss import SoftmaxCrossEntropyLoss

    rng = np.random.RandomState(1)
    X = rng.randn(8, 6).astype("f4")
    Y = rng.randint(0, 3, 8).astype("f4")

    def rule(name, shape):
        if name.endswith("dense0_weight"):
            return P("tp", None)
        return None

    def run(fuse):
        np.random.seed(0)
        mx.random.seed(0)
        net = gluon.nn.HybridSequential()
        with net.name_scope():
            net.add(gluon.nn.Dense(8, activation="relu", in_units=6),
                    gluon.nn.Dense(3, in_units=8))
        net.initialize(mx.init.Xavier())
        mesh = parallel.make_mesh({"dp": 2, "tp": 2})
        dpt = parallel.DataParallelTrainer(
            net, SoftmaxCrossEntropyLoss(), "adam",
            {"learning_rate": 1e-3}, mesh=mesh, param_sharding=rule,
            fuse_step=fuse)
        losses = [float(dpt.step(nd.array(X), nd.array(Y)).asnumpy())
                  for _ in range(4)]
        sharding = net[0].weight.data()._data.sharding
        return losses, sharding

    lf, sf = run(True)
    lt, st = run(False)
    np.testing.assert_allclose(lf, lt, rtol=1e-5, atol=1e-6)
    assert "tp" in str(sf.spec), sf  # weights stayed TP-sharded


def test_fuse_step_failure_poisons_donated_state():
    """donate_argnums hands the optimizer state to the executable; if
    the fused call fails mid-flight the trainer must refuse to keep
    stepping on invalid buffers with a clear error (ADVICE r2)."""
    from mxnet_tpu.base import MXNetError
    from mxnet_tpu import gluon

    rng = np.random.RandomState(0)
    X, Y = rng.randn(8, 6).astype("f4"), \
        rng.randint(0, 3, 8).astype("f4")
    net = gluon.nn.HybridSequential()
    with net.name_scope():
        net.add(gluon.nn.Dense(8, activation="relu", in_units=6),
                gluon.nn.Dense(3, in_units=8))
    net.initialize(mx.init.Xavier())
    mesh = parallel.make_mesh({"dp": 4})
    dpt = parallel.DataParallelTrainer(
        net, SoftmaxCrossEntropyLoss(), "adam",
        {"learning_rate": 1e-3}, mesh=mesh, fuse_step=True)
    dpt.step(nd.array(X), nd.array(Y))   # healthy step builds the jit

    # the seam is the resolved executable ({aval sig: executable}); a
    # fake installed as the jitted step itself would have to survive an
    # AOT lower(), and that failure is raised, not demoted
    execs = dpt._full_exec[0]
    real = dict(execs)

    # a PRE-dispatch failure leaves the donated buffers alive (the CPU
    # backend never consumes them) and must NOT brick the trainer.
    # (Not TypeError: that is the aval-drift signal the dispatch
    # absorbs by demoting to the jit path.)
    def pre_dispatch_boom(*a, **k):
        raise ValueError("bad argument binding")

    execs.update(dict.fromkeys(real, pre_dispatch_boom))
    with pytest.raises(ValueError):
        dpt.step(nd.array(X), nd.array(Y))
    execs.update(real)
    dpt.step(nd.array(X), nd.array(Y))   # still healthy

    # a failure after the executable CONSUMED the donated state (we
    # simulate consumption by deleting the buffers, which is what
    # donation does on TPU) poisons the trainer
    def post_dispatch_boom(params, states, *a, **k):
        for vals in states:
            for v in vals:
                v.delete()
        raise RuntimeError("transient device error")

    execs.update(dict.fromkeys(real, post_dispatch_boom))
    with pytest.raises(MXNetError, match="donated"):
        dpt.step(nd.array(X), nd.array(Y))
    # the trainer is now invalid and says so — even though the next
    # call would not itself fail
    with pytest.raises(MXNetError, match="no longer valid"):
        dpt.step(nd.array(X), nd.array(Y))


class TestGradientCompressionInTrainer:
    """VERDICT r2 next #3: compression wired into the REAL training
    path — the fused SPMD step exchanges gradients over an int8 wire."""

    def _run(self, compression, steps=15, lr=5e-3):
        from mxnet_tpu import gluon
        rng = np.random.RandomState(0)
        X = rng.randn(16, 6).astype("f4")
        Y = rng.randint(0, 3, 16).astype("f4")
        np.random.seed(0)
        mx.random.seed(0)
        net = gluon.nn.HybridSequential()
        with net.name_scope():
            net.add(gluon.nn.Dense(16, activation="relu", in_units=6),
                    gluon.nn.Dense(3, in_units=16))
        net.initialize(mx.init.Xavier())
        dpt = parallel.DataParallelTrainer(
            net, SoftmaxCrossEntropyLoss(), "adam",
            {"learning_rate": lr}, mesh=parallel.make_mesh({"dp": 8}),
            fuse_step=True, compression=compression)
        losses = [float(dpt.step(nd.array(X), nd.array(Y)).asnumpy())
                  for _ in range(steps)]
        return losses, dpt

    def test_int8_convergence_parity(self):
        base, _ = self._run(None)
        comp, _ = self._run({"type": "int8"})
        assert comp[-1] < comp[0]
        # int8 chunk-scaled quantization tracks the fp32 curve closely
        assert abs(comp[-1] - base[-1]) / base[-1] < 0.05, (comp, base)

    def test_2bit_converges_with_error_feedback(self):
        comp, dpt = self._run({"type": "2bit", "threshold": 0.05})
        assert comp[-1] < comp[0], comp
        # error-feedback residuals are carried and non-trivial
        assert dpt._residual_vals is not None
        r = np.asarray(dpt._residual_vals[0])
        assert r.shape[0] == 8 and np.abs(r).max() > 0

    def test_compression_rejects_tp_and_two_phase(self):
        from mxnet_tpu.base import MXNetError
        from mxnet_tpu import gluon
        net = gluon.nn.Dense(3, in_units=6)
        net.initialize(mx.init.Xavier())
        with pytest.raises(MXNetError, match="tensor-parallel"):
            parallel.DataParallelTrainer(
                net, SoftmaxCrossEntropyLoss(), "sgd",
                {"learning_rate": 0.1},
                mesh=parallel.make_mesh({"dp": 8}), fuse_step=True,
                param_sharding=lambda n, s: None,
                compression={"type": "int8"})
        with pytest.raises(MXNetError, match="fuse_step"):
            parallel.DataParallelTrainer(
                net, SoftmaxCrossEntropyLoss(), "sgd",
                {"learning_rate": 0.1},
                mesh=parallel.make_mesh({"dp": 8}),
                compression={"type": "int8"})

    def test_wire_dtype_is_int8(self):
        """The collectives that cross the dp axis carry i8 tensors —
        checked in the lowered program, not inferred from numerics."""
        import jax
        import jax.numpy as jnp
        from jax import shard_map
        from jax.sharding import PartitionSpec as P
        from mxnet_tpu.parallel import collectives

        mesh = parallel.make_mesh({"dp": 8})

        f2 = jax.jit(shard_map(
            lambda x: collectives.twobit_psum(x, "dp",
                                              threshold=0.1)[0],
            mesh=mesh, in_specs=P("dp"), out_specs=P(),
            check_vma=False))
        txt = f2.lower(jnp.ones((8, 64), jnp.float32)).as_text()
        # two-phase: all_to_all of ternary codes, all_gather of narrow
        # partial sums — both int8 lanes
        assert "all_to_all" in txt and "all_gather" in txt \
            and "i8" in txt, txt[:500]

        fq = jax.jit(shard_map(
            lambda x: collectives.quantized_psum(x, "dp"),
            mesh=mesh, in_specs=P("dp"), out_specs=P(),
            check_vma=False))
        txt = fq.lower(jnp.ones((8, 64), jnp.float32)).as_text()
        assert "all_to_all" in txt and "i8" in txt, txt[:500]


def test_step_placement_cache_bounded_and_correct():
    """The input-placement cache must serve reused batch NDArrays on a
    multi-device mesh (the crash path for naive weak-keying: NDArray
    __eq__ is elementwise) and stay bounded across distinct batches."""
    from mxnet_tpu import gluon
    net = gluon.nn.Dense(3, in_units=6)
    net.initialize(mx.init.Xavier())
    dpt = parallel.DataParallelTrainer(
        net, L2Loss(), "sgd", {"learning_rate": 0.01},
        mesh=parallel.make_mesh({"dp": 4}), fuse_step=True)
    rng = np.random.RandomState(0)
    x = nd.array(rng.randn(8, 6).astype("f4"))
    y = nd.array(rng.randn(8, 3).astype("f4"))
    sh = x._data.sharding
    for _ in range(4):                      # reuse: hits the cache
        l1 = float(dpt.step(x, y).asnumpy())
    assert np.isfinite(l1)
    assert x._data.sharding == sh           # caller never mutated
    for i in range(6):                      # distinct batches
        dpt.step(nd.array(rng.randn(8, 6).astype("f4")),
                 nd.array(rng.randn(8, 3).astype("f4")))
    assert len(dpt._placed) <= 2            # bounded to current inputs


class TestStepMulti:
    """step_multi: K scanned fused steps == K individual step() calls
    (same RNG stream, same optimizer-scalar schedule)."""

    def _mk(self, seed=0):
        import mxnet_tpu as mx
        from mxnet_tpu import gluon, nd, parallel
        from mxnet_tpu.gluon import nn
        mx.random.seed(seed)
        np.random.seed(seed)
        net = nn.HybridSequential()
        with net.name_scope():
            net.add(nn.Dense(16, activation="relu", in_units=8),
                    nn.Dense(1, in_units=16))
        net.initialize(mx.init.Xavier())
        net.hybridize()
        L = gluon.loss.L2Loss()
        mesh = parallel.make_mesh({"dp": 8})
        tr = parallel.DataParallelTrainer(
            net, lambda o, l: L(o, l).mean(), "adam",
            {"learning_rate": 0.05}, mesh=mesh, fuse_step=True)
        return net, tr

    def test_matches_sequential_steps(self):
        import mxnet_tpu as mx
        from mxnet_tpu import nd
        rng = np.random.RandomState(0)
        K, B = 4, 16
        Xk = rng.randn(K, B, 8).astype("f4")
        Yk = (Xk[..., :1] * 0.5 + 0.1).astype("f4")

        net_a, tr_a = self._mk(seed=3)
        seq_losses = []
        for k in range(K):
            seq_losses.append(float(tr_a.step(
                (nd.array(Xk[k]),), nd.array(Yk[k])).asnumpy()))

        net_b, tr_b = self._mk(seed=3)
        multi = tr_b.step_multi((nd.array(Xk),), nd.array(Yk))
        np.testing.assert_allclose(multi.asnumpy(),
                                   np.asarray(seq_losses),
                                   rtol=1e-5, atol=1e-6)
        for (ka, pa), (kb, pb) in zip(
                sorted(net_a.collect_params().items()),
                sorted(net_b.collect_params().items())):
            np.testing.assert_allclose(pa.data().asnumpy(),
                                       pb.data().asnumpy(),
                                       rtol=1e-4, atol=1e-6, err_msg=ka)

    def test_repeat_matches_sequential_steps_same_batch(self):
        """repeat=K scans one batch K times — identical to K step()
        calls on it, with no (K, B, ...) host broadcast materialized
        (the path chip_smoke.py runs on the chip)."""
        from mxnet_tpu import nd
        rng = np.random.RandomState(2)
        K, B = 3, 16
        X = rng.randn(B, 8).astype("f4")
        Y = (X[..., :1] * 0.5 + 0.1).astype("f4")

        net_a, tr_a = self._mk(seed=7)
        seq_losses = [float(tr_a.step((nd.array(X),),
                                      nd.array(Y)).asnumpy())
                      for _ in range(K)]

        net_b, tr_b = self._mk(seed=7)
        multi = tr_b.step_multi((nd.array(X),), nd.array(Y), repeat=K)
        assert multi.shape == (K,)
        np.testing.assert_allclose(multi.asnumpy(),
                                   np.asarray(seq_losses),
                                   rtol=1e-5, atol=1e-6)
        for (ka, pa), (kb, pb) in zip(
                sorted(net_a.collect_params().items()),
                sorted(net_b.collect_params().items())):
            np.testing.assert_allclose(pa.data().asnumpy(),
                                       pb.data().asnumpy(),
                                       rtol=1e-4, atol=1e-6, err_msg=ka)

    def test_multi_then_single_continues(self):
        from mxnet_tpu import nd
        rng = np.random.RandomState(1)
        net, tr = self._mk(seed=5)
        Xk = rng.randn(3, 16, 8).astype("f4")
        Yk = (Xk[..., :1]).astype("f4")
        l0 = tr.step_multi((nd.array(Xk),), nd.array(Yk))
        assert l0.shape == (3,)
        l1 = tr.step((nd.array(Xk[0]),), nd.array(Yk[0]))
        assert np.isfinite(float(l1.asnumpy()))
        # losses trend down across the combined sequence
        l2 = tr.step_multi((nd.array(Xk),), nd.array(Yk))
        assert float(l2.asnumpy()[-1]) < float(l0.asnumpy()[0])

    def test_requires_fused(self):
        import pytest
        import mxnet_tpu as mx
        from mxnet_tpu import gluon, nd, parallel
        from mxnet_tpu.base import MXNetError
        from mxnet_tpu.gluon import nn
        net = nn.Dense(1, in_units=4)
        net.initialize(mx.init.Xavier())
        L = gluon.loss.L2Loss()
        mesh = parallel.make_mesh({"dp": 8})
        tr = parallel.DataParallelTrainer(
            net, lambda o, l: L(o, l).mean(), "adam",
            {"learning_rate": 0.01}, mesh=mesh, fuse_step=False)
        with pytest.raises(MXNetError):
            tr.step_multi((nd.zeros((2, 8, 4)),), nd.zeros((2, 8, 1)))


class TestVocabParallelCE:
    """Megatron-style vocab-parallel cross-entropy: the tp-sharded LM
    head's loss without ever materializing full logits on any device."""

    def test_matches_single_device_and_grads(self):
        import jax
        import jax.numpy as jnp
        from jax import shard_map
        from jax.sharding import PartitionSpec as P
        from mxnet_tpu.parallel import collectives

        mesh = parallel.make_mesh({"tp": 8})
        rng = np.random.RandomState(0)
        n, u, v = 16, 12, 64                     # v/tp = 8 rows/rank
        h = jnp.asarray(rng.randn(n, u).astype("f4"))
        w = jnp.asarray(rng.randn(v, u).astype("f4") * 0.3)
        lbl = jnp.asarray(rng.randint(0, v, (n,)).astype("f4"))

        def sharded_loss(h, w, lbl):
            return shard_map(
                lambda h_, w_, l_: collectives.vocab_parallel_softmax_ce(
                    h_, w_, l_, "tp"),
                mesh=mesh, in_specs=(P(), P("tp", None), P()),
                out_specs=P(), check_vma=False)(h, w, lbl).mean()

        def ref_loss(h, w, lbl):
            logits = h @ w.T
            lp = jax.nn.log_softmax(logits, axis=-1)
            return -jnp.take_along_axis(
                lp, lbl.astype("int32")[:, None], 1).mean()

        got = float(jax.jit(sharded_loss)(h, w, lbl))
        want = float(ref_loss(h, w, lbl))
        np.testing.assert_allclose(got, want, rtol=1e-5)

        gh, gw = jax.jit(jax.grad(sharded_loss, argnums=(0, 1)))(
            h, w, lbl)
        rh, rw = jax.grad(ref_loss, argnums=(0, 1))(h, w, lbl)
        np.testing.assert_allclose(np.asarray(gh), np.asarray(rh),
                                   rtol=2e-4, atol=1e-6)
        np.testing.assert_allclose(np.asarray(gw), np.asarray(rw),
                                   rtol=2e-4, atol=1e-6)

    def test_unified_entry_parity_matrix(self):
        """VERDICT r4 #4: ONE entry point (`chunked_softmax_ce`) whose
        {1-dev, tp=2} × {chunked, full} variants all agree with the
        full-softmax reference — values AND grads (dH, dW)."""
        import jax
        import jax.numpy as jnp
        from jax import shard_map
        from jax.sharding import PartitionSpec as P
        from mxnet_tpu.ops.nn import chunked_softmax_ce
        from mxnet_tpu.parallel import collectives

        mesh = parallel.make_mesh({"tp": 2})
        rng = np.random.RandomState(1)
        n, u, v = 16, 12, 64
        h = jnp.asarray(rng.randn(n, u).astype("f4"))
        w = jnp.asarray(rng.randn(v, u).astype("f4") * 0.3)
        lbl = jnp.asarray(rng.randint(0, v, (n,)).astype("f4"))

        def ref_loss(h, w, lbl):
            lp = jax.nn.log_softmax(h @ w.T, axis=-1)
            return -jnp.take_along_axis(
                lp, lbl.astype("int32")[:, None], 1).mean()

        def tp_loss(chunk):
            def fn(h, w, lbl):
                return shard_map(
                    lambda h_, w_, l_: chunked_softmax_ce(
                        h_, w_, l_, chunk=chunk, axis_name="tp"),
                    mesh=mesh, in_specs=(P(), P("tp", None), P()),
                    out_specs=P(), check_vma=False)(h, w, lbl).mean()
            return fn

        variants = {
            "1dev_chunked": lambda h, w, l: chunked_softmax_ce(
                h, w, l, chunk=8).mean(),
            "1dev_full": lambda h, w, l: chunked_softmax_ce(
                h, w, l, chunk=v).mean(),
            "tp2_chunked": tp_loss(8),       # multi-slab inside shard
            "tp2_full": tp_loss(v),          # single local slab
            "tp2_via_vocab_parallel": lambda h, w, l: shard_map(
                lambda h_, w_, l_:
                collectives.vocab_parallel_softmax_ce(
                    h_, w_, l_, "tp", chunk=8),
                mesh=mesh, in_specs=(P(), P("tp", None), P()),
                out_specs=P(), check_vma=False)(h, w, l).mean(),
        }
        want = float(ref_loss(h, w, lbl))
        rh, rw = jax.grad(ref_loss, argnums=(0, 1))(h, w, lbl)
        for name, fn in variants.items():
            got = float(jax.jit(fn)(h, w, lbl))
            np.testing.assert_allclose(got, want, rtol=1e-5,
                                       err_msg=name)
            gh, gw = jax.jit(jax.grad(fn, argnums=(0, 1)))(h, w, lbl)
            np.testing.assert_allclose(np.asarray(gh), np.asarray(rh),
                                       rtol=2e-4, atol=1e-6,
                                       err_msg=name)
            np.testing.assert_allclose(np.asarray(gw), np.asarray(rw),
                                       rtol=2e-4, atol=1e-6,
                                       err_msg=name)

    def test_chunked_ce_bias_parity(self):
        """The bias variant (BERT-style tied decode h@Wᵀ+b): values
        and grads — INCLUDING dBias — match the full-logits reference
        across {1dev chunked, 1dev single-slab, tp=2 with the bias
        sharded alongside the vocab rows}."""
        import jax
        import jax.numpy as jnp
        from jax import shard_map
        from jax.sharding import PartitionSpec as P
        from mxnet_tpu.ops.nn import chunked_softmax_ce_bias

        mesh = parallel.make_mesh({"tp": 2})
        rng = np.random.RandomState(3)
        n, u, v = 16, 12, 64
        h = jnp.asarray(rng.randn(n, u).astype("f4"))
        w = jnp.asarray(rng.randn(v, u).astype("f4") * 0.3)
        b = jnp.asarray(rng.randn(v).astype("f4") * 0.5)
        lbl = jnp.asarray(rng.randint(0, v, (n,)).astype("f4"))

        def ref_loss(h, w, b, lbl):
            lp = jax.nn.log_softmax(h @ w.T + b[None, :], axis=-1)
            return -jnp.take_along_axis(
                lp, lbl.astype("int32")[:, None], 1).mean()

        variants = {
            "1dev_chunked": lambda h, w, b, l: chunked_softmax_ce_bias(
                h, w, b, l, chunk=8).mean(),
            "1dev_full": lambda h, w, b, l: chunked_softmax_ce_bias(
                h, w, b, l, chunk=v).mean(),
            "tp2_chunked": lambda h, w, b, l: shard_map(
                lambda h_, w_, b_, l_: chunked_softmax_ce_bias(
                    h_, w_, b_, l_, chunk=8, axis_name="tp"),
                mesh=mesh,
                in_specs=(P(), P("tp", None), P("tp"), P()),
                out_specs=P(), check_vma=False)(h, w, b, l).mean(),
        }
        want = float(ref_loss(h, w, b, lbl))
        rh, rw, rb = jax.grad(ref_loss, argnums=(0, 1, 2))(h, w, b, lbl)
        for name, fn in variants.items():
            got = float(jax.jit(fn)(h, w, b, lbl))
            np.testing.assert_allclose(got, want, rtol=1e-5,
                                       err_msg=name)
            gh, gw, gb = jax.jit(
                jax.grad(fn, argnums=(0, 1, 2)))(h, w, b, lbl)
            for g, r in ((gh, rh), (gw, rw), (gb, rb)):
                np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                           rtol=2e-4, atol=1e-6,
                                           err_msg=name)

    def test_chunked_ce_bias_ndarray_op(self):
        """The registered 4-input op drives the same math through the
        NDArray tape (gradients to hidden, weight, AND bias)."""
        from mxnet_tpu import nd, autograd
        import jax
        import jax.numpy as jnp

        rng = np.random.RandomState(4)
        n, u, v = 8, 6, 32
        h0 = rng.randn(n, u).astype("f4")
        w0 = (rng.randn(v, u) * 0.3).astype("f4")
        b0 = (rng.randn(v) * 0.5).astype("f4")
        l0 = rng.randint(0, v, (n,)).astype("f4")
        h, w, b = nd.array(h0), nd.array(w0), nd.array(b0)
        for x in (h, w, b):
            x.attach_grad()
        with autograd.record():
            loss = nd.chunked_softmax_ce_bias(
                h, w, b, nd.array(l0), chunk=8).mean()
        loss.backward()

        def ref(h, w, b):
            lp = jax.nn.log_softmax(h @ w.T + b[None, :], axis=-1)
            return -jnp.take_along_axis(
                lp, jnp.asarray(l0.astype("i4"))[:, None], 1).mean()
        rh, rw, rb = jax.grad(ref, argnums=(0, 1, 2))(
            jnp.asarray(h0), jnp.asarray(w0), jnp.asarray(b0))
        np.testing.assert_allclose(h.grad.asnumpy(), np.asarray(rh),
                                   rtol=2e-4, atol=1e-6)
        np.testing.assert_allclose(w.grad.asnumpy(), np.asarray(rw),
                                   rtol=2e-4, atol=1e-6)
        np.testing.assert_allclose(b.grad.asnumpy(), np.asarray(rb),
                                   rtol=2e-4, atol=1e-6)

    def test_unified_tp_chunked_no_full_logits(self):
        """tp × chunked keeps BOTH bounds: no (N, V) and no
        (N, V/tp) tensor in the lowered HLO — only (N, chunk) slabs."""
        import jax
        import jax.numpy as jnp
        from jax import shard_map
        from jax.sharding import PartitionSpec as P
        from mxnet_tpu.ops.nn import chunked_softmax_ce

        mesh = parallel.make_mesh({"tp": 2})
        # n deliberately != v/(tp*chunk): the positive (n, chunk)
        # assertion below must pin the LOGITS slab, not coincidentally
        # match the (n_chunks, chunk, u) weight reshape
        n, u, v, chunk = 7, 4, 4096, 256
        h = jnp.ones((n, u), jnp.float32)
        w = jnp.ones((v, u), jnp.float32)
        lbl = jnp.zeros((n,), jnp.float32)
        fn = jax.jit(shard_map(
            lambda h_, w_, l_: chunked_softmax_ce(
                h_, w_, l_, chunk=chunk, axis_name="tp"),
            mesh=mesh, in_specs=(P(), P("tp", None), P()),
            out_specs=P(), check_vma=False))
        txt = fn.lower(h, w, lbl).as_text()
        assert f"{n}x{v}" not in txt, "full logits materialized"
        assert f"{n}x{v // 2}" not in txt, "full LOCAL slab materialized"
        assert f"{n}x{chunk}" in txt     # the streamed slab exists

    def test_no_full_logits_anywhere(self):
        """The lowered program must not contain an (N, V) f32 tensor —
        the whole point of the vocab split."""
        import jax
        import jax.numpy as jnp
        from jax import shard_map
        from jax.sharding import PartitionSpec as P
        from mxnet_tpu.parallel import collectives

        mesh = parallel.make_mesh({"tp": 8})
        n, u, v = 8, 4, 4096
        h = jnp.ones((n, u), jnp.float32)
        w = jnp.ones((v, u), jnp.float32)
        lbl = jnp.zeros((n,), jnp.float32)
        fn = jax.jit(shard_map(
            lambda h_, w_, l_: collectives.vocab_parallel_softmax_ce(
                h_, w_, l_, "tp"),
            mesh=mesh, in_specs=(P(), P("tp", None), P()),
            out_specs=P(), check_vma=False))
        txt = fn.lower(h, w, lbl).as_text()
        assert f"{n}x{v}" not in txt, "full logits materialized"
        assert f"{n}x{v // 8}" in txt       # the local slab exists


class TestShardedWeightUpdate:
    """ZeRO-1 cross-replica weight-update sharding (PAPERS.md arXiv
    2004.13336): optimizer state 1/N per dp member, gradients
    reduce-scattered, updated weight slices all-gathered — numerics
    EXACTLY the replicated path."""

    def _run(self, n_params_shape, dp=4, steps=3):
        import jax
        import jax.numpy as jnp
        from jax import shard_map
        from jax.sharding import PartitionSpec as P
        from mxnet_tpu.parallel import collectives as C

        mesh = parallel.make_mesh({"dp": dp})
        rng = np.random.RandomState(7)
        p0 = rng.randn(*n_params_shape).astype("f4")
        # per-member local grads (dp members hold DIFFERENT data)
        gs = rng.randn(dp, *n_params_shape).astype("f4")
        lr, b1, b2, eps = 0.1, 0.9, 0.999, 1e-8

        def adam_slice(p, g, m, v):
            m2 = b1 * m + (1 - b1) * g
            v2 = b2 * v + (1 - b2) * g * g
            return p - lr * m2 / (jnp.sqrt(v2) + eps), (m2, v2)

        def member(p, g_loc, m, v):
            # state slices arrive with the sharded leading dp axis
            # (1, chunk) — strip it for the flat-slice contract
            new_p, (m2, v2) = C.sharded_weight_update(
                p, g_loc, (m[0], v[0]), adam_slice, "dp")
            return new_p, m2[None], v2[None]

        m0, v0 = C.sharded_update_state_init(p0, 2, dp)
        size = p0.size
        assert m0.shape[0] == dp          # global (N, chunk) layout
        chunk = m0.shape[1]
        # state slices enter/leave with an explicit leading dp axis —
        # the init helper's global shape round-trips across steps
        fn = jax.jit(shard_map(
            member, mesh=mesh,
            in_specs=(P(), P("dp", *[None] * p0.ndim),
                      P("dp"), P("dp")),
            out_specs=(P(), P("dp"), P("dp")),
            check_vma=False))

        p = jnp.asarray(p0)
        mm = jnp.asarray(m0)
        vv = jnp.asarray(v0)
        # replicated reference: full adam on the SUMMED grad
        rp = jnp.asarray(p0).reshape(-1).astype(jnp.float32)
        rm = jnp.zeros_like(rp)
        rv = jnp.zeros_like(rp)
        gsum = jnp.asarray(gs.sum(0)).reshape(-1)
        for _ in range(steps):
            p, mm, vv = fn(p, jnp.asarray(gs), mm, vv)
            rp, (rm, rv) = adam_slice(rp, gsum, rm, rv)
        np.testing.assert_allclose(
            np.asarray(p).reshape(-1),
            np.asarray(rp)[:size].astype("f4"), rtol=1e-6, atol=1e-7)
        # optimizer memory really is 1/N per member
        assert chunk == (size + (-size) % dp) // dp
        return fn, (jnp.asarray(p0), jnp.asarray(gs), mm, vv)

    def test_parity_even_size(self):
        self._run((8, 16), dp=4)       # 128 divides evenly

    def test_parity_padded_size(self):
        self._run((7, 9), dp=4)        # 63 pads to 64

    def test_wire_is_reduce_scatter_plus_all_gather(self):
        """The lowered program must carry the paper's wire pattern —
        a reduce-scatter for gradients and an all-gather for updated
        weights — NOT a full psum of gradients."""
        fn, args = self._run((8, 16), dp=4, steps=1)
        txt = fn.lower(*args).as_text()
        assert "reduce_scatter" in txt, "gradient wire is not RS"
        assert "all_gather" in txt, "updated weights not gathered"

    def test_bf16_param_gathers_bf16(self):
        """The weight all-gather ships the PARAM dtype: an f32 gather
        of bf16 params would double the wire bytes of that half."""
        import jax
        import jax.numpy as jnp
        import re
        from jax import shard_map
        from jax.sharding import PartitionSpec as P
        from mxnet_tpu.parallel import collectives as C

        mesh = parallel.make_mesh({"dp": 4})

        def member(p, g):
            new_p, _ = C.sharded_weight_update(
                p, g, (), lambda ps, gs: (ps - 0.1 * gs, ()), "dp")
            return new_p

        fn = jax.jit(shard_map(
            member, mesh=mesh, in_specs=(P(), P("dp", None, None)),
            out_specs=P(), check_vma=False))
        p = jnp.zeros((8, 16), jnp.bfloat16)
        g = jnp.zeros((4, 8, 16), jnp.float32)
        txt = fn.lower(p, g).as_text()
        gathers = re.findall(r"all_gather[^\n]*", txt)
        assert gathers and all("bf16" in ln for ln in gathers), gathers
