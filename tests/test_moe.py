"""Mixture-of-experts + expert parallelism (beyond-reference capability;
SURVEY.md §2.3 parallelism checklist lists MoE/ep as absent upstream —
built here as a first-class ``ep`` mesh axis)."""
import numpy as np
import pytest

# every test here builds the 8-device virtual mesh — auto-skip on fewer
pytestmark = pytest.mark.needs_mesh(8)

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, nd
from mxnet_tpu.gluon.contrib.nn import MoEFFN


def _numpy_expert_ffn(x, w1, b1, w2, b2):
    h = np.maximum(x @ w1 + b1, 0.0)
    return h @ w2 + b2


def test_moe_matches_dense_oracle_no_drops():
    """k=1 with generous capacity: every token goes to its argmax
    expert; output must equal gate_prob * expert_ffn(token)."""
    rng = np.random.RandomState(0)
    t, d, h, e = 10, 6, 12, 3
    x = rng.randn(t, d).astype("float32")
    gate_w = rng.randn(d, e).astype("float32")
    w1 = rng.randn(e, d, h).astype("float32") * 0.3
    b1 = rng.randn(e, h).astype("float32") * 0.1
    w2 = rng.randn(e, h, d).astype("float32") * 0.3
    b2 = rng.randn(e, d).astype("float32") * 0.1

    out, aux = nd._contrib_MoEFFN(
        nd.array(x), nd.array(gate_w), nd.array(w1), nd.array(b1),
        nd.array(w2), nd.array(b2), num_experts=e, k=1,
        capacity_factor=float(e) * 2)
    got = out.asnumpy()

    logits = x @ gate_w
    probs = np.exp(logits) / np.exp(logits).sum(-1, keepdims=True)
    want = np.zeros_like(x)
    for i in range(t):
        ei = logits[i].argmax()
        want[i] = probs[i, ei] * _numpy_expert_ffn(
            x[i], w1[ei], b1[ei], w2[ei], b2[ei])
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    assert float(aux.asnumpy()) > 0


def test_moe_capacity_drops_tokens():
    """Tiny capacity: overflow tokens contribute zero output."""
    rng = np.random.RandomState(1)
    t, d, e = 8, 4, 2
    x = rng.randn(t, d).astype("float32")
    # gate forcing everyone onto expert 0
    gate_w = np.zeros((d, e), "float32")
    gate_w[:, 0] = 10.0
    w1 = np.ones((e, d, 4), "float32")
    b1 = np.zeros((e, 4), "float32")
    w2 = np.ones((e, 4, d), "float32")
    b2 = np.zeros((e, d), "float32")
    out, _ = nd._contrib_MoEFFN(
        nd.array(np.abs(x)), nd.array(gate_w * 0 + gate_w),
        nd.array(w1), nd.array(b1), nd.array(w2), nd.array(b2),
        num_experts=e, k=1, capacity_factor=0.5)  # capacity = 2
    got = out.asnumpy()
    nonzero_rows = (np.abs(got).sum(axis=1) > 1e-6).sum()
    assert nonzero_rows == 2, nonzero_rows  # only capacity tokens kept


def test_moe_k2_uses_two_experts():
    rng = np.random.RandomState(2)
    t, d, e = 6, 4, 4
    x = rng.randn(t, d).astype("float32")
    gate_w = rng.randn(d, e).astype("float32")
    w1 = rng.randn(e, d, 8).astype("float32") * 0.3
    b1 = np.zeros((e, 8), "float32")
    w2 = rng.randn(e, 8, d).astype("float32") * 0.3
    b2 = np.zeros((e, d), "float32")
    args = [nd.array(a) for a in (x, gate_w, w1, b1, w2, b2)]
    out1, _ = nd._contrib_MoEFFN(*args, num_experts=e, k=1,
                                 capacity_factor=8.0)
    out2, _ = nd._contrib_MoEFFN(*args, num_experts=e, k=2,
                                 capacity_factor=8.0)
    # second expert adds signal: outputs must differ
    assert np.abs(out1.asnumpy() - out2.asnumpy()).max() > 1e-4


def test_moe_block_trains():
    class Net(gluon.HybridBlock):
        def __init__(self, **kw):
            super().__init__(**kw)
            with self.name_scope():
                self.moe = MoEFFN(8, 16, num_experts=4, k=2,
                                  capacity_factor=4.0)
                self.head = gluon.nn.Dense(3, flatten=False)

        def hybrid_forward(self, F, x):
            out, aux = self.moe(x)
            self._aux = aux
            return self.head(out)

    net = Net()
    net.initialize(mx.init.Xavier())
    tr = gluon.Trainer(net.collect_params(), "adam",
                       {"learning_rate": 5e-3})
    sce = gluon.loss.SoftmaxCrossEntropyLoss()
    rng = np.random.RandomState(3)
    X = nd.array(rng.randn(8, 5, 8).astype("f4"))
    Y = nd.array(rng.randint(0, 3, (8, 5)).astype("f4"))
    losses = []
    for _ in range(30):
        with autograd.record():
            logits = net(X)
            loss = nd.mean(sce(logits.reshape((-1, 3)),
                               Y.reshape(-1))) + 0.01 * net._aux
        loss.backward()
        tr.step(8)
        losses.append(float(loss.asnumpy()))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0] - 0.1, (losses[0], losses[-1])


def test_moe_expert_parallel_matches_single_device():
    """ep-sharded trainer step == single-device numerics: expert
    weights shard over the ep axis, GSPMD handles dispatch."""
    from mxnet_tpu import parallel
    from mxnet_tpu.gluon.loss import SoftmaxCrossEntropyLoss

    class Net(gluon.HybridBlock):
        def __init__(self, **kw):
            super().__init__(**kw)
            with self.name_scope():
                self.moe = MoEFFN(8, 16, num_experts=4, k=1,
                                  capacity_factor=8.0)
                self.head = gluon.nn.Dense(3, flatten=False)

        def hybrid_forward(self, F, x):
            out, aux = self.moe(x)
            return self.head(out)

    rng = np.random.RandomState(4)
    X = rng.randn(4, 6, 8).astype("f4")
    Y = rng.randint(0, 3, (4, 6)).astype("f4")

    def run(mesh, rule):
        np.random.seed(0)  # initializers draw from the numpy global rng
        mx.random.seed(0)
        net = Net()
        net.initialize(mx.init.Xavier())
        dpt = parallel.DataParallelTrainer(
            net, SoftmaxCrossEntropyLoss(), "sgd",
            {"learning_rate": 0.1}, mesh=mesh, param_sharding=rule)
        losses = []
        for _ in range(3):
            losses.append(float(
                dpt.step(nd.array(X), nd.array(Y)).asnumpy()))
        return losses

    mesh1 = parallel.make_mesh({"dp": 1})
    base = run(mesh1, None)
    mesh_ep = parallel.make_mesh({"dp": 2, "ep": 4})
    ep = run(mesh_ep, parallel.moe_param_rule("ep"))
    np.testing.assert_allclose(ep, base, rtol=2e-4, atol=1e-5)


def test_pipeline_apply_matches_sequential():
    """GPipe schedule over the pp axis == sequentially applying every
    stage on one device; gradients flow through the pipeline."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu import parallel

    n_stages, d = 4, 6
    rng = np.random.RandomState(0)
    w = rng.randn(n_stages, d, d).astype("f4") * 0.4
    b = rng.randn(n_stages, d).astype("f4") * 0.1
    params = {"w": jnp.asarray(w), "b": jnp.asarray(b)}

    def stage_fn(p, x):
        return jnp.tanh(x @ p["w"] + p["b"])

    x = rng.randn(8, d).astype("f4")
    mesh = parallel.make_mesh({"pp": n_stages})
    got = np.asarray(parallel.pipeline_apply(
        stage_fn, params, jnp.asarray(x), n_microbatches=4, mesh=mesh))

    want = x
    for i in range(n_stages):
        want = np.tanh(want @ w[i] + b[i])
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)

    # differentiability: grad of a scalar loss w.r.t. stage params
    def loss(ps):
        y = parallel.pipeline_apply(stage_fn, ps, jnp.asarray(x),
                                    n_microbatches=4, mesh=mesh)
        return jnp.sum(y * y)

    g = jax.grad(loss)(params)
    assert np.isfinite(np.asarray(g["w"])).all()
    assert np.abs(np.asarray(g["w"])).max() > 0


def test_pipeline_rejects_bad_config():
    import jax.numpy as jnp
    from mxnet_tpu import parallel
    mesh = parallel.make_mesh({"pp": 4})
    params = {"w": jnp.zeros((3, 2, 2))}  # wrong leading dim
    with pytest.raises(mx.MXNetError, match="leading dims"):
        parallel.pipeline_apply(lambda p, x: x, params,
                                jnp.zeros((4, 2)), 2, mesh=mesh)


def test_moe_rejects_k_above_experts():
    import jax.numpy as jnp
    x = nd.zeros((4, 4))
    w = nd.zeros((4, 2))
    e1 = nd.zeros((2, 4, 4))
    b = nd.zeros((2, 4))
    with pytest.raises(Exception, match="exceeds num_experts"):
        nd._contrib_MoEFFN(x, w, e1, b, nd.zeros((2, 4, 4)), b,
                           num_experts=2, k=3)


def test_pipeline_cache_structural():
    """Per-call lambdas with identical source reuse the executable."""
    import importlib
    import jax.numpy as jnp
    from mxnet_tpu import parallel
    pl = importlib.import_module("mxnet_tpu.parallel.pipeline")
    mesh = parallel.make_mesh({"pp": 4})
    params = {"w": jnp.ones((4, 4, 4), "float32") * 0.1}
    x = jnp.ones((8, 4), "float32")
    before = len(pl._EXEC_CACHE)
    for _ in range(3):
        parallel.pipeline_apply(lambda p, xx: jnp.tanh(xx @ p["w"]),
                                params, x, n_microbatches=4, mesh=mesh)
    assert len(pl._EXEC_CACHE) == before + 1


def test_moe_bf16_dispatch_positions():
    """Routing bookkeeping must stay exact under low-precision inputs:
    with >256 tokens on one expert, bf16 counters would collide."""
    import jax.numpy as jnp
    t, d, e = 600, 4, 2
    x = np.ones((t, d), np.float32)
    gate_w = np.zeros((d, e), np.float32)
    gate_w[:, 0] = 5.0  # everyone routes to expert 0
    w1 = np.ones((e, d, 4), np.float32)
    b1 = np.zeros((e, 4), np.float32)
    w2 = np.ones((e, 4, d), np.float32)
    b2 = np.zeros((e, d), np.float32)
    out, _ = nd._contrib_MoEFFN(
        nd.array(x.astype("float32")).astype("bfloat16"),
        nd.array(gate_w).astype("bfloat16"),
        nd.array(w1).astype("bfloat16"), nd.array(b1).astype("bfloat16"),
        nd.array(w2).astype("bfloat16"), nd.array(b2).astype("bfloat16"),
        num_experts=e, k=1, capacity_factor=2.0)
    got = out.asnumpy().astype("float32")
    # capacity = 600 (k*T/E * 2.0): every token fits; each kept row is
    # gate(=1.0) * ffn(ones) = 16 per element; none doubled/merged
    rows = np.abs(got).sum(axis=1)
    kept = rows > 1.0
    assert kept.sum() == 600
    np.testing.assert_allclose(
        got[kept], np.broadcast_to(got[kept][0], got[kept].shape),
        rtol=0.05)


def test_pipeline_recreated_array_capture_hits_cache():
    """Equal-but-recreated array captures must hit the exec cache (the
    per-step recompile pitfall) — keyed by content, not identity."""
    import importlib
    import jax.numpy as jnp
    from mxnet_tpu import parallel
    pl = importlib.import_module("mxnet_tpu.parallel.pipeline")
    mesh = parallel.make_mesh({"pp": 4})
    params = {"w": jnp.ones((4, 1), "float32")}
    x = jnp.ones((8, 16), "float32")
    before = len(pl._EXEC_CACHE)
    for _ in range(3):
        cap = jnp.full((16,), 2.0, "float32")  # fresh object, equal value
        parallel.pipeline_apply(lambda p, xx: xx * cap, params, x,
                                n_microbatches=4, mesh=mesh)
    assert len(pl._EXEC_CACHE) == before + 1


# -- _contrib_RoutedExperts: one chip's share of a no-drop expert layer --------
# (ops/moe.py; the model around it is tests/test_afmoe.py)

def _routed_inputs(seed, t=12, d=8, h=6, e=16):
    rng = np.random.RandomState(seed)
    return dict(
        x=rng.randn(t, d).astype("f4"),
        wr=rng.randn(e, d).astype("f4"),
        b=(0.5 * rng.randn(e)).astype("f4"),
        wg=(0.3 * rng.randn(e, d, h)).astype("f4"),
        wu=(0.3 * rng.randn(e, d, h)).astype("f4"),
        wd=(0.3 * rng.randn(e, h, d)).astype("f4"))


def _routed_numpy(a, first, n, k, scale, valid=None, weigh_with_bias=False):
    """(out, held assignments, held experts touched, picked): a loop over
    rows and experts, float64."""
    x = a["x"].astype("f8")
    s = 1 / (1 + np.exp(-(x @ a["wr"].T.astype("f8"))))
    out = np.zeros_like(x)
    picked, held, touched = [], 0, set()
    for t in range(len(x)):
        pick = np.argsort(-(s[t] + a["b"]), kind="stable")[:k]
        picked.append(pick)
        if valid is not None and not valid[t]:
            continue
        w = (s[t] + a["b"] if weigh_with_bias else s[t])[pick]
        w = w / (w.sum() + 1e-20) * scale
        for g, e in zip(w, pick):
            if first <= e < first + n:
                held += 1
                touched.add(e)
                j = e - first
                gate = x[t] @ a["wg"][j]
                mid = gate / (1 + np.exp(-gate)) * (x[t] @ a["wu"][j])
                out[t] += g * (mid @ a["wd"][j])
    return out, held, len(touched), np.array(picked)


def _routed_op(a, first, n, k, scale, valid=None):
    extra = [] if valid is None else [nd.array(valid.astype("f4"))]
    out, held, touched, picked = nd._contrib_RoutedExperts(
        nd.array(a["x"]), nd.array(a["wr"]), nd.array(a["b"]),
        nd.array(a["wg"][first:first + n]), nd.array(a["wu"][first:first + n]),
        nd.array(a["wd"][first:first + n]), *extra, k=k, route_scale=scale,
        first_held=first, use_valid=valid is not None)
    return (out.asnumpy(), int(held.asnumpy()), int(touched.asnumpy()),
            picked.asnumpy())


def _share_of(a, first, n):
    return dict(a, wg=a["wg"][first:first + n], wu=a["wu"][first:first + n],
                wd=a["wd"][first:first + n])


@pytest.mark.parametrize("first,n", [(0, 16), (0, 4), (4, 4), (12, 4),
                                     (3, 9)])
def test_routed_experts_match_a_loop_over_rows_and_experts(first, n):
    """The held range's partial sum, its two counts and the picks, for
    the uncut layer (the same path) and for shares of it."""
    a = _routed_inputs(0)
    want = _routed_numpy(_share_of(a, first, n), first, n, 4, 2.448)
    got = _routed_op(a, first, n, 4, 2.448)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5, atol=1e-6)
    assert got[1:3] == want[1:3]
    np.testing.assert_array_equal(got[3], want[3])
    assert got[0].dtype == np.float32 and got[3].dtype == np.int32


def test_routed_shares_add_up_to_the_uncut_layer():
    a = _routed_inputs(1)
    whole = _routed_op(a, 0, 16, 4, 2.448)
    parts = [_routed_op(a, first, 4, 4, 2.448) for first in (0, 4, 8, 12)]
    np.testing.assert_allclose(sum(p[0] for p in parts), whole[0],
                               rtol=1e-5, atol=1e-6)
    assert sum(p[1] for p in parts) == whole[1] == 12 * 4
    assert sum(p[2] for p in parts) == whole[2]


def test_routed_bias_selects_and_never_weighs():
    a = _routed_inputs(2)
    biased = _routed_op(a, 0, 16, 4, 1.0)
    unbiased = _routed_op(dict(a, b=np.zeros_like(a["b"])), 0, 16, 4, 1.0)
    # the bias changes who is picked (top4(s + b) != top4(s)) ...
    assert (np.sort(biased[3], -1) != np.sort(unbiased[3], -1)).any()
    # ... and the picked are weighed by their scores alone
    np.testing.assert_allclose(
        biased[0], _routed_numpy(a, 0, 16, 4, 1.0)[0], rtol=1e-5, atol=1e-6)
    wrong = _routed_numpy(a, 0, 16, 4, 1.0, weigh_with_bias=True)[0]
    assert np.abs(biased[0] - wrong).max() > 1e-2


@pytest.mark.parametrize("k", [1, 4])
def test_routed_experts_drop_nothing_under_total_imbalance(k):
    """Every one of 96 rows picks the same ``k`` experts (a bias of 50 on
    them): whatever a capacity would have been, nothing is dropped."""
    a = _routed_inputs(3, t=96)
    a["b"] = np.zeros(16, "f4")
    a["b"][[5, 6, 9, 11][:k]] = 50.0
    got = _routed_op(a, 4, 8, k, 2.448)
    want = _routed_numpy(_share_of(a, 4, 8), 4, 8, k, 2.448)
    assert (np.sort(got[3], -1) == sorted([5, 6, 9, 11][:k])).all()
    assert got[1] == want[1] == 96 * k and got[2] == want[2] == k
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5, atol=1e-6)
    assert np.abs(got[0]).min(axis=1).min() >= 0 and \
        (np.abs(got[0]).sum(axis=1) > 0).all()          # no row left out


def test_routed_padding_rows_go_nowhere_and_count_nowhere():
    a = _routed_inputs(4)
    valid = np.arange(12) < 7
    got = _routed_op(a, 2, 10, 4, 2.448, valid)
    want = _routed_numpy(_share_of(a, 2, 10), 2, 10, 4, 2.448, valid)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5, atol=1e-6)
    assert got[1:3] == want[1:3]
    assert np.abs(got[0][7:]).max() == 0.0


def test_routed_selection_is_float32_whatever_the_inputs_are():
    """bfloat16 rows and weights: the scores, the top-k and the counts
    run in float32 / int32 (bfloat16 cannot tell 256 scores apart, nor
    count past 256), the partial sum comes back float32."""
    a = _routed_inputs(5, t=300)
    bf = {k: nd.array(v).astype("bfloat16") for k, v in a.items()}
    out, held, touched, picked = nd._contrib_RoutedExperts(
        bf["x"], bf["wr"], bf["b"], bf["wg"], bf["wu"], bf["wd"], k=4,
        route_scale=2.448, first_held=0)
    assert str(out.dtype) == "float32" and int(held.asnumpy()) == 1200
    rounded = {k: v.astype("float32").asnumpy() for k, v in bf.items()}
    want = _routed_numpy(rounded, 0, 16, 4, 2.448)
    agree = (picked.asnumpy() == want[3]).all(axis=1)
    assert agree.mean() > 0.97          # a rounding apart at a near tie
    np.testing.assert_allclose(out.asnumpy()[agree], want[0][agree],
                               rtol=0.05, atol=0.02)


def test_routed_experts_refuse_a_range_outside_the_router():
    a = {k: nd.array(v) for k, v in _routed_inputs(6).items()}
    held = [a[k][:8] for k in ("wg", "wu", "wd")]
    with pytest.raises(Exception, match="not among the router's 16"):
        nd._contrib_RoutedExperts(a["x"], a["wr"], a["b"], *held, k=4,
                                  first_held=12)
    with pytest.raises(Exception, match="exceeds the router's"):
        nd._contrib_RoutedExperts(a["x"], a["wr"], a["b"], *held, k=17)
