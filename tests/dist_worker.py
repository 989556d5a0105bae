"""Worker body for the local two-process distributed test.

Run through ``tools/launch.py -n 2 python tests/dist_worker.py`` (the
reference's ``--launcher local`` trick — SURVEY.md §4 "Distributed tests
without a cluster").  Asserts, per the reference's
``dist_sync_kvstore.py``: after every worker pushes known constants, the
pulled value equals the cross-worker aggregate.
"""
import os
import sys

# CPU backend: set before jax initializes
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np

import mxnet_tpu as mx
from mxnet_tpu import nd


def main():
    # realistic flow: computation happens BEFORE the kvstore exists
    # (Gluon Trainer creates it lazily at the first step) — this only
    # works because `import mxnet_tpu` joined the rendezvous already
    warm = nd.dot(nd.ones((8, 8)), nd.ones((8, 8)))
    assert float(warm.asnumpy()[0, 0]) == 8.0

    kv = mx.kv.create("dist_tpu_sync")
    assert kv.is_distributed
    n = kv.num_workers
    rank = kv.rank
    assert n == int(os.environ["MXTPU_DIST_NUM_PROCS"])

    # 1. push known constants, pull the aggregate: sum_r (r+1)
    kv.init("w", nd.zeros((4, 2)))
    kv.push("w", nd.full((4, 2), rank + 1))
    out = nd.zeros((4, 2))
    kv.pull("w", out=out)
    expect = n * (n + 1) / 2
    np.testing.assert_allclose(out.asnumpy(), expect)

    # 2. multi-key pushpull round
    kv.init(["a", "b"], [nd.zeros((3,)), nd.zeros((3,))])
    outs = [nd.zeros((3,)), nd.zeros((3,))]
    kv.pushpull(["a", "b"],
                [nd.full((3,), rank * 10 + 1), nd.full((3,), rank + 1)],
                out=outs)
    np.testing.assert_allclose(
        outs[0].asnumpy(), sum(r * 10 + 1 for r in range(n)))
    np.testing.assert_allclose(outs[1].asnumpy(), expect)

    # 2b. init broadcasts rank 0's value (workers may init with
    # different random weights; all must adopt one copy)
    kv.init("init_bc", nd.full((2,), float(rank * 7 + 1)))
    got_bc = nd.zeros((2,))
    kv.pull("init_bc", out=got_bc)
    np.testing.assert_allclose(got_bc.asnumpy(), 1.0)  # rank 0's value

    # 2c. gradient compression on the cross-process hop: 0.3 pushes
    # quantize to 0 (residual 0.3); the second push sees 0.6 -> snaps
    # to +0.5 per worker -> aggregate n*0.5 (error feedback carried)
    kv.set_gradient_compression({"type": "2bit", "threshold": 0.5})
    kv.init("comp", nd.zeros((4,)))
    kv.push("comp", nd.full((4,), 0.3))
    got_c = nd.zeros((4,))
    kv.pull("comp", out=got_c)
    np.testing.assert_allclose(got_c.asnumpy(), 0.0)
    kv.push("comp", nd.full((4,), 0.3))
    kv.pull("comp", out=got_c)
    np.testing.assert_allclose(got_c.asnumpy(), 0.5 * n)
    # 2d. int8 compression on the same hop: absmax codes + per-proc
    # scale travel the wire; result within one quantization step
    kv.set_gradient_compression({"type": "int8"})
    kv.init("comp8", nd.zeros((4,)))
    kv.push("comp8", nd.full((4,), 0.37))
    got_8 = nd.zeros((4,))
    kv.pull("comp8", out=got_8)
    np.testing.assert_allclose(got_8.asnumpy(), 0.37 * n, rtol=2e-2)
    kv._compression = None  # back to plain aggregation for part 3

    # 3. barrier then server-side-updater path (optimizer on store)
    kv._barrier()
    kv2_key = "u"
    kv.set_optimizer(mx.optimizer.SGD(learning_rate=1.0))
    kv.init(kv2_key, nd.ones((2, 2)))
    kv.push(kv2_key, nd.full((2, 2), 1.0))  # grad = n after aggregation
    got = nd.zeros((2, 2))
    kv.pull(kv2_key, out=got)
    # w <- w - lr * (sum of grads) = 1 - n
    np.testing.assert_allclose(got.asnumpy(), 1.0 - n)

    print(f"WORKER_OK rank={rank}/{n}", flush=True)


if __name__ == "__main__":
    main()
    sys.exit(0)
