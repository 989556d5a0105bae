"""Fault-injection worker: rank 1 dies mid-training; rank 0 must
DETECT the failure (error at the next collective) rather than hang
forever — SURVEY.md §5 "failure detection" (the reference's ps-lite
noticed dead nodes via ZeroMQ send failures/heartbeats)."""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
import numpy as np  # noqa: E402

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu import nd  # noqa: E402


def main():
    kv = mx.kv.create("dist_tpu_sync")
    rank, n = kv.rank, kv.num_workers
    kv.init("w", nd.zeros((2,)))
    kv.push("w", nd.full((2,), 1.0))  # round 1: everyone participates
    out = nd.zeros((2,))
    kv.pull("w", out=out)
    np.testing.assert_allclose(out.asnumpy(), float(n))
    print(f"ROUND1_OK rank={rank}", flush=True)

    if rank == 1:
        os._exit(17)  # simulated hard crash (no cleanup, no goodbye)

    # rank 0: the next cross-process collective must FAIL, not hang
    try:
        kv.push("w", nd.full((2,), 1.0))
        print("SURVIVOR_NO_ERROR", flush=True)
        return 3
    except BaseException as e:  # gloo/coordination error surfaces here
        print(f"SURVIVOR_DETECTED_FAILURE: {type(e).__name__}",
              flush=True)
        return 0


if __name__ == "__main__":
    sys.exit(main())
