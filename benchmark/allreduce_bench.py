"""KVStore allreduce bandwidth harness.

Parity model: the reference's ``tools/bandwidth/measure.py``, which
exists precisely to measure kvstore push/pull bandwidth (SURVEY.md §6,
metric #3 "KVStore allreduce GB/s").

Measures the eager kvstore-style allreduce (``parallel.collectives.
allreduce`` — jitted shard_map psum, one shard per mesh device) across a
sweep of tensor sizes and reports algorithmic bus bandwidth::

    busbw = 2 * (n-1)/n * bytes / time      (ring-allreduce accounting)

Run on the real chip (mesh=1: measures device<->HBM round trip only) or
on the virtual CPU mesh::

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python benchmark/allreduce_bench.py

Prints one JSON line per size and a trailing summary.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time


def bench_allreduce(sizes_mb, iters=10):
    import numpy as np
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import nd, parallel

    devs = jax.devices()
    n = len(devs)
    mesh = parallel.make_mesh({"dp": n}, devices=devs)
    ctxs = [mx.Context("tpu" if devs[0].platform != "cpu" else "cpu", i)
            for i in range(n)]

    rows = []
    for mb in sizes_mb:
        elems = int(mb * 1e6 / 4)
        shards = [nd.array(np.full((elems,), i + 1, "f4"), ctx=ctxs[i])
                  for i in range(n)]
        # warm (compiles the shard_map for this shape)
        out = parallel.collectives.allreduce(shards, axis="dp", mesh=mesh)
        out[0].wait_to_read()
        # block every iteration: overlapping in-flight collectives can
        # wedge the XLA:CPU in-process rendezvous, and for bandwidth
        # sizes the per-call sync cost is in the noise
        t0 = time.perf_counter()
        for _ in range(iters):
            out = parallel.collectives.allreduce(shards, axis="dp",
                                                 mesh=mesh)
            for o in out:
                o.wait_to_read()
        dt = (time.perf_counter() - t0) / iters

        expect = n * (n + 1) / 2
        assert abs(float(out[0].asnumpy()[0]) - expect) < 1e-3

        nbytes = elems * 4
        if n == 1:
            # mesh=1: no inter-device traffic — report the device
            # round-trip (copy) bandwidth instead of a ring busbw of 0
            busbw = nbytes / dt / 1e9
        else:
            busbw = (2 * (n - 1) / n) * nbytes / dt / 1e9
        row = {"size_mb": mb, "n_devices": n,
               "time_ms": round(dt * 1e3, 3),
               "busbw_gbps": round(busbw, 2)}
        rows.append(row)
        print(json.dumps(row), flush=True)
    return rows, n


def bench_dist(sizes_mb, iters=10):
    """Cross-PROCESS hop (runs inside a ``tools/launch.py`` worker):
    measures the ``process_allgather`` + sum exchange that
    ``KVStoreTPUSync._merge`` rides — the DCN-analog with REAL process
    boundaries and measured byte volumes (VERDICT r2 weak #8: the
    busbw series needs more than an in-process rendezvous number)."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from jax.experimental import multihost_utils

    import mxnet_tpu  # noqa: F401  joins the MXTPU_DIST_* rendezvous

    rank, nproc = jax.process_index(), jax.process_count()
    rows = []
    for mb in sizes_mb:
        elems = int(mb * 1e6 / 4)
        x = jnp.full((elems,), float(rank + 1), jnp.float32)
        g = multihost_utils.process_allgather(x)    # warm
        jax.block_until_ready(g)
        t0 = time.perf_counter()
        for _ in range(iters):
            g = multihost_utils.process_allgather(x)
            jax.block_until_ready(g)
        dt = (time.perf_counter() - t0) / iters
        assert float(np.asarray(g).reshape(nproc, -1)[:, 0].sum()) == \
            nproc * (nproc + 1) / 2
        nbytes = elems * 4
        # each process receives (n-1) remote shards per allgather
        algbw = (nproc - 1) * nbytes / dt / 1e9
        row = {"dist": True, "size_mb": mb, "n_procs": nproc,
               "time_ms": round(dt * 1e3, 3),
               "allgather_gbps_per_proc": round(algbw, 2)}
        rows.append(row)
        if rank == 0:
            print(json.dumps(row), flush=True)
    return rows


def _launch_dist(n, sizes, iters):
    import signal
    import subprocess
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("XLA_FLAGS", None)
    # N worker processes on one host measure the CPU (gloo) transport;
    # a chip belongs to one process, so tools/launch.py refuses N > 1
    # unless the environment forces the CPU
    env["JAX_PLATFORMS"] = "cpu"
    # own process group: a wedged rendezvous must not leave orphaned
    # workers holding the coordinator port after the timeout kill
    proc = subprocess.Popen(
        [sys.executable, os.path.join(repo, "tools", "launch.py"),
         "-n", str(n), "--launcher", "local",
         sys.executable, os.path.abspath(__file__), "--dist",
         "--sizes-mb", ",".join(str(s) for s in sizes),
         "--iters", str(iters)],
        env=env, cwd=repo, start_new_session=True)
    try:
        return proc.wait(timeout=600)
    except subprocess.TimeoutExpired:
        os.killpg(os.getpgid(proc.pid), signal.SIGKILL)
        proc.wait()
        print(json.dumps({"error": "dist bench timed out"}),
              flush=True)
        return 124


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--sizes-mb", default="1,4,16,64",
                    help="comma-separated tensor sizes in MB")
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--dist", action="store_true",
                    help="worker body: measure the cross-process "
                         "allgather hop (run via tools/launch.py)")
    ap.add_argument("--dist-launch", type=int, default=0, metavar="N",
                    help="spawn N launcher workers running --dist")
    args = ap.parse_args(argv)

    sizes = [float(s) for s in args.sizes_mb.split(",")]
    if args.dist_launch:
        # worker failures must surface as a nonzero exit, not be
        # dropped by the bare __main__ call
        sys.exit(_launch_dist(args.dist_launch, sizes, args.iters))
    if args.dist:
        return bench_dist(sizes, iters=args.iters)
    rows, n = bench_allreduce(sizes, iters=args.iters)
    peak = max(r["busbw_gbps"] for r in rows)
    print(json.dumps({"summary": "allreduce", "n_devices": n,
                      "peak_busbw_gbps": peak}), flush=True)
    return rows


if __name__ == "__main__":
    main()
