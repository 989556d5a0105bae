#!/usr/bin/env python
"""Distributed job launcher.

Parity model: the reference's ``tools/launch.py`` + dmlc_tracker, whose
``--launcher local`` mode runs a whole multi-node job as processes on one
box (SURVEY.md §2.3 "Launcher / tracker", §3.5).  The ps-lite world
needed three roles (scheduler / servers / workers) and a ZeroMQ
rendezvous; the TPU-native world needs exactly one role — every process
is a worker entering the same SPMD program — and the rendezvous is the
JAX/PJRT distributed runtime's coordination service.

So this launcher:

1. picks a coordinator address (``127.0.0.1:<free port>`` for
   ``--launcher local``),
2. spawns ``-n`` copies of the command with the rendezvous exported in
   ``MXTPU_DIST_*`` env vars (plus the reference's ``DMLC_*`` spellings
   for scripts that read those),
3. streams each worker's output with a ``[worker N]`` prefix and exits
   non-zero if any worker fails.

Worker processes pick the rendezvous up automatically: creating a
``dist_*`` kvstore (or calling ``mx.kvstore.init_distributed()``
directly) reads ``MXTPU_DIST_*`` and calls
``jax.distributed.initialize``.

Usage::

    JAX_PLATFORMS=cpu python tools/launch.py -n 2 python train.py ...

More than one worker needs ``JAX_PLATFORMS=cpu`` in the environment: the
workers of a ``local`` launch all see the same host, and its TPU chips
cannot be shared between processes (see ``launch_local``).

``--launcher ssh/mpi/yarn`` are declared capability gaps: multi-host TPU
pods are normally launched by the pod runtime (one process per host,
same command), which makes a remote-spawning tracker redundant.
"""
from __future__ import annotations

import argparse
import os
import signal
import socket
import subprocess
import sys
import threading


def _free_port():
    s = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _stream(proc, rank, out=sys.stdout):
    for line in iter(proc.stdout.readline, b""):
        out.write(f"[worker {rank}] {line.decode(errors='replace')}")
        out.flush()


def launch_local(num_workers, command, extra_env=None):
    """Spawn ``num_workers`` local processes with rendezvous env set.

    Returns the list of exit codes (one per worker).

    Every worker sees every device of the host, and a TPU chip belongs
    to one process at a time — so more than one worker is only started
    when the environment forces the CPU backend.  The chips of one host
    are driven from ONE process over a ``Mesh`` (README, "Several
    chips").
    """
    if num_workers > 1 and {**os.environ, **(extra_env or {})}.get(
            "JAX_PLATFORMS") != "cpu":
        raise SystemExit(
            f"launch.py: refusing to start {num_workers} workers: "
            "each would claim every TPU chip of this host, and a chip "
            "belongs to one process.  Drive the host's chips from one "
            "process over a Mesh (parallel.make_mesh), or set "
            "JAX_PLATFORMS=cpu to run the workers on the CPU backend.")
    coord = f"127.0.0.1:{_free_port()}"
    procs = []
    threads = []
    for rank in range(num_workers):
        env = dict(os.environ)
        env.update(extra_env or {})
        env.update({
            "MXTPU_DIST_COORDINATOR": coord,
            "MXTPU_DIST_NUM_PROCS": str(num_workers),
            "MXTPU_DIST_PROC_ID": str(rank),
            # reference spellings (ps-lite scripts read these)
            "DMLC_ROLE": "worker",
            "DMLC_NUM_WORKER": str(num_workers),
            "DMLC_NUM_SERVER": "0",
            "DMLC_PS_ROOT_URI": coord.split(":")[0],
            "DMLC_PS_ROOT_PORT": coord.split(":")[1],
        })
        p = subprocess.Popen(command, env=env, stdout=subprocess.PIPE,
                             stderr=subprocess.STDOUT)
        t = threading.Thread(target=_stream, args=(p, rank), daemon=True)
        t.start()
        procs.append(p)
        threads.append(t)

    codes = []
    try:
        for p in procs:
            codes.append(p.wait())
    except KeyboardInterrupt:
        for p in procs:
            p.send_signal(signal.SIGTERM)
        raise
    for t in threads:
        t.join(timeout=5)
    return codes


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="Launch a distributed mxnet_tpu job.",
        usage="launch.py [-h] -n NUM_WORKERS [--launcher local] command ...")
    ap.add_argument("-n", "--num-workers", type=int, required=True,
                    help="number of worker processes")
    ap.add_argument("-s", "--num-servers", type=int, default=0,
                    help="accepted for reference CLI parity; the TPU "
                         "backend has no server role (ignored)")
    ap.add_argument("--launcher", default="local",
                    choices=["local", "ssh", "mpi", "yarn"],
                    help="only 'local' is implemented (documented gap: "
                         "pod runtimes launch multi-host jobs)")
    ap.add_argument("command", nargs=argparse.REMAINDER)
    args = ap.parse_args(argv)

    if args.launcher != "local":
        ap.error(f"--launcher {args.launcher} is a declared capability "
                 "gap: multi-host TPU jobs are launched by the pod "
                 "runtime (one process per host). Use --launcher local.")
    if not args.command:
        ap.error("no command given")
    if args.num_servers:
        print("launch.py: note: -s/--num-servers ignored (no server "
              "role on TPU)", file=sys.stderr)

    codes = launch_local(args.num_workers, args.command)
    bad = [(i, c) for i, c in enumerate(codes) if c != 0]
    for i, c in bad:
        print(f"launch.py: worker {i} exited with {c}", file=sys.stderr)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
