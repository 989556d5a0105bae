#!/usr/bin/env python3
"""The limit of ``correct`` in ``phi4_mini_flash.reason_closed``, read ON
THE CHIP through the harness's own comparison.

    chiprun -- python tools/sambay_chip_check.py [--seed 2147489003]

Builds the cell's server as a run does and calls the serving driver's own
``_probe`` (the probe request served through ``Server``, its greedy tokens
held to a full-sequence forward of the same weights, the traffic file's
``gap_share``) once for each precision of the plain reference
(``chipbench/models/sambay_server.py`` ``PRECISIONS``): ``stated`` is what
every run of the cell compares with; ``float32`` is the mathematics;
``state_bfloat16`` and ``float8`` are the controls one precision lower,
which a limit has to refuse.  One JSON line a precision: the worst
regret, the exact-argmax count, whether the harness's check held.  One
seed a process (two models do not fit a chip).  PERF.md section 6 (PR 29)
has the readings.  ``--rehearse`` runs the config's toy shapes on any
backend.
"""
import argparse
import json
import os
import sys
import types
from functools import partial

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

CELL = "phi4_mini_flash.reason_closed"
PRECISIONS = ("stated", "float32", "state_bfloat16", "float8")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rehearse", action="store_true",
                    help="the config's toy rehearsal shapes, any backend")
    ap.add_argument("--seed", type=int, default=2147489003)
    args = ap.parse_args()

    from tools import jax_cache
    jax_cache.place()
    import jax
    from chipbench.drivers import serve_loop
    from chipbench.harness import resolve, runtime

    workload, config, traffic = resolve.cell(resolve.load_benchmark(), CELL)
    run = runtime.Run(
        types.SimpleNamespace(seed=args.seed, seconds=0.0, trace=0,
                              rehearse=args.rehearse, sweep=None),
        workload, config, traffic, None, 0.0)
    run.devices = jax.devices()[:1]
    builder = runtime.builder_for(run)
    net, srv, ctx = builder.build_server(
        run.shapes, args.seed, run.devices[0],
        int(run.traffic["max_queue"]))
    loop = serve_loop.Loop(srv, run.spans)
    for name in PRECISIONS:
        run.checks = runtime.Checks()
        probe = serve_loop._probe(
            run, net, srv, ctx, types.SimpleNamespace(
                full_forward_logits=partial(
                    builder.full_forward_logits, precision=name)),
            loop)
        print(json.dumps(dict(
            probe, seed=args.seed, precision=name,
            gap_share=run.traffic["probe"]["gap_share"],
            held=not run.checks.failed)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
