#!/usr/bin/env python3
"""Run ONE cell of ``BENCHMARK.json`` ONCE, on the chip.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is the one JSON result object (see
``chipbench/README.md``); every earlier line is one JSON object of
things worth knowing.  With no TPU, fewer chips than the cell asks for,
or a ``device_kind`` that ``chipbench/peaks.json`` does not list, the exit
code is not 0 and no result line is printed.  ``--rehearse`` runs the same
code at the configuration's toy ``rehearsal`` shapes on any backend and
then exits with 3 and no result line: a rehearsal is never a number.
``--sweep r1,r2,...`` (serving cells) offers each rate for ``--seconds``
and prints one line per rate; it too ends with no result line.
"""
import time

T_PROCESS = time.perf_counter()     # set-up is counted from here

import argparse
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
sys.path.insert(0, ROOT)

EXIT_NO_CHIP = 2
EXIT_NOT_A_MEASUREMENT = 3


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, default=0, choices=[0, 1])
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--sweep", default=None,
                    help="comma-separated rates; serving cells only")
    return ap.parse_args(argv)


def main(argv=None):
    args = parse(argv)
    from chipbench.harness import resolve
    bench = resolve.load_benchmark()
    workload, config, traffic = resolve.cell(bench, args.workload)
    if args.seconds is None:
        args.seconds = float(bench["run_seconds"])
    peaks_table = resolve.load_json(BENCH_DIR, "peaks.json")

    # jax's persistent cache: JAX_COMPILATION_CACHE_DIR from outside wins,
    # else <checkout>/.jax_cache; the repo's own second tier in a fixed
    # sub-directory of it, never emptied.  Both before jax is imported.
    from tools import jax_cache
    cache_dir = jax_cache.place()
    tier = os.path.join(cache_dir, "mxtpu_chipbench")
    os.makedirs(tier, exist_ok=True)
    os.environ["MXTPU_COMPILE_CACHE_DIR"] = tier

    import jax
    jax_cache_events = {"hits": 0, "misses": 0}

    def on_event(name, **_kw):
        if name == "/jax/compilation_cache/cache_hits":
            jax_cache_events["hits"] += 1
        elif name == "/jax/compilation_cache/cache_misses":
            jax_cache_events["misses"] += 1
    jax.monitoring.register_event_listener(on_event)

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    from chipbench.harness import runtime
    runtime.emit(start=args.workload, seed=args.seed, seconds=args.seconds,
                 trace=args.trace, rehearse=args.rehearse, device=device,
                 memory_stats=devs[0].memory_stats(),
                 jax=jax.__version__, cache_dir=cache_dir,
                 cache_entries_at_start=len(os.listdir(cache_dir)))
    if not args.rehearse:
        if device["platform"] != "tpu":
            print(f"chipbench: jax's first device is "
                  f"{device['platform']!r}, not a TPU", file=sys.stderr)
            return EXIT_NO_CHIP
        if device["kind"] not in peaks_table:
            print(f"chipbench: device_kind {device['kind']!r} is not in "
                  "chipbench/peaks.json; add it with its source",
                  file=sys.stderr)
            return EXIT_NO_CHIP
    if device["count"] < workload["chips"]:
        print(f"chipbench: the cell needs {workload['chips']} chip(s), jax "
              f"has {device['count']}", file=sys.stderr)
        return EXIT_NO_CHIP
    peaks = peaks_table.get(device["kind"])

    run = runtime.Run(args, workload, config, traffic, peaks, T_PROCESS)
    run.devices = devs[:run.chips]
    driver = resolve.load_module("drivers", run.traffic["driver"])
    obs = driver.run(run)
    if obs is None:               # a sweep: lines, no result
        return EXIT_NOT_A_MEASUREMENT
    obs["setup_s"] = run.setup_s
    obs["setup"] = run.setup
    obs["peaks"] = peaks
    obs["chips"] = run.chips
    t_reduce = time.perf_counter()
    obs["trace"] = run.tracer.reduce(run.chips) if args.trace else None

    e2e = resolve.read_metrics(bench, "end_to_end", args.workload, obs)
    layer = resolve.read_metrics(bench, "per_layer", args.workload, obs)
    device["memory_peak_bytes"], memory_parts = runtime.peak_bytes(run.devices)
    runtime.emit(setup=run.setup, jax_cache=jax_cache_events,
                 memory=memory_parts,
                 counters=runtime.program_counters(),
                 checks_failed=run.checks.failed,
                 trace_reduce_s=time.perf_counter() - t_reduce,
                 trace={k: v for k, v in (obs["trace"] or {}).items()
                        if k not in ("device_ops", "idle_gaps")},
                 end_to_end=e2e, per_layer=layer, device=device)
    if args.rehearse:
        print("chipbench: a rehearsal ran to its end; toy shapes prove "
              "nothing about the chip", file=sys.stderr)
        return EXIT_NOT_A_MEASUREMENT

    result = {"correct": not run.checks.failed,
              "attempted": int(obs["attempted"]),
              "failed": int(obs["failed"]),
              "metrics": layer if args.trace else e2e,
              "device": device}
    if args.trace:
        tr = obs["trace"]
        if tr is None:
            print("chipbench: --trace 1 but the trace holds no device "
                  "event", file=sys.stderr)
            return 1
        device["busy_s"] = tr["busy_s"]
        device["window_s"] = tr["window_s"]
        result["breakdown"] = {"device_ops": tr["device_ops"],
                               "idle_gaps": tr["idle_gaps"]}
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
