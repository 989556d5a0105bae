#!/usr/bin/env python3
"""``chipbench/run.py`` with the HELD per-layer metrics read as well.

    python3 chipbench/run_held.py --workload <name> --seed <n> --seconds <s> --trace 1

``chipbench/held_per_layer.json`` holds per-layer entries that are written
and tested but NOT in ``BENCHMARK.json``: tests the benchmark already has
pin each cell's exact metric set and the tail of ``per_layer``, so no
entry can be appended until a ``benchmark`` PR relaxes them (``PERF.md``
section 7).  This runs one cell once exactly as ``run.py`` does, with the
held entries appended to the benchmark it loads: a traced run prints the
line ``device_scopes`` (``harness/device_scopes.py``) and its result line
holds the held metrics beside the accepted ones.  The driver runs
``run.py`` and never this; a number from here is a builder's reading.
"""
import os
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(BENCH_DIR))

from chipbench import run                    # noqa: E402
from chipbench.harness import resolve        # noqa: E402


def with_held(bench):
    """``bench`` with the held entries at the end of ``per_layer``."""
    held = resolve.load_json(BENCH_DIR, "held_per_layer.json")
    return dict(bench, per_layer=bench["per_layer"] + held)


def main(argv=None):
    load = resolve.load_benchmark
    resolve.load_benchmark = lambda: with_held(load())
    try:
        return run.main(argv)
    finally:
        resolve.load_benchmark = load


if __name__ == "__main__":
    sys.exit(main())
