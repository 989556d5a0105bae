#!/usr/bin/env python3
"""``chipbench/run.py`` by its former side entry: kept for the documents.

    python3 chipbench/run_held.py --workload <name> --seed <n> --seconds <s> --trace 1

From PR 37 to PR 38 the 15 per-layer entries of
``chipbench/held_per_layer.json`` (device time by ``mxtpu.*`` scope) were
written and tested but NOT in ``BENCHMARK.json``, and this ran a cell with
them appended.  Since PR 39 all 15 are entries of ``BENCHMARK.json`` and
``run.py`` prints them itself: an entry held here under a name the
benchmark has is left out, so today this adds nothing and prints what
``run.py`` prints.  ``docs/observability.md`` and the verify skill still
give this command and name that file, and a ``benchmark`` PR may edit
neither: both files go, together, once those documents say ``run.py``
(``PERF.md`` section 7).  Hold no new entry here.  The driver runs
``run.py`` and never this.
"""
import os
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(BENCH_DIR))

from chipbench import run                    # noqa: E402
from chipbench.harness import resolve        # noqa: E402


def with_held(bench):
    """``bench`` with the held entries that it does not have itself at
    the end of ``per_layer``."""
    has = {m["name"] for m in bench["per_layer"]}
    held = [m for m in resolve.load_json(BENCH_DIR, "held_per_layer.json")
            if m["name"] not in has]
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
