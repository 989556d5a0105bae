"""Wall-clock timing of asynchronously dispatched device work.

JAX returns from a call before the device finishes, so a loop timed
without a closing ``block_until_ready`` (or a host read of the result,
which implies it) measures the enqueue, not the work.  Two defenses,
used together by every benchmark in this directory:

1. **Value chaining** — each iteration's output is folded into an
   accumulator the next iteration (or the closing materialization)
   depends on, and the window is closed by an ``asnumpy``-style host
   materialization of that accumulator: the ``block_until_ready`` of
   the whole chain.  The data dependency also stops the device from
   reordering or dropping work.
2. **Two-window slope** — timing windows of n and 3n iterations and
   taking ``(t3 - t1) / 2n`` cancels the fixed cost both windows share
   (first dispatch, queue drain, the closing transfer), leaving the
   per-iteration time.

Shared by ``bert_phase_bench.py``, ``resnet_bench.py``,
``llm_decode_bench.py`` (bench.py carries its own copy so it stays
self-contained for the driver).
"""
import json
import time

import numpy as np


def slope(window, iters, grow_to=2000, min_spread=0.02):
    """Per-iteration time from two chained windows with noise guards.

    ``window(n)`` must run n chained iterations and block on a true
    host materialization.  Windows grow while their spread is below
    timer/transfer noise; a non-positive or implausibly small slope
    (window order flipped by host noise) falls back to the naive
    rate with a warning on stdout.
    """
    t1 = window(iters)
    t3 = window(3 * iters)
    while (t3 - t1) < min_spread and iters < grow_to:
        iters *= 4
        t1 = window(iters)
        t3 = window(3 * iters)
    s = (t3 - t1) / (2 * iters)
    naive = t3 / (3 * iters)
    if s <= 0 or s < 0.2 * naive:
        print(json.dumps({"warn": "slope unstable, reporting naive",
                          "slope_ms": round(s * 1e3, 4),
                          "naive_ms": round(naive * 1e3, 4)}),
              flush=True)
        return naive
    return s


def time_nd_steps(step_fn, iters=10):
    """Slope timing for framework-path loops over NDArrays.

    ``step_fn()`` must return an NDArray whose value depends on that
    call's work (loss, logits, output activations).  Each window chains
    every iteration's output into an accumulator; the closing
    ``asnumpy`` waits for the whole chain.
    """
    step_fn().asnumpy()                      # compile + warm

    def window(n):
        t0 = time.perf_counter()
        acc = None
        for _ in range(n):
            out = step_fn().reshape((-1,))[0:1]
            acc = out if acc is None else acc + out * 1e-30
        float(np.asarray(acc.asnumpy()).ravel()[0])
        return time.perf_counter() - t0

    return slope(window, iters)
