#!/usr/bin/env python3
"""The grouped expert products of ONE expert layer, timed ON THE CHIP at
the three expert cells' shapes, the definition beside the kernel
(``mxnet_tpu/ops/grouped_matmul.py``).

    python tools/expert_ffn_bench.py        # on a host with a TPU

Shapes, ``(rows T, top-k, held N of E, d, h)``: each cell's decode round
(Solar 256 rows, Pangu 160, Trinity 160) and its largest prefill rung
(1,024, 1,024, 512 positions), bfloat16.  Picks are drawn as the cells
route: each row picks ``k`` distinct experts of the router's ``E``, the
experts held are the first ``N``.  One JSON line a variant, each with its
milliseconds a call on the device (``--loops`` calls chained in one
program, median of ``--calls`` such programs after a warm-up), the bytes
of the experts the call touched (three matrices each) over that time,
and its largest difference from the definition:

* ``definition``: ``_definition``, three ``lax.ragged_dot`` (the parent's
  path and the lowering off the TPU);
* ``kernel``: ``_kernel_sum``, the layout, the Pallas kernel and the
  gathers around it, at the step budget ``step_MiB`` (the kernel's own
  is ``_STEP_BYTES``; the decode shapes are also timed at half and twice
  it).

``--rehearse`` runs toy shapes through the Pallas interpreter on any
backend (no time it prints means anything).
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

# name: (rows, k, held, router's experts, d, h)
SHAPES = {
    "solar_decode": (256, 8, 40, 320, 4096, 1280),
    "solar_prefill1024": (1024, 8, 40, 320, 4096, 1280),
    "pangu_decode": (160, 8, 16, 256, 7680, 2048),
    "pangu_prefill1024": (1024, 8, 16, 256, 7680, 2048),
    "trinity_decode": (160, 4, 32, 256, 3072, 3072),
    "trinity_prefill512": (512, 4, 32, 256, 3072, 3072),
}
REHEARSE = {"toy_decode": (12, 4, 4, 16, 128, 256),
            "toy_prefill": (40, 4, 4, 16, 256, 128)}


def routed(rng, t, k, n, e):
    """(rows, sizes, gates, back) as ``routed_experts`` hands them to
    ``expert_sum``, for rows that each pick ``k`` distinct experts of
    ``e`` at random."""
    import numpy as np
    picks = np.stack([rng.choice(e, k, replace=False) for _ in range(t)])
    key = np.where(picks < n, picks, n).reshape(-1)
    order = np.argsort(key, kind="stable")
    sizes = np.bincount(key, minlength=n + 1)[:n].astype(np.int32)
    gates = np.where(key[order] < n, rng.uniform(0.05, 0.3, t * k), 0.0)
    back = np.empty_like(order)
    back[order] = np.arange(t * k)
    return ((order // k).astype(np.int32), sizes, gates.astype(np.float32),
            back.astype(np.int32))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rehearse", action="store_true",
                    help="toy shapes, the Pallas interpreter, any backend")
    ap.add_argument("--seed", type=int, default=2147496201)
    ap.add_argument("--calls", type=int, default=5)
    ap.add_argument("--loops", type=int, default=20,
                    help="calls chained inside one timed program")
    args = ap.parse_args()

    from tools import jax_cache
    jax_cache.place()
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax
    from mxnet_tpu.ops import grouped_matmul as gm

    if args.rehearse:
        shapes, calls, loops = REHEARSE, 1, 2
        gm._INTERPRET = True
        dev = jax.devices()[0]
    else:
        shapes, calls, loops = SHAPES, args.calls, args.loops
        dev = jax.devices("tpu")[0]       # no chip, no number
    rng = np.random.RandomState(args.seed % 2**32)
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    budget = gm._STEP_BYTES
    lines = []

    def timed(fn, x, ops):
        """Milliseconds a call ON THE DEVICE: ``loops`` calls in one
        program, each call's rows waiting for the one before (their
        values unchanged); and the result of one more call."""
        def chain(x, *ops):
            def body(_, x):
                return x.at[0, 0].add((fn(x, *ops)[0, 0] * 0).astype(x.dtype))
            x = lax.fori_loop(0, loops, body, x)
            return fn(x, *ops)
        chain = jax.jit(chain)
        jax.block_until_ready(chain(x, *ops))
        ts = []
        for _ in range(calls):
            t = time.perf_counter()
            got = jax.block_until_ready(chain(x, *ops))
            ts.append(time.perf_counter() - t)
        return float(np.median(ts)) * 1e3 / (loops + 1), got

    for name, (t, k, n, e, d, h) in shapes.items():
        rows, sizes, gates, back = routed(rng, t, k, n, e)
        key = jax.random.PRNGKey(args.seed % 2**31)
        with jax.default_device(dev):
            ks = jax.random.split(key, 4)
            x = jax.random.normal(ks[0], (t, d), jnp.bfloat16)
            ws = [(jax.random.normal(kk, shape, jnp.bfloat16)
                   * (2.0 / sum(shape[1:])) ** 0.5).astype(jnp.bfloat16)
                  for kk, shape in zip(ks[1:], [(n, d, h)] * 2 + [(n, h, d)])]
            ops = [jnp.asarray(a) for a in (rows, sizes, gates, back)] + ws
        touched = int((sizes > 0).sum())
        variants = [("definition", None, gm._definition)]
        steps = (budget // 2, budget, budget * 2) \
            if name.endswith("decode") else (budget,)
        for step in steps:
            def kernel(*a, step=step):
                gm._STEP_BYTES = step           # read while tracing
                return gm._kernel_sum(*a)
            variants.append(("kernel", step, kernel))
        want = None
        for variant, step, fn in variants:
            ms, got = timed(fn, x, ops)
            block = step and gm._block(d, h, 2)
            gm._STEP_BYTES = budget
            got = np.asarray(got)
            want = got if want is None else want
            line = {
                "shape": name, "variant": variant,
                "step_MiB": step and step / 2**20, "block_h": block,
                "ms": round(ms, 4), "rows": t * k,
                "held_rows": int(sizes.sum()), "touched": touched,
                "held": n, "largest_group": int(sizes.max()),
                "touched_GB_per_s": round(
                    touched * 3 * d * h * 2 / ms / 1e6, 1),
                "max_diff_from_definition": float(np.abs(got - want).max()),
                "largest_value": float(np.abs(want).max()),
                "device": {"platform": dev.platform,
                           "kind": dev.device_kind}}
            print(json.dumps(line), flush=True)
            lines.append(line)
        del x, ws, ops
    with open(os.path.join(out_dir, "expert_ffn_bench.jsonl"), "w") as f:
        f.writelines(json.dumps(x) + "\n" for x in lines)


if __name__ == "__main__":
    main()
