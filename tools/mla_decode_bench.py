#!/usr/bin/env python3
"""The absorbed latent attention of ONE layer of a decode round, timed ON
THE CHIP at the shapes of ``pangu_ultra_moe_ep16.longgen_closed`` (160
rows, 128 heads over 576-wide rows, a page of 3,072 positions,
bfloat16), the definition beside the kernel.

    chiprun -- python tools/mla_decode_bench.py

Offsets are drawn as the cell's window sees them: 160 slots that each
serve the traffic file's requests back to back (prompt lognormal median
384, output median 1,024, one position a round), looked at in a round
between the ramp's end and the window's; ``--round-ms`` sets how many
rounds that is (a faster round lengthens the live rows).  One JSON line
a variant, each with its milliseconds a call on the device (``--loops``
calls chained in one program, median of ``--calls`` such programs after
a warm-up), the bytes and operations the LIVE positions need
(``chipbench``'s own count: 278,528 operations a position a row, the
row's 1,152 bytes) over that time, and its largest difference from the
definition:

* ``dense``: ``_attend_dense``, the three XLA passes over the whole page
  (the parent's decode path and the definition);
* ``walk``: ``_walk_call``, the kernel the op takes on a TPU: a ``(B,)``
  grid, the page left in HBM, a loop of dynamic length over a row's live
  blocks with double-buffered copies, at ``_BLK`` 256, 512 and 1,024;
* ``grid``: the simpler shape the kernel was weighed against (PERF.md
  section 6, PR 36): a ``(B, C / blk)`` grid whose index map clamps to
  the row's last live block and whose body runs under ``pl.when``.

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


def cell_offsets(rng, slots, cache_len, round_ms, ramp_s=12.0, window_s=51.0):
    """(slots,) positions: each slot's live length less one in a round
    drawn between the ramp's end and the window's."""
    import numpy as np

    def length(median, sigma, lo, hi):
        return int(np.clip(np.rint(median * np.exp(sigma * rng.randn())),
                           lo, hi))

    at = int(rng.uniform(ramp_s, ramp_s + window_s) * 1e3 / round_ms)
    out = np.zeros(slots, np.int32)
    for i in range(slots):
        t = 0
        while True:
            prompt = length(384, 0.8, 32, 1024)
            new = length(1024, 0.5, 128, 2048)
            if t + new > at:
                out[i] = min(prompt + at - t, cache_len - 1)
                break
            t += new
    return out


def grid_call(qq, page, off, rkv, scale, blk, interpret):
    """The ``(B, C / blk)`` grid form: Pallas moves the blocks, a step
    past a row's last live block re-names that block (no copy) and does
    nothing."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    from mxnet_tpu.ops.latent_attention import _attend_block
    b, c, r = page.shape
    h = qq.shape[1]
    f32 = jnp.float32
    last = jnp.clip(off, 0, c - 1)

    def kernel(last_ref, q_ref, page_ref, out_ref, m_ref, l_ref, acc_ref):
        i, j = pl.program_id(0), pl.program_id(1)
        end = last_ref[i] // blk

        @pl.when(j == 0)
        def _():
            m_ref[...] = jnp.full(m_ref.shape, -jnp.inf, f32)
            l_ref[...] = jnp.zeros(l_ref.shape, f32)
            acc_ref[...] = jnp.zeros(acc_ref.shape, f32)

        @pl.when(j < end)
        def _():
            _attend_block(q_ref[0], page_ref[0], None, m_ref, l_ref,
                          acc_ref, scale)

        @pl.when(j == end)
        def _():
            pos = j * blk + lax.broadcasted_iota(jnp.int32, (h, blk), 1)
            _attend_block(q_ref[0], page_ref[0], pos <= last_ref[i], m_ref,
                          l_ref, acc_ref, scale)
            out_ref[0] = (acc_ref[...] / l_ref[...]).astype(out_ref.dtype)

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(b, c // blk),
            in_specs=[pl.BlockSpec((1, h, r), lambda i, j, o: (i, 0, 0)),
                      pl.BlockSpec((1, r, blk), lambda i, j, o: (
                          i, 0, jnp.minimum(j, o[i] // blk)))],
            out_specs=pl.BlockSpec((1, h, rkv), lambda i, j, o: (i, 0, 0)),
            scratch_shapes=[pltpu.VMEM((h, 1), f32), pltpu.VMEM((h, 1), f32),
                            pltpu.VMEM((h, rkv), f32)]),
        out_shape=jax.ShapeDtypeStruct((b, h, rkv), qq.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret, name="latent_attend_grid",
    )(last, qq, jnp.transpose(page, (0, 2, 1)))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rehearse", action="store_true",
                    help="toy shapes, the Pallas interpreter, any backend")
    ap.add_argument("--seed", type=int, default=2147494001)
    ap.add_argument("--round-ms", default="36,24",
                    help="the round lengths the offsets are drawn at")
    ap.add_argument("--calls", type=int, default=5)
    ap.add_argument("--loops", type=int, default=50,
                    help="calls chained inside one timed program")
    args = ap.parse_args()

    from tools import jax_cache
    jax_cache.place()
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax
    from mxnet_tpu.ops import latent_attention as la

    if args.rehearse:
        b, h, rkv, dr, c, blks, calls, loops = 6, 8, 32, 16, 512, (128, 256), 1, 2
        la._INTERPRET = True
        dev = jax.devices()[0]
    else:
        b, h, rkv, dr, c, blks = 160, 128, 512, 64, 3072, (256, 512, 1024)
        calls, loops = args.calls, args.loops
        dev = jax.devices("tpu")[0]       # no chip, no number
    scale = float(128 + 64) ** -0.5
    rng = np.random.RandomState(args.seed % 2**32)
    dt = jnp.bfloat16
    with jax.default_device(dev):
        qq = jnp.asarray(rng.randn(b, h, rkv + dr), dt)
        # what a slot holds past its offset is an evicted request's rows
        page = jnp.asarray(rng.randn(b, c, rkv + dr), dt)
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    lines = []

    def timed(fn, q, p, o):
        """Milliseconds a call ON THE DEVICE: ``loops`` calls in one
        program, each call's query and offsets waiting for the one before
        (a host clock around one call reads the launch, ~1 ms, not the
        kernel; operands the loop did not touch would let the compiler
        lift the dense scores out of it); and one call's result."""
        def chain(q, p, o):
            def body(_, qo):
                q, o = qo
                nought = fn(q, p, o)[0, 0, 0] * 0
                return (q.at[0, 0, 0].add(nought.astype(q.dtype)),
                        o + nought.astype(o.dtype))
            return lax.fori_loop(0, loops, body, (q, o))
        chain = jax.jit(chain)
        jax.block_until_ready(chain(q, p, o))
        ts = []
        for _ in range(calls):
            t = time.perf_counter()
            jax.block_until_ready(chain(q, p, o))
            ts.append(time.perf_counter() - t)
        return float(np.median(ts)) * 1e3 / loops, jax.jit(fn)(q, p, o)

    for round_ms in (float(x) for x in args.round_ms.split(",")):
        off_np = cell_offsets(rng, b, c, round_ms) if not args.rehearse \
            else rng.randint(0, c, b).astype(np.int32)
        off = jax.device_put(jnp.asarray(off_np), dev)
        live = int(off_np.sum() + b)
        variants = [("dense", None,
                     lambda q, p, o: la._attend_dense(q, p, o, rkv, scale))]
        for blk in blks:
            def walk(q, p, o, blk=blk):
                la._BLK = blk               # read while tracing
                return la._walk_call(q, p, o, rkv, scale)
            variants.append(("walk", blk, walk))
        for blk in blks[:2]:
            variants.append(("grid", blk, lambda q, p, o, blk=blk: grid_call(
                q, p, o, rkv, scale, blk, la._INTERPRET)))
        want = None
        for name, blk, fn in variants:
            ms, got = timed(fn, qq, page, off)
            got = np.asarray(got.astype(jnp.float32))
            want = got if want is None else want
            walked = int((off_np // blk + 1).sum() * blk) if blk else b * c
            line = {
                "variant": name, "blk": blk, "round_ms": round_ms,
                "ms": round(ms, 4), "mean_live": round(live / b, 1),
                "live_share_of_walked": round(live / walked, 4),
                "live_GB_per_s": round(live * (rkv + dr) * 2 / ms / 1e6, 1),
                "live_TFLOP_per_s": round(
                    live * 2 * h * (2 * rkv + dr) / ms / 1e9, 2),
                "max_diff_from_dense": float(np.abs(got - want).max()),
                "largest_value": float(np.abs(want).max()),
                "device": {"platform": dev.platform,
                           "kind": dev.device_kind}}
            print(json.dumps(line), flush=True)
            lines.append(line)
    with open(os.path.join(out_dir, "mla_decode_bench.jsonl"), "w") as f:
        f.writelines(json.dumps(x) + "\n" for x in lines)


if __name__ == "__main__":
    main()
