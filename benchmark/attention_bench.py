#!/usr/bin/env python
"""Flash-vs-XLA attention benchmark: crossover table + block sweep.

Evidence for the Pallas flash kernel claim (SURVEY.md §5 long-context;
VERDICT r3 #4 "win or retire"):  on a TPU it slope-times the Mosaic
kernel against the `_sdpa_xla` reference at growing sequence lengths
(fwd and fwd+bwd, causal and not) and prints a machine-readable
crossover table, ending with the auto-select policy's verdict per
config — every auto-selected path must be >= 1.0x vs XLA within noise.
On CPU it falls back to a tiny interpret-mode correctness sweep
(timings there measure the interpreter, not the kernel, and say so).

    python benchmark/attention_bench.py --seqs 128,512,2048
    python benchmark/attention_bench.py --block-sweep --seqs 2048

Timing: chained two-window slope closed by a host materialization
(benchmark/_timing.py).
"""
import argparse
import json
import os as _os
import sys as _sys
import time

_sys.path.insert(0, _os.path.dirname(_os.path.dirname(
    _os.path.abspath(__file__))))

import numpy as np


def _slope_time(fn, iters=10):
    """Per-call ms via chained two-window slope: each call's output is
    folded into an accumulator the closing host transfer depends on."""
    import jax
    import jax.numpy as jnp
    from benchmark._timing import slope

    def window(n):
        t0 = time.perf_counter()
        acc = None
        for _ in range(n):
            out = fn()
            piece = out.ravel()[0:1]
            acc = piece if acc is None else acc + piece * 1e-30
        float(np.asarray(jax.device_get(acc)).ravel()[0])
        return time.perf_counter() - t0

    fn().block_until_ready()          # compile + warm
    return slope(window, iters) * 1e3


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--seqs", default="128,512,1024")
    p.add_argument("--batch", type=int, default=4)
    p.add_argument("--heads", type=int, default=8)
    p.add_argument("--head-dim", type=int, default=64)
    p.add_argument("--iters", type=int, default=10)
    p.add_argument("--causal", default="1,0",
                   help="comma list of 0/1: which causal settings to run")
    p.add_argument("--block-sweep", action="store_true",
                   help="sweep (block_q, block_k) for the flash bwd at "
                        "each seq (the s>=1024 tuning lever)")
    p.add_argument("--windows", default="",
                   help="comma list of sliding-window widths to time "
                        "per causal seq (flash banded vs XLA banded — "
                        "the O(S·W) block-skip claim)")
    args = p.parse_args()

    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops import flash_attention as fa
    from mxnet_tpu.ops.attention import _sdpa_xla, _flash_preferred

    from mxnet_tpu.base import on_accelerator
    on_tpu = on_accelerator()
    if not on_tpu:
        fa._INTERPRET = True
        print("# CPU backend: interpret-mode correctness sweep "
              "(timings reflect the interpreter, not the kernel)")

    b, h, d = args.batch, args.heads, args.head_dim
    scale = 1.0 / np.sqrt(d)
    causal_set = [bool(int(c)) for c in args.causal.split(",") if c]

    def make_fns(q, k, v, causal):
        flash_f = jax.jit(lambda q, k, v: fa.flash_attention(
            q, k, v, causal=causal))
        xla_f = jax.jit(lambda q, k, v: _sdpa_xla(
            q, k, v, None, scale, causal))
        flash_g = jax.jit(lambda q, k, v: jax.grad(
            lambda q, k, v: fa.flash_attention(
                q, k, v, causal=causal).sum(), argnums=0)(q, k, v))
        xla_g = jax.jit(lambda q, k, v: jax.grad(
            lambda q, k, v: _sdpa_xla(
                q, k, v, None, scale, causal).sum(),
            argnums=0)(q, k, v))
        return flash_f, xla_f, flash_g, xla_g

    rows = []
    for s in [int(x) for x in args.seqs.split(",")]:
        rng = np.random.RandomState(0)
        q = jnp.asarray(rng.randn(b, s, h, d).astype("float32"))
        k = jnp.asarray(rng.randn(b, s, h, d).astype("float32"))
        v = jnp.asarray(rng.randn(b, s, h, d).astype("float32"))

        for causal in causal_set:
            flash_f, xla_f, flash_g, xla_g = make_fns(q, k, v, causal)

            # correctness first, always; on TPU the two paths use
            # different internal precisions for bf16, and f32 matmul
            # accumulation order differs, so bf16-scale tolerance
            tol = 2e-2 if on_tpu else 2e-4
            np.testing.assert_allclose(
                np.asarray(flash_f(q, k, v)),
                np.asarray(xla_f(q, k, v)), rtol=tol, atol=tol)
            if not on_tpu:
                np.testing.assert_allclose(
                    np.asarray(flash_g(q, k, v)),
                    np.asarray(xla_g(q, k, v)), rtol=5e-4, atol=5e-4)
                print(f"seq {s:6d} causal={int(causal)}: numerics OK "
                      "(fwd + bwd)")
                continue

            tf = _slope_time(lambda: flash_f(q, k, v), args.iters)
            tx = _slope_time(lambda: xla_f(q, k, v), args.iters)
            tgf = _slope_time(lambda: flash_g(q, k, v), args.iters)
            tgx = _slope_time(lambda: xla_g(q, k, v), args.iters)
            # mirror the real dispatch decision (batch/heads feed the
            # HBM score-tensor budget) or the recorded auto row could
            # measure a path dot_product_attention would not take
            picked = _flash_preferred(s, s, batch=b, heads=h,
                                      causal=causal)
            t_auto = (tf if picked else tx, tgf if picked else tgx)
            row = {"seq": s, "causal": causal,
                   "fwd_flash_ms": round(tf, 3),
                   "fwd_xla_ms": round(tx, 3),
                   "fwd_ratio": round(tx / tf, 3),
                   "bwd_flash_ms": round(tgf, 3),
                   "bwd_xla_ms": round(tgx, 3),
                   "bwd_ratio": round(tgx / tgf, 3),
                   "auto_picks": "flash" if picked else "xla",
                   "auto_vs_xla_fwd": round(tx / t_auto[0], 3),
                   "auto_vs_xla": round(tgx / t_auto[1], 3)}
            rows.append(row)
            print(json.dumps({"crossover_row": row}), flush=True)

            if causal and args.windows:
                for w in [int(x) for x in args.windows.split(",")
                          if x and int(x) < s]:
                    fw = jax.jit(lambda q, k, v: fa.flash_attention(
                        q, k, v, causal=True, window=w))
                    xw = jax.jit(lambda q, k, v: _sdpa_xla(
                        q, k, v, None, scale, True, window=w))
                    np.testing.assert_allclose(
                        np.asarray(fw(q, k, v)),
                        np.asarray(xw(q, k, v)), rtol=tol, atol=tol)
                    twf = _slope_time(lambda: fw(q, k, v), args.iters)
                    twx = _slope_time(lambda: xw(q, k, v), args.iters)
                    print(json.dumps(
                        {"window_row": {"seq": s, "window": w,
                                        "flash_banded_ms":
                                            round(twf, 3),
                                        "xla_banded_ms":
                                            round(twx, 3),
                                        "flash_vs_full_causal":
                                            round(tf / twf, 3),
                                        "xla_vs_flash_banded":
                                            round(twx / twf, 3)}}),
                        flush=True)

            if args.block_sweep:
                for bq, bk in ((128, 128), (128, 256), (256, 128),
                               (256, 256), (128, 512), (512, 128)):
                    if s % bq or s % bk:
                        continue
                    _os.environ["MXTPU_FLASH_BLOCK_Q"] = str(bq)
                    _os.environ["MXTPU_FLASH_BLOCK_K"] = str(bk)
                    try:
                        gfn = jax.jit(lambda q, k, v: jax.grad(
                            lambda q, k, v: fa.flash_attention(
                                q, k, v, causal=causal).sum(),
                            argnums=0)(q, k, v))
                        t = _slope_time(lambda: gfn(q, k, v),
                                        args.iters)
                        print(json.dumps(
                            {"block_sweep": {"seq": s,
                                             "causal": causal,
                                             "block_q": bq,
                                             "block_k": bk,
                                             "bwd_ms": round(t, 3)}}),
                            flush=True)
                    except Exception as e:  # Mosaic reject etc.
                        print(json.dumps(
                            {"block_sweep": {"seq": s, "block_q": bq,
                                             "block_k": bk,
                                             "error": repr(e)[:200]}}),
                            flush=True)
                    finally:
                        _os.environ.pop("MXTPU_FLASH_BLOCK_Q", None)
                        _os.environ.pop("MXTPU_FLASH_BLOCK_K", None)

    if rows:
        bad = [r for r in rows
               if min(r["auto_vs_xla"], r["auto_vs_xla_fwd"]) < 0.9]
        print(json.dumps({"auto_select_ok": not bad,
                          "configs": len(rows),
                          "below_0.9x": bad}), flush=True)


if __name__ == "__main__":
    main()
