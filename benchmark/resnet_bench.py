"""ResNet-50 images/sec macro benchmark (metric #2).

Parity model: the reference's
``example/image-classification/benchmark_score.py`` (inference img/s
across nets) plus its training-speed tables.  Hybridized whole-graph XLA
on synthetic ImageNet-shaped data, bf16 matmuls via AMP.

Usage::

    python benchmark/resnet_bench.py [--model resnet50_v1]
        [--batch 64] [--train] [--steps 20]

On the CPU backend a tiny image size is substituted so the bench stays a
smoke test; the real number comes from the chip.
"""
from __future__ import annotations

import argparse
import json
import os as _os
import sys
import time

sys.path.insert(0, _os.path.dirname(_os.path.dirname(
    _os.path.abspath(__file__))))


def bench(model_name, batch, image_size, steps, warmup, train,
          use_amp=False):
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon.model_zoo import vision

    on_tpu = bool(mx.num_tpus())
    ctx = mx.tpu() if on_tpu else mx.cpu()

    if use_amp:
        from mxnet_tpu.contrib import amp
        amp.init(target_dtype="bfloat16")

    net = vision.get_model(model_name, classes=1000)
    net.initialize(mx.init.Xavier(), ctx=ctx)
    net.hybridize()

    x = mx.nd.array(
        np.random.rand(batch, 3, image_size, image_size).astype("f4"),
        ctx=ctx)

    if train:
        # the FUSED SPMD step (fwd+bwd+sgd in ONE compiled program) —
        # the path real training uses.  The eager autograd loop pays
        # a host dispatch per CachedOp/backward/param-update and
        # measures dispatch, not the chip.
        from mxnet_tpu import parallel
        y = mx.nd.array(np.random.randint(0, 1000, batch).astype("f4"),
                        ctx=ctx)
        loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
        mesh = parallel.make_mesh({"dp": 1}, devices=[ctx.device])
        dpt = parallel.DataParallelTrainer(
            net, lambda out, label: loss_fn(out, label).mean(),
            "sgd", {"learning_rate": 0.05}, mesh=mesh, fuse_step=True)

        def step():
            return dpt.step(x, y)
    else:
        def step():
            return net(x)

    for _ in range(warmup):
        out = step()
    mx.nd.waitall()
    # chained two-window slope closed by a host materialization; see
    # benchmark/_timing.py
    try:
        from benchmark._timing import time_nd_steps
    except ImportError:
        from _timing import time_nd_steps
    per_step = time_nd_steps(step, iters=max(steps // 3, 2))
    return batch / per_step, on_tpu


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--model", default="resnet50_v1")
    ap.add_argument("--batch", type=int, default=0,
                    help="0 = auto (64 on tpu, 8 on cpu)")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--warmup", type=int, default=3)
    ap.add_argument("--train", action="store_true",
                    help="fwd+bwd+update instead of inference")
    ap.add_argument("--amp", choices=["auto", "on", "off"],
                    default="auto",
                    help="bf16 AMP (auto = on when a TPU is present)")
    args = ap.parse_args(argv)

    import mxnet_tpu as mx
    on_tpu = bool(mx.num_tpus())
    batch = args.batch or (64 if on_tpu else 8)
    image_size = 224 if on_tpu else 64
    use_amp = args.amp == "on" or (args.amp == "auto" and on_tpu)

    print(f"# {args.model} {'train' if args.train else 'inference'} "
          f"batch={batch} image={image_size} tpu={on_tpu} "
          f"amp={use_amp}", file=sys.stderr)
    ips, on_tpu = bench(args.model, batch, image_size, args.steps,
                        args.warmup, args.train, use_amp=use_amp)
    mode = "train" if args.train else "infer"
    row = {"metric": f"{args.model}_{mode}_images_per_sec",
           "value": round(ips, 2), "unit": "images/sec",
           "image_size": image_size, "batch": batch,
           "amp": use_amp,
           "platform": "tpu" if on_tpu else "cpu"}
    print(json.dumps(row), flush=True)
    return row


if __name__ == "__main__":
    main()
