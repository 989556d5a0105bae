#!/usr/bin/env python
"""BERT pretraining (MLM + NSP) through the SPMD data-parallel trainer.

Parity model: GluonNLP's BERT pretraining scripts (BASELINE config #3).
The step is compiled as ONE XLA program over the device mesh:
forward + backward + psum(grads) + optimizer update — the kvstore
push/pull of the reference collapses into in-graph collectives
(``mx.parallel.DataParallelTrainer``).  bf16 matmuls via AMP.

    python example/bert_pretrain.py --config bert_base --ctx tpu
    python example/bert_pretrain.py --config bert_small --vocab 1000 \
        --batch-size 4 --seq-len 32 --steps 3          # CI smoke
"""
import argparse
import time

import numpy as np

import os as _os
import sys as _sys

# run from a plain checkout: make the repo importable
_sys.path.insert(0, _os.path.dirname(_os.path.dirname(
    _os.path.abspath(__file__))))
from tools import jax_cache

jax_cache.place()   # before jax: compiled programs survive a restart

import mxnet_tpu as mx
from mxnet_tpu import nd, parallel, models
from mxnet_tpu.contrib import amp
from mxnet_tpu.gluon.loss import SoftmaxCrossEntropyLoss


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--config", default="bert_small",
                    choices=["bert_small", "bert_base", "bert_large"])
    ap.add_argument("--ctx", default="cpu", choices=["cpu", "tpu"])
    ap.add_argument("--vocab", type=int, default=30522)
    ap.add_argument("--batch-size", type=int, default=32)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--num-masked", type=int, default=20)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--lr", type=float, default=1e-4)
    ap.add_argument("--no-amp", action="store_true")
    ap.add_argument("--bulk", type=int, default=1,
                    help="K fused steps per dispatch (step_multi: one "
                         "compiled lax.scan over K optimizer steps — "
                         "amortizes per-dispatch host cost)")
    args = ap.parse_args()

    ctx = mx.tpu() if args.ctx == "tpu" else mx.cpu()
    if not args.no_amp:
        amp.init(target_dtype="bfloat16")

    builder = getattr(models, args.config)
    model = models.BERTForPretrain(
        builder(vocab_size=args.vocab, max_length=args.seq_len,
                dropout=0.1))
    model.initialize(mx.init.Xavier(), ctx=ctx)

    sce = SoftmaxCrossEntropyLoss()
    b, m = args.batch_size, args.num_masked

    def loss_fn(outs, label):
        mlm_scores, nsp_scores = outs
        mlm_labels = label[:, :m].reshape((-1,))
        nsp_labels = label[:, m]
        return sce(mlm_scores, mlm_labels).mean() + \
            sce(nsp_scores, nsp_labels).mean()

    # data parallel over every local device (mesh=1 on a single chip;
    # the same code shards the batch across a pod slice)
    n_dev = max(1, mx.num_tpus()) if args.ctx == "tpu" else 1
    mesh = parallel.make_mesh({"dp": n_dev})
    dpt = parallel.DataParallelTrainer(model, loss_fn, "adam",
                                      {"learning_rate": args.lr},
                                      mesh=mesh,
                                      fuse_step=args.bulk > 1)

    rng = np.random.RandomState(0)
    tokens = nd.array(rng.randint(0, args.vocab,
                                  (b, args.seq_len)).astype("f"), ctx=ctx)
    types = nd.array(rng.randint(0, 2,
                                 (b, args.seq_len)).astype("f"), ctx=ctx)
    vlen = nd.array(np.full((b,), args.seq_len, "f"), ctx=ctx)
    positions = nd.array(rng.randint(0, args.seq_len,
                                     (b, m)).astype("f"), ctx=ctx)
    label = nd.array(np.concatenate(
        [rng.randint(0, args.vocab, (b, m)),
         rng.randint(0, 2, (b, 1))], axis=1).astype("f"), ctx=ctx)
    data = (tokens, types, vlen, positions)

    print(f"compiling {args.config} pretraining step "
          f"(batch={b}, seq={args.seq_len}, mesh dp={n_dev}, "
          f"bulk={args.bulk}) ...")
    # one loop serves both paths: bulked calls run K optimizer steps
    # per dispatch (step_multi scans the fused step), so the call
    # count shrinks by K while samples/sec counts real steps
    if args.bulk > 1:
        data = tuple(nd.array(np.broadcast_to(
            a.asnumpy()[None], (args.bulk,) + a.shape).copy(), ctx=ctx)
            for a in data)
        label = nd.array(np.broadcast_to(
            label.asnumpy()[None], (args.bulk,) + label.shape).copy(),
            ctx=ctx)
        run = dpt.step_multi
    else:
        run = dpt.step
    n_calls = max(1, args.steps // args.bulk)
    loss = run(data, label)
    loss.wait_to_read()
    tic = time.time()
    for _ in range(n_calls):
        loss = run(data, label)
    last = float(np.asarray(loss.asnumpy()).ravel()[-1])
    dt = time.time() - tic
    sps = b * n_calls * args.bulk / dt
    print(f"{args.config}: {sps:.2f} samples/sec/chip "
          f"(bulk={args.bulk}, loss={last:.3f})")
    if not args.no_amp:
        amp._deinit()
    return sps


if __name__ == "__main__":
    main()
