#!/usr/bin/env python
"""Fine-tune a Llama checkpoint on a tp×pp device mesh.

The big-model serving/training story end to end (BASELINE config #5):

  1. write (or point at) an HF-layout sharded safetensors checkpoint;
  2. stream it STRAIGHT onto a ``(tp, pp)`` mesh — each device reads
     only its own byte range from the checkpoint mmap
     (``models.llama_spmd.load_llama_stacked``);
  3. run fused 1F1B pipeline fine-tune steps whose loss is the
     streaming large-vocab CE (the (N, V) logits never exist);
  4. reshard-save back to an HF-layout checkpoint any tool can read.

On a CPU host this runs on 8 virtual devices (the default below); on a
TPU pod slice the same code runs over real chips — only the mesh
changes.

    python example/llama_spmd_finetune.py                  # CPU smoke
    python example/llama_spmd_finetune.py --steps 20 --lr 0.05
"""
import argparse
import os as _os
import sys as _sys
import tempfile

_sys.path.insert(0, _os.path.dirname(_os.path.dirname(
    _os.path.abspath(__file__))))

TP, PP = 2, 4


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--checkpoint", default=None,
                   help="HF safetensors file/dir/index (default: write "
                        "a synthetic tiny-llama checkpoint first)")
    p.add_argument("--steps", type=int, default=6)
    p.add_argument("--lr", type=float, default=0.05)
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--seq", type=int, default=32)
    p.add_argument("--vocab", type=int, default=256)
    p.add_argument("--heads", type=int, default=4)
    p.add_argument("--kv-heads", type=int, default=2)
    p.add_argument("--vocab-chunk", type=int, default=64)
    p.add_argument("--out", default=None,
                   help="directory for the resharded save")
    args = p.parse_args()

    if not _os.environ.get("MXTPU_EXAMPLE_ON_TPU"):
        # CPU smoke: 8 virtual devices for the 2x4 mesh
        flags = _os.environ.get("XLA_FLAGS", "")
        if "host_platform_device_count" not in flags:
            _os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8"
            ).strip()
        _os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import nd, parallel
    from mxnet_tpu.models import llama_spmd
    from mxnet_tpu.models.hf_loader import export_hf_llama
    from mxnet_tpu.models.llama import LlamaForCausalLM, get_llama

    tmp = None
    ckpt = args.checkpoint
    if ckpt is None:
        tmp = tempfile.mkdtemp(prefix="llama_ckpt_")
        net = LlamaForCausalLM(get_llama(
            "llama_tiny", vocab_size=args.vocab, num_layers=PP,
            num_heads=args.heads, num_kv_heads=args.kv_heads))
        net.initialize(mx.init.Xavier())
        net(nd.array(np.zeros((1, 4), "f4")))
        export_hf_llama(net, tmp, max_shard_bytes=128 * 1024)
        ckpt = tmp
        print(f"wrote synthetic sharded checkpoint -> {ckpt}")

    mesh = parallel.make_mesh({"tp": TP, "pp": PP})
    params, specs, cfg = llama_spmd.load_llama_stacked(
        ckpt, mesh, num_heads=args.heads, num_kv_heads=args.kv_heads)
    print(f"loaded {cfg['num_layers']} layers onto tp={TP} pp={PP}: "
          f"units={cfg['units']} hidden={cfg['hidden']} "
          f"vocab={cfg['vocab']}")

    rng = np.random.RandomState(0)
    toks = rng.randint(0, cfg["vocab"], (args.batch, args.seq))
    for i in range(args.steps):
        loss, params = llama_spmd.train_step(
            params, toks, cfg, mesh, specs, lr=args.lr,
            vocab_chunk=args.vocab_chunk)
        print(f"step {i}: loss {float(np.asarray(loss)):.4f}")

    # default the save NEXT TO the input checkpoint, never into the
    # caller's cwd
    base = tmp if tmp is not None else (
        ckpt if _os.path.isdir(ckpt) else _os.path.dirname(ckpt) or ".")
    out = args.out or _os.path.join(base, "finetuned")
    llama_spmd.save_llama_stacked(params, out, cfg,
                                  max_shard_bytes=128 * 1024)
    print(f"resharded save -> {out} (HF layout; loadable by "
          f"load_hf_llama or HF tooling)")


if __name__ == "__main__":
    main()
