#!/usr/bin/env python
"""The quickest proof that the system still starts on the chip.

Drives the two paths this framework sells, once each, through the entry
points a user calls, in ONE process on ONE TPU chip:

* **train** — BERT-base (768 x 12 layers x 12 heads, FFN 3072, vocab
  30522; no cut) MLM+NSP pretraining, bf16 AMP, through
  ``parallel.DataParallelTrainer(..., fuse_step=True)`` on a one-chip
  mesh at batch 64 x seq 128: one cold ``step()``, warm ``step()``s, a
  ``step_multi(repeat=4)``; checked against the same first step in
  float32 at ``jax.default_matmul_precision("highest")``.
* **serve** — ``serving.Server`` over Mistral-7B-v0.1 widths
  (4096/14336, 32 heads, 8 KV heads, window 4096, vocab 32000, untied
  head) CUT IN DEPTH 32 -> 8 layers, bf16 weights and KV pages, two
  buckets, warm-started through the persistent tier, 8 seeded prompts
  run to completion; greedy tokens checked against a plain
  full-sequence forward.  Then the Pallas flash kernel, forward and
  backward, COMPILED, at shapes the attention policy does send to it,
  checked against ``_sdpa_xla`` at highest precision.

``--chips 4`` runs instead, and only, the data-parallel step over a
``{"dp": 4}`` mesh (dense and ZeRO-2) against the one-chip step.

Every line of standard output is one JSON object.  The LAST line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``
and is printed only when every check passed on a TPU at full size.  Any
failed check or exception is a traceback and a non-zero exit: nothing on
this path catches one.  The script never sets ``JAX_PLATFORMS`` and has
no CPU mode: with no accelerator it fails before doing anything.
``--tiny`` is the rehearsal (same code, toy shapes, any backend); it
runs the phases and then fails, because toy shapes prove nothing.

Timings printed here are SMOKE timings (one reading, closed by a host
read of the result), not benchmark numbers.
"""
import argparse
import gc
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)
from tools import jax_cache

# before jax is imported (see tools/jax_cache.py)
CACHE_DIR = jax_cache.place()

import numpy as np

FULL = {
    "train": dict(builder="bert_base", vocab=30522, batch=64, seq=128,
                  masked=20),
    # Mistral-7B-v0.1 widths; depth is the one cut (32 -> 8 layers,
    # ~2.0 B parameters, ~4 GB in bf16) so weights + KV pages fit one
    # 16 GB chip with room
    "serve": dict(preset="mistral_7b", layers=8, published_layers=32,
                  vocab=32000, buckets=[(8, 256), (4, 1024)],
                  max_new=32,
                  prompt_lens=[12, 57, 130, 256, 300, 511, 800, 1024]),
    # shapes today's policy (ops/attention.py) sends to the kernel:
    # a Mistral prefill at the full 4096 window (seq >= 4096 -> flash),
    # and a banded prefill (window < seq -> flash at any length)
    "flash": [dict(b=1, s=4096, h=32, kv=8, d=128, window=None),
              dict(b=1, s=2048, h=8, kv=8, d=128, window=512)],
}
TINY = {
    "train": dict(builder="bert_small", vocab=512, batch=8, seq=32,
                  masked=4),
    "serve": dict(preset="mistral_tiny", layers=2, published_layers=2,
                  vocab=256, buckets=[(2, 8), (2, 16)], max_new=6,
                  prompt_lens=[2, 3, 5, 8, 9, 12, 14, 16]),
    "flash": [dict(b=1, s=256, h=4, kv=2, d=64, window=64)],
}
N_WARM = 4          # warm step()s after the cold one
K_MULTI = 4         # step_multi(repeat=K)

# |bf16-AMP loss - f32 loss| / f32 loss on the first step.  bf16 keeps 8
# significant bits (relative rounding 2^-9 per operand); the loss is a
# mean over batch x masked positions of a log-softmax whose logits went
# through 12 layers of bf16 matmuls with f32 accumulation, so the
# per-logit error (~1e-2 relative) averages down.  1e-2 of a loss near
# ln(vocab) is ~0.1 nat: far below what wrong weights, a wrong mask or
# a host/chip mix-up would cost, far above bf16 rounding.
AMP_VS_F32_RTOL = 1e-2
# dp=4 / ZeRO-2 against one chip, same weights, batch and (dropout off)
# math: only the order of the gradient reduction and of bf16 partial
# sums differs (per-shard means of bf16 logits, then a mean of means).
# The first loss is the forward alone; the SECOND has been through the
# reduced gradient and one update, so a gradient that was not summed
# over the mesh shows there.  The parameter norm moves by two Adam
# steps of lr 1e-4, sign-like in direction, so it is the weakest of the
# three and held tightest.
# 5e-3 of a loss near ln(vocab) is ~0.05 nat; the CPU rehearsal at toy
# shapes measured 3e-4.  Replicas are separately held BIT-identical.
DP_LOSS_RTOL = 5e-3
DP_NORM_RTOL = 1e-5
# flash kernel (bf16 in and out, f32 accumulate) against _sdpa_xla in
# float32 at highest precision on the same inputs, per tensor, as
# max|kernel - reference| / max|reference|: the kernel's outputs and
# gradients are ROUNDED to bf16 (2^-9 relative at the tensor's own
# scale) and its probabilities are bf16 before the P.V matmul, so a few
# bf16 roundings stack; 2e-2 of the tensor's largest magnitude is four
# of them, and a wrong mask or band is an O(1) error.
FLASH_RTOL = 2e-2
# a served greedy token must be the full-forward argmax, or within this
# share of the reference row's largest |logit| of it: the paged decode
# (bf16 KV pages, one position at a time) and the full-sequence forward
# round differently at each of the layers, ~2^-9 relative per bf16
# rounding on an activation that the residual stream carries to the
# logits, so two near-tied candidates may legitimately swap; a wrong
# page, position or mask picks a token that is nowhere near the top.
GAP_SHARE = 2 ** -5


class SmokeFailure(AssertionError):
    """A check of this script did not hold."""


def check(ok, what):
    # not ``assert``: it is stripped under -O
    if not ok:
        raise SmokeFailure(what)


def emit(**row):
    print(json.dumps(row, default=str), flush=True)


_JAX_CACHE_EVENTS = {"hits": 0, "misses": 0}


def _on_jax_event(name, **_kw):
    if name == "/jax/compilation_cache/cache_hits":
        _JAX_CACHE_EVENTS["hits"] += 1
    elif name == "/jax/compilation_cache/cache_misses":
        _JAX_CACHE_EVENTS["misses"] += 1


def _ctx(i=0):
    """``mx.tpu(i)`` as a user would write it — spelled through the
    platform of jax's first device so the ``--tiny`` rehearsal runs the
    same lines on whatever backend it is given."""
    import jax
    import mxnet_tpu as mx
    return mx.Context(jax.devices()[0].platform, i)


def _peak_bytes(devices):
    out = []
    for d in devices:
        stats = d.memory_stats() or {}
        out.append(stats.get("peak_bytes_in_use"))
    return out


def _on_platform(arrays, want, what):
    """Every jax array of ``arrays`` lives only on ``want`` devices."""
    for name, a in arrays:
        plats = {d.platform for d in a.devices()}
        check(plats == {want},
              f"{what} {name} lives on {sorted(plats)}, not on {want}")


def _no_events(where):
    """Nothing retraced, fell back or lost its AOT executable since the
    last ``telemetry.clear_events()``."""
    from mxnet_tpu import telemetry
    for kind in ("retrace", "fallback", "persist_error"):
        evs = telemetry.events(kind)
        check(not evs, f"{kind} events {where}: {evs}")


def _free():
    from mxnet_tpu import engine
    engine.clear_cache()
    gc.collect()


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------

def _build_bert_trainer(shape, seed, devices, dropout):
    """BERTForPretrain + the fused SPMD trainer on a
    ``{"dp": len(devices)}`` mesh; plus one seeded batch."""
    import mxnet_tpu as mx
    from mxnet_tpu import models, nd, parallel
    from mxnet_tpu.gluon.block import HybridBlock
    from mxnet_tpu.gluon.loss import SoftmaxCrossEntropyLoss

    vocab, b, s, m = (shape["vocab"], shape["batch"], shape["seq"],
                      shape["masked"])
    np.random.seed(seed)        # initializers draw from numpy
    mx.random.seed(seed)
    ctx = _ctx(0)

    class FullLenPretrain(HybridBlock):
        """Full-length sequences need no padding mask."""

        def __init__(self, mod, **kw):
            super().__init__(**kw)
            with self.name_scope():
                self.mod = mod

        def hybrid_forward(self, F, tokens, types, positions):
            return self.mod(tokens, types, None, positions)

    model = FullLenPretrain(models.BERTForPretrain(
        getattr(models, shape["builder"])(
            vocab_size=vocab, max_length=s, dropout=dropout)))
    model.initialize(mx.init.Xavier(), ctx=ctx)
    sce = SoftmaxCrossEntropyLoss()

    def loss_fn(outs, label):
        mlm_scores, nsp_scores = outs
        mlm = sce(mlm_scores, label[:, :m].reshape((-1,))).mean()
        return mlm + sce(nsp_scores, label[:, m]).mean()

    mesh = parallel.make_mesh({"dp": len(devices)}, devices=devices)
    dpt = parallel.DataParallelTrainer(
        model, loss_fn, "adam", {"learning_rate": 1e-4}, mesh=mesh,
        fuse_step=True)
    rng = np.random.RandomState(seed)
    data = tuple(
        nd.array(a.astype("f"), ctx=ctx) for a in (
            rng.randint(0, vocab, (b, s)), rng.randint(0, 2, (b, s)),
            rng.randint(0, s, (b, m))))
    label = nd.array(np.concatenate(
        [rng.randint(0, vocab, (b, m)), rng.randint(0, 2, (b, 1))],
        axis=1).astype("f"), ctx=ctx)
    return model, dpt, data, label


def _first_step(dpt, data, label, seed):
    """The cold step, its loss read back: (loss, seconds).  The key
    stream is re-seeded so two trainers draw the same dropout masks."""
    import mxnet_tpu as mx
    mx.random.seed(seed + 1)
    t0 = time.perf_counter()
    loss = float(dpt.step(data, label).asnumpy())
    return loss, time.perf_counter() - t0


def _param_norm(model):
    import jax.numpy as jnp
    tot = sum(jnp.sum(jnp.square(p.data()._data.astype(jnp.float32)))
              for p in model.collect_params().values())
    return float(jnp.sqrt(tot))


def _compiled_texts(dpt):
    """HLO text of every executable the fused step resolved."""
    return [fn.as_text() for fn in dpt._full_exec[0].values()]


def phase_train(shape, seed):
    import jax
    from mxnet_tpu import engine, telemetry
    from mxnet_tpu.contrib import amp
    from mxnet_tpu.ops import attention as attn

    want = jax.devices()[0].platform
    devices = [_ctx(0).device]

    # the reference first: the same step in float32, highest precision
    model, dpt, data, label = _build_bert_trainer(
        shape, seed, devices, dropout=0.1)
    with jax.default_matmul_precision("highest"):
        ref_loss, ref_s = _first_step(dpt, data, label, seed)
    del model, dpt, data, label
    _free()

    amp.init(target_dtype="bfloat16")
    try:
        model, dpt, data, label = _build_bert_trainer(
            shape, seed, devices, dropout=0.1)
        flash0 = attn.flash_dispatch_count()
        engine.reset_counters()
        cache0 = dict(_JAX_CACHE_EVENTS)
        loss0, cold_s = _first_step(dpt, data, label, seed)
        # jax's persistent cache over the cold step alone: a second run
        # in the same call reads the step program back (hit, no miss)
        cold_cache = {k: _JAX_CACHE_EVENTS[k] - cache0[k]
                      for k in cache0}
        flash_in_step = attn.flash_dispatch_count() - flash0
        losses = [loss0]

        # warm window: every step exactly one dispatch, nothing
        # retraced, nothing demoted
        telemetry.clear_events()
        warm_ms = []
        for _ in range(N_WARM):
            d0 = engine.cache_info()["dispatches"]
            t0 = time.perf_counter()
            losses.append(float(dpt.step(data, label).asnumpy()))
            warm_ms.append((time.perf_counter() - t0) * 1e3)
            d = engine.cache_info()["dispatches"] - d0
            check(d == 1, f"a warm step() took {d} dispatches, not 1")

        t0 = time.perf_counter()
        multi = dpt.step_multi(data, label, repeat=K_MULTI).asnumpy()
        multi_cold_s = time.perf_counter() - t0
        losses.extend(float(x) for x in multi)
        telemetry.clear_events()      # its trace is not a retrace
        d0 = engine.cache_info()["dispatches"]
        t0 = time.perf_counter()
        multi = dpt.step_multi(data, label, repeat=K_MULTI).asnumpy()
        multi_ms = (time.perf_counter() - t0) * 1e3
        d = engine.cache_info()["dispatches"] - d0
        check(d == 1, f"a warm step_multi took {d} dispatches, not 1")
        losses.extend(float(x) for x in multi)
        _no_events("in the warm window")
        info = engine.cache_info()
        check(info["aot_demotions"] == 0,
              f"{info['aot_demotions']} AOT executables were demoted "
              "to plain jit")

        check(all(np.isfinite(losses)), f"non-finite loss: {losses}")
        check(losses[-1] < losses[0],
              f"loss did not fall on the repeated batch: {losses}")
        rel = abs(loss0 - ref_loss) / abs(ref_loss)
        check(rel <= AMP_VS_F32_RTOL,
              f"first-step loss {loss0} (bf16 AMP) vs {ref_loss} "
              f"(float32, highest): relative {rel:.2e} > "
              f"{AMP_VS_F32_RTOL}")

        _on_platform([(p.name, p.data()._data)
                      for p in model.collect_params().values()],
                     want, "parameter")
        _on_platform(dpt._opt_state_leaves(), want, "optimizer state")
        _on_platform([(f"batch[{i}]", hit[2]) for i, hit in
                      enumerate(dpt._placed.values())]
                     + [(f"input[{i}]", a._data) for i, a in
                        enumerate(data + (label,))],
                     want, "batch array")
        kernel_in_hlo = any("tpu_custom_call" in t
                            for t in _compiled_texts(dpt))
        emit(phase="train", model=shape["builder"], batch=shape["batch"],
             seq=shape["seq"], masked=shape["masked"],
             vocab=shape["vocab"],
             params=sum(int(np.prod(p.shape)) for p in
                        model.collect_params().values()),
             timings="smoke", compile_and_first_step_seconds=cold_s,
             cold_step_jax_compile_cache=cold_cache,
             cold_step_compile_was_a_cache_hit=(
                 cold_cache["hits"] > 0 and cold_cache["misses"] == 0),
             reference_f32_first_step_seconds=ref_s,
             warm_step_ms=warm_ms, step_multi_k=K_MULTI,
             step_multi_cold_seconds=multi_cold_s,
             step_multi_warm_ms=multi_ms,
             dispatches_per_warm_step=1, dispatches_per_step_multi=1,
             losses=losses, first_loss_f32_reference=ref_loss,
             first_loss_rel_diff=rel, rel_tolerance=AMP_VS_F32_RTOL,
             fresh_compiles=info["fresh_compiles"],
             aot_demotions=info["aot_demotions"],
             flash_dispatches_at_trace=flash_in_step,
             flash_kernel_in_compiled_step=kernel_in_hlo,
             peak_bytes_in_use=_peak_bytes(devices),
             jax_compile_cache=dict(_JAX_CACHE_EVENTS))
    finally:
        amp._deinit()
    del model, dpt, data, label
    _free()


# ---------------------------------------------------------------------------
# four chips: dp=4 and ZeRO-2 against one chip
# ---------------------------------------------------------------------------

def _one_dp_step(shape, seed, devices, zero_stage):
    """Build, take the first step, report what the comparison needs.
    Dropout is OFF here: under dp the masks are drawn per shard, so
    with dropout the one-chip and four-chip steps are different draws
    of the same distribution and could only be compared loosely."""
    import jax
    from mxnet_tpu import engine

    if zero_stage:
        os.environ["MXTPU_ZERO_STAGE"] = str(zero_stage)
    else:
        os.environ.pop("MXTPU_ZERO_STAGE", None)
    try:
        model, dpt, data, label = _build_bert_trainer(
            shape, seed, devices, dropout=0.0)
        engine.reset_counters()
        loss, cold_s = _first_step(dpt, data, label, seed)
        t0 = time.perf_counter()
        loss2 = float(dpt.step(data, label).asnumpy())
        warm_ms = (time.perf_counter() - t0) * 1e3
    finally:
        os.environ.pop("MXTPU_ZERO_STAGE", None)
    n = len(devices)
    # the first loss is computed BEFORE any update (the forward alone);
    # the second has been through one reduced gradient and one update
    row = dict(dp=n, zero_stage=zero_stage, first_loss=loss,
               second_loss=loss2, timings="smoke",
               compile_and_first_step_seconds=cold_s,
               second_step_ms=warm_ms,
               aot_demotions=engine.cache_info()["aot_demotions"],
               peak_bytes_in_use=_peak_bytes(devices))
    check(row["aot_demotions"] == 0, "an AOT executable was demoted")
    if n > 1:
        import jax.numpy as jnp
        for p in model.collect_params().values():
            shards = p.data()._data.addressable_shards
            on = {s.device for s in shards}
            check(on == set(devices),
                  f"parameter {p.name} is addressable on {len(on)} "
                  f"device(s), not on the {n} of the mesh")
            # replicas that each applied a LOCAL gradient would drift
            # apart; after two updates they must still be identical
            sums = {float(jnp.sum(s.data.astype(jnp.float32)))
                    for s in shards}
            check(len(sums) == 1,
                  f"replicas of {p.name} diverged after two steps: "
                  f"{sorted(sums)}")
        per = shape["batch"] // n
        for hit in dpt._placed.values():
            rows = sorted((s.device.id, s.data.shape[0])
                          for s in hit[2].addressable_shards)
            check([r for _d, r in rows] == [per] * n
                  and len({d for d, _r in rows}) == n,
                  f"batch array is split {rows}, not {per} rows on "
                  f"each of {n} devices")
        row["batch_rows_per_device"] = per
        # what the step ASKS of the wire (the wire auditor's walk of
        # its jaxpr; GSPMD's implicit dense all-reduce is its derived
        # leg) and what the compiler EMITTED (the executable's text)
        from mxnet_tpu.analysis import wire_passes
        legs = wire_passes.wire_report()[f"spmd:{model.name}"]["legs"]
        asked = sorted({leg["op"] for leg in legs
                        if not leg["obs_only"]})
        text = "\n".join(_compiled_texts(dpt))
        found = {op: text.count(f" {op}(") + text.count(f" {op}-start(")
                 for op in ("all-reduce", "reduce-scatter",
                            "all-gather")}
        row["collectives_asked"] = asked
        row["collectives_in_compiled_step"] = found
        if zero_stage >= 2:
            # the TPU compiler may turn a reduce-scatter into an
            # all-reduce plus a slice (it did for every one of this
            # step's when compiled for a described v5e:2x2), so the
            # compiled text is held to "a gradient reduction and the
            # weight gather", the request to the exact pair
            check({"reduce-scatter", "all-gather"} <= set(asked),
                  f"ZeRO-2 step does not ask for reduce-scatter + "
                  f"all-gather: {asked}")
            check(found["all-gather"] and
                  (found["reduce-scatter"] or found["all-reduce"]),
                  f"ZeRO-2 executable lacks its collectives: {found}")
        else:
            check(found["all-reduce"],
                  f"dp step lacks the gradient all-reduce: {found}")
    # after the second step: a norm that includes two updates
    row["param_norm_after_2_steps"] = _param_norm(model)
    del model, dpt, data, label
    _free()
    return row


def phase_four_chips(shape, seed, n):
    import jax
    from mxnet_tpu.contrib import amp

    devs = jax.local_devices()
    check(len(devs) >= n, f"--chips {n} but jax has {len(devs)} device(s)")
    devs = devs[:n]
    amp.init(target_dtype="bfloat16")
    try:
        one = _one_dp_step(shape, seed, devs[:1], 0)
        emit(phase="dp", **one)
        for zero in (0, 2):
            row = _one_dp_step(shape, seed, devs, zero)
            dl = max(abs(row[k] - one[k]) / abs(one[k])
                     for k in ("first_loss", "second_loss"))
            dn = abs(row["param_norm_after_2_steps"]
                     - one["param_norm_after_2_steps"]) \
                / one["param_norm_after_2_steps"]
            emit(phase="dp", **row, loss_rel_diff_vs_one_chip=dl,
                 norm_rel_diff_vs_one_chip=dn,
                 tolerances=dict(loss=DP_LOSS_RTOL, norm=DP_NORM_RTOL))
            check(dl <= DP_LOSS_RTOL,
                  f"dp={n} zero={zero} first/second loss differs from "
                  f"one chip by {dl:.2e} > {DP_LOSS_RTOL}")
            check(dn <= DP_NORM_RTOL,
                  f"dp={n} zero={zero} parameter norm differs from "
                  f"one chip by {dn:.2e} > {DP_NORM_RTOL}")
    finally:
        amp._deinit()


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------

def phase_serve(shape, seed):
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import engine, nd, telemetry
    from mxnet_tpu.models import LlamaForCausalLM, get_llama
    from mxnet_tpu.serving import Server

    want = jax.devices()[0].platform
    ctx = _ctx(0)
    vocab = shape["vocab"]
    np.random.seed(seed)
    mx.random.seed(seed)
    t0 = time.perf_counter()
    net = LlamaForCausalLM(
        get_llama(shape["preset"], vocab_size=vocab,
                  num_layers=shape["layers"]), tie_embeddings=False)
    net.cast("bfloat16")
    net.collect_params().setattr("grad_req", "null")
    net.initialize(mx.init.Xavier(), ctx=ctx)
    n_params = sum(int(np.prod(p.shape))
                   for p in net.collect_params().values())
    init_s = time.perf_counter() - t0
    emit(phase="serve", step="model", preset=shape["preset"],
         depth_cut=f"{shape['published_layers']} -> {shape['layers']} "
                   "layers (widths as published)",
         params=n_params, weight_dtype="bfloat16",
         weight_bytes=2 * n_params, init_seconds=init_s)

    rng = np.random.RandomState(seed)
    prompts = [rng.randint(1, vocab, n).astype("f4")
               for n in shape["prompt_lens"]]
    kw = dict(buckets=shape["buckets"], max_new_tokens=shape["max_new"],
              ctx=ctx, cache_dtype="bfloat16")

    # the repo's second tier, at a fixed emptied sub-directory of the
    # cache directory: a first server compiles each bucket's programs
    # (cold), a second one warm-starts from its manifest as a restarted
    # process would, and must then compile nothing
    tier = jax_cache.fresh_subdir("mxtpu_smoke_serve")
    os.environ["MXTPU_COMPILE_CACHE_DIR"] = tier
    try:
        engine.reset_counters()
        cold = Server(net, **kw)
        t0 = time.perf_counter()
        # one request per bucket reaches prefill + decode of both
        cold.generate([prompts[0], prompts[-1]], max_new_tokens=2)
        cold_s = time.perf_counter() - t0
        cold_compiles = engine.cache_info()["fresh_compiles"]
        manifest = cold.save_signature(os.path.join(tier, "serve.json"))
        del cold
        _free()

        engine.reset_counters()
        srv = Server(net, **kw)
        t0 = time.perf_counter()
        check(srv.warm_start(manifest),
              "Server.warm_start refused its own manifest: "
              f"{telemetry.events('warm_start')[-1:]}")
        warm_s = time.perf_counter() - t0
        info = engine.cache_info()
        check(info["fresh_compiles"] == 0,
              f"warm start compiled {info['fresh_compiles']} programs "
              "fresh; the persistent tier should have served them")

        _on_platform([(p.name, p.data(ctx)._data)
                      for p in net.collect_params().values()],
                     want, "weight")
        _on_platform([(f"page[{k}][{i}]", c) for k, pool in
                      srv._pools.items()
                      for i, c in enumerate(pool.flat())],
                     want, "KV page")

        telemetry.clear_events()
        reqs = [srv.submit(p) for p in prompts]
        decode_ms, rounds = [], 0
        # the server reads a round's decode tokens in the NEXT round:
        # the last round of the loop enqueues nothing and only reads
        while not srv.idle():
            check(rounds < 64 + len(reqs) * (shape["max_new"] + 2),
                  "serving loop did not drain")
            busy_before = sum(1 for b in srv.sched.buckets
                              if b.n_active())
            d0 = engine.cache_info()["dispatches"]
            t0 = time.perf_counter()
            st = srv.step()
            dt = (time.perf_counter() - t0) * 1e3
            # one prefill dispatch per admission, then ONE decode
            # dispatch per non-empty bucket
            decodes = engine.cache_info()["dispatches"] - d0 \
                - st["admitted"]
            if st["admitted"]:
                check(busy_before <= decodes <= len(srv.sched.buckets),
                      f"a step() with {st['admitted']} admissions "
                      f"over {busy_before} busy bucket(s) took "
                      f"{decodes} decode dispatches")
            else:
                check(decodes == busy_before,
                      f"a decode-only step() over {busy_before} "
                      f"bucket(s) took {decodes} dispatches")
                decode_ms.append(dt)
            rounds += 1
        for r in reqs:
            check(len(r.generated) == shape["max_new"],
                  f"request {r.id} (prompt {r.prompt_len}) produced "
                  f"{len(r.generated)} of {shape['max_new']} tokens")
        info = engine.cache_info()
        check(info["fresh_compiles"] == 0,
              f"{info['fresh_compiles']} fresh compiles after the "
              "warm-up")
        check(info["aot_demotions"] == 0,
              "a serving program was demoted to plain jit")
        stats = srv.stats()["buckets"]
        for k, s in stats.items():
            check(s["steady_misses"] == 0
                  and s["steady_fresh_compiles"] == 0,
                  f"bucket {k} kept compiling in steady state: {s}")
        _no_events("while serving")

        # greedy parity: one request against a plain full-sequence
        # forward of the same model
        r = reqs[2]
        toks = r.tokens()
        logits = net(nd.array(toks[None, :-1], ctx=ctx)).asnumpy()[0] \
            .astype(np.float32)
        exact, regrets = 0, []
        for i, tok in enumerate(r.generated):
            row = logits[r.prompt_len - 1 + i]
            exact += int(np.argmax(row) == tok)
            regrets.append(float((row.max() - row[tok])
                                 / np.abs(row).max()))
        check(max(regrets) <= GAP_SHARE,
              f"a served greedy token is {max(regrets):.3f} of the "
              f"largest |logit| below the full-sequence forward's "
              f"best (allowed {GAP_SHARE}); per position: {regrets}")
        emit(phase="serve", step="requests", requests=len(reqs),
             prompt_lens=shape["prompt_lens"], buckets=shape["buckets"],
             max_new_tokens=shape["max_new"], timings="smoke",
             cold_compile_and_first_tokens_seconds=cold_s,
             cold_fresh_compiles=cold_compiles,
             warm_start_seconds=warm_s, post_warm_fresh_compiles=0,
             rounds=rounds, decode_only_step_ms=decode_ms,
             decode_dispatches_per_bucket_step=1,
             greedy_positions=len(r.generated),
             greedy_exact_argmax=exact,
             greedy_worst_regret_share=max(regrets),
             gap_share=GAP_SHARE,
             bucket_stats=stats, second_tier_dir=tier,
             peak_bytes_in_use=_peak_bytes([ctx.device]),
             jax_compile_cache=dict(_JAX_CACHE_EVENTS))
    finally:
        os.environ.pop("MXTPU_COMPILE_CACHE_DIR", None)
    del srv, net, reqs
    _free()


def phase_flash(shapes, seed):
    """The Pallas kernel forward and backward through the public op, at
    shapes the policy sends to it; says which programs hold it."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops import attention as attn
    from mxnet_tpu.ops import flash_attention as fa

    dev = _ctx(0).device
    for sh in shapes:
        b, s, h, kv, d, window = (sh[k] for k in
                                  ("b", "s", "h", "kv", "d", "window"))
        ks = jax.random.split(jax.random.PRNGKey(seed), 4)
        q, k, v, g = (jax.device_put(
            jax.random.normal(kk, (b, s, n, d), jnp.bfloat16), dev)
            for kk, n in zip(ks, (h, kv, kv, h)))

        def kernel(q, k, v):
            return attn.dot_product_attention(
                q, k, v, causal=True, window=window, flash=True)

        def reference(q, k, v):
            with jax.default_matmul_precision("highest"):
                return attn._sdpa_xla(
                    q.astype(jnp.float32), k.astype(jnp.float32),
                    v.astype(jnp.float32), None, 1.0 / np.sqrt(d), True,
                    window=window)

        def fwd_bwd(f):
            def run(q, k, v, g):
                out, vjp = jax.vjp(f, q, k, v)
                return (out,) + vjp(g.astype(out.dtype))
            return jax.jit(run)

        n0 = attn.flash_dispatch_count()
        t0 = time.perf_counter()
        lowered = fwd_bwd(kernel).lower(q, k, v, g)
        routed = attn.flash_dispatch_count() - n0
        text = lowered.as_text()
        compiled = lowered.compile()
        compile_s = time.perf_counter() - t0
        got = [np.asarray(x, np.float32) for x in compiled(q, k, v, g)]
        t0 = time.perf_counter()
        jax.block_until_ready(compiled(q, k, v, g))
        run_ms = (time.perf_counter() - t0) * 1e3
        ref_lowered = fwd_bwd(reference).lower(q, k, v, g)
        ref_text = ref_lowered.as_text()
        want = [np.asarray(x, np.float32)
                for x in ref_lowered.compile()(q, k, v, g)]
        errs = {n: float(np.abs(a - w).max() / np.abs(w).max())
                for n, a, w in zip(("out", "dq", "dk", "dv"), got, want)}
        row = dict(phase="flash", shape=sh, dtype="bfloat16",
                   timings="smoke", compile_seconds=compile_s,
                   fwd_bwd_ms=run_ms, policy_routed_to_kernel=routed,
                   interpret_mode=bool(fa._INTERPRET),
                   tpu_custom_call_in_kernel_program=
                   "tpu_custom_call" in text,
                   tpu_custom_call_in_reference_program=
                   "tpu_custom_call" in ref_text,
                   max_err_over_max_ref_vs_sdpa_xla_highest=errs,
                   rtol=FLASH_RTOL)
        emit(**row)
        check(routed >= 1, f"the attention policy did not send {sh} to "
                           "the flash kernel")
        check(all(np.isfinite(x).all() for x in got),
              f"flash kernel produced non-finite values at {sh}")
        check(max(errs.values()) <= FLASH_RTOL,
              f"flash kernel vs _sdpa_xla at {sh}: {errs} > {FLASH_RTOL}")
        if dev.platform == "tpu":
            check(not fa._INTERPRET and
                  row["tpu_custom_call_in_kernel_program"],
                  "on a TPU the kernel must run compiled (Mosaic), "
                  "not interpreted")
        del q, k, v, g, got, want
    gc.collect()


# ---------------------------------------------------------------------------

def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--phase", choices=["train", "serve", "all"],
                    default="all")
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4],
                    help="4: run only the dp=4 / ZeRO-2 comparison")
    ap.add_argument("--tiny", action="store_true",
                    help="rehearsal shapes; never ends in ok")
    args = ap.parse_args(argv)

    import jax
    import jaxlib
    jax.monitoring.register_event_listener(_on_jax_event)
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    if not args.tiny:
        check(dev.platform == "tpu",
              f"jax's first device is {dev.platform!r}, not a TPU; "
              "this script proves the chip and has no other mode")
    check(device["count"] >= args.chips,
          f"jax sees {device['count']} device(s); --chips "
          f"{args.chips} needs that many")
    try:
        import libtpu
        libtpu_version = libtpu.__version__
    except ImportError:
        libtpu_version = None
    emit(phase="start", seed=args.seed, tiny=args.tiny,
         jax=jax.__version__, jaxlib=jaxlib.__version__,
         libtpu=libtpu_version, device=device,
         jax_compilation_cache_dir=CACHE_DIR,
         cache_dir_entries_at_start=len(os.listdir(CACHE_DIR))
         if os.path.isdir(CACHE_DIR) else 0)

    shapes = TINY if args.tiny else FULL
    if args.chips == 4:
        phase_four_chips(shapes["train"], args.seed, 4)
    else:
        if args.phase in ("train", "all"):
            phase_train(shapes["train"], args.seed)
        if args.phase in ("serve", "all"):
            phase_serve(shapes["serve"], args.seed)
            phase_flash(shapes["flash"], args.seed)

    # programs under jax's minimum compile time are never stored, so a
    # warm run still shows a few misses
    emit(phase="done", jax_compilation_cache_dir=CACHE_DIR,
         jax_compile_cache=dict(_JAX_CACHE_EVENTS))
    check(dev.platform == "tpu",
          f"the phases ran, but on platform {dev.platform!r}, not tpu")
    check(not args.tiny, "--tiny is a rehearsal: toy shapes prove "
                         "nothing about the chip")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
