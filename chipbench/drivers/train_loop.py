"""The loop a trainer user writes, timed.

Every step: a fresh batch made on the host from the seed, placed on the
device(s), one ``trainer.step(data, label)``; the loss is read back every
``loss_read_every``-th step only.  The window opens and closes on a
``block_until_ready`` of the newest loss, and every step begun inside it
is finished inside it.
"""
import math
import time

import numpy as np

from chipbench.harness import runtime, traffic as gen


def run(run):
    import jax
    from mxnet_tpu import nd, telemetry

    tr, shapes, checks, spans = run.traffic, run.shapes, run.checks, run.spans
    chips = run.chips
    batch, seq = int(tr["batch_per_chip"]) * chips, int(tr["seq"])
    masked = int(shapes["training"]["masked_positions"])
    every = int(tr["loss_read_every"])
    builder = runtime.builder_for(run)

    with run.setup_phase("import_and_build"):
        model, dpt, ctx = builder.build_trainer(shapes, run.seed, run.devices)
    rng = gen.rng_for(run.seed, 0)
    cdf = gen.zipf_cdf(int(shapes["vocab_size"]),
                       float(tr["token_ids"]["exponent"]))

    def one_step():
        with spans("bench.make_batch"):
            data, label = gen.bert_batch(rng, cdf, batch, seq, masked)
            data = tuple(nd.array(a, ctx=ctx) for a in data)
            label = nd.array(label, ctx=ctx)
        with spans("bench.step_call"):
            return dpt.step(data, label), data, label

    def read(loss):
        with spans("bench.loss_read"):
            return float(loss.asnumpy())

    # warm-up: the first call traces and compiles (or loads) the step
    # program; the rest make sure nothing else is left to compile
    losses = {}                       # step number (from 1) -> loss read
    kept = {}                         # step number -> loss still on device
    with run.setup_phase("first_step"):
        loss, data, label = one_step()
        losses[1] = read(loss)
    with run.setup_phase("warm_steps"):
        for i in range(2, 2 + int(tr["warm_steps"])):
            loss, data, label = one_step()
            losses[i] = read(loss)
    done = len(losses)
    band_lo, band_hi = tr["loss_checks"]["band_steps"]

    telemetry.clear_events()
    jax.block_until_ready(loss._data)
    c0 = runtime.program_counters()
    t0 = run.window_opens()
    steps = 0
    while True:
        loss, data, label = one_step()
        steps += 1
        n = done + steps
        if band_lo <= n <= band_hi:
            kept[n] = loss            # read after the window, not in it
        if steps % every == 0:
            losses[n] = read(loss)
        elapsed = time.perf_counter() - t0
        run.tracer.tick(elapsed)
        if elapsed >= run.seconds:
            break
    jax.block_until_ready(loss._data)
    t1 = time.perf_counter()
    run.tracer.stop()
    c1 = runtime.program_counters()

    # -- correct ----------------------------------------------------------
    band = [float(kept[n].asnumpy()) for n in sorted(kept)]
    band_mean = sum(band) / len(band) if band else None
    lc = tr["loss_checks"]
    checks.hold(all(math.isfinite(x) for x in list(losses.values()) + band),
                f"non-finite loss: {losses} {band}")
    first = lc["first_loss"]
    checks.hold(abs(losses[1] - first["expect"])
                <= first["rtol"] * first["expect"],
                f"first loss {losses[1]} not within {first['rtol']} of "
                f"{first['expect']}")
    if lc.get("band"):
        checks.hold(band_mean is not None
                    and lc["band"][0] <= band_mean <= lc["band"][1],
                    f"mean loss of steps {band_lo}-{band_hi} is {band_mean}, "
                    f"outside {lc['band']}")
    d = c1["dispatches"] - c0["dispatches"]
    checks.hold(d == steps, f"{d} dispatches in {steps} steps, not 1 each")
    for k in ("fresh_compiles", "aot_demotions"):
        checks.hold(c1[k] == c0[k],
                    f"{k} rose by {c1[k] - c0[k]} inside the window")
    runtime.hold_no_events(checks, "inside the window")
    want = run.devices[0].platform
    runtime.hold_on_platform(
        checks, [(p.name, p.data()._data)
                 for p in model.collect_params().values()], want, "parameter")
    runtime.hold_on_platform(checks, dpt._opt_state_leaves(), want,
                             "optimizer state")
    runtime.hold_on_platform(
        checks, [(f"batch[{i}]", hit[2])
                 for i, hit in enumerate(dpt._placed.values())]
        + [(f"input[{i}]", a._data) for i, a in enumerate(data + (label,))],
        want, "batch array")
    extra = {}
    if chips > 1:
        extra = _hold_data_parallel(checks, model, dpt, run.devices,
                                    batch // chips)

    runtime.emit(train=run.workload["name"], batch=batch, seq=seq,
                 masked=masked, steps_in_window=steps,
                 window_s=t1 - t0, steps_before_window=done,
                 losses_read=losses, band_steps=[band_lo, band_hi],
                 band_losses=band, band_mean=band_mean,
                 params=builder.n_params(model),
                 dispatches_in_window=d, **extra)
    return {"attempted": steps, "failed": 0, "steps": steps,
            "window": (t0, t1), "tokens_per_step": batch * seq,
            "samples_per_step": batch,
            "flops_per_sample": builder.flops_per_sample(
                model, shapes, seq, masked),
            "counters": (c0, c1), "spans": spans}


def _hold_data_parallel(checks, model, dpt, devices, per):
    """The batch is split ``per`` rows to each device, the compiled step
    holds an all-reduce, and the replicas are bit-identical after the
    window (chip_smoke.py's dp checks)."""
    import jax.numpy as jnp
    n = len(devices)
    for hit in dpt._placed.values():
        rows = sorted((s.device.id, s.data.shape[0])
                      for s in hit[2].addressable_shards)
        checks.hold([r for _d, r in rows] == [per] * n
                    and len({d for d, _r in rows}) == n,
                    f"batch array is split {rows}, not {per} rows on each "
                    f"of {n} devices")
    text = "\n".join(fn.as_text() for fn in dpt._full_exec[0].values())
    found = text.count(" all-reduce(") + text.count(" all-reduce-start(")
    checks.hold(found > 0, "the dp step holds no all-reduce")
    diverged = []
    for p in model.collect_params().values():
        shards = p.data()._data.addressable_shards
        if {s.device for s in shards} != set(devices):
            diverged.append((p.name, "not on every device"))
            continue
        prints = {np.asarray(jnp.sum(s.data.astype(jnp.float32))).tobytes()
                  for s in shards}
        if len(prints) != 1:
            diverged.append((p.name, "replicas differ"))
    checks.hold(not diverged, f"replicas diverged: {diverged[:3]}")
    return {"all_reduce_in_compiled_step": found,
            "batch_rows_per_device": per}
