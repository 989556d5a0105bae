"""Environment-variable registry (parity: the reference's ``MXNET_*``
env-var system, ``docs/.../env_var.md`` — SURVEY.md §5 "Config / flag
system").

One module declares every knob with type, default, and doc; reads go
through :func:`get` so the supported surface is greppable.  The matching
``MXNET_*`` spelling is honoured as a fallback where the reference had
the same knob.
"""
from __future__ import annotations

import os
from typing import Any, Dict, NamedTuple

__all__ = ["get", "registry", "EnvVar"]


class EnvVar(NamedTuple):
    name: str
    type: type
    default: Any
    doc: str
    mxnet_alias: str = ""


_REGISTRY: Dict[str, EnvVar] = {}


def _reg(name, typ, default, doc, mxnet_alias=""):
    _REGISTRY[name] = EnvVar(name, typ, default, doc, mxnet_alias)


_reg("MXTPU_ENGINE_TYPE", str, "",
     "Set to 'NaiveEngine' for synchronous per-op execution "
     "(debugging/determinism). Read ONCE at the first op dispatch "
     "(cached on the hot path) — set it before running any op, not "
     "mid-process.", "MXNET_ENGINE_TYPE")
_reg("MXTPU_TEST_ON_TPU", bool, False,
     "Run the test suite against the real TPU chip instead of the "
     "8-device CPU mesh.")
_reg("MXTPU_FLASH_BLOCK_Q", int, 0,
     "Flash-attention query block size (rows per grid step). 0 = the "
     "measured seq-adaptive default; values that do not divide the "
     "sequence length fall back to it.")
_reg("MXTPU_FLASH_BLOCK_K", int, 0,
     "Flash-attention key block size. 0 = the measured seq-adaptive "
     "default; non-dividing values fall back to it.")
_reg("MXTPU_FLASH_INTERPRET", bool, False,
     "Run the Pallas flash kernel in interpreter mode (any backend; "
     "slow). Read at import of ops.flash_attention — set before "
     "importing, or toggle flash_attention._INTERPRET in tests.")
_reg("MXTPU_FLASH_MODE", str, "auto",
     "Flash-vs-XLA attention dispatch: auto (measured crossover "
     "policy), always (flash whenever viable), never.")
_reg("MXTPU_FLASH_XLA_FROM", int, 0,
     "CAUSAL attention: below this sequence length auto mode prefers "
     "the flash kernel; 0 (default) = XLA SDPA whenever it can "
     "(an in-model A/B read at sha dc2bc5d5 found the Pallas "
     "custom-call a fusion barrier; not measured on today's code: no "
     "benchmark cell runs the kernel yet). "
     "The kernel still takes windowed, HBM-exceeding, and "
     "seq>=UNTIL attention regardless.")
_reg("MXTPU_FLASH_XLA_FROM_NONCAUSAL", int, 0,
     "NON-causal attention: below this sequence length auto mode "
     "prefers the flash kernel; 0 (default) = XLA SDPA whenever it "
     "can — see MXTPU_FLASH_XLA_FROM.")
_reg("MXTPU_FLASH_XLA_UNTIL", int, 4096,
     "Sequence length from which auto mode returns to the flash "
     "kernel regardless: XLA's O(S^2) score tensor becomes the HBM "
     "bottleneck.")
_reg("MXTPU_FLASH_XLA_MAX_SCORE_GB", float, 2.0,
     "HBM budget (GiB) for the f32 score tensor XLA SDPA would "
     "materialize; auto mode falls back to flash above it even "
     "inside the XLA-win window.")
_reg("MXTPU_PRNG_IMPL", str, "auto",
     "Key implementation for mx.random: auto (rbg on accelerator "
     "backends — the hardware-friendly analog of the reference's "
     "counter-based per-device PRNG; threefry on CPU so seeded test "
     "values stay stable), or an explicit threefry2x32 / rbg / "
     "unsafe_rbg. Latched at the first key creation.")
_reg("MXTPU_SEED", int, 0,
     "Global RNG seed override applied at import.", "MXNET_SEED")
_reg("MXTPU_NATIVE_IO", bool, True,
     "Schedule data-pipeline work (prefetch, decode/augment, DataLoader "
     "workers) on the native C++ engine when libmxtpu.so is built; "
     "0 falls back to Python thread pools.")
_reg("MXTPU_NATIVE_IMAGE", bool, True,
     "Run the recognized decode/resize/crop/normalize pipeline as one "
     "native C++ call (libmxtpu_image.so) inside ImageIter workers; "
     "0 keeps the Python augmenter path. Independent of "
     "MXTPU_NATIVE_IO so pool backend and decode stage toggle "
     "separately.")
_reg("MXTPU_ENABLE_X64", bool, False,
     "Enable 64-bit tensor types (int64/float64) via jax_enable_x64. "
     "Off by default: x64 risks silent f64 promotion on TPU hot paths "
     "where the MXU wants bf16/f32. MXNet's float32-default dtype rules "
     "are preserved either way; turn this on for workloads that need "
     "genuine f64/i64 tensors.")
_reg("MXTPU_FUSED_UPDATE", bool, True,
     "Route Trainer.step through the fused one-dispatch multi-tensor "
     "optimizer update (multi_sgd/multi_adam/... with buffer donation) "
     "when the optimizer supports it. 0 restores the per-parameter "
     "update loop (numerically identical; ~P dispatches per step for "
     "P parameters).")
_reg("MXTPU_COMPILED_STEP", bool, True,
     "Route gluon.CompiledStep (Trainer.compile_step) through the "
     "one-dispatch compiled train step: forward + backward + the fused "
     "optimizer update as ONE donated XLA program, with step_multi(K) "
     "bulking K steps per dispatch. 0 forces the eager "
     "record/backward/step path (numerically identical; one dispatch "
     "per op).")
_reg("MXTPU_PREFETCH_TO_DEVICE", bool, False,
     "DataLoader default when prefetch_to_device is not passed: stage "
     "upcoming batches on the device ahead of the consumer so the "
     "async host->device copy overlaps device execution "
     "(double-buffered).")
_reg("MXTPU_PREFETCH_DEPTH", int, 2,
     "How many batches the DataLoader keeps in flight on the device "
     "when prefetch-to-device is active (2 = classic double "
     "buffering).")
_reg("MXTPU_EXEC_BULK_EXEC_TRAIN", bool, True,
     "Accepted for parity; XLA fuses whole graphs at the hybridize "
     "seam so bulking is a no-op.", "MXNET_EXEC_BULK_EXEC_TRAIN")
_reg("MXTPU_COMPILE_CACHE_DIR", str, "",
     "Directory for the persistent compiled-executable cache (the "
     "second tier under the engine's in-memory jit cache): compiled "
     "programs are serialized there and reloaded across process "
     "restarts, keyed by op/attrs/donation/input-avals plus a "
     "jax+jaxlib+PJRT-platform fingerprint. Empty (default) disables "
     "the tier. See docs/compile_cache.md.")
_reg("MXTPU_COMPILE_CACHE_MAX_BYTES", int, 1 << 30,
     "Size bound for MXTPU_COMPILE_CACHE_DIR: on insert, "
     "least-recently-used entries are pruned until the directory fits "
     "(loads refresh recency).")
_reg("MXTPU_TELEMETRY", bool, True,
     "Master switch for the runtime telemetry plane (metrics, "
     "structured events, flight recorder, retrace-cause attribution). "
     "0 disables all recording; instrumented call sites then pay one "
     "attribute load per call.")
_reg("MXTPU_FLIGHT_RECORDER_SIZE", int, 512,
     "Capacity of the flight-recorder event ring (recent dispatches, "
     "retraces, fallbacks, prefetch stalls, poison events). Older "
     "events fall off; the dump records how many were dropped.")
_reg("MXTPU_TELEMETRY_EXPORT", str, "",
     "Directory for telemetry artifacts: flight-recorder dumps and "
     "telemetry.export_metrics() JSONL snapshots. Empty = flight "
     "dumps go to the system temp dir, metric exports to the cwd "
     "(explicit paths always win).")
_reg("MXTPU_DISPATCH_RETRIES", int, 0,
     "Bounded retry for TRANSIENT dispatch failures (runtime/IO "
     "errors with every input buffer still alive): how many times the "
     "engine re-invokes a failed executable before surfacing the "
     "error. 0 (default) disables retry. Post-donation failures "
     "(consumed buffers) are never retried — they take the "
     "poison/recover protocol. See docs/elasticity.md.")
_reg("MXTPU_DISPATCH_BACKOFF_MS", float, 50.0,
     "Base backoff between dispatch retries, in milliseconds. "
     "Decorrelated jitter: attempt k sleeps uniform(base, prev*3), "
     "capped at base*32, so concurrent retriers fan out instead of "
     "hammering the device in lockstep.")
_reg("MXTPU_FAULT_INJECT", str, "",
     "Deterministic fault-injection plan for the elastic subsystem "
     "(';'-separated 'point[:nth=N|step=N|times=K|prob=P|ms=N]' "
     "specs; points: dispatch, dispatch_post, dispatch_hang, "
     "checkpoint_write, host_copy, nonfinite_grad, preempt_signal, "
     "resize_*). prob=P fires each arrival with probability P from "
     "the MXTPU_FAULT_SEED stream (deterministic replay of a random "
     "plan). Read at import of mxnet_tpu.elastic.faults; tests "
     "reconfigure via faults.configure(). Empty (default) injects "
     "nothing. See docs/elasticity.md.")
_reg("MXTPU_FAULT_SEED", int, 0,
     "Seed for the prob= qualifier's RNG in MXTPU_FAULT_INJECT "
     "(elastic.faults) and the default chaos-soak schedule "
     "(elastic.chaos.Schedule): the same seed replays the same "
     "random fault plan exactly. Re-read at every faults.configure().")
_reg("MXTPU_WATCHDOG_TIMEOUT", float, 300.0,
     "Guardian hang watchdog (elastic.guardian.Guardian): seconds a "
     "step/dispatch heartbeat may stay in flight before a retained "
     "hang_suspected event (with per-thread stacks) fires and the "
     "MXTPU_WATCHDOG_ACTION escalation runs.")
_reg("MXTPU_WATCHDOG_ACTION", str, "dump",
     "Guardian escalation on a suspected hang: 'warn' records the "
     "event + counter; 'dump' also writes a flight-recorder "
     "artifact; 'recover' additionally runs the owner's poison->"
     "recover protocol when the hung dispatch resolves poisoned "
     "(a hung dispatch becomes a recovered step, not a dead job). "
     "See docs/elasticity.md (Guardian & chaos soak).")
_reg("MXTPU_DRAIN_DEADLINE_S", float, 30.0,
     "Preemption drain budget (elastic.guardian.PreemptionGuard): "
     "SIGTERM -> committed checkpoint + serving drain must land "
     "inside this many seconds; overruns are recorded on the "
     "preempted event (deadline_ok: false) and warned, not "
     "interrupted (a torn checkpoint would be worse than a late "
     "one).")
_reg("MXTPU_CHECKPOINT_KEEP", int, 3,
     "Default retention for elastic.CheckpointManager: committed "
     "checkpoints beyond the newest N are pruned after each commit.")
_reg("MXTPU_CHECKPOINT_DIR", str, "",
     "Default checkpoint directory for tools/mxckpt.py and the mxlint "
     "elastic integrity pass (MXL502); CheckpointManager itself takes "
     "an explicit directory.")
_reg("MXTPU_HEALTH", bool, True,
     "Training-health plane: compute loss/grad-norm/update-norm/"
     "nonfinite statistics INSIDE the compiled train step (extra "
     "scalar outputs of the same single dispatch) and watch them with "
     "the host sentinel. 0 removes the stats from the traced program "
     "entirely; also inert whenever MXTPU_TELEMETRY=0. See "
     "docs/observability.md (Training health).")
_reg("MXTPU_HEALTH_EVERY", int, 10,
     "Health sampling period K: the device health vector is read back "
     "to the host every K train steps (the read is the plane's only "
     "host sync; its cost on the chip is not measured). K=1 samples "
     "every step.")
_reg("MXTPU_HEALTH_ACTION", str, "warn",
     "What a health verdict does: 'warn' records events only; 'skip' "
     "bakes an in-graph nonfinite gate into the step (a step whose "
     "gradients carry NaN/Inf writes the OLD params/state back out — "
     "the poisoned update becomes a no-op); 'rollback' drives "
     "recover(manager) to the last committed checkpoint on a "
     "nonfinite or sustained-divergence verdict (attach "
     "owner.health_manager). Part of the traced program: flipping it "
     "retraces once, with attribution.")
_reg("MXTPU_HEALTH_WINDOW", int, 64,
     "Rolling-window length (in samples) for the health sentinel's "
     "loss/grad-norm/update-ratio baselines.")
_reg("MXTPU_HEALTH_PATIENCE", int, 3,
     "Consecutive anomalous health samples before the sentinel "
     "escalates to a 'divergence' verdict (the rollback trigger for "
     "non-NaN divergence).")
_reg("MXTPU_INTEGRITY", bool, True,
     "Silent-corruption sentry (elastic.integrity; docs/elasticity.md "
     "'Integrity sentry'): per-dp-replica bitwise fingerprints of the "
     "fused SPMD step's params and post-collective gradients ride the "
     "health vector under the same lax.cond(due) sampling gate, and "
     "the host sentinel audits cross-replica agreement — a minority "
     "replica is flagged as a corruption suspect WITH device "
     "attribution (retained corruption_suspected event). Rides the "
     "health plane: inert whenever MXTPU_HEALTH=0/MXTPU_TELEMETRY=0 "
     "or the mesh has no >1 dp axis (the program is then identical "
     "to a pre-integrity build). 0 removes the fingerprint rows.")
_reg("MXTPU_INTEGRITY_ACTION", str, "warn",
     "What an integrity_divergence verdict does: 'warn' records the "
     "retained corruption_suspected event only; 'rollback' restores "
     "the last committed checkpoint through recover(manager) — the "
     "corrupt state is discarded; 'quarantine' additionally resizes "
     "the live trainer onto a mesh EXCLUDING the suspect device "
     "(ResizeController drain -> reshard -> pre-warmed swap, retained "
     "device_quarantined event). rollback/quarantine need "
     "owner.health_manager attached.")
_reg("MXTPU_SCRUB_EVERY_S", float, 0.0,
     "Background checkpoint-scrub cadence for "
     "CheckpointManager.start_scrub(): every N seconds the committed "
     "shard sha256s are re-verified and a rotten checkpoint is "
     "quarantined out of the restore path (retained scrub_corrupt "
     "event + mxtpu_scrub_* counters). 0 (default) = no background "
     "thread; scrub() stays callable manually.")
_reg("MXTPU_SERVING_SLOTS", int, 4,
     "Default batch slots per serving bucket (concurrent requests one "
     "compiled decode program advances in lockstep) when "
     "serving.Server is constructed without explicit buckets. See "
     "docs/serving.md.")
_reg("MXTPU_SERVING_BUCKETS", str, "32,128",
     "Default prompt-length buckets for serving.Server (comma-"
     "separated): a request lands in the smallest bucket holding its "
     "prompt; each bucket owns one compiled decode program and a "
     "ladder of compiled prefill programs (its length halved down to "
     "256 positions; a prompt is right-padded to the shortest rung "
     "that holds it).")
_reg("MXTPU_SERVING_MAX_NEW_TOKENS", int, 32,
     "Default per-request generation cap for serving.Server; sizes "
     "the KV-cache pages (cache_len = prompt_len bucket + this).")
_reg("MXTPU_SERVING_MAX_QUEUE", int, 128,
     "Bound on the serving wait queue; submissions past it are "
     "rejected with a retained slot_oom telemetry event.")
_reg("MXTPU_ZERO_STAGE", int, 0,
     "ZeRO-style cross-replica sharding of the weight update inside "
     "the fused SPMD step (arXiv 2004.13336; docs/zero.md): 0 (default) "
     "replicates the optimizer update on every dp member; 1 shards "
     "optimizer state + update FLOPs 1/dp per member (all-reduce "
     "gradient leg); 2 additionally reduce-scatters the gradients "
     "(half the gradient wire bytes) and all-gathers only the updated "
     "weights. Read at DataParallelTrainer construction; numerics are "
     "fp32-parity with stage 0, and checkpoints stay portable across "
     "stages and dp sizes.")
_reg("MXTPU_SHARDING_PLAN", str, "",
     "Path to a sharding-plan JSON (parallel.ShardingPlan.save; "
     "docs/parallelism.md 'The sharding planner'). When set, "
     "DataParallelTrainer constructed without an explicit plan= / "
     "param_sharding= adopts it: the plan's named mesh axes, regex "
     "partition rules, ZeRO stage, and pipeline/serving fields become "
     "the single source of truth for every layout decision. A "
     "malformed file raises at construction (a typo'd plan silently "
     "training replicated is the failure mode the planner exists to "
     "kill). Empty (default) = off.")
_reg("MXTPU_RESIZE_UP_QUEUE", int, 4,
     "ServingAutoscaler grow signal: wait-queue depth at/above which "
     "an observation counts toward growing the serving plane's slot "
     "count (elastic.resize; docs/elasticity.md 'Live resize').")
_reg("MXTPU_RESIZE_DOWN_OCCUPANCY", float, 0.25,
     "ServingAutoscaler shrink signal: slot occupancy at/below which "
     "(with an empty queue) an observation counts toward halving the "
     "slot count.")
_reg("MXTPU_RESIZE_PATIENCE", int, 3,
     "Consecutive breaching observations before the ServingAutoscaler "
     "acts — the hysteresis that keeps a bursty queue from flapping "
     "the serving plane.")
_reg("MXTPU_RESIZE_COOLDOWN_S", float, 30.0,
     "Minimum seconds between autoscaler-driven resizes (each resize "
     "pays a drain + migrate, so back-to-back flips are never free).")
_reg("MXTPU_RESIZE_MIN_SLOTS", int, 1,
     "Lower bound on the autoscaled per-bucket slot count.")
_reg("MXTPU_RESIZE_MAX_SLOTS", int, 64,
     "Upper bound on the autoscaled per-bucket slot count (each slot "
     "holds cache_len KV positions of HBM in every bucket).")
_reg("MXTPU_SANITIZE", int, 0,
     "mxsan, the donation-lifetime & lock-order sanitizer "
     "(analysis.sanitizer; docs/static_analysis.md 'The sanitizer'). "
     "0 (default) off — every instrumented seam pays one attribute "
     "load; 1 collects MXL70x findings (use-after-donate, double "
     "donation, poisoned-step, live-bytes leak, lock-order cycle, "
     "lock-across-dispatch) as retained sanitizer_violation events + "
     "mxlint findings; 2 additionally RAISES on a lifetime violation "
     "(MXL701/702) before the bad dispatch runs. Read at import; "
     "tests/tools re-arm via sanitizer.configure(level).")
_reg("MXTPU_WIRE_AUDIT", bool, True,
     "mxwire, the jaxpr-level wire-leg auditor (analysis.wire_passes; "
     "docs/static_analysis.md 'The wire auditor'). When on (default) "
     "the trainers and the serving plane register each compiled "
     "fused-step variant (an abstract aval signature only — no live "
     "buffers) so analyze_wire()/tools/mxwire.py can walk its jaxpr "
     "and check the MXL8xx wire contracts (declared leg precision, "
     "ZeRO-2 wire shape, sampling gates, static-vs-observatory "
     "bytes). 0 disables registration entirely.")
_reg("MXTPU_MEM_REPORT_TOP_N", int, 10,
     "How many programs (sorted by peak per-device bytes) "
     "telemetry.memory.report() and tools/mxmem.py include.")


def registry():
    """All declared env vars (name → EnvVar)."""
    return dict(_REGISTRY)


def get(name: str):
    """Read an env var through the registry (with MXNET_* fallback)."""
    var = _REGISTRY[name]
    raw = os.environ.get(var.name)
    if raw is None and var.mxnet_alias:
        raw = os.environ.get(var.mxnet_alias)
    if raw is None:
        return var.default
    if var.type is bool:
        return raw not in ("", "0", "false", "False")
    return var.type(raw)


def to_markdown():
    """Render the registry as the docs/env_vars.md table (the doc's
    'Generated from' claim is kept true by regenerating via
    ``python -m mxnet_tpu.envs > docs/env_vars.md``)."""
    lines = [
        "# Environment variables",
        "",
        "Generated from `mxnet_tpu/envs.py` (the typed registry; parity:",
        "the reference's `MXNET_*` env-var page). `MXNET_*` aliases are",
        "honoured as fallbacks where the reference had the same knob.",
        "",
        "| Variable | Type | Default | MXNet alias | Description |",
        "|---|---|---|---|---|",
    ]
    for var in _REGISTRY.values():
        alias = f"`{var.mxnet_alias}`" if var.mxnet_alias else "—"
        doc = " ".join(str(var.doc).split())
        lines.append(f"| `{var.name}` | {var.type.__name__} | "
                     f"`{var.default}` | {alias} | {doc} |")
    return "\n".join(lines) + "\n"


if __name__ == "__main__":
    print(to_markdown(), end="")
