"""``mxnet_tpu.telemetry``: the runtime observability plane.

PRs 1-3 built a train stack whose performance contract — one dispatch
per step, zero steady-state retraces, a prefetch pipeline that keeps
the device fed — was only checkable in tests.  This package measures
those invariants continuously:

* **metrics** (``telemetry.metrics``): thread-safe counters / gauges /
  fixed-bucket histograms with ``snapshot()``, Prometheus-text and
  JSONL exporters;
* **events + flight recorder** (``telemetry.recorder``): a bounded
  ring of structured events (dispatch, retrace, fallback,
  prefetch_stall, poison, evict, error) dumped to a JSON artifact on
  failure or on demand, and mirrored into the profiler's chrome-trace
  stream while profiling is active;
* **retrace-cause attribution**: the engine and ``CompiledStep`` emit
  ``retrace`` events carrying the exact attr/shape/dtype diff that
  invalidated a cached executable — "op X retraced because
  ``momentum`` changed 0.9 -> 0.5", not "misses went up".

Master switch: ``MXTPU_TELEMETRY`` (default on) /
:func:`enable` / :func:`disable`.  Disabled, every call site pays one
attribute load and returns.  See docs/observability.md for the metric
schema and event taxonomy.
"""
from __future__ import annotations

from . import _switch
from . import metrics
from .metrics import (Counter, Gauge, Histogram, counter, gauge,
                      histogram, snapshot, reset_metrics, to_prometheus,
                      parse_prometheus, write_jsonl, read_jsonl,
                      DEFAULT_LATENCY_BUCKETS)
from .recorder import (record_event, events, clear_events,
                       dump_flight_recorder, auto_dump, last_dump,
                       note_step, current_step)
from . import memory
from . import health

__all__ = [
    "enabled", "enable", "disable", "reset",
    "Counter", "Gauge", "Histogram", "counter", "gauge", "histogram",
    "snapshot", "reset_metrics", "to_prometheus", "parse_prometheus",
    "write_jsonl", "read_jsonl", "DEFAULT_LATENCY_BUCKETS",
    "record_event", "events", "clear_events", "dump_flight_recorder",
    "auto_dump", "last_dump", "note_step", "current_step",
    "record_step", "step_owner", "step_owned",
    "prefetch_stall_ratio", "export_metrics", "memory", "health",
]

#: dispatch-count boundaries for the per-step dispatch histogram: the
#: compiled path is exactly 1; the eager path is O(ops); powers of two
#: keep the regression signature readable.
DISPATCH_BUCKETS = (1, 2, 4, 8, 16, 32, 64, 128, 256, 512, 1024)


def enabled() -> bool:
    """Is the telemetry plane recording?"""
    return _switch.enabled


def enable():
    _switch.enabled = True


def disable():
    _switch.enabled = False


def reset():
    """Zero every metric, empty the event ring, rewind the global
    step counter, and forget the memory observatory's harvested
    programs (test isolation).  Instrument
    identities survive."""
    from . import recorder
    reset_metrics()
    clear_events()
    recorder._reset_steps()
    memory.reset()
    health.reset()


import threading as _threading

_tls = _threading.local()


#: heartbeat hook installed by ``elastic.guardian`` while a Guardian /
#: PreemptionGuard is live: ``(begin(owner, what) -> token,
#: end(token, exc))``.  None (the default) costs one attribute load
#: per step — the guardian plane is pay-for-what-you-watch.
_hb_hook = None


class _StepOwner:
    """Marks the dynamic extent of a WHOLE-step owner (CompiledStep,
    DataParallelTrainer, a serving dispatch bracket): a
    ``Trainer.step`` running inside it records latency only, so the
    step/throughput accounting is done exactly once per real train
    step.  When the owner identifies itself (``owner=``), the bracket
    doubles as the guardian plane's HEARTBEAT: entry registers the
    in-flight step with the hang watchdog, exit clears it (and lets a
    watching ``Guardian`` run its escalation on the owning thread) —
    see ``elastic.guardian``."""

    __slots__ = ("_owner", "_what", "_tok", "_hook")

    def __init__(self, owner=None, what=None):
        self._owner = owner
        self._what = what
        self._tok = None
        self._hook = None

    def __enter__(self):
        _tls.depth = getattr(_tls, "depth", 0) + 1
        hook = _hb_hook
        if hook is not None and self._owner is not None:
            try:
                self._tok = hook[0](self._owner, self._what)
                self._hook = hook
            except Exception:
                self._tok = None   # a broken watchdog never stops a step
        return self

    def __exit__(self, exc_type, exc, tb):
        _tls.depth -= 1
        if self._tok is not None and self._hook is not None:
            # the ENTRY-time hook, not the global: uninstalling the
            # guardian plane mid-step must still clear this bracket's
            # in-flight record, or it leaks and false-flags the next
            # Guardian's first scan as an ancient hang
            try:
                self._hook[1](self._tok, exc)
            except Exception:
                pass               # escalation errors surface as events


def step_owner(owner=None, what: str = None) -> _StepOwner:
    return _StepOwner(owner, what)


def step_owned() -> bool:
    """Is a whole-step owner currently on this thread's stack?"""
    return getattr(_tls, "depth", 0) > 0


def record_step(where: str, seconds: float, dispatches=None,
                examples=None, path: str = None, steps: int = 1):
    """One call records everything a train step owes the telemetry
    plane: latency histogram (per seam — ``compiled_step``,
    ``trainer_step``, ``spmd_step``), the steps counter, the
    dispatches-per-step distribution, and throughput.

    ``dispatches``: engine-dispatch delta across the step — THE
    one-dispatch contract number.  ``path``: which execution path ran
    (``compiled`` / ``eager`` / ``fused`` / ``per_param``), kept as a
    field on the step event so the flight recorder shows path flips.
    ``steps``: real optimizer steps in this call (``step_multi(K)``
    passes K) — the steps counter advances by it, and a bulked call's
    wall time lands in a separate ``..._bulk_seconds`` histogram so
    the per-step latency distribution stays a distribution of
    measured single steps.
    """
    if not _switch.enabled:
        return
    step = None
    for _ in range(max(1, int(steps))):
        step = note_step()
    suffix = "_seconds" if steps <= 1 else "_bulk_seconds"
    histogram(f"mxtpu_{where}{suffix}",
              f"{where} wall-clock latency (s)"
              + ("" if steps <= 1 else ", per bulked multi-step call")
              ).observe(seconds)
    counter("mxtpu_steps_total", "train steps recorded").inc(
        max(1, int(steps)))
    fields = {"where": where, "seconds": round(seconds, 6)}
    if steps > 1:
        fields["bulked_steps"] = int(steps)
    if path is not None:
        fields["path"] = path
    if dispatches is not None:
        fields["dispatches"] = dispatches
        if steps <= 1:
            # per-step contract numbers only from single-step calls: a
            # bulked call's 1 dispatch covers K steps and would read
            # as a (wrong) per-step value
            gauge("mxtpu_last_step_dispatches",
                  "engine dispatches in the most recent step"
                  ).set(dispatches)
            histogram("mxtpu_step_dispatches",
                      "engine dispatches per train step",
                      buckets=DISPATCH_BUCKETS).observe(dispatches)
    if examples:
        counter("mxtpu_examples_total", "training examples consumed"
                ).inc(examples)
        if seconds > 0:
            gauge("mxtpu_examples_per_sec",
                  "throughput of the most recent step"
                  ).set(examples / seconds)
    record_event("step", **fields)
    return step


def prefetch_stall_ratio() -> float:
    """Fraction of consumed batches on which the consumer found the
    prefetch queue dry (input-bound signature); 0.0 before any loader
    ran."""
    snap = snapshot()["counters"]
    batches = snap.get("mxtpu_dataloader_batches_total", 0.0)
    if not batches:
        return 0.0
    return snap.get("mxtpu_prefetch_stalls_total", 0.0) / batches


def export_metrics(path: str = None) -> str:
    """Append a JSONL metrics snapshot to ``path`` (default:
    ``metrics.jsonl`` under ``MXTPU_TELEMETRY_EXPORT`` or the cwd);
    returns the path written."""
    import os
    from .. import envs
    if path is None:
        out_dir = envs.get("MXTPU_TELEMETRY_EXPORT") or "."
        os.makedirs(out_dir, exist_ok=True)
        path = os.path.join(out_dir, "metrics.jsonl")
    write_jsonl(path)
    return path
