"""Profiler (parity: ``python/mxnet/profiler.py`` over
``src/profiler/profiler.cc`` — SURVEY.md §5 "Tracing / profiling").

One span class, two sinks (docs/observability.md, "Spans"):

* **The profiler's own trace** — every :class:`span` (``record_scope`` is
  its MXNet-shaped alias) is a ``jax.profiler.TraceAnnotation`` whenever
  a jax profiler session is live (``jax.profiler.start_trace``, or
  ``set_config(profile_device=True)`` + ``set_state("run")``), so the
  program's phases land on the ``/host:CPU`` plane of the same XPlane
  trace that holds the device's ``XLA Ops``, on one clock.  The switch
  is jax's own: no session, no event.
* **Chrome events** — while ``set_state("run")`` the same span also
  appends a chrome://tracing complete event (``args`` = its ids), as
  does the engine's per-op hook (``engine._profiler_hook``).  ``dump()``
  writes the JSON, ``dumps()`` an aggregate table — the artifacts the
  reference produced.  These time the host's side of an asynchronous
  dispatch; device time is read from the XPlane trace.
"""
from __future__ import annotations

import json
import threading
import time
from collections import defaultdict
from typing import List

from jax.profiler import StepTraceAnnotation, TraceAnnotation

from .base import MXNetError

__all__ = ["set_config", "set_state", "state", "pause", "resume", "dump",
           "dumps", "Marker", "span", "record_scope"]

_lock = threading.Lock()
_events: List[dict] = []
_state = "stop"
_paused = False
_config = {
    "filename": "profile.json",
    "profile_all": False,
    "profile_symbolic": True,
    "profile_imperative": True,
    "profile_memory": False,
    "profile_api": False,
    "profile_device": False,
    "aggregate_stats": False,
    "device_logdir": "/tmp/mxtpu_xplane",
}
_device_trace_active = False
_t0 = time.perf_counter()
#: whether a jax profiler session is recording ``TraceMe`` events now
_tracing = TraceAnnotation.is_enabled


def _now_us():
    return (time.perf_counter() - _t0) * 1e6


def set_config(**kwargs):
    """Configure (parity: profiler.set_config)."""
    unknown = set(kwargs) - set(_config)
    if unknown:
        raise MXNetError(f"unknown profiler config keys {sorted(unknown)}")
    _config.update(kwargs)


def _record_event(name, cat, start_us, end_us, args=None):
    """Append one chrome-trace complete event (shared schema)."""
    if _paused:
        return
    event = {"name": name, "ph": "X", "ts": start_us,
             "dur": end_us - start_us, "pid": 0,
             "tid": threading.get_ident() % 100000, "cat": cat}
    if args:
        event["args"] = args
    with _lock:
        _events.append(event)


def _hook(name, fn, arrays):
    start = _now_us()
    out = fn(*arrays)
    _record_event(name, "operator", start, _now_us())
    return out


def set_state(state_name="stop", profile_process="worker"):
    """'run' starts collection; 'stop' ends it (parity:
    profiler.set_state)."""
    global _state, _device_trace_active
    from . import engine
    if state_name not in ("run", "stop"):
        raise MXNetError("state must be 'run' or 'stop'")
    if state_name == "run" and _state != "run":
        engine._profiler_hook = _hook
        if _config["profile_device"]:
            import jax
            jax.profiler.start_trace(_config["device_logdir"])
            _device_trace_active = True
    elif state_name == "stop" and _state != "stop":
        engine._profiler_hook = None
        if _device_trace_active:
            import jax
            jax.profiler.stop_trace()
            _device_trace_active = False
    _state = state_name


def state():
    return _state


def pause(profile_process="worker"):
    global _paused
    _paused = True


def resume(profile_process="worker"):
    global _paused
    _paused = False


def dump(finished=True, profile_process="worker"):
    """Write chrome://tracing JSON to the configured filename."""
    with _lock:
        events = list(_events)
        if finished:
            _events.clear()
    with open(_config["filename"], "w") as f:
        json.dump({"traceEvents": events, "displayTimeUnit": "ms"}, f)


def dumps(reset=False, format_="table"):
    """Aggregate per-op stats (parity: profiler.dumps).

    ``format_="table"`` renders the classic fixed-width text table;
    ``format_="json"`` returns the same aggregates as a JSON object
    (``{"ops": {name: {calls, total_us, min_us, max_us, avg_us}}}``)
    for machine consumers.  Unknown formats raise ``MXNetError`` —
    the parameter was previously accepted and silently ignored.
    """
    if format_ not in ("table", "json"):
        raise MXNetError(
            f"unknown dumps format {format_!r} (want 'table' or 'json')")
    with _lock:
        events = list(_events)
        if reset:
            _events.clear()
    agg = defaultdict(lambda: [0, 0.0, float("inf"), 0.0])
    for e in events:
        if "dur" not in e:
            continue          # instant events carry no span to total
        a = agg[e["name"]]
        a[0] += 1
        a[1] += e["dur"]
        a[2] = min(a[2], e["dur"])
        a[3] = max(a[3], e["dur"])
    if format_ == "json":
        return json.dumps({"ops": {
            name: {"calls": n, "total_us": round(tot, 1),
                   "min_us": round(mn, 1), "max_us": round(mx, 1),
                   "avg_us": round(tot / n, 1)}
            for name, (n, tot, mn, mx) in sorted(
                agg.items(), key=lambda kv: -kv[1][1])}})
    lines = [f"{'Name':<40}{'Calls':>8}{'Total(us)':>14}{'Min(us)':>12}"
             f"{'Max(us)':>12}{'Avg(us)':>12}"]
    for name, (n, tot, mn, mx) in sorted(agg.items(),
                                         key=lambda kv: -kv[1][1]):
        lines.append(f"{name:<40}{n:>8}{tot:>14.1f}{mn:>12.1f}"
                     f"{mx:>12.1f}{tot / n:>12.1f}")
    return "\n".join(lines)


def active() -> bool:
    """True while collection runs (cheap guard for call sites)."""
    return _state == "run" and not _paused


def recording() -> bool:
    """True while either sink would keep a span: collection runs, or a
    jax profiler session is live.  :class:`span` asks this itself; a
    call site asks first only where half a microsecond per span is too
    much (the engine's per-op path)."""
    return _state == "run" or _tracing()


def _mirror_event(name, args=None):
    """Telemetry mirror: one instant event in the chrome-trace stream
    for a structured telemetry event (retrace, prefetch stall, poison),
    so a single timeline shows op spans AND the telemetry plane's
    annotations.  Only called while :func:`active`."""
    if not active():
        return
    with _lock:
        _events.append({"name": name, "ph": "i", "ts": _now_us(),
                        "pid": 0,
                        "tid": threading.get_ident() % 100000,
                        "s": "p", "cat": "telemetry",
                        "args": dict(args) if args else {}})


class span:
    """``with profiler.span("mxtpu.serving.admit", req=7):`` — one named
    host range with its ids, for framework call sites and users alike.

    Entered while a jax profiler session is live it is a
    ``jax.profiler.TraceAnnotation(name, **ids)`` (a
    ``StepTraceAnnotation`` when ``step_num`` is given: the root of a
    train step or a serving round), so it lands in the XPlane trace
    beside the device's ops; left while ``set_state("run")`` it appends
    the chrome event ``{name, cat, args: ids}`` that ``dump()`` and
    ``dumps()`` read.  With neither on it is one ``with`` and one call
    of jax's own switch (``TraceAnnotation.is_enabled``): under a
    microsecond, so call sites enter it unconditionally.  A span's
    parent is the span that encloses it on its thread; spans of one
    request share ``req``."""

    __slots__ = ("name", "cat", "ids", "_step_num", "_annotation",
                 "_start")

    def __init__(self, name, cat="scope", step_num=None, **ids):
        self.name = name
        self.cat = cat
        self.ids = ids
        self._step_num = step_num

    def __enter__(self):
        if _tracing():
            if self._step_num is None:
                ann = TraceAnnotation(self.name, **self.ids)
            else:
                ann = StepTraceAnnotation(
                    self.name, step_num=self._step_num, **self.ids)
            ann.__enter__()
            self._annotation = ann
        else:
            self._annotation = None
        self._start = _now_us() if _state == "run" else None
        return self

    def __exit__(self, *exc):
        if self._annotation is not None:
            self._annotation.__exit__(*exc)
        if self._start is not None and _state == "run":
            _record_event(self.name, self.cat, self._start, _now_us(),
                          self.ids)


#: parity: ``mx.profiler.record_scope`` — the same class
record_scope = span


class Marker:
    """Custom instant marker (parity: profiler.Marker)."""

    def __init__(self, name, domain=None):
        self.name = name

    def mark(self, scope="process"):
        if _state == "run" and not _paused:
            with _lock:
                _events.append({"name": self.name, "ph": "i",
                                "ts": _now_us(), "pid": 0, "tid": 0,
                                "s": "p", "cat": "marker"})
