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

The device half (docs/observability.md, "Device time by scope"):
:func:`device_scope` names the device ops traced inside it,
:func:`device_scopes` maps every live executable's instructions to those
names (from the executables the memory observatory keeps; HLO text is
rendered when this is called and never before), and
:func:`device_dumps` joins that map to the newest device trace: per
program its runs, device ms a run and share of the busy time, per scope
forward and backward ms a run, how much of it the scope INHERITED
(instructions the compiler inserted, named by their consumer) and the
ops that took them.
"""
from __future__ import annotations

import bisect
import glob
import json
import os
import re
import statistics
import threading
import time
import weakref
from collections import defaultdict
from typing import List

from jax.profiler import StepTraceAnnotation, TraceAnnotation

from .base import MXNetError

__all__ = ["set_config", "set_state", "state", "pause", "resume", "dump",
           "dumps", "Marker", "span", "record_scope", "device_scope",
           "device_scopes", "device_dumps"]

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


def device_scope(name):
    """``with profiler.device_scope("mxtpu.mlp"):`` names the DEVICE ops
    traced inside it: the name enters the ``op_name`` of every HLO
    instruction lowered from the block (``jit(f)/mxtpu.mlp/dot_general``;
    the backward twin reads ``transpose(jvp(mxtpu.mlp))``), which is
    what :func:`device_scopes` maps and :func:`device_dumps` sums device
    time by.  A ``jax.named_scope``: it exists only while a program is
    traced and costs a compiled program nothing.  The vocabulary is in
    docs/observability.md ("Spans")."""
    import jax
    return jax.named_scope(name)


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


# -- device time by scope -----------------------------------------------------

NO_SCOPE = "(no scope)"
UNKNOWN_PROGRAM = "(unknown program)"
OUTSIDE_RUNS = "(outside any run)"
_SCOPE_RE = re.compile(r"mxtpu\.[a-z_.]+")
_MODULE_RE = re.compile(r"^HloModule\s+([^\s,]+)", re.M)
_DEF_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s")
_OP_NAME_RE = re.compile(r'\bop_name="([^"]*)"')
_OPERAND_RE = re.compile(r"%([\w.\-]+)")
_RUN_RE = re.compile(r"\(\d+\)$")
#: executable -> (module name, {instruction: (scope, backward,
#: inherited)}), so that an executable's text is rendered once however
#: often a trace is read
_scope_cache = weakref.WeakKeyDictionary()


def scope_of(op_name):
    """``(scope, backward)`` of one HLO ``op_name``, or None where it
    names no ``mxtpu.*`` scope: the LAST scope of the first path (a
    fusion's ``op_name`` may join several with ``;``; the innermost
    scope wins), backward when a ``transpose(`` stands before it
    (``transpose(jvp(mxtpu.mlp))``: the twin that ``grad`` made)."""
    path = op_name.split(";", 1)[0]
    hits = list(_SCOPE_RE.finditer(path))
    if not hits:
        return None
    last = hits[-1]
    return last.group().rstrip("."), "transpose(" in path[:last.start()]


def scopes_of_text(text):
    """Compiled HLO text -> ``(module name as a trace prints it,
    {instruction name: (scope, backward, inherited)})`` over every
    computation of the module (a loop's body ops run as events of their
    own; names are unique within a module).  ``inherited`` is False for
    an instruction whose own ``op_name`` names the scope.  An
    instruction with no ``op_name`` at all is one the compiler inserted
    (``copy-start`` / ``copy-done``, ``slice-done``, a layout copy: a
    prefetch FOR some op): it INHERITS the scope of the first scoped
    instruction, in text order, that consumes it, through chains of its
    kind, and says so (``inherited`` True), because that is a guess at
    whose time a wait is: :func:`reduce_device` keeps the two apart.
    Instructions under no scope are left out."""
    m = _MODULE_RE.search(text)
    table, inserted = {}, {}     # inserted: name -> its operands' names
    for line in text.splitlines():
        hit = _DEF_RE.match(line)
        if hit is None:
            continue
        name = hit.group(1)
        meta = line.find(" metadata={")
        body = line[hit.end():meta if meta >= 0 else None]
        named = _OP_NAME_RE.search(line, meta) if meta >= 0 else None
        if named is None:
            inserted[name] = _OPERAND_RE.findall(body)
            continue
        got = scope_of(named.group(1))
        if not got:
            continue
        table[name] = got + (False,)
        stack = _OPERAND_RE.findall(body)
        while stack:
            operand = stack.pop()
            below = inserted.pop(operand, None)
            if below is not None:
                table[operand] = got + (True,)
                stack.extend(below)
    return (m.group(1) if m else ""), table


def _scope_tables():
    """``(module name, table)`` of every executable the memory
    observatory holds, oldest first; an executable's text is rendered on
    its first pass through here and kept with it."""
    from .telemetry import memory
    for _name, compiled in memory.executables():
        got = _scope_cache.get(compiled)
        if got is None:
            try:
                got = scopes_of_text(compiled.as_text())
            except Exception:    # a backend that renders no text: skip
                continue
            _scope_cache[compiled] = got
        yield got


def device_scopes():
    """``{module name: {instruction name: (scope, backward, inherited)}}``
    for every program this process compiled or loaded: the served and
    trained programs all pass the engine's tiered compile seam, where
    the memory observatory (``telemetry.memory``; off with the telemetry
    switch) keeps the newest executable of each program name with its
    record.  ``as_text()`` is rendered HERE, once an executable, and
    nowhere on a path that serves or trains: the text of a fused train
    step is megabytes, and set-up is an end-to-end metric.

    One table a module, the NEWEST executable's: where two live
    executables share a module name (one function compiled for two
    shapes under two program names) their ``fusion.N`` repeat with other
    meanings, and a trace does not say which of them a run was, so the
    tables are never merged; :func:`device_dumps` lists such modules
    under ``shadowed``.  Programs that must be told apart get names of
    their own (the server names a program by bucket and kind)."""
    return dict(_scope_tables())


def _self_times(ops):
    """Each event's duration minus the events it encloses on the line
    (``while``, ``conditional``, ``call``: their bodies' ops are events
    of their own).  ``ops`` sorted by (start, -end)."""
    out = [b - a for _n, a, b in ops]
    stack = []
    for i, (_n, a, b) in enumerate(ops):
        while stack and ops[stack[-1]][2] < b:
            stack.pop()
        if stack and ops[stack[-1]][1] <= a:
            out[stack[-1]] -= b - a
        stack.append(i)
    return out


def reduce_device(ops, runs, scopes):
    """The pure part of :func:`device_dumps`: ``ops`` the ``XLA Ops``
    events ``(instruction name, start_s, end_s)`` of one device, ``runs``
    its ``XLA Modules`` events ``(module name, start_s, end_s)``,
    ``scopes`` the map of :func:`device_scopes`.  Every op goes to the
    run that contains its start and, by its SELF time, to the scope its
    instruction has in THAT module's map, so a program's scopes sum to
    its busy time.  A scope's ``inherited_ms`` is the part of its time
    taken by instructions that carry no name of their own and were given
    their consumer's (``scopes_of_text``): the named part is
    ``ms_per_run - inherited_ms``.  Milliseconds are per run of the
    program."""
    runs = sorted(((_RUN_RE.sub("", n), a, b) for n, a, b in runs),
                  key=lambda r: r[1])
    starts = [r[1] for r in runs]
    ops = sorted(ops, key=lambda e: (e[1], -e[2]))
    # module -> scope -> [forward_s, backward_s, {name: self s},
    #                      {name of an op that encloses others: whole s},
    #                      inherited_s]
    acc = {}
    for (name, a, b), own in zip(ops, _self_times(ops)):
        k = bisect.bisect_right(starts, a) - 1
        module = runs[k][0] if k >= 0 and a <= runs[k][2] else OUTSIDE_RUNS
        table = scopes.get(module)
        if table is None:
            scope, backward, inherited = UNKNOWN_PROGRAM, False, False
        else:
            scope, backward, inherited = table.get(
                name, (NO_SCOPE, False, False))
        row = acc.setdefault(module, {}).setdefault(
            scope, [0.0, 0.0, {}, {}, 0.0])
        row[1 if backward else 0] += own
        if inherited:
            row[4] += own
        row[2][name] = row[2].get(name, 0.0) + own
        if own < b - a:
            row[3][name] = row[3].get(name, 0.0) + (b - a)
    busy = sum(r[0] + r[1] for by in acc.values() for r in by.values())

    def largest(names, ms):
        return [[k, v * ms] for k, v in sorted(
            names.items(), key=lambda kv: -kv[1])[:5]]
    programs = {}
    for module, by in acc.items():
        lengths = [b - a for n, a, b in runs if n == module]
        n = max(len(lengths), 1)
        ms = 1e3 / n
        total = sum(r[0] + r[1] for r in by.values())
        programs[module] = {
            "runs": len(lengths),
            "ms_per_run": statistics.median(lengths) * 1e3
            if lengths else None,
            "busy_ms_per_run": total * ms,
            "busy_share": total / busy if busy else 0.0,
            "scopes": {
                scope: {"ms_per_run": (fwd + bwd) * ms,
                        "forward_ms": fwd * ms, "backward_ms": bwd * ms,
                        "inherited_ms": inherited * ms,
                        "ops": len(names), "top": largest(names, ms),
                        "enclosing": largest(whole, ms)}
                for scope, (fwd, bwd, names, whole, inherited) in sorted(
                    by.items(), key=lambda kv: -(kv[1][0] + kv[1][1]))},
        }
    return {"busy_ms": busy * 1e3,
            "programs": dict(sorted(programs.items(),
                                    key=lambda kv: -kv[1]["busy_share"]))}


def _read_device_trace(path):
    """``(plane name, XLA Ops events, XLA Modules events)`` of the first
    device plane of an ``.xplane.pb`` that has an ``XLA Ops`` line; an
    op event is named by its whole HLO text (``%fusion.398 = ...``), of
    which the instruction name is kept."""
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    for plane in sorted(data.planes, key=lambda p: p.name):
        if not plane.name.startswith("/device:"):
            continue
        lines = {line.name: line for line in plane.lines}
        if "XLA Ops" not in lines:
            continue

        def events(line, name_of):
            return [(name_of(e.name), e.start_ns * 1e-9,
                     (e.start_ns + e.duration_ns) * 1e-9)
                    for e in line.events]

        ops = events(lines["XLA Ops"],
                     lambda t: t.split(" = ", 1)[0].lstrip("%"))
        runs = events(lines["XLA Modules"], str) \
            if "XLA Modules" in lines else []
        return plane.name, ops, runs
    return None, [], []


def device_dumps(logdir=None, format_="json"):
    """Device time by program and by ``mxtpu.*`` scope: the device half
    of :func:`dumps`.  Reads the newest ``.xplane.pb`` under ``logdir``
    (default: the configured ``device_logdir``, where
    ``set_config(profile_device=True)`` + ``set_state("run")`` ...
    ``set_state("stop")`` writes), device 0, and joins it to
    :func:`device_scopes`.  Call it in the process that ran the programs:
    a module the observatory holds no executable of (telemetry off, a
    plain ``jax.jit``) reads ``(unknown program)``.

    ``format_="json"`` returns ``{"path", "device", "busy_ms",
    "seconds": {map, read, reduce}, "shadowed": [modules that more than
    one live executable goes by: read by the newest's map], "programs":
    {module: {runs, ms_per_run (median length of a run),
    busy_ms_per_run, busy_share, "scopes": {scope: {ms_per_run,
    forward_ms, backward_ms, inherited_ms (of ms_per_run, the part under
    instructions the compiler inserted, named by their consumer), ops,
    top: the five instructions with most self time, enclosing: the five
    ``while`` / ``conditional`` / ``call`` instructions with most time
    bodies included}}}}}`` as a JSON string; ``"table"`` the same as
    text."""
    if format_ not in ("table", "json"):
        raise MXNetError(
            f"unknown dumps format {format_!r} (want 'table' or 'json')")
    logdir = logdir or _config["device_logdir"]
    hits = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                     recursive=True)
    if not hits:
        raise MXNetError(f"no .xplane.pb under {logdir!r}: trace first "
                         "(set_config(profile_device=True), "
                         "set_state('run') ... set_state('stop'))")
    path = max(hits, key=os.path.getmtime)
    t0 = time.perf_counter()
    tables = list(_scope_tables())
    scopes = dict(tables)
    t1 = time.perf_counter()
    device, ops, runs = _read_device_trace(path)
    t2 = time.perf_counter()
    named = [module for module, _table in tables]
    out = {"path": path, "device": device,
           "shadowed": sorted(m for m in scopes if named.count(m) > 1),
           **reduce_device(ops, runs, scopes)}
    out["seconds"] = {"map": t1 - t0, "read": t2 - t1,
                      "reduce": time.perf_counter() - t2}
    if format_ == "json":
        return json.dumps(out)
    lines = [f"{'Program / scope':<44}{'Runs':>6}{'ms/run':>10}"
             f"{'fwd':>10}{'bwd':>10}{'inherited':>10}{'busy %':>8}"]
    for module, p in out["programs"].items():
        lines.append(f"{module:<44}{p['runs']:>6}"
                     f"{p['busy_ms_per_run']:>10.3f}{'':>30}"
                     f"{100 * p['busy_share']:>8.2f}")
        for scope, r in p["scopes"].items():
            lines.append(f"  {scope:<42}{r['ops']:>6}"
                         f"{r['ms_per_run']:>10.3f}{r['forward_ms']:>10.3f}"
                         f"{r['backward_ms']:>10.3f}"
                         f"{r['inherited_ms']:>10.3f}")
    return "\n".join(lines)
