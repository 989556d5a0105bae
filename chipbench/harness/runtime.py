"""What one run carries: its arguments, clock, spans, checks and tracer."""
import contextlib
import json
import os
import shutil
import time

from . import resolve, xplane

TRACE_SECONDS = 3.0


def emit(**row):
    """One JSON object on a line of its own, before the result line."""
    print(json.dumps(row, default=str), flush=True)


class Checks:
    """``correct`` is the conjunction of everything held here.  A check
    that fails is listed and the run goes on to its result line, so that
    the driver reads ``correct: false`` and not a crash."""

    def __init__(self):
        self.failed = []

    def hold(self, ok, what):
        if not ok:
            self.failed.append(what)
            emit(check_failed=what)
        return bool(ok)


class Spans:
    """The benchmark's own host spans: kept on the host clock for the
    per-layer readers, and written into the profiler's trace under the
    same name so idle gaps can be attributed to them."""

    def __init__(self):
        import jax
        self.log = {}
        self._annotation = jax.profiler.TraceAnnotation

    @contextlib.contextmanager
    def __call__(self, name):
        t0 = time.perf_counter()
        with self._annotation(name):
            yield
        self.log.setdefault(name, []).append((t0, time.perf_counter()))

    def durations(self, name, t0=None, t1=None):
        return [b - a for a, b in self.log.get(name, ())
                if (t0 is None or a >= t0) and (t1 is None or b <= t1)]


class Tracer:
    """With ``--trace 1``, starts ``jax.profiler`` ``TRACE_SECONDS`` before
    the window closes; the driver calls ``tick`` once a step or round and
    ``stop`` after the closing edge, so that writing the trace out (which
    takes seconds) stalls nothing inside the window.  With ``--trace 0``
    every call returns at once."""

    def __init__(self, enabled, workload, window_s):
        self.dir = os.path.join(resolve.ROOT, ".chipbench", "trace", workload)
        self.start_at = window_s - min(TRACE_SECONDS, window_s / 2)
        self._state = "off" if not enabled else "armed"
        self._window = None

    def tick(self, elapsed):
        if self._state == "armed" and elapsed >= self.start_at:
            import jax
            shutil.rmtree(self.dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self._window = jax.profiler.TraceAnnotation(xplane.WINDOW_SPAN)
            self._window.__enter__()
            self._state = "on"

    def stop(self):
        if self._state != "on":
            return
        import jax
        self._window.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self._state = "done"

    def reduce(self, chips):
        """Read the trace back (after the window, it takes seconds)."""
        self.stop()
        if self._state != "done":
            return None
        path = xplane.find_xplane(self.dir)
        if path is None:
            return None
        return xplane.reduce_trace(xplane.read_trace(path), chips)


class Run:
    """Handed to a driver's ``run``: everything one run of a cell has."""

    def __init__(self, args, workload, config, traffic, peaks, t_process):
        self.seed = int(args.seed)
        self.seconds = float(args.seconds)
        self.rehearse = bool(args.rehearse)
        self.sweep = args.sweep
        self.workload = workload
        self.chips = int(workload["chips"])
        self.config = config
        self.traffic = dict(traffic)
        if self.rehearse:
            self.traffic.update(traffic.get("rehearsal", {}))
        # a rehearsal runs the config's toy shapes: same code, any backend
        self.shapes = config["rehearsal"] if self.rehearse else config
        self.peaks = peaks
        self.t_process = t_process
        self.checks = Checks()
        self.spans = Spans()
        self.tracer = Tracer(args.trace, workload["name"], self.seconds)
        self.setup = {}          # phase of set-up -> seconds
        self.setup_s = None

    @contextlib.contextmanager
    def setup_phase(self, name):
        t0 = time.perf_counter()
        yield
        self.setup[name] = self.setup.get(name, 0.0) \
            + time.perf_counter() - t0

    def window_opens(self):
        """Call at the window's first edge: ends set-up."""
        now = time.perf_counter()
        self.setup_s = now - self.t_process
        return now


def builder_for(run):
    return resolve.load_module("models", run.config["builder"])


def program_counters():
    """The program's own counts that ``correct`` and the readers use."""
    from mxnet_tpu import engine
    info = engine.cache_info()
    return {k: info[k] for k in ("dispatches", "fresh_compiles",
                                 "aot_demotions", "hits", "misses")} \
        | {"persist": dict(info["persist"])}


def hold_no_events(checks, where):
    """Nothing retraced, fell back or lost its AOT executable since the
    last ``telemetry.clear_events()`` (chip_smoke.py's ``_no_events``)."""
    from mxnet_tpu import telemetry
    for kind in ("retrace", "fallback", "persist_error"):
        evs = telemetry.events(kind)
        checks.hold(not evs, f"{kind} events {where}: {evs[:3]}")


def hold_on_platform(checks, arrays, want, what):
    """Every jax array of ``arrays`` lives only on ``want`` devices
    (chip_smoke.py's ``_on_platform``)."""
    bad = [(name, sorted({d.platform for d in a.devices()}))
           for name, a in arrays
           if {d.platform for d in a.devices()} != {want}]
    checks.hold(not bad, f"{what} not on {want}: {bad[:3]}")


def peak_bytes(devices):
    """(peak on the fullest chip, its two parts).

    On this runtime ``memory_stats()["peak_bytes_in_use"]`` counts the
    buffers of arrays (arguments, outputs, KV pages) and NOT the
    temporaries of the program that runs: a BERT-base step whose
    executable holds 13.8 GB of temporaries read 3.2 GB there (my chip
    run, PR 26, call 3).  So the peak is the allocator's plus the largest
    ``temp_size_in_bytes`` among the programs the engine compiled or
    loaded, from the executable's own ``memory_analysis()`` as the
    program's memory observatory keeps it."""
    from mxnet_tpu import engine
    allocator = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                    for d in devices)
    temp = max((r.get("temp_bytes") or 0 for r in
                engine.cache_info()["memory"]["per_program"].values()),
               default=0)
    return allocator + temp, {"allocator_peak_bytes": allocator,
                              "largest_program_temp_bytes": temp}
