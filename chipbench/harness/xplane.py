"""From the profiler's ``.xplane.pb`` to busy time, idle gaps and op sums.

``read_trace`` is the only part that touches the file; the rest works on
plain lists of ``(name, start_s, end_s)`` so that ``chipbench/tests`` can
check it on a hand-built event list.

What a TPU v5e trace holds (looked at by hand, PR 26, jax 0.9.0): one
plane per chip named ``/device:TPU:<n>`` whose line ``XLA Ops`` has one
event per executed HLO op (~6,000 a BERT-base step), named by the op's
whole HLO text (``%fusion.398 = (bf16[...]) fusion(...)``: the
instruction name before `` = `` is kept), whose line ``XLA Modules`` has
one event per program run (``jit_full(<hash>)``) and whose line ``Async
XLA Ops`` has copies and slices that overlap the ops (not counted busy);
the host's ``TraceAnnotation`` spans are events of the line ``python3`` of
the ``/host:CPU`` plane, on the same clock.  ``python chipbench/harness/xplane.py <file>``
prints such a summary of any trace.
"""
import glob
import os
import sys

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.trace_window"
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute")


def find_xplane(trace_dir):
    hits = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return hits[-1] if hits else None


def read_trace(path):
    """{"devices": {plane: {line: [(name, start_s, end_s)]}},
    "host": [(name, start_s, end_s)] of the benchmark's own spans}."""
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    devices, host = {}, []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE):
            lines = devices.setdefault(plane.name, {})
            for line in plane.lines:
                lines[line.name] = [
                    (op_name(e.name), e.start_ns * 1e-9,
                     (e.start_ns + e.duration_ns) * 1e-9)
                    for e in line.events]
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host.extend(
                    (e.name, e.start_ns * 1e-9,
                     (e.start_ns + e.duration_ns) * 1e-9)
                    for e in line.events
                    if e.name.startswith(SPAN_PREFIX))
    return {"devices": devices, "host": sorted(host, key=lambda e: e[1])}


def op_name(text):
    """``%fusion.398 = (bf16[...]) fusion(...)`` -> ``fusion.398``."""
    return text.split(" = ", 1)[0].lstrip("%")


def clip(events, t0, t1):
    """Events cut to [t0, t1]; those wholly outside are dropped."""
    return [(n, max(a, t0), min(b, t1)) for n, a, b in events
            if b > t0 and a < t1]


def busy_union(events):
    """Merged, sorted [start, end] intervals during which any event ran,
    and their summed length.  Overlapping and nested events count once."""
    merged = []
    for _n, a, b in sorted(events, key=lambda e: e[1]):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged, sum(b - a for a, b in merged)


def idle_gaps(merged, t0, t1):
    """The [start, end] stretches of [t0, t1] that ``merged`` leaves."""
    out, at = [], t0
    for a, b in merged:
        if a > at:
            out.append((at, a))
        at = max(at, b)
    if t1 > at:
        out.append((at, t1))
    return out


def span_at(host, t):
    """Name of the innermost (latest-started) benchmark span open at
    ``t``, the window span aside; ``bench.(none)`` between spans."""
    best = None
    for name, a, b in host:
        if a <= t < b and name != WINDOW_SPAN:
            if best is None or a >= best[1]:
                best = (name, a)
    return best[0] if best else SPAN_PREFIX + "(none)"


def name_gaps(gaps, host, longest=5):
    """[[name, seconds]]: the ``longest`` single gaps, each named by the
    host span open at its midpoint, then ``sum:<span>`` totals of ALL gaps
    by that same naming, largest first — at most ten entries in all."""
    named = [(span_at(host, (a + b) / 2), b - a) for a, b in gaps]
    single = sorted(named, key=lambda g: -g[1])[:longest]
    totals = {}
    for name, d in named:
        totals[name] = totals.get(name, 0.0) + d
    sums = sorted(totals.items(), key=lambda kv: -kv[1])[:10 - len(single)]
    return [[n, d] for n, d in single] + [["sum:" + n, d] for n, d in sums]


def top_ops(events, n=10):
    """[[name, seconds]] of the ``n`` op names with the most summed time."""
    tot = {}
    for name, a, b in events:
        tot[name] = tot.get(name, 0.0) + (b - a)
    return [[k, v] for k, v in
            sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def collective_seconds(events):
    return sum(b - a for name, a, b in events
               if any(c in name for c in COLLECTIVES))


def reduce_trace(trace, chips):
    """What the per-layer readers and the result line take from a trace.
    The window is the ``bench.trace_window`` span where the trace has it
    (else first to last device event); idle share, gaps, op sums and
    collectives are those of device 0, ``busy_s`` the mean over chips."""
    planes = sorted(trace["devices"])[:chips]
    if not planes:
        return None
    ops = {p: trace["devices"][p].get(OPS_LINE)
           or [e for line in trace["devices"][p].values() for e in line]
           for p in planes}
    win = [e for e in trace["host"] if e[0] == WINDOW_SPAN]
    every = [e for p in planes for e in ops[p]]
    if not every:
        return None
    t0, t1 = (win[0][1], win[0][2]) if win else (
        min(e[1] for e in every), max(e[2] for e in every))
    ev0 = clip(ops[planes[0]], t0, t1)
    merged0, busy0 = busy_union(ev0)
    busy = [busy0] + [busy_union(clip(ops[p], t0, t1))[1]
                      for p in planes[1:]]
    modules = clip(trace["devices"][planes[0]].get(MODULES_LINE, []), t0, t1)
    spans = {}
    for name, a, b in clip(trace["host"], t0, t1):
        spans[name] = spans.get(name, 0) + 1
    return {
        "window_s": t1 - t0,
        "busy_s": sum(busy) / len(busy),
        "busy_s_per_chip": busy,
        "idle_share_dev0": 1.0 - busy0 / (t1 - t0),
        "device_ops": top_ops(ev0),
        "idle_gaps": name_gaps(idle_gaps(merged0, t0, t1), trace["host"]),
        "collective_s_dev0": collective_seconds(ev0),
        "modules": top_ops(modules),
        "module_runs": {n: sum(1 for e in modules if e[0] == n)
                        for n in {e[0] for e in modules}},
        "host_span_counts": spans,
    }


def summarize(path, out=sys.stdout):
    """Planes, lines, event counts and the first names of a trace."""
    import jax
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        print("PLANE", plane.name, file=out)
        for line in plane.lines:
            evs = list(line.events)
            names = {}
            for e in evs:
                names[e.name] = names.get(e.name, 0.0) + e.duration_ns
            top = sorted(names.items(), key=lambda kv: -kv[1])[:12]
            print(f"  LINE {line.name!r}: {len(evs)} events; top by time:",
                  [(n[:60], round(d * 1e-6, 3)) for n, d in top], file=out)
            for e in evs[:2]:
                print("    stats of", e.name[:40],
                      [(k, str(v)[:60]) for k, v in e.stats], file=out)


if __name__ == "__main__":
    summarize(sys.argv[1])
