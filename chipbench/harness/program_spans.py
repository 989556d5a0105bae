"""The program's own host spans (``mxtpu.*``), read back from the
profiler's trace of a ``--trace 1`` run.

``xplane.read_trace`` keeps only the benchmark's ``bench.*`` host events,
so this reads the ``/host:CPU`` plane itself.  ``of(obs)`` is what the
metric files call: None unless the run was traced (an untraced run never
opens a stale file), else the reduction of the newest ``.xplane.pb``
under ``<checkout>/.chipbench/trace/``, made once per run, kept in
``obs`` and printed once as the earlier line ``program_spans``: per span
name its count, median and median SELF time (the span minus its children
on its thread), and device 0's idle seconds by the innermost span open at
each idle gap's midpoint.  A program without such spans (the parent of
the PR that added them) gives None, and every metric that reads them is
left out of the line.

``read_spans`` is the only part that touches the file; the rest works on
plain lists of ``(name, thread, start_s, end_s)`` (a fifth element, the
span's ids, rides along unread) so that ``chipbench/tests`` can check it
on a hand-built list.
"""
import bisect
import glob
import os

from . import resolve, runtime, stats, xplane

PREFIX = "mxtpu."
TRACE_ROOT = os.path.join(resolve.ROOT, ".chipbench", "trace")
KEY = "program_spans"          # where ``of`` keeps its result in ``obs``


def newest_xplane():
    hits = glob.glob(os.path.join(TRACE_ROOT, "*", "plugins", "profile",
                                  "*", "*.xplane.pb"))
    return max(hits, key=os.path.getmtime) if hits else None


def read_spans(path):
    """{"spans": [(name, thread, start_s, end_s, ids)] of the program's
    spans, "window": (t0, t1) of ``bench.trace_window`` or None,
    "device_ops": [(name, start_s, end_s)] of device 0's ``XLA Ops``}."""
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    spans, window, devices = [], None, {}
    for plane in data.planes:
        if plane.name == xplane.HOST_PLANE:
            for thread, line in enumerate(plane.lines):
                for e in line.events:
                    a = e.start_ns * 1e-9
                    b = (e.start_ns + e.duration_ns) * 1e-9
                    if e.name.startswith(PREFIX):
                        spans.append((e.name, thread, a, b,
                                      {k: v for k, v in e.stats
                                       if not k.startswith("_")}))
                    elif e.name == xplane.WINDOW_SPAN:
                        window = (a, b)
        elif plane.name.startswith(xplane.DEVICE_PLANE):
            devices[plane.name] = [
                ("", e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
                for line in plane.lines if line.name == xplane.OPS_LINE
                for e in line.events]
    first = devices[min(devices)] if devices else []
    return {"spans": spans, "window": window, "device_ops": first}


# -- pure functions over (name, thread, start, end[, ids]) lists -------------

def within(span, t0, t1):
    return span[2] >= t0 and span[3] <= t1


def inside(spans, t0, t1):
    """The spans that lie wholly inside [t0, t1]: a span cut by the
    window's edge has no duration worth a median."""
    return [s for s in spans if within(s, t0, t1)]


def parents(spans):
    """For each span the index of the span that encloses it most tightly
    on its thread, or None for a root."""
    order = sorted(range(len(spans)),
                   key=lambda i: (spans[i][1], spans[i][2], -spans[i][3]))
    out, stack = [None] * len(spans), []
    for i in order:
        thread, end = spans[i][1], spans[i][3]
        while stack and (spans[stack[-1]][1] != thread
                         or spans[stack[-1]][3] < end):
            stack.pop()
        out[i] = stack[-1] if stack else None
        stack.append(i)
    return out


def children(par):
    out = [[] for _ in par]
    for i, p in enumerate(par):
        if p is not None:
            out[p].append(i)
    return out


def self_times(spans, kids):
    """Each span's duration minus its children's (children of one span on
    one thread do not overlap)."""
    return [(s[3] - s[2]) - sum(spans[k][3] - spans[k][2] for k in kids[i])
            for i, s in enumerate(spans)]


def by_name(spans, values):
    out = {}
    for s, v in zip(spans, values):
        out.setdefault(s[0], []).append(v)
    return out


class Innermost:
    """``at(t)``: index of the span open at ``t`` that started last (the
    innermost one; over several threads, the newest), or None."""

    def __init__(self, spans, par):
        self.spans, self.par = spans, par
        self.threads = {}
        for i in sorted(range(len(spans)), key=lambda i: spans[i][2]):
            starts, idx = self.threads.setdefault(spans[i][1], ([], []))
            starts.append(spans[i][2])
            idx.append(i)

    def at(self, t):
        best = None
        for starts, idx in self.threads.values():
            k = bisect.bisect_right(starts, t) - 1
            i = idx[k] if k >= 0 else None
            while i is not None and not self.spans[i][3] > t:
                i = self.par[i]
            if i is not None and (best is None or
                                  self.spans[i][2] > self.spans[best][2]):
                best = i
        return best


def name_gaps(gaps, spans, par, kids):
    """({span name: idle seconds}, share of all idle time that lies in a
    LEAF span): each gap goes whole to the innermost span open at its
    midpoint, ``(none)`` where no span is open.  A gap in a span that has
    children (a root, or a phase between two of its children) is named
    but counts as not attributed: some phase there has no span yet."""
    inner = Innermost(spans, par)
    idle, in_leaf, total = {}, 0.0, 0.0
    for a, b in gaps:
        i = inner.at((a + b) / 2)
        name = spans[i][0] if i is not None else "(none)"
        idle[name] = idle.get(name, 0.0) + (b - a)
        total += b - a
        if i is not None and not kids[i]:
            in_leaf += b - a
    return idle, (in_leaf / total if total else None)


def overlap(gaps, t0, t1):
    return sum(max(0.0, min(b, t1) - max(a, t0)) for a, b in gaps)


def minus_child(spans, kids, i, child_name):
    """Span ``i``'s duration without its children called ``child_name``."""
    return (spans[i][3] - spans[i][2]) - sum(
        spans[k][3] - spans[k][2] for k in kids[i]
        if spans[k][0] == child_name)


def admission(span, i):
    """What names the admission a ``serving.admit`` span belongs to: its
    ``req`` id, or, for a span that carries none, the span itself."""
    req = span[4].get("req") if len(span) > 4 else None
    return ("span", i) if req is None else ("req", req)


def reduce_spans(spans, window, device_ops):
    """Everything the metric files read, from one trace's lists."""
    t0, t1 = window
    cut = {admission(s, None) for s in spans
           if s[0] == PREFIX + "serving.admit" and not within(s, t0, t1)}
    spans = inside(spans, t0, t1)
    if not spans:
        return None
    par = parents(spans)
    kids = children(par)
    durations = by_name(spans, [s[3] - s[2] for s in spans])
    selfs = by_name(spans, self_times(spans, kids))
    merged, _busy = xplane.busy_union(xplane.clip(device_ops, t0, t1))
    gaps = xplane.idle_gaps(merged, t0, t1) if merged else []
    idle, named_share = name_gaps(gaps, spans, par, kids)

    # serving: a round's host time is what its decode (or admit) spans
    # take beside their token_read child, in which the host only waits.
    # One admission is every admit span of one ``req`` id (since the
    # program enqueues a prefill and reads its token later, two): their
    # sum, and none of an admission that the window's edge cut in two.
    read = PREFIX + "serving.token_read"
    decode_host, decode_rounds, admits = [], [], {}
    for i, s in enumerate(spans):
        if s[0] == PREFIX + "serving.admit":
            key = admission(s, i)
            if key not in cut:
                admits[key] = admits.get(key, 0.0) \
                    + minus_child(spans, kids, i, read)
        elif s[0] == PREFIX + "serving.round":
            names = [spans[k][0] for k in kids[i]]
            if PREFIX + "serving.admit" in names \
                    or PREFIX + "serving.decode" not in names:
                continue
            decode_host.append(sum(
                minus_child(spans, kids, k, read) for k in kids[i]
                if spans[k][0] == PREFIX + "serving.decode"))
            decode_rounds.append((s[3] - s[2], overlap(gaps, s[2], s[3])))
    return {
        "window_s": t1 - t0, "durations": durations, "selfs": selfs,
        "idle_s": idle, "idle_named_share": named_share,
        "decode_host_s": decode_host,
        "admit_host_s": list(admits.values()),
        "decode_only_rounds": decode_rounds,
    }


def summary(red):
    """The ``program_spans`` line: the whole table, not only the metrics."""
    ms = 1e3
    rounds = red["decode_only_rounds"]
    return {
        "window_s": red["window_s"],
        "spans": {n: {"n": len(d), "median_ms": stats.median(d) * ms,
                      "self_median_ms": stats.median(red["selfs"][n]) * ms,
                      "sum_s": sum(d)}
                  for n, d in sorted(red["durations"].items())},
        "idle_s": dict(sorted(red["idle_s"].items(), key=lambda kv: -kv[1])),
        "idle_named_share": red["idle_named_share"],
        "decode_only_rounds": {
            "n": len(rounds),
            "round_median_ms": (stats.median(
                [r[0] for r in rounds]) or 0) * ms,
            "device_idle_median_ms": (stats.median(
                [r[1] for r in rounds]) or 0) * ms,
            "host_median_ms": (stats.median(
                red["decode_host_s"]) or 0) * ms},
        "admit_host_median_ms": (stats.median(
            red["admit_host_s"]) or 0) * ms,
    }


def of(obs):
    """The reduction for this run, or None: no traced run, no trace file,
    no ``bench.trace_window`` in it, or a program that has no spans."""
    if not obs.get("trace"):
        return None
    if KEY not in obs:
        path = newest_xplane()
        red = None
        if path is not None:
            trace = read_spans(path)
            if trace["window"] is not None:
                red = reduce_spans(trace["spans"], trace["window"],
                                   trace["device_ops"])
        obs[KEY] = red
        if red is not None:
            runtime.emit(program_spans=summary(red), xplane=path)
    return obs[KEY]


# -- what several metric files share ------------------------------------------

def median_ms(obs, name, self_time=False):
    """Median duration (or self time), in ms, of the span ``name``."""
    red = of(obs)
    values = red and red["selfs" if self_time else "durations"].get(name)
    return stats.median(values) * 1e3 if values else None


def median_of_ms(obs, key):
    red = of(obs)
    return stats.median(red[key]) * 1e3 if red and red[key] else None


def idle_named_share(obs):
    red = of(obs)
    if not red or red["idle_named_share"] is None:
        return None
    return red["idle_named_share"] * 100.0
