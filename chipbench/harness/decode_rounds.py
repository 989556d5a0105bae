"""What a serving cell's builder reads of a run's decode rounds, whatever
the model: the program's own counts over the dispatches of one kind read
inside the window, the positions every decoding slot had written in each
decode-only round, and the decode step's roofline share from the
builder's own byte and operation counts of a round.

A builder keeps only what is its model's: its configuration file, its
counters, and ``bytes_of(active, positions)`` / ``flops_of(active,
positions)``, the work one round HAS to do.  Every function returns None
where the run has nothing to read, and raises nothing.
"""
import bisect
import json
import os

import numpy as np

from . import program_spans, resolve, stats


def config_shapes(name, holds):
    """The configuration file ``chipbench/configs/<name>`` or its
    ``rehearsal`` group, whichever ``holds``; None for neither."""
    with open(os.path.join(resolve.BENCH_DIR, "configs", name)) as f:
        cfg = json.load(f)
    return next((s for s in (cfg, cfg["rehearsal"]) if holds(s)), None)


def of_slots(name, slots):
    """The shapes a run of configuration file ``name`` with ``slots``
    slots used (a metric file has only ``obs`` to tell them apart by)."""
    return config_shapes(
        name, lambda s: sum(b[0] for b in s["serving"]["buckets"]) == slots)


def calls(listener, obs, kind):
    """What the dispatches of ``kind`` (``"decode"``, ``"prefill"``) whose
    tokens were read inside the window counted, by the run's
    ``Server.statistics_listener`` (``afmoe_server.Calls``): {counter:
    sum}, ``dispatches``, and ``rows``, the columns that held a request
    summed over them.  None without such dispatches."""
    t0, t1 = obs["window"]
    inside = [(n, c) for t, k, n, c in (listener.log if listener else ())
              if k == kind and t0 <= t <= t1]
    if not inside:
        return None
    out = {name: sum(c[i] for _n, c in inside)
           for i, name in enumerate(listener.names)}
    out["dispatches"] = len(inside)
    out["rows"] = sum(n for n, _c in inside)
    return out


def live(active, positions, cache_len):
    """Positions written so far, one a decoding slot, none past a page:
    ``positions`` is one number for ``active`` slots, or one a slot."""
    pos = [float(positions)] * int(active) if np.ndim(positions) == 0 \
        else [float(p) for p in positions]
    return [min(p, cache_len) for p in pos]


def decoding_positions(obs):
    """[(round, positions of each slot decoding in it)] over the window's
    decode-only rounds, from the run's requests: a slot decodes in a round
    once it has its first token and until it is done."""
    t0, t1 = obs["window"]
    written = [r for r in obs["requests"] if r["stamps"]]
    out = []
    for rnd in obs["rounds"]:
        if rnd["admitted"] or not (t0 <= rnd["t0"] and rnd["t1"] <= t1):
            continue
        pos = [r["prompt_len"] + bisect.bisect_right(r["stamps"], rnd["t0"])
               for r in written if r["stamps"][0] <= rnd["t0"]
               and (r["done"] is None or r["done"] > rnd["t0"])]
        if pos:
            out.append((rnd, pos))
    return out


def round_counts(obs, bytes_of, flops_of):
    """(median bytes, median operations) a decode-only round of the window
    has to move and do, ``bytes_of`` / ``flops_of`` (active, positions)
    of each; None without such rounds."""
    rounds = decoding_positions(obs)
    if not rounds:
        return None
    return (stats.median([bytes_of(len(p), p) for _r, p in rounds]),
            stats.median([flops_of(len(p), p) for _r, p in rounds]))


def roofline_share(obs, counts, which, peak):
    """100 x ``counts[which]`` (a round's bytes, 0, or operations, 1)
    over a decode-only round's device-busy seconds x the peak ``peak`` of
    ``obs["peaks"]``: the median, over the decode-only rounds of the
    traced seconds (``program_spans``), of the round's length less the
    device's idle time in it.  ``counts`` is called only where there is
    such a trace.  None without it, the peaks or the counts."""
    red = program_spans.of(obs)
    busy = [length - idle for length, idle in
            (red["decode_only_rounds"] if red else ())]
    got = counts() if busy and obs.get("peaks") else None
    if got is None:
        return None
    return 100.0 * got[which] / (stats.median(busy) * obs["peaks"][peak])
