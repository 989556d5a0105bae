"""Memory & communication observatory: the DEVICE side of the plane.

PR 4's telemetry watches the host (dispatches, retraces, stalls); this
module watches HBM and the interconnect.  The engine's tiered AOT seam
does an explicit ``lower().compile()``, so a compiled-executable object
exists for every cached program — and XLA already computed everything
worth knowing about it:

* ``compiled.memory_analysis()`` — argument / output / temp /
  generated-code bytes per device, from which a peak-footprint figure
  follows (``arg + out + temp + code - aliased``);
* ``compiled.cost_analysis()`` — FLOPs and bytes-accessed;
* the compiled HLO text — every collective op (all-reduce /
  reduce-scatter / all-gather / all-to-all / collective-permute) with
  its per-device payload shape, from which analytic bytes-on-wire
  follow (ring formulas over the replica-group size);
* the donate tuple — bytes the step does NOT double-buffer, summed
  from the donated arguments' avals.

Everything here is NEVER-RAISES and gated on the telemetry master
switch: ``MXTPU_TELEMETRY=0`` harvests nothing, records nothing, and
costs one attribute load per seam.  ``cost_analysis``/
``memory_analysis`` are backend-dependent; when they raise or return
nothing (CPU, older jaxlib) the harvest degrades to analytic aval-based
estimates and a single ``mem_analysis_unavailable`` event is recorded
for the whole process, not one per program.

The live side: :func:`census` walks the engine's live-buffer set for
per-device HBM bytes; :func:`param_census` attributes bytes to gluon
parameters by name; ``oom_risk`` events fire when live + peak
approaches the device capacity (``device.memory_stats()`` — absent on
CPU, so the check is inert there).

Consumers: ``engine.cache_info()["memory"]``, ``tools/mxmem.py``
and the mxlint rules MXL308/MXL309 (``analysis.analyze_memory``).  See
docs/observability.md ("Device memory & comms").
"""
from __future__ import annotations

import re
import threading
from typing import Any, Dict, List, Optional, Tuple

from . import _switch
from .metrics import gauge
from .recorder import record_event

__all__ = [
    "harvest_compiled", "programs", "executables", "collective_stats",
    "census",
    "param_census", "note_param_tree", "param_trees",
    "opt_state_census", "note_opt_state", "opt_state_trees", "report",
    "dump_report", "device_capacity", "reset",
    "OOM_RISK_RATIO",
]

_lock = threading.Lock()
#: program name -> harvest record (latest aval signature wins; the
#: record counts how many signatures/harvests it has absorbed)
_programs: Dict[str, dict] = {}
#: registered param trees (SPMD trainers): name -> layout snapshot,
#: the MXL309 input
_param_trees: Dict[str, dict] = {}
#: registered optimizer-state layouts (SPMD trainers): name -> census,
#: the MXL310 input and the ZeRO memory-drop evidence
_opt_trees: Dict[str, dict] = {}
# the unavailable event is per PROCESS, not per program — a CPU run
# compiles hundreds of programs and one event says it all
_unavailable_reported = [False]
# monotonically stamps each harvest so report() can pick "the variant
# that actually ran last" when a program has step_multi bulk variants
_harvest_seq = [0]
_capacity_cache: List[Any] = []      # [] = unprobed, [None] = unknown

#: live + peak above this fraction of device capacity emits ``oom_risk``
OOM_RISK_RATIO = 0.92

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1,
    "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3b11fnuz": 1, "f8e4m3fnuz": 1,
    "f8e5m2fnuz": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

# one HLO collective definition: ``%name = <shape-or-tuple> all-reduce(``.
# Async pairs count via their ``-done`` half, whose result type is
# exactly the collective's result; ``-start`` definitions are SKIPPED —
# their tuple type interleaves operands with results (e.g.
# ``(f32[8,128], f32[64,128]) all-gather-start``), so summing it would
# overcount payloads by ~the operand size.  Tuple types allow one level
# of nesting (variadic starts/dones).
_COLL_RE = re.compile(
    r"=\s*(?P<ty>\((?:[^()]|\([^()]*\))*\)"
    r"|[a-z0-9\-]+\[[^\]]*\](?:\{[^}]*\})?)\s+"
    r"%?(?P<op>all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute|collective-broadcast)(?P<suffix>-start|-done)?\(")
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([0-9, ]*)\}")
_DEF_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=")
_BULK_SUFFIX_RE = re.compile(r"_k\d+r?$")
_OPERAND_RE = re.compile(r"%([\w.\-]+)")


# -- aval arithmetic ---------------------------------------------------------

def _aval_entry_bytes(entry) -> int:
    """Bytes of one ``persist.aval_sig`` entry; 1-tuples (non-array
    leaves — python scalars riding as weak-typed inputs) count 0."""
    if len(entry) != 2:
        return 0
    import numpy as np
    shape, dtype = entry
    n = 1
    for d in shape:
        n *= int(d)
    try:
        return n * np.dtype(dtype).itemsize
    except TypeError:
        return 0


def _flatten_args(args, donate) -> Tuple[list, set]:
    """Per-positional-arg flattening: ``(flat aval list, donated flat
    index set)``.  ``donate`` holds POSITIONAL argnums (what
    ``jax.jit(donate_argnums=...)`` takes); pytree args (the SPMD
    trainer passes tuples) flatten to several leaves each, so the flat
    index set is derived per arg, not assumed 1:1."""
    from ..engine import persist
    donate_set = set(int(d) for d in donate)
    flat: list = []
    donated: set = set()
    for i, a in enumerate(args):
        leaves = persist.aval_sig([a])
        start = len(flat)
        flat.extend(leaves)
        if i in donate_set:
            donated.update(range(start, start + len(leaves)))
    return flat, donated


# -- HLO collective walk -----------------------------------------------------

def _shape_bytes(type_str: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(type_str):
        size = _DTYPE_BYTES.get(dtype)
        if size is None:
            continue            # token types (s32[] indices still match)
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * size
    return total


def _wire_bytes(op: str, payload: int, k: int) -> int:
    """Analytic per-device bytes-on-wire for one collective (ring
    algorithm; ``payload`` = the op's per-device RESULT bytes, ``k`` =
    replica-group size).  all-reduce moves 2N(k-1)/k (reduce-scatter +
    all-gather phases); reduce-scatter's HLO result is the scattered
    1/k shard, so its N(k-1)/k reads ``result*(k-1)``; all-gather's
    result is the full gathered tensor, N(k-1)/k directly."""
    if k <= 1:
        return 0
    if op == "all-reduce":
        return int(2 * payload * (k - 1) / k)
    if op == "reduce-scatter":
        return int(payload * (k - 1))
    if op in ("all-gather", "all-to-all"):
        return int(payload * (k - 1) / k)
    # collective-permute / collective-broadcast: the payload crosses
    # the wire once
    return int(payload)


def _group_size(line: str) -> Optional[int]:
    g = _GROUPS_IOTA_RE.search(line)
    if g:
        return int(g.group(2))
    g = _GROUPS_LIST_RE.search(line)
    if g:
        return len([t for t in g.group(1).split(",") if t.strip()])
    return None


def collective_stats(hlo_text: str,
                     default_group: Optional[int] = None) -> dict:
    """Count collective ops in compiled HLO text and derive analytic
    traffic: ``{kind: {count, payload_bytes, wire_bytes}}`` plus a
    ``total_wire_bytes`` roll-up.  Payloads are the per-device result
    bytes XLA printed (async pairs counted once, at the ``-done``);
    group size comes from ``replica_groups`` on the instruction — or
    its paired ``-start``, where the attribute lives for async forms —
    falling back to ``default_group`` or the process device count."""
    kinds: Dict[str, dict] = {}
    total_wire = 0
    start_groups: Dict[str, int] = {}
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if m is None:
            continue
        if m.group("suffix") == "-start":
            # payload counted at the paired -done, whose result type
            # is the collective's actual result (see _COLL_RE note);
            # remember the group size the -done line won't carry
            k = _group_size(line)
            d = _DEF_RE.match(line)
            if k and d:
                start_groups[d.group(1)] = k
            continue
        op = m.group("op")
        payload = _shape_bytes(m.group("ty"))
        k = _group_size(line)
        if not k and m.group("suffix") == "-done":
            for opname in _OPERAND_RE.findall(line[m.end():]):
                if opname in start_groups:
                    k = start_groups[opname]
                    break
        if not k:
            k = default_group
        if not k:
            try:
                import jax
                k = jax.device_count()
            except Exception:
                k = 1
        row = kinds.setdefault(
            op, {"count": 0, "payload_bytes": 0, "wire_bytes": 0})
        row["count"] += 1
        row["payload_bytes"] += payload
        wire = _wire_bytes(op, payload, k)
        row["wire_bytes"] += wire
        total_wire += wire
    return {"kinds": kinds, "total_wire_bytes": total_wire}


# -- harvest -----------------------------------------------------------------

def _note_unavailable(name: str, what: str, err: str):
    with _lock:
        if _unavailable_reported[0]:
            return
        _unavailable_reported[0] = True
    record_event("mem_analysis_unavailable", op=name, what=what,
                 error=err[:200])


def _memory_stats(name, compiled) -> Optional[dict]:
    try:
        stats = compiled.memory_analysis()
        if stats is None:
            raise ValueError("memory_analysis returned None")
        return {
            "argument_bytes": int(stats.argument_size_in_bytes),
            "output_bytes": int(stats.output_size_in_bytes),
            "temp_bytes": int(stats.temp_size_in_bytes),
            "generated_code_bytes":
                int(stats.generated_code_size_in_bytes),
            "alias_bytes": int(stats.alias_size_in_bytes),
        }
    except Exception as e:
        _note_unavailable(name, "memory_analysis", repr(e))
        return None


def _cost_stats(name, compiled) -> Optional[dict]:
    try:
        cost = compiled.cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0] if cost else None
        if not isinstance(cost, dict):
            raise ValueError(f"cost_analysis returned {type(cost)}")
        out = {}
        if "flops" in cost:
            out["flops"] = float(cost["flops"])
        if "bytes accessed" in cost:
            out["bytes_accessed"] = float(cost["bytes accessed"])
        return out or None
    except Exception as e:
        _note_unavailable(name, "cost_analysis", repr(e))
        return None


def device_capacity() -> Optional[int]:
    """Per-device memory capacity in bytes (``bytes_limit`` from
    ``device.memory_stats()``), or None where the backend does not
    report one (CPU) — the oom-risk check is inert then.  Probed once
    per process."""
    if not _capacity_cache:
        cap = None
        try:
            import jax
            stats = jax.local_devices()[0].memory_stats()
            if stats:
                cap = int(stats.get("bytes_limit") or 0) or None
        except Exception:
            cap = None
        _capacity_cache.append(cap)
    return _capacity_cache[0]


def _check_oom_risk(name: str, peak_bytes: Optional[int],
                    argument_bytes: Optional[int]):
    cap = device_capacity()
    if not cap or not peak_bytes:
        return
    from .. import engine
    live = engine.live_bytes()
    # the program's arguments (params, states, inputs) are themselves
    # live buffers, so live + peak would double-count them; the
    # program's NEW demand on top of what already resides is
    # peak - arguments (output + temp + code)
    extra = max(0, peak_bytes - (argument_bytes or 0))
    if live + extra > OOM_RISK_RATIO * cap:
        record_event(
            "oom_risk", op=name, live_bytes=live,
            program_peak_bytes=peak_bytes,
            program_extra_bytes=extra, capacity_bytes=cap,
            ratio=round((live + extra) / cap, 4))


def _single_device() -> bool:
    """True when the process sees one device — no program can carry a
    cross-device collective, so the HLO-text walk is pure waste."""
    try:
        import jax
        return jax.device_count() <= 1
    except Exception:
        return False


def harvest_compiled(name: str, compiled, args=(), donate=(),
                     out_avals=None, source: str = "fresh",
                     kind: str = "program",
                     cached_memory: Optional[dict] = None
                     ) -> Optional[dict]:
    """Record everything XLA knows about one compiled program.

    Called from the engine's tiered AOT seam (fresh compiles AND
    persistent-tier reloads) — never raises, returns the record (or
    ``None`` with telemetry disabled).  ``args`` are the call's
    positional arguments (arrays / ShapeDtypeStructs / pytrees of
    them); ``donate`` the positional donate argnums; ``out_avals`` the
    flattened output avals when the caller has them (``lowered
    .out_info`` — absent on deserialized executables, which only
    narrows MXL308, nothing else).  ``cached_memory`` is a persist
    entry's saved compact block: its per-kind collective table is
    reused so a warm-start reload never re-renders HLO text (which can
    be tens of MB for a large fused step) on the path the persistent
    cache exists to make fast.

    The record HOLDS ``compiled`` (key ``executable``; what
    ``profiler.device_scopes()`` asks for its text, later and only if a
    device trace is read).  That ties an executable's life to this
    table and no longer to its owner's: the newest executable of each
    program name, with the device memory of its code, stays until a
    newer one of that name is harvested or :func:`reset` is called,
    also after the engine has dropped it (``engine.drop_cached``) or
    its trainer is gone.  Nothing is held with telemetry disabled.
    """
    if not _switch.enabled:
        return None
    try:
        from ..engine import persist
        in_avals, donated = _flatten_args(args, donate)
        donation_saved = sum(_aval_entry_bytes(in_avals[j])
                             for j in sorted(donated))
        mem = _memory_stats(name, compiled)
        analytic = mem is None
        if analytic:
            # aval-based estimate: argument bytes are exact, outputs/
            # temp unknowable without the executable's word
            mem = {"argument_bytes": sum(_aval_entry_bytes(e)
                                         for e in in_avals),
                   "output_bytes": None, "temp_bytes": None,
                   "generated_code_bytes": None, "alias_bytes": None}
            peak = mem["argument_bytes"]
        else:
            peak = (mem["argument_bytes"] + mem["output_bytes"]
                    + mem["temp_bytes"] + mem["generated_code_bytes"]
                    - mem["alias_bytes"])
        cost = _cost_stats(name, compiled)
        coll = None
        if cached_memory is not None and \
                isinstance(cached_memory.get("collectives"), dict):
            coll = {"kinds": cached_memory["collectives"],
                    "total_wire_bytes":
                        cached_memory.get("collective_wire_bytes") or 0}
        elif _single_device():
            # a one-device program cannot contain cross-device
            # collectives; skip rendering its HLO text entirely
            coll = {"kinds": {}, "total_wire_bytes": 0}
        else:
            try:
                coll = collective_stats(compiled.as_text())
            except Exception as e:
                _note_unavailable(name, "as_text", repr(e))
        out_sig = None
        if out_avals is not None:
            try:
                out_sig = persist.aval_sig(list(out_avals))
            except Exception:
                out_sig = None
        rec = {
            "name": name, "kind": kind, "source": source,
            "analytic": analytic, "peak_bytes": peak,
            **mem,
            "donation_saved_bytes": int(donation_saved),
            "donated_args": len(donated),
            "flops": (cost or {}).get("flops"),
            "bytes_accessed": (cost or {}).get("bytes_accessed"),
            "collectives": (coll or {}).get("kinds", {}),
            "collective_wire_bytes":
                (coll or {}).get("total_wire_bytes", 0),
            "in_avals": in_avals, "donated_idx": sorted(donated),
            "out_avals": out_sig,
            # the executable itself (an ``export`` reload is a plain
            # jit function and has no text to ask for): what
            # ``profiler.device_scopes()`` asks for its HLO text when a
            # device trace is read, and never before.  A plain
            # reference, one a program name like the record: a weak one
            # was dead by the time the benchmark read its trace (a
            # freshly compiled train step is owned by its trainer, which
            # the train driver drops before the readers run; the chip
            # showed it, PERF.md section 6, PR 37)
            "executable": compiled if hasattr(compiled, "as_text")
            else None,
        }
        with _lock:
            prev = _programs.get(name)
            rec["harvests"] = (prev["harvests"] + 1) if prev else 1
            _harvest_seq[0] += 1
            rec["seq"] = _harvest_seq[0]
            _programs[name] = rec
            max_peak = max((r["peak_bytes"] or 0)
                           for r in _programs.values())
        gauge("mxtpu_program_peak_bytes",
              "largest per-device peak footprint (arg+out+temp+code-"
              "alias) among harvested programs").set(max_peak)
        if donated:
            gauge("mxtpu_donation_saved_bytes",
                  "HBM bytes the most recently harvested donating "
                  "program avoids double-buffering").set(donation_saved)
        if rec["collective_wire_bytes"]:
            gauge("mxtpu_collective_bytes_per_step",
                  "analytic per-device bytes-on-wire of the most "
                  "recently harvested collective-bearing program"
                  ).set(rec["collective_wire_bytes"])
        _check_oom_risk(name, peak, mem["argument_bytes"])
        return rec
    except Exception:
        # the observatory must never cost a dispatch or a compile
        return None


def programs() -> Dict[str, dict]:
    """Snapshot of every harvested program record (name -> record)."""
    with _lock:
        return {k: dict(v) for k, v in _programs.items()}


def executables() -> List[Tuple[str, Any]]:
    """``(program name, compiled executable)`` of every harvested
    program, in harvest order: the newest executable of each name, kept
    with its record until :func:`reset`.  Nothing is rendered here: the
    caller decides what to ask an executable
    (``profiler.device_scopes`` asks for ``as_text()``, once, when a
    device trace is read)."""
    with _lock:
        recs = sorted(_programs.values(), key=lambda r: r["seq"])
    return [(r["name"], r["executable"]) for r in recs
            if r.get("executable") is not None]


# -- live-buffer + param census ----------------------------------------------

def _sharding_info(v) -> Tuple[str, bool]:
    """``(spec string, fully-replicated?)`` of one device array — THE
    replicated-detection rule MXL309 (params) and MXL310 (optimizer
    state) both judge by, so the two censuses can never disagree on
    what "replicated" means."""
    spec = ""
    replicated = True
    try:
        s = v.sharding
        spec = str(getattr(s, "spec", ""))
        replicated = not any(
            ax is not None for ax in getattr(s, "spec", ()) or ())
    except Exception:
        pass
    return spec, replicated

def census() -> dict:
    """Per-device HBM bytes of the engine's live tracked buffers:
    ``{"total_bytes", "count", "by_device"}``.  Donated/deleted buffers
    are skipped (the ``waitall`` guard); per-device attribution comes
    from addressable shards, so a replicated array counts once per
    device holding it.  Updates the ``mxtpu_hbm_live_bytes`` gauge."""
    from .. import engine
    total = 0
    count = 0
    by_device: Dict[str, int] = {}
    for arr in engine.live_arrays():
        try:
            if getattr(arr, "is_deleted", lambda: False)():
                continue
            nb = int(arr.nbytes)
        except Exception:
            continue
        total += nb
        count += 1
        try:
            for shard in arr.addressable_shards:
                dev = str(shard.device)
                by_device[dev] = by_device.get(dev, 0) \
                    + int(shard.data.nbytes)
        except Exception:
            by_device["unknown"] = by_device.get("unknown", 0) + nb
    if _switch.enabled:
        gauge("mxtpu_hbm_live_bytes",
              "bytes of live (non-donated, non-deleted) tracked "
              "device buffers").set(total)
    return {"total_bytes": total, "count": count,
            "by_device": by_device}


def _param_items(params):
    if hasattr(params, "collect_params"):
        params = params.collect_params()
    if hasattr(params, "items"):
        return list(params.items())
    out = []
    for p in params:
        out.append((getattr(p, "name", repr(p)), p))
    return out


def param_census(params) -> dict:
    """Attribute HBM bytes to gluon parameters by name.

    ``params`` may be a block (``collect_params()`` is called), a
    ``ParameterDict``, or an iterable of Parameters.  Rows are sorted
    largest first; ``total_bytes`` is their sum (deferred-init
    parameters carry no buffer yet and are skipped).  Each row records
    the sharding spec and whether the buffer is fully replicated —
    the MXL309 signal."""
    rows = []
    total = 0
    for name, p in _param_items(params):
        try:
            d = p.data()
            v = d._data
            nb = int(v.nbytes)
        except Exception:
            continue
        spec, replicated = _sharding_info(v)
        rows.append({"name": name, "shape": list(d.shape),
                     "dtype": str(d.dtype), "nbytes": nb,
                     "sharding": spec, "replicated": replicated})
        total += nb
    rows.sort(key=lambda r: -r["nbytes"])
    return {"params": rows, "total_bytes": total, "count": len(rows)}


def note_param_tree(name: str, params, mesh=None,
                    dp_axis: Optional[str] = None):
    """Register a sharded param layout for the MXL309 pass (called by
    ``DataParallelTrainer`` after placing its params on the mesh).  A
    snapshot, not a live view — re-registering under the same name
    replaces it.  No-op with telemetry disabled."""
    if not _switch.enabled:
        return
    try:
        tree = param_census(params)
        mesh_size = 1
        dp_size = 1
        if mesh is not None:
            try:
                for v in mesh.shape.values():
                    mesh_size *= int(v)
                if dp_axis is not None:
                    dp_size = int(mesh.shape.get(dp_axis, 1))
            except Exception:
                pass
        tree["mesh_size"] = mesh_size
        tree["dp_size"] = dp_size
        tree["dp_axis"] = dp_axis
        with _lock:
            _param_trees[name] = tree
    except Exception:
        pass


def param_trees() -> Dict[str, dict]:
    with _lock:
        return {k: dict(v) for k, v in _param_trees.items()}


def opt_state_census(leaves) -> dict:
    """Attribute HBM bytes to optimizer-state leaves, split into
    per-replica SHARDED vs REPLICATED residency.

    ``leaves``: iterable of ``(label, jax array)`` (what
    ``DataParallelTrainer._opt_state_leaves`` registers).  Each row
    records global bytes, per-DEVICE bytes (the sharding's
    ``shard_shape`` — a leaf sharded over dp counts 1/dp per device),
    and the replicated flag.  ``per_device_bytes = replicated_bytes +
    sharded_bytes_per_device`` is the figure the ZeRO ~dp x drop is
    measured against (gauge ``mxtpu_optimizer_state_bytes``)."""
    import numpy as np
    rows = []
    total = 0
    per_device = 0
    sharded_pd = 0
    repl_b = 0
    for name, v in leaves:
        try:
            nb = int(v.nbytes)
        except Exception:
            continue
        spec, replicated = _sharding_info(v)
        pd = nb
        try:
            shard_shape = v.sharding.shard_shape(v.shape)
            pd = int(np.prod(shard_shape)) * int(v.dtype.itemsize)
        except Exception:
            pass
        rows.append({"name": str(name), "shape": list(v.shape),
                     "dtype": str(v.dtype), "nbytes": nb,
                     "bytes_per_device": pd, "sharding": spec,
                     "replicated": replicated})
        total += nb
        per_device += pd
        if replicated:
            repl_b += nb
        else:
            sharded_pd += pd
    rows.sort(key=lambda r: -r["nbytes"])
    return {"leaves": rows, "count": len(rows), "total_bytes": total,
            "per_device_bytes": per_device,
            "replicated_bytes": repl_b,
            "sharded_bytes_per_device": sharded_pd}


def note_opt_state(name: str, leaves, mesh=None,
                   dp_axis: Optional[str] = None, zero_stage: int = 0):
    """Register a trainer's optimizer-state layout (called by
    ``DataParallelTrainer`` after state creation).  A snapshot —
    re-registering under the same name replaces it.  Sets the
    ``mxtpu_optimizer_state_bytes`` gauge to the per-device total so
    the ZeRO drop is measurable, not asserted.  No-op with telemetry
    disabled."""
    if not _switch.enabled:
        return
    try:
        tree = opt_state_census(leaves)
        mesh_size = 1
        dp_size = 1
        if mesh is not None:
            try:
                for v in mesh.shape.values():
                    mesh_size *= int(v)
                if dp_axis is not None:
                    dp_size = int(mesh.shape.get(dp_axis, 1))
            except Exception:
                pass
        tree["mesh_size"] = mesh_size
        tree["dp_size"] = dp_size
        tree["dp_axis"] = dp_axis
        tree["zero_stage"] = int(zero_stage)
        with _lock:
            _opt_trees[name] = tree
        gauge("mxtpu_optimizer_state_bytes",
              "per-device optimizer-state bytes of the most recently "
              "registered trainer (replicated + sharded shard)"
              ).set(tree["per_device_bytes"])
    except Exception:
        pass


def opt_state_trees() -> Dict[str, dict]:
    with _lock:
        return {k: dict(v) for k, v in _opt_trees.items()}


# -- reporting ---------------------------------------------------------------

def _compact(rec: dict) -> dict:
    """A program record without its aval lists and its executable (the
    report/cache_info face, which is written out as JSON; the full
    record stays in :func:`programs`)."""
    return {k: v for k, v in rec.items()
            if k not in ("in_avals", "out_avals", "donated_idx",
                         "executable")}


def _latest_per_base(recs) -> List[dict]:
    """One record per LOGICAL program: ``step_multi`` bulking harvests
    ``<base>_k{K}[r]`` variants of the same train step (the scan-body
    collective still reads as one inner step's traffic), so summing a
    base with its bulk variants would double-count per-step numbers.
    Keeps the most recently harvested variant of each base."""
    latest: Dict[str, dict] = {}
    for r in recs:
        base = _BULK_SUFFIX_RE.sub("", r.get("name") or "")
        prev = latest.get(base)
        if prev is None or (r.get("seq") or 0) > (prev.get("seq") or 0):
            latest[base] = r
    return list(latest.values())


def report(top_n: Optional[int] = None, params=None) -> dict:
    """The observatory's one-call summary: top-N programs by peak
    bytes, the live-buffer census, collective traffic, device capacity,
    and (when ``params`` is given) the per-param HBM table.  This is
    what ``tools/mxmem.py`` renders."""
    if top_n is None:
        from .. import envs
        top_n = envs.get("MXTPU_MEM_REPORT_TOP_N")
    progs = sorted(programs().values(),
                   key=lambda r: -(r["peak_bytes"] or 0))
    coll: Dict[str, dict] = {}
    for r in _latest_per_base(progs):
        for op, row in (r.get("collectives") or {}).items():
            agg = coll.setdefault(
                op, {"count": 0, "payload_bytes": 0, "wire_bytes": 0})
            for k in agg:
                agg[k] += row.get(k, 0)
    out = {
        "n_programs": len(progs),
        "programs": [_compact(r) for r in progs[:max(0, int(top_n))]],
        "live": census(),
        "collectives": coll,
        "device_capacity_bytes": device_capacity(),
    }
    if params is not None:
        out["param_census"] = param_census(params)
    opt_trees = opt_state_trees()
    if opt_trees:
        out["opt_states"] = opt_trees
    return out


def dump_report(path: str, top_n: Optional[int] = None,
                params=None) -> str:
    """Write :func:`report` as a JSON artifact ``tools/mxmem.py
    render`` can display offline; returns the path."""
    import json
    import os
    rep = report(top_n=top_n, params=params)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(rep, f, indent=1, default=str)
    os.replace(tmp, path)
    return path


def cache_info_block() -> dict:
    """The ``engine.cache_info()["memory"]`` view: per-program compact
    records plus roll-ups.  Empty when nothing harvested (telemetry
    off, or no tiered compiles yet)."""
    with _lock:
        progs = {k: _compact(v) for k, v in _programs.items()}
    if not progs:
        return {"programs": 0, "per_program": {}}
    per_base = _latest_per_base(progs.values())
    return {
        "programs": len(progs),
        "max_peak_bytes": max((r["peak_bytes"] or 0)
                              for r in progs.values()),
        "donation_saved_bytes": sum(r["donation_saved_bytes"]
                                    for r in per_base),
        "collective_wire_bytes": sum(r["collective_wire_bytes"]
                                     for r in per_base),
        "per_program": progs,
    }


def reset():
    """Forget every harvested program, param tree, and the
    once-per-process unavailable flag (test isolation; part of
    ``telemetry.reset()``).  The device-capacity probe survives — it
    cannot change within a process."""
    with _lock:
        _programs.clear()
        _param_trees.clear()
        _opt_trees.clear()
        _unavailable_reported[0] = False
