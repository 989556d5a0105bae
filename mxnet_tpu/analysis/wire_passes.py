"""mxwire: the jaxpr-level wire-leg auditor (MXL8xx;
docs/static_analysis.md, "The wire auditor").

Every compiled fused-step variant — train single / ``step_multi``,
the ZeRO stages, the compressed paths, serving prefill/decode —
registers an abstract signature here (:func:`note_step`, riding the
same seams that feed ``planner.note_plan`` and the memory
observatory).  The auditor traces each variant's **closed jaxpr**
lazily and walks it into a **wire-leg inventory**: every collective
primitive (``psum``, ``psum_scatter``/``reduce_scatter``,
``all_gather``, ``all_to_all``, ``ppermute``) classified by leg kind
— dp grad sync, ZeRO scatter/gather, tp activation, decode — via its
axis names resolved through the live :class:`ShardingPlan`, with wire
dtype, payload bytes, and analytic bytes-on-wire (the SAME ring
formulas the memory observatory applies to compiled HLO —
``telemetry.memory._wire_bytes`` — so the static and runtime
accountings are commensurable by construction).

The rules (:func:`analyze_wire`, riding ``self_check()`` /
``mxlint --self-check``; standalone: ``tools/mxwire.py``):

* **MXL801** (error) — a leg whose ON-WIRE dtype is wider than the
  plan's declared ``precision`` for that leg kind: the silent
  fp32-widening class (a "quantized" grad leg paying full-width
  bytes).  Sub-4KiB payloads are exempt (the fp32 scale lanes every
  block-scaled scheme ships beside its codes), as are ``stats`` /
  ``scalar`` legs.
* **MXL802** (error) — a full all-reduce surviving on a ZeRO-2 grad
  leg: the stage-2 wire contract is reduce-scatter + all-gather;
  a grad-sized ungated ``psum`` over the dp axis there moves the
  whole gradient anyway (previously a runtime wire-assertion, now
  static).
* **MXL803** (warning) — an observability-only collective (a leg
  whose outputs feed ONLY the health/stats outputs — a backward
  liveness slice finds them) executing OUTSIDE any ``lax.cond``
  sampling gate in a variant registered as sampled: the
  integrity/health spec claims those rows are gated, so an ungated
  one pays unsampled wire cost every step.
* **MXL804** (warning) — the static bytes-on-wire total diverging
  more than ``drift`` (default 10%) from the memory observatory's
  runtime accounting for the same program: either the static model
  or the runtime counter is lying, and both feed the compression
  -ratio claims.

Free in a fresh process (empty registry — the CI gate stays quiet);
``MXTPU_WIRE_AUDIT=0`` disables registration entirely.  Registration
stores ONLY aval signatures (``jax.ShapeDtypeStruct``) — never live
arrays, so noting a variant cannot pin HBM.
"""
from __future__ import annotations

import threading
from typing import Dict, List, Optional, Tuple

from .findings import Finding

__all__ = ["note_step", "variants", "analyze_wire", "wire_report",
           "SCALAR_LEG_BYTES"]

_lock = threading.Lock()
#: (owner, variant) -> registered record
_variants: Dict[Tuple[str, str], dict] = {}

#: jaxpr collective primitive -> the HLO op name the observatory's
#: analytic ring model (``telemetry.memory._wire_bytes``) speaks
_COLLECTIVE_HLO = {
    "psum": "all-reduce",
    "pmax": "all-reduce",
    "pmin": "all-reduce",
    "reduce_scatter": "reduce-scatter",
    "all_gather": "all-gather",
    "all_to_all": "all-to-all",
    "ppermute": "collective-permute",
    "pbroadcast": "collective-broadcast",
}

#: a load-bearing dp reduction below this is a ``scalar`` leg (loss /
#: aux pmeans, the fp32 scale lanes riding beside quantized codes) —
#: inventoried, never precision-audited
SCALAR_LEG_BYTES = 4096
#: MXL802 only fires on grad-sized payloads: healthy stage-2 variants
#: still psum tiny stats rows under their sampling gate
_MXL802_FLOOR = 16384


# -- registry ---------------------------------------------------------------

def _aval(v):
    """One value -> its abstract signature (never holds the array)."""
    import jax
    if hasattr(v, "shape") and hasattr(v, "dtype"):
        return jax.ShapeDtypeStruct(tuple(v.shape), v.dtype)
    return v                    # python scalar: weak-typed, no HBM


def note_step(owner: str, variant: str, fn, vals, *,
              plan=None, mesh_axes=None, dp_axis: Optional[str] = None,
              zero_stage: int = 0, compressed: bool = False,
              sampled: bool = False, kind: str = "train",
              program: Optional[str] = None, params_bytes=None,
              obs_outputs=()) -> None:
    """Register one compiled step variant for the wire audit.

    ``fn`` is the PURE python step function (what the trainer jits),
    ``vals`` its example positional arguments — converted to
    ``ShapeDtypeStruct`` immediately; the jaxpr is traced lazily at
    audit time.  ``program`` names the memory-observatory record the
    MXL804 reconciliation reads; ``params_bytes`` is the
    ``[(name, nbytes, dtype_name)]`` trainable census the derived
    dense-dp leg model needs (GSPMD inserts the grad all-reduce
    implicitly, so a dense jaxpr carries no collective to walk);
    ``obs_outputs`` are the (negative-ok) flat output indices that
    are observability-only (the health vector).  Never raises
    (telemetry-grade); ``MXTPU_WIRE_AUDIT=0`` makes it a no-op.
    """
    try:
        from .. import envs
        if fn is None or not envs.get("MXTPU_WIRE_AUDIT"):
            return
        import jax
        # vals are pytrees (the trainers pass nested tuples): convert
        # every LEAF, or the registry would pin the live arrays
        avals = jax.tree_util.tree_map(_aval, tuple(vals))
        axes = dict(mesh_axes) if mesh_axes else \
            dict(getattr(plan, "axes", None) or {})
        rec = {
            "owner": str(owner), "variant": str(variant),
            "fn": fn, "avals": avals, "plan": plan,
            "mesh_axes": axes,
            "dp_axis": str(dp_axis) if dp_axis else
            str(getattr(plan, "dp_axis", "dp")),
            "zero_stage": int(zero_stage or 0),
            "compressed": bool(compressed),
            "sampled": bool(sampled), "kind": str(kind),
            "program": None if program is None else str(program),
            "params_bytes": [(str(n), int(b), str(d))
                             for n, b, d in (params_bytes or ())],
            "obs_outputs": tuple(int(i) for i in (obs_outputs or ())),
            "jaxpr": None, "trace_error": None, "legs": None,
        }
        with _lock:
            _variants[(rec["owner"], rec["variant"])] = rec
    except Exception:
        pass


def variants() -> Dict[Tuple[str, str], dict]:
    """Registered variants (shallow copies; ``legs``/``jaxpr`` may be
    unpopulated until an audit ran)."""
    with _lock:
        return {k: dict(v) for k, v in _variants.items()}


def _reset():
    """Test hook."""
    with _lock:
        _variants.clear()


# -- the jaxpr walk ---------------------------------------------------------

def _traced(rec):
    """The variant's closed jaxpr, traced once and cached; ``None``
    (with ``trace_error`` set and a telemetry event) when the pure fn
    cannot be abstractly traced."""
    if rec.get("jaxpr") is not None or rec.get("trace_error"):
        return rec.get("jaxpr")
    import jax
    try:
        rec["jaxpr"] = jax.make_jaxpr(rec["fn"])(*rec["avals"])
    except Exception as e:
        rec["trace_error"] = repr(e)[:300]
        try:
            from ..telemetry import record_event
            record_event("wire_trace_unavailable",
                         owner=rec["owner"], variant=rec["variant"],
                         error=rec["trace_error"])
        except Exception:
            pass
    return rec.get("jaxpr")


def _eqn_axes(eqn) -> tuple:
    """Named axes one collective eqn reduces/moves over (``psum``
    spells them ``axes``, the others ``axis_name`` — which
    ``all_to_all`` carries as a bare string, the rest as a tuple)."""
    p = eqn.params
    ax = p.get("axes")
    if ax is None:
        ax = p.get("axis_name")
    if ax is None:
        return ()
    if not isinstance(ax, (tuple, list)):
        ax = (ax,)
    return tuple(a for a in ax if isinstance(a, str))


def _sub_jaxprs(eqn) -> list:
    """Every sub-jaxpr an eqn carries (pjit/scan/while ClosedJaxprs,
    shard_map's plain Jaxpr, cond's branch tuple), as plain Jaxprs."""
    from jax.extend.core import ClosedJaxpr, Jaxpr
    out = []
    for v in eqn.params.values():
        items = v if isinstance(v, (tuple, list)) else (v,)
        for item in items:
            if isinstance(item, Jaxpr):
                out.append(item)
            elif isinstance(item, ClosedJaxpr):
                out.append(item.jaxpr)
    return out


def _note_leg(eqn, gated, obs_only, mesh_axes, legs):
    import numpy as np
    from ..telemetry.memory import _wire_bytes
    axes = _eqn_axes(eqn)
    if not axes:
        return                  # positional (vmap) axes: not wire
    op = _COLLECTIVE_HLO[eqn.primitive.name]
    k = 1
    for ax in axes:
        k *= int(mesh_axes.get(ax, 1))
    payload, itemsize, dtype = 0, 0, None
    for v in eqn.outvars:
        aval = getattr(v, "aval", None)
        shape = getattr(aval, "shape", None)
        dt = getattr(aval, "dtype", None)
        if shape is None or dt is None:
            continue
        dt = np.dtype(dt)
        n = 1
        for d in shape:
            n *= int(d)
        payload += n * dt.itemsize
        if dt.itemsize > itemsize:
            itemsize, dtype = int(dt.itemsize), str(dt.name)
    legs.append({
        "primitive": eqn.primitive.name, "op": op, "axes": axes,
        "group": k, "dtype": dtype, "itemsize": itemsize,
        "payload_bytes": int(payload),
        "wire_bytes": int(_wire_bytes(op, payload, k)),
        "gated": bool(gated), "obs_only": bool(obs_only),
        "implicit": False,
    })


def _walk(jaxpr, obs_idx, gated, mesh_axes, legs, state):
    """One jaxpr level: a backward liveness pass splits every var into
    primal-live / obs-live (relative to this level's ``obs_idx``
    output positions), so a collective whose outputs feed ONLY the
    observability outputs is tagged ``obs_only``; dead eqns (XLA DCEs
    them — the compiled HLO carries no trace) are skipped entirely.
    Descends into pjit/scan/while bodies once and BOTH cond branches
    (matching ``collective_stats``'s count-per-HLO-text-appearance
    convention, so MXL804 compares like with like); cond branches are
    ``gated``; shard_map scopes its own mesh axis sizes."""
    from jax.extend.core import Literal
    n_out = len(jaxpr.outvars)
    idxset = {i % n_out for i in obs_idx} if (n_out and obs_idx) \
        else set()
    primal, obs = set(), set()
    for i, v in enumerate(jaxpr.outvars):
        if isinstance(v, Literal):
            continue
        (obs if i in idxset else primal).add(v)
    for eqn in reversed(jaxpr.eqns):
        flags = [((v in primal), (v in obs)) for v in eqn.outvars]
        p_live = any(p for p, _o in flags)
        o_live = any(o for _p, o in flags)
        if not (p_live or o_live):
            continue
        for v in eqn.invars:
            if isinstance(v, Literal):
                continue
            if p_live:
                primal.add(v)
            if o_live:
                obs.add(v)
        name = eqn.primitive.name
        if name in _COLLECTIVE_HLO:
            _note_leg(eqn, gated, o_live and not p_live,
                      mesh_axes, legs)
            continue
        subs = _sub_jaxprs(eqn)
        if not subs:
            continue
        sub_mesh = mesh_axes
        if name == "shard_map":
            state["shard_map"] = True
            shape = getattr(eqn.params.get("mesh"), "shape", None)
            if shape:
                sub_mesh = dict(shape)
        sub_gated = gated or name == "cond"
        for sub in subs:
            if len(sub.outvars) == len(eqn.outvars):
                # 1:1 outvar mapping (pjit, cond branches, shard_map;
                # scan: carry + ys line up positionally on both sides)
                sub_obs = tuple(i for i, (p, o) in enumerate(flags)
                                if o and not p)
            elif o_live and not p_live:
                sub_obs = tuple(range(len(sub.outvars)))
            else:
                sub_obs = ()    # conservative: treat all as primal
            _walk(sub, sub_obs, sub_gated, sub_mesh, legs, state)


# -- classification ---------------------------------------------------------

def _classify(leg, rec) -> str:
    """Leg kind via the plan's role axes: ``stats`` (obs-only) >
    serving ``decode`` > ``tp_act``/``pp``/``sp`` > the dp branch
    (``scalar`` below 4KiB; under ZeRO, reduce-scatter/all-gather are
    the ``zero_scatter``/``zero_gather`` contract legs; every other
    dp collective — including a quantized path's all-gather of int8
    codes at stage 0 — is grad sync)."""
    if leg["obs_only"]:
        return "stats"
    if rec.get("kind") != "train":
        return "decode"
    plan = rec.get("plan")
    dp = rec.get("dp_axis") or getattr(plan, "dp_axis", "dp")
    axes = set(leg["axes"])
    if getattr(plan, "tp_axis", "tp") in axes:
        return "tp_act"
    if getattr(plan, "pp_axis", "pp") in axes:
        return "pp"
    if getattr(plan, "sp_axis", "sp") in axes:
        return "sp"
    if dp in axes:
        if leg["payload_bytes"] < SCALAR_LEG_BYTES:
            return "scalar"
        if int(rec.get("zero_stage") or 0) >= 1:
            if leg["op"] == "reduce-scatter":
                return "zero_scatter"
            if leg["op"] == "all-gather":
                return "zero_gather"
        return "dp_grad"
    return "other"


def _implicit_legs(rec) -> list:
    """The derived dense-dp model: under plain jit + GSPMD the grad
    all-reduce never appears in the jaxpr (the partitioner inserts
    it), so a dense dp-only variant gets one implicit all-reduce leg
    per trainable param — payload = the param's global bytes, dtype =
    the param dtype.  This is what makes MXL801 and the MXL804
    reconciliation reach the dense path at all."""
    import numpy as np
    from ..telemetry.memory import _wire_bytes
    dp = rec.get("dp_axis") or "dp"
    k = int((rec.get("mesh_axes") or {}).get(dp, 1))
    legs = []
    if k <= 1:
        return legs
    for name, nbytes, dtype in rec.get("params_bytes") or ():
        try:
            itemsize = int(np.dtype(dtype).itemsize)
        except TypeError:
            itemsize, dtype = 4, "float32"
        legs.append({
            "primitive": "psum", "op": "all-reduce", "axes": (dp,),
            "group": k, "dtype": dtype, "itemsize": itemsize,
            "payload_bytes": int(nbytes),
            "wire_bytes": int(_wire_bytes("all-reduce", nbytes, k)),
            "gated": False, "obs_only": False,
            "implicit": True, "param": name,
            "kind": ("dp_grad" if nbytes >= SCALAR_LEG_BYTES
                     else "scalar"),
        })
    return legs


def _legs_for(rec) -> Tuple[list, bool]:
    """``(legs, derived)`` for one registered variant: the walked
    inventory, with the implicit dense-dp grad model APPENDED when the
    trace carries no load-bearing explicit leg (the health plane's
    gated stats rows appear explicitly — inside their own nested
    shard_map — even in a dense jaxpr, but the grad all-reduce stays
    GSPMD-implicit; ZeRO/compressed variants carry their grad legs
    explicitly and derive nothing).  Dense tp>1 also derives nothing —
    GSPMD's tensor-parallel activation traffic is not modelable from
    the jaxpr, so neither MXL801 nor MXL804 can speak to it."""
    if rec.get("legs") is not None:
        return rec["legs"], bool(rec.get("derived"))
    legs: list = []
    state = {"shard_map": False}
    closed = _traced(rec)
    if closed is not None:
        _walk(closed.jaxpr, rec.get("obs_outputs") or (), False,
              dict(rec.get("mesh_axes") or {}), legs, state)
        for leg in legs:
            leg["kind"] = _classify(leg, rec)
    derived = False
    # "load-bearing": any explicit leg that moves primal data at
    # above-scalar size — a grad psum, a ZeRO scatter/gather, a
    # compressed-wire leg.  Obs-only stats rows and sub-4KiB scalars
    # never carry the gradient, so their presence must not suppress
    # the implicit dense-dp model.
    load_bearing = any(
        (not leg["obs_only"]) and
        leg["payload_bytes"] >= SCALAR_LEG_BYTES
        for leg in legs)
    if not load_bearing and \
            rec.get("kind") == "train" and rec.get("params_bytes"):
        axes = rec.get("mesh_axes") or {}
        dp = rec.get("dp_axis") or "dp"
        if all(int(v) == 1 for a, v in axes.items() if a != dp):
            legs = legs + _implicit_legs(rec)
            derived = True
    rec["legs"], rec["derived"] = legs, derived
    return legs, derived


# -- the rules --------------------------------------------------------------

def _measured_wire(rec) -> Optional[int]:
    """The observatory's runtime bytes-on-wire for this variant's
    program, or ``None`` when it was never harvested."""
    name = rec.get("program")
    if not name:
        return None
    from ..telemetry import memory as _memory
    prog = _memory.programs().get(name)
    if prog is None:
        return None
    return int(prog.get("collective_wire_bytes") or 0)


def _reconcile_eligible(rec, legs, derived) -> bool:
    """MXL804 compares only where the static model is complete: the
    derived dense dp-only model, or a variant whose GRAD wire is
    explicit in the jaxpr (the shard_map'd ZeRO/quantized steps).
    Compressed paths dispatch outside the tiered AOT seam (never
    harvested) and dense tp>1 has unmodelable GSPMD activation
    traffic riding beside an implicit grad all-reduce — a stats-only
    or tp-only explicit inventory is NOT a complete model, so both
    skip."""
    if rec.get("kind") != "train" or rec.get("compressed"):
        return False
    if derived:
        return True
    return any((not leg["implicit"]) and leg["kind"] in
               ("dp_grad", "zero_scatter", "zero_gather")
               for leg in legs)


def _audit_one(rec, drift: float,
               measured_override: Optional[int] = None
               ) -> List[Finding]:
    owner, variant = rec["owner"], rec["variant"]
    loc = f"wire:{owner}:{variant}" if variant else f"wire:{owner}"
    findings: List[Finding] = []
    legs, derived = _legs_for(rec)
    if rec.get("trace_error"):
        return findings         # fail-open; event already recorded
    plan = rec.get("plan")
    prec = getattr(plan, "precision", None) or {}
    zero_stage = int(rec.get("zero_stage") or 0)
    for leg in legs:
        kind, ax = leg["kind"], "/".join(leg["axes"])
        # MXL801 — wire dtype wider than the plan's declaration for
        # this leg kind.  Sub-4KiB payloads (scale lanes) are exempt;
        # stats/scalar/other kinds are never declarable.
        want = prec.get(kind)
        if want is not None and \
                leg["payload_bytes"] >= SCALAR_LEG_BYTES:
            from ..parallel.planner import wire_dtype_itemsize
            want_size = wire_dtype_itemsize(want)
            if leg["itemsize"] > want_size:
                what = f"param {leg['param']!r}" if leg.get("param") \
                    else f"a {leg['primitive']}"
                findings.append(Finding(
                    "MXL801",
                    f"{owner}:{variant or 'step'}: {kind} leg over "
                    f"axis {ax!r} ({what}, "
                    f"{leg['payload_bytes']} payload bytes) rides "
                    f"the wire as {leg['dtype']} "
                    f"({leg['itemsize']} B/elem) but the plan "
                    f"declares {kind}={want} ({want_size} B/elem) — "
                    f"the leg silently widened "
                    f"{leg['itemsize'] / want_size:.0f}x; route it "
                    "through the quantized collective family or fix "
                    "the plan's precision declaration", loc))
        # MXL802 — the stage-2 wire contract: grad sync must be
        # reduce-scatter + all-gather; a grad-sized ungated psum on
        # the dp axis moves the full gradient anyway.
        if zero_stage == 2 and kind == "dp_grad" and \
                leg["op"] == "all-reduce" and not leg["gated"] and \
                leg["payload_bytes"] >= _MXL802_FLOOR:
            findings.append(Finding(
                "MXL802",
                f"{owner}:{variant or 'step'}: a full all-reduce "
                f"({leg['payload_bytes']} payload bytes, "
                f"{leg['dtype']}) survives on the ZeRO-2 grad leg "
                f"over axis {ax!r} — stage 2 contracts "
                "reduce-scatter + all-gather (each member reduces "
                "only its shard); this psum moves the whole gradient "
                "and defeats the partitioning", loc))
        # MXL803 — an obs-only leg outside any lax.cond gate in a
        # variant registered as sampled: the health/integrity spec
        # says those rows ride the sampling gate.
        if rec.get("sampled") and kind == "stats" and \
                not leg["gated"]:
            findings.append(Finding(
                "MXL803",
                f"{owner}:{variant or 'step'}: an observability-only "
                f"{leg['primitive']} over axis {ax!r} "
                f"({leg['payload_bytes']} payload bytes) executes "
                "OUTSIDE the health plane's lax.cond(due) sampling "
                "gate — the variant is registered as sampled, so "
                "this row pays its wire cost every step; move it "
                "under the gate", loc))
    # MXL804 — static vs observatory bytes-on-wire (gated legs
    # included: collective_stats counts both cond branches in the
    # HLO text, so the static total must too).
    measured = measured_override if measured_override is not None \
        else _measured_wire(rec)
    if measured is not None and _reconcile_eligible(rec, legs,
                                                    derived):
        static = sum(leg["wire_bytes"] for leg in legs)
        if static or measured:
            ratio = abs(static - measured) / float(max(measured, 1))
            if ratio > drift:
                findings.append(Finding(
                    "MXL804",
                    f"{owner}:{variant or 'step'}: static "
                    f"bytes-on-wire {static} vs the observatory's "
                    f"runtime accounting {measured} for program "
                    f"{rec.get('program') or '(explicit)'} — "
                    f"{ratio:.0%} drift (> {drift:.0%}); either the "
                    "static wire model or the runtime counter is "
                    "lying, and both feed the compression-ratio "
                    "claims", loc))
    return findings


def analyze_wire(jaxpr=None, plan=None, *, drift: float = 0.10,
                 owner: str = "wire", kind: str = "train",
                 zero_stage: Optional[int] = None,
                 sampled: bool = False, obs_outputs=(),
                 mesh_axes=None,
                 measured_wire_bytes: Optional[int] = None
                 ) -> List[Finding]:
    """MXL801–804 — the wire audit (docs/static_analysis.md, "The
    wire auditor").

    Registry-driven by default: walks every variant the trainers and
    the serving plane registered via :func:`note_step` (free in a
    fresh process — the ``--self-check`` CI gate stays quiet).  The
    explicit ``(jaxpr, plan)`` entry point audits one closed jaxpr
    directly (the ``tools/mxwire.py lint`` / seeded-corpus path);
    ``measured_wire_bytes`` there supplies the observatory side of
    the MXL804 reconciliation, which otherwise reads the program
    record named at registration.
    """
    if jaxpr is not None:
        rec = {
            "owner": str(owner), "variant": "", "fn": None,
            "avals": (), "plan": plan,
            "mesh_axes": dict(mesh_axes) if mesh_axes else
            dict(getattr(plan, "axes", None) or {}),
            "dp_axis": str(getattr(plan, "dp_axis", "dp")),
            "zero_stage": int(
                zero_stage if zero_stage is not None
                else (getattr(plan, "zero_stage", None) or 0)),
            "compressed": False, "sampled": bool(sampled),
            "kind": str(kind), "program": None, "params_bytes": [],
            "obs_outputs": tuple(int(i)
                                 for i in (obs_outputs or ())),
            "jaxpr": jaxpr, "trace_error": None, "legs": None,
        }
        # an explicit caller handing us measured bytes opts into the
        # reconciliation even without a harvested program record
        if measured_wire_bytes is not None:
            rec["kind"] = rec["kind"] or "train"
        return _audit_one(rec, drift,
                          measured_override=measured_wire_bytes)
    findings: List[Finding] = []
    with _lock:
        recs = list(_variants.values())
    for rec in sorted(recs, key=lambda r: (r["owner"], r["variant"])):
        try:
            findings.extend(_audit_one(rec, drift))
        except Exception:
            # one untraceable/odd variant must not kill the gate
            continue
    return findings


# -- report (the CLI surface) -----------------------------------------------

def wire_report() -> Dict[str, dict]:
    """Per-variant leg inventory for ``tools/mxwire.py show``:
    ``{"owner:variant": {legs, static/measured wire bytes, drift,
    ...}}``."""
    out: Dict[str, dict] = {}
    with _lock:
        recs = list(_variants.values())
    for rec in sorted(recs, key=lambda r: (r["owner"], r["variant"])):
        key = f"{rec['owner']}:{rec['variant']}" if rec["variant"] \
            else rec["owner"]
        try:
            legs, derived = _legs_for(rec)
        except Exception:
            legs, derived = [], False
        static = sum(leg["wire_bytes"] for leg in legs)
        measured = _measured_wire(rec)
        row = {
            "kind": rec["kind"], "zero_stage": rec["zero_stage"],
            "compressed": rec["compressed"],
            "sampled": rec["sampled"], "derived": derived,
            "program": rec.get("program"),
            "trace_error": rec.get("trace_error"),
            "legs": [dict(leg) for leg in legs],
            "static_wire_bytes": int(static),
            "measured_wire_bytes": measured,
            "reconciled": _reconcile_eligible(rec, legs, derived)
            and measured is not None,
        }
        if row["reconciled"] and (static or measured):
            row["drift"] = abs(static - (measured or 0)) / float(
                max(measured or 0, 1))
        out[key] = row
    return out
