"""Silent-corruption sentry: in-graph integrity fingerprints,
cross-replica agreement audits, and quarantine-by-resize.

Every robustness plane so far reacts to LOUD failures — raises, hangs,
signals.  The scarier production failure is silent: a bit flips in one
dp replica's parameter buffer, a collective delivers a corrupt payload
on one link, a checkpoint shard rots on disk — and the job keeps
training wrong with no event.  This module makes corruption
*injectable* (the ``corrupt_param``/``corrupt_grad``/``corrupt_wire``
points of the ``MXTPU_FAULT_INJECT`` grammar), *detectable inside the
one-dispatch step*, and *healable* through the existing
checkpoint/resize machinery:

* **fingerprints** — a cheap per-replica bitwise fingerprint
  (:func:`fingerprint`: the uint32 wraparound sum of each tensor's bit
  pattern — a single bitflip changes it by ±2^b, which is never 0 mod
  2^32, so every single-bit flip is detected) of the step's input
  params and its post-collective gradients, computed INSIDE the same
  single donated dispatch under the health plane's existing
  ``lax.cond(due)`` sampling gate (``telemetry.health``), so the
  steady-state 1-dispatch/0-retrace contract holds and un-sampled
  steps pay nothing;
* **cross-replica agreement** — replicated values must agree across
  the dp axis: an ``all_gather`` of the per-replica fingerprints rides
  the health vector as ``(hi16, lo16)`` f32 slot pairs (exact — both
  halves are < 2^16), and the host sentinel flags any replica whose
  fingerprint differs from the MAJORITY value, *with device
  attribution*.  The corrupted replica is named, not hunted;
* **escalation** — an ``integrity_divergence`` anomaly joins the
  health sentinel's taxonomy with its own action ladder
  (``MXTPU_INTEGRITY_ACTION``): ``warn`` records the retained
  ``corruption_suspected`` event only; ``rollback`` restores the last
  committed checkpoint (the corrupt state is discarded — the PR 7
  protocol); ``quarantine`` additionally resizes the live trainer off
  the suspect device through :class:`~.resize.ResizeController` + the
  sharding planner (arXiv 2112.01075's portable redistribution used
  as an eviction move), emitting ``device_quarantined``;
* **checkpoint scrubbing** — ``CheckpointManager.scrub()``
  re-verifies committed shard sha256s in the background and
  quarantines rotten checkpoints so a restore can never serve them
  (:mod:`.manager`); the serving plane verifies KV-page checksums on
  migration and drain-manifest token hashes on restore
  (:func:`page_checksum`), so a corrupt resident replays loudly
  instead of decoding garbage.

The corruption points are deterministic under ``MXTPU_FAULT_SEED``:
``corrupt_param`` flips a bit in a chosen device's buffer of a live
replicated param (host-side — real physical state corruption);
``corrupt_grad``/``corrupt_wire`` bake a ctl-driven XOR into the
traced step (arming them retraces ONCE with attribution, exactly like
a health-config flip; production programs are byte-identical when no
drill is armed) so the detector is red→green testable on the tier-1
CPU mesh.  See docs/elasticity.md ("Integrity sentry").
"""
from __future__ import annotations

import hashlib
import math
import threading
from typing import List, Optional, Sequence, Tuple

from ..base import MXNetError
from ..profiler import device_scope as _device_scope

__all__ = ["IntegritySpec", "enabled", "action", "trace_signature",
           "build_spec", "fingerprint", "body_rows", "jit_block",
           "ctl_vector", "corrupt_param_host", "agreement",
           "respond", "quarantine", "quarantine_mesh",
           "page_checksum", "token_checksum"]

#: bits above this stay clear of the f32 exponent/sign, so a
#: seeded-random ``corrupt_param`` flip perturbs the value without
#: manufacturing NaN/Inf (which the health plane's nonfinite detector
#: would catch FIRST and steal the attribution from the drill)
MAX_SAFE_BIT = 22


# -- configuration -----------------------------------------------------

def enabled() -> bool:
    """Is the integrity plane armed?  Rides the health plane (the
    fingerprints are extra slots of ITS vector, under ITS sampling
    gate) plus ``MXTPU_INTEGRITY``."""
    from ..telemetry import health as _health
    if not _health.enabled():
        return False
    from .. import envs
    return bool(envs.get("MXTPU_INTEGRITY"))


def action() -> str:
    """``warn`` | ``rollback`` | ``quarantine``
    (``MXTPU_INTEGRITY_ACTION``; unknown values degrade to warn)."""
    from .. import envs
    act = str(envs.get("MXTPU_INTEGRITY_ACTION")).strip().lower()
    return act if act in ("warn", "rollback", "quarantine") else "warn"


def trace_signature(mesh=None, dp_axis: Optional[str] = None,
                    grad_rows: bool = True) -> Optional[tuple]:
    """What the TRACED program bakes from this module: ``None`` when
    the plane is off or the mesh has no >1 dp axis (cross-replica
    agreement is vacuous — the program is then byte-identical to a
    pre-integrity build, and every pre-integrity persist hash still
    serves).  The step stacks fold this into their signature/persist
    identity next to ``health.trace_signature()`` so arming a
    corruption drill — which adds the ctl input and the XOR block —
    retraces once with attribution instead of mis-reading outputs."""
    if not enabled() or mesh is None or dp_axis is None:
        return None
    n_dp = int(dict(zip(mesh.axis_names,
                        mesh.devices.shape)).get(dp_axis, 1))
    if n_dp <= 1:
        return None
    from . import faults
    return ("integrity", 1, n_dp, bool(grad_rows),
            bool(faults.corrupt_armed()))


def struct_signature(grad_rows: bool = True) -> Optional[tuple]:
    """The MESH-INDEPENDENT integrity identity (``None`` when the
    plane is off): armed + grad-rows + inject, WITHOUT the dp size —
    the reshard warm-start path compares struct hashes across mesh
    sizes (a dp=1 save restoring onto dp=2 re-AOTs anyway; whether
    the fingerprint rows exist on the target is the target's own
    business, decided by its mesh)."""
    if not enabled():
        return None
    from . import faults
    return ("integrity", bool(grad_rows),
            bool(faults.corrupt_armed()))


class IntegritySpec:
    """Layout of the integrity slots appended to one owner's health
    vector: per-dp-replica uint32 fingerprints packed as ``(hi16,
    lo16)`` f32 pairs — params always, post-collective grads when
    ``grad_rows`` (ZeRO stage-2 never materializes a replicated
    gradient, so its spec drops the grad rows).  ``inject`` bakes the
    ctl-driven corruption block (drills only)."""

    __slots__ = ("n_dp", "grad_rows", "inject")

    def __init__(self, n_dp: int, grad_rows: bool = True,
                 inject: bool = False):
        if n_dp < 2:
            raise MXNetError(
                f"IntegritySpec needs a >1 dp axis, got {n_dp}")
        self.n_dp = int(n_dp)
        self.grad_rows = bool(grad_rows)
        self.inject = bool(inject)

    @property
    def kinds(self) -> Tuple[str, ...]:
        return ("param", "grad") if self.grad_rows else ("param",)

    @property
    def slots(self) -> int:
        return 2 * self.n_dp * len(self.kinds)

    def fields(self) -> List[str]:
        out = []
        for kind in self.kinds:
            out.extend(f"integrity.{kind}_fp_hi{i}"
                       for i in range(self.n_dp))
            out.extend(f"integrity.{kind}_fp_lo{i}"
                       for i in range(self.n_dp))
        return out

    def signature(self) -> tuple:
        return ("integrity", 1, self.n_dp, self.grad_rows, self.inject)

    def parse(self, tail) -> dict:
        """Recombine the f32 slot tail into per-replica uint32
        fingerprints: ``{"param_fp": [...], "grad_fp": [...]|None}``."""
        out = {"param_fp": None, "grad_fp": None}
        off = 0
        for kind in self.kinds:
            hi = tail[off:off + self.n_dp]
            lo = tail[off + self.n_dp:off + 2 * self.n_dp]
            out[f"{kind}_fp"] = [int(h) * 65536 + int(l)
                                 for h, l in zip(hi, lo)]
            off += 2 * self.n_dp
        return out


def build_spec(mesh, dp_axis: str,
               grad_rows: bool = True) -> Optional[IntegritySpec]:
    """The spec for one SPMD step owner, or ``None`` when the plane is
    off / the dp axis is not >1 (matches :func:`trace_signature`)."""
    sig = trace_signature(mesh, dp_axis, grad_rows)
    if sig is None:
        return None
    return IntegritySpec(sig[2], grad_rows=sig[3], inject=sig[4])


# -- traced computation ------------------------------------------------

def fingerprint(leaves):
    """uint32 wraparound sum of every leaf's bit pattern (one pass,
    no extra tensor materialized).  A single bitflip changes the sum
    by ±2^b (b < 32), never 0 mod 2^32 — every single-bit corruption
    is detected.  Leaves are viewed at f32 (a flip in a low-precision
    leaf changes its f32 image too)."""
    import jax.numpy as jnp
    from jax import lax
    total = jnp.uint32(0)
    for x in leaves:
        bits = lax.bitcast_convert_type(x.astype(jnp.float32),
                                        jnp.uint32)
        total = total + jnp.sum(bits.reshape(-1), dtype=jnp.uint32)
    return total


def _pack_rows(vecs):
    """``(n_dp,) uint32`` per kind -> one f32 vector of exact
    ``(hi16, lo16)`` halves (both < 2^16, exactly representable)."""
    import jax.numpy as jnp
    rows = []
    for vec in vecs:
        rows.append((vec >> 16).astype(jnp.float32))
        rows.append((vec & jnp.uint32(0xFFFF)).astype(jnp.float32))
    return jnp.concatenate(rows)


def _gather_rows(spec, dp_axis, other_axes, fams):
    """Per-device fingerprint scalars -> the packed slot rows with ONE
    all_gather: the kind fingerprints stack into a tiny ``(kinds,)``
    vector first (one psum lane, one gather lane — on a CPU mesh the
    collective COUNT, not the payload, is the cost)."""
    import jax.numpy as jnp
    from jax import lax
    fp = jnp.stack([fingerprint(f) for f in fams])     # (kinds,)
    for ax in (other_axes or ()):
        fp = lax.psum(fp, ax)
    mat = lax.all_gather(fp, dp_axis)                  # (n_dp, kinds)
    return _pack_rows([mat[:, k] for k in range(len(fams))])


def maybe_corrupt(spec: IntegritySpec, ictl, leaves, axis):
    """The in-graph corruption block (PER-DEVICE context — a shard_map
    body): XOR one bit into element 0 of leaf ``ictl[2]`` on the
    device whose dp index equals ``ictl[1]``.  ``ictl[0] <= 0`` is the
    exact identity (the XOR mask is 0), so an armed-but-idle drill
    step is bit-identical to an unarmed one."""
    import jax.numpy as jnp
    from jax import lax
    if spec is None or not spec.inject or ictl is None:
        return leaves
    dev = lax.axis_index(axis)
    armed = (ictl[0] > 0) & (dev == ictl[1].astype(jnp.int32))
    out = []
    for j, g in enumerate(leaves):
        bits = lax.bitcast_convert_type(g.astype(jnp.float32),
                                        jnp.uint32)
        flat = bits.reshape(-1)
        mask = jnp.where(
            armed & (ictl[2].astype(jnp.int32) == j),
            jnp.left_shift(jnp.uint32(1), ictl[3].astype(jnp.uint32)),
            jnp.uint32(0))
        flat = flat.at[0].set(flat[0] ^ mask)
        out.append(lax.bitcast_convert_type(
            flat.reshape(g.shape), jnp.float32).astype(g.dtype))
    return tuple(out)


def body_rows(spec: IntegritySpec, dp_axis: str, other_axes,
              param_leaves, grad_leaves, due=None):
    """The integrity slot rows, computed in a PER-DEVICE context (a
    shard_map body): local fingerprints, psum'd over any non-dp mesh
    axes (a tp-sharded layout contributes one fingerprint per dp
    REPLICA), all-gathered over dp, packed as f32 halves.  Gated on
    ``due`` exactly like the health reductions — un-sampled steps pay
    nothing and emit zero rows (all-zero rows parse as agreement)."""
    import jax.numpy as jnp
    from jax import lax
    if spec is None:
        return None

    def _rows():
        fams = [param_leaves] + \
            ([grad_leaves] if spec.grad_rows else [])
        return _gather_rows(spec, dp_axis, other_axes, fams)

    if due is None:
        return _rows()
    return lax.cond(due > 0, _rows,
                    lambda: jnp.zeros((spec.slots,), jnp.float32))


def jit_block(spec: IntegritySpec, mesh, dp_axis: str, param_leaves,
              grad_leaves, due=None, ictl=None):
    """The integrity block for a GLOBALLY-traced step body (the plain
    fused step, where no shard_map surrounds the caller): one inner
    shard_map computes the per-device rows — and, when a drill is
    armed, corrupts the gradients of the targeted device BEFORE they
    reach the optimizer update (the corruption enters the real
    dataflow; the same block's grad fingerprints detect it).

    Returns ``(grads, rows)`` — ``grads`` unchanged (and NOT routed
    through the block) when no drill is armed, so the production
    program carries only the sampled fingerprint reductions."""
    if spec is None:
        return grad_leaves, None
    with _device_scope("mxtpu.step.integrity"):
        return _jit_block(spec, mesh, dp_axis, param_leaves, grad_leaves,
                          due, ictl)


def _jit_block(spec, mesh, dp_axis, param_leaves, grad_leaves, due, ictl):
    from jax.sharding import PartitionSpec as P
    from jax import shard_map
    other = tuple(a for a in mesh.axis_names if a != dp_axis)
    n_p, n_g = len(param_leaves), len(grad_leaves)

    if spec.inject and ictl is not None:
        def body(ctl, *leaves):
            params = leaves[:n_p]
            grads = maybe_corrupt(spec, ctl, leaves[n_p:], dp_axis)
            return grads + (body_rows(spec, dp_axis, other, params,
                                      grads, due=None),)

        out = shard_map(
            body, mesh=mesh,
            in_specs=(P(),) * (1 + n_p + n_g),
            out_specs=(P(),) * (n_g + 1),
            check_vma=False)(ictl, *(tuple(param_leaves) +
                                     tuple(grad_leaves)))
        new_grads, rows = tuple(out[:n_g]), out[n_g]
        if due is not None:
            import jax.numpy as jnp
            from jax import lax
            rows = lax.cond(
                due > 0, lambda: rows,
                lambda: jnp.zeros((spec.slots,), jnp.float32))
        return new_grads, rows

    def body(*leaves):
        return body_rows(spec, dp_axis, other, leaves[:n_p],
                         leaves[n_p:], due=None)

    import jax.numpy as jnp
    from jax import lax

    def _rows():
        return shard_map(
            body, mesh=mesh, in_specs=(P(),) * (n_p + n_g),
            out_specs=P(), check_vma=False)(
                *(tuple(param_leaves) + tuple(grad_leaves)))

    rows = _rows() if due is None else lax.cond(
        due > 0, _rows,
        lambda: jnp.zeros((spec.slots,), jnp.float32))
    return grad_leaves, rows


# -- host side: drill plumbing ----------------------------------------

def ctl_vector(spec: Optional[IntegritySpec], n_leaves: int):
    """One step's corruption-ctl row ``[armed, device, leaf, bit]``
    (f32 (4,)): consults the ``corrupt_grad``/``corrupt_wire`` fault
    points and clamps the seeded payload to this owner's shape.  All
    zeros when nothing fires — the XOR block is then the identity."""
    import numpy as np
    out = np.zeros((4,), np.float32)
    if spec is None or not spec.inject:
        return out
    from . import faults
    point = "corrupt_grad"
    payload = faults.corrupt_due(point)
    if payload is None:
        point = "corrupt_wire"
        payload = faults.corrupt_due(point)
    if payload is None:
        return out
    out[0] = 1.0
    out[1] = float(int(payload["device"]) % spec.n_dp)
    out[2] = float(int(payload["leaf"]) % max(1, n_leaves))
    out[3] = float(int(payload["bit"]) % 32)
    faults.note_corruption_applied(
        point, device=int(out[1]), leaf=int(out[2]), bit=int(out[3]))
    return out


def corrupt_param_host(trainer, payload: dict) -> dict:
    """The ``corrupt_param`` drill: flip one bit in ONE device's local
    shard of a live replicated param buffer — real physical state
    corruption, exactly what a DRAM/HBM upset leaves behind.  The
    in-graph fingerprints see the divergent replica on the next
    sampled step, with the device attributed.  Deterministic under
    ``MXTPU_FAULT_SEED`` (the payload is drawn from the faults RNG).
    Returns the applied ``{device, leaf, bit, param}``."""
    import numpy as np
    import jax
    tr_idx = trainer._tr_idx
    j = int(payload["leaf"]) % len(tr_idx)
    p = trainer._params[tr_idx[j]]
    d = p.data()._data
    shards = list(d.addressable_shards)
    dev = int(payload["device"]) % len(shards)
    bit = int(payload["bit"]) % (MAX_SAFE_BIT + 1)
    hosts = [np.asarray(s.data).copy() for s in shards]
    flat = hosts[dev].reshape(-1)
    if flat.dtype != np.float32:
        raise MXNetError(
            f"corrupt_param targets f32 params; {p.name} is "
            f"{flat.dtype}")
    flat.view(np.uint32)[0] ^= np.uint32(1 << bit)
    arrs = [jax.device_put(h, s.device)
            for h, s in zip(hosts, shards)]
    p.data()._set_data(jax.make_array_from_single_device_arrays(
        d.shape, d.sharding, arrs))
    applied = {"device": dev, "leaf": j, "bit": bit, "param": p.name}
    from . import faults
    faults.note_corruption_applied("corrupt_param", **applied)
    return applied


# -- host side: agreement + escalation ---------------------------------

def agreement(fps: Sequence[int]) -> Optional[List[int]]:
    """Majority vote over per-replica fingerprints: ``None`` when all
    agree, else the MINORITY replica indices (the suspects).  An exact
    50/50 split names the higher-indexed half (arbitrary but
    deterministic — with 2 replicas there is no majority to trust)."""
    vals = list(fps)
    if len(set(vals)) <= 1:
        return None
    counts = {}
    for v in vals:
        counts[v] = counts.get(v, 0) + 1
    modal = sorted(counts.items(),
                   key=lambda kv: (-kv[1], vals.index(kv[0])))[0][0]
    return [i for i, v in enumerate(vals) if v != modal]


def note_suspected(where: str, row: str, suspects: List[int],
                   fps: Sequence[int], step: int) -> None:
    """The retained ``corruption_suspected`` event + counter — the
    flight-recorder row every escalation (and the MXL505 audit) hangs
    off."""
    from .. import telemetry
    telemetry.counter(
        "mxtpu_corruption_suspected_total",
        "cross-replica integrity divergences the sentry flagged").inc()
    telemetry.record_event(
        "corruption_suspected", where=where, row=row,
        suspects=[int(s) for s in suspects],
        fingerprints=[f"{int(v):08x}" for v in fps],
        step=int(step))


def respond(owner, verdict: dict) -> bool:
    """The action half of an ``integrity_divergence`` verdict
    (``MXTPU_INTEGRITY_ACTION``): ``warn`` records only (the
    ``corruption_suspected`` event already landed); ``rollback``
    restores the last committed checkpoint through the owner's
    ``recover(manager)``; ``quarantine`` additionally resizes the
    owner off the suspect device.  Returns True when a recovery
    action ran.  Missing manager degrades LOUDLY (a retained event),
    never crashes the training loop."""
    from .. import telemetry
    act = action()
    if act == "warn":
        return False
    manager = getattr(owner, "health_manager", None)
    if manager is None:
        telemetry.record_event(
            "health_anomaly", where="integrity",
            anomaly=f"{act}_unarmed",
            detail=f"MXTPU_INTEGRITY_ACTION={act} but no "
                   "health_manager is attached; set "
                   "owner.health_manager to a CheckpointManager")
        return False
    suspects = verdict.get("suspects") or []
    try:
        if act == "quarantine" and suspects:
            quarantine(owner, manager, int(suspects[0]))
        else:
            owner.recover(manager)
    except Exception as e:
        telemetry.record_event(
            "health_anomaly", where="integrity",
            anomaly=f"{act}_failed",
            detail=f"{act} on suspects {suspects} failed: "
                   f"{e!r}"[:300])
        return False
    telemetry.record_event("corruption_resolved", where="integrity",
                           action=act,
                           suspects=[int(s) for s in suspects],
                           step=int(verdict.get("step", 0)))
    return True


def quarantine_mesh(mesh, dp_axis: str, suspect: int,
                    new_dp: Optional[int] = None):
    """The resize target that EXCLUDES the suspect device: the
    remaining dp members, shrunk to ``new_dp`` (default: the largest
    power of two below the old size — power-of-two sizes keep the
    usual batch divisibility).  Only a pure-dp mesh can quarantine
    one device (a dp x tp mesh would have to drop a whole dp column —
    raise so the caller degrades to rollback)."""
    import numpy as np
    from ..parallel.mesh import make_mesh
    shape = dict(zip(mesh.axis_names, mesh.devices.shape))
    n_dp = int(shape.get(dp_axis, 1))
    if len([a for a, s in shape.items() if s > 1]) > 1 or n_dp < 2:
        raise MXNetError(
            f"quarantine needs a pure-dp mesh with dp >= 2, got "
            f"{shape} (set MXTPU_INTEGRITY_ACTION=rollback for "
            "multi-axis meshes)")
    devs = [d for i, d in enumerate(np.asarray(
        mesh.devices).reshape(-1)) if i != (suspect % n_dp)]
    if new_dp is None:
        new_dp = 2 ** int(math.floor(math.log2(n_dp - 1)))
    new_dp = int(new_dp)
    if not 1 <= new_dp <= len(devs):
        raise MXNetError(
            f"quarantine target dp={new_dp} does not fit the "
            f"{len(devs)} remaining devices")
    return make_mesh({dp_axis: new_dp}, devices=devs)


def quarantine(owner, manager, suspect: int,
               new_dp: Optional[int] = None) -> dict:
    """Evict the suspect device from a live trainer: (1) roll back to
    the last committed checkpoint (the corrupt state is discarded —
    fp32-exact restore, PR 7), then (2) resize onto a mesh excluding
    the suspect through :class:`~.resize.ResizeController` (drain →
    reshard → pre-warmed swap, PR 11) — the arXiv 2112.01075
    redistribution used as an eviction move.  Emits the retained
    ``device_quarantined`` event + counter; returns the resize
    record."""
    import time
    from .. import telemetry
    from .resize import ResizeController
    t0 = time.perf_counter()
    qmesh = quarantine_mesh(owner.mesh, owner.dp_axis, suspect,
                            new_dp=new_dp)
    restored = owner.recover(manager)
    rec = ResizeController(owner, manager).resize(qmesh)
    telemetry.counter(
        "mxtpu_corruption_quarantines_total",
        "devices quarantined off a live mesh on an integrity "
        "verdict").inc()
    telemetry.record_event(
        "device_quarantined", where="integrity",
        suspect=int(suspect),
        restored_step=int(restored),
        mesh_to=rec.get("mesh_to"),
        seconds=round(time.perf_counter() - t0, 4))
    return rec


# -- checksums (checkpoint scrub + serving legs) -----------------------

def page_checksum(host) -> str:
    """sha256 (16 hex chars) of a host array's bytes — the KV-page /
    shard checksum shared by the serving migration verify and the
    drain-manifest rows."""
    import numpy as np
    a = np.ascontiguousarray(np.asarray(host))
    return hashlib.sha256(a.tobytes()).hexdigest()[:16]


def token_checksum(prompt, generated) -> str:
    """Checksum of one serving request's host-owned token state (the
    drain-manifest integrity row: a corrupt manifest replays loudly
    instead of decoding garbage)."""
    blob = ",".join(str(int(t)) for t in prompt) + "|" + \
        ",".join(str(int(t)) for t in generated)
    return hashlib.sha256(blob.encode()).hexdigest()[:16]


# -- scrub bookkeeping (the MXL505 input) ------------------------------

import collections as _collections

_scrub_lock = threading.Lock()
#: bounded like the retained event ring — a background scrubber on a
#: long-lived job appends one verdict per committed checkpoint per
#: pass, and the MXL505 audit only needs the recent window
_scrub_log = _collections.deque(maxlen=512)


def note_scrub(row: dict) -> None:
    with _scrub_lock:
        _scrub_log.append(dict(row))


def scrub_log() -> List[dict]:
    """Per-checkpoint scrub verdicts of THIS process (oldest first;
    copies) — ``analyze_elasticity``'s MXL505 input."""
    with _scrub_lock:
        return [dict(r) for r in _scrub_log]


def _reset():
    """Test hook."""
    with _scrub_lock:
        _scrub_log.clear()
