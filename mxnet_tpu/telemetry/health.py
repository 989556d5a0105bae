"""Training-health plane: in-graph numerics monitoring + host sentinel.

The telemetry plane (PR 4) proves the PERFORMANCE contract — one
dispatch per step, zero steady-state retraces — but says nothing about
whether the numbers coming out of that one dispatch are any good: a
diverging run (loss spike, gradient explosion, a NaN from a bad batch)
burns a full chip window before a human reads a loss curve.  This
module watches the numerics continuously, without breaking the
contracts the rest of the stack fought for:

* **in-graph stats** — :func:`compute` runs INSIDE the compiled step
  trace (``gluon.CompiledStep`` and the SPMD
  ``DataParallelTrainer``'s fused step splice it in) and returns one
  flat f32 vector as an extra program output: loss, global grad norm,
  global nonfinite count, and per-top-level-subtree param/grad/update
  norms + nonfinite counts.  Monitoring therefore costs ZERO extra
  dispatches — the one-dispatch contract holds with health on;
* **sampled host transfer** — the device vector is read back only
  every ``MXTPU_HEALTH_EVERY`` steps (the read is the only host sync
  the plane adds; its cost on the chip is not measured alone —
  PERF.md section 5 finds it as one of the train cell's two drains);
* **host sentinel** — :class:`Sentinel` keeps rolling loss/grad-norm
  statistics per step owner and emits retained ``health_anomaly``
  flight-recorder events (loss spike, grad-norm explosion,
  update-ratio collapse, any nonfinite) with SUBTREE attribution, in
  the style of PR 4's retrace-cause attribution;
* **actions** (``MXTPU_HEALTH_ACTION``) — ``warn`` records only;
  ``skip`` bakes a nonfinite gate into the traced step
  (:func:`gate`): a step whose gradients carry any nonfinite value
  writes the OLD params/optimizer state back out, so one poisoned
  batch cannot corrupt the donated training state; ``rollback``
  drives the elastic plane's ``recover(manager)`` protocol on a
  nonfinite or sustained-divergence verdict, restoring the last
  committed checkpoint (docs/elasticity.md) — the loop PR 7 left
  open.

Everything is inert under ``MXTPU_TELEMETRY=0`` or ``MXTPU_HEALTH=0``:
the traced program is then byte-identical to a health-less build (no
extra outputs), and the host pays one attribute check per step.  The
action and subtree layout are part of the traced program, so they ride
the persist identity / ``_check_sig`` eviction seams — flipping
``MXTPU_HEALTH*`` mid-process retraces ONCE with an attributed cause
instead of silently serving a stale program.  See
docs/observability.md ("Training health").
"""
from __future__ import annotations

import collections
import json
import math
import threading
from typing import Dict, List, Optional, Sequence, Tuple

from ..profiler import device_scope as _device_scope

__all__ = ["enabled", "every", "action", "trace_signature", "build_spec",
           "HealthSpec", "compute", "gate", "due_flags", "Sentinel",
           "get_sentinel",
           "sample_owner", "handle_verdict", "sentinels", "report",
           "dump_report", "render_table", "reset", "poison_inputs",
           "UPDATE_RATIO_BUCKETS"]

#: update-ratio (||delta w|| / ||w||) distribution boundaries: healthy
#: SGD sits around 1e-3; the decades below catch collapse, above catch
#: blow-up.
UPDATE_RATIO_BUCKETS = (1e-7, 1e-6, 1e-5, 1e-4, 1e-3, 1e-2, 1e-1, 1.0)

_GLOBAL_FIELDS = ("loss", "grad_norm", "nonfinite")
_SUBTREE_FIELDS = ("param_norm", "grad_norm", "update_norm", "nonfinite")


# -- configuration (env-driven; re-read per call so tests/operators can
# flip knobs at runtime — the step stacks detect the flip through
# trace_signature() and retrace once, with attribution) ----------------

def enabled() -> bool:
    """Is the health plane recording?  Requires BOTH the telemetry
    master switch and ``MXTPU_HEALTH``."""
    from . import _switch
    if not _switch.enabled:
        return False
    from .. import envs
    return bool(envs.get("MXTPU_HEALTH"))


def every() -> int:
    """Host sampling period K (``MXTPU_HEALTH_EVERY``): the device
    health vector is read back on every K-th train step."""
    from .. import envs
    return max(1, int(envs.get("MXTPU_HEALTH_EVERY")))


def action() -> str:
    """``warn`` | ``skip`` | ``rollback`` (``MXTPU_HEALTH_ACTION``;
    unknown values degrade to ``warn`` — a typo'd knob must not change
    the traced program silently)."""
    from .. import envs
    act = str(envs.get("MXTPU_HEALTH_ACTION")).strip().lower()
    return act if act in ("warn", "skip", "rollback") else "warn"


def _window() -> int:
    from .. import envs
    return max(4, int(envs.get("MXTPU_HEALTH_WINDOW")))


def _patience() -> int:
    from .. import envs
    return max(1, int(envs.get("MXTPU_HEALTH_PATIENCE")))


def trace_signature() -> Optional[tuple]:
    """What the TRACED program bakes from this module: None when the
    plane is off (no extra outputs), else ``("health", version,
    skip_gate_active)``.  The step stacks fold this into their
    signature/persist identity so a config flip evicts the stale
    executable instead of mis-unpacking its outputs."""
    if not enabled():
        return None
    return ("health", 1, action() == "skip")


# -- spec: the health vector's layout ---------------------------------

class HealthSpec:
    """Layout of one step's health vector.

    ``fields()`` names every slot: 3 globals (``loss``, ``grad_norm``,
    ``nonfinite``) then 4 per top-level subtree
    (``<subtree>.param_norm/grad_norm/update_norm/nonfinite``) — plus,
    when the integrity sentry is armed (``elastic.integrity``), the
    per-dp-replica fingerprint pairs its cross-replica agreement
    audit reads.  ``groups`` maps each subtree to positions in the
    TRAINABLE param list (the j-indices the step stacks use for
    tvals/grads/new values), so attribution points at the exact child
    block.
    """

    __slots__ = ("subtrees", "groups", "skip", "integrity")

    def __init__(self, subtrees: List[str], groups: List[List[int]],
                 skip: bool, integrity=None):
        self.subtrees = list(subtrees)
        self.groups = [list(g) for g in groups]
        self.skip = bool(skip)
        #: optional ``elastic.integrity.IntegritySpec`` — its slot
        #: rows ride the TAIL of this vector (the step builders append
        #: them after :func:`compute`'s numerics section)
        self.integrity = integrity

    @property
    def base_n(self) -> int:
        """Slot count of the numerics section — what :func:`compute`
        builds (the integrity rows are appended by the step builder)."""
        return len(_GLOBAL_FIELDS) + \
            len(_SUBTREE_FIELDS) * len(self.subtrees)

    @property
    def n(self) -> int:
        return self.base_n + (self.integrity.slots
                              if self.integrity is not None else 0)

    def fields(self) -> List[str]:
        out = list(_GLOBAL_FIELDS)
        for s in self.subtrees:
            out.extend(f"{s}.{f}" for f in _SUBTREE_FIELDS)
        if self.integrity is not None:
            out.extend(self.integrity.fields())
        return out

    def signature(self) -> tuple:
        """Structural identity (part of the step's persist/sig hash):
        the subtree layout, the skip gate, and the integrity layout
        are all baked into the traced program."""
        return ("health", 1, self.skip, tuple(self.subtrees),
                tuple(tuple(g) for g in self.groups)) + (
                    (self.integrity.signature(),)
                    if self.integrity is not None else ())

    def parse(self, vec) -> dict:
        """Host-side view of one sampled vector: globals + a per-
        subtree dict (+ the per-replica fingerprints when armed)."""
        import numpy as np
        v = np.asarray(vec, dtype=np.float64).reshape(-1)
        if v.shape[0] != self.n:
            raise ValueError(
                f"health vector has {v.shape[0]} slots, spec expects "
                f"{self.n}")
        out = {k: float(v[i]) for i, k in enumerate(_GLOBAL_FIELDS)}
        subs = {}
        off = len(_GLOBAL_FIELDS)
        for s in self.subtrees:
            subs[s] = {f: float(v[off + i])
                       for i, f in enumerate(_SUBTREE_FIELDS)}
            off += len(_SUBTREE_FIELDS)
        out["subtrees"] = subs
        if self.integrity is not None:
            out["integrity"] = self.integrity.parse(v[off:])
        return out


def _subtree_of(name: str, prefix: str) -> str:
    """Top-level subtree of a param name: the first path component
    after the net's own prefix (gluon names are flat,
    ``netX_childY_weight``)."""
    if prefix and name.startswith(prefix):
        name = name[len(prefix):]
    name = name.lstrip("_")
    head, _, rest = name.partition("_")
    # "dense0_weight" -> "dense0"; a bare "weight" (param directly on
    # the net) groups under its own name
    return head if rest else name


def build_spec(prefix: str, param_names: Sequence[str],
               integrity=None) -> Optional[HealthSpec]:
    """Build the health layout for one step owner, or None when the
    plane is off.  ``param_names`` are the TRAINABLE params in the
    order the step passes tvals/grads (position j in that list is the
    group index).  ``integrity``: an
    ``elastic.integrity.IntegritySpec`` for owners with a >1 dp axis
    (the SPMD trainer) — its fingerprint rows ride this vector's
    tail."""
    if not enabled():
        return None
    order: List[str] = []
    groups: Dict[str, List[int]] = {}
    for j, name in enumerate(param_names):
        s = _subtree_of(str(name), prefix or "")
        if s not in groups:
            groups[s] = []
            order.append(s)
        groups[s].append(j)
    return HealthSpec(order, [groups[s] for s in order],
                      skip=action() == "skip", integrity=integrity)


# -- traced computation ------------------------------------------------

def _compute_full(spec: HealthSpec, loss_val, old_tvals, grads,
                  new_tvals):
    import jax.numpy as jnp

    def _sq(x):
        return jnp.sum(jnp.square(x.astype(jnp.float32)))

    return _compute_from_sq(spec, loss_val, old_tvals,
                            [_sq(g) for g in grads], new_tvals)


def _compute_from_sq(spec: HealthSpec, loss_val, old_tvals, g_sq,
                     new_tvals):
    import jax.numpy as jnp

    def _sq(x):
        return jnp.sum(jnp.square(x.astype(jnp.float32)))

    # nonfinite DETECTION rides the squared sums the norms need
    # anyway: any NaN/Inf in a gradient poisons its sum, so
    # ~isfinite(sum) flags the subtree with ZERO extra passes over the
    # tensors (an explicit isfinite scan measured ~40% of the whole
    # health cost).  A finite-but-enormous gradient whose square
    # overflows f32 also flags — a grad norm past 1.8e19 is divergence
    # by any name.  Slots are therefore 0/1 indicators per subtree;
    # the global slot counts flagged subtrees (+1 for a nonfinite
    # loss), keeping the "> 0 means poisoned" contract.
    def _bad(s):
        return (~jnp.isfinite(s)).astype(jnp.float32)

    loss_mean = jnp.mean(loss_val.astype(jnp.float32))
    sub_slots = []
    bad_total = _bad(loss_mean)
    for g in spec.groups:
        g2 = sum(g_sq[j] for j in g)
        bad_s = _bad(g2)
        bad_total = bad_total + bad_s
        sub_slots.append([
            jnp.sqrt(sum(_sq(old_tvals[j]) for j in g)),
            jnp.sqrt(g2),
            jnp.sqrt(sum(_sq(new_tvals[j] - old_tvals[j])
                         for j in g)),
            bad_s])
    slots = [loss_mean, jnp.sqrt(sum(g_sq)), bad_total]
    for row in sub_slots:
        slots.extend(row)
    return jnp.stack(slots)


def compute(spec: HealthSpec, loss_val, old_tvals, grads, new_tvals,
            due=None):
    """Build the health vector INSIDE a step trace.

    ``loss_val``: the (possibly unreduced) loss value; ``old_tvals`` /
    ``new_tvals``: trainable param values before/after the optimizer
    update; ``grads``: their gradients — all aligned with the spec's
    group indices.  Returns a 1-D f32 array of ``spec.n`` slots.

    ``due`` is the DYNAMIC sampling flag (a 0-d f32 program input, 1.0
    on sampled steps): the reductions run under ``lax.cond``, so the
    ~P element passes they cost are paid only every
    ``MXTPU_HEALTH_EVERY`` steps — on a CPU/memory-bound step the
    always-on cost would dwarf the update itself.  With the skip gate
    armed the stats are needed EVERY step (the gate reads the
    nonfinite count), so ``spec.skip`` computes unconditionally; a
    ``None`` due does too (callers without a sampling schedule).
    """
    with _device_scope("mxtpu.step.health"):
        if due is None or spec.skip:
            return _compute_full(spec, loss_val, old_tvals, grads,
                                 new_tvals)
        import jax.numpy as jnp
        from jax import lax
        return lax.cond(
            due > 0,
            lambda: _compute_full(spec, loss_val, old_tvals, grads,
                                  new_tvals),
            lambda: jnp.zeros((spec.base_n,), jnp.float32))


def compute_sharded(spec: HealthSpec, loss_val, old_tvals, g_sq,
                    new_tvals, due=None):
    """:func:`compute` for a step whose full gradients NEVER
    materialize (the ZeRO-2 reduce-scatter path, docs/zero.md):
    ``g_sq`` holds the per-trainable-param GLOBAL squared gradient
    sums, which the step derives from its scattered slices plus ONE
    (T,)-vector psum — ``sum over members of sum(slice**2)`` equals
    the full gradient's squared sum exactly, so every slot (norms,
    nonfinite flags, attribution) matches the replicated computation
    while the gradient wire stays reduce-scatter.  Same ``due``/skip
    semantics as :func:`compute`."""
    with _device_scope("mxtpu.step.health"):
        if due is None or spec.skip:
            return _compute_from_sq(spec, loss_val, old_tvals, g_sq,
                                    new_tvals)
        import jax.numpy as jnp
        from jax import lax
        return lax.cond(
            due > 0,
            lambda: _compute_from_sq(spec, loss_val, old_tvals, g_sq,
                                     new_tvals),
            lambda: jnp.zeros((spec.base_n,), jnp.float32))


def due_flags(base: int, k: int):
    """Host-side sampling schedule for the next ``k`` steps after
    ``base`` completed ones: a (k,) f32 of 0/1 flags matching
    :func:`sample_owner`'s read-back decision (step ``base + i + 1``
    is sampled when it hits the ``MXTPU_HEALTH_EVERY`` boundary)."""
    import numpy as np
    ev = every()
    return np.asarray([1.0 if (base + i + 1) % ev == 0 else 0.0
                       for i in range(k)], np.float32)


def gate(health_vec, new_vals, old_vals):
    """The in-graph ``skip`` action: when the health vector saw any
    nonfinite (slot 2 > 0), every updated value is replaced by its
    pre-step original — the poisoned update becomes a no-op on the
    donated training state (loss output still reports the bad step).
    Identity when the step is healthy, so warn-mode parity is exact.
    """
    import jax.numpy as jnp
    bad = health_vec[2] > 0
    return tuple(jnp.where(bad, o, n) for n, o in
                 zip(new_vals, old_vals))


def gate_update(health_vec, new_params, old_params, new_states,
                old_states, aux, old_aux):
    """The skip gate over a fused step's whole update — params,
    per-param optimizer-state tuples, and forward-mutated aux — so
    both SPMD step bodies carry the invariant from ONE place (the
    compressed variant adds residual gating on top)."""
    with _device_scope("mxtpu.step.health"):
        new_params = gate(health_vec, new_params, old_params)
        new_states = tuple(
            tuple(gate(health_vec, sn, so))
            for sn, so in zip(new_states, old_states))
        aux = gate(health_vec, aux, old_aux)
    return new_params, new_states, aux


# -- deterministic nonfinite injection (docs/elasticity.md grammar) ----

def poison_inputs(args, ctx=None):
    """Plant a NaN in the leading element of each input batch — the
    ``nonfinite_grad`` fault point's payload (``MXTPU_FAULT_INJECT=
    nonfinite_grad:step=N``).  A NaN input propagates through forward/
    backward to a nonfinite loss and gradients, which is exactly the
    numerics failure the sentinel, the skip gate, and the rollback
    protocol must catch; shapes/dtypes are unchanged so nothing
    retraces."""
    import numpy as np
    from .. import ndarray as nd
    out = []
    poisoned = False
    for a in args:
        host = a.asnumpy().copy()
        if host.size and np.issubdtype(host.dtype, np.floating):
            host.reshape(-1)[0] = np.nan
            poisoned = True
        out.append(nd.array(host, dtype=host.dtype,
                            ctx=ctx or getattr(a, "context", None)))
    if not poisoned:
        # integer-only inputs (embedding-first nets): NaN cannot ride
        # them, and the one-shot spec is already consumed — say so
        # loudly instead of letting a drill "fire" while doing nothing
        from .recorder import record_event
        record_event("fault_injected", point="nonfinite_grad",
                     noop=True,
                     reason="no floating-point input to poison")
    return out


# -- host sentinel ------------------------------------------------------

class Sentinel:
    """Rolling-statistics watchdog over one step owner's samples.

    ``observe(vec, step)`` parses a sampled health vector, updates the
    gauges/counters, appends to the bounded history, and returns a
    VERDICT dict when action is warranted — ``kind`` is ``nonfinite``
    (immediate) or ``divergence`` (``patience`` consecutive anomalous
    samples).  Each individual anomaly (loss spike, grad explosion,
    update-ratio collapse, nonfinite) emits one retained
    ``health_anomaly`` flight-recorder event with subtree attribution.

    Baselines are ROBUST: anomalous samples never enter the rolling
    windows, so one spike cannot drag the mean up and mask the next.
    """

    #: loss > mean + LOSS_SIGMA * std of the rolling window
    LOSS_SIGMA = 6.0
    #: grad norm > GRAD_FACTOR * rolling median
    GRAD_FACTOR = 10.0
    #: mean update ratio < COLLAPSE_FACTOR * rolling median
    COLLAPSE_FACTOR = 1e-3
    #: rolling windows must hold this many samples before spike/
    #: explosion/collapse verdicts arm (nonfinite always fires)
    MIN_SAMPLES = 8
    #: bounded per-owner history backing report()/tools/mxhealth.py
    HISTORY = 256

    def __init__(self, spec: HealthSpec, where: str):
        self.spec = spec
        self.where = where
        self._lock = threading.Lock()
        win = _window()
        self._loss_win = collections.deque(maxlen=win)
        self._grad_win = collections.deque(maxlen=win)
        self._ratio_win = collections.deque(maxlen=win)
        self._history = collections.deque(maxlen=self.HISTORY)
        self._anomalies = collections.deque(maxlen=self.HISTORY)
        self._streak = 0
        self.last_verdict: Optional[dict] = None
        self.samples = 0

    # -- helpers -------------------------------------------------------
    @staticmethod
    def _median(win) -> float:
        s = sorted(win)
        n = len(s)
        return s[n // 2] if n % 2 else 0.5 * (s[n // 2 - 1] + s[n // 2])

    def _worst_subtree(self, parsed: dict, field: str) -> Optional[str]:
        subs = parsed.get("subtrees") or {}
        best, best_v = None, -math.inf
        for name, row in subs.items():
            v = row.get(field, 0.0)
            if math.isfinite(v) and v > best_v:
                best, best_v = name, v
        return best

    def _mean_ratio(self, parsed: dict) -> Optional[float]:
        """Mean ||update|| / ||param|| over subtrees with nonzero
        params — the per-step learning-signal size."""
        ratios = []
        for row in (parsed.get("subtrees") or {}).values():
            p = row.get("param_norm", 0.0)
            if p > 0 and math.isfinite(p) and \
                    math.isfinite(row.get("update_norm", 0.0)):
                ratios.append(row["update_norm"] / p)
        return sum(ratios) / len(ratios) if ratios else None

    # -- the sample path -----------------------------------------------
    def observe(self, vec, step: Optional[int] = None,
                skipped: Optional[bool] = None) -> Optional[dict]:
        """Ingest one sampled health vector; returns the verdict (or
        None).  ``skipped`` marks whether the in-graph skip gate was
        armed for this step (action=skip), purely for event fields."""
        from . import _switch
        if not _switch.enabled:
            return None
        from . import metrics as _m
        from .recorder import record_event, current_step
        parsed = self.spec.parse(vec)
        if step is None:
            step = current_step()
        if skipped is None:
            skipped = self.spec.skip
        loss, gnorm = parsed["loss"], parsed["grad_norm"]
        nonfinite = parsed["nonfinite"]
        ratio = self._mean_ratio(parsed)

        _m.counter("mxtpu_health_samples_total",
                   "health vectors read back from the device").inc()
        _m.gauge("mxtpu_health_loss",
                 "loss at the most recent health sample").set(
            loss if math.isfinite(loss) else float("nan"))
        _m.gauge("mxtpu_health_grad_norm",
                 "global gradient norm at the most recent health "
                 "sample").set(gnorm if math.isfinite(gnorm)
                               else float("nan"))
        if ratio is not None and math.isfinite(ratio):
            _m.histogram(
                "mxtpu_health_update_ratio",
                "per-sample mean ||update||/||param|| over subtrees",
                buckets=UPDATE_RATIO_BUCKETS).observe(ratio)
        if nonfinite > 0:
            _m.counter(
                "mxtpu_health_nonfinite_total",
                "nonfinite values observed in sampled loss/gradients"
                ).inc(nonfinite)

        anomalies: List[dict] = []
        # cross-replica integrity audit (elastic.integrity): replicated
        # values must agree across the dp axis — a minority replica is
        # the corruption suspect, attributed by device index.  Checked
        # BEFORE the numerics branches: a bitflip usually stays finite
        # and would otherwise pass every norm check silently.
        integ = parsed.get("integrity")
        if integ:
            from ..elastic import integrity as _integrity
            for row in ("param", "grad"):
                fps = integ.get(f"{row}_fp")
                if not fps:
                    continue
                suspects = _integrity.agreement(fps)
                if suspects is None:
                    continue
                anomalies.append({
                    "anomaly": "integrity_divergence",
                    "row": row, "suspects": suspects,
                    "subtrees": [],
                    "detail": (f"{row} fingerprints diverge across "
                               f"the dp axis; suspect device(s) "
                               f"{suspects} "
                               f"(fps: "
                               f"{[f'{v:08x}' for v in fps]})")})
                _integrity.note_suspected(self.where, row, suspects,
                                          fps, int(step))
        with self._lock:
            armed = len(self._loss_win) >= self.MIN_SAMPLES
            if nonfinite > 0 or not math.isfinite(loss) or \
                    not math.isfinite(gnorm):
                bad_subs = sorted(
                    s for s, row in parsed["subtrees"].items()
                    if row["nonfinite"] > 0)
                anomalies.append({
                    "anomaly": "nonfinite",
                    "count": int(nonfinite),
                    "subtrees": bad_subs,
                    "detail": (f"{int(nonfinite)} nonfinite value(s) in "
                               "loss/gradients"
                               + (f"; subtree(s) {', '.join(bad_subs)}"
                                  if bad_subs else ""))})
            else:
                if armed:
                    mean = sum(self._loss_win) / len(self._loss_win)
                    var = sum((x - mean) ** 2 for x in self._loss_win) \
                        / len(self._loss_win)
                    std = math.sqrt(var)
                    bound = mean + self.LOSS_SIGMA * max(
                        std, 1e-8 + 1e-3 * abs(mean))
                    if loss > bound:
                        anomalies.append({
                            "anomaly": "loss_spike", "value": loss,
                            "bound": bound,
                            "subtrees": [self._worst_subtree(
                                parsed, "grad_norm")],
                            "detail": f"loss {loss:.6g} above rolling "
                                      f"bound {bound:.6g} (mean "
                                      f"{mean:.6g} + {self.LOSS_SIGMA}"
                                      "*std)"})
                    gmed = self._median(self._grad_win)
                    if gmed > 0 and gnorm > self.GRAD_FACTOR * gmed:
                        anomalies.append({
                            "anomaly": "grad_explosion", "value": gnorm,
                            "bound": self.GRAD_FACTOR * gmed,
                            "subtrees": [self._worst_subtree(
                                parsed, "grad_norm")],
                            "detail": f"grad norm {gnorm:.6g} is "
                                      f"{gnorm / gmed:.1f}x the rolling "
                                      f"median {gmed:.6g}"})
                    if ratio is not None and self._ratio_win:
                        rmed = self._median(self._ratio_win)
                        if rmed > 0 and \
                                ratio < self.COLLAPSE_FACTOR * rmed:
                            anomalies.append({
                                "anomaly": "update_ratio_collapse",
                                "value": ratio,
                                "bound": self.COLLAPSE_FACTOR * rmed,
                                "subtrees": [self._worst_subtree(
                                    parsed, "param_norm")],
                                "detail":
                                    f"update ratio {ratio:.3g} "
                                    "collapsed vs rolling median "
                                    f"{rmed:.3g}"})
                if not anomalies:
                    # only healthy samples feed the baselines
                    self._loss_win.append(loss)
                    self._grad_win.append(gnorm)
                    if ratio is not None:
                        self._ratio_win.append(ratio)
            if anomalies:
                self._streak += 1
            else:
                self._streak = 0
            streak = self._streak
            self.samples += 1
            row = dict(parsed)
            row["step"] = int(step)
            # the ratio THE DETECTOR USED (isfinite-guarded), so the
            # report never shows a different number than the verdict
            # was judged against
            row["update_ratio"] = ratio
            row["anomalies"] = [a["anomaly"] for a in anomalies]
            self._history.append(row)

        for a in anomalies:
            _m.counter("mxtpu_health_anomalies_total",
                       "health anomalies the sentinel flagged").inc()
            record_event("health_anomaly", where=self.where,
                         skipped=bool(skipped and
                                      a["anomaly"] == "nonfinite"),
                         **a)

        verdict = None
        integ_anoms = [a for a in anomalies
                       if a["anomaly"] == "integrity_divergence"]
        if integ_anoms:
            # immediate, like nonfinite — and ranked above it: a
            # bitflip that ALSO went nonfinite is still a corruption
            # incident first (the response ladder differs).  The
            # streak rides along so handle_verdict can fall through
            # to the HEALTH ladder when an unactioned (warn-mode)
            # corruption verdict co-occurs with sustained numerics
            # anomalies.
            suspects = sorted({s for a in integ_anoms
                               for s in a["suspects"]})
            verdict = {"kind": "integrity_divergence",
                       "suspects": suspects, "streak": streak,
                       "anomalies": anomalies, "step": int(step)}
        elif any(a["anomaly"] == "nonfinite" for a in anomalies):
            verdict = {"kind": "nonfinite", "anomalies": anomalies,
                       "step": int(step)}
        elif anomalies and streak >= _patience():
            verdict = {"kind": "divergence", "streak": streak,
                       "anomalies": anomalies, "step": int(step)}
        with self._lock:
            if verdict is not None:
                self.last_verdict = verdict
            # under the lock: snapshot() iterates this deque from
            # other threads (live report renders)
            for a in anomalies:
                self._anomalies.append(dict(a, step=int(step)))
        return verdict

    # -- reporting -----------------------------------------------------
    def snapshot(self) -> dict:
        with self._lock:
            return {
                "where": self.where,
                "fields": self.spec.fields(),
                "subtrees": list(self.spec.subtrees),
                "skip_gate": self.spec.skip,
                "samples": self.samples,
                "history": [dict(r) for r in self._history],
                "anomalies": [dict(a) for a in self._anomalies],
                "last_verdict": self.last_verdict,
            }


def sample_owner(owner, where: str, spec: HealthSpec, health_out,
                 k: int = 1) -> Optional[dict]:
    """The shared per-dispatch sampling path for the step stacks.

    Advances ``owner._health_count`` by the dispatch's ``k`` real
    steps, and ONLY when a sampled index (every ``MXTPU_HEALTH_EVERY``
    steps) landed in this dispatch reads the device vector back (the
    plane's one host sync), feeds the owner's sentinel, and applies
    the verdict action.  ``health_out`` is the raw program output — a
    1-D vector for a single step, a (K, n) matrix for a bulked
    ``step_multi``.  Returns the verdict, if any."""
    import numpy as np
    base = getattr(owner, "_health_count", 0)
    owner._health_count = base + k
    ev = every()
    due = [i for i in range(k) if (base + i + 1) % ev == 0]
    if not due:
        return None
    sent = get_sentinel(where, spec)
    mat = np.asarray(health_out)
    # each row keeps ITS step index (owner-local, 1-based) so a bulked
    # dispatch's anomalies localize to the exact inner step
    rows = [(base + 1, mat)] if mat.ndim == 1 else \
        [(base + i + 1, mat[i]) for i in due]
    verdict = None
    for step_i, r in rows:
        v = sent.observe(r, step=step_i)
        if v is not None:
            verdict = v
    handle_verdict(owner, verdict)
    return verdict


def handle_verdict(owner, verdict: Optional[dict]) -> bool:
    """The action half of a sentinel verdict: under
    ``MXTPU_HEALTH_ACTION=rollback`` with a manager attached
    (``owner.health_manager``), a nonfinite or divergence verdict
    drives the owner's ``recover(manager)`` — the elastic plane's
    restore-from-last-committed-checkpoint protocol.  Returns True
    when a rollback ran.  ``skip`` needs no host action (the gate is
    in-graph); ``warn`` records only.  An ``integrity_divergence``
    verdict takes the corruption ladder instead
    (``MXTPU_INTEGRITY_ACTION`` — warn / rollback / QUARANTINE,
    ``elastic.integrity.respond``)."""
    if verdict is None:
        return False
    if verdict.get("kind") == "integrity_divergence":
        from ..elastic import integrity as _integrity
        if _integrity.respond(owner, verdict):
            return True
        others = [a for a in verdict.get("anomalies", ())
                  if a.get("anomaly") != "integrity_divergence"]
        nonfinite = any(a.get("anomaly") == "nonfinite"
                        for a in others)
        diverging = others and \
            int(verdict.get("streak", 0)) >= _patience()
        if not (nonfinite or diverging):
            return False
        # the sample ALSO carried numerics anomalies the health
        # ladder would have acted on (nonfinite, or a sustained
        # spike/explosion/collapse streak past patience): an
        # unactioned corruption verdict (warn mode) must not
        # suppress the user's configured MXTPU_HEALTH_ACTION —
        # fall through to it
    if action() != "rollback":
        return False
    manager = getattr(owner, "health_manager", None)
    if manager is None:
        from .recorder import record_event
        record_event("health_anomaly", where="health",
                     anomaly="rollback_unarmed",
                     detail="MXTPU_HEALTH_ACTION=rollback but no "
                            "health_manager is attached; set "
                            "owner.health_manager to a "
                            "CheckpointManager")
        return False
    try:
        owner.recover(manager)
    except Exception as e:
        # armed but nothing committed yet (or the restore itself
        # died): degrade LOUDLY like the unarmed case instead of
        # crashing the training loop — the sentinel keeps flagging and
        # retrying on every sampled verdict until a save commits
        from .recorder import record_event
        record_event("health_anomaly", where="health",
                     anomaly="rollback_failed",
                     detail=f"recover(manager) failed: {e!r}"[:300])
        return False
    # counted AFTER the restore: a failed recover must not read as a
    # rollback that happened
    from . import metrics as _m
    _m.counter("mxtpu_health_rollbacks_total",
               "automatic checkpoint rollbacks on a health verdict"
               ).inc()
    return True


# -- per-process registry (tools/mxhealth.py reads it) ------------------

_reg_lock = threading.Lock()
_sentinels: Dict[str, Sentinel] = {}


def get_sentinel(where: str, spec: HealthSpec) -> Sentinel:
    """The step stacks register here so one process-wide report covers
    every owner.  A spec change (retrace after a config flip) replaces
    the sentinel — stale windows from a different layout would
    misparse."""
    with _reg_lock:
        s = _sentinels.get(where)
        if s is None or s.spec.signature() != spec.signature():
            s = Sentinel(spec, where)
            _sentinels[where] = s
        return s


def sentinels() -> Dict[str, Sentinel]:
    with _reg_lock:
        return dict(_sentinels)


def reset():
    """Forget every sentinel (test isolation; part of
    ``telemetry.reset()``)."""
    with _reg_lock:
        _sentinels.clear()


def report() -> dict:
    """Process-wide health report: one entry per step owner, plus the
    plane's config."""
    return {
        "kind": "mxtpu_health_report",
        "enabled": enabled(),
        "every": every(),
        "action": action(),
        "owners": {w: s.snapshot() for w, s in sentinels().items()},
    }


def dump_report(path: str) -> str:
    """Write :func:`report` as a JSON artifact (atomic); returns the
    path — ``tools/mxhealth.py render`` displays it."""
    import os
    rep = report()
    tmp = path + f".tmp{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(rep, f, indent=1, default=str)
    os.replace(tmp, path)
    return path


def render_table(rep: dict, last: int = 12) -> str:
    """Text rendering of a :func:`report` dict: per-owner rolling
    health table (last N samples), the anomaly log, and the last
    verdict — the ``tools/mxhealth.py`` view."""
    lines = [f"health plane: enabled={rep.get('enabled')} "
             f"every={rep.get('every')} action={rep.get('action')}"]
    owners = rep.get("owners") or {}
    if not owners:
        lines.append("no health samples recorded")
        return "\n".join(lines)
    for where, snap in sorted(owners.items()):
        lines.append("")
        lines.append(f"[{where}] {snap.get('samples', 0)} sample(s), "
                     f"subtrees: {', '.join(snap.get('subtrees', []))}"
                     + (" (skip gate armed)"
                        if snap.get("skip_gate") else ""))
        hist = (snap.get("history") or [])[-last:]
        lines.append(f"{'STEP':>6} {'LOSS':>12} {'GRAD':>12} "
                     f"{'RATIO':>10} {'NONFIN':>7} ANOMALIES")
        for row in hist:
            ratio = row.get("update_ratio")
            if ratio is None:
                ratio = float("nan")
            lines.append(
                f"{row.get('step', 0):>6} {row.get('loss', 0):>12.5g} "
                f"{row.get('grad_norm', 0):>12.5g} {ratio:>10.3g} "
                f"{int(row.get('nonfinite', 0)):>7} "
                f"{','.join(row.get('anomalies') or []) or '-'}")
        anomalies = snap.get("anomalies") or []
        if anomalies:
            lines.append("anomaly log:")
            for a in anomalies[-last:]:
                subs = ", ".join(x for x in (a.get("subtrees") or [])
                                 if x)
                lines.append(
                    f"  step {a.get('step', 0)}: {a.get('anomaly')} "
                    f"[{subs or 'global'}] {a.get('detail', '')}")
        v = snap.get("last_verdict")
        lines.append(f"last verdict: "
                     + (f"{v['kind']} at step {v.get('step')}"
                        if v else "healthy"))
    return "\n".join(lines)
