"""Dispatch engine: the TPU-native stand-in for the threaded dependency engine.

Capability parity: reference ``src/engine/`` (ThreadedEnginePerDevice,
NaiveEngine, ``WaitForVar/WaitForAll``) — see SURVEY.md §2.1.  The reference
builds its own var-based dataflow scheduler because CUDA needs one; XLA/PJRT
already executes asynchronously with per-buffer dataflow ordering, so the
TPU-native engine is a thin layer that:

  * compiles each (op, static-attrs) pair once via ``jax.jit`` and caches the
    executable — the "one-op jit" (SURVEY.md §7 P1);
  * preserves the user-visible async semantics: ops return immediately,
    ``wait_to_read()`` / ``asnumpy()`` are the sync points, and runtime errors
    teleport to the next sync point (PJRT does this natively);
  * offers the NaiveEngine equivalent (``MXNET_ENGINE_TYPE=NaiveEngine`` or
    ``MXTPU_ENGINE_TYPE=NaiveEngine``): block after every op, for debugging
    and determinism, matching the reference's env-var swap.

``waitall`` tracks live output buffers in a weak set, mirroring
``Engine::WaitForAll``.
"""
from __future__ import annotations

import functools
import os
import random as _random_mod
import threading
import weakref
from typing import Any, Callable, Dict, Optional, Tuple

from . import persist
from ..elastic import faults as _faults
from ..profiler import recording as _recording, span as _span

__all__ = ["invoke_compiled", "waitall", "is_naive", "set_bulk_size",
           "cache_info", "cache_size", "live_bytes", "live_arrays",
           "clear_cache",
           "drop_cached", "reset_counters", "dispatch_count",
           "compile_counts", "aot_compile", "persist", "retrying_call"]

_lock = threading.Lock()
_jit_cache: Dict[Tuple, Callable] = {}
# weak map of in-flight jax arrays for waitall() / the live-buffer
# census, keyed by id: jax arrays are UNHASHABLE (like numpy), so a
# WeakSet.add would raise TypeError on every buffer and track nothing
_live: "weakref.WeakValueDictionary[int, Any]" = \
    weakref.WeakValueDictionary()

# dispatch/compile-cache telemetry (surfaced via cache_info()): one
# "dispatch" = one invoke_compiled call = one XLA executable launch.
# The tier-1 dispatch-count tests, chip_smoke.py and the benchmark's
# ``dispatches_per_step.train`` read these, so the counters are part
# of the public introspection contract, not debug scaffolding.
_hits = 0
_misses = 0
_dispatches = 0
# compiles served by NO cache tier (memory or persistent).  With the
# persistent tier on, this is exact (the tiered wrapper counts at the
# actual lower+compile); with it off, a memory-tier miss is counted at
# jit creation (the compile follows at first dispatch).  The warm-start
# acceptance contract ("a warm restart performs 0 fresh compiles") is
# asserted against this counter.
_fresh_compiles = 0
# AOT executables replaced by the plain jit path (``_note_aot_demotion``):
# a tier that silently stopped serving shows up here, not only as an
# event.  A run on the chip asserts it stays 0.
_aot_demotions = 0

# -- telemetry plane (PR 4) -------------------------------------------------
# The engine is the hottest seam in the process, so the telemetry
# wiring follows a strict pattern: one lazily-bound module ref, one
# `_switch.enabled` attribute load per dispatch, and ALL structured
# work (key recompute, aval signatures, event dicts) behind it.
_telem = None
# mxsan hook (analysis.sanitizer, docs/static_analysis.md "The
# sanitizer"): the sanitizer module itself when MXTPU_SANITIZE >= 1,
# None otherwise — the off cost is ONE attribute load per dispatch
# (held by tests/test_sanitizer.py).  Set via
# sanitizer.configure(), never imported here (the analysis package
# imports the engine; a top-level import back would cycle).
_san = None
# op name -> attr signatures that have compiled (retrace-cause
# attribution diffs a new signature against the closest prior one)
_op_attr_sigs: Dict[str, list] = {}
# cache key -> input (shape, dtype) signatures seen by invoke_compiled;
# jax.jit re-traces per shape/dtype, so a NEW signature for an existing
# key is exactly a retrace the cache counters cannot see
_key_avals: Dict[Any, list] = {}
_AVAL_HISTORY_CAP = 64
# attribution state takes its own lock (same reasoning as the counter
# lock below: DataLoader workers dispatch while the train thread does —
# an unlocked check-then-append would let two first-time dispatches of
# the same signature emit a phantom empty-diff retrace event, and the
# contract is that steady state shows ZERO retrace events)
_attr_lock = threading.Lock()


def _telemetry():
    global _telem
    if _telem is None:
        from .. import telemetry
        _telem = telemetry
    return _telem


# counter objects cached at first use: the registry lookup behind
# telemetry.counter() takes the metrics lock, which the per-dispatch
# hot path should not pay twice per call
_c_dispatch = None
_c_donated = None
_c_miss = None
_c_retrace = None


def _counters(t):
    global _c_dispatch, _c_donated, _c_miss, _c_retrace
    if _c_dispatch is None:
        _c_dispatch = t.counter(
            "mxtpu_engine_dispatches_total",
            "invoke_compiled calls (XLA executable launches)")
        _c_donated = t.counter(
            "mxtpu_donated_dispatches_total",
            "dispatches that donated input buffers")
        _c_miss = t.counter("mxtpu_engine_cache_misses_total",
                            "jit-cache misses (compiles)")
        _c_retrace = t.counter(
            "mxtpu_retraces_total",
            "cache misses attributable to a changed attr/shape/dtype")
    return _c_dispatch, _c_donated, _c_miss, _c_retrace


def _sig_diff(old_sig, new_sig) -> dict:
    """``{attr: [old, new]}`` for every attr that differs between two
    frozen signatures (``<absent>`` marks one-sided attrs)."""
    try:
        old = dict(old_sig)
        new = dict(new_sig)
    except (TypeError, ValueError):
        return {"signature": [repr(old_sig), repr(new_sig)]}
    changed = {}
    for k in set(old) | set(new):
        ov = old.get(k, "<absent>")
        nv = new.get(k, "<absent>")
        if ov != nv:
            changed[k] = [repr(ov), repr(nv)]
    return changed


def _note_compile(name: str, sig):
    """Called on every cache miss (telemetry on): if this op compiled
    before under a DIFFERENT attr signature, emit a ``retrace`` event
    attributing the exact attrs that changed — the Relay lesson applied
    to the jit cache (structured provenance over opaque counters)."""
    best = None
    with _attr_lock:
        prior = _op_attr_sigs.setdefault(name, [])
        if sig in prior:
            return
        if prior:
            for p in prior:
                d = _sig_diff(p, sig)
                if best is None or len(d) < len(best):
                    best = d
        prior.append(sig)
    if best:
        t = _telemetry()
        _counters(t)[3].inc()
        t.record_event("retrace", op=name, cause="attrs",
                       changed=best)


def _note_avals(name: str, key, arrays):
    """Shape/dtype-driven retrace attribution: a new input signature
    for an already-compiled key means jax.jit re-traced underneath the
    engine cache.  Emits the old->new diff against the closest seen
    signature."""
    aval = tuple(
        (tuple(getattr(a, "shape", ()) or ()),
         str(getattr(a, "dtype", type(a).__name__)))
        for a in arrays)
    # lock-free fast path: steady state is "signature already seen" —
    # a plain list read under the GIL is safe against concurrent
    # appends, and a rare false negative just falls through to the
    # locked re-check
    seen = _key_avals.get(key)
    if seen is not None and aval in seen:
        return
    best = None
    with _attr_lock:
        seen = _key_avals.setdefault(key, [])
        if aval in seen:
            return
        for prev in seen:
            changed = {}
            if len(prev) != len(aval):
                changed["nargs"] = [len(prev), len(aval)]
            for i, (o, n) in enumerate(zip(prev, aval)):
                if o[0] != n[0]:
                    changed[f"arg{i}.shape"] = [list(o[0]), list(n[0])]
                if o[1] != n[1]:
                    changed[f"arg{i}.dtype"] = [o[1], n[1]]
            # <= : on equally-similar signatures, diff against the most
            # RECENT one — "what changed since last time" reads better
            # than a diff vs an arbitrary older entry
            if best is None or len(changed) <= len(best):
                best = changed
        # ALWAYS record the new signature, evicting the oldest at the
        # cap — refusing to record would make every later dispatch of
        # signature 65 re-enter this path and emit a phantom retrace
        # per dispatch, forever
        seen.append(aval)
        if len(seen) > _AVAL_HISTORY_CAP:
            del seen[0]
    if best:
        cause = "dtypes" if all(
            k.endswith(".dtype") for k in best) else "shapes"
        t = _telemetry()
        _counters(t)[3].inc()
        t.record_event("retrace", op=name, cause=cause, changed=best)


def _note_fresh_compile(name: str, seconds: Optional[float] = None):
    """Count a compile no cache tier served (``seconds`` known only on
    the AOT path, where the lower+compile is explicit)."""
    global _fresh_compiles
    with _lock:
        _fresh_compiles += 1
    t = _telem if _telem is not None else _telemetry()
    if t._switch.enabled:
        t.counter("mxtpu_fresh_compiles_total",
                  "XLA compiles served by no cache tier").inc()
        if seconds is not None:
            t.histogram("mxtpu_compile_seconds",
                        "fresh-compile wall clock (s)").observe(seconds)


def _note_aot_demotion(name: str, err: BaseException):
    """Count an AOT executable demoted to the plain jit path and leave
    the ``persist_error`` event that says why."""
    global _aot_demotions
    with _lock:
        _aot_demotions += 1
    t = _telem if _telem is not None else _telemetry()
    if t._switch.enabled:
        t.record_event("persist_error", op=name,
                       error=f"aot demoted: {err!r}"[:300])


class _TieredFn:
    """Memory-tier entry backed by the persistent tier (``persist.py``).

    ``jax.jit``'s implicit per-aval retrace+compile is replaced by an
    EXPLICIT per-aval-signature resolution: persistent tier (reload, no
    trace) -> fresh AOT ``lower().compile()`` (serialized back to disk).
    The explicit step is what makes a compiled-executable object exist
    to serialize — a plain jit call never surfaces one.

    An AOT lower/compile failure is raised, never demoted: a quiet
    switch to plain jit would hide that the device refused a program.
    The one demotion left is an AOT executable that rejects an aval
    drift with ``TypeError`` at call time, counted in ``cache_info()
    ["aot_demotions"]``.
    """

    __slots__ = ("name", "persist_name", "_bound", "_donate", "_sig",
                 "_jitted", "_by_aval", "_rlock")

    def __init__(self, name, bound, donate, sig, persist_name=None):
        self.name = name
        self.persist_name = persist_name or name
        self._bound = bound
        self._donate = tuple(donate)
        self._sig = sig
        self._jitted = None
        self._by_aval: Dict[Tuple, Callable] = {}
        self._rlock = threading.Lock()

    def _jit(self):
        if self._jitted is None:
            jax = __import__("jax")
            self._jitted = jax.jit(self._bound,
                                   donate_argnums=self._donate) \
                if self._donate else jax.jit(self._bound)
        return self._jitted

    def _resolve(self, s, arrays):
        with self._rlock:
            fn = self._by_aval.get(s)
            if fn is not None:
                return fn, "cached"
            fn, src = persist.tiered_compile(
                self.persist_name, self._jit(), arrays,
                donate=self._donate, sig=self._sig,
                op_label=self.name)
            self._by_aval[s] = fn
            return fn, src

    def warm(self, arrays) -> str:
        """Ensure an executable exists for these avals (arrays or
        ``ShapeDtypeStruct``s) WITHOUT dispatching.  Returns where it
        came from: ``cached`` / ``persist`` / ``compiled``."""
        return self._resolve(persist.aval_sig(arrays), arrays)[1]

    def __call__(self, *arrays):
        s = persist.aval_sig(arrays)
        fn = self._by_aval.get(s)
        if fn is None:
            fn = self._resolve(s, arrays)[0]
        try:
            return fn(*arrays)
        except TypeError as e:
            # a drift the (shape, dtype) signature cannot see and an
            # AOT executable rejects (e.g. a tuple argument now passed
            # as a list): demote this signature to the jit path
            # permanently; a genuine arity/type error re-raises
            # identically from the jit call
            jit = self._jit()
            if fn is jit:
                raise
            _note_aot_demotion(self.name, e)
            with self._rlock:
                self._by_aval[s] = jit
            return jit(*arrays)


_NAIVE = None


def is_naive() -> bool:
    # cached: this sits on the per-op hot path, and two environ reads
    # per dispatch cost ~6 us; the engine type is a process-lifetime
    # choice (set _NAIVE = None to re-read in tests)
    global _NAIVE
    if _NAIVE is None:
        _NAIVE = (os.environ.get("MXTPU_ENGINE_TYPE",
                                 os.environ.get("MXNET_ENGINE_TYPE", ""))
                  == "NaiveEngine")
    return _NAIVE


def _freeze(v: Any):
    if isinstance(v, (list,)):
        return tuple(_freeze(x) for x in v)
    if isinstance(v, dict):
        return tuple(sorted((k, _freeze(x)) for k, x in v.items()))
    return v


def _cache_key(name: str, attrs: dict, donate: Tuple[int, ...]):
    """``(key, sig)`` for the jit cache.  Attr-less ops (the bulk of
    elemwise traffic) skip the freeze/sort; hashable attr values take a
    SORTED items key so reordered-kwargs call sites share one cache
    entry for the same executable."""
    if not attrs and not donate:
        return name, ()
    try:
        sig = tuple(sorted(attrs.items()))
        key = (name, sig, tuple(donate)) if donate else (name, sig)
        hash(key)
    except TypeError:
        sig = _freeze(attrs)
        key = (name, sig, tuple(donate)) if donate else (name, sig)
    return key, sig


def get_compiled(name: str, fcompute: Callable, attrs: dict,
                 donate: Tuple[int, ...] = (),
                 persist_name: Optional[str] = None) -> Callable:
    """Return the jitted executable for (op, attrs); compile-once semantics.

    This is the moral equivalent of the reference's per-op FCompute lookup +
    engine push: jax.jit re-traces per input shape/dtype/device, which plays
    the role of the per-(shape,dtype,ctx) plan cache in CachedOp.

    ``donate``: positional indices of input arrays whose buffers the
    executable may reuse for its outputs (``jax.jit(donate_argnums=...)``).
    The fused multi-tensor optimizer step donates the weight/state buffers
    so a BERT-sized update does not double live-HBM; callers that donate
    own the aliasing contract (the donated jax.Array is dead after the
    call — swap the new buffer in before anything reads the old one).
    Donating and non-donating callers of the same (op, attrs) get
    distinct cache entries.

    ``persist_name``: stable identity for the PERSISTENT tier when the
    in-memory ``name`` is process-scoped (CompiledStep's uid-suffixed
    step names); defaults to ``name``.  With
    ``MXTPU_COMPILE_CACHE_DIR`` set, misses return a tiered wrapper
    that consults the on-disk executable cache before compiling.
    """
    return _lookup(name, fcompute, attrs, donate, persist_name)[1]


def _lookup(name, fcompute, attrs, donate, persist_name):
    """``(cache key, executable)``: invoke_compiled shares the key with
    the telemetry plane's aval tracking instead of recomputing the attr
    sort/freeze per dispatch."""
    key, sig = _cache_key(name, attrs, donate)
    return key, _get_compiled_keyed(key, sig, name, fcompute, attrs,
                                    donate, persist_name=persist_name)


def _get_compiled_keyed(key, sig, name, fcompute, attrs, donate,
                        persist_name=None, force_tiered=False):
    """:func:`get_compiled` body with the cache key precomputed."""
    global _hits, _misses
    fn = _jit_cache.get(key)
    if fn is None:
        compiled_now = False
        plain_jit = False
        with _lock:
            fn = _jit_cache.get(key)
            if fn is None:
                _misses += 1  # under _lock, like every counter mutation
                bound = functools.partial(fcompute, **attrs) if attrs else fcompute
                # ops that orchestrate their own device placement /
                # inner jit (ring attention's shard_map over a mesh)
                # must not be wrapped in an outer single-device jit
                if getattr(fcompute, "_mxtpu_no_jit", False):
                    fn = bound
                elif force_tiered or persist.enabled() or donate \
                        or persist_name is not None:
                    # tiered wrapper: persistent tier under the memory
                    # tier; the actual compile (and its fresh-compile
                    # accounting) happens at per-aval resolution.
                    # Donating and persist-named entries (the fused
                    # optimizer step, CompiledStep) go tiered even
                    # with the persistent tier OFF: the explicit
                    # lower().compile() is what gives the memory
                    # observatory an executable to harvest, and these
                    # step-class programs are exactly the ones whose
                    # HBM footprint matters
                    fn = _TieredFn(name, bound, tuple(donate), sig,
                                   persist_name)
                else:
                    jax = __import__("jax")
                    fn = jax.jit(bound, donate_argnums=tuple(donate)) \
                        if donate else jax.jit(bound)
                    plain_jit = True
                _jit_cache[key] = fn
                compiled_now = True
        if compiled_now:
            if plain_jit:
                # persist tier off: the compile follows at the first
                # dispatch of this jit — counted here, where the miss is
                _note_fresh_compile(name)
            t = _telem if _telem is not None else _telemetry()
            if t._switch.enabled:
                _counters(t)[2].inc()
                _note_compile(name, sig)
            return fn
    # += on a module global is not atomic (read-modify-write can lose
    # increments across threads, e.g. DataLoader workers dispatching
    # while the main thread trains) and the dispatch counters are an
    # exact contract for the tests and the benchmark — take the lock
    with _lock:
        _hits += 1
    return fn


_tracer_cls = None


def track(arr):
    """Register an output buffer so waitall() can find it.  Tracers
    (op calls inside a jax trace — CompiledStep's core, hybridized
    forwards) are NOT buffers and must stay out: blocking or size-
    probing one later would raise ConcretizationTypeError."""
    global _tracer_cls
    if _tracer_cls is None:
        from jax.core import Tracer
        _tracer_cls = Tracer
    if isinstance(arr, _tracer_cls):
        return arr
    try:
        _live[id(arr)] = arr
    except TypeError:
        pass
    return arr


def live_arrays() -> list:
    """Snapshot of the live tracked buffers (shared by ``waitall``,
    :func:`live_bytes`, and ``telemetry.memory.census``)."""
    return list(_live.values())


# profiler interception point — the reference wires its profiler inside
# ThreadedEngine::ExecuteOprBlock (SURVEY.md §5 Tracing); ours wraps the
# dispatch here and records the per-op chrome event (``cat: operator``,
# named by the op).  None unless ``profiler.set_state("run")``.
_profiler_hook = None


# -- transient-failure retry (docs/elasticity.md) ---------------------------
# A remote PJRT tunnel hiccup or a device-side transient should not
# reach the poison protocol when the dispatch can simply run again.
# Retry is only SAFE while every input buffer is still alive — once a
# donated argument was consumed, re-invoking would read dead memory —
# so the probe gates every attempt.  Opt-in via MXTPU_DISPATCH_RETRIES
# (default 0: semantics identical to the pre-elastic engine).

def _retry_policy():
    from .. import envs
    return (int(envs.get("MXTPU_DISPATCH_RETRIES")),
            float(envs.get("MXTPU_DISPATCH_BACKOFF_MS")))


# errors that look like RuntimeError but can never succeed on retry:
# XLA surfaces compile/shape/arity problems and device OOM as
# XlaRuntimeError (a RuntimeError subclass) with a canonical status
# prefix, and re-dispatching them just burns MXTPU_DISPATCH_RETRIES
# before the poison protocol gets to run.  Matched case-insensitively
# against the message so wrapped/tunnelled copies still classify.
_NON_TRANSIENT_MARKERS = (
    "resource_exhausted", "out of memory", "invalid_argument",
    "failed_precondition", "unimplemented", "incompatible shapes")

#: jitter source for the retry backoff — intentionally unseeded
#: (synchronized retries are the problem jitter exists to solve)
_retry_rng = _random_mod.Random()


def _retryable_error(e: Exception) -> bool:
    """Transient-shaped errors only: runtime/IO failures.  Program
    errors (TypeError/ValueError: aval drift, bad arity — the tiered
    wrapper's own demotion protocol keys on TypeError), our own
    MXNetError diagnostics, and non-transient device errors
    (``XlaRuntimeError`` OOM / shape / invalid-argument statuses —
    :data:`_NON_TRANSIENT_MARKERS`) re-raise immediately: they fail
    fast into the caller's poison protocol instead of burning the
    retry budget on a dispatch that can never succeed."""
    from ..base import MXNetError
    if isinstance(e, MXNetError):
        return False
    if not isinstance(e, (RuntimeError, OSError)):
        return False
    msg = str(e).lower()
    if any(m in msg for m in _NON_TRANSIENT_MARKERS):
        return False
    return True


def _next_backoff_ms(base_ms: float, prev_ms: float) -> float:
    """Decorrelated-jitter backoff: ``U[base, max(base, prev * 3)]``
    capped at ``base * 32``.  Unlike the plain exponential schedule
    this one never synchronizes — N workers retrying the same
    transient fan out across the window instead of hammering the
    device in lockstep at ``base * 2^k``."""
    if base_ms <= 0:
        return 0.0
    hi = max(base_ms, prev_ms * 3.0)
    return min(base_ms * 32.0, _retry_rng.uniform(base_ms, hi))


def retrying_call(call, probe_arrays, op: str):
    """Run ``call()`` under the bounded-retry + decorrelated-jitter
    backoff policy.  ``probe_arrays``: the input buffers whose
    deletion marks the dispatch as post-donation (never retried).
    Shared by ``invoke_compiled`` and the SPMD trainer's fused
    dispatch — which makes it the one place a device dispatch is
    COUNTED (``cache_info()["dispatches"]``): one call here is one
    executable launch, whichever path built the executable."""
    import time as _time
    global _dispatches
    with _lock:
        _dispatches += 1
    san = _san
    if san is not None:
        # the lifetime sanitizer's dispatch-entry check (MXL701
        # use-after-donate over the probe set, MXL706 lock held across
        # a blocking dispatch) — this seam sees BOTH the engine path
        # (probe = every input) and the SPMD trainer's direct fused
        # dispatches (probe = the pre-filtered donated set)
        san.pre_dispatch(op, probe_arrays)
    attempt = 0
    sleep_ms = 0.0
    retries = backoff_ms = None
    while True:
        try:
            return call()
        except Exception as e:
            if retries is None:
                retries, backoff_ms = _retry_policy()
            if attempt >= retries or not _retryable_error(e) or any(
                    getattr(a, "is_deleted", lambda: False)()
                    for a in probe_arrays):
                raise
            attempt += 1
            sleep_ms = _next_backoff_ms(backoff_ms, sleep_ms)
            t = _telem if _telem is not None else _telemetry()
            if t._switch.enabled:
                t.counter(
                    "mxtpu_dispatch_retries_total",
                    "transient dispatch failures absorbed by retry"
                    ).inc()
                t.record_event("dispatch_retry", op=op,
                               attempt=attempt,
                               backoff_ms=round(sleep_ms, 2),
                               error=repr(e)[:300])
            _time.sleep(sleep_ms / 1000.0)


def _account_dispatch(t, name, key, arrays, donate):
    """The telemetry plane's share of one dispatch: counters, the
    ``dispatch`` event, and the input signature's retrace attribution."""
    c_disp, c_don = _counters(t)[:2]
    c_disp.inc()
    if donate:
        c_don.inc()
    t.record_event("dispatch", op=name)
    _note_avals(name, key, arrays)


def invoke_compiled(name: str, fcompute: Callable, attrs: dict, *arrays,
                    donate: Tuple[int, ...] = (),
                    persist_name: Optional[str] = None):
    """Execute an op through the compile cache. Returns jax array(s).

    ``donate`` flows to :func:`get_compiled` (buffer donation for the
    fused optimizer path).  NaiveEngine semantics are honored for every
    entry, donating or not: a donated fused step still blocks per
    dispatch when ``MXTPU_ENGINE_TYPE=NaiveEngine``.
    ``persist_name``: see :func:`get_compiled`.
    """
    t = _telem if _telem is not None else _telemetry()
    telem_on = t._switch.enabled
    # this is the eager per-op path too, where a span's 0.5 us would be
    # over 1% of an op: the engine's spans are entered only while a sink
    # records
    recording = _recording()
    if recording:
        with _span("mxtpu.engine.lookup", "engine", op=name):
            key, fn = _lookup(name, fcompute, attrs, donate, persist_name)
        if telem_on:
            with _span("mxtpu.engine.telemetry", "engine", op=name):
                _account_dispatch(t, name, key, arrays, donate)
    else:
        key, fn = _lookup(name, fcompute, attrs, donate, persist_name)
        if telem_on:
            _account_dispatch(t, name, key, arrays, donate)

    def _run():
        if _faults._active:
            # deterministic fault injection (docs/elasticity.md):
            # "dispatch" raises pre-execution with buffers alive — a
            # one-shot spec is absorbed by the retry loop around this
            # thunk; "dispatch_post" consumes the donated buffers
            # first, so the caller's poison protocol engages exactly
            # as on real hardware
            _faults.on_dispatch(name, arrays, donate)
        if not recording:
            return fn(*arrays)
        with _span("mxtpu.engine.execute", "engine", op=name):
            hook = _profiler_hook
            if hook is not None:
                return hook(name, fn, arrays)
            return fn(*arrays)

    san = _san
    if san is not None and donate:
        # MXL702 (same buffer at two donate indices) before the
        # dispatch can alias outputs onto it; the MXL701/706 checks
        # run inside retrying_call
        san.check_donation(name, arrays, donate)
    try:
        out = retrying_call(_run, arrays, name)
        if is_naive():
            import jax
            jax.block_until_ready(out)
    except Exception as e:
        # crash forensics: the ring holds the dispatches/retraces that
        # led here — dump it (throttled, never raising) and let the
        # original error propagate untouched
        if telem_on:
            t.record_event("error", op=name, error=repr(e)[:500])
            t.auto_dump(reason=f"invoke_compiled:{name}")
        raise
    if san is not None and donate:
        # the donated inputs are now dead: shadow-mark them with
        # op attribution so a later use convicts by name (MXL701)
        san.post_dispatch(name, arrays, donate)
    if isinstance(out, tuple):
        for o in out:
            track(o)
    else:
        track(out)
    return out


def waitall():
    """Block until every tracked in-flight buffer is ready.

    Parity: ``mx.nd.waitall()`` → ``Engine::WaitForAll``.
    """
    import jax
    for arr in live_arrays():
        # a buffer donated to a fused update is deleted the moment its
        # successor exists — that is normal, not an in-flight error
        if getattr(arr, "is_deleted", lambda: False)():
            continue
        try:
            jax.block_until_ready(arr)
        except Exception:
            # teleported async error: surface it, like WaitForAll would
            raise


def aot_compile(name: str, fcompute: Callable, attrs: dict,
                example_args, donate: Tuple[int, ...] = (),
                persist_name: Optional[str] = None) -> str:
    """Warm-start entry: make sure (op, attrs) has a ready executable
    for ``example_args`` (concrete arrays or ``ShapeDtypeStruct``s)
    WITHOUT dispatching anything.

    Resolution is the tiered wrapper's: memory -> persistent tier
    (reload, no trace/compile) -> fresh AOT compile (persisted for the
    next process).  Returns where the executable came from:
    ``"cached"`` / ``"persist"`` / ``"compiled"``, or ``"jit"`` when
    the key already holds a plain jit fn (warm in-process) /
    ``"uncompilable"`` for ``_mxtpu_no_jit`` ops.
    """
    key, sig = _cache_key(name, attrs, donate)
    fn = _get_compiled_keyed(key, sig, name, fcompute, attrs, donate,
                             persist_name=persist_name,
                             force_tiered=True)
    if isinstance(fn, _TieredFn):
        return fn.warm(example_args)
    return "uncompilable" if getattr(fcompute, "_mxtpu_no_jit", False) \
        else "jit"


def dispatch_count() -> int:
    """Dispatches since process start (or ``reset_counters``) — the
    cheap accessor for per-step deltas; ``cache_info()`` builds the
    whole per-op dict, which is too heavy for once-per-step reads."""
    return _dispatches


def compile_counts() -> Tuple[int, int]:
    """``(misses, fresh_compiles)`` — the cheap accessor for
    per-dispatch compile deltas (the serving plane brackets every
    steady-state dispatch with this to attribute compiles to ITS
    programs without a cache_info() walk)."""
    return _misses, _fresh_compiles


def cache_size() -> int:
    return len(_jit_cache)


def live_bytes() -> int:
    """Logical bytes of the live tracked buffers — the cheap always-on
    census form (``cache_info()["live_bytes"]``).  Donated/deleted
    buffers are skipped, the same guard :func:`waitall` applies; for
    per-device attribution use ``telemetry.memory.census()``."""
    total = 0
    for arr in live_arrays():
        try:
            if arr.is_deleted():
                continue
            total += int(arr.nbytes)
        except Exception:
            continue
    return total


def cache_info() -> dict:
    """Introspect the jit-cache, dispatch counters, and live buffers.

    Returns ``{"size", "live_buffers", "live_bytes", "engine", "ops",
    "hits", "misses", "dispatches", "memory", ...}`` where ``ops`` maps
    op name -> list of attr
    signatures (one per cached executable; ``()`` for the attr-less fast
    path).  mxlint's runtime-hazard report reads ``ops`` to surface
    cache-key blowup: one op accumulating many entries that differ only
    in a numeric attr value is the retrace-storm signature (the fix is
    usually ``scalar_attrs``).  ``dispatches`` counts invoke_compiled
    calls since process start (or :func:`reset_counters`); the fused
    optimizer step's one-dispatch contract is asserted against it.
    """
    per_op: Dict[str, list] = {}
    with _lock:
        keys = list(_jit_cache)
    for key in keys:
        if isinstance(key, str):
            per_op.setdefault(key, []).append(())
        else:
            name, attrs = key[0], key[1]  # (name, sig[, donate])
            per_op.setdefault(name, []).append(attrs)
    t = _telem if _telem is not None else _telemetry()
    return {"size": len(keys), "live_buffers": len(_live),
            "live_bytes": live_bytes(),
            "engine": "NaiveEngine" if is_naive() else "ThreadedEngine",
            "hits": _hits, "misses": _misses, "dispatches": _dispatches,
            "fresh_compiles": _fresh_compiles,
            "aot_demotions": _aot_demotions,
            "persist": {"enabled": persist.enabled(),
                        "dir": persist.cache_dir() or "",
                        **persist.counters()},
            "memory": t.memory.cache_info_block(),
            "ops": per_op}


def clear_cache(persistent: bool = False):
    """Empty the in-memory jit cache.  ``persistent=True`` also removes
    every on-disk entry in ``MXTPU_COMPILE_CACHE_DIR`` — the scope is
    explicit because the persistent tier is exactly the state meant to
    OUTLIVE a process-level reset.

    Safe around persist reloads: executables DESERIALIZED from the
    persistent tier are pinned for the life of the process
    (``persist._loaded_execs``) — on jaxlib CPU, garbage-collecting a
    deserialized sharded executable after its cache entry drops
    segfaults nondeterministically (the PR 13 CAUTION), so the entry
    eviction here never triggers their teardown.  Repeated
    ``clear_cache()`` calls are therefore safe; only the (cheap)
    Python-side cache bookkeeping is released."""
    with _lock:
        _jit_cache.clear()
    # attribution history follows the cache it describes
    with _attr_lock:
        _op_attr_sigs.clear()
        _key_avals.clear()
    if persistent:
        persist.clear()


def drop_cached(name: str, persistent: bool = False) -> int:
    """Evict every cache entry for op ``name``; returns the count.

    Exists for callers whose compiled program BAKES host state that can
    legitimately change between calls (``gluon.CompiledStep`` bakes the
    optimizer's static attrs — momentum, betas, clip bounds): when the
    baked value drifts, the stale executable must be dropped and
    rebuilt rather than silently applying the old value.  Per-name so a
    single invalidation cannot flush the whole process's warm cache.
    ``persistent=True`` extends the eviction to the on-disk tier
    (entries whose persist name starts with ``name``).
    """
    with _lock:
        stale = [k for k in _jit_cache
                 if (k == name if isinstance(k, str) else k[0] == name)]
        for k in stale:
            del _jit_cache[k]
    n_disk = persist.drop(name) if persistent else 0
    if stale or n_disk:
        t = _telem if _telem is not None else _telemetry()
        if t._switch.enabled:
            t.record_event("evict", op=name, entries=len(stale),
                           persistent=n_disk)
    return len(stale) + n_disk


def reset_counters():
    """Zero the hit/miss/dispatch/fresh-compile counters (cache entries
    untouched); the persistent tier's hit/miss/saved counters reset
    with them."""
    global _hits, _misses, _dispatches, _fresh_compiles, _aot_demotions
    with _lock:
        _hits = _misses = _dispatches = _fresh_compiles = 0
        _aot_demotions = 0
    persist.reset_counters()


def _reset_naive():
    """Forget the cached engine-type choice so the next ``is_naive()``
    re-reads the env vars — for tests that flip MXTPU_ENGINE_TYPE."""
    global _NAIVE
    _NAIVE = None


_bulk_size = 0


def set_bulk_size(size: int) -> int:
    """Parity shim for ``mx.engine.set_bulk_size``.

    XLA fuses whole graphs at the hybridize/CachedOp seam, so imperative
    bulking is a no-op; the knob is kept so user code runs unchanged.
    """
    global _bulk_size
    prev, _bulk_size = _bulk_size, size
    return prev


class bulk:
    """Parity context manager ``with mx.engine.bulk(n):`` — no-op on XLA."""

    def __init__(self, size: int):
        self.size = size

    def __enter__(self):
        self._prev = set_bulk_size(self.size)
        return self

    def __exit__(self, *exc):
        set_bulk_size(self._prev)
