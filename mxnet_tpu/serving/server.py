"""``Server``: the engine + persist integration of the serving plane.

One compiled DECODE program per ``(slots, prompt_len)`` bucket (plus
``decode_multi(K)`` lax.scan variants) and a short LADDER of compiled
PREFILL programs (``scheduler.prefill_ladder``: the bucket's
``prompt_len`` halved down to 256 positions), all dispatched through
``engine.invoke_compiled`` with the bucket's state pool DONATED.  What
a slot holds is the model's to say (``lm.state_spec``: K/V pages,
rolling windows, recurrent and conv state, any rank and dtype;
docs/serving.md, "State kinds"); the server moves the buffers and never
looks inside a layer:

* **admit** — prefill one prompt at batch 1, right-padded to the
  shortest rung of its bucket's ladder that holds it, into a batch-1
  state of that length, scatter every buffer of it into the pool at the
  assigned slot (``lax.dynamic_update_slice`` per buffer, at its own
  rank, from position 0), and sample the first token at the prompt's own
  last position — ONE dispatch per admission.  A bucket's first
  admission makes every rung's program ready (``_warm_ladder``), so no
  later one compiles;
* **decode** — every active slot advances one token in lockstep at its
  OWN absolute position (per-slot rope offsets / cache scatter /
  validity mask ride as dynamic inputs), the sampler picks
  greedy-or-temperature per slot, and the whole pool round-trips
  through donation — ONE dispatch per step, zero retraces across any
  admit/evict sequence (shapes never change);
* **decode_multi(K)** — K decode steps as one dispatch (``lax.scan``
  with the pool as carry, like ``step_multi``): one host sync per K
  tokens instead of per token.

The host stays ONE decode dispatch ahead of its reads
(docs/serving.md, "A round"): a slot's last token lives in the pool, on
the device, where the next decode finds it, so a round enqueues its
prefills and its decodes first and only then reads what the device owes
it — the previous round's decode tokens and this round's first tokens.
What the host needs before a dispatch it knows from COUNTS (offsets, the
active mask, whose budget is spent); what only a token's VALUE tells
(an ``eos_id``) it learns one dispatch late, and drops the one token
decoded past the end.

Sampling is greedy at ``temperature == 0`` and softmax sampling with
optional server-wide top-k truncation otherwise; the RNG threads the
CachedOp fold_in scheme — ONE base key a server, drawn from the global
stream and resident on the device, INPUT to every dispatch beside a
dispatch counter that the program folds into it, then folded per inner
step and per slot (nothing is made on the host before a dispatch, and
keys never retrace).

``save_signature``/``warm_start`` extend the PR 5 AOT warm-start
machinery to serving: a fresh process precompiles every recorded
bucket variant through ``engine.aot_compile`` + the persistent tier
and serves its FIRST token with 0 fresh compiles.

Failure protocol (docs/elasticity.md applied to serving): the engine's
bounded transient retry covers pre-donation hiccups; a dispatch that
fails AFTER consuming the donated pool poisons the bucket, and
``recover()`` rebuilds zeroed pages and requeues every resident
request (prompts are host-owned, so they replay from scratch).
"""
from __future__ import annotations

import hashlib
import itertools
import json
import os
import threading
import time
import weakref
from collections import deque
from typing import Dict, List, Optional

import numpy as np

from ..base import MXNetError
from ..profiler import device_scope as _device_scope, span as _span
from .kvcache import KVCachePool, check_spec
from .scheduler import ACTIVE, BucketScheduler, Request

__all__ = ["Server", "servers"]

_uid = itertools.count(1)

# live-server registry read by mxlint's serving runtime pass
# (``analysis.analyze_serving`` — MXL601's runtime twin)
_reg_lock = threading.Lock()
_servers: "weakref.WeakValueDictionary[int, Server]" = \
    weakref.WeakValueDictionary()


def servers() -> List["Server"]:
    with _reg_lock:
        return [s for s in _servers.values()]


def _reset_registry():
    """Test hook."""
    with _reg_lock:
        _servers.clear()


# inputs of a bucket program after the params and the pool (the state,
# then the slots' last tokens): the kind's own (prefill: prompt,
# last_pos, slot, temp; decode: off, active, temp), then the resident
# base key and the dispatch counter
_N_INPUTS = {"prefill": 6, "decode": 5}


class _Owed:
    """One dispatch whose tokens the host has not read yet: ``out`` is
    the program's un-donated output, ``take`` the ``(column, request,
    n)`` rows that say whose tokens it holds (``n`` of its ``k`` steps,
    the rest were decoded past the request's budget).  ``variant`` is
    the program's own ``k`` (``Server._suffix``: a multi-step decode's
    steps, a prefill's rung, 0 for the plain program), which names it."""

    __slots__ = ("kind", "bucket", "out", "k", "take", "t0", "ids",
                 "variant")

    def __init__(self, kind, bucket, out, k, take, t0, ids, variant):
        self.kind, self.bucket, self.out, self.k = kind, bucket, out, k
        self.take, self.t0, self.ids = take, t0, ids
        self.variant = variant


def _dispatch_key(key_raw, counter):
    """This dispatch's key (traced): the counter folded into the base
    key.  Rows and scanned steps fold their index into it in turn."""
    import jax
    return jax.random.fold_in(jax.random.wrap_key_data(key_raw), counter)


def _default_buckets():
    from .. import envs
    slots = int(envs.get("MXTPU_SERVING_SLOTS"))
    lens = [int(x) for x in
            str(envs.get("MXTPU_SERVING_BUCKETS")).split(",") if x.strip()]
    return [(slots, n) for n in lens]


class Server:
    """Continuously batched serving over a model-zoo decoder: anything
    exposing ``state_spec``/``prefill``/``decode_step`` over a flat state
    list and ``model.vocab_size`` (``LlamaForCausalLM``,
    ``SambaYForCausalLM``, ``AfmoeForCausalLM``,
    ``PanguMoeForCausalLM``).  A model may also
    declare ``statistics`` (docs/serving.md, "Model statistics"):
    ``(counter, help)`` rows whose per-call counts, left by ``prefill`` /
    ``decode_step`` in ``lm.last_statistics``, ride out of every program
    beside its tokens and are added to those counters at the read; what
    the model leaves BEHIND those counts (what each row of the call
    chose) rides out with them and goes, with the counts, to
    ``statistics_listener`` if one is set.

    Args:
      lm: initialized causal LM.
      buckets: ``[(slots, prompt_len), ...]`` shape classes (defaults
        from ``MXTPU_SERVING_SLOTS`` x ``MXTPU_SERVING_BUCKETS``).
      max_new_tokens: per-request generation cap (sizes the cache
        pages: ``cache_len = prompt_len + max_new_tokens``); defaults
        to ``MXTPU_SERVING_MAX_NEW_TOKENS``.
      top_k: server-wide top-k truncation for sampled requests (shapes
        the compiled sampler; 0 = full softmax).
      eos_id: stop token (None = run to the token budget).
      ctx: device context; default current.
      cache_dtype: KV page dtype (``bfloat16`` halves page HBM).
      max_queue: wait-queue bound (``MXTPU_SERVING_MAX_QUEUE``).
    """

    def __init__(self, lm, buckets=None, max_new_tokens: int = None,
                 top_k: int = 0, eos_id: Optional[int] = None,
                 ctx=None, cache_dtype: str = "float32",
                 max_queue: Optional[int] = None, plan=None):
        from .. import envs
        from ..context import current_context
        from ..parallel import planner as _planner
        self.lm = lm
        self.ctx = ctx or current_context()
        # the sharding planner's serving leg (docs/parallelism.md):
        # plan.decode is the KV-page / decode-batch partition spec on
        # the plan's named mesh — pinned into the struct hash and the
        # warm-start manifest, and APPLIED to the pools/params when it
        # actually shards (>1 device on the named axes)
        if plan is not None and \
                not isinstance(plan, _planner.ShardingPlan):
            raise MXNetError(
                f"plan= must be a parallel.ShardingPlan, got "
                f"{type(plan).__name__}")
        self.plan = plan
        self._decode_sharding = None
        self._repl_sharding = None
        self._placed_params = None
        if plan is not None and plan.decode_shards():
            import jax
            from jax.sharding import NamedSharding, PartitionSpec as P
            mesh = plan.build_mesh()
            self._decode_sharding = NamedSharding(mesh,
                                                  P(*plan.decode))
            self._repl_sharding = NamedSharding(mesh, P())
        if max_new_tokens is None:
            max_new_tokens = int(envs.get("MXTPU_SERVING_MAX_NEW_TOKENS"))
        if max_queue is None:
            max_queue = int(envs.get("MXTPU_SERVING_MAX_QUEUE"))
        self.max_new_tokens = int(max_new_tokens)
        self.eos_id = None if eos_id is None else int(eos_id)
        self.cache_dtype = str(cache_dtype)
        vocab = int(lm.model.vocab_size)
        self._kk = min(int(top_k), vocab) if top_k else 0
        self.sched = BucketScheduler(buckets or _default_buckets(),
                                     self.max_new_tokens, max_queue)
        try:
            self._param_nds = [p.data(self.ctx)
                               for p in lm.collect_params().values()]
        except Exception as e:
            raise MXNetError(
                "Server needs an initialized model (run initialize() "
                f"and one forward first): {e!r}") from e
        self.name = f"serving_{lm.name}_{next(_uid)}"
        # what the model's programs count beside their tokens
        self._stat_rows = tuple(
            (str(n), str(h)) for n, h in getattr(lm, "statistics", ()))
        #: ``fn(kind, columns, counts, rows)``, called at the read of
        #: every dispatch of a model that declares ``statistics``
        self.statistics_listener = None
        if self._decode_sharding is not None:
            # the slot dim is the decode spec's leading entry: every
            # bucket's slot count must divide its device fan-out, or
            # the planned layout is unbuildable — reject NAMING the
            # spec instead of letting XLA pad silently
            fan = plan.decode_fanout()
            for b in self.sched.buckets:
                if fan > 1 and b.slots % fan:
                    raise MXNetError(
                        f"plan decode spec {plan.decode} shards the "
                        f"slot dim {fan}-way but bucket "
                        f"{b.slots}x{b.prompt_len} has {b.slots} "
                        "slot(s); pick slot counts divisible by the "
                        "decode axis size")
            import jax
            self._placed_params = [
                jax.device_put(p._data, self._repl_sharding)
                for p in self._param_nds]
        self._pools: Dict[tuple, KVCachePool] = {}
        for b in self.sched.buckets:
            self._pools[b.key] = KVCachePool(
                lm, b.slots, b.cache_len, ctx=self.ctx,
                dtype=self.cache_dtype,
                sharding=self._decode_sharding)
        self._state_gauges()
        if plan is not None:
            # the planner registry (MXL313 coverage audit + mxplan):
            # the serving leg registers its resolved param tree too
            from ..parallel import planner as _pl
            _pl.note_plan(
                f"serving:{lm.name}", plan,
                [(p.name, tuple(int(x) for x in p.data(self.ctx).shape))
                 for p in lm.collect_params().values()])
        self._pure_cache: Dict[str, callable] = {}
        self._variants: Dict[str, dict] = {}   # suffix -> manifest row
        self._warmed: set = set()              # suffixes dispatched
        self._bucket_stats: Dict[tuple, dict] = {
            b.key: {"steady_dispatches": 0, "tokens": 0,
                    "steady_misses": 0, "steady_fresh_compiles": 0}
            for b in self.sched.buckets}
        self._poisoned: Optional[str] = None
        # dispatches whose tokens are still on the device, oldest first:
        # between rounds at most one decode a bucket (docs/serving.md,
        # "A round"), and when the last of them was read
        self._owed: deque = deque()
        self._read_mark = 0.0
        # the sampler's base key (raw data, on the device), the
        # random._seed_epoch it was drawn under, and the dispatches
        # since: _rng_inputs
        self._key_base = None
        self._key_epoch = None
        self._key_counter = 0
        # calls of step() so far: the `round` id of this server's
        # profiler spans (docs/observability.md, "Spans")
        self._span_round = 0
        self.warm_started = False
        self._persist_pinned = False
        self._struct_hash = self._compute_struct_hash()
        self._persist_base = f"serving_{lm.name}_{self._struct_hash}"
        with _reg_lock:
            _servers[id(self)] = self

    # -- identity ---------------------------------------------------------
    def _compute_struct_hash(self, buckets=None) -> str:
        """Structural identity over model/bucket/sampler config.
        ``buckets``: optional ``(slots, prompt_len, cache_len)`` rows
        to hash INSTEAD of the live ones — the resize pre-warm keys
        the target configuration's persist identities while the old
        buckets still serve."""
        rows = sorted(tuple(r) for r in (
            buckets if buckets is not None else
            [(b.slots, b.prompt_len, b.cache_len)
             for b in self.sched.buckets]))
        parts = (
            tuple((tuple(p.data(self.ctx).shape),
                   str(p.data(self.ctx).dtype))
                  for p in self.lm.collect_params().values()),
            tuple(rows),
            # what a slot holds: the compiled programs' state avals
            tuple(tuple(self._spec_for(slots, cache_len))
                  for slots, _p, cache_len in rows),
            self._kk, self.cache_dtype, self.max_new_tokens,
            int(self.lm.model.vocab_size)) + (
                # the plan pin: decode sharding is baked into the
                # compiled programs' input layouts; appended only when
                # a plan exists so pre-planner hashes (and persisted
                # executables) still serve
                (self.plan.struct_hash(),)
                if self.plan is not None else ()) + (
                # the programs' first output is longer by these, and by
                # the rows the model leaves behind them: appended only
                # for a model that declares statistics
                ("counts, rows", tuple(n for n, _h in self._stat_rows))
                if self._stat_rows else ())
        return hashlib.sha256(repr(parts).encode()).hexdigest()[:16]

    def _spec_for(self, slots, cache_len):
        return check_spec(
            self.lm.state_spec(slots, cache_len, self.cache_dtype), slots)

    def _n_state(self, bucket):
        """Buffers in a bucket's pool (the spec's and the token vector),
        from the spec: a resize's shadow bucket has programs before it
        has a pool."""
        return len(self._spec_for(bucket.slots, bucket.cache_len)) + 1

    def _state_gauges(self):
        """``mxtpu_serving_state_bytes`` per bucket and state kind (the
        registry has no labels: they ride in the name)."""
        from .. import telemetry
        for b in self.sched.buckets:
            for kind, n in self._pools[b.key].bytes_by_kind().items():
                telemetry.gauge(
                    f"mxtpu_serving_state_bytes_b{b.slots}x"
                    f"{b.prompt_len}_{kind}",
                    "bytes of one bucket's state buffers of one kind"
                    ).set(n)

    # -- public API -------------------------------------------------------
    def submit(self, prompt, max_new_tokens: Optional[int] = None,
               temperature: float = 0.0,
               eos_id: Optional[int] = None,
               ttl_ms: Optional[float] = None) -> Request:
        """Queue one generation request; admission happens at the next
        :meth:`step`.  Raises ``MXNetError`` when no bucket fits the
        prompt or the queue is full (both recorded as retained
        ``slot_oom`` events).

        ``ttl_ms`` arms the overload policy (docs/serving.md,
        "Overload policy"): when the ESTIMATED queue wait — queue
        depth x the rolling per-token service rate from the decode
        histograms — already exceeds the deadline, the request is SHED
        here (state ``shed``, retained ``shed`` event,
        ``mxtpu_requests_shed_total``, and an ``MXNetError`` the
        caller turns into a fast 429) instead of growing the queue a
        request that can only expire in it."""
        from .. import telemetry
        mnt = self.max_new_tokens if max_new_tokens is None \
            else min(int(max_new_tokens), self.max_new_tokens)
        req = Request(prompt, mnt, temperature=temperature,
                      eos_id=self.eos_id if eos_id is None else eos_id,
                      ttl_ms=ttl_ms)
        if req.deadline is not None:
            est = self.estimate_queue_wait()
            budget = req.deadline - time.perf_counter()
            if est is not None and est > budget:
                from .scheduler import SHED
                req.state = SHED
                req.evict_reason = "shed"
                telemetry.counter(
                    "mxtpu_requests_shed_total",
                    "requests shed at enqueue by the overload policy"
                    ).inc()
                telemetry.record_event(
                    "shed", server=self.name, request=req.id,
                    prompt_len=req.prompt_len, ttl_ms=req.ttl_ms,
                    est_wait_s=round(est, 4),
                    queue_depth=self.sched.queue_depth())
                raise MXNetError(
                    f"request shed: estimated queue wait {est:.3f}s "
                    f"exceeds the {req.ttl_ms:g}ms deadline (queue "
                    f"depth {self.sched.queue_depth()}); retry with "
                    "backoff, raise ttl_ms, or scale the plane "
                    "(docs/serving.md, 'Overload policy')")
        try:
            self.sched.enqueue(req)
        except MXNetError as e:
            telemetry.record_event(
                "slot_oom", server=self.name, request=req.id,
                prompt_len=req.prompt_len,
                queue_depth=self.sched.queue_depth(),
                reason=str(e)[:200])
            raise
        telemetry.counter("mxtpu_serving_requests_total",
                          "requests submitted to the serving plane"
                          ).inc()
        self._update_gauges()
        return req

    # -- overload policy (docs/serving.md, "Overload policy") -------------
    def estimate_queue_wait(self) -> Optional[float]:
        """Expected seconds a request submitted NOW waits before its
        slot frees up: queue depth x tokens-per-request x the rolling
        per-token service rate, spread over the plane's slots.  The
        rate comes from the histograms the plane already keeps
        (decode wall seconds / tokens generated); ``None`` before any
        decode history exists — an un-warmed plane never sheds."""
        from .. import telemetry
        q = self.sched.queue_depth()
        if q == 0 and self.sched.occupancy() < 1.0:
            return 0.0
        dh = telemetry.histogram(
            "mxtpu_serving_decode_seconds",
            "one decode dispatch wall clock (s)").summary()
        tokens = telemetry.counter(
            "mxtpu_serving_tokens_total",
            "tokens generated by the serving plane").value
        if not dh["count"] or tokens <= 0:
            return None
        per_token_s = dh["sum"] / tokens
        slots = sum(b.slots for b in self.sched.buckets) or 1
        # every queued request ahead needs ~max_new_tokens service
        # slots-widths of decode wall time before a slot frees
        waves = (q + slots) / slots
        return waves * self.max_new_tokens * per_token_s

    def _expire_deadlines(self) -> int:
        """Evict every live request whose deadline passed (queue AND
        slots — the scheduler's existing evict path does both), with
        the ``deadline_evicted`` taxonomy on top of the standard
        ``request_evicted`` audit trail."""
        from .. import telemetry
        now = time.perf_counter()
        expired = [r for r in self.sched.active_requests()
                   + list(self.sched.queue) if r.expired(now)]
        n = 0
        for req in expired:
            waited = now - req.submit_t
            if not self.evict(req, reason="deadline", requeue=False):
                continue
            n += 1
            telemetry.counter(
                "mxtpu_deadline_evictions_total",
                "live requests evicted on an expired deadline").inc()
            telemetry.record_event(
                "deadline_evicted", server=self.name, request=req.id,
                ttl_ms=req.ttl_ms, waited_s=round(waited, 4),
                generated=len(req.generated))
        return n

    def step(self, decode_steps: int = 1) -> dict:
        """One scheduling round: admit every queued request with a free
        slot (one prefill dispatch each), advance every non-empty
        bucket by ``decode_steps`` tokens (ONE decode dispatch per
        bucket; ``decode_steps > 1`` uses the scan-bulked variant —
        one host sync per K tokens), and only then read what the device
        owes: the PREVIOUS round's decode tokens and this round's first
        tokens.  This round's decodes stay outstanding, so a token
        decoded in round n is in ``req.generated`` when round n+1
        returns (docs/serving.md, "A round").  Returns round stats;
        ``tokens`` counts the decode tokens that ARRIVED."""
        self._check_poisoned()
        self._span_round = n = self._span_round + 1
        with _span("mxtpu.serving.round", "serving", step_num=n, round=n):
            return self._round(int(decode_steps))

    def _check_poisoned(self):
        if self._poisoned is not None:
            raise MXNetError(
                "this Server's KV-cache pages were donated to a "
                "dispatch that failed and are no longer valid; call "
                "recover() to rebuild the pools and requeue resident "
                "requests (docs/serving.md). Original error: "
                f"{self._poisoned}")

    def _round(self, decode_steps: int) -> dict:
        # deadline sweep FIRST: an expired queued request must not
        # consume the slot (and the prefill dispatch) it can no longer
        # use, and an expired resident frees its slot for this round's
        # admissions
        with _span("mxtpu.serving.expire", "serving"):
            self._expire_deadlines()
        admitted = 0
        with _span("mxtpu.serving.schedule", "serving"):
            pending = self.sched.admissions()
        for i, (bucket, slot, req) in enumerate(pending):
            try:
                self._admit(bucket, slot, req)
            except Exception:
                # admissions() reserved EVERY slot up front: the
                # failed request and the ones behind it were placed
                # but never prefilled — release them back to the HEAD
                # of the queue (reverse order preserves FIFO), or a
                # retried step() would decode their zeroed pages as if
                # they held real prompts.  When the pool is POISONED,
                # recover() requeues every resident instead.
                if self._poisoned is None:
                    for _b, _s, r in reversed(pending[i:]):
                        self.sched.evict(r, reason="admit_aborted",
                                         requeue=True)
                raise
            admitted += 1
        # everything enqueued so far is due; this round's decodes go in
        # behind it and are read by the next round
        due = len(self._owed)
        for bucket in self.sched.buckets:
            if bucket.n_active() == 0:
                continue
            self._decode(bucket, decode_steps)
        tokens = self._collect(due)
        self._update_gauges()
        return {"admitted": admitted, "tokens": tokens,
                "active": len(self.sched.active_requests()),
                "queued": self.sched.queue_depth()}

    def _collect(self, n: Optional[int] = None) -> int:
        """Read the ``n`` oldest owed dispatches (default: all of them);
        returns the decode tokens that arrived."""
        tokens = 0
        for _ in range(len(self._owed) if n is None else n):
            rec = self._owed[0]
            got = self._read(rec)
            self._owed.popleft()
            if rec.kind == "decode":
                tokens += got
        return tokens

    def settle(self) -> int:
        """Read every token the device still owes (the outstanding
        decodes' included) without enqueueing anything: afterwards
        ``req.generated`` is complete and nothing is in flight.  What a
        caller does before it looks at the slots from outside a round
        (``resize_slots``, ``save_signature``, a preemption drain).
        Returns the decode tokens that arrived."""
        self._check_poisoned()
        tokens = self._collect()
        self._update_gauges()
        return tokens

    def idle(self) -> bool:
        """Nothing queued, nothing resident and no token owed: every
        submitted request has finished or was evicted."""
        return not (self._owed or self.sched.queue_depth()
                    or self.sched.active_requests())

    def awaiting(self) -> List[Request]:
        """Requests that left their slot by count and still wait for
        their last tokens, oldest first."""
        out = {}
        for rec in self._owed:
            for _col, req, _n in rec.take:
                if req.state == ACTIVE and req.bucket is None:
                    out[req.id] = req
        return list(out.values())

    def run(self, decode_steps: int = 1,
            max_rounds: Optional[int] = None) -> int:
        """Step until every submitted request finished; returns rounds
        run.  ``max_rounds`` bounds runaway loops (default: generous
        budget derived from the workload)."""
        if max_rounds is None:
            pending = len(self.sched.active_requests()) \
                + len(self.awaiting()) + self.sched.queue_depth()
            max_rounds = 16 + pending * (self.max_new_tokens + 2)
        rounds = 0
        while not self.idle():
            if rounds >= max_rounds:
                raise MXNetError(
                    f"serving run() exceeded {max_rounds} rounds with "
                    "requests still live — scheduler wedged?")
            self.step(decode_steps=decode_steps)
            rounds += 1
        return rounds

    def generate(self, prompts, max_new_tokens: Optional[int] = None,
                 temperature: float = 0.0,
                 decode_steps: int = 1) -> List[np.ndarray]:
        """Batch convenience: submit every prompt, run to drain, and
        return ``prompt + continuation`` per request (in order)."""
        reqs = [self.submit(p, max_new_tokens=max_new_tokens,
                            temperature=temperature) for p in prompts]
        self.run(decode_steps=decode_steps)
        return [r.tokens() for r in reqs]

    def evict(self, req: Request, reason: str = "user",
              requeue: bool = False) -> bool:
        """Remove a live request (slot, queue, or waiting for its last
        tokens); returns True when it was live (a request that already
        finished is left untouched — no event, no counter).  Tokens the
        device still owes it are dropped, not delivered.  Retained
        ``request_evicted`` event + counter; ``requeue=True`` restarts
        it from its prompt (the recovery path)."""
        from .. import telemetry
        if not self.sched.evict(req, reason, requeue=requeue):
            return False
        self._disown(req)
        telemetry.counter("mxtpu_serving_requests_evicted_total",
                          "requests evicted from the serving plane"
                          ).inc()
        telemetry.record_event("request_evicted", server=self.name,
                               request=req.id, reason=reason,
                               requeued=bool(requeue),
                               generated=len(req.generated))
        self._update_gauges()
        return True

    def recover(self) -> int:
        """Rebuild every poisoned (or healthy) KV-cache pool and
        requeue every request that holds a slot or is owed tokens (what
        the device owes is lost with the pool); clears the poison
        latch.  Returns the number of requests requeued.  The serving
        twin of the trainers' ``recover(manager)`` — state here is
        cache pages rebuilt by replaying host-owned prompts, so no
        checkpoint is involved."""
        from ..elastic.manager import record_recovery
        t0 = time.perf_counter()
        was_poisoned = self._poisoned is not None
        requeued = 0
        # reverse: evict(requeue=True) pushes to the queue HEAD, so
        # iterating backwards leaves them in the order they were
        # submitted in
        live = sorted(self.awaiting() + self.sched.active_requests(),
                      key=lambda r: r.id)
        self._owed.clear()
        for req in reversed(live):
            self.evict(req, reason="recover", requeue=True)
            requeued += 1
        for pool in self._pools.values():
            pool.reset()
        for b in self.sched.buckets:
            b.offsets[:] = 0.0
            b.active[:] = 0.0
            b.temps[:] = 0.0
        self._poisoned = None
        record_recovery("serving", time.perf_counter() - t0,
                        was_poisoned, name=self.name,
                        requeued=requeued)
        return requeued

    # -- live resize (docs/elasticity.md, "Live resize" — serving leg) ----
    def _fresh_bucket_stats(self):
        return {b.key: {"steady_dispatches": 0, "tokens": 0,
                        "steady_misses": 0, "steady_fresh_compiles": 0}
                for b in self.sched.buckets}

    def resize_slots(self, new_slots: int,
                     reason: Optional[str] = None) -> dict:
        """Grow/shrink every bucket's slot count IN-JOB through the
        same prewarm -> drain -> migrate -> swap protocol the train
        plane's ``ResizeController`` runs (``elastic.resize``;
        typically driven by its ``ServingAutoscaler`` off the
        queue-depth/occupancy signals).

        * **prewarm** — every recorded bucket variant is AOT-compiled
          for the new slot count (``engine.aot_compile`` + the
          persistent tier) BEFORE anything moves, so the first
          post-swap dispatch is already steady state with 0 fresh
          compiles (the variants land pre-warmed in the steady
          accounting MXL601 audits).  Compile time is not downtime —
          the old buckets could still serve here.
        * **drain** — between rounds one decode a bucket is in flight:
          its tokens are read here (:meth:`settle`), after which
          nothing is; this is the settled boundary (fault point
          ``resize_drain``) and where the downtime clock starts.
        * **migrate** — resident state gathers into the new pool by
          slot index (one ``take`` per pool buffer, the slots' last
          tokens among them; generated tokens/offsets are host-owned
          and ride along), so live requests keep their progress.
          On a shrink, residents beyond the new capacity are
          evicted-with-requeue (they replay from their host-owned
          prompts — the documented recovery semantics).
        * **swap** — buckets/pools/identities rebind; a failure after
          migration started crash-heals onto the NEW slot count with
          zeroed pages and every resident requeued (``recovery``
          telemetry), so the plane is never left unroutable.

        Returns the registry record (``elastic.resize.resizes``)."""
        from .. import engine
        from ..elastic import faults as _faults
        from ..elastic import resize as _resize
        from ..elastic.manager import record_recovery
        import jax.numpy as jnp

        new_slots = int(new_slots)
        if new_slots < 1:
            raise MXNetError(f"resize_slots: need >= 1, got {new_slots}")
        if self._decode_sharding is not None:
            fan = self.plan.decode_fanout()
            if fan > 1 and new_slots % fan:
                raise MXNetError(
                    f"resize_slots: {new_slots} slot(s) do not divide "
                    f"the plan's decode fan-out {fan} "
                    f"({self.plan.decode}); pick a multiple")
        if self._poisoned is not None:
            raise MXNetError("server is poisoned; recover() before "
                             "resizing")
        old_counts = sorted({b.slots for b in self.sched.buckets})
        if old_counts == [new_slots]:
            raise MXNetError(
                f"resize_slots: already at {new_slots} slots")
        # a heterogeneous construction (per-bucket slot counts)
        # uniformizes on its first resize; the record keeps the real
        # before-state so slots_from never misreports a smaller bucket
        old_slots = old_counts[0] if len(old_counts) == 1 \
            else old_counts

        phase = "prewarm"
        try:
            # PREWARM: compile the new-slot programs while the old
            # buckets could still serve — a failure here leaves the
            # server untouched on the old configuration (same phase
            # order as the train controller: the downtime clock must
            # not start until the compiles are paid)
            _faults.maybe_fire("resize_prewarm")
            new_rows = [(new_slots, b.prompt_len, b.cache_len)
                        for b in self.sched.buckets]
            new_hash = self._compute_struct_hash(buckets=new_rows)
            new_base = f"serving_{self.lm.name}_{new_hash}"
            P = len(self._param_nds)
            shadow = {b.key: b.resized(new_slots)
                      for b in self.sched.buckets}
            import jax
            prewarmed: Dict[str, dict] = {}
            for suffix, v in sorted(self._variants.items()):
                b = self._bucket_for_suffix(suffix)
                if b is None:
                    continue
                nb = shadow[b.key]
                kind, k = str(v["kind"]), int(v.get("k") or 0)
                NS = self._pools[b.key].num_buffers
                avals = list(engine.persist.sig_from_json(v["avals"]))
                for i, a in enumerate(avals):
                    # the slot dim is dim 0 of every pool buffer and —
                    # for decode — of the 3 per-slot extras (off/
                    # active/temp); everything else (params, prefill
                    # extras, the RNG key and counter) is
                    # slot-count-independent
                    per_slot = (P <= i < P + NS) or (
                        kind == "decode" and
                        P + NS <= i < P + NS + 3)
                    if per_slot and len(a) == 2 and a[0]:
                        avals[i] = ((new_slots,) + tuple(a[0][1:]),
                                    a[1])
                sds = [jax.ShapeDtypeStruct(a[0], np.dtype(a[1]))
                       for a in avals]
                new_suffix = self._suffix(nb, kind, k)
                pure = self._pure_for(nb, kind, k)
                engine.aot_compile(
                    self.name + new_suffix, pure, {}, sds,
                    donate=tuple(int(i) for i in v["donate"]),
                    persist_name=new_base + new_suffix)
                prewarmed[new_suffix] = {
                    "suffix": new_suffix, "kind": kind, "k": k,
                    "donate": [int(i) for i in v["donate"]],
                    "avals": engine.persist.sig_to_json(tuple(avals))}
            # DRAIN: read what the device owes, after which nothing is
            # in flight; the downtime clock starts here — after the
            # pre-warm, whose compile time is NOT downtime
            phase = "drain"
            _faults.maybe_fire("resize_drain")
            t_drain = time.perf_counter()
            self.settle()
        except Exception as e:
            # pre-migration failure: the server is untouched on the
            # old configuration — record the abort (the train
            # controller does the same for its pre-drain phases)
            _resize._note_failed("serving", phase, repr(e),
                                 name=self.name,
                                 still_on="old_config")
            raise

        healed = False
        heal_error = None
        migrated = 0
        requeued = 0
        try:
            # MIGRATE: resident pages gather into the new pools
            _faults.maybe_fire("resize_reshard")
            new_pools: Dict[tuple, KVCachePool] = {}
            new_buckets = []
            for b in list(self.sched.buckets):
                nb = shadow[b.key]
                residents = [(j, r) for j, r in enumerate(b.requests)
                             if r is not None]
                kept = residents[:new_slots]
                for _j, r in reversed(residents[new_slots:]):
                    self.evict(r, reason="resize_shrink", requeue=True)
                    requeued += 1
                npool = KVCachePool(self.lm, new_slots, b.cache_len,
                                    ctx=self.ctx,
                                    dtype=self.cache_dtype,
                                    sharding=self._decode_sharding)
                if kept:
                    idx = np.zeros((new_slots,), np.int32)
                    for j2, (j, _r) in enumerate(kept):
                        idx[j2] = j
                    flat = self._pools[b.key].flat()
                    if _faults._active:
                        # the donate-tuple discipline: every source
                        # page IS consumed by the move (deleted as the
                        # successors land), so the pre-filtered form
                        # is the whole pool
                        _faults.on_dispatch("serving_resize_migrate",
                                            flat, donate=None)
                    jidx = jnp.asarray(idx)
                    moved = [jnp.take(c, jidx, axis=0) for c in flat]
                    if self._decode_sharding is not None:
                        # adopt() bypasses _build_pages, so the plan's
                        # decode layout must be re-applied here or the
                        # migrated pages land wherever jnp.take put
                        # them (kvcache's "every page build" promise)
                        import jax as _jax
                        moved = [_jax.device_put(
                            m, self._decode_sharding) for m in moved]
                    # integrity audit (docs/elasticity.md, "Integrity
                    # sentry"): every migrated resident's K/V pages
                    # must checksum-match their source slot — a page
                    # corrupted in flight (or rotten in the source
                    # pool) raises HERE, which lands in the
                    # crash-heal below: the resident replays loudly
                    # from its host-owned prompt instead of decoding
                    # garbage on the new pool.  Gated like every
                    # other leg of the sentry (MXTPU_INTEGRITY=0
                    # skips it): the per-page host readbacks sit
                    # inside the measured migrate window
                    from ..elastic import integrity as _integrity
                    if _integrity.enabled():
                        for j2, (j, r) in enumerate(kept):
                            for ci, c in enumerate(flat):
                                if _integrity.page_checksum(c[j]) != \
                                        _integrity.page_checksum(
                                            moved[ci][j2]):
                                    raise MXNetError(
                                        f"KV-page checksum mismatch "
                                        f"migrating request {r.id} "
                                        f"slot {j}->{j2} (page "
                                        f"tensor {ci}): corrupt "
                                        "resident page; the request "
                                        "will be requeued and "
                                        "replayed")
                    npool.adopt(moved)
                    for c in flat:
                        try:
                            c.delete()
                        except Exception:
                            pass
                    migrated += len(kept)
                for j2, (j, _r) in enumerate(kept):
                    nb.adopt_slot(b, j, j2)
                new_pools[nb.key] = npool
                new_buckets.append(nb)
            # SWAP: rebind buckets/pools/identities
            _faults.maybe_fire("resize_swap")
            self.sched.buckets = sorted(new_buckets,
                                        key=lambda x: x.prompt_len)
            self._pools = new_pools
        except Exception as e:
            # crash-heal: cleanly on the NEW slot count with zeroed
            # pages and every resident requeued (prompts are
            # host-owned — the replay path recover() already proves)
            heal_error = repr(e)
            _resize._note_failed("serving", "reshard_swap", heal_error,
                                 name=self.name, heal="requeue_replay")
            t_heal = time.perf_counter()
            # `requeued` keeps the shrink-overflow evictions that
            # already landed in the queue before the fault — the
            # heal's sweep only finds the residents still in bucket
            # tables, and the record must count BOTH.
            # the OLD bucket tables still list every resident —
            # adopt_slot deliberately leaves the source row in place
            # until the swap commits, exactly so this sweep can find
            # requests mid-migration (their .bucket may already point
            # at a shadow bucket; evict releases through it)
            for b in list(self.sched.buckets):
                for r in reversed([r for r in b.requests
                                   if r is not None]):
                    # through Server.evict, not the bare scheduler:
                    # heal evictions must leave the same audit trail
                    # (retained request_evicted event + counter) as
                    # every other eviction — the failure path is where
                    # the flight recorder matters most
                    if self.evict(r, reason="resize_heal",
                                  requeue=True):
                        requeued += 1
            self.sched.buckets = sorted(
                (b.resized(new_slots) for b in shadow.values()),
                key=lambda x: x.prompt_len)
            self._pools = {
                b.key: KVCachePool(self.lm, new_slots, b.cache_len,
                                   ctx=self.ctx,
                                   dtype=self.cache_dtype,
                                   sharding=self._decode_sharding)
                for b in self.sched.buckets}
            self._poisoned = None
            migrated = 0
            healed = True
            record_recovery("resize_heal",
                            time.perf_counter() - t_heal, False,
                            name=self.name, requeued=requeued)
        self._bucket_stats = self._fresh_bucket_stats()
        # rows for buckets that no longer exist would make a later
        # save_signature manifest un-warm-startable; the current
        # configuration's prewarmed rows replace them, and the
        # variants are warm NOW — their first live dispatch is
        # already steady state (same rule as warm_start)
        self._variants = dict(prewarmed)
        self._warmed.update(prewarmed)
        self._struct_hash = new_hash
        self._persist_base = new_base
        self._persist_pinned = False
        self._state_gauges()
        rec = {
            "kind": "serving", "name": self.name,
            "slots_from": old_slots, "slots_to": new_slots,
            "buckets": [f"{b.slots}x{b.prompt_len}"
                        for b in self.sched.buckets],
            "prewarmed_variants": len(prewarmed),
            "migrated": migrated, "requeued": requeued,
            "healed": healed,
            "downtime_seconds": round(
                time.perf_counter() - t_drain, 4),
        }
        if reason:
            rec["autoscale_reason"] = reason
        if heal_error:
            rec["heal_error"] = heal_error[:300]
        _resize._note_completed(rec)
        self._update_gauges()
        return dict(rec)

    def stats(self) -> dict:
        """Live occupancy/queue stats plus per-bucket steady-state
        compile accounting (what ``analyze_serving`` reads): every
        dispatch of an already-warmed variant is bracketed with
        ``engine.compile_counts()``, so a nonzero
        ``steady_misses``/``steady_fresh_compiles`` means THIS bucket's
        programs kept compiling after they existed — the retrace
        signature continuous batching exists to prevent."""
        out = {"name": self.name, "occupancy": self.sched.occupancy(),
               "queue_depth": self.sched.queue_depth(),
               "poisoned": self._poisoned is not None,
               "warm_started": self.warm_started, "buckets": {}}
        for b in self.sched.buckets:
            out["buckets"][f"{b.slots}x{b.prompt_len}"] = \
                dict(self._bucket_stats[b.key])
        return out

    # -- AOT warm start (docs/compile_cache.md, serving leg) --------------
    def save_signature(self, path: str) -> str:
        """Write the serving warm-start manifest: every dispatched
        bucket variant's avals + donation layout + the persistent-tier
        identity.  A fresh process (same model/bucket construction)
        feeds it to :meth:`warm_start` to precompile the whole plane
        before the first request."""
        from .. import engine
        if not self._variants:
            raise MXNetError(
                "save_signature: serve at least one request first "
                "(no compiled variants recorded)")
        # a dispatch that failed on the device says so at its read: no
        # manifest of programs whose last run is still unread
        self.settle()
        manifest = {
            "format": 1, "kind": "mxtpu_serving_plane",
            "fingerprint": engine.persist.fingerprint(),
            # the canonical plan pin (docs/parallelism.md): None for
            # plan-less servers, so pre-planner manifests still serve
            "plan": self.plan.to_record() if self.plan is not None
            else None,
            "net": self.lm.name,
            "persist_base": self._persist_base,
            "struct_hash": self._struct_hash,
            "max_new_tokens": self.max_new_tokens,
            "top_k": self._kk, "cache_dtype": self.cache_dtype,
            "buckets": [
                {"slots": b.slots, "prompt_len": b.prompt_len,
                 "cache_len": b.cache_len,
                 "state": [list(r) for r in self._pools[b.key].spec]}
                for b in self.sched.buckets],
            "variants": [self._variants[k]
                         for k in sorted(self._variants)],
        }
        tmp = path + f".tmp{os.getpid()}"
        try:
            with open(tmp, "w") as f:
                json.dump(manifest, f, indent=1, sort_keys=True)
            os.replace(tmp, path)
        except BaseException:
            try:
                os.remove(tmp)  # a failed write must not leak .tmp*
            except OSError:
                pass
            raise
        return path

    def warm_start(self, path: str) -> bool:
        """Precompile every variant a :meth:`save_signature` manifest
        records — persistent-tier reload when the cache dir holds the
        executables, fresh AOT compile otherwise — so the first
        request is served with 0 fresh compiles.  Never raises for a
        bad/mismatched manifest: returns False (with a ``warm_start``
        telemetry event carrying the reason) and the plane compiles on
        first use as it always did."""
        from .. import engine, telemetry

        def _fail(reason):
            telemetry.record_event("warm_start", name=self.name,
                                   ok=False, reason=reason)
            return False

        try:
            with open(path) as f:
                m = json.load(f)
        except (OSError, ValueError) as e:
            return _fail(f"unreadable manifest: {e!r}"[:300])
        if m.get("kind") != "mxtpu_serving_plane" or \
                m.get("format") != 1:
            return _fail("not an mxtpu_serving_plane manifest")
        if m.get("fingerprint") != engine.persist.fingerprint():
            return _fail("environment fingerprint mismatch "
                         "(jax/jaxlib/platform/salt)")
        # the plan pin is compared FIRST and by field, so a rejection
        # names the exact diverging rule/field instead of the opaque
        # struct hash (fail-open: cold compile, never a crash)
        from ..parallel import planner as _planner
        plan_diff = _planner.diff_records(
            m.get("plan"),
            self.plan.to_record() if self.plan is not None else None)
        if plan_diff is not None:
            return _fail(f"sharding-plan mismatch: {plan_diff}")
        if m.get("struct_hash") != self._struct_hash:
            return _fail("structural hash mismatch: the manifest "
                         "describes a different model/bucket/sampler "
                         "configuration")
        want = sorted((b["slots"], b["prompt_len"], b["cache_len"])
                      for b in m.get("buckets", ()))
        have = sorted((b.slots, b.prompt_len, b.cache_len)
                      for b in self.sched.buckets)
        if want != have:
            return _fail(f"bucket mismatch: manifest {want} vs "
                         f"configured {have}")
        for row in m.get("buckets", ()):
            pool = self._pools[(row["slots"], row["prompt_len"])]
            mine = json.loads(json.dumps(pool.spec))    # tuples -> lists
            theirs = row.get("state") or []
            if theirs != mine:
                a, b = next(ab for ab in itertools.zip_longest(theirs, mine)
                            if ab[0] != ab[1])
                return _fail(
                    f"state spec mismatch in bucket {row['slots']}x"
                    f"{row['prompt_len']}: manifest {a} vs configured {b}")
        if self._poisoned is not None:
            return _fail("server is poisoned")
        for v in m.get("variants", ()):
            bucket = self._bucket_for_suffix(str(v.get("suffix")))
            if bucket is None:
                continue                    # named and refused below
            P, NS = len(self._param_nds), self._n_state(bucket)
            have = (len(v.get("avals", ())),
                    [int(i) for i in v.get("donate", ())])
            want = (P + NS + _N_INPUTS.get(str(v.get("kind")), 0),
                    list(range(P, P + NS)))
            if have != want:
                # a manifest of an older call shape: its programs are
                # ones no dispatch calls any more, so none is
                # pre-compiled
                return _fail(
                    f"variant {v.get('suffix')!r} records {have[0]} "
                    f"inputs, {len(have[1])} of them donated, where the "
                    f"program takes {want[0]} and donates {NS}: the "
                    "slots' last tokens are a buffer of the pool now "
                    "(`last_token`, after the state: written by the "
                    "programs, donated with it), and the RNG key input "
                    "is the server's resident base key and a dispatch "
                    "counter (re-save the signature)")
        try:
            import jax
            self._persist_base = m["persist_base"]
            self._persist_pinned = True
            sources = {}
            for v in m.get("variants", ()):
                suffix = str(v["suffix"])
                bucket = self._bucket_for_suffix(suffix)
                if bucket is None:
                    return _fail(f"variant {suffix!r} names no "
                                 "configured bucket")
                pure = self._pure_for(bucket, str(v["kind"]),
                                      int(v.get("k") or 0))
                sds = [jax.ShapeDtypeStruct(a[0], np.dtype(a[1]))
                       for a in engine.persist.sig_from_json(v["avals"])]
                name = self.name + suffix
                sources[name] = engine.aot_compile(
                    name, pure, {}, sds,
                    donate=tuple(int(i) for i in v["donate"]),
                    persist_name=self._persist_base + suffix)
                self._variants[suffix] = v
                # the variant is warm NOW: its first live dispatch is
                # already steady state, so a fresh compile there (a
                # corrupt/evicted persist entry, aval drift from the
                # manifest) lands in the steady accounting instead of
                # hiding as "first dispatch pays its compile"
                self._warmed.add(suffix)
            if not sources:
                return _fail("manifest has no compiled variants")
        except Exception as e:
            return _fail(f"warm-start failed: {e!r}"[:300])
        self.warm_started = True
        telemetry.record_event("warm_start", name=self.name, ok=True,
                               sources=sources)
        return True

    # -- program builders --------------------------------------------------
    def _suffix(self, bucket, kind: str, k: int = 0) -> str:
        """A bucket program's name after the server's: ``k`` is what
        tells the variants of one kind apart, the steps of a multi-step
        decode or the rung of a prefill shorter than the bucket's
        ``prompt_len`` (:meth:`_rung_k`); the plain programs have 0."""
        return f"_b{bucket.slots}x{bucket.prompt_len}_{kind}" + \
            (f"{k}" if k else "")

    @staticmethod
    def _rung_k(bucket, rung: int) -> int:
        """The ``k`` of the prefill program of ``rung``: the full rung
        keeps the name a bucket's one prefill program always had."""
        return 0 if rung == bucket.prompt_len else int(rung)

    def _bucket_for_suffix(self, suffix: str):
        for b in self.sched.buckets:
            if suffix.startswith(f"_b{b.slots}x{b.prompt_len}_"):
                return b
        return None

    def _pure_for(self, bucket, kind: str, k: int = 0):
        key = self._suffix(bucket, kind, k)
        fn = self._pure_cache.get(key)
        if fn is None:
            if kind == "prefill":
                fn = self._make_prefill(bucket, k or bucket.prompt_len)
            elif kind == "decode" and not k:
                fn = self._make_decode(bucket)
            elif kind == "decode" and k:
                fn = self._make_decode_multi(bucket, k)
            else:
                raise MXNetError(f"unknown serving variant {kind!r}")
            # the compiled module's name: a device trace then reads
            # ``jit_decode_b48x256`` where two buckets' programs were
            # both ``jit_decode_pure``
            fn.__name__ = f"{kind}_b{bucket.slots}x{bucket.prompt_len}" \
                + (f"k{k}" if k else "")
            self._pure_cache[key] = fn
        return fn

    def _pick(self, logits, temp, active, keys, vmapped=True):
        """Greedy + temperature/top-k sampler (traced): per-row pick of
        ``argmax`` (temp == 0) or categorical over the truncated,
        temperature-scaled logits."""
        import jax
        import jax.numpy as jnp
        from jax import lax
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.float32)
        lg = logits.astype(jnp.float32) / \
            jnp.maximum(temp[:, None], 1e-6)
        if self._kk:
            kth = lax.top_k(lg, self._kk)[0][:, -1:]
            lg = jnp.where(lg < kth, -jnp.inf, lg)
        sampled = jax.vmap(jax.random.categorical)(keys, lg) \
            .astype(jnp.float32)
        nxt = jnp.where(temp > 0, sampled, greedy)
        return jnp.where(active > 0, nxt, jnp.zeros_like(nxt))

    def _counted(self):
        """What the model's last ``prefill`` / ``decode_step`` left in
        ``last_statistics`` (traced), flat, in float32 (exact under
        2**24): one count a row of ``statistics``, then whatever arrays
        of per-row integers the model put behind them; empty for a model
        that declares none."""
        import jax.numpy as jnp
        return jnp.concatenate(
            [jnp.asarray(c._data, jnp.float32).reshape(-1)
             for c in self.lm.last_statistics]) \
            if self._stat_rows else jnp.zeros((0,), jnp.float32)

    def _tokens_out(self, toks, counted):
        """A program's first output: its tokens, and behind them what
        the model counted, in ONE array that ONE read brings back."""
        import jax.numpy as jnp
        if not self._stat_rows:
            return toks
        return jnp.concatenate([toks.reshape(-1), counted])

    def _make_decode(self, bucket):
        lm, ctx = self.lm, self.ctx
        params = self._param_nds
        P, NS = len(params), self._n_state(bucket)
        N = bucket.slots

        def decode_pure(*flat):
            import jax
            import jax.numpy as jnp
            from ..gluon import block as block_mod
            from ..ndarray.ndarray import NDArray
            param_vals = list(flat[:P])
            # the pool's last buffer is the slots' last tokens: this
            # step's input, wherever the host is with its reads
            tok = flat[P + NS - 1]
            off, active, temp, key_raw, counter = flat[P + NS:]
            with block_mod.tracing_scope(params, param_vals):
                shells = [NDArray(c, ctx=ctx)
                          for c in flat[P:P + NS - 1]]
                logits = lm.decode_step(
                    NDArray(tok, ctx=ctx), shells,
                    NDArray(off, ctx=ctx))._data
                new_caches = tuple(s._data for s in shells)
            with _device_scope("mxtpu.serving.pick"):
                counted = self._counted()
                k0 = _dispatch_key(key_raw, counter)
                keys = jax.vmap(lambda i: jax.random.fold_in(k0, i))(
                    jnp.arange(N))
                nxt = self._pick(logits, temp, active, keys)
                # the tokens twice: an output of their own that the host
                # may read late, and the pool's successor
                return (self._tokens_out(nxt, counted),) + new_caches \
                    + (nxt.reshape(N, 1),)

        return decode_pure

    def _make_decode_multi(self, bucket, k_steps: int):
        lm, ctx = self.lm, self.ctx
        params = self._param_nds
        P, NS = len(params), self._n_state(bucket)
        N = bucket.slots

        def decode_multi_pure(*flat):
            import jax
            import jax.numpy as jnp
            from jax import lax
            from ..gluon import block as block_mod
            from ..ndarray.ndarray import NDArray
            param_vals = list(flat[:P])
            cache_vals = tuple(flat[P:P + NS - 1])
            tok = flat[P + NS - 1]
            off, active, temp, key_raw, counter = flat[P + NS:]
            with _device_scope("mxtpu.serving.pick"):
                k0 = _dispatch_key(key_raw, counter)

            def body(carry, step_i):
                tok_c, off_c, caches = carry
                with block_mod.tracing_scope(params, param_vals):
                    shells = [NDArray(c, ctx=ctx) for c in caches]
                    logits = lm.decode_step(
                        NDArray(tok_c, ctx=ctx), shells,
                        NDArray(off_c, ctx=ctx))._data
                    new_caches = tuple(s._data for s in shells)
                with _device_scope("mxtpu.serving.pick"):
                    counted = self._counted()
                    k_step = jax.random.fold_in(k0, step_i)
                    keys = jax.vmap(
                        lambda i: jax.random.fold_in(k_step, i))(
                        jnp.arange(N))
                    nxt = self._pick(logits, temp, active, keys)
                    # inactive slots hold position (offset AND token),
                    # so the in-graph carry matches the host's
                    # bookkeeping
                    return (nxt.reshape(N, 1), off_c + active,
                            new_caches), (nxt, counted)

            (tok_f, _, caches_f), (toks, counted) = lax.scan(
                body, (tok, off, cache_vals),
                jnp.arange(k_steps))
            with _device_scope("mxtpu.serving.pick"):
                # toks: (K, N); the K steps' counts add up, their rows
                # follow one another
                n = len(self._stat_rows)
                counted = jnp.concatenate([counted[:, :n].sum(axis=0),
                                           counted[:, n:].reshape(-1)])
                return (self._tokens_out(toks, counted),) \
                    + caches_f + (tok_f,)

        return decode_multi_pure

    def _make_prefill(self, bucket, rung: int):
        lm, ctx = self.lm, self.ctx
        params = self._param_nds
        P, NS = len(params), self._n_state(bucket)
        # the batch-1 state a prompt padded to ``rung`` prefills into;
        # it lands in the slot's buffers from position 0, and what lies
        # past it there keeps the last tenant's values, which the
        # per-row validity mask never exposes
        one = self._spec_for(1, rung)

        def prefill_pure(*flat):
            import jax
            import jax.numpy as jnp
            from jax import lax
            from ..gluon import block as block_mod
            from ..ndarray.ndarray import NDArray
            param_vals = list(flat[:P])
            prompt, last_pos, slot, temp, key_raw, counter = flat[P + NS:]
            with block_mod.tracing_scope(params, param_vals):
                tmp = [NDArray(jnp.zeros(shape, jnp.dtype(dt)), ctx=ctx)
                       for _name, _kind, shape, dt in one]
                logits = lm.prefill(
                    NDArray(prompt, ctx=ctx), tmp,
                    last_pos=NDArray(last_pos, ctx=ctx))._data
            slot_i = jnp.asarray(slot, jnp.int32)
            zero = jnp.int32(0)
            new_caches = [
                lax.dynamic_update_slice(
                    c, t._data.astype(c.dtype),
                    (slot_i,) + (zero,) * (c.ndim - 1))
                for c, t in zip(flat[P:P + NS - 1], tmp)]
            with _device_scope("mxtpu.serving.pick"):
                counted = self._counted()
                k0 = _dispatch_key(key_raw, counter)
                keys = jax.vmap(lambda i: jax.random.fold_in(k0, i))(
                    slot_i.reshape(1))
                nxt = self._pick(logits, temp, jnp.ones((1,)), keys)
                # the first token goes where the slot's next decode
                # reads it
                toks = lax.dynamic_update_slice(
                    flat[P + NS - 1], nxt.reshape(1, 1), (slot_i, zero))
                return (self._tokens_out(nxt, counted),) \
                    + tuple(new_caches) + (toks,)

        return prefill_pure

    # -- dispatch ----------------------------------------------------------
    def _dispatch(self, bucket, kind: str, extra, k: int = 0, **ids):
        """One engine dispatch of a bucket program with the pool
        donated; returns the tokens output (still on the device: the
        caller owes its read) with the successor pool adopted.
        Post-donation failures poison the bucket (the recovery half
        lives in :meth:`recover`).  ``ids`` (``req`` of an admission)
        go onto the dispatch's profiler span."""
        with _span("mxtpu.serving.dispatch", "serving", kind=kind, **ids):
            return self._dispatch_impl(bucket, kind, extra, k)

    def _dispatch_impl(self, bucket, kind: str, extra, k: int):
        from .. import engine, telemetry
        pool = self._pools[bucket.key]
        if pool.poisoned is not None:
            raise MXNetError(
                f"bucket {bucket.key} pool is poisoned "
                f"({pool.poisoned}); call recover()")
        suffix = self._suffix(bucket, kind, k)
        pure = self._pure_for(bucket, kind, k)
        P = len(self._param_nds)
        NS = pool.num_buffers
        with _span("mxtpu.serving.flatten", "serving"):
            if self._decode_sharding is not None:
                # the planned decode mesh: params ride as the
                # replicated copies placed at construction, the base
                # key as the one placed when it was drawn, and every
                # host-made extra (tokens/offsets/temps/counter) is
                # committed replicated — one coherent SPMD program, no
                # mixed-device inputs
                import jax as _jax
                extra = [_jax.device_put(e, self._repl_sharding)
                         if isinstance(e, np.ndarray) else e
                         for e in extra]
                params_flat = list(self._placed_params)
            else:
                params_flat = [p._data for p in self._param_nds]
            flat = params_flat + pool.flat() + list(extra)
            donate = tuple(range(P, P + NS))
        name = self.name + suffix
        persist_name = self._persist_base + suffix
        if kind == "prefill" and suffix not in self._warmed:
            self._warm_ladder(bucket, suffix, flat, donate)
        m0, f0 = engine.compile_counts()
        # the step-owner bracket doubles as the guardian plane's
        # heartbeat: a hung serving dispatch is watchdog-visible
        # exactly like a hung train step, and the bracket encloses the
        # poison latch so a Guardian(action='recover') sees the
        # poisoned server at the heartbeat's exit (elastic.guardian)
        with telemetry.step_owner(self, "serving_dispatch"):
            try:
                res = engine.invoke_compiled(name, pure, {}, *flat,
                                             donate=donate,
                                             persist_name=persist_name)
            except Exception as e:
                if pool.consumed():
                    self._poison(pool, name, e)
                raise
        with _span("mxtpu.serving.state_adopt", "serving"):
            n_out = len(res) - NS
            pool.adopt(res[n_out:])
            if suffix not in self._variants:
                self._note_variant(suffix, kind, k, donate, pure, flat)
            if suffix not in self._warmed:
                # first dispatch of this variant pays its compile; every
                # later one is steady state and must compile NOTHING
                self._warmed.add(suffix)
            else:
                m1, f1 = engine.compile_counts()
                stats = self._bucket_stats[bucket.key]
                stats["steady_dispatches"] += 1
                stats["steady_misses"] += m1 - m0
                stats["steady_fresh_compiles"] += f1 - f0
        return res[0]

    def _note_variant(self, suffix, kind, k, donate, pure, flat):
        """Record a variant's manifest row (what ``save_signature``,
        ``warm_start`` and ``resize_slots``' prewarm carry), once."""
        from .. import engine
        self._variants[suffix] = {
            "suffix": suffix, "kind": kind, "k": k,
            "donate": [int(i) for i in donate],
            "avals": engine.persist.sig_to_json(
                engine.persist.aval_sig(flat))}
        # the wire auditor (analysis.wire_passes): serving decode/
        # prefill legs classify via the plan's decode spec; no
        # observatory reconciliation (program="") — serving wire
        # is GSPMD-implicit on the decode mesh
        try:
            from ..analysis import wire_passes as _wire
            _wire.note_step(
                f"serving:{self.lm.name}", suffix, pure, flat,
                plan=self.plan, kind=kind, program="")
        except Exception:
            pass

    def _warm_ladder(self, bucket, suffix, flat, donate):
        """Before the first dispatch of a prefill program that is not
        warm: make every OTHER rung of the bucket's ladder ready too
        (``engine.aot_compile``: no execution; this dispatch's inputs
        with the prompt's shape replaced), record each as a variant and
        mark it warmed, so its first live dispatch is already steady
        state.  After a bucket's first admission no rung compiles,
        whichever prompt lengths the traffic brings later."""
        import jax
        from .. import engine
        at = len(flat) - _N_INPUTS["prefill"]       # the prompt's place
        prompt = flat[at]
        for rung in bucket.rungs:
            k = self._rung_k(bucket, rung)
            sfx = self._suffix(bucket, "prefill", k)
            if sfx == suffix or sfx in self._warmed:
                continue
            args = list(flat)
            args[at] = jax.ShapeDtypeStruct(
                (1, rung), prompt.dtype,
                sharding=getattr(prompt, "sharding", None))
            pure = self._pure_for(bucket, "prefill", k)
            engine.aot_compile(self.name + sfx, pure, {}, args,
                               donate=donate,
                               persist_name=self._persist_base + sfx)
            self._note_variant(sfx, "prefill", k, donate, pure, args)
            self._warmed.add(sfx)

    def _poison(self, pool, name: str, e: Exception):
        """Latch the post-donation failure of the dispatch ``name`` and
        raise: the pool's buffers went into a program that died, whether
        that showed when it was enqueued or when its tokens were read."""
        from .. import telemetry
        pool.poison(repr(e))
        self._poisoned = repr(e)
        telemetry.counter(
            "mxtpu_poisons_total",
            "post-donation failures (training state lost)").inc()
        telemetry.record_event(
            "poison", where="serving", name=name, error=repr(e)[:500])
        telemetry.auto_dump(reason=f"serving_poisoned:{name}")
        raise MXNetError(
            "serving dispatch failed AFTER the KV-cache pool was "
            "donated; call Server.recover() to rebuild the pages and "
            "requeue resident requests (docs/serving.md). Original "
            f"error: {e!r}") from e

    def _rng_inputs(self):
        """The sampler's two inputs of one dispatch: the base key and
        the count of dispatches since it was drawn, which the program
        folds into it (``_dispatch_key``).  The key is drawn from the
        global stream at the first dispatch, and again at the first one
        after a ``mx.random.seed``: the only device work the host does
        for the RNG, and none of it in a steady round."""
        from .. import random as _rnd
        if self._key_epoch != _rnd._seed_epoch:
            from .. import telemetry
            self._key_epoch = _rnd._seed_epoch
            base = _rnd._next_key_nd(self.ctx)._data
            if self._repl_sharding is not None:
                import jax
                base = jax.device_put(base, self._repl_sharding)
            self._key_base = base
            self._key_counter = 0
            telemetry.counter(
                "mxtpu_serving_host_keys_total",
                "base RNG keys a Server drew from the host's stream "
                "(one a server, plus one a reseed)").inc()
        n = self._key_counter
        self._key_counter = (n + 1) & 0xFFFFFFFF
        return self._key_base, np.asarray(n, np.uint32)

    def _admit(self, bucket, slot: int, req: Request):
        """Enqueue one admission's prefill, at the shortest rung of the
        bucket's ladder that holds the prompt; its first token is read
        after this round's decodes are enqueued (:meth:`_read`)."""
        rung = bucket.rung_for(req.prompt_len)
        with _span("mxtpu.serving.admit", "serving", req=req.id,
                   bucket=bucket.prompt_len, rung=rung, slot=slot):
            self._admit_impl(bucket, slot, req, rung)

    def _admit_impl(self, bucket, slot: int, req: Request, rung: int):
        from .. import telemetry
        t0 = req.admit_t = time.perf_counter()
        telemetry.histogram(
            "mxtpu_serving_queue_wait_seconds",
            "submit -> start of the admission (s)").observe(
            t0 - req.submit_t)
        with _span("mxtpu.serving.build_inputs", "serving", req=req.id):
            prompt = np.zeros((1, rung), np.float32)
            prompt[0, :req.prompt_len] = req.prompt
            extra = [prompt,
                     np.asarray([req.prompt_len - 1], np.float32),
                     np.asarray(slot, np.float32),
                     np.asarray([req.temperature], np.float32),
                     *self._rng_inputs()]
        # pre-dispatch failures (trace/compile, retries exhausted)
        # propagate to step(), which releases THIS placement and the
        # ones behind it back to the queue in FIFO order
        variant = self._rung_k(bucket, rung)
        out = self._dispatch(bucket, "prefill", extra, k=variant,
                             req=req.id)
        with _span("mxtpu.serving.bookkeeping", "serving", req=req.id):
            telemetry.counter("mxtpu_serving_prefills_total",
                              "admission prefill dispatches").inc()
            # their ratio is the live share of what prefill ran over
            telemetry.counter(
                "mxtpu_serving_prompt_tokens_total",
                "prompt tokens of the admissions prefilled").inc(
                req.prompt_len)
            telemetry.counter(
                "mxtpu_serving_prefill_positions_total",
                "positions the admissions' prefill programs ran over "
                "(each prompt padded to its rung)").inc(rung)
            self._owe("prefill", bucket, out, 1, [(0, slot, req)], t0,
                      {"req": req.id}, variant)

    def _decode(self, bucket, decode_steps: int):
        """Enqueue one decode of the bucket; nothing is read here."""
        with _span("mxtpu.serving.decode", "serving",
                   bucket=bucket.prompt_len, active=bucket.n_active()):
            self._decode_impl(bucket, decode_steps)

    def _decode_impl(self, bucket, decode_steps: int):
        from .. import telemetry
        t0 = time.perf_counter()
        k = max(1, int(decode_steps))
        with _span("mxtpu.serving.build_inputs", "serving"):
            active = bucket.active.copy()
            extra = [bucket.offsets.copy(), active, bucket.temps.copy(),
                     *self._rng_inputs()]
        ahead = any(rec.kind == "decode" and rec.bucket is bucket
                    for rec in self._owed)
        variant = 0 if k == 1 else k
        out = self._dispatch(bucket, "decode", extra, k=variant)
        with _span("mxtpu.serving.bookkeeping", "serving"):
            if ahead:
                telemetry.counter(
                    "mxtpu_serving_decodes_ahead_total",
                    "decode dispatches enqueued while the same bucket's "
                    "previous decode was unread").inc()
            # host bookkeeping mirrors the in-graph carry: offsets
            # advance K per slot ACTIVE AT DISPATCH (release() rewinds
            # the ones that leave)
            bucket.offsets += k * active
            slots = [int(j) for j in np.nonzero(active > 0)[0]]
            self._owe("decode", bucket, out, k,
                      [(j, j, bucket.requests[j]) for j in slots], t0, {},
                      variant)

    def _owe(self, kind, bucket, out, k, rows, t0, ids, variant):
        """The count half of a dispatch's bookkeeping, at dispatch time:
        each ``(column, slot, request)`` row is owed as many of the
        dispatch's ``k`` tokens as its budget still holds, and a request
        whose budget is spent thereby leaves its slot NOW (the next
        admission into it runs after this dispatch on the device
        anyway); it is ``done`` when the tokens are read."""
        take = []
        for col, slot, req in rows:
            n = min(k, req.room())
            req.owed += n
            take.append((col, req, n))
            if req.room() == 0:
                bucket.release(slot)
        self._owed.append(
            _Owed(kind, bucket, out, k, take, t0, ids, variant))

    def _disown(self, req: Request):
        """Strike ``req`` from the owed dispatches: whatever the device
        still decodes for it is dropped at the read."""
        dropped = 0
        for rec in self._owed:
            dropped += sum(n for _c, r, n in rec.take if r is req)
            rec.take = [t for t in rec.take if t[1] is not req]
        req.owed = 0
        self._overrun(dropped)

    def _overrun(self, n: int):
        if n:
            from .. import telemetry
            telemetry.counter(
                "mxtpu_serving_overrun_tokens_total",
                "tokens decoded for a request that had already ended "
                "(eos, eviction, a K-step dispatch past the budget): "
                "dropped at the read").inc(n)

    def _read(self, rec: _Owed) -> int:
        """Read one owed dispatch's tokens and do the VALUE half of its
        bookkeeping; returns the tokens delivered.  The host waits for
        the device here and nowhere else."""
        first = rec.kind == "prefill"
        ids = dict(rec.ids, rung=rec.variant or rec.bucket.prompt_len) \
            if first else rec.ids
        with _span("mxtpu.serving.admit" if first
                   else "mxtpu.serving.decode", "serving",
                   bucket=rec.bucket.prompt_len, **ids):
            return self._read_impl(rec, first)

    def _read_impl(self, rec: _Owed, first: bool) -> int:
        from .. import telemetry
        # the bracket is the guardian plane's heartbeat, as around the
        # dispatch: a device that hangs shows HERE, where the host waits
        with _span("mxtpu.serving.token_read", "serving", **rec.ids), \
                telemetry.step_owner(self, "serving_token_read"):
            try:
                toks = np.asarray(rec.out)      # host sync: TTFT is real
            except Exception as e:
                self._poison(
                    self._pools[rec.bucket.key], self.name + self._suffix(
                        rec.bucket, rec.kind, rec.variant), e)
        with _span("mxtpu.serving.bookkeeping", "serving", **rec.ids):
            if self._stat_rows:
                # the model's counts came back behind the tokens, and
                # behind them what its rows chose
                at = rec.k * (1 if first else rec.bucket.slots)
                n = at + len(self._stat_rows)
                toks, counted, rows = toks[:at], toks[at:n], toks[n:]
                for (name, doc), c in zip(self._stat_rows, counted):
                    telemetry.counter(name, doc).inc(int(c))
                if self.statistics_listener is not None:
                    self.statistics_listener(
                        rec.kind, [col for col, _r, _n in rec.take],
                        counted, rows)
            toks = toks.reshape(rec.k, -1)                  # (K, columns)
            produced = dropped = 0
            for i, row in enumerate(toks):
                for col, req, n in rec.take:
                    if i >= n or req.state != ACTIVE:
                        # past its budget, or ended by a token read
                        # since the dispatch: overrun rows
                        dropped += 1
                        continue
                    req.owed -= 1
                    produced += 1
                    if req.push_token(int(row[col])):
                        self._finish(req)
                    if first:
                        telemetry.histogram(
                            "mxtpu_serving_ttft_seconds",
                            "submit -> first generated token (s)"
                            ).observe(req.first_token_t - req.submit_t)
            self._overrun(dropped)
            if produced:
                telemetry.counter(
                    "mxtpu_serving_tokens_total",
                    "tokens generated by the serving plane").inc(produced)
            self._bucket_stats[rec.bucket.key]["tokens"] += produced
            # what this dispatch added to the round: from its enqueue,
            # or from the read before it if the host was still busy
            # with that one
            now = time.perf_counter()
            dt = now - max(rec.t0, self._read_mark)
            self._read_mark = now
            hist = ("mxtpu_serving_prefill_seconds",
                    "one admission (prefill dispatch + first token) (s)") \
                if first else ("mxtpu_serving_decode_seconds",
                               "one decode dispatch wall clock (s)")
            telemetry.histogram(*hist).observe(dt)
            return produced

    def _finish(self, req: Request):
        from .. import telemetry
        self.sched.finish(req)
        telemetry.counter("mxtpu_serving_requests_completed_total",
                          "requests run to completion").inc()
        if req.done_t is not None:
            telemetry.histogram(
                "mxtpu_serving_request_seconds",
                "submit -> completion per-request latency (s)"
                ).observe(req.done_t - req.submit_t)

    def _update_gauges(self):
        from .. import telemetry
        telemetry.gauge("mxtpu_serving_batch_occupancy",
                        "active slots / total slots").set(
            self.sched.occupancy())
        telemetry.gauge("mxtpu_serving_queue_depth",
                        "requests waiting for a slot").set(
            self.sched.queue_depth())
