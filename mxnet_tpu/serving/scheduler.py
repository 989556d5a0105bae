"""Continuous-batching scheduler: admit/evict between decode steps
into FIXED bucket shapes (docs/serving.md).

The TPU contract that shapes this module: a bucket is a
``(batch_slots, prompt_len_bucket)`` pair with ONE compiled decode
program and a short fixed LADDER of prefill programs
(:func:`prefill_ladder`), and NOTHING else may vary.  So the scheduler
never changes shapes — admission swaps a slot's cache page + flips its
active-mask bit, eviction flips the bit back, and the decode program
runs the same avals every step.  Steady state therefore performs ZERO
retraces across any admit/evict sequence (asserted in tier-1 via
``engine.cache_info()``).

Pure host logic: no jax, no dispatches.  ``Server`` (``server.py``)
owns the compiled programs and drives this scheduler between them.
"""
from __future__ import annotations

import itertools
import time
from collections import deque
from typing import List, Optional

import numpy as np

from ..base import MXNetError

__all__ = ["Request", "Bucket", "BucketScheduler", "prefill_ladder"]

_req_uid = itertools.count(1)

#: request lifecycle states (``shed`` = rejected at admission by the
#: overload policy — never held a slot or a queue place)
QUEUED, ACTIVE, DONE, EVICTED, SHED = \
    "queued", "active", "done", "evicted", "shed"


class Request:
    """One generation request moving through the serving plane.

    ``ttl_ms`` arms the overload policy (docs/serving.md, "Overload
    policy"): the request must COMPLETE within ``ttl_ms`` of
    submission or it is shed at enqueue (the estimated queue wait
    already exceeds the deadline) / evicted when the deadline expires
    in the queue or in a slot.  ``None`` (default) = no deadline."""

    __slots__ = ("id", "prompt", "max_new_tokens", "temperature",
                 "eos_id", "state", "generated", "owed", "bucket", "slot",
                 "submit_t", "admit_t", "first_token_t", "done_t",
                 "evict_reason", "ttl_ms", "deadline")

    def __init__(self, prompt, max_new_tokens: int,
                 temperature: float = 0.0,
                 eos_id: Optional[int] = None,
                 ttl_ms: Optional[float] = None):
        self.id = next(_req_uid)
        self.prompt = np.asarray(prompt, dtype=np.float32).reshape(-1)
        if self.prompt.size == 0:
            raise MXNetError("empty prompt")
        self.max_new_tokens = int(max_new_tokens)
        if self.max_new_tokens < 1:
            raise MXNetError(
                f"max_new_tokens must be >= 1, got {max_new_tokens}")
        self.temperature = float(temperature)
        self.eos_id = None if eos_id is None else int(eos_id)
        self.state = QUEUED
        self.generated: List[int] = []
        # tokens the device was asked for and the host has not read yet
        # (docs/serving.md, "A round"): the server stays one decode
        # dispatch ahead of its reads
        self.owed = 0
        self.bucket: Optional["Bucket"] = None
        self.slot: Optional[int] = None
        self.submit_t = time.perf_counter()
        # when its (latest) admission began: admit_t - submit_t is the
        # wait for a slot
        self.admit_t: Optional[float] = None
        self.first_token_t: Optional[float] = None
        self.done_t: Optional[float] = None
        self.evict_reason: Optional[str] = None
        if ttl_ms is not None and float(ttl_ms) <= 0:
            raise MXNetError(f"ttl_ms must be > 0, got {ttl_ms}")
        self.ttl_ms = None if ttl_ms is None else float(ttl_ms)
        self.deadline = None if ttl_ms is None else \
            self.submit_t + self.ttl_ms / 1000.0

    def expired(self, now: Optional[float] = None) -> bool:
        """Deadline passed while the request is still live (queued OR
        holding a slot)?  Terminal states never expire."""
        if self.deadline is None or self.state in (DONE, EVICTED, SHED):
            return False
        return (time.perf_counter() if now is None else now) \
            > self.deadline

    @property
    def prompt_len(self) -> int:
        return int(self.prompt.size)

    def room(self) -> int:
        """Tokens of the budget not yet asked of the device: read ones
        and owed ones both count against it.  At 0 the request leaves
        its slot, whatever the owed tokens turn out to be."""
        return self.max_new_tokens - len(self.generated) - self.owed

    def tokens(self) -> np.ndarray:
        """Prompt + generated continuation (what the caller reads
        back)."""
        return np.concatenate(
            [self.prompt, np.asarray(self.generated, np.float32)])

    def push_token(self, tok: int) -> bool:
        """Record one generated token; returns True when the request
        just FINISHED (hit eos or its token budget)."""
        if self.first_token_t is None:
            self.first_token_t = time.perf_counter()
        self.generated.append(int(tok))
        if self.eos_id is not None and int(tok) == self.eos_id:
            return True
        return len(self.generated) >= self.max_new_tokens


#: the shortest rung of a prefill ladder, and what every rung below a
#: bucket's own ``prompt_len`` is a multiple of (a multiple of 128
#: positions keeps the lane tiles of a page stored positions-minor
#: whole).  256, not 128: every rung is one more program to reload in a
#: process's warm-up, and the 128 rungs cost over 5% of the set-up of
#: the largest served programs (PERF.md section 6, PR 38)
RUNG_FLOOR = 256


def prefill_ladder(prompt_len: int, below: int = 0) -> tuple:
    """The prompt shapes a bucket of ``prompt_len`` positions has a
    prefill program for, shortest first: ``prompt_len`` halved for as
    long as the half is a multiple of ``RUNG_FLOOR`` and longer than
    ``below``, the next smaller bucket's ``prompt_len`` (a prompt that
    short never lands here).  ``(48, 256), (24, 1024)`` give ``(256,)``
    and ``(512, 1024)``, ``(96, 512)`` gives ``(256, 512)``; under 512
    positions there is one rung."""
    rungs = [int(prompt_len)]
    while True:
        half, odd = divmod(rungs[0], 2)
        if odd or half % RUNG_FLOOR or half <= below:
            return tuple(rungs)
        rungs.insert(0, half)


class Bucket:
    """One fixed ``(slots, prompt_len)`` shape class and its host-side
    slot table: slots, pages and ONE decode program, and a ladder of
    prefill programs (``rungs``; ``below`` is the next smaller bucket's
    ``prompt_len``).  ``cache_len = prompt_len + max_new_tokens``
    positions per slot; per-slot decode offsets are the ABSOLUTE next
    position (they drive rope + the cache scatter + the validity mask
    as dynamic inputs)."""

    def __init__(self, slots: int, prompt_len: int, cache_len: int,
                 below: int = 0):
        if slots < 1 or prompt_len < 1 or cache_len <= prompt_len:
            raise MXNetError(
                f"bad bucket (slots={slots}, prompt_len={prompt_len}, "
                f"cache_len={cache_len}): need slots/prompt_len >= 1 "
                "and cache_len > prompt_len")
        self.slots = int(slots)
        self.prompt_len = int(prompt_len)
        self.cache_len = int(cache_len)
        self.below = int(below)
        self.rungs = prefill_ladder(self.prompt_len, self.below)
        self.requests: List[Optional[Request]] = [None] * self.slots
        self.offsets = np.zeros(self.slots, np.float32)
        self.active = np.zeros(self.slots, np.float32)
        self.temps = np.zeros(self.slots, np.float32)

    @property
    def key(self):
        return (self.slots, self.prompt_len)

    def rung_for(self, prompt_len: int) -> int:
        """The shortest rung that holds a prompt of ``prompt_len``
        tokens: the shape its admission is padded to and prefilled at."""
        return next(r for r in self.rungs if prompt_len <= r)

    def resized(self, slots: int) -> "Bucket":
        """An empty bucket of the same shape class with ``slots``
        slots."""
        return Bucket(slots, self.prompt_len, self.cache_len, self.below)

    def n_active(self) -> int:
        return int(self.active.sum())

    def occupancy(self) -> float:
        return self.n_active() / self.slots

    def free_slot(self) -> Optional[int]:
        for j, r in enumerate(self.requests):
            if r is None:
                return j
        return None

    def place(self, req: Request, slot: int):
        """Host bookkeeping of an admission (the cache page itself is
        written by the admit program)."""
        if self.requests[slot] is not None:
            raise MXNetError(f"slot {slot} is occupied")
        self.requests[slot] = req
        req.state = ACTIVE
        req.bucket, req.slot = self, slot
        # the admit program samples the first token at prompt_len-1's
        # logits; decode continues at absolute position prompt_len
        self.offsets[slot] = float(req.prompt_len)
        self.active[slot] = 1.0
        self.temps[slot] = req.temperature

    def adopt_slot(self, src: "Bucket", j: int, j2: int):
        """Move ``src``'s slot ``j`` bookkeeping into THIS bucket's
        slot ``j2`` — the host half of a live slot-count resize
        (``Server.resize_slots``): the request keeps its absolute
        offset / temperature (its state and its last token migrate by
        the same index on the device side), only its (bucket, slot)
        address changes."""
        req = src.requests[j]
        if req is None:
            raise MXNetError(f"adopt_slot: source slot {j} is empty")
        if self.requests[j2] is not None:
            raise MXNetError(f"adopt_slot: slot {j2} is occupied")
        self.requests[j2] = req
        req.bucket, req.slot = self, j2
        self.offsets[j2] = src.offsets[j]
        self.active[j2] = 1.0
        self.temps[j2] = src.temps[j]

    def release(self, slot: int):
        """Drop a slot back to free: active-mask off, offset rewound.
        The page contents stay as garbage the per-row validity mask
        never exposes to other slots."""
        req = self.requests[slot]
        self.requests[slot] = None
        self.active[slot] = 0.0
        self.offsets[slot] = 0.0
        self.temps[slot] = 0.0
        if req is not None:
            req.bucket, req.slot = None, None


class BucketScheduler:
    """FIFO admission over fixed buckets + a bounded wait queue.

    ``buckets``: list of ``(slots, prompt_len)`` pairs (one compiled
    decode program and a ladder of prefill programs each).  A request
    lands in the SMALLEST bucket whose ``prompt_len`` holds its prompt,
    and is prefilled there right-padded to the shortest rung of the
    bucket's ladder that holds it (``Bucket.rung_for``); prompts longer
    than every bucket are rejected.  The queue is
    bounded by ``max_queue`` — overflow is the ``slot_oom`` signal
    (the caller records the retained telemetry event).
    """

    def __init__(self, buckets, max_new_tokens: int, max_queue: int):
        if not buckets:
            raise MXNetError("need at least one (slots, prompt_len) "
                             "bucket")
        self.max_new_tokens = int(max_new_tokens)
        self.max_queue = int(max_queue)
        rows = sorted(buckets, key=lambda b: b[1])
        if len({p for _s, p in rows}) != len(rows):
            raise MXNetError("duplicate prompt_len buckets")
        self.buckets: List[Bucket] = [
            Bucket(s, p, p + self.max_new_tokens, below)
            for (s, p), below in zip(rows, [0] + [p for _s, p in rows])]
        # no terminal-request registry: callers hold their own Request
        # references, and a server-side dict of every finished request
        # would grow without bound on a production stream
        self.queue: deque = deque()

    # -- admission --------------------------------------------------------
    def select_bucket(self, prompt_len: int) -> Optional[Bucket]:
        for b in self.buckets:
            if prompt_len <= b.prompt_len:
                return b
        return None

    def enqueue(self, req: Request) -> Bucket:
        """Queue ``req`` for admission; raises ``MXNetError`` when no
        bucket fits the prompt or the queue is full (callers emit the
        ``slot_oom`` event for the latter)."""
        bucket = self.select_bucket(req.prompt_len)
        if bucket is None:
            raise MXNetError(
                f"prompt of {req.prompt_len} tokens exceeds the "
                f"largest bucket "
                f"({self.buckets[-1].prompt_len}); add a bigger "
                "prompt-length bucket")
        if len(self.queue) >= self.max_queue:
            raise MXNetError(
                f"serving queue full ({self.max_queue}); evict or "
                "raise MXTPU_SERVING_MAX_QUEUE")
        self.queue.append(req)
        return bucket

    def admissions(self):
        """Pop every queued request whose bucket has a free slot:
        returns ``[(bucket, slot, request)]`` in FIFO order (a request
        whose bucket is full never blocks one whose bucket has room).
        Each returned request is already PLACED (slot reserved, mask
        on) so later queue entries cannot race it; the caller
        dispatches the admit program per entry — and must release a
        placement whose dispatch failed (``Server.step`` requeues the
        ones behind a failure)."""
        out = []
        blocked = deque()
        while self.queue:
            req = self.queue.popleft()
            bucket = self.select_bucket(req.prompt_len)
            slot = bucket.free_slot()
            if slot is None:
                blocked.append(req)
                continue
            # reserve so a later queued request cannot take the slot
            bucket.place(req, slot)
            out.append((bucket, slot, req))
        self.queue = blocked
        return out

    # -- completion / eviction --------------------------------------------
    def finish(self, req: Request):
        req.state = DONE
        req.done_t = time.perf_counter()
        if req.bucket is not None and req.slot is not None:
            req.bucket.release(req.slot)

    def evict(self, req: Request, reason: str,
              requeue: bool = False) -> bool:
        """Remove a live request from its slot or the queue (one that
        left its slot by count and waits for its last tokens is in
        neither, and just changes state); returns True when anything
        happened.  A request already in a terminal
        state (DONE/EVICTED) is left untouched — evicting a request
        that finished in the same scheduling round must not wipe its
        output or skew the lifecycle counters.  With ``requeue=True``
        the request restarts from its prompt at the next admission
        round (the recovery path)."""
        if req.state in (DONE, EVICTED):
            return False
        if req.bucket is not None and req.slot is not None:
            req.bucket.release(req.slot)
        elif req in self.queue:
            self.queue.remove(req)
        req.evict_reason = reason
        if requeue:
            req.state = QUEUED
            req.generated = []
            req.owed = 0
            req.first_token_t = None
            # head, not tail: a requeued request (transient admit
            # failure, recovery) keeps its place ahead of
            # later-submitted traffic — callers requeueing a batch
            # iterate it in REVERSE to preserve relative order
            self.queue.appendleft(req)
        else:
            req.state = EVICTED
        return True

    def active_requests(self) -> List[Request]:
        return [r for b in self.buckets for r in b.requests
                if r is not None]

    def queue_depth(self) -> int:
        return len(self.queue)

    def occupancy(self) -> float:
        total = sum(b.slots for b in self.buckets)
        used = sum(b.n_active() for b in self.buckets)
        return used / total if total else 0.0
