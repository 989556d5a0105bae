"""Guardian plane: hang watchdog + preemption-safe drain.

Every recovery path PRs 7/8/11 built waits for the runtime to RAISE.
Production TPU jobs also die silently: a dispatch that hangs instead
of failing (a wedged PJRT tunnel, a deadlocked collective), and a
scheduler that SIGTERMs the process mid-step.  This module watches the
runtime instead of waiting for it:

* :class:`Guardian` — a daemon watchdog fed by HEARTBEATS from the
  existing telemetry step-owner seam (``telemetry.step_owner(owner,
  what)`` — ``CompiledStep``/``DataParallelTrainer`` steps and the
  serving ``Server``'s dispatch bracket all open one): a step/dispatch
  in flight longer than ``MXTPU_WATCHDOG_TIMEOUT`` emits a retained
  ``hang_suspected`` event carrying a per-thread stack dump, then
  escalates per ``MXTPU_WATCHDOG_ACTION``:

  - ``warn``    — the event + ``mxtpu_hangs_total`` only;
  - ``dump``    — additionally writes a flight-recorder artifact
    (the dump carries the stacks via the event it retains);
  - ``recover`` — additionally, when the hung dispatch finally
    resolves with the owner POISONED (the ``dispatch_hang`` drill —
    and a real TPU hang resolved by a device reset — consume the
    donated buffers), runs the owner's ``recover()`` through the same
    poison→``timed_recover`` protocol PR 7 built, ON the owning
    thread at the heartbeat's exit: a hung dispatch becomes a
    recovered step, not a dead job.  The step call that hung still
    raises (its buffers are gone), but the NEXT step trains on.

* :class:`PreemptionGuard` — SIGTERM/SIGINT handlers that reuse the
  drain leg of the live-resize protocol: finish the in-flight step
  (the handler runs on the main thread, so the current dispatch
  completes first), commit a checkpoint boundary
  (``manager.save(block=True, force=True)``), drain the serving
  scheduler (residents requeue-with-state and their replay manifest
  lands next to the checkpoint — :func:`drain_server`), emit a
  retained ``preempted`` event, and exit 0 — all inside
  ``MXTPU_DRAIN_DEADLINE_S``.  A SECOND signal force-exits (code 1)
  after dumping forensics.  ``exit_process=False`` makes the whole
  protocol in-process-testable (the tier-1 suite kills itself with
  ``os.kill`` and inspects the drain).

The ``preempt_signal`` fault point (``MXTPU_FAULT_INJECT``) is
consulted at the heartbeat's entry while this plane is installed: when
due, a REAL ``SIGTERM`` is delivered to the process so drills exercise
the actual signal path.

See docs/elasticity.md ("Guardian & chaos soak") for the escalation
ladder and the drain state machine.
"""
from __future__ import annotations

import itertools
import json
import os
import signal as _signal
import sys
import threading
import time
import traceback
import weakref
from typing import Dict, List, Optional

from ..base import MXNetError
from . import faults

__all__ = ["Guardian", "PreemptionGuard", "drain_server",
           "restore_drained_requests", "inflight", "thread_stacks"]

_lock = threading.Lock()
_tokens = itertools.count(1)
#: token -> in-flight heartbeat record (owner weakref, what, t0, the
#: Guardian that flagged it hung — None while healthy)
_inflight: Dict[int, dict] = {}
#: live Guardians/PreemptionGuards: the telemetry heartbeat hook is
#: installed iff this is nonzero (pay-for-what-you-watch)
_installed: List[object] = []


def _sync_hook():
    from .. import telemetry
    telemetry._hb_hook = (_hb_begin, _hb_end) if _installed else None


def _register(plane):
    with _lock:
        if plane not in _installed:
            _installed.append(plane)
        _sync_hook()


def _unregister(plane):
    with _lock:
        if plane in _installed:
            _installed.remove(plane)
        _sync_hook()


def inflight() -> List[dict]:
    """Snapshot of the currently-open heartbeats (watchdog input)."""
    now = time.monotonic()
    with _lock:
        return [{"what": r["what"], "seconds": now - r["t0"],
                 "hung": r["hung"] is not None}
                for r in _inflight.values()]


def _hb_begin(owner, what):
    # the preempt_signal drill rides the heartbeat: a due spec delivers
    # a REAL SIGTERM so the installed PreemptionGuard's handler runs
    # the actual signal path (not a shortcut into drain())
    if faults._active and faults.preempt_due(what or ""):
        os.kill(os.getpid(), _signal.SIGTERM)
    tok = next(_tokens)
    rec = {"token": tok, "owner_id": id(owner),
           "owner": weakref.ref(owner), "what": what or
           type(owner).__name__, "t0": time.monotonic(), "hung": None}
    with _lock:
        _inflight[tok] = rec
    return tok


def _hb_end(tok, exc):
    with _lock:
        rec = _inflight.pop(tok, None)
    if rec is None:
        return
    g = rec["hung"]
    if g is not None:
        g._on_hang_exit(rec, exc)


def thread_stacks(limit_frames: int = 10,
                  per_thread_chars: int = 1500) -> Dict[str, str]:
    """Per-thread stack snapshot (``sys._current_frames``), trimmed to
    the newest ``limit_frames`` frames — the forensic payload of a
    ``hang_suspected`` event."""
    names = {t.ident: t.name for t in threading.enumerate()}
    out = {}
    for tid, frame in sys._current_frames().items():
        text = "".join(traceback.format_stack(frame)[-limit_frames:])
        out[f"{names.get(tid, 'thread')}:{tid}"] = \
            text[-per_thread_chars:]
    return out


def _owner_poison(owner) -> Optional[str]:
    """The owner's poison latch, whichever spelling it uses
    (``CompiledStep._poisoned`` / ``DataParallelTrainer.
    _donation_poisoned`` / ``Server._poisoned``)."""
    return getattr(owner, "_poisoned", None) or \
        getattr(owner, "_donation_poisoned", None)


class Guardian:
    """Hang watchdog for ONE step owner.

    Args:
      owner: a ``gluon.CompiledStep``, ``parallel.
        DataParallelTrainer``, or ``serving.Server`` (anything whose
        steps/dispatches open the ``telemetry.step_owner(owner, what)``
        heartbeat).  Held by weakref — a collected owner stops the
        watch.
      manager: the owner's ``CheckpointManager`` for the ``recover``
        action (omit for a ``Server``, whose ``recover()`` replays
        host-owned prompts instead of restoring a checkpoint).
      timeout: seconds in flight before a step is suspected hung
        (default ``MXTPU_WATCHDOG_TIMEOUT``).
      action: ``warn`` | ``dump`` | ``recover`` (default
        ``MXTPU_WATCHDOG_ACTION``) — the escalation ladder above.
      poll: watchdog scan period (default ``min(timeout / 4, 0.25)``).

    Use as a context manager or ``start()``/``stop()``.  The watchdog
    thread only OBSERVES; the recover escalation runs on the owning
    thread at the heartbeat's exit, so no cross-thread buffer races.
    """

    def __init__(self, owner, manager=None, timeout: float = None,
                 action: str = None, poll: float = None,
                 name: str = None):
        from .. import envs
        self.owner_ref = weakref.ref(owner)
        self.manager = manager
        self.timeout = float(envs.get("MXTPU_WATCHDOG_TIMEOUT")) \
            if timeout is None else float(timeout)
        if self.timeout <= 0:
            raise MXNetError(
                f"Guardian timeout must be > 0, got {self.timeout}")
        act = (action if action is not None
               else str(envs.get("MXTPU_WATCHDOG_ACTION"))).strip() \
            .lower()
        if act not in ("warn", "dump", "recover"):
            raise MXNetError(
                f"MXTPU_WATCHDOG_ACTION must be warn|dump|recover, "
                f"got {act!r}")
        self.action = act
        self.poll = max(0.005, float(poll) if poll is not None
                        else min(self.timeout / 4.0, 0.25))
        self.name = name or getattr(owner, "name",
                                    type(owner).__name__)
        self.hangs = 0
        self.recovered = 0
        self.last: Optional[dict] = None
        self._stop_ev = threading.Event()
        self._thread: Optional[threading.Thread] = None

    # -- lifecycle --------------------------------------------------------
    def start(self) -> "Guardian":
        if self._thread is not None:
            return self
        self._stop_ev.clear()
        _register(self)
        self._thread = threading.Thread(
            target=self._loop, name=f"mxtpu-guardian-{self.name}",
            daemon=True)
        self._thread.start()
        return self

    def stop(self):
        self._stop_ev.set()
        t, self._thread = self._thread, None
        if t is not None:
            t.join(timeout=5.0)
        _unregister(self)

    def __enter__(self) -> "Guardian":
        return self.start()

    def __exit__(self, *exc):
        self.stop()

    def report(self) -> dict:
        return {"name": self.name, "timeout": self.timeout,
                "action": self.action, "hangs": self.hangs,
                "recovered": self.recovered, "last": self.last}

    # -- watchdog ---------------------------------------------------------
    def _loop(self):
        while not self._stop_ev.wait(self.poll):
            owner = self.owner_ref()
            if owner is None:
                break            # owner collected: nothing to watch
            try:
                self._scan(id(owner))
            except Exception:
                pass             # the watchdog must never take down a job
        _unregister(self)

    def _scan(self, owner_id: int):
        now = time.monotonic()
        with _lock:
            # mark AND record the hang_suspected event while holding
            # the heartbeat lock: _hb_end blocks on it to pop the
            # record, so a dispatch resolving in this window is
            # guaranteed a LATER event seq for its hang_resolved /
            # recovery — the ordering MXL504's answered-check relies on
            due = [r for r in _inflight.values()
                   if r["owner_id"] == owner_id and r["hung"] is None
                   and now - r["t0"] > self.timeout]
            for r in due:
                r["hung"] = self
                self._suspect(r, now)
        # the flight-recorder artifact (file IO) happens OUTSIDE the
        # lock — it retains the event just recorded, and heartbeats
        # must not stall on the dump
        if due and self.action in ("dump", "recover"):
            from .. import telemetry
            try:
                path = telemetry.dump_flight_recorder(
                    reason=f"hang_suspected:{self.name}")
                if self.last is not None:
                    self.last["artifact"] = path
            except Exception:
                pass             # forensics must not mask the hang

    def _suspect(self, rec: dict, now: float):
        from .. import telemetry
        self.hangs += 1
        seconds = round(now - rec["t0"], 4)
        stacks = thread_stacks()
        telemetry.counter(
            "mxtpu_hangs_total",
            "dispatches suspected hung by the guardian watchdog").inc()
        telemetry.record_event(
            "hang_suspected", owner=self.name, what=rec["what"],
            seconds=seconds, timeout=self.timeout, action=self.action,
            stacks=stacks)
        self.last = {"what": rec["what"], "seconds": seconds,
                     "artifact": None}

    def _on_hang_exit(self, rec: dict, exc):
        """Owning-thread callback: the suspected-hung dispatch finally
        returned (or raised).  ``recover`` action + a poisoned owner →
        the PR 7 poison/recover protocol runs HERE, so the next step
        dispatches against healthy buffers."""
        from .. import telemetry
        owner = rec["owner"]()
        seconds = round(time.monotonic() - rec["t0"], 4)
        poison = _owner_poison(owner) if owner is not None else None
        recovered = False
        restored = None
        err = None
        if self.action == "recover" and owner is not None and \
                poison is not None:
            try:
                if self.manager is not None:
                    restored = owner.recover(self.manager)
                else:
                    restored = owner.recover()
                recovered = True
                self.recovered += 1
            except Exception as e:
                err = repr(e)[:300]
        telemetry.record_event(
            "hang_resolved", owner=self.name, what=rec["what"],
            seconds=seconds, poisoned=poison is not None,
            recovered=recovered, restored_step=restored,
            error=err or (repr(exc)[:300] if exc is not None else None))
        if self.last is not None:
            self.last.update(resolved_seconds=seconds,
                             recovered=recovered)


# -- preemption-safe drain ---------------------------------------------------

def drain_server(server, directory: str) -> dict:
    """Requeue every serving resident WITH its state recorded: live
    requests go back to the queue head (the documented replay-exact
    recovery path — prompts are host-owned) and the full queue —
    prompt, budget, temperature, eos, tokens generated so far — lands
    in ``serving-drain.json`` under ``directory`` so a RESTARTED
    process can resubmit them (:func:`restore_drained_requests`).
    Returns ``{"requeued", "queued", "manifest"}``."""
    from . import integrity as _integrity
    # the server runs one decode ahead of its reads: take what the
    # device still owes first, so "generated so far" is all of it.  A
    # poisoned server cannot; the requests it owed their last tokens
    # hold no slot any more and drain beside the residents
    if not server.stats()["poisoned"]:
        server.settle()
    residents = server.awaiting() + server.sched.active_requests()
    queued = list(server.sched.queue)
    rows = []
    for req in residents + queued:
        rows.append({
            "prompt": [float(t) for t in req.prompt],
            "max_new_tokens": int(req.max_new_tokens),
            "temperature": float(req.temperature),
            "eos_id": req.eos_id,
            "generated": [int(t) for t in req.generated],
            # integrity row: restore_drained_requests refuses a
            # manifest whose token state rotted on disk — a corrupt
            # resident must replay LOUDLY, not decode garbage
            "sha256": _integrity.token_checksum(req.prompt,
                                                req.generated),
        })
    # reverse: evict(requeue=True) pushes to the queue HEAD, so
    # iterating backwards preserves the residents' relative order
    for req in reversed(residents):
        server.evict(req, reason="preempt_drain", requeue=True)
    manifest = {"format": 1, "kind": "mxtpu_serving_drain",
                "server": server.name, "requests": rows}
    path = os.path.join(directory, "serving-drain.json")
    tmp = path + f".tmp{os.getpid()}"
    os.makedirs(directory, exist_ok=True)
    with open(tmp, "w") as f:
        json.dump(manifest, f, indent=1)
    os.replace(tmp, path)
    return {"requeued": len(residents), "queued": len(queued),
            "manifest": path}


def restore_drained_requests(server, path: str) -> list:
    """Resubmit every request a :func:`drain_server` manifest recorded
    (fresh-process restart leg).  Requests restart from their prompts —
    greedy replay reproduces the original stream token-for-token, the
    same recovery semantics ``Server.recover`` already proves.
    Deadlines are NOT re-applied (they dated the preempted process).
    Returns the new ``Request`` objects in manifest order."""
    import numpy as np
    from . import integrity as _integrity
    with open(path) as f:
        m = json.load(f)
    if m.get("kind") != "mxtpu_serving_drain" or m.get("format") != 1:
        raise MXNetError(f"{path} is not a serving drain manifest")
    rows = list(m.get("requests", ()))
    # validate EVERY checksum before the first submit: a rotten row
    # must not leave a partial restore behind (a retry after dropping
    # it would double-submit the rows that already landed)
    for i, row in enumerate(rows):
        want = row.get("sha256")
        if want is not None and want != _integrity.token_checksum(
                row["prompt"], row.get("generated", ())):
            # pre-checksum manifests (no sha256 row) restore as
            # before; a ROW THAT ROTTED refuses loudly — resubmitting
            # a silently-corrupt prompt would decode garbage with no
            # event anywhere
            raise MXNetError(
                f"serving drain manifest {path} row {i} failed its "
                "token checksum — the manifest is corrupt; drop the "
                "row or re-drain")
    out = []
    for row in rows:
        out.append(server.submit(
            np.asarray(row["prompt"], np.float32),
            max_new_tokens=int(row["max_new_tokens"]),
            temperature=float(row.get("temperature", 0.0)),
            eos_id=row.get("eos_id")))
    return out


class PreemptionGuard:
    """SIGTERM/SIGINT → drain to a committed boundary → exit 0.

    Args:
      manager: ``CheckpointManager`` (with its trainer attached) — the
        drain commits ``manager.save(block=True, force=True)``.
      server: optional ``serving.Server`` to drain (residents requeue
        + the replay manifest lands next to the checkpoint).
      deadline_s: drain budget (default ``MXTPU_DRAIN_DEADLINE_S``);
        overruns are recorded on the ``preempted`` event
        (``deadline_ok: false``), not enforced by interruption — a
        torn checkpoint would be worse than a late one.
      exit_process: ``os._exit(0)`` after a clean drain (production);
        ``False`` records the would-be code in ``exit_code`` instead
        (the in-process test/soak mode).
      signals: handled signal numbers (default SIGTERM + SIGINT).

    First signal: drain → exit 0.  Second signal while draining:
    dump forensics (flight recorder + stacks) → exit 1.  Install from
    the MAIN thread (CPython's ``signal.signal`` contract).
    """

    def __init__(self, manager=None, server=None,
                 deadline_s: float = None, exit_process: bool = True,
                 signals=None):
        from .. import envs
        if manager is None and server is None:
            raise MXNetError("PreemptionGuard needs a manager and/or "
                             "a server to drain")
        self.manager = manager
        self.server = server
        self.deadline_s = float(envs.get("MXTPU_DRAIN_DEADLINE_S")) \
            if deadline_s is None else float(deadline_s)
        self.exit_process = bool(exit_process)
        self.signals = tuple(signals) if signals is not None else \
            (_signal.SIGTERM, _signal.SIGINT)
        self._prev: Dict[int, object] = {}
        self._installed = False
        self._draining = False
        self.exit_code: Optional[int] = None
        self.drained: Optional[dict] = None

    # -- lifecycle --------------------------------------------------------
    def install(self) -> "PreemptionGuard":
        if self._installed:
            return self
        for sig in self.signals:
            self._prev[sig] = _signal.signal(sig, self._on_signal)
        self._installed = True
        _register(self)
        return self

    def uninstall(self):
        if not self._installed:
            return
        for sig, prev in self._prev.items():
            try:
                _signal.signal(sig, prev)
            except (ValueError, TypeError):
                pass
        self._prev.clear()
        self._installed = False
        _unregister(self)

    def __enter__(self) -> "PreemptionGuard":
        return self.install()

    def __exit__(self, *exc):
        self.uninstall()

    # -- the protocol -----------------------------------------------------
    def _on_signal(self, signum, frame):
        from .. import telemetry
        if self._draining:
            # second signal: the operator (or the scheduler's kill
            # escalation) wants OUT — dump forensics and force-exit
            try:
                telemetry.record_event("preempt_forced",
                                       signal=int(signum),
                                       stacks=thread_stacks())
                telemetry.dump_flight_recorder(reason="preempt_forced")
            except Exception:
                pass
            self._exit(1)
            return
        self._draining = True
        try:
            self.drain(signum=int(signum))
        except Exception as e:
            try:
                telemetry.record_event("preempted", ok=False,
                                       signal=int(signum),
                                       error=repr(e)[:300])
                telemetry.auto_dump(reason="preempt_drain_failed")
            except Exception:
                pass
            self._exit(1)
            return
        self._exit(0)

    def drain(self, signum: Optional[int] = None,
              reason: str = "signal") -> dict:
        """The drain state machine (callable directly for tests and
        orchestrators): in-flight step already finished (main-thread
        handler) → blocking force save to a committed boundary → drain
        the serving scheduler with a replay manifest → emit the
        retained ``preempted`` event + drain-duration histogram."""
        from .. import telemetry
        t0 = time.perf_counter()
        committed = None
        serving = None
        if self.manager is not None and self.manager.trainer is not None:
            committed = int(self.manager.save(block=True, force=True))
        if self.server is not None:
            if self.manager is not None:
                out_dir = self.manager.directory
            else:
                from .. import envs
                import tempfile
                out_dir = str(envs.get("MXTPU_TELEMETRY_EXPORT")
                              or "") or tempfile.gettempdir()
            serving = drain_server(self.server, out_dir)
        dt = time.perf_counter() - t0
        deadline_ok = dt <= self.deadline_s
        telemetry.counter(
            "mxtpu_preemptions_total",
            "preemption signals drained to a committed boundary").inc()
        telemetry.histogram(
            "mxtpu_drain_seconds",
            "preemption drain wall clock: signal -> committed "
            "boundary (s)").observe(dt)
        rec = {"reason": reason, "signal": signum,
               "committed_step": committed,
               "seconds": round(dt, 4),
               "deadline_s": self.deadline_s,
               "deadline_ok": deadline_ok}
        if serving is not None:
            rec.update(requeued=serving["requeued"],
                       queued=serving["queued"],
                       drain_manifest=serving["manifest"])
        telemetry.record_event("preempted", ok=True, **rec)
        if not deadline_ok:
            import warnings
            warnings.warn(
                f"preemption drain took {dt:.2f}s, over the "
                f"{self.deadline_s:.2f}s MXTPU_DRAIN_DEADLINE_S "
                "budget — the scheduler may have force-killed a real "
                "job here", RuntimeWarning, stacklevel=2)
        self.drained = rec
        return rec

    def _exit(self, code: int):
        self.exit_code = code
        if self.exit_process:
            # handlers run between bytecodes of arbitrary code;
            # sys.exit would be swallowed by bare except blocks —
            # preemption means GO, so hard-exit after flushing
            try:
                sys.stdout.flush()
                sys.stderr.flush()
            except Exception:
                pass
            os._exit(code)


def _reset():
    """Test hook: tear down every installed guardian plane and clear
    the heartbeat table."""
    for plane in list(_installed):
        try:
            if isinstance(plane, Guardian):
                plane.stop()
            else:
                plane.uninstall()
        except Exception:
            pass
    with _lock:
        _installed.clear()
        _inflight.clear()
        _sync_hook()
