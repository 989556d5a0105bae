"""Persistent second tier under the engine's jit cache.

The in-memory tier (``engine._jit_cache``) dies with the process, so
every restart re-pays the full XLA compile bill before the
one-dispatch step ever runs (PERF.md section 5, "Set-up": cold against
warm ``setup_s`` on the chip).  Restarts are a first-class hot path
for the ROADMAP north-star (production traffic, autoscaled replicas),
and compiled-program reuse is the standard answer in TPU compilation
stacks (the serializable-artifact design of Relay, arXiv:1810.00952;
whole-program AOT in arXiv:1810.09868).

This module stores COMPILED EXECUTABLES on disk, keyed by everything
that could invalidate them::

    entry hash = sha256(persist name, canonical attr signature,
                        donate tuple, input avals,
                        jax/jaxlib versions + PJRT platform fingerprint
                        + a library salt)

Two payload kinds:

* ``exec`` — ``jax.experimental.serialize_executable`` of the AOT
  ``lower(*avals).compile()`` result (the fast path: reload skips BOTH
  trace and compile; donation/aliasing is baked into the executable);
* ``export`` — a serialized ``jax.export`` StableHLO artifact, written
  when the backend cannot serialize executables (the same seam
  ``deploy.py`` uses).  Reload skips the Python trace and re-runs only
  the XLA compile.

Loads are corruption-tolerant BY CONTRACT: any unreadable, truncated,
checksum-failing, or fingerprint-mismatched entry returns ``None`` and
the caller compiles fresh — a bad cache dir can cost time, never
correctness or a crash.  The dir is size-bounded
(``MXTPU_COMPILE_CACHE_MAX_BYTES``) with LRU pruning on insert; loads
touch mtime so hot entries survive.

Trust note: ``exec`` payloads deserialize via pickle (what
``serialize_executable`` emits).  The cache dir is a local artifact the
operator owns — treat it like any other build cache and do not point
``MXTPU_COMPILE_CACHE_DIR`` at untrusted data.

Tooling: ``tools/mxcache.py`` (``ls`` / ``verify`` / ``prune``);
``verify`` also runs inside the mxlint ``--self-check`` CI gate
(MXL402).  See docs/compile_cache.md.
"""
from __future__ import annotations

import hashlib
import json
import os
import struct as _struct
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

__all__ = ["enabled", "cache_dir", "fingerprint", "aval_sig",
           "entry_hash", "contains", "fetch", "save_compiled",
           "tiered_compile", "ls", "verify", "prune", "clear", "drop",
           "counters", "reset_counters", "LIBRARY_SALT"]

#: bump to invalidate every existing entry (format or semantics change
#: in the programs we serialize — the tier-1 suite asserts a salt bump
#: misses cleanly).  2: PR 34 rewrote the per-row page write inside
#: every served decode program; no aval moved, so only the salt keeps
#: an older checkout's executable out (ROADMAP D16).  3: PR 36 gave the
#: absorbed latent attention inside Pangu's decode program a kernel.
#: 4: PR 37 put ``mxtpu.*`` device scopes on the fused train step and on
#: BERT's and Llama's blocks: metadata only, but an older entry's
#: executable reads ``(no scope)`` in ``profiler.device_dumps``
LIBRARY_SALT = "mxtpu-compile-cache-4"

_MAGIC = b"MXTPUCC1"
#: header layout version; 2 added ``devices`` (an entry without it
#: cannot be reloaded onto the right devices and reads as a miss)
_FORMAT = 2
_SUFFIX = ".mxc"

_lock = threading.Lock()
_hits = 0
_misses = 0
_seconds_saved = 0.0
_fp_cache: Optional[Dict[str, Any]] = None

_telem = None


def _telemetry():
    global _telem
    if _telem is None:
        from .. import telemetry
        _telem = telemetry
    return _telem


def cache_dir() -> str:
    """The persistent-tier directory ('' = tier disabled)."""
    from .. import envs
    return envs.get("MXTPU_COMPILE_CACHE_DIR")


def max_bytes() -> int:
    from .. import envs
    return envs.get("MXTPU_COMPILE_CACHE_MAX_BYTES")


def enabled() -> bool:
    return bool(cache_dir())


def fingerprint() -> Dict[str, Any]:
    """Everything environmental that invalidates a compiled program:
    jax/jaxlib versions, the PJRT platform + its version, the x64 mode,
    and the library salt.  Computed once per process."""
    global _fp_cache
    if _fp_cache is None:
        import jax
        import jaxlib
        try:
            backend = jax.extend.backend.get_backend()
            platform = backend.platform
            platform_version = str(
                getattr(backend, "platform_version", ""))
        except Exception:  # backend not initializable: still hashable
            platform, platform_version = "unknown", ""
        _fp_cache = {
            "jax": jax.__version__,
            "jaxlib": jaxlib.__version__,
            "platform": platform,
            "platform_version": platform_version,
            "x64": bool(jax.config.jax_enable_x64),
            "salt": LIBRARY_SALT,
        }
    return dict(_fp_cache)


def _reset_fingerprint():
    """Test hook: forget the cached fingerprint (e.g. after
    monkeypatching LIBRARY_SALT)."""
    global _fp_cache
    _fp_cache = None


def aval_sig(arrays) -> Tuple:
    """Canonical (shape, dtype) signature of an argument list.

    Nested containers are flattened (the SPMD trainer passes pytrees);
    the signature is identical for a concrete array, a numpy
    array/scalar, and a ``jax.ShapeDtypeStruct`` of the same aval, so
    manifests recorded from live arguments can warm-start from
    abstract ones.  Non-array leaves (python scalars) degrade to their
    type name.
    """
    if any(isinstance(a, (tuple, list, dict)) for a in arrays):
        from jax import tree_util
        arrays = tree_util.tree_leaves(list(arrays))
    sig = []
    for a in arrays:
        dtype = getattr(a, "dtype", None)
        if dtype is None:
            sig.append((type(a).__name__,))
        else:
            shape = getattr(a, "shape", ()) or ()
            sig.append((tuple(int(d) for d in shape), str(dtype)))
    return tuple(sig)


def sig_to_json(sig) -> list:
    """JSON-able form of :func:`aval_sig` output (manifests).  A
    1-tuple (non-array leaf, carries a type NAME) becomes ``[name]`` —
    never ``list(name)``, which would shatter the string into
    characters and poison every later ``sig_from_json``."""
    return [[entry[0]] if len(entry) == 1
            else [list(entry[0]), entry[1]] for entry in sig]


def sig_from_json(data) -> Tuple:
    out = []
    for entry in data:
        if len(entry) == 1:
            out.append((entry[0] if isinstance(entry[0], str)
                        else tuple(entry[0]),))
        else:
            out.append((tuple(int(d) for d in entry[0]), entry[1]))
    return tuple(out)


def _sanitize(name: str) -> str:
    return "".join(c if c.isalnum() or c in "._-" else "_"
                   for c in name)[:80]


def entry_hash(persist_name: str, sig, donate, avals) -> str:
    canon = repr((persist_name, sig, tuple(donate), avals,
                  tuple(sorted(fingerprint().items()))))
    return hashlib.sha256(canon.encode()).hexdigest()[:32]


def _entry_path(persist_name: str, h: str) -> str:
    return os.path.join(cache_dir(),
                        f"{_sanitize(persist_name)}-{h}{_SUFFIX}")


# -- counters ----------------------------------------------------------------

def counters() -> Dict[str, Any]:
    """``{"hits", "misses", "seconds_saved"}`` for ``cache_info()``."""
    with _lock:
        return {"hits": _hits, "misses": _misses,
                "seconds_saved": round(_seconds_saved, 3)}


def reset_counters():
    global _hits, _misses, _seconds_saved
    with _lock:
        _hits = _misses = 0
        _seconds_saved = 0.0


def _note_hit(op: str, meta: dict):
    global _hits, _seconds_saved
    saved = float(meta.get("compile_seconds", 0.0) or 0.0)
    with _lock:
        _hits += 1
        _seconds_saved += saved
    t = _telemetry()
    if t._switch.enabled:
        t.counter("mxtpu_persist_hits_total",
                  "compiled executables served from the persistent "
                  "tier").inc()
        t.gauge("mxtpu_compile_seconds_saved",
                "compile wall-clock skipped via persistent-cache hits "
                "this process").set(_seconds_saved)
        t.record_event("persist_hit", op=op,
                       payload=meta.get("kind"),
                       saved_s=round(saved, 3))


def _note_miss(op: str):
    global _misses
    with _lock:
        _misses += 1
    t = _telemetry()
    if t._switch.enabled:
        t.counter("mxtpu_persist_misses_total",
                  "persistent-tier lookups that fell through to a "
                  "fresh compile").inc()


# -- entry IO ----------------------------------------------------------------

def _write_entry(path: str, header: dict, payload: bytes):
    blob = json.dumps(header, sort_keys=True).encode()
    tmp = path + f".tmp{os.getpid()}"
    with open(tmp, "wb") as f:
        f.write(_MAGIC)
        f.write(_struct.pack("<QQ", len(blob), len(payload)))
        f.write(blob)
        f.write(payload)
    os.replace(tmp, path)  # atomic: readers never see a torn entry


def _read_entry(path: str, want_payload: bool = True):
    """(header, payload, payload_bytes) — raises on ANY malformation
    (callers catch).  ``payload_bytes`` is the frame's recorded payload
    length, reported even when ``want_payload=False`` so ls/verify get
    serialized-executable sizes without a second open+parse."""
    with open(path, "rb") as f:
        if f.read(len(_MAGIC)) != _MAGIC:
            raise ValueError("bad magic")
        hdr = f.read(16)
        if len(hdr) != 16:
            raise ValueError("truncated header")
        n_hdr, n_payload = _struct.unpack("<QQ", hdr)
        blob = f.read(n_hdr)
        if len(blob) != n_hdr:
            raise ValueError("truncated header json")
        header = json.loads(blob)
        if not want_payload:
            return header, None, n_payload
        payload = f.read(n_payload)
        if len(payload) != n_payload:
            raise ValueError("truncated payload")
        if hashlib.sha256(payload).hexdigest() != \
                header.get("payload_sha256"):
            raise ValueError("payload checksum mismatch")
        return header, payload, n_payload


def contains(persist_name: str, sig, donate, avals) -> bool:
    """Cheap existence probe (no payload read, no deserialization) —
    used by callers that must pre-trace host-side bookkeeping before a
    persist hit skips the trace (CompiledStep's aux routing)."""
    if not enabled():
        return False
    return os.path.exists(
        _entry_path(persist_name,
                    entry_hash(persist_name, sig, donate, avals)))


def fetch(persist_name: str, sig, donate, avals,
          count: bool = True) -> Optional[Tuple[Any, dict]]:
    """Load a persisted executable: ``(callable, header)`` or ``None``.

    Never raises.  A corrupt/mismatched entry is deleted (best-effort)
    and reported as a miss — the caller's fresh compile will rewrite
    it.
    """
    if not enabled():
        return None
    h = entry_hash(persist_name, sig, donate, avals)
    path = _entry_path(persist_name, h)
    if not os.path.exists(path):
        if count:
            _note_miss(persist_name)
        return None
    try:
        header, payload, _ = _read_entry(path)
        if header.get("fingerprint") != fingerprint() or \
                header.get("format") != _FORMAT:
            raise ValueError("fingerprint/format mismatch")
        fn = _deserialize(header, payload, donate)
    except Exception as e:
        t = _telemetry()
        if t._switch.enabled:
            t.record_event("persist_error", op=persist_name,
                           error=repr(e)[:300], file=os.path.basename(path))
        try:
            os.remove(path)
        except OSError:
            pass
        if count:
            _note_miss(persist_name)
        return None
    try:
        os.utime(path)            # LRU recency
    except OSError:
        pass
    if count:
        _note_hit(persist_name, header)
    return fn, header


# process-lifetime strong refs to every DESERIALIZED executable
# (``se.deserialize_and_load`` results).  The PR 13 CAUTION made this
# load-bearing: on jaxlib CPU, letting a deserialized sharded
# executable be garbage-collected after ``engine.clear_cache()`` —
# while the runtime still holds internal references — segfaults/aborts
# the process NONDETERMINISTICALLY later on (reproduced bracketing the
# warm-start persist tests with extra clears).  Keeping the loaded
# objects alive for the life of the process sidesteps the teardown
# entirely: a deserialized executable is small (the serialized bytes
# already lived on disk), and repeated clear_cache() calls are now
# safe around persist reloads.  See docs/compile_cache.md ("Safe
# cache-clear recipe").
_loaded_execs: list = []


def deserialized_alive() -> int:
    """How many deserialized executables the keep-alive guard holds
    (diagnostics + the clear_cache regression test)."""
    return len(_loaded_execs)


def _deserialize(header: dict, payload: bytes, donate):
    kind = header.get("kind")
    if kind == "exec":
        import pickle
        from jax.experimental import serialize_executable as se
        import jax
        blob, in_tree, out_tree = pickle.loads(payload)
        # an executable is compiled for an exact device list, and
        # deserialize_and_load otherwise assumes EVERY device of the
        # default backend (a one-device program reloaded on an 8-device
        # host then demands 8 shards at call time).  A recorded device
        # this process does not have is a KeyError -> miss -> fresh
        # compile.
        platform, ids = header["devices"]
        by_id = {d.id: d for d in jax.devices(platform)}
        fn = se.deserialize_and_load(
            blob, in_tree, out_tree, backend=platform,
            execution_devices=[by_id[i] for i in ids])
        _loaded_execs.append(fn)
        return fn
    if kind == "export":
        import jax
        import jax.export  # explicit: not re-exported from the jax ns
        exported = jax.export.deserialize(payload)
        # reload re-pays only the XLA compile of the serialized
        # StableHLO — the Python trace is skipped.  Donation best
        # effort: the exported call is re-jitted with the same donate
        # positions (aliasing depends on backend support).
        return jax.jit(exported.call,
                       donate_argnums=tuple(donate)) if donate \
            else jax.jit(exported.call)
    raise ValueError(f"unknown payload kind {kind!r}")


def save_compiled(persist_name: str, sig, donate, avals, jitted,
                  compiled, compile_seconds: float,
                  example_args=None, memory=None) -> bool:
    """Serialize ``compiled`` (fallback: ``jax.export`` of ``jitted``)
    into the cache dir.  Never raises; returns True when an entry was
    written.  ``memory``: the observatory's harvest record for this
    program — a compact slice is embedded in the entry header so
    ``tools/mxcache.py ls`` can show per-entry peak bytes offline."""
    if not enabled():
        return False
    payload, kind, devices = None, None, None
    try:
        import pickle
        from jax.experimental import serialize_executable as se
        payload = pickle.dumps(se.serialize(compiled))
        # the same object se.serialize reads: the exact, ordered device
        # list the executable was compiled for (see _deserialize)
        dl = compiled._executable._unloaded_executable.device_list
        devices = [dl[0].platform, [int(d.id) for d in dl]]
        kind = "exec"
    except Exception:
        # backend executable serialization unavailable: fall back to
        # the StableHLO artifact (deploy.py's seam) — reload skips the
        # trace and re-pays only the XLA compile
        try:
            import jax
            import jax.export
            exported = jax.export.export(jitted)(
                *(example_args if example_args is not None else ()))
            payload = exported.serialize()
            kind = "export"
        except Exception as e:
            t = _telemetry()
            if t._switch.enabled:
                t.record_event("persist_error", op=persist_name,
                               error=f"serialize failed: {e!r}"[:300])
            return False
    header = {
        "format": _FORMAT,
        "kind": kind,
        "op": persist_name,
        "attrs": repr(sig),
        "donate": [int(d) for d in donate],
        "devices": devices,
        "avals": sig_to_json(avals),
        "fingerprint": fingerprint(),
        "compile_seconds": round(float(compile_seconds), 4),
        "created": time.time(),
        "payload_sha256": hashlib.sha256(payload).hexdigest(),
    }
    if memory:
        header["memory"] = {
            k: memory.get(k)
            for k in ("peak_bytes", "argument_bytes", "output_bytes",
                      "temp_bytes", "generated_code_bytes",
                      "donation_saved_bytes", "flops",
                      "collective_wire_bytes", "analytic",
                      # per-kind table: a persist reload reuses it so
                      # the warm-start path never re-renders HLO text
                      "collectives")}
    try:
        os.makedirs(cache_dir(), exist_ok=True)
        path = _entry_path(
            persist_name, entry_hash(persist_name, sig, donate, avals))
        _write_entry(path, header, payload)
        prune()
    except OSError as e:
        t = _telemetry()
        if t._switch.enabled:
            t.record_event("persist_error", op=persist_name,
                           error=f"write failed: {e!r}"[:300])
        return False
    return True


def tiered_compile(persist_name: str, jitted, args, donate=(),
                   sig=(), op_label: Optional[str] = None):
    """Memory-miss resolution shared by the engine's tiered wrapper and
    the SPMD trainer: persistent tier -> fresh AOT compile (+ save).

    ``args`` may be concrete arrays or ``ShapeDtypeStruct``s.  Returns
    ``(callable, source)`` with source ``"persist"`` or ``"compiled"``.

    This is also THE harvest seam of the memory observatory
    (``telemetry.memory``): the explicit ``lower().compile()`` is what
    makes a compiled-executable object exist, and both branches — a
    reload and a fresh compile — hand it to ``harvest_compiled`` for
    per-program memory/FLOPs/collective accounting (never-raises,
    inert under ``MXTPU_TELEMETRY=0``).
    """
    from ..telemetry import memory as _mem
    avals = aval_sig(args)
    hit = fetch(persist_name, sig, donate, avals)
    if hit is not None:
        _mem.harvest_compiled(op_label or persist_name, hit[0],
                              args=args, donate=donate,
                              source="persist",
                              cached_memory=hit[1].get("memory"))
        return hit[0], "persist"
    t0 = time.perf_counter()
    lowered = jitted.lower(*args)
    compiled = lowered.compile()
    dt = time.perf_counter() - t0
    from . import _note_fresh_compile
    _note_fresh_compile(op_label or persist_name, dt)
    try:
        from jax import tree_util as _tu
        out_avals = _tu.tree_leaves(lowered.out_info)
    except Exception:
        out_avals = None
    mem_rec = _mem.harvest_compiled(op_label or persist_name, compiled,
                                    args=args, donate=donate,
                                    out_avals=out_avals,
                                    source="fresh")
    save_compiled(persist_name, sig, donate, avals, jitted, compiled,
                  dt, example_args=args, memory=mem_rec)
    return compiled, "compiled"


# -- maintenance (mxcache CLI / mxlint gate) ---------------------------------

def _entries(directory: Optional[str] = None) -> List[str]:
    d = directory or cache_dir()
    if not d or not os.path.isdir(d):
        return []
    return sorted(os.path.join(d, f) for f in os.listdir(d)
                  if f.endswith(_SUFFIX))


def ls(directory: Optional[str] = None) -> List[dict]:
    """One dict per entry (corrupt entries flagged, never raised).
    ``payload_bytes`` is the serialized-executable size alone;
    ``memory`` (when the writer harvested it) carries the program's
    peak/argument/donation byte accounting for offline inspection."""
    out = []
    for path in _entries(directory):
        row = {"file": os.path.basename(path),
               "bytes": os.path.getsize(path),
               "payload_bytes": None,
               "mtime": os.path.getmtime(path)}
        try:
            header, _, n_payload = _read_entry(path, want_payload=False)
            row["payload_bytes"] = n_payload
            row.update(op=header.get("op"), kind=header.get("kind"),
                       compile_seconds=header.get("compile_seconds"),
                       memory=header.get("memory"),
                       ok=True)
        except Exception as e:
            row.update(ok=False, error=repr(e)[:200])
        out.append(row)
    return out


def verify(directory: Optional[str] = None) -> List[dict]:
    """Full integrity pass: header parse + payload checksum + current
    fingerprint match.  Returns one dict per entry with ``ok`` /
    ``error`` (``stale`` marks a well-formed entry another
    jax/platform wrote — unusable here but not corruption)."""
    out = []
    for path in _entries(directory):
        row = {"file": os.path.basename(path), "ok": True,
               "stale": False, "payload_bytes": None}
        try:
            header, _, n_payload = _read_entry(path)
            row["payload_bytes"] = n_payload
            if header.get("fingerprint") != fingerprint():
                row["stale"] = True
        except Exception as e:
            row.update(ok=False, error=repr(e)[:200])
        out.append(row)
    return out


def prune(limit: Optional[int] = None,
          directory: Optional[str] = None) -> int:
    """Evict least-recently-used entries until the dir fits ``limit``
    bytes (default ``MXTPU_COMPILE_CACHE_MAX_BYTES``).  Returns the
    number of files removed."""
    if limit is None:
        limit = max_bytes()
    paths = _entries(directory)
    sized = []
    for p in paths:
        try:
            sized.append((os.path.getmtime(p), os.path.getsize(p), p))
        except OSError:
            continue
    total = sum(s for _, s, _ in sized)
    removed = 0
    for _, size, path in sorted(sized):      # oldest mtime first
        if total <= limit:
            break
        try:
            os.remove(path)
            removed += 1
            total -= size
        except OSError:
            continue
    return removed


def clear(directory: Optional[str] = None) -> int:
    """Remove every entry; returns the count."""
    removed = 0
    for path in _entries(directory):
        try:
            os.remove(path)
            removed += 1
        except OSError:
            continue
    return removed


def drop(name: str, directory: Optional[str] = None) -> int:
    """Remove entries whose recorded op starts with ``name`` (the
    persistent scope of ``engine.drop_cached``).  Filename prefixes
    make the common case cheap; headers disambiguate truncation."""
    removed = 0
    want = _sanitize(name)
    for path in _entries(directory):
        base = os.path.basename(path)
        if not base.startswith(want):
            continue
        try:
            header, _, _ = _read_entry(path, want_payload=False)
            op = header.get("op", "")
        except Exception:
            op = name                     # corrupt + name-prefixed: drop
        if op == name or op.startswith(name):
            try:
                os.remove(path)
                removed += 1
            except OSError:
                continue
    return removed
