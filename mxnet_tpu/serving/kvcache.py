"""State plane: preallocated per-slot state buffers as DONATED carry
state (docs/serving.md, "State kinds").

A bucket's state is the flat list of buffers the model's
``state_spec(slots, cache_len, dtype)`` names: rows ``(name, kind, shape,
dtype)`` with ``shape[0] == slots``, any rank, any dtype.  Slot ``j`` of
every buffer is request ``j``'s.  ``kind`` says what a buffer is (it
labels gauges and manifests; the shape is what the programs use):

* ``kv_full``   K or V of a full-attention layer, ``(slots, cache_len,
  kv_heads, head_dim)``: grows with the request;
* ``kv_window`` K or V of a sliding-window layer, as long as the model's
  window asks (a rolling buffer, or a full page behind a banded mask);
* ``kv_latent`` the rows of a latent-attention layer, ``(slots,
  cache_len, kv_rank + rope_dim)``: ONE buffer a layer holds the normed
  latent and the rotated shared key of each position, all heads' keys
  and values in one row (``models/pangu_moe.py``);
* ``ssm``       a recurrent state, ``(slots, d_state, d_inner)``;
* ``conv``      the tail of inputs a causal convolution continues from.

A decoder of identical attention layers declares two ``kv_*`` buffers a
layer; a hybrid declares what each layer kind holds, and nothing for a
layer that reads another layer's buffers.  After the model's buffers the
pool holds one of its own, the slots' LAST TOKENS (``(slots, 1)``
float32): the admit program writes a slot's first token there, the
decode programs read it as their input and write the next one back, so
no decode waits for the host to have read the one before
(docs/serving.md, "A round").  It is no row of ``spec`` and no byte of
``nbytes``: those are the model's.  Every dispatch donates the
whole pool to the compiled program (the PR 2/3 donation protocol): the
executable updates each active slot in place and returns the successor
buffers, so a decode step never doubles state HBM.  ``adopt()`` swaps the
successors in; a dispatch that fails AFTER the donation consumed the
buffers latches ``poisoned`` (the pool holds dead arrays) and ``reset()``
— driven by ``Server.recover()`` — rebuilds zeroed buffers.

Slot lifecycle is content-swap only: admission scatters a freshly
prefilled batch-1 state into slot ``j`` (one ``lax.dynamic_update_slice``
per buffer, at the buffer's own rank, inside the admit program), eviction
just drops the slot's active-mask bit on the host.  Shapes never change,
so steady state retraces NOTHING (docs/serving.md, "Bucket anatomy").
"""
from __future__ import annotations

import math
from typing import List, Optional

from ..base import MXNetError

__all__ = ["KVCachePool", "STATE_KINDS", "check_spec"]

STATE_KINDS = ("kv_full", "kv_window", "kv_latent", "ssm", "conv")


def check_spec(spec, slots: int):
    """Normalize a model's ``state_spec`` rows to ``(name, kind, shape,
    dtype)`` tuples of plain types and hold them to the contract."""
    rows = []
    for name, kind, shape, dtype in spec:
        shape = tuple(int(x) for x in shape)
        if kind not in STATE_KINDS:
            raise MXNetError(f"state buffer {name!r}: kind {kind!r} is not "
                             f"one of {STATE_KINDS}")
        if not shape or shape[0] != slots:
            raise MXNetError(f"state buffer {name!r}: shape {shape} must "
                             f"lead with the slot dim {slots}")
        rows.append((str(name), str(kind), shape, str(dtype)))
    if not rows:
        raise MXNetError("the model's state_spec names no buffer")
    return rows


class KVCachePool:
    """Per-bucket preallocated state for ``slots`` concurrent requests of
    ``lm``, built from ``lm.state_spec``.

    Args:
      lm: a model-zoo decoder (anything with ``state_spec``).
      slots: concurrent requests the pool holds (the bucket batch dim).
      cache_len: positions per slot (bucket prompt length + the
        server's max new tokens).
      ctx: device context for the buffers.
      dtype: cache dtype (float; ``bfloat16`` halves page HBM and
        decode bandwidth — ``state_spec`` validates, and may keep a
        buffer in a type of its own: a recurrent state stays float32).
      sharding: optional ``jax.sharding.NamedSharding`` for the buffers
        — the sharding planner's decode spec (``ShardingPlan.decode``,
        typically the slot dim over ``dp``).  Applied after EVERY
        build (construction AND :meth:`reset`), so a recovery can
        never silently drop the planned layout.
    """

    def __init__(self, lm, slots: int, cache_len: int, ctx=None,
                 dtype: str = "float32", sharding=None):
        if slots < 1 or cache_len < 1:
            raise MXNetError(
                f"KVCachePool needs slots >= 1 and cache_len >= 1, got "
                f"{slots}/{cache_len}")
        self.slots = int(slots)
        self.cache_len = int(cache_len)
        self.ctx = ctx
        self.dtype = str(dtype)
        self.sharding = sharding
        self.poisoned: Optional[str] = None
        self.spec = check_spec(
            lm.state_spec(self.slots, self.cache_len, self.dtype),
            self.slots)
        self._bufs: List = self._build()

    def _build(self):
        """The spec's buffers, then the last-token vector."""
        from .. import ndarray as nd
        bufs = [nd.zeros(shape, ctx=self.ctx, dtype=dtype)
                for _name, _kind, shape, dtype in self.spec]
        bufs.append(nd.zeros((self.slots, 1), ctx=self.ctx,
                             dtype="float32"))
        if self.sharding is not None:
            import jax
            from jax.sharding import NamedSharding, PartitionSpec as P
            for b in bufs[:-1]:
                b._set_data(jax.device_put(b._data, self.sharding))
            # the token vector has the slot dim and one more: it takes
            # the spec's leading entry only
            bufs[-1]._set_data(jax.device_put(
                bufs[-1]._data, NamedSharding(
                    self.sharding.mesh, P(*self.sharding.spec[:1]))))
        return bufs

    @property
    def num_buffers(self) -> int:
        """Buffers a dispatch donates: the spec's and the token vector."""
        return len(self._bufs)

    def buffers(self) -> list:
        """The live NDArrays, in :meth:`flat`'s order."""
        return list(self._bufs)

    def flat(self) -> list:
        """Flat jax buffers in donate order (spec order, then the token
        vector) — exactly the slice of the dispatch argument list the
        donate tuple names."""
        return [b._data for b in self._bufs]

    def nbytes(self) -> int:
        """Bytes of the model's state (the spec's buffers)."""
        return sum(int(b._data.nbytes) for b in self._bufs[:-1])

    def bytes_by_kind(self) -> dict:
        """{kind: bytes}, from the spec (what the buffers hold, not how a
        device tiles them)."""
        import jax.numpy as jnp
        out = {}
        for _name, kind, shape, dtype in self.spec:
            out[kind] = out.get(kind, 0) \
                + math.prod(shape) * jnp.dtype(dtype).itemsize
        return out

    def adopt(self, new_flat):
        """Swap the post-dispatch successor buffers in (the donated
        predecessors are already dead)."""
        if len(new_flat) != len(self._bufs):
            raise MXNetError(
                f"adopt: expected {len(self._bufs)} buffers (the "
                f"state and the token vector), got {len(new_flat)}")
        for b, new in zip(self._bufs, new_flat):
            b._set_data(new)

    def poison(self, error: str):
        """Latch the post-donation-failure state: the buffers were
        consumed by a dispatch that died, so nothing here is
        dispatchable until :meth:`reset`."""
        self.poisoned = error

    def consumed(self) -> bool:
        """Did a dispatch actually consume the buffers?  (Distinguishes
        post-donation failures — dead buffers — from pre-dispatch
        trace/compile errors that left everything alive.)"""
        return any(getattr(b._data, "is_deleted", lambda: False)()
                   for b in self._bufs)

    def reset(self):
        """Rebuild zeroed buffers and clear the poison latch (the
        recovery half of the donation protocol — every resident
        request must be re-prefilled by the caller)."""
        self._bufs = self._build()
        self.poisoned = None
