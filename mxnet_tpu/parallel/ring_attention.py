"""Ring attention: sequence/context parallelism over a mesh axis.

Capability: long-context scaling the reference never had (SURVEY.md §5
"Long-context / sequence parallelism" — listed as a required first-class
capability of the rebuild).  The sequence axis is sharded over the ``sp``
mesh axis; each device holds its Q shard permanently and passes K/V
shards around the ring with ``lax.ppermute`` (XLA lowers to ICI RDMA on a
TPU torus — the same pattern as pallas_guide.md §18's ring collectives,
expressed at the collective level so it is differentiable and testable on
a CPU mesh).  Online-softmax accumulation keeps memory O(S/devices) per
chip.
"""
from __future__ import annotations

from functools import partial

import numpy as np

from ..base import MXNetError
from .mesh import current_mesh

__all__ = ["ring_attention", "ring_attention_sharded"]


def _ring_attention_local(q, k, v, axis_name, scale, causal_offset=None):
    """Per-shard body (runs inside shard_map).

    q: (B, Sq_local, H, D); k/v: (B, Sk_local, KV, D) with KV dividing H
    (grouped-query attention: each KV head serves H//KV query heads).
    Only the small KV-head tensors travel the ring — queries are grouped
    by reshape instead of materializing repeated K/V.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax

    from jax.lax import axis_size
    n = axis_size(axis_name)
    my = lax.axis_index(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]

    b, sq, h, d = q.shape
    kv = k.shape[2]
    g = h // kv
    # (B, Sq, KV, G, D): query heads grouped under their KV head
    qg = q.astype(jnp.float32).reshape(b, sq, kv, g, d)

    m = jnp.full((b, sq, kv, g, 1), -jnp.inf, jnp.float32)
    # running max / sum and (B, Sq, KV, G, D) accumulator
    l = jnp.zeros_like(m)
    acc = jnp.zeros(qg.shape, jnp.float32)

    def step(i, carry):
        k_cur, v_cur, m, l, acc = carry
        # K/V block currently held came from shard (my - i) mod n
        src = (my - i) % n
        s = jnp.einsum("bqcgd,bkcd->bqcgk", qg,
                       k_cur.astype(jnp.float32)) * scale
        if causal_offset is not None:
            sk = k_cur.shape[1]
            q_pos = my * sq + jax.lax.broadcasted_iota(
                jnp.int32, (sq, sk), 0)
            k_pos = src * sk + jax.lax.broadcasted_iota(
                jnp.int32, (sq, sk), 1)
            s = jnp.where(
                (q_pos >= k_pos)[None, :, None, None, :], s, -1e30)
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_new = alpha * l + p.sum(axis=-1, keepdims=True)
        acc_new = alpha * acc + jnp.einsum(
            "bqcgk,bkcd->bqcgd", p, v_cur.astype(jnp.float32))
        # rotate K/V to the next device; overlapped with next-step compute
        # by XLA's async collectives
        k_nxt = lax.ppermute(k_cur, axis_name, perm)
        v_nxt = lax.ppermute(v_cur, axis_name, perm)
        return k_nxt, v_nxt, m_new, l_new, acc_new

    _, _, m, l, acc = _unrolled(step, n, (k, v, m, l, acc))
    return (acc / l).reshape(q.shape).astype(q.dtype)


def _unrolled(step, n, carry):
    # static unroll: n is the mesh-axis size (small); lets XLA overlap
    # each step's ppermute with the previous step's einsum
    for i in range(n):
        carry = step(i, carry)
    return carry


# jit caches traces per function OBJECT — a fresh shard_map(partial(...))
# every call would retrace+recompile per invocation (~200x measured on an
# 8-device CPU mesh), so the jitted executable is cached per variant
_RING_EXEC_CACHE = {}


def _ring_executable(mesh, axis, scale, causal):
    import jax
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    key = (mesh, axis, float(scale), bool(causal))
    fn = _RING_EXEC_CACHE.get(key)
    if fn is None:
        spec = P(None, axis, None, None)
        fn = jax.jit(shard_map(
            partial(_ring_attention_local, axis_name=axis,
                    scale=float(scale),
                    causal_offset=True if causal else None),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec))
        _RING_EXEC_CACHE[key] = fn
    return fn


def _resolve_plan(plan, mesh, axis):
    """``plan`` (a ``planner.ShardingPlan``) supplies the mesh and the
    sequence axis (``plan.sp_axis``) — same convention as the pipeline
    entry points."""
    from .planner import resolve_plan_axis
    return resolve_plan_axis(plan, mesh, axis, "sp_axis")


def ring_attention(q, k, v, mesh=None, axis="sp", scale=None,
                   causal=False, plan=None):
    """SPMD ring attention over sequence-sharded jax arrays.

    q: (B, S_global, H, D); k/v: (B, S_global, KV, D) with KV dividing H
    (KV == H is plain multi-head attention), sharded or to-be-sharded
    along the sequence dim over ``axis``.  Returns (B, S_global, H, D)
    with the same sharding.  ``plan`` (a ``parallel.ShardingPlan``)
    supplies the mesh and the sequence axis (``plan.sp_axis``).
    """
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh, axis = _resolve_plan(plan, mesh, axis)
    mesh = mesh if mesh is not None else current_mesh()
    if axis not in mesh.axis_names:
        raise MXNetError(f"mesh has no axis {axis!r}")
    n = mesh.shape[axis]
    if q.shape[1] % n:
        raise MXNetError(
            f"sequence length {q.shape[1]} not divisible by mesh axis "
            f"{axis!r} size {n}")
    if q.shape[2] % k.shape[2]:
        raise MXNetError(
            f"query heads {q.shape[2]} not a multiple of KV heads "
            f"{k.shape[2]}")
    if scale is None:
        scale = 1.0 / np.sqrt(q.shape[-1])

    sharding = NamedSharding(mesh, P(None, axis, None, None))
    q = jax.device_put(q, sharding)
    k = jax.device_put(k, sharding)
    v = jax.device_put(v, sharding)
    return _ring_executable(mesh, axis, scale, causal)(q, k, v)


_SHARDED_OPDEF_CACHE = {}
_OPDEF_SEQ = __import__("itertools").count()


def ring_attention_sharded(q_nd, k_nd, v_nd, mesh=None, axis="sp",
                           scale=None, causal=False, plan=None):
    """NDArray wrapper around :func:`ring_attention` — on the autograd
    tape, so training through the ring path gets real gradients.

    When the inputs live on ONE device (eager model forward mixing
    single-device weights with the SP mesh), the output is brought back
    to that device — only the attention itself (the quadratic part)
    runs sequence-sharded.  Fully-sharded callers keep the sharding.

    Not usable inside a single-device CachedOp trace (hybridize): the
    shard_map needs the mesh's devices, which a one-device jit cannot
    provide — run eagerly, or inside a mesh-jitted SPMD step.
    """
    import jax
    from ..base import MXNetError
    from ..gluon.block import _is_tracing
    from ..ndarray.ndarray import invoke
    from ..ops.registry import OpDef

    if _is_tracing():
        raise MXNetError(
            "ring attention cannot run inside a single-device "
            "hybridize/CachedOp trace; call the block unhybridized or "
            "run it inside a mesh-jitted SPMD step")

    mesh, axis = _resolve_plan(plan, mesh, axis)
    mesh = mesh if mesh is not None else current_mesh()
    try:
        devs = q_nd._data.sharding.device_set
        restore = (next(iter(devs)) if len(devs) == 1 else None)
    except Exception:
        restore = None

    d = q_nd.shape[-1]
    s = float(scale) if scale is not None else 1.0 / float(np.sqrt(d))
    key = (mesh, axis, s, bool(causal), restore)
    op = _SHARDED_OPDEF_CACHE.get(key)
    if op is None:
        def fcompute(q, k, v):
            out = ring_attention(q, k, v, mesh=mesh, axis=axis,
                                 scale=s, causal=causal)
            if restore is not None:
                out = jax.device_put(out, restore)
            return out

        # placement (device_put to the mesh, restore to one device)
        # happens inside fcompute — an outer single-device jit would
        # reject the cross-device transfers
        fcompute._mxtpu_no_jit = True
        # engine.get_compiled caches executables by (op.name, attrs), so
        # the name must be unique per (mesh, axis, scale, causal, restore)
        # variant — a shared name would silently reuse the first-compiled
        # closure for every later variant
        op = OpDef("_ring_attention_%d" % next(_OPDEF_SEQ),
                   fcompute, 3, 1, (), False, None)
        _SHARDED_OPDEF_CACHE[key] = op
    return invoke(op, [q_nd, k_nd, v_nd])
