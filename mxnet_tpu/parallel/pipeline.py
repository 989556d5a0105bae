"""Pipeline parallelism: GPipe-style microbatching over a ``pp`` mesh
axis.

Beyond-reference capability (the reference's closest analog is the
manual model-parallel LSTM example — SURVEY.md §2.3 "Pipeline parallel:
none"); built because the rebuild treats pp as a first-class mesh axis
alongside dp/tp/sp/ep.

TPU-first design: the schedule is SPMD — every device runs the same
program over its own stage's parameters (stages must therefore share
one structure, the transformer-stack case); activations hop stage→
stage with ``lax.ppermute`` (ICI neighbor transfer on a TPU torus) and
the M+P-1 step loop is statically unrolled so XLA overlaps each hop
with the next step's compute.  Differentiable end-to-end (the schedule
is plain traced code), so it composes with ``jax.grad`` and the fused
trainer.
"""
from __future__ import annotations

from functools import partial

import numpy as np

from ..base import MXNetError
from .mesh import current_mesh

__all__ = ["pipeline_apply", "pipeline_value_and_grad"]


def _local_schedule(params, xs, *, stage_fn, axis, n_microbatches):
    """Per-device body (runs inside shard_map).

    params: this stage's param pytree (leading stage dim of size 1);
    xs: (M, mb, ...) microbatches (replicated); returns (M, mb, ...) —
    nonzero only on the LAST stage, made global with a psum.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax

    from jax.lax import axis_size
    n = axis_size(axis)
    p = lax.axis_index(axis)
    m = n_microbatches
    perm = [(i, (i + 1) % n) for i in range(n)]
    local_params = jax.tree_util.tree_map(lambda a: a[0], params)

    carry = jnp.zeros_like(xs[0])
    ys = jnp.zeros_like(xs)
    for t in range(m + n - 1):
        mb = t - p                      # microbatch this stage works on
        active = (mb >= 0) & (mb < m)
        idx = jnp.clip(mb, 0, m - 1)
        x_in = jnp.where(p == 0, xs[idx], carry)
        out = stage_fn(local_params, x_in)
        out = jnp.where(active, out, jnp.zeros_like(out))
        is_last = p == n - 1
        ys = ys.at[idx].add(jnp.where(active & is_last, out,
                                      jnp.zeros_like(out)))
        carry = lax.ppermute(out, axis, perm)
    # only the last stage holds results; sum-replicate across the axis
    return lax.psum(ys, axis)


_EXEC_CACHE = {}
_EXEC_CACHE_MAX = 64  # FIFO-bounded: a pathological caller cannot leak
                      # executables without bound


_HASH_MEMO = {}  # id -> (weakref, content hash): arrays hashed ONCE


def _capture_key(c):
    """Structural key for one closure capture."""
    if isinstance(c, (int, float, bool, str, bytes, type(None))):
        # include the type: ('v', 2) == ('v', 2.0) == ('v', True) would
        # otherwise alias executables compiled for different dtypes
        return ("v", type(c).__name__, c)
    try:
        import weakref
        memo = _HASH_MEMO.get(id(c))
        if memo is not None and memo[0]() is c:
            return memo[1]
        a = np.asarray(c)
        if a.dtype != object:
            key = ("a", a.shape, str(a.dtype), hash(a.tobytes()))
            try:
                # memoize per object so big device arrays pay the
                # device→host copy + hash ONCE, not per call
                _HASH_MEMO[id(c)] = (weakref.ref(c), key)
                if len(_HASH_MEMO) > 512:
                    _HASH_MEMO.pop(next(iter(_HASH_MEMO)))
            except TypeError:
                pass  # object not weakref-able: hash each call
            return key
    except Exception:
        pass
    return ("o", id(c))  # retained via the cache entry while cached


def _structural_fn_key(fn):
    """Key a callable structurally (code object + closure captures) so
    per-call lambdas with identical source hit the exec cache; closure
    captures are keyed by VALUE for scalars and by content hash for
    arrays (so equal re-created captures hit), falling back to
    identity (retained in the entry) for opaque objects.  Returns
    (key, captured) — captured must be retained alongside the cache
    entry so ids stay live."""
    code = getattr(fn, "__code__", None)
    closure = getattr(fn, "__closure__", None) or ()
    captured = tuple(c.cell_contents for c in closure)
    key = ((code.co_code, repr(code.co_consts),
            tuple(_capture_key(c) for c in captured))
           if code is not None else fn)
    return key, captured


def _resolve_specs(stacked_params, param_specs, axis):
    """Per-leaf PartitionSpecs: default P(axis); a caller-supplied
    pytree (matching stacked_params' structure) lets individual leaves
    carry EXTRA mesh axes — e.g. P('pp', 'tp') column-parallel layer
    weights, composing pipeline with tensor parallelism.  Every spec
    must keep ``axis`` on the leading (stage) dim."""
    import jax
    from jax.sharding import PartitionSpec as P

    if param_specs is None:
        return jax.tree_util.tree_map(lambda _: P(axis),
                                      stacked_params)
    def _check(_, s):
        if not len(s) or s[0] != axis:
            raise MXNetError(
                f"param_specs leaf {s} must shard the leading stage "
                f"dim over {axis!r}")

    jax.tree_util.tree_map(_check, stacked_params, param_specs)
    return param_specs


def _resolve_plan(plan, mesh, axis):
    """A ``planner.ShardingPlan`` supplies BOTH the named mesh and the
    stage axis (``plan.pp_axis``) — the planner is the one source of
    truth for axis names."""
    from .planner import resolve_plan_axis
    return resolve_plan_axis(plan, mesh, axis, "pp_axis")


def _validate_and_place(fname, stacked_params, x, n_microbatches,
                        mesh, axis, y=None, param_specs=None):
    """Shared arg validation + param placement for the pipeline entry
    points.  Returns (mesh, n_stages, placed params, specs)."""
    import jax
    from jax.sharding import NamedSharding

    mesh = mesh if mesh is not None else current_mesh()
    if axis not in mesh.axis_names:
        raise MXNetError(f"mesh has no axis {axis!r}")
    n = mesh.shape[axis]
    leaves = jax.tree_util.tree_leaves(stacked_params)
    if any(l.shape[0] != n for l in leaves):
        raise MXNetError(
            f"{fname}: stacked param leading dims "
            f"{[l.shape[0] for l in leaves]} must equal the {axis!r} "
            f"axis size {n}")
    if x.shape[0] % n_microbatches:
        raise MXNetError(
            f"batch {x.shape[0]} not divisible by n_microbatches "
            f"{n_microbatches}")
    if y is not None and y.shape[0] != x.shape[0]:
        raise MXNetError(
            f"{fname}: y batch {y.shape[0]} != x batch {x.shape[0]}")
    specs = _resolve_specs(stacked_params, param_specs, axis)
    params = jax.tree_util.tree_map(
        lambda l, s: jax.device_put(l, NamedSharding(mesh, s)),
        stacked_params, specs)
    return mesh, n, params, specs


def pipeline_apply(stage_fn, stacked_params, x, n_microbatches,
                   mesh=None, axis="pp", param_specs=None, plan=None):
    """Apply ``n_stages`` homogeneous stages as a GPipe pipeline.

    stage_fn(params_i, x_mb) -> y_mb (same shape as x_mb);
    stacked_params: pytree whose leaves have leading dim n_stages
    (sharded over ``axis``); x: (batch, ...) jax array — split into
    ``n_microbatches`` along dim 0.  Returns (batch, ...).
    ``param_specs`` (optional pytree of PartitionSpecs) lets leaves
    carry extra mesh axes — e.g. ``P('pp', 'tp')`` tensor-parallel
    weights, with ``stage_fn`` issuing the matching ``tp``
    collectives.

    The jitted executable is cached per (mesh, axis, stage_fn, shapes).
    ``plan`` (a ``parallel.ShardingPlan``) supplies the mesh AND the
    stage axis (``plan.pp_axis``) — the planner's axis names instead
    of an ad-hoc string.
    """
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    mesh, axis = _resolve_plan(plan, mesh, axis)
    mesh, n, params, specs = _validate_and_place(
        "pipeline_apply", stacked_params, x, n_microbatches, mesh,
        axis, param_specs=param_specs)
    leaves = jax.tree_util.tree_leaves(stacked_params)
    fn_key, captured = _structural_fn_key(stage_fn)
    key = (mesh, axis, fn_key, n_microbatches,
           tuple(l.shape for l in leaves), x.shape, str(x.dtype),
           tuple(str(s) for s in jax.tree_util.tree_leaves(
               specs, is_leaf=lambda s: isinstance(s, P))))
    entry = _EXEC_CACHE.get(key)
    fn = entry[0] if entry is not None else None
    if fn is None:
        rspec = P()
        body = shard_map(
            partial(_local_schedule, stage_fn=stage_fn, axis=axis,
                    n_microbatches=n_microbatches),
            mesh=mesh,
            in_specs=(specs, rspec),
            out_specs=rspec)

        def run(params, xb):
            xs = xb.reshape((n_microbatches,
                             xb.shape[0] // n_microbatches)
                            + xb.shape[1:])
            ys = body(params, xs)
            return ys.reshape(xb.shape)

        fn = jax.jit(run)
        # retain the captured objects so their ids stay live while the
        # cache entry exists (no id-reuse aliasing); FIFO-evict so the
        # cache cannot grow without bound
        while len(_EXEC_CACHE) >= _EXEC_CACHE_MAX:
            _EXEC_CACHE.pop(next(iter(_EXEC_CACHE)))
        _EXEC_CACHE[key] = (fn, captured)

    return fn(params, x)


def _local_1f1b(params, xs, ys, *, stage_fn, loss_fn, axis,
                n_microbatches, grad_fix=None):
    """Per-device 1F1B schedule (runs inside shard_map).

    Interleaved one-forward-one-backward over ``R = m + 2(n-1)``
    rounds: stage p forwards microbatch ``r - p`` and backwards
    microbatch ``r - 2(n-1) + p`` in round r, so the last stage runs
    its backward immediately after its forward (the 1F1B signature)
    and every stage holds at most ``2(n-1)+1`` stashed activations —
    bounded by PIPELINE DEPTH, not by the microbatch count (GPipe via
    plain autodiff keeps all m alive).

    The stash is a ring buffer of INPUT activations only (a jax array,
    so the traced per-stage slot index can dynamically select into
    it); the backward recomputes the stage forward under ``jax.vjp``
    — the standard remat trade (≈1 extra forward) that makes the
    schedule static-shape and SPMD-uniform.  Activations hop stage→
    stage with ``lax.ppermute`` (+1 forward, −1 cotangent), one
    neighbor transfer each way per round on a TPU torus.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax

    from jax.lax import axis_size
    n = axis_size(axis)
    p = lax.axis_index(axis)
    m = n_microbatches
    local = jax.tree_util.tree_map(lambda a: a[0], params)
    perm_fwd = [(i, (i + 1) % n) for i in range(n)]
    perm_bwd = [((i + 1) % n, i) for i in range(n)]
    depth = 2 * (n - 1) + 1
    mb_shape = xs[0].shape

    ring = jnp.zeros((depth,) + mb_shape, xs.dtype)
    fcarry = jnp.zeros(mb_shape, xs.dtype)
    bcarry = jnp.zeros(mb_shape, xs.dtype)
    grad_acc = jax.tree_util.tree_map(
        lambda a: jnp.zeros_like(a, jnp.float32), local)
    loss_acc = jnp.zeros((), jnp.float32)
    is_last = p == n - 1

    R = m + 2 * (n - 1)
    for r in range(R):
        # ---- forward half-round
        f = r - p
        f_active = (f >= 0) & (f < m)
        fidx = jnp.clip(f, 0, m - 1)
        x_in = jnp.where(p == 0, xs[fidx], fcarry)
        out = stage_fn(local, x_in)
        # last stage: loss for THIS microbatch + cotangent wrt out
        loss_mb, loss_vjp = jax.vjp(
            lambda o: loss_fn(o, ys[fidx]), out)
        # the seed cotangent must carry the same device-varying type
        # as loss_mb under shard_map's manual-axes checking — derive
        # it from loss_mb instead of a fresh (replicated) constant
        (dy,) = loss_vjp(loss_mb * 0 + 1)
        loss_acc = loss_acc + jnp.where(
            f_active & is_last, loss_mb.astype(jnp.float32), 0.0)
        # stash the stage INPUT at this round's slot (static index)
        ring = ring.at[r % depth].set(
            jnp.where(f_active, x_in, ring[r % depth]))
        fcarry = lax.ppermute(out, axis, perm_fwd)

        # ---- backward half-round
        b = r - 2 * (n - 1) + p
        b_active = (b >= 0) & (b < m)
        # the slot this stage forwarded microbatch b in: traced per
        # stage, hence the array ring + dynamic take
        slot = jnp.mod(r - 2 * (n - 1) + 2 * p, depth)
        x_saved = jnp.take(ring, slot, axis=0)
        cot = jnp.where(is_last, dy, bcarry).astype(x_saved.dtype)
        _, stage_vjp = jax.vjp(stage_fn, local, x_saved)
        dparams, dx = stage_vjp(cot)
        grad_acc = jax.tree_util.tree_map(
            lambda g, d: g + jnp.where(
                b_active, d.astype(jnp.float32), 0.0),
            grad_acc, dparams)
        bcarry = lax.ppermute(dx, axis, perm_bwd)

    # loss lives on the last stage; grads are per-stage (stay sharded)
    # and return in the PARAM dtype (f32 accumulation is internal)
    loss = lax.psum(loss_acc, axis) / m
    if grad_fix is not None:
        # tensor-parallel closure (grad_reduce_axes): a leaf replicated
        # over a reduce axis came back as per-device PARTIALS — psum
        # restores the replication its out_spec claims
        gl, td = jax.tree_util.tree_flatten(grad_acc)
        gl = [lax.psum(g, ax) if ax else g
              for g, ax in zip(gl, grad_fix)]
        grad_acc = jax.tree_util.tree_unflatten(td, gl)
    grads = jax.tree_util.tree_map(
        lambda g, a: (g[None] / m).astype(a.dtype), grad_acc, local)
    return loss, grads


def pipeline_value_and_grad(stage_fn, stacked_params, x, y, loss_fn,
                            n_microbatches, mesh=None, axis="pp",
                            param_specs=None, grad_reduce_axes=None,
                            plan=None):
    """1F1B pipeline training step: mean loss + stacked param grads.

    stage_fn(params_i, x_mb) -> y_mb (same shape); loss_fn(out_mb,
    y_mb) -> scalar (mean over the microbatch); stacked_params: pytree
    with leading dim n_stages sharded over ``axis``; x, y: (batch,
    ...) split into ``n_microbatches`` along dim 0.  Returns
    ``(loss, grads)`` with ``grads`` shaped/sharded like
    ``stacked_params`` — feed them to any optimizer.  ``param_specs``
    (optional pytree of PartitionSpecs) composes tensor parallelism
    into the pipeline: leaves may shard extra mesh axes (e.g.
    ``P('pp', 'tp')``) with ``stage_fn``/``loss_fn`` issuing the
    matching collectives; grads come back in the same layout.
    ``grad_reduce_axes`` names the NON-pipeline mesh axes those
    collectives close with ``psum`` (e.g. ``('tp',)`` for row-parallel
    projections + a tp-reduced loss): with it set, a param replicated
    over such an axis gets its per-device partial grads psummed back
    to true replication (a trained norm weight would otherwise hold
    DIVERGENT replicas — undefined on gather), so grads match the
    unsharded reference exactly.

    ``plan`` (a ``parallel.ShardingPlan``) supplies the mesh and the
    stage axis (``plan.pp_axis``) — consumers of one plan never spell
    axis names twice.

    Compared with differentiating :func:`pipeline_apply`, the explicit
    1F1B schedule bounds in-flight activation memory by pipeline depth
    instead of microbatch count, at the cost of one recompute-forward
    per microbatch per stage (the jax.checkpoint trade).
    """
    import jax
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    mesh, axis = _resolve_plan(plan, mesh, axis)
    mesh, n, params, specs = _validate_and_place(
        "pipeline_value_and_grad", stacked_params, x, n_microbatches,
        mesh, axis, y=y, param_specs=param_specs)
    leaves = jax.tree_util.tree_leaves(stacked_params)
    sfn_key, s_cap = _structural_fn_key(stage_fn)
    lfn_key, l_cap = _structural_fn_key(loss_fn)
    # falsy entries mean "no extra axis" (e.g. a pp-only model passes
    # its tp_axis=None straight through) — filter them rather than
    # crash on mesh.shape[None]
    reduce_axes = tuple(a for a in (grad_reduce_axes or ()) if a)
    key = ("1f1b", mesh, axis, sfn_key, lfn_key, n_microbatches,
           tuple(l.shape for l in leaves),
           tuple(str(l.dtype) for l in leaves),
           x.shape, str(x.dtype), y.shape, str(y.dtype),
           reduce_axes,
           tuple(str(s) for s in jax.tree_util.tree_leaves(
               specs, is_leaf=lambda s: isinstance(s, P))))
    entry = _EXEC_CACHE.get(key)
    fn = entry[0] if entry is not None else None
    if fn is None:
        rspec = P()
        grad_fix = None
        if reduce_axes:
            def _mentioned(spec):
                out = set()
                for e in tuple(spec or ()):
                    if e is None:
                        continue
                    out.update(e if isinstance(e, tuple) else (e,))
                return out

            spec_leaves = jax.tree_util.tree_leaves(
                specs, is_leaf=lambda s: isinstance(s, P))
            grad_fix = tuple(
                tuple(a for a in reduce_axes if a not in _mentioned(s))
                for s in spec_leaves)
        body = shard_map(
            partial(_local_1f1b, stage_fn=stage_fn, loss_fn=loss_fn,
                    axis=axis, n_microbatches=n_microbatches,
                    grad_fix=grad_fix),
            mesh=mesh,
            in_specs=(specs, rspec, rspec),
            out_specs=(rspec, specs))

        def run(params, xb, yb):
            mb = xb.shape[0] // n_microbatches
            xs = xb.reshape((n_microbatches, mb) + xb.shape[1:])
            ys = yb.reshape((n_microbatches, mb) + yb.shape[1:])
            return body(params, xs, ys)

        fn = jax.jit(run)
        while len(_EXEC_CACHE) >= _EXEC_CACHE_MAX:
            _EXEC_CACHE.pop(next(iter(_EXEC_CACHE)))
        _EXEC_CACHE[key] = (fn, (s_cap, l_cap))

    return fn(params, x, y)
