"""Collective wrappers over XLA's mesh collectives.

Capability parity: the reference's three comm transports (device rings/
trees in ``src/kvstore/comm.h``, NCCL allreduce in ``kvstore_nccl.h``,
ps-lite push/pull) all reduce to these four primitives on a TPU mesh; XLA
lowers them onto ICI (intra-slice) or DCN (cross-slice) automatically.

Two usage modes:

* **Inside shard_map/jit** (the hot path): the ``lax``-level functions
  ``psum/pmean/all_gather/ppermute/all_to_all`` taking an ``axis_name``.
* **Eager on NDArrays** (kvstore facade, tests): :func:`allreduce` — a
  jitted shard_map over the current mesh.
"""
from __future__ import annotations

from functools import partial

from ..base import MXNetError
from .mesh import current_mesh

__all__ = ["vocab_parallel_softmax_ce",
           "psum", "pmean", "all_gather", "ppermute", "all_to_all",
           "allreduce", "reduce_scatter", "quantized_psum",
           "quantized_reduce_scatter", "twobit_psum",
           "sharded_weight_update", "sharded_update_state_init"]


def psum(x, axis_name):
    import jax.lax as lax
    return lax.psum(x, axis_name)


def pmean(x, axis_name):
    import jax.lax as lax
    return lax.pmean(x, axis_name)


def all_gather(x, axis_name, axis=0, tiled=True):
    import jax.lax as lax
    return lax.all_gather(x, axis_name, axis=axis, tiled=tiled)


def reduce_scatter(x, axis_name, *, scatter_dimension=0, tiled=False):
    """Fused reduce-scatter over a mesh axis (inside shard_map/jit).

    Each of the N axis members contributes its ``x``; member i receives
    the cross-member SUM of slice i along ``scatter_dimension`` — the
    first half of a decomposed all-reduce, as ONE collective
    (``lax.psum_scatter``).  With ``tiled=False`` (default) the scatter
    dim must equal N and disappears from the result (``(N, c) ->
    (c,)``); ``tiled=True`` keeps it, leaving each member a 1/N-length
    slice.

    Ring cost (the accounting :func:`quantized_psum` documents): a ring
    reduce-scatter moves ``size * (N-1)/N`` bytes per member — exactly
    HALF a ring all-reduce, which pays the same again to all-gather the
    sums back.  That saved half is the ZeRO-2 gradient leg: shard the
    optimizer update (`sharded_weight_update`) and the gather half
    ships updated WEIGHTS instead of repeating the gradient bytes.
    """
    import jax.lax as lax
    return lax.psum_scatter(x, axis_name,
                            scatter_dimension=scatter_dimension,
                            tiled=tiled)


def ppermute(x, axis_name, perm):
    import jax.lax as lax
    return lax.ppermute(x, axis_name, perm)


def all_to_all(x, axis_name, split_axis, concat_axis, tiled=True):
    import jax.lax as lax
    return lax.all_to_all(x, axis_name, split_axis, concat_axis,
                          tiled=tiled)


_ALLREDUCE_CACHE = {}


def allreduce(values, axis="dp", mesh=None, op="sum"):
    """Eager allreduce of per-device NDArray shards over a mesh axis.

    ``values``: list of NDArrays, one per device along ``axis`` (the
    kvstore ``device`` layout).  Returns the list of reduced NDArrays, one
    per input device.  The reduction runs as a single jitted shard_map —
    XLA emits one fused allreduce instead of the reference's hand-built
    reduce-broadcast tree.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from jax import shard_map

    from ..ndarray.ndarray import NDArray

    mesh = mesh if mesh is not None else current_mesh()
    n = mesh.shape[axis]
    if len(values) != n:
        raise MXNetError(
            f"allreduce: got {len(values)} shards for mesh axis "
            f"{axis!r} of size {n}")
    if op not in ("sum", "mean"):
        raise MXNetError(f"allreduce: unsupported op {op!r}")

    shape = values[0].shape
    dtype = values[0].dtype
    key = (mesh, axis, shape, str(dtype), op)
    fn = _ALLREDUCE_CACHE.get(key)
    if fn is None:
        spec = P(axis, *([None] * len(shape)))

        def _reduce(stacked):
            red = psum(stacked, axis) if op == "sum" else pmean(stacked,
                                                               axis)
            return red

        fn = jax.jit(shard_map(
            _reduce, mesh=mesh, in_specs=(spec,), out_specs=spec))
        _ALLREDUCE_CACHE[key] = fn

    sharding = NamedSharding(mesh, P(axis, *([None] * len(shape))))
    if len({v._data.device for v in values}) <= 1:
        stacked = jax.device_put(jnp.stack([v._data for v in values]),
                                 sharding)
    elif len(mesh.axis_names) == 1:
        # shards already live on their devices (kvstore 'device'
        # layout): assemble the global array in place, no host hop
        devs = list(mesh.devices.flat)
        arrs = [jax.device_put(v._data[None], d)
                for v, d in zip(values, devs)]
        stacked = jax.make_array_from_single_device_arrays(
            (n,) + tuple(shape), sharding, arrs)
    else:
        # multi-axis mesh with scattered shards: go through the host
        import numpy as _np
        stacked = jax.device_put(
            jnp.asarray(_np.stack([v.asnumpy() for v in values])),
            sharding)
    out = fn(stacked)
    return [NDArray(out[i], ctx=values[i].context)
            for i in range(len(values))]


def quantized_psum(x, axis_name, *, bits=8):
    """int8-wire quantized allreduce (inside shard_map/jit).

    The SPMD analog of the reference's 2-bit gradient compression
    (``src/kvstore/gradient_compression.cc``; SURVEY.md §7 P6
    "quantized-allreduce ≙ gradient compression", cf. PAPERS.md
    EQuARX): a two-phase reduce-scatter/all-gather where BOTH phases
    move int8 — (1) each device splits into N chunks, quantizes each
    against its own absmax, and ``all_to_all``s the int8 chunks plus
    fp32 scalar scales; (2) each device dequant-sums its chunk,
    REQUANTIZES the partial sum, and int8-``all_gather``s it back.
    Wire bytes ≈ 2·size·1 vs a ring fp32 psum's ≈ 2·size·4 — a real
    4x, at the cost of two rounding stages.

    Deterministic, stateless, and differentiable-through (straight
    through estimator: gradients treat it as psum).  Error feedback is
    the caller's residual to keep, as in the reference.
    """
    import jax
    import jax.numpy as jnp
    import jax.lax as lax

    from jax.lax import axis_size

    if bits != 8:
        raise MXNetError(f"quantized_psum: bits must be 8, got {bits}")
    qmax = float(2 ** (bits - 1) - 1)

    @jax.custom_vjp
    def _qpsum(v):
        n = axis_size(axis_name)
        flat = v.reshape(-1).astype(jnp.float32)
        padded = flat.size + ((-flat.size) % n)
        if padded != flat.size:
            flat = jnp.concatenate(
                [flat, jnp.zeros((padded - flat.size,), jnp.float32)])
        chunks = flat.reshape(n, -1)                       # (n, c)
        scale = jnp.maximum(jnp.max(jnp.abs(chunks), axis=1) / qmax,
                            1e-20)                         # (n,)
        q = jnp.clip(jnp.round(chunks / scale[:, None]), -qmax,
                     qmax).astype(jnp.int8)
        # phase 1: int8 chunks to their owner device + scalar scales
        q_x = lax.all_to_all(q, axis_name, 0, 0, tiled=True)
        s_x = lax.all_to_all(scale[:, None], axis_name, 0, 0,
                             tiled=True)                   # (n, 1)
        part = jnp.sum(q_x.astype(jnp.float32) * s_x, axis=0)  # (c,)
        # phase 2: requantize the partial sum, int8 all-gather back
        s2 = jnp.maximum(jnp.max(jnp.abs(part)) / qmax, 1e-20)
        q2 = jnp.clip(jnp.round(part / s2), -qmax,
                      qmax).astype(jnp.int8)
        allq = lax.all_gather(q2, axis_name, axis=0)       # (n, c)
        alls = lax.all_gather(s2, axis_name, axis=0)       # (n,)
        full = (allq.astype(jnp.float32)
                * alls[:, None]).reshape(-1)[:v.size]
        return full.reshape(v.shape).astype(v.dtype)

    def _fwd(v):
        return _qpsum(v), None

    def _bwd(_, g):
        # straight-through psum transpose: the all_gather-built output
        # is VARYING-typed, so its per-device cotangents accumulate
        # explicitly (psum), then re-mark varying for the input's type
        ct = lax.psum(g, axis_name)
        return (lax.pcast(ct, (axis_name,), to="varying"),)

    _qpsum.defvjp(_fwd, _bwd)
    return _qpsum(x)


def quantized_reduce_scatter(x, axis_name, *, bits=8):
    """int8-wire reduce-scatter: :func:`quantized_psum`'s REDUCE phase
    composed with the ZeRO gradient leg (inside shard_map/jit).

    quantize -> scatter -> fp32 local accumulate: each member splits
    ``x`` into N chunks, quantizes each against its own absmax
    (int8 codes + one fp32 scale per chunk), ``all_to_all``s the codes,
    and dequant-SUMS its own chunk in fp32.  Member i returns the fp32
    cross-member sum of chunk i, shaped ``(padded_size/N,)`` with
    ``padded_size = size + (-size) % N`` (padding tail carries zeros) —
    exactly the flat-slice layout :func:`sharded_weight_update`'s
    ``grad_reduce=`` callable contract expects.

    Wire bytes ≈ ``size * (N-1)/N`` at int8 vs a ring fp32
    reduce-scatter's ``4 * size * (N-1)/N`` — 4x, with ONE rounding
    stage (the fp32 accumulate never requantizes, unlike
    ``quantized_psum``'s gather phase, so the scattered sums are
    strictly more accurate than the allreduce's).
    """
    import jax.numpy as jnp
    import jax.lax as lax

    from jax.lax import axis_size

    if bits != 8:
        raise MXNetError(
            f"quantized_reduce_scatter: bits must be 8, got {bits}")
    qmax = float(2 ** (bits - 1) - 1)
    n = axis_size(axis_name)
    flat = x.reshape(-1).astype(jnp.float32)
    padded = flat.size + ((-flat.size) % n)
    if padded != flat.size:
        flat = jnp.concatenate(
            [flat, jnp.zeros((padded - flat.size,), jnp.float32)])
    chunks = flat.reshape(n, -1)                       # (n, c)
    scale = jnp.maximum(jnp.max(jnp.abs(chunks), axis=1) / qmax,
                        1e-20)                         # (n,)
    q = jnp.clip(jnp.round(chunks / scale[:, None]), -qmax,
                 qmax).astype(jnp.int8)
    # int8 chunks to their owner member + the fp32 scalar scales
    q_x = lax.all_to_all(q, axis_name, 0, 0, tiled=True)
    s_x = lax.all_to_all(scale[:, None], axis_name, 0, 0,
                         tiled=True)                   # (n, 1)
    return jnp.sum(q_x.astype(jnp.float32) * s_x, axis=0)  # (c,)


def twobit_psum(x, axis_name, *, threshold=0.5, residual=None):
    """2-bit quantized allreduce with error feedback (inside shard_map).

    The SPMD spelling of the reference's ``dist_sync`` gradient
    compression (``src/kvstore/gradient_compression.cc``): each device
    adds its carried ``residual``, snaps every element to
    {-threshold, 0, +threshold}, and only PACKED codes cross the wire
    — four ternary codes per byte, genuinely 2 bits per element (the
    reference packs 16 per int32).  Like :func:`quantized_psum`, the
    exchange is two-phase so wire bytes stay O(size) regardless of
    axis width: (1) ``all_to_all`` the bit-packed chunks
    (size/4 bytes), (2) each device unpacks, sums its chunk (a sum of
    n ternary codes fits int8 exactly while n ≤ 127) and
    int8-``all_gather``s the partial back (size bytes).  Wire ≈
    1.25·size bytes vs a ring fp32 psum's ≈ 8·size — 6.4x.

    Returns ``(summed, new_residual)`` — the caller keeps the residual
    for the next step, which is what makes the quantization unbiased
    over time.
    """
    import jax.numpy as jnp
    import jax.lax as lax

    from jax.lax import axis_size
    n = axis_size(axis_name)
    g = x if residual is None else x + residual
    codes = jnp.where(g >= threshold, 1,
                      jnp.where(g <= -threshold, -1, 0)).astype(jnp.int8)
    flat = codes.reshape(-1)
    # chunk count multiple of n, chunk length multiple of 4 (packing)
    chunk = -(-flat.size // n)
    chunk += (-chunk) % 4
    padded = chunk * n
    if padded != flat.size:
        flat = jnp.concatenate(
            [flat, jnp.zeros((padded - flat.size,), jnp.int8)])
    chunks = flat.reshape(n, -1)                            # (n, c)
    # phase 1: PACK {-1,0,1}+1 -> {0,1,2} into 2-bit lanes, 4/byte
    u = (chunks + 1).astype(jnp.uint8).reshape(n, -1, 4)
    packed = (u[..., 0] | (u[..., 1] << 2) | (u[..., 2] << 4)
              | (u[..., 3] << 6))                           # (n, c/4)
    px = lax.all_to_all(packed, axis_name, 0, 0, tiled=True)
    quads = jnp.stack([(px >> s) & 0x3 for s in (0, 2, 4, 6)],
                      axis=-1)
    cx = quads.reshape(n, -1).astype(jnp.int32) - 1         # (n, c)
    # partial sums are in [-n, n]: exact in int8 up to n == 127
    part_dtype = jnp.int8 if n <= 127 else jnp.int32
    part = cx.sum(axis=0).astype(part_dtype)
    # phase 2: narrow partial sums gathered back
    allp = lax.all_gather(part, axis_name, axis=0)          # (n, c)
    summed = (allp.astype(jnp.float32).reshape(-1)[:x.size]
              * threshold).reshape(x.shape)
    new_residual = g - codes.astype(g.dtype) * jnp.asarray(
        threshold, g.dtype)
    return summed.astype(x.dtype), new_residual


def vocab_parallel_softmax_ce(hidden, w_local, label, axis_name,
                              chunk=None):
    """Megatron-style vocab-parallel cross-entropy (inside shard_map).

    Dispatch rule (VERDICT r4 #4 — one documented entry point):
    ``ops.nn.chunked_softmax_ce`` is THE large-vocab CE; this function
    is its single-slab tp specialization, kept for callers whose
    per-shard slab (N, V/tp) already fits activation memory.  Pass
    ``chunk`` to stream even the local shard (tp × huge-vocab) — that
    delegates to ``chunked_softmax_ce(axis_name=...)``, same
    collective budget (one pmax + one fused psum), O(N·chunk)
    activations.

    The tensor-parallel LM head shards the (V, U) projection over
    ``axis_name`` by vocab rows; each rank computes its LOCAL logits
    slab (N, V/tp) and the softmax normalizer is assembled with ONE
    pmax + psum pair — the full (N, V) logits never exist on any
    device and the wire carries only (N,)-sized rows.  The label
    logit comes from whichever rank owns the label's row (everyone
    else contributes an exact zero).  Differentiable through the
    collectives (the vjp of psum is broadcast; the max subtraction
    cancels analytically), so dW stays sharded and dH is exact.

    hidden (N, U); w_local (V_local, U) — ranks tile the vocab in
    order (rank i owns rows [i·V_local, (i+1)·V_local)); label (N,)
    int.  Returns per-row loss (N,), f32.

    Reference analog: the kvstore sharded softmax has no upstream
    equivalent — this is the TPU-idiomatic replacement for replicating
    the full head on every data-parallel worker (SURVEY.md §7 P6).
    """
    import jax.numpy as jnp
    import jax.lax as lax

    if chunk is not None:
        from ..ops.nn import chunked_softmax_ce
        return chunked_softmax_ce(hidden, w_local, label, chunk=chunk,
                                  axis_name=axis_name)
    i = lax.axis_index(axis_name)
    v_local = w_local.shape[0]
    logits = jnp.dot(hidden, w_local.T,
                     preferred_element_type=jnp.float32)
    m = lax.pmax(lax.stop_gradient(logits).max(axis=1), axis_name)
    lbl = label.astype(jnp.int32)
    idx = lbl - i * jnp.int32(v_local)
    in_range = (idx >= 0) & (idx < v_local)
    picked = jnp.take_along_axis(
        logits, jnp.clip(idx, 0, v_local - 1)[:, None], axis=1)[:, 0]
    # ONE collective for both reductions: the normalizer partial sums
    # and the label-logit contributions ride the same psum (a second
    # psum would add a full collective latency per loss evaluation)
    s, lab = lax.psum(
        jnp.stack([jnp.exp(logits - m[:, None]).sum(axis=1),
                   jnp.where(in_range, picked, 0.0)]), axis_name)
    return m + jnp.log(s) - lab


def sharded_weight_update(param, grad, states, update_fn, axis_name,
                          *, grad_reduce="scatter"):
    """ZeRO-1 / cross-replica weight-update sharding (PAPERS.md:
    "Automatic Cross-Replica Sharding of Weight Update in
    Data-Parallel Training", arXiv 2004.13336 — the paper's XLA
    recipe, expressed at the collective level).

    Replicated data-parallel training makes every dp member do the
    SAME full optimizer update on the SAME summed gradient — O(P)
    optimizer state and update FLOPs per member.  This helper shards
    the update over ``axis_name`` instead:

      1. ``psum_scatter`` the per-member gradient: one fused
         reduce-scatter leaves each member the SUM of its 1/N slice
         (half the wire bytes of a psum — the all-gather half moves
         updated WEIGHTS below instead of gradients);
      2. apply ``update_fn`` on the slice — optimizer state lives
         ONLY as (size/N,) slices per member (adam m/v memory drops
         by N);
      3. ``all_gather`` the updated slices back into the full
         replicated parameter.

    Runs INSIDE shard_map/jit.  ``param`` (any shape, replicated over
    ``axis_name``); ``grad`` the LOCAL (un-reduced) gradient, same
    shape; ``states`` a tuple of (padded_size/N,)-shaped state slices
    (start from :func:`sharded_update_state_init`); ``update_fn``
    ``(p_slice, g_slice, *state_slices) -> (new_p_slice,
    new_state_slices)`` — flat f32 slices.  The flat length is padded
    to a multiple of N; padding tail slices carry zeros and update_fn
    must be pointwise in the slice (every standard optimizer is).
    Returns ``(new_param, new_state_slices)``.

    ``grad_reduce`` selects the gradient leg:

    * ``"scatter"`` (default, ZeRO-2): one fused ``psum_scatter`` —
      grads cross the wire once, sharded;
    * ``"local"`` (ZeRO-1, or a caller that already reduced): ``grad``
      is ALREADY the cross-member-reduced gradient, replicated — just
      slice the local chunk, no collective on this leg;
    * a callable ``(padded_flat_grad,) -> (chunk,)`` supplying its own
      reduce-scatter — e.g. :func:`quantized_reduce_scatter` for the
      int8-wire leg (quantize -> scatter -> fp32 local accumulate).
    """
    import jax.numpy as jnp
    import jax.lax as lax

    from jax.lax import axis_size
    n = axis_size(axis_name)
    flat = grad.reshape(-1).astype(jnp.float32)
    size = flat.size
    pad = (-size) % n
    if pad:
        flat = jnp.pad(flat, (0, pad))
    idx = lax.axis_index(axis_name)
    chunk = flat.size // n
    if grad_reduce == "scatter":
        # one fused reduce-scatter: member i receives sum over members
        # of slice i (tiled=False keeps the scatter dim explicit)
        g_slice = reduce_scatter(flat.reshape(n, -1), axis_name)
    elif grad_reduce == "local":
        g_slice = lax.dynamic_slice_in_dim(flat, idx * chunk, chunk)
    elif callable(grad_reduce):
        g_slice = grad_reduce(flat)
    else:
        raise MXNetError(
            f"sharded_weight_update: grad_reduce must be 'scatter', "
            f"'local', or a callable, got {grad_reduce!r}")
    p_flat = param.reshape(-1).astype(jnp.float32)
    if pad:
        p_flat = jnp.pad(p_flat, (0, pad))
    p_slice = lax.dynamic_slice_in_dim(p_flat, idx * chunk, chunk)
    new_p_slice, new_states = update_fn(p_slice, g_slice, *states)
    # cast BEFORE the gather: for bf16/f16 params an f32 gather would
    # ship the weight half of the wire at 2x the necessary bytes —
    # defeating the function's whole purpose
    new_flat = lax.all_gather(new_p_slice.astype(param.dtype),
                              axis_name, axis=0, tiled=True)
    if pad:
        new_flat = new_flat[:size]
    return new_flat.reshape(param.shape), tuple(new_states)


def sharded_update_state_init(param, n_states, axis_name_size):
    """Optimizer-state arrays for :func:`sharded_weight_update`:
    ``n_states`` zero arrays of GLOBAL shape (N, padded_size/N) — feed
    each through ``shard_map`` with ``in_specs=P(axis)`` /
    ``out_specs=P(axis)`` so every member holds its (1, chunk) slice
    (strip the leading local axis before ``update_fn``, re-add it on
    the way out: ``m2[None]``).  Per-member memory is 1/N the
    replicated state; the round-trip shape is stable across steps.
    Call OUTSIDE shard_map with the dp axis size."""
    import numpy as np

    size = 1
    for d in param.shape:
        size *= int(d)
    padded = size + ((-size) % axis_name_size)
    chunk = padded // axis_name_size
    return tuple(np.zeros((axis_name_size, chunk), "float32")
                 for _ in range(n_states))
