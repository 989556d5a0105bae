"""One position a row into a K,V page, every row at its own position.

``_cache_update`` with a ``(B,)`` offset (per-slot decode in the serving
plane) lands here.  The obvious spelling, a ``vmap`` of
``dynamic_update_slice``, batches into a scatter whose indices name all
four dimensions of the page, and the TPU compiler expands THAT form into
a serial ``while`` over the rows: 2.8-8.5 us a row, a fifth of a decode
round (PERF.md section 6, PR 34).  The rule here is one: write the
smallest tile-aligned block of the page that holds the position, all
rows in one op, the page in place.  Which block that is depends on how
the TPU stores the page, and that is the compiler's choice, made from
the shape alone:

* row-major (``(.., 8, 128)`` rows: Mistral, Trinity): the block is the
  ``(KV, D)`` row itself.  A scatter with the row as a batching
  dimension and the position as its only index compiles to one
  in-place scatter fusion.
* positions on the LANES (``(.., 20, 64)`` rows: phi4; any D under 128
  or KV off a multiple of 8): one position is a single lane of
  ``KV x D / 16`` tiles.  The same scatter compiles there to itself
  between two COPIES of the page, so the block, ``(KV, D, 128)``, is
  read, one lane of it replaced, and written back by a small Pallas
  kernel over the page seen as ``(B, KV, D, C)``: the stored bytes, so
  the transposes around it are bitcasts and the page stays aliased.

``tests/test_tpu_compile.py`` holds both to the compiled program: no
``while``, no copy of the page, the page aliased to the output.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental.custom_partitioning import custom_partitioning
from jax.experimental.layout import Layout

__all__ = ["write_rows"]

_LANES = 128
# the Pallas interpreter in place of Mosaic: tests of the kernel's body
# on the CPU set this; nothing else does
_INTERPRET = False


def _tpu_device():
    """A TPU of this process, or None (tests steer this to a described
    chip)."""
    try:
        return jax.devices("tpu")[0]
    except RuntimeError:
        return None


def _positions_on_lanes(shape, dtype):
    """True where the TPU stores an array of this shape with axis 1 as
    its minor-most (lane) dimension.  Asked of the runtime, which is
    the compiler's own rule; False where no TPU is attached."""
    dev = _tpu_device()
    if dev is None:
        return False
    layout = Layout.from_pjrt_layout(
        dev.client.get_default_layout(jnp.dtype(dtype), shape, dev))
    return layout.major_to_minor[-1] == 1


def _scatter_rows(cache, new, off):
    """Row ``b`` of ``new`` (B, *row) to ``cache[b, off[b]]``: the row
    is a batching dimension of the scatter, its index names the
    position only, the update window is the whole ``*row`` (a row index
    made of ``arange`` would cost an all-gather under a ``dp`` plan)."""
    dnums = lax.ScatterDimensionNumbers(
        update_window_dims=tuple(range(1, cache.ndim - 1)),
        inserted_window_dims=(1,), scatter_dims_to_operand_dims=(1,),
        operand_batching_dims=(0,), scatter_indices_batching_dims=(0,))
    return lax.scatter(cache, off[:, None], new, dnums,
                       indices_are_sorted=True, unique_indices=True,
                       mode=lax.GatherScatterMode.PROMISE_IN_BOUNDS)


def _lane_block_call(cache, new, off):
    """The same write for a page stored positions-minor: grid step
    ``b`` moves the ``(*row, 128)`` block holding ``off[b]`` through
    VMEM and replaces lane ``off[b] % 128`` by row ``b`` of ``new``,
    which arrives as ``(*row, B)``: slot b's values are lane b.  A page
    whose length is no multiple of 128 ends in a partial block, which
    Pallas pads on the way in and cuts on the way out."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    nd = cache.ndim
    b, row = cache.shape[0], cache.shape[2:]
    zeros = (0,) * len(row)
    view = jnp.transpose(cache, (0,) + tuple(range(2, nd)) + (1,))
    cols = jnp.transpose(new, tuple(range(1, nd - 1)) + (0,))

    def kernel(off_ref, page_ref, cols_ref, out_ref):
        i = pl.program_id(0)
        n = cols_ref[...].astype(jnp.float32)
        mine = lax.broadcasted_iota(jnp.int32, n.shape, n.ndim - 1) == i
        col = jnp.sum(jnp.where(mine, n, 0.0), axis=-1, keepdims=True)
        blk = page_ref[0]
        lane = lax.broadcasted_iota(jnp.int32, blk.shape, blk.ndim - 1)
        out_ref[0] = jnp.where(lane == off_ref[i] % _LANES,
                               col.astype(blk.dtype), blk)

    page = pl.BlockSpec((1,) + row + (_LANES,),
                        lambda i, o: (i,) + zeros + (o[i] // _LANES,))
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(b,),
            in_specs=[page,
                      pl.BlockSpec(row + (b,), lambda i, o: zeros + (0,))],
            out_specs=page),
        out_shape=jax.ShapeDtypeStruct(view.shape, view.dtype),
        input_output_aliases={1: 0},
        interpret=_INTERPRET,
        name="page_write_rows",
    )(off, view, cols)
    return jnp.transpose(out, (0, nd - 1) + tuple(range(1, nd - 1)))


def _by_rows(mesh, arg_shapes, result_shape):
    """Every operand and the result split as the page's rows are (a
    ``dp`` plan), nothing else: a shard writes its own rows."""
    page = arg_shapes[0].sharding
    spec = getattr(page, "spec", ())
    return jax.sharding.NamedSharding(
        mesh, jax.sharding.PartitionSpec(spec[0] if spec else None))


def _partition(mesh, arg_shapes, result_shape):
    rows = _by_rows(mesh, arg_shapes, result_shape)
    return mesh, _lane_block_call, rows, (rows,) * 3


def _rows_rule(mesh, value_types, result_types):
    row = " ".join(f"r{i}" for i in range(len(result_types[0].shape) - 2))
    return f"b c {row}, b {row}, b -> b c {row}"


# a kernel is opaque to the partitioner, which would gather the whole
# page onto every device of a ``dp`` plan; told that rows are
# independent, it runs the kernel on each shard's rows
_lane_block_sharded = custom_partitioning(_lane_block_call)
_lane_block_sharded.def_partition(
    partition=_partition, infer_sharding_from_operands=_by_rows,
    sharding_rule=_rows_rule)


@jax.custom_jvp
def _lane_block_rows(cache, new, off):
    return _lane_block_sharded(cache, new, off)


@_lane_block_rows.defjvp
def _lane_block_rows_jvp(primals, tangents):
    # the write is linear in (cache, new): the kernel has no derivative
    # rule of its own, the scatter's serves (no served path takes one)
    return (_lane_block_rows(*primals),
            _scatter_rows(tangents[0], tangents[1], primals[2]))


def write_rows(cache, new, off):
    """``cache`` (B, C, *row) with ``new`` (B, 1, *row) written at
    ``cache[b, off[b]]``; ``off`` (B,) int32, brought into the page as
    ``lax.dynamic_update_slice`` brings it (an idle slot's may lie
    anywhere): one below zero counts from the end, then it is clamped.
    ``new`` is cast on store."""
    c = cache.shape[1]
    off = jnp.clip(jnp.where(off < 0, off + c, off), 0, c - 1)
    new = new[:, 0].astype(cache.dtype)
    if _positions_on_lanes(cache.shape, cache.dtype):
        return lax.platform_dependent(cache, new, off,
                                      tpu=_lane_block_rows,
                                      default=_scatter_rows)
    return _scatter_rows(cache, new, off)
