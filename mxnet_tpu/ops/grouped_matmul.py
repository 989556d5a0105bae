"""The held experts' feed-forward over rows grouped by expert, at the
cost of the rows each expert holds.

``expert_sum`` is the products half of ``moe.routed_experts``: the
assignments arrive sorted by held expert (expert ``j``'s ``sizes[j]``
rows, then the next's; rows past the last group belong to no expert
held here), each goes through its expert's SwiGLU
``W_down (silu(W_gate x) * W_up x)``, is weighed by its gate and summed
back onto its row.  Two lowerings, one function of the same operands:

* THE DEFINITION: three ``lax.ragged_dot``.  The TPU compiler turns
  each into its own grouped matmul, whose row tile it sizes by ALL the
  rows (512 at 2,048): every (tile, expert) visit multiplies a full
  tile whatever the expert holds, so at a few rows an expert the
  products are bound by the MXU on padding, not by the weights
  (PERF.md section 5).  The lowering of every program off the TPU.
* THE KERNEL (``_ffn_call``): one Pallas kernel, expert-major.  Grid
  step ``(e, j)`` has expert ``e``'s gate and up columns ``j`` and the
  down rows ``j`` in VMEM, double-buffered by the pipeline: each
  expert's weights are read once, in blocks of up to 8 MiB a step,
  and an expert with no rows repeats the block the step before had, so
  nothing is copied for it.  Its rows, laid out from a multiple of 16
  (``_layout``), are multiplied in ONE tile of 16, 32, 64 or 128 rows
  whose float32 sum over ``j`` stays in VMEM; an expert that holds more
  rows goes on in tiles of 128 whose sums go through HBM.  A tile that
  runs past its expert's rows reads and writes rows of a LATER expert,
  which rewrites them when its own steps come: the grid is sequential.
  Taken on a TPU where ``d`` and ``h`` are multiples of 128
  (``_takes_kernel``), through ``lax.platform_dependent``.

Both take ``x.dtype`` in and accumulate float32; ``silu(gate) * up`` is
float32 and rounded to ``x.dtype`` before the down product.  The kernel
adds its down products over blocks of ``h`` in float32 where the
definition adds them in one product: the two differ by float32
rounding.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["expert_sum"]

_F32 = jnp.float32
_LANES = 128
# rows of a tile: the first tile of an expert is the smallest of these
# that holds its rows; further tiles are the last
_TILES = (16, 32, 64, 128)
# the gate, up and down blocks of one grid step together, bytes: the
# pipeline holds two such sets.  At the cells' decode shapes 8 and 16 MiB
# time alike (Solar 2.12 / 2.13 ms a layer, Trinity 2.60 / 2.55) and 32
# is slower; Pangu's 7,680-wide blocks take 2.32 / 2.42 (TPU v5e,
# ``tools/expert_ffn_bench.py``; PERF.md section 6)
_STEP_BYTES = 8 << 20
# the Pallas interpreter in place of Mosaic, on any backend: tests of
# the kernel's body on the CPU set this; nothing else does
_INTERPRET = False


def _definition(x, rows, sizes, gates, back, w_gate, w_up, w_down):
    """The held experts' weighed sum onto each row of ``x``, by
    ``lax.ragged_dot`` over the sorted rows."""
    t = x.shape[0]
    xs = jnp.take(x, rows, axis=0)
    grouped = dict(group_sizes=sizes, preferred_element_type=_F32)
    mid = jax.nn.silu(lax.ragged_dot(xs, w_gate, **grouped)) \
        * lax.ragged_dot(xs, w_up, **grouped)
    ys = lax.ragged_dot(mid.astype(x.dtype), w_down, **grouped)
    # rows past the last group belong to no held expert: what a grouped
    # product leaves there is not a result
    ys = jnp.where(gates[:, None] > 0, ys * gates[:, None], 0.0)
    return jnp.sum(jnp.take(ys, back, axis=0).reshape(t, -1, ys.shape[1]),
                   axis=1)


def _block(d, h, itemsize):
    """Columns of ``h`` a grid step takes: the widest multiple of 128
    that divides ``h`` and keeps the step's three blocks within
    ``_STEP_BYTES``."""
    fits = [b for b in range(_LANES, h + 1, _LANES)
            if h % b == 0 and 3 * d * b * itemsize <= _STEP_BYTES]
    return max(fits, default=_LANES)


def _layout(sizes, m):
    """Where each sorted row lives in the kernel's layout, in which every
    group starts at a multiple of 16: (padded rows ``p``, each group's
    start (N,), each sorted row's place (M,), ``p`` for a row past the
    last group).  ``p`` leaves room for a last tile of 128 past the
    groups."""
    n = sizes.shape[0]
    p = -(-(m + 15 * n) // 16) * 16 + _TILES[-1]
    ends = jnp.cumsum(sizes)
    padded = (sizes + 15) // 16 * 16
    starts = jnp.cumsum(padded) - padded
    i = jnp.arange(m, dtype=jnp.int32)
    group = jnp.searchsorted(ends, i, side="right").astype(jnp.int32)
    g = jnp.minimum(group, n - 1)
    place = jnp.where(group < n, starts[g] + i - (ends[g] - sizes[g]), p)
    return p, starts.astype(jnp.int32), place.astype(jnp.int32)


def _ffn_call(xs, w_gate, w_up, w_down, sizes, starts):
    """xs (P, d) in ``_layout``'s rows; w_gate, w_up (N, d, h), w_down
    (N, h, d) -> (P, d) float32, each group's rows through its expert;
    rows of no group are left as they are."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    p, d = xs.shape
    n, _, h = w_gate.shape
    dt = xs.dtype
    bh = _block(d, h, jnp.dtype(w_gate.dtype).itemsize)
    steps = h // bh
    big = _TILES[-1]
    # an expert with no rows takes the block of the step before it (the
    # last expert with rows before it, at its last step; before the first
    # such, the first one's first block): the pipeline copies nothing
    held = sizes > 0
    e_ids = jnp.arange(n, dtype=jnp.int32)
    before = lax.cummax(jnp.where(held, e_ids, -1))
    first = jnp.argmax(held).astype(jnp.int32)
    w_of = jnp.where(held, e_ids, jnp.where(before >= 0, before, first))
    j_of = jnp.where(held, -1, jnp.where(before >= 0, steps - 1, 0))

    def cols(e, j, j_of):
        return jnp.where(j_of[e] < 0, j, j_of[e])

    def gate_up_map(e, j, _sizes, _starts, w_of, j_of):
        return w_of[e], 0, cols(e, j, j_of)

    def down_map(e, j, _sizes, _starts, w_of, j_of):
        return w_of[e], cols(e, j, j_of), 0

    def kernel(sizes_ref, starts_ref, _w_of, _j_of, x_hbm, wg_ref, wu_ref,
               wd_ref, y_hbm, x_first, acc, x_more, acc_more, sem):
        e, j = pl.program_id(0), pl.program_id(1)
        size = sizes_ref[e]
        start = pl.multiple_of(starts_ref[e], 16)

        def ffn(x):
            gate = jnp.dot(x, wg_ref[0], preferred_element_type=_F32)
            up = jnp.dot(x, wu_ref[0], preferred_element_type=_F32)
            mid = (jax.nn.silu(gate) * up).astype(dt)
            return jnp.dot(mid, wd_ref[0], preferred_element_type=_F32)

        def copy(src, dst, k):
            cp = pltpu.make_async_copy(src, dst, sem.at[k])
            cp.start()
            cp.wait()

        # the first tile: its rows and its sum stay in VMEM over the steps
        tile = big
        for rows in reversed(_TILES[:-1]):
            tile = jnp.where(size <= rows, rows, tile)
        for rows in _TILES:
            @pl.when((size > 0) & (tile == rows))
            def _(rows=rows):
                @pl.when(j == 0)
                def _():
                    copy(x_hbm.at[pl.ds(start, rows)],
                         x_first.at[pl.ds(0, rows)], 0)

                y = ffn(x_first[pl.ds(0, rows)])

                @pl.when(j == 0)
                def _():
                    acc[pl.ds(0, rows)] = y

                @pl.when(j > 0)
                def _():
                    acc[pl.ds(0, rows)] += y

                @pl.when(j == steps - 1)
                def _():
                    copy(acc.at[pl.ds(0, rows)],
                         y_hbm.at[pl.ds(start, rows)], 1)

        # further tiles of 128 rows: their sums over the steps go through
        # the output
        def more(i, carry):
            row = pl.multiple_of(start + i * big, 16)
            copy(x_hbm.at[pl.ds(row, big)], x_more, 0)
            y = ffn(x_more[...])

            @pl.when(j == 0)
            def _():
                acc_more[...] = y

            @pl.when(j > 0)
            def _():
                copy(y_hbm.at[pl.ds(row, big)], acc_more, 2)
                acc_more[...] += y

            copy(acc_more, y_hbm.at[pl.ds(row, big)], 1)
            return carry

        lax.fori_loop(1, (size + big - 1) // big, more, 0)

    itemsize = jnp.dtype(dt).itemsize
    vmem = (2 * 3 * d * bh * itemsize + 2 * big * d * (itemsize + 4)
            + big * (3 * bh + 2 * d) * 4)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(n, steps),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec((1, d, bh), gate_up_map),
                      pl.BlockSpec((1, d, bh), gate_up_map),
                      pl.BlockSpec((1, bh, d), down_map)],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[pltpu.VMEM((big, d), dt),
                            pltpu.VMEM((big, d), _F32),
                            pltpu.VMEM((big, d), dt),
                            pltpu.VMEM((big, d), _F32),
                            pltpu.SemaphoreType.DMA((3,))]),
        out_shape=jax.ShapeDtypeStruct((p, d), _F32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem + (16 << 20)),
        interpret=_INTERPRET,
        name="grouped_expert_ffn",
    )(sizes, starts, w_of, j_of, xs, w_gate, w_up, w_down)


def _kernel_sum(x, rows, sizes, gates, back, w_gate, w_up, w_down):
    """``_definition``'s result through ``_ffn_call``."""
    t, d = x.shape
    p, starts, place = _layout(sizes, rows.shape[0])
    # each place's row of ``x``; a place of no row takes row 0
    src = jnp.zeros((p,), jnp.int32).at[place].set(rows, mode="drop")
    ys = _ffn_call(jnp.take(x, src, axis=0), w_gate, w_up, w_down,
                   sizes, starts)
    at = jnp.take(place, back)
    g = jnp.take(gates, back)[:, None]
    ys = jnp.take(ys, jnp.minimum(at, p - 1), axis=0)
    return jnp.sum(jnp.where(g > 0, ys * g, 0.0).reshape(t, -1, d), axis=1)


def _takes_kernel(x, w_gate):
    """True where the kernel can run these shapes: ``d`` and ``h``
    whole lane tiles."""
    return x.shape[1] % _LANES == 0 and w_gate.shape[2] % _LANES == 0


def expert_sum(x, rows, sizes, gates, back, w_gate, w_up, w_down):
    """x (T, d); rows (T*k,) int32, the row of ``x`` each sorted
    assignment takes: ``sizes[0]`` of held expert 0 first, then expert
    1's, ..., then the assignments of no held expert; sizes (N,) int32;
    gates (T*k,) float32, each sorted assignment's gate, 0 for those of
    no held expert; back (T*k,), the sorted place of assignment ``t*k +
    i``; w_gate, w_up (N, d, h), w_down (N, h, d).  Returns (T, d)
    float32: each row's gated sum over its held experts' SwiGLU."""
    args = (x, rows, sizes, gates, back, w_gate, w_up, w_down)
    if not _takes_kernel(x, w_gate):
        return _definition(*args)
    if _INTERPRET:
        return _kernel_sum(*args)
    return lax.platform_dependent(*args, tpu=_kernel_sum,
                                  default=_definition)
