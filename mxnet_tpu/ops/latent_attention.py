"""Multi-head latent attention (MLA, DeepSeek-V2 section 2.1): keys and
values of all heads are ONE low-rank latent a position, and one rotated
key shared by the heads.

A position's row is ``[c ; k_r]``: the normed latent ``c`` (``rkv``
wide) and the rotated shared key ``k_r`` (``dr``).  Head ``i`` has
``k_n,i = W_uk,i c`` (``dn``), ``v_i = W_uv,i c`` (``dv``) and scores
``(q_n,i . k_n,i + q_r,i . k_r) * scale``.  ONE op, two modes over the
same ``w_ukv``, chosen by what it is given:

* a PROMPT (no ``offset``): the definition.  ``k_n`` and ``v`` are
  expanded per head from the prompt's own rows, causal softmax, the
  heads' values summed: prefill, where the expansion is paid once a
  position and the products are ``dn + dr`` and ``dv`` wide.
* a PAGE and an ``offset`` a row (decode, one query a row): ABSORBED.
  ``W_uk,i`` goes into the query (``qt_i = W_uk,i^T q_n,i``, ``rkv``
  wide), the scores and the weighted sum run over the page's rows
  themselves (all heads share them: one ``(H, rkv + dr) x (rkv + dr,
  C)`` product a row of the batch), and ``W_uv,i`` is applied to the
  ``rkv``-wide sum.  No per-head key or value exists for a cached
  position, so a page is ``rkv + dr`` numbers a position where
  per-head K,V would be ``H (dn + dr + dv)``.

The absorbed mode's attention proper (scores, softmax, weighted sum:
the scope ``mxtpu.mixer.mla.attend``) has TWO LOWERINGS of one function,
chosen from what the code can observe and by nothing else:

* DENSE (``_attend_dense``): the definition.  Three XLA passes over
  every position of the page, the ones past a row's offset masked.  Any
  backend; the non-TPU lowering of every program.
* THE WALK (``_walk_call``): one Pallas kernel.  Row ``b`` visits only
  the ``offset[b] // _BLK + 1`` blocks of ``_BLK`` positions that hold
  ``[0, offset[b]]``, double-buffered out of HBM, with an online
  softmax whose scores never leave the chip; only a row's last block is
  masked.  Taken where a TPU is attached AND the runtime stores the
  page with the positions on the lanes (any row width off a multiple of
  128: Pangu's 576; asked of the runtime by ``page_write``'s rule, so
  the page seen as ``(B, rkv + dr, C)`` is the stored bytes and the
  transpose around the call is a bitcast) AND the page's length is a
  multiple of 128 (whole lane tiles); lowered through
  ``lax.platform_dependent``, so the same traced program still runs the
  definition on a CPU.

``_contrib_LatentAttentionWalked`` counts the positions the lowering
taken runs over (``mxtpu_mla_page_positions_total``): blocks walked x
``_BLK`` under the kernel, rows x page length under the definition.

Scores and softmax are float32; every product takes the inputs' dtype
in and accumulates float32; ``qt_i`` and the latent sum are rounded to
that dtype where they enter the next product.  Under the walk the
running maximum, the running sum and the accumulator are float32, the
exponentials are rounded to the page's dtype where they enter the
second product and the sum is divided by the float32 running sum after
it.  Every position in ``[0, offset[b]]`` is attended by either
lowering; none past it has a say (finite values there are multiplied
by an exact zero).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental.custom_partitioning import custom_partitioning

from ..profiler import device_scope
from . import page_write
from .registry import register

_F32 = jnp.float32
_LANES = page_write._LANES
# positions a step of the walk.  A row's last block is part empty and a
# step has a fixed cost, so small blocks walk less and step more: at the
# Pangu cell's shapes and live lengths 256 / 512 / 1,024 walk 90 / 80 /
# 68% live positions in 0.72 / 0.59 / 0.56 ms a layer (PERF.md section
# 6, PR 36)
_BLK = 512
# the Pallas interpreter in place of Mosaic, on any backend: tests of
# the kernel's body on the CPU set this; nothing else does
_INTERPRET = False


def _softmax_rows(scores, keep):
    """float32 softmax over the last axis of what ``keep`` admits."""
    return jax.nn.softmax(jnp.where(keep, scores, _F32(-1e30)), axis=-1)


def _expanded(q, latent, w, nope_dim, scale):
    """q (B, S, H, dn + dr), latent (B, S, rkv + dr), w (H, dn + dv, rkv)
    -> (B, S, H, dv): causal, position t sees [0, t]."""
    b, s, h, _ = q.shape
    rkv = w.shape[-1]
    with device_scope("mxtpu.mixer.mla.expand"):
        kv = jnp.einsum("bsr,hnr->bshn", latent[..., :rkv], w,
                        preferred_element_type=_F32).astype(q.dtype)
        k_r = jnp.broadcast_to(latent[:, :, None, rkv:],
                               (b, s, h, latent.shape[-1] - rkv))
        k = jnp.concatenate([kv[..., :nope_dim], k_r], axis=-1)
        v = kv[..., nope_dim:]
    with device_scope("mxtpu.mixer.mla.attend"):
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                            preferred_element_type=_F32) * _F32(scale)
        probs = _softmax_rows(scores, jnp.tril(jnp.ones((s, s), bool)))
        return jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v,
                          preferred_element_type=_F32)


def _attend_dense(qq, page, off, rkv, scale):
    """qq (B, H, rkv + dr), page (B, C, rkv + dr), off (B,) int32 -> the
    softmax-weighted sum of the rows' latents (B, H, rkv) in ``qq``'s
    dtype, over every position of the page with those past ``off[b]``
    masked: the definition."""
    b, c, _ = page.shape
    scores = jnp.einsum("bhr,bcr->bhc", qq, page,
                        preferred_element_type=_F32) * _F32(scale)
    live = jnp.arange(c, dtype=jnp.int32)[None, :] <= off.reshape(b, 1)
    probs = _softmax_rows(scores, live[:, None, :])
    # over the whole row: its last ``dr`` columns are cut from the
    # SUM (H x (rkv + dr) numbers), never from the page
    u = jnp.einsum("bhc,bcr->bhr", probs.astype(page.dtype), page,
                   preferred_element_type=_F32)[..., :rkv]
    return u.astype(qq.dtype)


def _attend_block(q, rows, keep, m_ref, l_ref, acc_ref, scale):
    """One step of the online softmax inside a kernel: q (H, rkv + dr)
    over a block ``rows`` (rkv + dr, blk) of a page seen positions-minor,
    ``keep`` (H, blk) or None for a block that is live throughout; the
    running maximum ``m_ref`` and sum ``l_ref`` (H, 1) and the
    accumulator ``acc_ref`` (H, rkv), all float32, are brought up to
    date."""
    s = jnp.dot(q, rows, preferred_element_type=_F32) * _F32(scale)
    if keep is not None:
        s = jnp.where(keep, s, _F32(-1e30))
    m_was = m_ref[...]
    m_now = jnp.maximum(m_was, jnp.max(s, axis=1, keepdims=True))
    fade = jnp.exp(m_was - m_now)
    e = jnp.exp(s - m_now)
    l_ref[...] = fade * l_ref[...] + jnp.sum(e, axis=1, keepdims=True)
    # the lanes of both: the block's first rkv sublanes are ``c``
    acc_ref[...] = fade * acc_ref[...] + lax.dot_general(
        e.astype(rows.dtype), rows[:acc_ref.shape[1]],
        (((1,), (1,)), ((), ())), preferred_element_type=_F32)
    m_ref[...] = m_now


def _walk_call(qq, page, off, rkv, scale):
    """The same function of the same operands for a page stored
    positions-minor, as ONE kernel.  Grid step ``b`` walks row ``b``'s
    blocks ``0 .. off[b] // blk`` of the page seen as ``(B, rkv + dr,
    C)``: block ``j + 1`` (or the next row's first) is on its way into
    one half of a VMEM buffer while block ``j`` is attended out of the
    other (which half: the parity of the blocks walked before it, so no
    state crosses grid steps but the buffer).  A block's scores are a
    plain ``(H, rkv + dr) x (rkv + dr, blk)`` product, the weighted sum
    contracts the lanes of the exponentials and of the block's first
    ``rkv`` sublanes (``c`` alone: ``k_r`` never enters it).  Only a
    row's last block is masked.  A page no multiple of ``blk`` long
    ends in a block that starts early and masks what the block before
    it attended."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    b, c, r = page.shape
    h = qq.shape[1]
    blk = min(_BLK, c)
    view = jnp.transpose(page, (0, 2, 1))
    last = jnp.clip(off, 0, c - 1)
    blocks = last // blk + 1
    before = jnp.cumsum(blocks) - blocks

    def kernel(last_ref, blocks_ref, before_ref, q_ref, page_ref, out_ref,
               buf, sem, m_ref, l_ref, acc_ref):
        i = pl.program_id(0)
        n = blocks_ref[i]

        def begins(j):
            return pl.multiple_of(jnp.minimum(j * blk, c - blk), _LANES)

        def fetch(row, j, half):
            return pltpu.make_async_copy(
                page_ref.at[row, :, pl.ds(begins(j), blk)], buf.at[half],
                sem.at[half])

        @pl.when(i == 0)
        def _():
            fetch(0, 0, 0).start()

        m_ref[...] = jnp.full(m_ref.shape, -jnp.inf, _F32)
        l_ref[...] = jnp.zeros(l_ref.shape, _F32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, _F32)
        q = q_ref[0]

        def attend(j, masked):
            half = (before_ref[i] + j) % 2

            @pl.when(j + 1 < n)
            def _():
                fetch(i, j + 1, 1 - half).start()

            @pl.when((j + 1 == n) & (i + 1 < b))
            def _():
                fetch(i + 1, 0, 1 - half).start()

            fetch(i, j, half).wait()
            keep = None
            if masked:
                pos = begins(j) + lax.broadcasted_iota(jnp.int32, (h, blk), 1)
                keep = pos <= last_ref[i]
                if c % blk:
                    keep &= pos >= j * blk
            _attend_block(q, buf[half], keep, m_ref, l_ref, acc_ref, scale)

        lax.fori_loop(0, n - 1, lambda j, _: attend(j, False), None)
        attend(n - 1, True)
        out_ref[0] = (acc_ref[...] / l_ref[...]).astype(out_ref.dtype)

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(b,),
            in_specs=[pl.BlockSpec((1, h, r), lambda i, *_: (i, 0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, h, rkv), lambda i, *_: (i, 0, 0)),
            scratch_shapes=[pltpu.VMEM((2, r, blk), page.dtype),
                            pltpu.SemaphoreType.DMA((2,)),
                            pltpu.VMEM((h, 1), _F32),
                            pltpu.VMEM((h, 1), _F32),
                            pltpu.VMEM((h, rkv), _F32)]),
        out_shape=jax.ShapeDtypeStruct((b, h, rkv), qq.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=_INTERPRET,
        name="latent_attend_walk",
    )(last, blocks, before, qq, view)


def _by_rows(mesh, arg_shapes, result_shape):
    """Every operand and the result split as the page's rows are (a
    ``dp`` plan), nothing else: a shard attends its own rows."""
    return page_write._by_rows(mesh, arg_shapes[1:], result_shape)


@functools.lru_cache(maxsize=None)
def _walk_rows(rkv, scale):
    """``_walk_call`` at one latent width and scale, as a function of
    (qq, page, off) the partitioner and ``jax.jvp`` can take."""
    def call(qq, page, off):
        return _walk_call(qq, page, off, rkv, scale)

    def partition(mesh, arg_shapes, result_shape):
        rows = _by_rows(mesh, arg_shapes, result_shape)
        return mesh, call, rows, (rows,) * 3

    # a kernel is opaque to the partitioner, which would gather the whole
    # page onto every device of a ``dp`` plan; told that rows are
    # independent, it runs the kernel on each shard's rows
    sharded = custom_partitioning(call)
    sharded.def_partition(
        partition=partition, infer_sharding_from_operands=_by_rows,
        sharding_rule="b h r, b c r, b -> b h k")

    @jax.custom_jvp
    def walk(qq, page, off):
        return sharded(qq, page, off)

    @walk.defjvp
    def _(primals, tangents):
        # the kernel has no derivative rule of its own, the definition's
        # serves (no served path takes one)
        return jax.jvp(
            lambda a, p: _attend_dense(a, p, primals[2], rkv, scale),
            primals[:2], tangents[:2])

    return walk


def _walks(page):
    """True where the walk is the TPU's lowering for this page: the
    runtime stores it positions-minor, in whole lane tiles.  False
    where no TPU is attached."""
    return (page.shape[1] % _LANES == 0
            and page_write._positions_on_lanes(page.shape, page.dtype))


def _walk_or_dense(page, walk, dense, *args):
    """``walk(*args)`` where the kernel is what runs, ``dense(*args)``
    anywhere else: decided for the page, then by the platform the
    program is lowered for."""
    if not _walks(page):
        return dense(*args)
    if _INTERPRET:
        return walk(*args)
    return lax.platform_dependent(*args, tpu=walk, default=dense)


def _absorbed(q, page, w, offset, nope_dim, scale):
    """q (B, 1, H, dn + dr), page (B, C, rkv + dr) whose rows [0,
    offset[b]] are written, w (H, dn + dv, rkv) -> (B, 1, H, dv)."""
    rkv = w.shape[-1]
    w_uk, w_uv = w[:, :nope_dim], w[:, nope_dim:]
    with device_scope("mxtpu.mixer.mla.absorb"):
        # heads lead (XLA's CPU backend has no bfloat16 product for the
        # "bhn,hnr->bhr" order; the TPU's lays both out itself)
        qt = jnp.einsum("hbn,hnr->hbr",
                        jnp.swapaxes(q[:, 0, :, :nope_dim], 0, 1), w_uk,
                        preferred_element_type=_F32).astype(q.dtype)
        qq = jnp.concatenate([jnp.swapaxes(qt, 0, 1),
                              q[:, 0, :, nope_dim:]], axis=-1)
    with device_scope("mxtpu.mixer.mla.attend"):
        off = offset.astype(jnp.int32).reshape(-1)
        dense = functools.partial(_attend_dense, rkv=rkv, scale=scale)
        u = _walk_or_dense(page, _walk_rows(rkv, scale), dense, qq, page,
                           off)
    with device_scope("mxtpu.mixer.mla.absorb"):
        out = jnp.einsum("bhr,hvr->bhv", u, w_uv,
                         preferred_element_type=_F32)
    return out[:, None]


@register("_contrib_LatentAttentionWalked", num_inputs=2)
def latent_attention_walked(latent, offset):
    """The positions ``_contrib_LatentAttention``'s decode mode runs over
    for this page at these offsets (B,), by the lowering it takes: the
    blocks of ``_BLK`` positions the kernel walks, or rows x the page's
    length where the definition runs.  () int32."""
    b, c, _ = latent.shape
    blk = min(_BLK, c)

    def walked(off):
        return jnp.sum(jnp.clip(off, 0, c - 1) // blk + 1) * blk

    return _walk_or_dense(latent, walked, lambda off: jnp.int32(b * c),
                          offset.astype(jnp.int32).reshape(-1))


@register("_contrib_LatentAttention", num_inputs=None)
def latent_attention(query, latent, w_ukv, *rest, nope_dim=1, v_dim=1,
                     use_offset=False):
    """Multi-head latent attention, expanded or absorbed.

    query (B, S, H, dn + dr), its last ``dr`` features rotated; latent
    (B, C, rkv + dr), rows ``[c ; k_r]`` (``c`` normed, ``k_r``
    rotated); w_ukv (H (dn + dv), rkv), head ``i``'s rows ``[W_uk,i ;
    W_uv,i]``; with ``use_offset`` a fourth input ``offset`` (B,).

    Without ``offset`` ``latent`` is the PROMPT's own rows (C == S) and
    the keys and values are expanded per head from them (causal).  With
    it ``latent`` is a PAGE whose rows ``[0, offset[b]]`` are written,
    S is 1, and the up-projections are absorbed into the query and the
    output: the page is attended as it is stored.  Both are the same
    function of the same weights.  ``scale`` is ``(dn + dr)^-0.5``.
    Returns (B, S, H dv) in ``query``'s dtype.
    """
    b, s, h, d_qk = query.shape
    rope_dim = d_qk - nope_dim
    rkv = latent.shape[-1] - rope_dim
    if rope_dim < 0 or rkv < 1 or w_ukv.shape != (h * (nope_dim + v_dim),
                                                  rkv):
        raise ValueError(
            f"LatentAttention: query heads of {d_qk} = {nope_dim} + rope, "
            f"rows of {latent.shape[-1]} = latent + rope and w_ukv "
            f"{w_ukv.shape} do not fit {h} heads of {nope_dim} + {v_dim}")
    w = w_ukv.reshape(h, nope_dim + v_dim, rkv)
    scale = float(d_qk) ** -0.5
    if use_offset and rest:
        if s != 1:
            raise ValueError("LatentAttention: a page takes one query a "
                             f"row, got {s}")
        out = _absorbed(query, latent, w, rest[0], nope_dim, scale)
    else:
        if latent.shape[1] != s:
            raise ValueError(
                f"LatentAttention: {s} queries over {latent.shape[1]} "
                "rows and no offset: a prompt attends its own rows")
        out = _expanded(query, latent, w, nope_dim, scale)
    return out.reshape(b, s, h * v_dim).astype(query.dtype)
