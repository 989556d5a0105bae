"""Pallas flash-attention kernels for TPU (forward AND backward).

Capability parity / perf: the reference leans on cuDNN fused attention
(contrib transformer ops); the TPU equivalent is a Pallas kernel that
streams K/V blocks through VMEM with an online-softmax accumulator, never
materializing the (S,S) score matrix in HBM (SURVEY.md §5 "Long-context",
pallas_guide.md tiling/grid sections).

Forward emits the per-row log-sum-exp alongside the output; backward is
the standard two-pass flash scheme (FlashAttention-2 layout):
  * pass 1 (grid BH×Qblk×Kblk): recompute P from the saved LSE, accumulate
    dQ += (P ∘ (dO Vᵀ − Δ)) K · scale in VMEM scratch;
  * pass 2 (grid BH×Kblk×Qblk): accumulate dV += Pᵀ dO and
    dK += (P ∘ (dO Vᵀ − Δ))ᵀ Q · scale;
with Δ = rowsum(dO ∘ O) computed once in XLA.  Neither pass materializes
(S,S) in HBM.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["flash_attention"]

_BLOCK_Q = 128
_BLOCK_K = 128
_LANE = 128  # TPU lane width: head_dim is zero-padded up to this


def _default_blocks(s_q, s_k):
    """Seq-adaptive tile defaults from a v5e block sweep (taken at
    sha dc2bc5d5; not measured on today's code): 128x128 was the WORST
    row at every
    swept seq — bwd at 2048 runs 2.0x faster at 256x256 (10.46 →
    5.25 ms) and at 1024 1.7x faster at 128x512 (2.11 → 1.25 ms).
    Larger tiles amortize the dq/dkv revisits across the grid; VMEM
    stays comfortable (256x256 f32 scores = 256 KiB of ~16 MiB)."""
    s = max(s_q, s_k)
    if s >= 2048:
        want_q, want_k = 256, 256
    elif s >= 1024:
        want_q, want_k = 128, 512
    else:
        want_q, want_k = _BLOCK_Q, _BLOCK_K
    bq = want_q if s_q % want_q == 0 else _BLOCK_Q
    bk = want_k if s_k % want_k == 0 else _BLOCK_K
    return bq, bk


def _blocks(s_q, s_k):
    """(block_q, block_k) for this launch: env-tunable so an on-chip
    sweep can vary backward block sizes (the s>=1024 dq/dkv perf
    lever, VERDICT r3 #4) without rebuilding; unset or
    non-dividing values fall back to the measured seq-adaptive
    defaults (clamped to 128 when those don't divide either)."""
    from .. import envs
    dq, dk = _default_blocks(s_q, s_k)
    bq = envs.get("MXTPU_FLASH_BLOCK_Q") or dq
    bk = envs.get("MXTPU_FLASH_BLOCK_K") or dk
    if bq <= 0 or s_q % bq:
        bq = dq
    if bk <= 0 or s_k % bk:
        bk = dk
    return bq, bk

# interpret mode runs the kernel on the Pallas interpreter (any backend)
# — used by the CPU test suite; toggled via tests or MXTPU_FLASH_INTERPRET
# (typed read: '0'/'false' parse as off, unlike the old truthy-string)
from .. import envs as _envs
_INTERPRET = _envs.get("MXTPU_FLASH_INTERPRET")


def _fwd_kernel(q_ref, k_ref, v_ref, *rest, scale, causal,
                num_k_blocks, causal_offset, emit_lse, with_kmask,
                window=None):
    """One (batch*head, q-block, k-block) grid step.

    The k-block loop lives in the GRID (innermost dim, sequential on TPU)
    with the online-softmax state in VMEM scratch persisting across
    steps — the canonical Pallas flash layout, and it keeps every index
    static (dynamic in-kernel slices mis-lower under jax_enable_x64).
    """
    from jax.experimental import pallas as pl

    rest = list(rest)
    kmask_ref = rest.pop(0) if with_kmask else None
    o_ref = rest.pop(0)
    lse_ref = rest.pop(0) if emit_lse else None
    m_scr, l_scr, acc_scr = rest

    q_idx = pl.program_id(1)
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        m_scr[...] = jnp.full(m_scr.shape, -jnp.inf, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    q = q_ref[...]  # (block_q, d)
    k = k_ref[...]  # (block_k, d)
    v = v_ref[...]
    block_q, d = q.shape
    block_k = k.shape[0]

    def _accum():
        # operands stay in the input dtype (bf16 on the AMP path) so
        # the MXU runs at native rate; preferred_element_type keeps the
        # ACCUMULATOR f32 either way.  f32 inputs pin Precision.HIGHEST
        # explicitly: without it XLA's DEFAULT runs f32 matmuls at bf16
        # operand precision on TPU, making kernel numerics depend on the
        # ambient jax.default_matmul_precision context.  Contract: f32 in
        # → f32-grade math, bf16 in → MXU-native ops + f32 accumulate.
        prec = (None if q.dtype == jnp.bfloat16
                else jax.lax.Precision.HIGHEST)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32,
                    precision=prec) * scale
        if causal:
            # end-aligned like the XLA oracle's tril(k=s_k-s_q): query
            # i may attend keys up to i + (s_k - s_q), so
            # cross-attention with s_k != s_q masks identically
            q_pos = q_idx * np.int32(block_q) + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = kb * np.int32(block_k) + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            keep = q_pos + np.int32(causal_offset) >= k_pos
            if window is not None:
                # sliding window (Mistral-style band): query i attends
                # keys in (i+offset-W, i+offset]
                keep &= k_pos > q_pos + np.int32(causal_offset - window)
            s = jnp.where(keep, s, -1e30)
        if with_kmask:
            # key-padding mask row for this (batch, k-block): keep=True
            s = jnp.where(kmask_ref[...][:1] > 0, s, -1e30)

        # m/l scratch is (block_q, 128): TPU vector stores need a full
        # lane dim; value is replicated, column 0 is authoritative
        m = m_scr[...][:, :1]
        l = l_scr[...][:, :1]
        acc = acc_scr[...]
        m_new = jnp.maximum(m, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        lanes = m_scr.shape[1]
        m_scr[...] = jnp.broadcast_to(m_new, (m_new.shape[0], lanes))
        l_new = alpha * l + p.sum(axis=1, keepdims=True)
        l_scr[...] = jnp.broadcast_to(l_new, (l_new.shape[0], lanes))
        # P rides the MXU in the value dtype when v is low-precision
        # (what the bf16 XLA oracle does too); f32 v keeps the f32 pass
        p_op = p.astype(v.dtype) if v.dtype == jnp.bfloat16 else p
        acc_scr[...] = alpha * acc + jnp.dot(
            p_op, v, preferred_element_type=jnp.float32, precision=prec)

    if causal and causal_offset >= 0:
        # block-level causal skip: a k-block whose FIRST key is beyond
        # the last query this q-block may attend is entirely masked —
        # skip its matmuls (≈2x less MXU work over the full grid, the
        # long-seq causal perf lever).  With offset >= 0 this is
        # EXACTLY the old math: kb=0 is always visible, so by the time
        # a skipped block would run, m is finite and its contribution
        # was p = exp(-1e30 - m) = 0, alpha = 1 — a no-op.  offset < 0
        # (causal cross-attention, s_q > s_k) keeps the full grid:
        # there a whole q-block can attend zero keys and skipping it
        # would leave l = 0 → 0/0 NaN where the oracle emits uniform
        # rows.
        visible = (q_idx * np.int32(block_q)
                   + np.int32(block_q - 1 + causal_offset)
                   >= kb * np.int32(block_k))
        if window is not None:
            # band's other edge: block dead once its LAST key falls at
            # or below the FIRST query's window floor — with offset>=0
            # every row still attends >= 1 key (its own diagonal), so
            # the skip stays division-safe.  This is what makes sliding
            # window O(S·W): only ~W/block_k + 1 k-blocks per q-block
            # survive, independent of S.
            visible &= (kb * np.int32(block_k) + np.int32(block_k - 1)
                        > q_idx * np.int32(block_q)
                        + np.int32(causal_offset - window))
        pl.when(visible)(_accum)
    else:
        _accum()

    @pl.when(kb == num_k_blocks - 1)
    def _done():
        o_ref[...] = (acc_scr[...] / l_scr[...][:, :1]).astype(
            o_ref.dtype)
        if emit_lse:
            # per-row log-sum-exp (lane-replicated), for the backward
            lse = m_scr[...][:, :1] + jnp.log(l_scr[...][:, :1])
            lse_ref[...] = jnp.broadcast_to(lse, lse_ref.shape)


def _blocked_specs(d, bq=_BLOCK_Q, bk=_BLOCK_K):
    from jax.experimental import pallas as pl

    # NOTE on index maps: with jax_enable_x64 a literal `0` in an index
    # map becomes i64 and Mosaic rejects the mixed (i32, i64) signature;
    # `i - i` keeps everything i32 regardless of the x64 flag.
    zero = lambda i: i - i
    q_spec = pl.BlockSpec((None, bq, d),
                          lambda i, j, kb: (i, j, zero(i)))
    k_spec = pl.BlockSpec((None, bk, d),
                          lambda i, j, kb: (i, kb, zero(i)))
    return zero, q_spec, k_spec


def _kmask_rows(kmask, s_k):
    """(B, S_k) key-padding mask → (B, 8, S_k) f32 rows (sublane-padded
    so the (8, block_k) tile satisfies TPU tiling; row 0 is read)."""
    m = kmask.astype(jnp.float32)[:, None, :]
    return jnp.broadcast_to(m, (m.shape[0], 8, s_k))


def _kmask_spec(h, kb_in_dim2=True, bk=_BLOCK_K):
    from jax.experimental import pallas as pl

    # grid dim 0 is b*h: batch index = i // h (static closure over h).
    # The k-block rides grid dim 2 (fwd, dq) or dim 1 (dkv).
    if kb_in_dim2:
        return pl.BlockSpec((None, 8, bk),
                            lambda i, j, kb: (i // h, j - j, kb))
    return pl.BlockSpec((None, 8, bk),
                        lambda i, kb, j: (i // h, j - j, kb))


def _fold(x, b, h, s, d):
    return x.transpose(0, 2, 1, 3).reshape(b * h, s, d)


def _unfold(x, b, h, s, d):
    return x.reshape(b, h, s, d).transpose(0, 2, 1, 3)


def _flash_fwd_pallas(q, k, v, scale, causal, want_lse=True,
                      kmask=None, window=None):
    """q,k,v: (B, S, H, D) → (out (B, S, H, D), lse (B*H, S_q, 128) or
    None when ``want_lse=False`` — the inference path skips the LSE
    output entirely rather than writing HBM it will discard).

    head_dim < 128 (e.g. BERT's 64) is zero-padded up to the lane
    width: QKᵀ contracts over D so zero columns don't change scores,
    and PV leaves the padded output columns zero — sliced off at the
    end.  XLA would pad the minor dim to 128 on the MXU anyway, so the
    padding costs ~nothing on chip.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, s_q, h, d_orig = q.shape
    s_k = k.shape[1]
    pad = (-d_orig) % _LANE
    if pad:
        widths = ((0, 0), (0, 0), (0, 0), (0, pad))
        q = jnp.pad(q, widths)
        k = jnp.pad(k, widths)
        v = jnp.pad(v, widths)
    d = d_orig + pad
    qf = _fold(q, b, h, s_q, d)
    kf = _fold(k, b, h, s_k, d)
    vf = _fold(v, b, h, s_k, d)

    bq, bk = _blocks(s_q, s_k)
    num_k_blocks = s_k // bk
    grid = (b * h, s_q // bq, num_k_blocks)
    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                               num_k_blocks=num_k_blocks,
                               causal_offset=s_k - s_q,
                               emit_lse=want_lse,
                               with_kmask=kmask is not None,
                               window=window)
    zero, q_spec, k_spec = _blocked_specs(d, bq, bk)
    lse_spec = pl.BlockSpec((None, bq, _LANE),
                            lambda i, j, kb: (i, j, zero(i)))
    in_specs = [q_spec, k_spec, k_spec]
    inputs = [qf, kf, vf]
    if kmask is not None:
        in_specs.append(_kmask_spec(h, bk=bk))
        inputs.append(_kmask_rows(kmask, s_k))
    out_specs = [q_spec, lse_spec] if want_lse else q_spec
    out_shape = [jax.ShapeDtypeStruct((b * h, s_q, d), q.dtype),
                 jax.ShapeDtypeStruct((b * h, s_q, _LANE), jnp.float32)]
    res = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape if want_lse else out_shape[0],
        scratch_shapes=[
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        interpret=_INTERPRET,
    )(*inputs)
    out, lse = res if want_lse else (res, None)
    return _unfold(out, b, h, s_q, d)[..., :d_orig], lse


def _dq_kernel(q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref, *rest,
               scale, causal, num_k_blocks, causal_offset, with_kmask,
               window=None):
    from jax.experimental import pallas as pl

    rest = list(rest)
    kmask_ref = rest.pop(0) if with_kmask else None
    dq_ref, dq_scr = rest

    q_idx = pl.program_id(1)
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        dq_scr[...] = jnp.zeros(dq_scr.shape, jnp.float32)

    # operands keep the input dtype (MXU-native on the bf16 path; f32
    # precision when inputs are f32) — accumulators are always f32
    q = q_ref[...]
    k = k_ref[...]
    v = v_ref[...]
    g = g_ref[...]
    lse = lse_ref[...][:, :1]
    delta = delta_ref[...][:, :1]
    block_q, _ = q.shape
    block_k = k.shape[0]
    lowp = q.dtype == jnp.bfloat16
    # same precision contract as the forward: f32 inputs pin HIGHEST
    prec = None if lowp else jax.lax.Precision.HIGHEST

    def _accum():
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32,
                    precision=prec) * scale
        mask = None
        if causal:
            q_pos = q_idx * np.int32(block_q) + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = kb * np.int32(block_k) + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            mask = q_pos + np.int32(causal_offset) >= k_pos
            if window is not None:
                mask &= k_pos > q_pos + np.int32(causal_offset - window)
            s_m = jnp.where(mask, s, -1e30)
        else:
            s_m = s
        if with_kmask:
            s_m = jnp.where(kmask_ref[...][:1] > 0, s_m, -1e30)
        p = jnp.exp(s_m - lse)
        if causal:
            # explicit zero (not exp of a huge negative) so fully-masked
            # rows contribute NO gradient instead of fp32-rounding noise
            p = jnp.where(mask, p, 0.0)
        if with_kmask:
            p = jnp.where(kmask_ref[...][:1] > 0, p, 0.0)
        dp = jnp.dot(g, v.T, preferred_element_type=jnp.float32,
                     precision=prec)
        ds = p * (dp - delta.astype(jnp.float32))
        ds_op = ds.astype(jnp.bfloat16) if lowp else ds
        dq_scr[...] += jnp.dot(ds_op, k,
                               preferred_element_type=jnp.float32,
                               precision=prec) * scale

    if causal:
        # skip k-blocks this q-block cannot attend.  Safe for ANY
        # causal_offset (unlike the forward): a skipped block's
        # contribution was exactly zero — p is hard-zeroed by the
        # where(mask, p, 0) — so dq_scr is untouched either way.
        visible = (q_idx * np.int32(block_q)
                   + np.int32(block_q - 1 + causal_offset)
                   >= kb * np.int32(block_k))
        if window is not None:
            visible &= (kb * np.int32(block_k) + np.int32(block_k - 1)
                        > q_idx * np.int32(block_q)
                        + np.int32(causal_offset - window))
        pl.when(visible)(_accum)
    else:
        _accum()

    @pl.when(kb == num_k_blocks - 1)
    def _done():
        dq_ref[...] = dq_scr[...].astype(dq_ref.dtype)


def _dkv_kernel(k_ref, v_ref, q_ref, g_ref, lse_ref, delta_ref, *rest,
                scale, causal, num_q_blocks, causal_offset, with_kmask,
                window=None):
    from jax.experimental import pallas as pl

    rest = list(rest)
    kmask_ref = rest.pop(0) if with_kmask else None
    dk_ref, dv_ref, dk_scr, dv_scr = rest

    kb = pl.program_id(1)
    qb = pl.program_id(2)

    @pl.when(qb == 0)
    def _init():
        dk_scr[...] = jnp.zeros(dk_scr.shape, jnp.float32)
        dv_scr[...] = jnp.zeros(dv_scr.shape, jnp.float32)

    k = k_ref[...]
    v = v_ref[...]
    q = q_ref[...]
    g = g_ref[...]
    lse = lse_ref[...][:, :1]
    delta = delta_ref[...][:, :1]
    block_k = k.shape[0]
    block_q = q.shape[0]
    lowp = q.dtype == jnp.bfloat16
    prec = None if lowp else jax.lax.Precision.HIGHEST

    def _accum():
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32,
                    precision=prec) * scale
        mask = None
        if causal:
            q_pos = qb * np.int32(block_q) + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = kb * np.int32(block_k) + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            mask = q_pos + np.int32(causal_offset) >= k_pos
            if window is not None:
                mask &= k_pos > q_pos + np.int32(causal_offset - window)
            s_m = jnp.where(mask, s, -1e30)
        else:
            s_m = s
        if with_kmask:
            s_m = jnp.where(kmask_ref[...][:1] > 0, s_m, -1e30)
        p = jnp.exp(s_m - lse)                   # (block_q, block_k)
        if causal:
            p = jnp.where(mask, p, 0.0)
        if with_kmask:
            p = jnp.where(kmask_ref[...][:1] > 0, p, 0.0)
        p_op = p.astype(jnp.bfloat16) if lowp else p
        dv_scr[...] += jnp.dot(p_op.T, g,
                               preferred_element_type=jnp.float32,
                               precision=prec)
        dp = jnp.dot(g, v.T, preferred_element_type=jnp.float32,
                     precision=prec)
        ds = p * (dp - delta.astype(jnp.float32))
        ds_op = ds.astype(jnp.bfloat16) if lowp else ds
        dk_scr[...] += jnp.dot(ds_op.T, q,
                               preferred_element_type=jnp.float32,
                               precision=prec) * scale

    if causal:
        # skip q-blocks that cannot attend this k-block: fully-masked
        # key columns keep their exact-zero dK/dV from the scratch
        # init (p is hard-zeroed in the old path, so this is exact for
        # any causal_offset)
        visible = (qb * np.int32(block_q)
                   + np.int32(block_q - 1 + causal_offset)
                   >= kb * np.int32(block_k))
        if window is not None:
            # band floor: this k-block is past every window when its
            # last key <= the q-block's first query's floor
            visible &= (kb * np.int32(block_k) + np.int32(block_k - 1)
                        > qb * np.int32(block_q)
                        + np.int32(causal_offset - window))
        pl.when(visible)(_accum)
    else:
        _accum()

    @pl.when(qb == num_q_blocks - 1)
    def _done():
        dk_ref[...] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_scr[...].astype(dv_ref.dtype)


def _flash_bwd_pallas(q, k, v, out, lse, g, scale, causal,
                      kmask=None, window=None):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, s_q, h, d_orig = q.shape
    s_k = k.shape[1]
    pad = (-d_orig) % _LANE
    if pad:
        widths = ((0, 0), (0, 0), (0, 0), (0, pad))
        q = jnp.pad(q, widths)
        k = jnp.pad(k, widths)
        v = jnp.pad(v, widths)
        out = jnp.pad(out, widths)
        g = jnp.pad(g, widths)
    d = d_orig + pad
    qf = _fold(q, b, h, s_q, d)
    kf = _fold(k, b, h, s_k, d)
    vf = _fold(v, b, h, s_k, d)
    gf = _fold(g, b, h, s_q, d)
    of = _fold(out, b, h, s_q, d)
    # Δ = rowsum(dO ∘ O), lane-replicated like the saved LSE
    delta = jnp.sum(gf.astype(jnp.float32) * of.astype(jnp.float32),
                    axis=-1, keepdims=True)
    delta = jnp.broadcast_to(delta, (b * h, s_q, _LANE))

    bq, bk = _blocks(s_q, s_k)
    num_q_blocks = s_q // bq
    num_k_blocks = s_k // bk
    causal_offset = s_k - s_q
    zero, q_spec, k_spec = _blocked_specs(d, bq, bk)
    lseq_spec = pl.BlockSpec((None, bq, _LANE),
                             lambda i, j, kb: (i, j, zero(i)))

    dq_in_specs = [q_spec, k_spec, k_spec, q_spec, lseq_spec,
                   lseq_spec]
    dq_inputs = [qf, kf, vf, gf, lse, delta]
    if kmask is not None:
        dq_in_specs.append(_kmask_spec(h, bk=bk))
        dq_inputs.append(_kmask_rows(kmask, s_k))
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal,
                          num_k_blocks=num_k_blocks,
                          causal_offset=causal_offset,
                          with_kmask=kmask is not None,
                          window=window),
        grid=(b * h, num_q_blocks, num_k_blocks),
        in_specs=dq_in_specs,
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((b * h, s_q, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        interpret=_INTERPRET,
    )(*dq_inputs)

    # pass 2: grid is (BH, k-block, q-block) — index maps swap roles
    kk_spec = pl.BlockSpec((None, bk, d),
                           lambda i, kb, j: (i, kb, zero(i)))
    qq_spec = pl.BlockSpec((None, bq, d),
                           lambda i, kb, j: (i, j, zero(i)))
    lse2_spec = pl.BlockSpec((None, bq, _LANE),
                             lambda i, kb, j: (i, j, zero(i)))
    dkv_in_specs = [kk_spec, kk_spec, qq_spec, qq_spec, lse2_spec,
                    lse2_spec]
    dkv_inputs = [kf, vf, qf, gf, lse, delta]
    if kmask is not None:
        # grid here is (BH, k-block, q-block): mask block follows kb
        dkv_in_specs.append(_kmask_spec(h, kb_in_dim2=False, bk=bk))
        dkv_inputs.append(_kmask_rows(kmask, s_k))
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, causal=causal,
                          num_q_blocks=num_q_blocks,
                          causal_offset=causal_offset,
                          with_kmask=kmask is not None,
                          window=window),
        grid=(b * h, num_k_blocks, num_q_blocks),
        in_specs=dkv_in_specs,
        out_specs=[kk_spec, kk_spec],
        out_shape=[jax.ShapeDtypeStruct((b * h, s_k, d), k.dtype),
                   jax.ShapeDtypeStruct((b * h, s_k, d), v.dtype)],
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                        pltpu.VMEM((bk, d), jnp.float32)],
        interpret=_INTERPRET,
    )(*dkv_inputs)

    dq = _unfold(dq, b, h, s_q, d)[..., :d_orig]
    dk = _unfold(dk, b, h, s_k, d)[..., :d_orig]
    dv = _unfold(dv, b, h, s_k, d)[..., :d_orig]
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _flash(q, k, v, kmask, scale, causal, window):
    # primal (inference) path: no LSE output at all
    out, _ = _flash_fwd_pallas(q, k, v, scale, causal, want_lse=False,
                               kmask=kmask, window=window)
    return out


def _flash_fwd(q, k, v, kmask, scale, causal, window):
    out, lse = _flash_fwd_pallas(q, k, v, scale, causal, kmask=kmask,
                                 window=window)
    # residual holds ONE lane of the lane-replicated LSE: the full
    # (BH, S, 128) copy would cost 128x the HBM across the fwd→bwd
    # interval on exactly the long-context runs flash exists for
    return out, (q, k, v, out, lse[:, :, :1], kmask)


def _flash_bwd(scale, causal, window, res, g):
    q, k, v, out, lse1, kmask = res
    lse = jnp.broadcast_to(lse1, lse1.shape[:2] + (_LANE,))
    dq, dk, dv = _flash_bwd_pallas(q, k, v, out, lse, g, scale, causal,
                                   kmask=kmask, window=window)
    return dq, dk, dv, None


_flash.defvjp(_flash_fwd, _flash_bwd)


def _as_key_padding(mask, batch=None, s_k=None, s_q=None):
    """(B, 1, 1, S_k) / (B, S_k) masks depend only on key position —
    the flash kernels support those; anything query- or head-dependent
    (incl. 2-D (S_q, S_k) attention masks) returns None (XLA
    fallback).  The result is broadcast to ``batch`` rows so the
    per-batch kernel block indexing is always in range.

    A 2-D mask whose shape satisfies BOTH readings — (B, S_k) key
    padding and (S_q, S_k) attention matrix, i.e. B == S_q — is
    genuinely ambiguous, and either silent binding corrupts numerics
    for the other intent, so it raises (ADVICE r2): disambiguate with
    ``kmask=`` / a (B, 1, 1, S_k) reshape for key padding, or a
    (1, 1, S_q, S_k) reshape for attention-matrix semantics."""
    import jax.numpy as _jnp

    if mask is None:
        return None
    km = None
    if mask.ndim == 2:
        # the documented 2-D form is per-batch key padding: accept
        # exactly (B, S_k); other 2-D shapes keep the legacy XLA
        # broadcast behavior
        if batch is not None and s_k is not None and \
                mask.shape == (batch, s_k):
            if s_q is not None and batch == s_q and batch > 1:
                from ..base import MXNetError
                raise MXNetError(
                    f"ambiguous 2-D attention mask {mask.shape}: with "
                    f"batch == S_q == {batch} it reads equally as "
                    "(B, S_k) key padding or an (S_q, S_k) attention "
                    "matrix. Pass kmask=/reshape((B, 1, 1, S_k)) for "
                    "key padding, or reshape((1, 1, S_q, S_k)) for "
                    "attention-matrix semantics.")
            km = mask
    elif mask.ndim == 4 and mask.shape[1] == 1 and mask.shape[2] == 1:
        km = mask.reshape(mask.shape[0], mask.shape[3])
    if km is None:
        return None
    if batch is not None and km.shape[0] == 1 and batch > 1:
        km = _jnp.broadcast_to(km, (batch,) + km.shape[1:])
    if batch is not None and km.shape[0] != batch:
        return None
    return km


def flash_attention(q, k, v, mask=None, scale=None, causal=False,
                    kmask=None, window=None):
    """Flash attention; (B, S, H, D) in/out.

    Key-padding masks ((B, 1, 1, S_k) or (B, S_k)) run INSIDE the
    kernels (fwd and both bwd passes); general query-dependent masks
    fall back to the XLA path.  Dispatchers that already normalized the
    mask pass ``kmask`` directly (avoids a second conversion).

    ``window``: sliding-window (banded causal, Mistral-style) width —
    query i attends keys (i+off-W, i+off].  Requires ``causal=True``.
    The kernels SKIP out-of-band blocks, so compute is O(S·W) instead
    of O(S²) — the long-context shape ring attention composes with."""
    if scale is None:
        scale = 1.0 / np.sqrt(q.shape[-1])
    if window is not None:
        window = int(window)
        if not causal:
            from ..base import MXNetError
            raise MXNetError(
                "flash_attention: window= requires causal=True "
                "(sliding window is a banded CAUSAL mask)")
        if window <= 0:
            from ..base import MXNetError
            raise MXNetError(f"flash_attention: window must be "
                             f"positive, got {window}")
        if window >= k.shape[1]:
            window = None             # band wider than keys = causal
    if kmask is None and mask is not None:
        kmask = _as_key_padding(mask, batch=q.shape[0], s_k=k.shape[1],
                                s_q=q.shape[1])
        if kmask is None:
            # query-dependent masks: XLA broadcast path, exactly the
            # pre-kernel behavior (ambiguous B==S_q 2-D masks raise
            # inside _as_key_padding instead)
            from .attention import _sdpa_xla
            return _sdpa_xla(q, k, v, mask, scale, causal,
                             window=window)
    return _flash(q, k, v, kmask, float(scale), bool(causal), window)
