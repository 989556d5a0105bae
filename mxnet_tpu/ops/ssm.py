"""State-space operators: the Mamba-1 selective scan (arXiv:2312.00752)
over a whole prompt and its one-token step, and the causal depthwise
convolution in front of it.

TPU-first design: composed XLA ops, no hand kernel.  The recurrence
``s_t = exp(delta_t A) s_{t-1} + (delta_t B_t) x_t`` is linear in ``s``,
so a prompt is scanned in CHUNKS: ``lax.associative_scan`` inside a chunk
of ``chunk`` positions (log depth), ``lax.scan`` over the chunks with the
state as carry, everything in float32.  The state and every temporary of
the scan are laid out ``(..., d_state, d_inner)``: ``d_inner`` (thousands)
is the minor axis the chip tiles by 128 lanes; the textbook
``(d_inner, d_state)`` would pad ``d_state`` = 16 to 128 and hold eight
times the bytes.

Rows of a right-padded batch stop at their own ``last_pos``: past it
``delta`` = 0 and ``x`` = 0, so ``exp(0) s + 0`` leaves the state exactly
where the row's last real token put it (a recurrent state IS exposed to
the next decode step, unlike a causal K/V page behind a validity mask).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from .registry import register


def _valid(last_pos, s):
    """(B, S) float32: 1 at positions <= the row's ``last_pos``."""
    pos = jnp.arange(s, dtype=jnp.float32)[None, :]
    return (pos <= last_pos.astype(jnp.float32).reshape(-1, 1)) \
        .astype(jnp.float32)


def _ssm_terms(x, dt, dt_bias, a_log, bmat):
    """float32 (decay, drive) of the recurrence, ``(..., N, Di)``."""
    f32 = jnp.float32
    delta = jax.nn.softplus(dt.astype(f32) + dt_bias.astype(f32))
    a = -jnp.exp(a_log.astype(f32))                      # (N, Di)
    return delta, a, bmat.astype(f32)


@register("_causal_conv1d", num_inputs=4, num_outputs=2)
def causal_conv1d(x, weight, bias, last_pos):
    """Causal depthwise convolution + SiLU over a right-padded batch.

    x (B, S, C); weight (K, C) (tap ``k`` multiplies ``x[t - (K-1) + k]``);
    bias (C,); last_pos (B,).  Returns ``silu(conv(x) + bias)`` with ``x``
    zeroed past each row's ``last_pos`` (B, S, C), and the row's TAIL:
    the inputs at ``last_pos - (K-2) .. last_pos`` (zeros before position
    0), shape (B, K-1, C), which the one-token step continues from."""
    b, s, c = x.shape
    k = weight.shape[0]
    xf = x.astype(jnp.float32) * _valid(last_pos, s)[:, :, None]
    w = weight.astype(jnp.float32)
    y = bias.astype(jnp.float32)[None, None, :] + w[k - 1] * xf
    for j in range(1, k):                 # tap k-1-j looks j positions back
        y = y + w[k - 1 - j] * jnp.pad(xf, ((0, 0), (j, 0), (0, 0)))[:, :s]
    idx = last_pos.astype(jnp.int32).reshape(-1, 1) \
        + jnp.arange(-(k - 2), 1, dtype=jnp.int32)[None, :]      # (B, K-1)
    tail = jnp.take_along_axis(x, jnp.maximum(idx, 0)[:, :, None], axis=1)
    tail = jnp.where((idx >= 0)[:, :, None], tail, jnp.zeros_like(tail))
    return jax.nn.silu(y).astype(x.dtype), tail


@register("_causal_conv1d_step", num_inputs=4, num_outputs=2)
def causal_conv1d_step(x, weight, bias, tail):
    """One token of :func:`causal_conv1d`: x (B, C), tail (B, K-1, C) ->
    (silu(conv) (B, C), the new tail)."""
    f32 = jnp.float32
    full = jnp.concatenate([tail.astype(x.dtype), x[:, None, :]], axis=1)
    y = jnp.einsum("bkc,kc->bc", full.astype(f32), weight.astype(f32)) \
        + bias.astype(f32)[None, :]
    return jax.nn.silu(y).astype(x.dtype), full[:, 1:].astype(tail.dtype)


@register("_selective_scan", num_inputs=8, num_outputs=2)
def selective_scan(x, dt, bmat, cmat, a_log, d_skip, dt_bias, last_pos, *,
                   chunk=64):
    """Mamba-1 selective scan over a right-padded batch, from a zero state.

    x, dt (B, S, Di); bmat, cmat (B, S, N); a_log (N, Di); d_skip,
    dt_bias (Di,); last_pos (B,).  ``delta = softplus(dt + dt_bias)``,
    ``A = -exp(a_log)``, ``s_t = exp(delta_t A) s_{t-1} + (delta_t B_t)
    x_t``, ``y_t = C_t . s_t + D x_t``.  Returns y (B, S, Di) in x's dtype
    and the state at each row's ``last_pos`` (B, N, Di) float32."""
    f32 = jnp.float32
    b, s, di = x.shape
    n = a_log.shape[0]
    valid = _valid(last_pos, s)[:, :, None]
    delta, a, bm = _ssm_terms(x, dt, dt_bias, a_log, bmat)
    delta = delta * valid                     # frozen past last_pos: exact
    xf = x.astype(f32) * valid
    cm = cmat.astype(f32)
    ch = min(int(chunk), s)
    pad = (-s) % ch
    if pad:                                   # delta = 0, x = 0: no-ops
        delta, xf, bm, cm = (jnp.pad(t, ((0, 0), (0, pad), (0, 0)))
                             for t in (delta, xf, bm, cm))
    nc = (s + pad) // ch

    def chunks(t):                            # (B, S, F) -> (nc, B, ch, F)
        return t.reshape(b, nc, ch, t.shape[-1]).transpose(1, 0, 2, 3)

    def combine(left, right):
        return left[0] * right[0], right[0] * left[1] + right[1]

    def body(state, xs):
        dl, xc, bc, cc = xs
        decay = jnp.exp(dl[:, :, None, :] * a[None, None])     # (B,ch,N,Di)
        drive = (dl * xc)[:, :, None, :] * bc[:, :, :, None]
        acc_a, acc_b = lax.associative_scan(combine, (decay, drive), axis=1)
        st = acc_a * state[:, None] + acc_b
        return st[:, -1], jnp.einsum("bln,blnd->bld", cc, st)

    state, ys = lax.scan(body, jnp.zeros((b, n, di), f32),
                         (chunks(delta), chunks(xf), chunks(bm), chunks(cm)))
    y = ys.transpose(1, 0, 2, 3).reshape(b, s + pad, di)[:, :s]
    y = y + d_skip.astype(f32)[None, None, :] * xf[:, :s]
    return y.astype(x.dtype), state


@register("_selective_scan_step", num_inputs=8, num_outputs=2)
def selective_scan_step(x, dt, bmat, cmat, a_log, d_skip, dt_bias, state):
    """One token of :func:`selective_scan`: x, dt (B, Di); bmat, cmat
    (B, N); state (B, N, Di) float32 -> (y (B, Di), the new state)."""
    f32 = jnp.float32
    delta, a, bm = _ssm_terms(x, dt, dt_bias, a_log, bmat)
    xf = x.astype(f32)
    new = jnp.exp(delta[:, None, :] * a[None]) * state.astype(f32) \
        + (delta * xf)[:, None, :] * bm[:, :, None]
    y = jnp.einsum("bn,bnd->bd", cmat.astype(f32), new) \
        + d_skip.astype(f32)[None, :] * xf
    return y.astype(x.dtype), new.astype(state.dtype)


@register("_take_positions", num_inputs=2)
def take_positions(data, pos):
    """data (B, S, ...), pos (B,) -> data[b, pos[b]] kept as (B, 1, ...):
    each row's own position of a right-padded batch."""
    idx = pos.astype(jnp.int32).reshape((-1, 1) + (1,) * (data.ndim - 2))
    return jnp.take_along_axis(data, idx, axis=1)
