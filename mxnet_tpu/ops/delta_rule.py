"""The gated delta rule with a per-channel decay (Kimi Delta Attention,
arXiv:2510.26692): a recurrent state that is a MATRIX a head, over a whole
prompt (a chunked scan) and one token at a time.

Per head, with ``S`` a ``(d_k, d_v)`` matrix, a token ``t`` does::

    S' = Diag(a_t) S_{t-1}                     a_t = exp(log_a_t), (d_k,)
    S_t = S' + b_t k_t (v_t - S'^T k_t)^T      b_t a number
    o_t = d_k^-0.5 S_t^T q_t

``b_t`` up to 2 lets ``I - b_t k_t k_t^T`` have negative eigenvalues.

TPU-first design: composed XLA ops, no hand kernel, everything of the
state in float32 with every product at ``Precision.HIGHEST`` (a float32
product on a TPU otherwise rounds its inputs to bfloat16).  A prompt is
scanned in CHUNKS of ``chunk`` positions, ``lax.scan`` over the chunks
with the state as carry (``ops/ssm.py``'s plumbing).  Inside a chunk the
WY form: with ``G_t`` the decay summed from the chunk's start (a (d_k,)
vector of logs, never positive) and ``u_t = b_t (v_t - S'_t^T k_t)`` the
update each position writes,

    (I + A) U = Diag(b) (V - (e^G * K) S_0),
    A_ti = b_t sum_d k_td k_id e^(G_td - G_id)          (i < t),

a unit lower-triangular system solved by forward substitution in blocks
(``_unit_lower_inverse``); then
``O = (e^G * Q) S_0 + tril(Q K^T, with the same decay) U`` and
``S_C = e^G_C * S_0 + (e^(G_C - G) * K)^T U``.  The decay between two
positions is taken as ``e^(G_t - G_i)`` of the pair, never as a quotient
of two exponentials, which underflow and overflow within a chunk.

Rows of a right-padded batch stop at their own ``last_pos``: past it
``log_a`` = 0 and ``b`` = 0, so a position there writes nothing and decays
nothing, and the state a row ends with is the one its last real token
left (``ops/ssm.py``'s rule, and ``_valid``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from .registry import register
from .ssm import _from_chunks, _in_chunks, _valid

_HI = lax.Precision.HIGHEST


def _unit_lower_inverse(a):
    """``(I + a)^-1`` for ``a`` (..., C, C) strictly lower-triangular: the
    blocked form of forward substitution.  Diagonal blocks of 1, 2, 4, ..
    positions are inverted and merged in pairs, ``T21 = -T22 A21 T11``,
    log2(C) levels of batched products where row-by-row substitution takes
    C dependent steps (and a solver call, which XLA's CPU backend hands to
    LAPACK).  A C that is no power of two is padded with identity rows,
    which leave the leading block's inverse as it is."""
    c = a.shape[-1]
    lead = a.shape[:-2]
    size = 1 << (c - 1).bit_length()
    if size != c:
        a = jnp.pad(a, [(0, 0)] * len(lead) + [(0, size - c)] * 2)
    inv = jnp.ones(lead + (size, 1, 1), a.dtype)    # blocks of one position
    s = 1
    while s < size:
        nb = size // (2 * s)
        blocks = a.reshape(lead + (nb, 2 * s, nb, 2 * s))
        below = jnp.einsum("...iajb,ij->...iab", blocks,
                           jnp.eye(nb, dtype=a.dtype))[..., s:, :s]
        t11, t22 = inv[..., 0::2, :, :], inv[..., 1::2, :, :]
        t21 = -jnp.einsum("...nab,...nbc,...ncd->...nad", t22, below, t11,
                          precision=_HI)
        inv = jnp.concatenate([
            jnp.concatenate([t11, jnp.zeros_like(t11)], axis=-1),
            jnp.concatenate([t21, t22], axis=-1)], axis=-2)
        s *= 2
    return inv[..., 0, :c, :c]


@register("_delta_gates", num_inputs=4, num_outputs=2)
def delta_gates(f, a_log, dt_bias, beta):
    """The decay and the step of a token from its gate projections: f
    (..., H dk) and beta (..., H) the projections, a_log (H,), dt_bias
    (H dk,) -> log_a (..., H, dk) = ``-exp(a_log_h) softplus(f +
    dt_bias)`` and b (..., H) = ``2 sigmoid(beta)`` (the 2 allows
    negative eigenvalues), both float32."""
    f32 = jnp.float32
    h = a_log.shape[0]
    rate = jax.nn.softplus(f.astype(f32) + dt_bias.astype(f32))
    rate = rate.reshape(rate.shape[:-1] + (h, -1))
    log_a = -jnp.exp(a_log.astype(f32))[:, None] * rate
    return log_a, 2.0 * jax.nn.sigmoid(beta.astype(f32))


@register("_delta_scan", num_inputs=7, num_outputs=2)
def delta_scan(q, k, v, log_a, b, state0, last_pos, *, chunk=64):
    """The gated delta rule over a right-padded batch, from ``state0``.

    q, k (B, S, H, dk); v (B, S, H, dv); log_a (B, S, H, dk), the log of
    the decay (<= 0); b (B, S, H); state0 (B, H, dk, dv); last_pos (B,).
    Returns the outputs (B, S, H,
    dv) float32 and each row's state at its ``last_pos`` (B, H, dk, dv)
    float32."""
    f32 = jnp.float32
    s = q.shape[1]
    scale = q.shape[-1] ** -0.5
    valid = _valid(last_pos, s)                          # (B, S)
    la = log_a.astype(f32) * valid[:, :, None, None]     # frozen: a = 1
    bb = b.astype(f32) * valid[:, :, None]               # frozen: b = 0
    ch, xs = _in_chunks(chunk, q.astype(f32), k.astype(f32), v.astype(f32),
                        la, bb)
    strict = jnp.tril(jnp.ones((ch, ch), bool), -1)
    causal = jnp.tril(jnp.ones((ch, ch), bool))

    def body(st, x):
        qc, kc, vc, lc, bc = x                   # (B, C, H, .), bc (B, C, H)
        g = jnp.cumsum(lc, axis=1)                           # (B, C, H, dk)
        # the decay from position i to t, feature by feature, i <= t
        gap = g[:, :, None] - g[:, None, :]                  # (B, t, i, H, dk)
        decay = jnp.exp(jnp.where(causal[None, :, :, None, None], gap,
                                  -jnp.inf))
        kk = jnp.sum(kc[:, :, None] * kc[:, None, :] * decay, axis=-1)
        qk = jnp.sum(qc[:, :, None] * kc[:, None, :] * decay, axis=-1)
        kk = jnp.where(strict[None, :, :, None], kk, 0.0).transpose(0, 3, 1, 2)
        qk = qk.transpose(0, 3, 1, 2)                        # (B, H, t, i)
        solve = _unit_lower_inverse(bc.transpose(0, 2, 1)[..., None] * kk)
        eg = jnp.exp(g)
        rhs = bc[..., None] * (vc - jnp.einsum(
            "bthk,bhkv->bthv", eg * kc, st, precision=_HI))
        u = jnp.einsum("bhti,bihv->bhtv", solve, rhs,
                       precision=_HI)                        # (B, H, C, dv)
        out = jnp.einsum("bthk,bhkv->bhtv", eg * qc, st, precision=_HI) \
            + jnp.einsum("bhti,bhiv->bhtv", qk, u, precision=_HI)
        last = g[:, -1:]                                     # (B, 1, H, dk)
        new = jnp.exp(last[:, 0])[..., None] * st + jnp.einsum(
            "bihk,bhiv->bhkv", jnp.exp(last - g) * kc, u, precision=_HI)
        return new, (scale * out).transpose(0, 2, 1, 3)      # (B, C, H, dv)

    state, ys = lax.scan(body, state0.astype(f32), tuple(xs))
    return _from_chunks(ys, s), state


@register("_delta_step", num_inputs=6, num_outputs=2)
def delta_step(q, k, v, log_a, b, state):
    """One token of :func:`delta_scan` for every row: q, k (B, H, dk); v
    (B, H, dv); log_a (B, H, dk); b (B, H); state (B, H, dk, dv) ->
    (o (B, H, dv) float32, the new state in ``state``'s dtype)."""
    f32 = jnp.float32
    kf = k.astype(f32)
    decayed = jnp.exp(log_a.astype(f32))[..., None] * state.astype(f32)
    u = b.astype(f32)[..., None] * (v.astype(f32) - jnp.einsum(
        "bhk,bhkv->bhv", kf, decayed, precision=_HI))
    new = decayed + kf[..., None] * u[:, :, None, :]
    out = jnp.einsum("bhk,bhkv->bhv", q.astype(f32), new, precision=_HI)
    return out * q.shape[-1] ** -0.5, new.astype(state.dtype)
