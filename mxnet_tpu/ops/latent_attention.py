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

Scores and softmax are float32; every product takes the inputs' dtype
in and accumulates float32; ``qt_i`` and the latent sum are rounded to
that dtype where they enter the next product.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .registry import register

_F32 = jnp.float32


def _softmax_rows(scores, keep):
    """float32 softmax over the last axis of what ``keep`` admits."""
    return jax.nn.softmax(jnp.where(keep, scores, _F32(-1e30)), axis=-1)


def _expanded(q, latent, w, nope_dim, scale):
    """q (B, S, H, dn + dr), latent (B, S, rkv + dr), w (H, dn + dv, rkv)
    -> (B, S, H, dv): causal, position t sees [0, t]."""
    b, s, h, _ = q.shape
    rkv = w.shape[-1]
    with jax.named_scope("mxtpu.mixer.mla.expand"):
        kv = jnp.einsum("bsr,hnr->bshn", latent[..., :rkv], w,
                        preferred_element_type=_F32).astype(q.dtype)
        k_r = jnp.broadcast_to(latent[:, :, None, rkv:],
                               (b, s, h, latent.shape[-1] - rkv))
        k = jnp.concatenate([kv[..., :nope_dim], k_r], axis=-1)
        v = kv[..., nope_dim:]
    with jax.named_scope("mxtpu.mixer.mla.attend"):
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                            preferred_element_type=_F32) * _F32(scale)
        probs = _softmax_rows(scores, jnp.tril(jnp.ones((s, s), bool)))
        return jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v,
                          preferred_element_type=_F32)


def _absorbed(q, page, w, offset, nope_dim, scale):
    """q (B, 1, H, dn + dr), page (B, C, rkv + dr) whose rows [0,
    offset[b]] are written, w (H, dn + dv, rkv) -> (B, 1, H, dv)."""
    b, c, _ = page.shape
    rkv = w.shape[-1]
    w_uk, w_uv = w[:, :nope_dim], w[:, nope_dim:]
    with jax.named_scope("mxtpu.mixer.mla.absorb"):
        # heads lead (XLA's CPU backend has no bfloat16 product for the
        # "bhn,hnr->bhr" order; the TPU's lays both out itself)
        qt = jnp.einsum("hbn,hnr->hbr",
                        jnp.swapaxes(q[:, 0, :, :nope_dim], 0, 1), w_uk,
                        preferred_element_type=_F32).astype(q.dtype)
        qq = jnp.concatenate([jnp.swapaxes(qt, 0, 1),
                              q[:, 0, :, nope_dim:]], axis=-1)
    with jax.named_scope("mxtpu.mixer.mla.attend"):
        scores = jnp.einsum("bhr,bcr->bhc", qq, page,
                            preferred_element_type=_F32) * _F32(scale)
        live = jnp.arange(c, dtype=jnp.int32)[None, :] \
            <= offset.astype(jnp.int32).reshape(b, 1)
        probs = _softmax_rows(scores, live[:, None, :])
        # over the whole row: its last ``dr`` columns are cut from the
        # SUM (H x (rkv + dr) numbers), never from the page
        u = jnp.einsum("bhc,bcr->bhr", probs.astype(page.dtype), page,
                       preferred_element_type=_F32)[..., :rkv]
    with jax.named_scope("mxtpu.mixer.mla.absorb"):
        out = jnp.einsum("bhr,hvr->bhv", u.astype(q.dtype), w_uv,
                         preferred_element_type=_F32)
    return out[:, None]


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
