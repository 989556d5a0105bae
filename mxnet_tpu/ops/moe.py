"""Mixture-of-experts feed-forward layers: two routed layers, side by side.

Beyond-reference capability (the reference has no MoE — SURVEY.md §2.3
parallelism checklist lists expert parallel as absent upstream).

``_contrib_MoEFFN`` (``moe_ffn``) is the TRAINING layer "with expert
parallelism" under GSPMD (GShard/Switch dense-dispatch formulation):
- softmax routing, dispatch as einsums over a STATIC capacity that DROPS
  what overflows — no dynamic shapes, so the whole layer jits and fuses;
- expert FFNs run as ONE batched (E, C, d)×(E, d, h) matmul — MXU-sized
  instead of a Python loop over experts;
- under a mesh-jitted step with expert weights sharded over an ``ep``
  axis (``parallel.moe_param_rule``), GSPMD inserts the all-to-alls —
  the canonical expert-parallel lowering on TPU.

``_contrib_RoutedExperts`` (``routed_experts``) is ONE CHIP'S SHARE of an
expert-parallel deployment, as a served model holds it
(``models/afmoe.py``): it is TOLD which contiguous range of the experts
it holds, routes over all of them (sigmoid scores, a selection bias that
picks and does not weigh, normalised and scaled gates), and returns the
partial sum its own experts give.  No capacity and no dropped token:
assignments are sorted by expert and the products are grouped
(``jax.lax.ragged_dot``), so any imbalance is exact.  It runs without an
exchange; nothing here stands in for the absent chips.  Two layers until
one serves both (ROADMAP, debts).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..profiler import device_scope
from .registry import register


@register("_contrib_MoEFFN", num_inputs=6, num_outputs=2)
def moe_ffn(x, gate_w, w1, b1, w2, b2, *, num_experts=1, k=1,
            capacity_factor=1.25, activation="relu"):
    """Top-k routed expert FFN.

    x (T, d); gate_w (d, E); w1 (E, d, h); b1 (E, h); w2 (E, h, d);
    b2 (E, d).  Returns (out (T, d), aux_loss ()) — aux_loss is the
    Switch-Transformer load-balancing loss (mean fraction · mean
    router prob per expert, scaled by E).
    """
    t, d = x.shape
    e = num_experts
    if k > e:
        raise ValueError(
            f"MoEFFN: k={k} exceeds num_experts={e}; a further routing "
            "round would silently double-dispatch to expert 0")
    logits = x @ gate_w                         # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)

    capacity = int(np.ceil(k * t / e * capacity_factor))
    capacity = max(capacity, 1)

    # routing/bookkeeping run in int32/float32 REGARDLESS of x.dtype:
    # bf16 cannot count past 256, so slot positions would collide and
    # silently merge tokens under AMP
    combine = jnp.zeros((t, e, capacity), jnp.float32)
    remaining = probs.astype(jnp.float32)
    fill = jnp.zeros((e,), jnp.int32)
    for _ in range(k):
        choice = remaining.argmax(axis=-1)      # (T,)
        onehot_i = jax.nn.one_hot(choice, e, dtype=jnp.int32)
        onehot = onehot_i.astype(jnp.float32)
        # position of each token within its chosen expert's buffer
        pos = (jnp.cumsum(onehot_i, axis=0) - 1) + fill[None, :]
        pos_tok = jnp.sum(pos * onehot_i, axis=-1)
        keep = pos_tok < capacity
        gate = jnp.sum(probs.astype(jnp.float32) * onehot,
                       axis=-1) * keep
        combine = combine + (gate[:, None, None]
                             * onehot[:, :, None]
                             * jax.nn.one_hot(pos_tok, capacity,
                                              dtype=jnp.float32)[:, None, :])
        fill = fill + jnp.sum(onehot_i * keep[:, None], axis=0)
        remaining = remaining * (1.0 - onehot)  # next-best expert
    combine = combine.astype(x.dtype)

    dispatch = (combine > 0).astype(x.dtype)    # (T, E, C)
    expert_in = jnp.einsum("tec,td->ecd", dispatch, x)
    h = jnp.einsum("ecd,edh->ech", expert_in, w1) + b1[:, None, :]
    act = {"relu": jax.nn.relu, "gelu": jax.nn.gelu,
           "silu": jax.nn.silu}[activation]
    h = act(h)
    expert_out = jnp.einsum("ech,ehd->ecd", h, w2) + b2[:, None, :]
    out = jnp.einsum("tec,ecd->td", combine, expert_out)

    # load-balancing aux loss (Switch eq. 4)
    frac_tokens = jnp.mean(
        jax.nn.one_hot(logits.argmax(-1), e, dtype=x.dtype), axis=0)
    frac_probs = jnp.mean(probs, axis=0)
    aux = jnp.sum(frac_tokens * frac_probs) * e
    return out, aux


@register("_contrib_RoutedExperts", num_inputs=None, num_outputs=4)
def routed_experts(x, scores_w, bias, w_gate, w_up, w_down, *rest, k=1,
                   route_scale=1.0, first_held=0, use_valid=False):
    """The held experts' part of a sigmoid-routed, no-drop expert layer.

    x (T, d); scores_w (E, d), the router over ALL ``E`` experts; bias
    (E,), added to the scores to SELECT and never to weigh; w_gate, w_up
    (N, d, h) and w_down (N, h, d): the ``N`` experts held here, experts
    ``first_held .. first_held + N - 1`` of the ``E``; an optional
    seventh input ``valid`` (T,) when ``use_valid``: rows that are 0
    (padding) are routed nowhere and counted nowhere.

    ``s = sigmoid(float32(x W_r^T))``; ``S = top_k(s + bias)``; ``g_e =
    s_e / (sum_{e' in S} s_e' + 1e-20) * route_scale`` over ALL of ``S``;
    the sum ``sum_{e in S, held} g_e W_down_e (silu(W_gate_e x) * W_up_e
    x)`` runs over the held experts only.  ``N == E`` is the uncut layer,
    on the same path.  Selection runs in float32 and int32 whatever
    ``x.dtype``; the products take ``x.dtype`` in and accumulate float32.

    Returns ``(out (T, d) float32, held () int32, touched () int32,
    selected (T, k) int32)``: the partial sum, the assignments that
    landed on held experts, the held experts that received at least one,
    and the experts each row picked (all of ``S``, held or not).
    """
    f32 = jnp.float32
    t = x.shape[0]
    e, n = scores_w.shape[0], w_gate.shape[0]
    if k > e:
        raise ValueError(f"RoutedExperts: k={k} exceeds the router's "
                         f"{e} experts")
    if first_held < 0 or first_held + n > e:
        raise ValueError(
            f"RoutedExperts: held experts {first_held}.."
            f"{first_held + n - 1} are not among the router's {e}")
    with device_scope("mxtpu.moe.router"):
        scores = jax.nn.sigmoid(jnp.einsum(
            "td,ed->te", x, scores_w, preferred_element_type=f32))
        _, selected = jax.lax.top_k(scores + bias.astype(f32), k)
        picked = jnp.take_along_axis(scores, selected, axis=1)
        gates = picked / (jnp.sum(picked, axis=-1, keepdims=True)
                          + 1e-20) * f32(route_scale)
        local = selected - first_held
        held = (local >= 0) & (local < n)
        if use_valid and rest:
            held &= (rest[0] > 0).reshape(t, 1)
        # sort the T*k assignments by held expert, the others last: the
        # rows of expert j are then contiguous and ``sizes[j]`` long
        key = jnp.where(held, local, n).reshape(-1)
        order = jnp.argsort(key, stable=True)
        sizes = jnp.sum(key[:, None] == jnp.arange(n)[None, :], axis=0,
                        dtype=jnp.int32)
        g_sorted = jnp.where(jnp.take(key, order) < n,
                             jnp.take(gates.reshape(-1), order), 0.0)
        back = jnp.zeros_like(order).at[order].set(
            jnp.arange(order.shape[0], dtype=order.dtype))
    with device_scope("mxtpu.moe.experts"):
        xs = jnp.take(x, order // k, axis=0)
        grouped = dict(group_sizes=sizes, preferred_element_type=f32)
        mid = jax.nn.silu(jax.lax.ragged_dot(xs, w_gate, **grouped)) \
            * jax.lax.ragged_dot(xs, w_up, **grouped)
        ys = jax.lax.ragged_dot(mid.astype(x.dtype), w_down, **grouped)
        # rows past the last group belong to no held expert: what a
        # grouped product leaves there is not a result
        ys = jnp.where(g_sorted[:, None] > 0, ys * g_sorted[:, None], 0.0)
        out = jnp.sum(jnp.take(ys, back, axis=0).reshape(t, k, -1), axis=1)
    return (out, jnp.sum(held, dtype=jnp.int32),
            jnp.sum(sizes > 0, dtype=jnp.int32), selected.astype(jnp.int32))
