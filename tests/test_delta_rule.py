"""The gated delta rule (``ops/delta_rule.py``) against its per-token
recurrence written out in float64 NumPy, on the CPU.

TOLERANCE.  The chunked scan solves each chunk's unit lower-triangular
system and sums in another order than the recurrence, in float32 with
every product at ``Precision.HIGHEST`` (the recurrence here is float64).
At these shapes the largest difference read was 2.8e-6 of the largest
|output| or |state entry| (prefill then steps against the scan of the
whole: 1.9e-6).  ``TOL`` = 2e-5 is seven times that, and each control is
more than ten times ``TOL`` away (``CONTROLS``: the nearest, a bfloat16
state, read 1.9e-3; ``b = sigmoid`` 1.0, no correction 0.37): room on
both sides.
"""
import numpy as np
import pytest

import jax.numpy as jnp

from mxnet_tpu import nd
from mxnet_tpu.ops import delta_rule, ssm

TOL = 2e-5
CHUNK = 64
B, H, DK, DV = 2, 3, 16, 8


def _inputs(seed, s, b=B):
    """Seeded inputs as the model makes them: q, k unit vectors per head,
    decays over (e^-3, 1), steps over (0, 2) (negative eigenvalues
    allowed)."""
    rng = np.random.RandomState(seed)
    q = rng.randn(b, s, H, DK)
    k = rng.randn(b, s, H, DK)
    q /= np.linalg.norm(q, axis=-1, keepdims=True)
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    v = rng.randn(b, s, H, DV)
    log_a = -rng.uniform(0.0, 3.0, (b, s, H, DK))
    step = 2.0 / (1.0 + np.exp(-2.0 * rng.randn(b, s, H)))
    state = rng.randn(b, H, DK, DV)
    return q, k, v, log_a, step, state


def _recurrence(q, k, v, log_a, step, state, beta=1.0, correction=True,
                rounded=None):
    """The rule as written, one token at a time, float64: ``S' = Diag(a)
    S``, ``S = S' + b k (v - S'^T k)^T``, ``o = S^T q / sqrt(dk)``.  The
    controls: ``beta`` scales the step, ``correction=False`` drops the
    ``- b k k^T S'`` term, ``rounded`` rounds the state after each
    token."""
    st = np.array(state, np.float64)
    outs = []
    for t in range(q.shape[1]):
        held = np.exp(log_a[:, t])[..., None] * st
        old = np.einsum("bhk,bhkv->bhv", k[:, t], held) if correction \
            else 0.0
        u = beta * step[:, t][..., None] * (v[:, t] - old)
        st = held + k[:, t][..., None] * u[:, :, None, :]
        if rounded is not None:
            st = rounded(st)
        outs.append(np.einsum("bhk,bhkv->bhv", q[:, t], st) / np.sqrt(DK))
    return np.stack(outs, 1), st


def _f32(*xs):
    return [jnp.asarray(x, jnp.float32) for x in xs]


def _scan(q, k, v, log_a, step, state, last_pos, chunk=CHUNK):
    o, st = delta_rule.delta_scan(*_f32(q, k, v, log_a, step, state,
                                        np.asarray(last_pos)), chunk=chunk)
    return np.asarray(o), np.asarray(st)


def _err(got, want):
    return np.abs(np.asarray(got) - want).max() / np.abs(want).max()


@pytest.mark.parametrize("s", [1, CHUNK - 1, CHUNK, CHUNK + 1,
                               3 * CHUNK + 5])
def test_the_scan_is_the_recurrence(s):
    """One chunk short, whole, one over, several and a remainder: the
    outputs at every position and the state after the last."""
    x = _inputs(s, s)
    o, st = _scan(*x, [s - 1] * B)
    want_o, want_st = _recurrence(*x)
    assert _err(o, want_o) <= TOL
    assert _err(st, want_st) <= TOL


def test_ragged_rows_stop_at_their_own_last_pos():
    """One batch, rows of 70 and 5 real positions padded to 80: each row's
    outputs up to its ``last_pos`` and its state there are the recurrence
    over its own prefix."""
    s, lens = 80, (70, 5)
    x = _inputs(3, s)
    o, st = _scan(*x, [n - 1 for n in lens])
    for r, n in enumerate(lens):
        row = [t[r:r + 1, :n] for t in x[:5]] + [x[5][r:r + 1]]
        want_o, want_st = _recurrence(*row)
        assert _err(o[r:r + 1, :n], want_o) <= TOL, r
        assert _err(st[r:r + 1], want_st) <= TOL, r


def test_padding_leaves_the_state_exactly_where_the_last_token_put_it():
    """Past ``last_pos`` a position decays nothing and writes nothing,
    EXACTLY: two batches that differ only in what lies past each row's
    last position end in bit-identical states."""
    s, last = 2 * CHUNK, [CHUNK + 9, 3]
    x = _inputs(4, s)
    y = [t.copy() for t in x]
    rng = np.random.RandomState(9)
    for r, n in enumerate(last):
        for t in y[:5]:
            t[r, n + 1:] = 5.0 * rng.randn(*t[r, n + 1:].shape)
    np.testing.assert_array_equal(_scan(*x, last)[1], _scan(*y, last)[1])


def test_a_prefill_then_steps_is_the_scan_of_the_whole():
    """Prefill 37 positions, then 30 one-token steps from the state it
    left: the steps' outputs and the last state are the scan of all 67
    positions at once."""
    s, n = 67, 37
    x = _inputs(5, s)
    whole_o, whole_st = _scan(*x, [s - 1] * B)
    _o, st = _scan(*[t[:, :n] for t in x[:5]], x[5], [n - 1] * B)
    st, steps = jnp.asarray(st), []
    for t in range(n, s):
        o, st = delta_rule.delta_step(*_f32(*[a[:, t] for a in x[:5]]), st)
        steps.append(np.asarray(o))
    assert _err(np.stack(steps, 1), whole_o[:, n:]) <= TOL
    assert st.dtype == jnp.float32
    assert _err(st, whole_st) <= TOL


def _bfloat16(x):
    return np.asarray(jnp.asarray(x, jnp.float32).astype(jnp.bfloat16)
                      .astype(jnp.float32), np.float64)


CONTROLS = {"beta_sigmoid": dict(beta=0.5),
            "no_correction": dict(correction=False),
            "state_bfloat16": dict(rounded=_bfloat16)}


@pytest.mark.parametrize("control", sorted(CONTROLS))
def test_each_control_fails_the_comparison(control):
    """The three ways to get the mechanism wrong that a reference must be
    able to tell: ``b = sigmoid`` where it is ``2 sigmoid`` (no negative
    eigenvalues), plain gated linear attention (no ``- b k k^T S'``), a
    bfloat16 state.  Each moves the outputs past ``TOL``, and the scan
    still sits on the rule itself."""
    s = CHUNK + 20
    x = _inputs(6, s)
    o, _st = _scan(*x, [s - 1] * B)
    wrong, _ = _recurrence(*x, **CONTROLS[control])
    assert _err(o, wrong) > 10 * TOL
    assert _err(o, _recurrence(*x)[0]) <= TOL


def test_the_gates_are_the_equations():
    """``log a = -exp(A_log_h) softplus(f + dt_bias)`` per channel, ``b
    = 2 sigmoid(beta)``."""
    rng = np.random.RandomState(7)
    f = rng.randn(2, 5, H * DK)
    a_log, dt_bias, beta = rng.randn(H), rng.randn(H * DK), rng.randn(2, 5, H)
    log_a, b = delta_rule.delta_gates(*_f32(f, a_log, dt_bias, beta))
    rate = np.log1p(np.exp(f + dt_bias)).reshape(2, 5, H, DK)
    np.testing.assert_allclose(
        log_a, -np.exp(a_log)[:, None] * rate, rtol=1e-5)
    np.testing.assert_allclose(b, 2.0 / (1.0 + np.exp(-beta)), rtol=1e-6)


def test_the_ops_are_registered_and_keep_the_state_float32():
    x = _inputs(8, 5)
    o, st = nd._delta_scan(*[nd.array(t.astype("f4")) for t in x],
                           nd.array(np.array([4, 2], "f4")))
    assert o.shape == (B, 5, H, DV) and st.shape == (B, H, DK, DV)
    assert st.dtype == np.float32
    o, st2 = nd._delta_step(*[nd.array(t[:, 0].astype("f4"))
                              for t in x[:5]], st)
    assert o.shape == (B, H, DV) and st2.dtype == np.float32


@pytest.mark.parametrize("s,chunk", [(64, 64), (37, 16), (5, 64)])
def test_the_chunk_plumbing_round_trips(s, chunk):
    """``ops/ssm.py``'s chunking, which both scans take: (B, S, ...) cut
    into zero-padded chunks and back."""
    x = jnp.asarray(np.random.RandomState(s).randn(2, s, 3, 4), jnp.float32)
    ch, (cut,) = ssm._in_chunks(chunk, x)
    assert ch == min(chunk, s) and cut.shape == (-(-s // ch), 2, ch, 3, 4)
    np.testing.assert_array_equal(ssm._from_chunks(cut, s), x)


@pytest.mark.parametrize("c", [5, 37, 64])
def test_the_blocked_substitution_inverts_the_chunks_system(c):
    """``(I + A)^-1`` of a strictly lower-triangular ``A`` by merged
    diagonal blocks, against a dense inverse; and the hard case of a
    chunk of identical keys with ``b = 2`` (every ``A_ti`` = 2), whose
    inverse is bounded while the powers of ``A`` are not."""
    a = np.tril(np.random.RandomState(c).randn(3, c, c) * 0.5, -1)
    got = delta_rule._unit_lower_inverse(jnp.asarray(a, jnp.float32))
    assert _err(got, np.linalg.inv(np.eye(c) + a)) <= TOL
    same = np.tril(np.full((c, c), 2.0), -1)
    np.testing.assert_allclose(
        delta_rule._unit_lower_inverse(jnp.asarray(same, jnp.float32)),
        np.linalg.inv(np.eye(c) + same), atol=1e-5)
