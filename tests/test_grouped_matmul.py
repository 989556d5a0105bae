"""The held experts' grouped products (``ops/grouped_matmul.py``): the
expert-major Pallas kernel's body, through the Pallas interpreter,
against the definition (three ``lax.ragged_dot``), in float32 and in
bfloat16 with float32 accumulation; and ``routed_experts`` under both
lowerings.  What the TPU compiler makes of the kernel at the cells'
shapes is ``tests/test_tpu_compile.py``."""
import numpy as np
import pytest

import jax.numpy as jnp

from mxnet_tpu.ops import grouped_matmul as gm
from mxnet_tpu.ops import moe


@pytest.fixture
def interpret(monkeypatch):
    """The kernel through the Pallas interpreter, with blocks of 128
    columns of ``h`` a grid step, so an expert's sum runs over several
    steps as it does at the cells' widths."""
    monkeypatch.setattr(gm, "_INTERPRET", True)
    monkeypatch.setattr(gm, "_STEP_BYTES", 3 * 128 * 128 * 4)


def _operands(sizes, m, t, dtype, d=128, h=384, seed=0):
    """``expert_sum``'s operands: the ``m`` assignments of ``t`` rows in
    a random order, the first ``sum(sizes)`` of it on the held experts
    in turn, the rest on none held here (gate 0)."""
    rng = np.random.RandomState(seed)
    n = len(sizes)
    held = int(sum(sizes))
    assert held <= m and m % t == 0
    order = rng.permutation(m)
    gates = np.where(np.arange(m) < held, rng.uniform(0.1, 1.0, m), 0.0)
    cast = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}[dtype]
    return (jnp.asarray(rng.randn(t, d), cast),
            jnp.asarray(order // (m // t), jnp.int32),
            jnp.asarray(sizes, jnp.int32),
            jnp.asarray(gates, jnp.float32),
            jnp.asarray(np.argsort(order), jnp.int32),
            jnp.asarray(0.1 * rng.randn(n, d, h), cast),
            jnp.asarray(0.1 * rng.randn(n, d, h), cast),
            jnp.asarray(0.1 * rng.randn(n, h, d), cast))


# sizes (held experts' rows), sorted assignments, rows
_CASES = {
    # experts with no rows before, between and after the others
    "empty_experts": ([0, 3, 0, 17, 0, 40, 0], 96, 24),
    # one expert holds every row: three tiles of 128, two through HBM
    "one_expert_holds_every_row": ([0, 300, 0], 300, 75),
    # sizes off every tile: the first tile runs into later experts' rows
    "sizes_off_the_tile": ([5, 33, 65, 1, 127, 129, 16], 400, 100),
    # T*k > held: most assignments land on experts not held here
    "rows_past_the_last_group": ([2, 0, 7], 64, 16),
    "no_row_held": ([0, 0, 0], 32, 8),
}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", sorted(_CASES))
def test_kernel_matches_the_definition(interpret, case, dtype):
    args = _operands(*_CASES[case], dtype)
    assert gm._block(128, 384, 4) == 128          # three steps an expert
    want = np.asarray(gm._definition(*args))
    got = np.asarray(gm.expert_sum(*args))
    assert got.dtype == np.float32 and got.shape == want.shape
    scale = np.abs(want).max()
    if dtype == "float32":
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5 * scale)
    else:
        # the same bfloat16 products; float32 sums over another order,
        # and ``silu(gate) * up`` rounded to bfloat16 may land a rounding
        # apart where the two float32 values straddle one
        np.testing.assert_allclose(got, want, rtol=0, atol=2**-7 * scale)
    if not sum(_CASES[case][0]):
        assert scale == 0 and np.abs(got).max() == 0


def test_rows_of_no_held_expert_are_not_read(interpret):
    """What lies in the rows past the last group, or in a row whose gate
    is 0, has no say: NaN there changes nothing."""
    args = list(_operands([4, 0, 9], 64, 16, "float32"))
    want = np.asarray(gm.expert_sum(*args))
    x = np.asarray(args[0]).copy()
    rows = np.asarray(args[1])
    used = set(rows[:13])
    spare = [r for r in range(16) if r not in used]
    assert spare
    x[spare] = np.nan
    args[0] = jnp.asarray(x)
    got = np.asarray(gm.expert_sum(*args))
    np.testing.assert_array_equal(got, want)
    assert np.abs(want[spare]).max() == 0


def test_the_kernel_is_taken_only_at_whole_lane_tiles(interpret,
                                                      monkeypatch):
    """``d`` and ``h`` multiples of 128: the kernel (here through the
    interpreter); anything else: the definition, on every platform."""
    calls = []
    monkeypatch.setattr(gm, "_kernel_sum",
                        lambda *a: calls.append(1) or gm._definition(*a))
    for d, h, taken in ((128, 384, True), (128, 96, False),
                        (64, 256, False)):
        calls.clear()
        gm.expert_sum(*_operands([3, 5], 16, 4, "float32", d=d, h=h))
        assert bool(calls) == taken


# routed_experts' attributes in the tiny configurations of the three
# expert decoders (models/afmoe.py, pangu_moe.py, solar_open2.py): 16
# experts, top-4, and the scale each gives its gates
_TINY = {"afmoe_tiny": (2.448, True), "pangu_moe_tiny": (2.5, False),
         "solar_open2_tiny": (1.0, False)}


@pytest.mark.parametrize("config", sorted(_TINY))
def test_routed_experts_count_and_pick_the_same_under_both_lowerings(
        config, monkeypatch):
    """The same routed layer, widths of 128, 48 rows, experts 4..11 of
    16 held: the partial sum agrees and the counts and picks are the
    same array under the definition and under the kernel."""
    scale, biased = _TINY[config]
    rng = np.random.RandomState(len(config))
    d, h, e, first, n, t = 128, 256, 16, 4, 8, 48
    x = jnp.asarray(rng.randn(t, d), jnp.float32)
    router = jnp.asarray(rng.randn(e, d) / np.sqrt(d), jnp.float32)
    bias = jnp.asarray(0.02 * rng.randn(e) if biased else np.zeros(e),
                       jnp.float32)
    ws = [jnp.asarray(0.05 * rng.randn(*s), jnp.float32)
          for s in ((n, d, h), (n, d, h), (n, h, d))]
    attrs = dict(k=4, route_scale=scale, first_held=first)
    want = moe.routed_experts(x, router, bias, *ws, **attrs)
    monkeypatch.setattr(gm, "_INTERPRET", True)
    got = moe.routed_experts(x, router, bias, *ws, **attrs)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]),
                               rtol=1e-5, atol=1e-5 * float(
                                   jnp.abs(want[0]).max()))
    for a, b in zip(got[1:], want[1:]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert int(want[1]) > 0 and int(want[2]) > 0
