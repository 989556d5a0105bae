"""Flash-attention kernel tests (SURVEY.md §5 "Long-context").

The Pallas kernel runs under ``interpret=True`` on the CPU backend so
its numerics are validated in CI without a chip; the ``tpu``-marked
test compiles the real Mosaic kernel on hardware.  Oracle: the XLA
SDPA path (``_sdpa_xla``), itself validated against numpy in
tests/test_attention_ops.py-style coverage.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu.ops import flash_attention as fa_mod
from mxnet_tpu.ops.attention import _sdpa_xla, _flash_viable


@pytest.fixture(autouse=True)
def _f32_matmuls_on_tpu():
    """On the chip, XLA runs f32 matmuls at bf16 operand precision by
    default, which breaks the 2e-5 interpret-vs-oracle tolerances (the
    two sides truncate differently).  These tests check ALGORITHM
    equivalence, so pin true-f32 precision for the XLA ORACLE side on
    a TPU backend (the kernel side pins Precision.HIGHEST for f32
    inputs itself); the real Mosaic kernel's bf16 path is covered by
    TestFlashOnChip with bf16-scale tolerance."""
    from mxnet_tpu.base import on_accelerator
    if on_accelerator():
        with jax.default_matmul_precision("float32"):
            yield
    else:
        yield


@pytest.fixture
def interpret(monkeypatch):
    monkeypatch.setattr(fa_mod, "_INTERPRET", True)
    yield


def _tol(base):
    """Interpret-vs-oracle tolerance: calibrated on the CPU backend;
    on TPU hardware both sides now run true-f32 matmuls (kernel pins
    Precision.HIGHEST, fixture pins the oracle) but f32 accumulation
    ORDER still differs between the blocked kernel and the one-shot
    einsum, so widen one decade there — still 100x tighter than the
    bf16-scale error the r3 run showed when the precision pin missed
    the backend."""
    return base * (10.0 if jax.default_backend() != "cpu" else 1.0)


def _rand_qkv(b, s, h, d, seed=0, dtype="float32"):
    rng = np.random.RandomState(seed)
    q = jnp.asarray(rng.randn(b, s, h, d).astype(dtype))
    k = jnp.asarray(rng.randn(b, s, h, d).astype(dtype))
    v = jnp.asarray(rng.randn(b, s, h, d).astype(dtype))
    return q, k, v


class TestFlashInterpret:
    @pytest.mark.parametrize("d", [64, 128])
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_xla_sdpa(self, interpret, d, causal):
        q, k, v = _rand_qkv(2, 128, 2, d)
        scale = 1.0 / np.sqrt(d)
        got = fa_mod.flash_attention(q, k, v, scale=scale, causal=causal)
        want = _sdpa_xla(q, k, v, None, scale, causal)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=_tol(2e-5), atol=_tol(2e-5))

    def test_multi_k_block(self, interpret):
        # seq 256 → two k-blocks: exercises the online-softmax carry
        q, k, v = _rand_qkv(1, 256, 1, 64, seed=3)
        got = fa_mod.flash_attention(q, k, v)
        want = _sdpa_xla(q, k, v, None, 1 / np.sqrt(64), False)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=_tol(2e-5), atol=_tol(2e-5))

    @pytest.mark.parametrize("causal", [False, True])
    def test_cross_attention_lengths(self, interpret, causal):
        rng = np.random.RandomState(1)
        q = jnp.asarray(rng.randn(1, 128, 2, 64).astype("f"))
        k = jnp.asarray(rng.randn(1, 256, 2, 64).astype("f"))
        v = jnp.asarray(rng.randn(1, 256, 2, 64).astype("f"))
        got = fa_mod.flash_attention(q, k, v, causal=causal)
        want = _sdpa_xla(q, k, v, None, 1 / np.sqrt(64), causal)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=_tol(2e-5), atol=_tol(2e-5))

    def test_causal_short_keys_no_nan(self, interpret):
        """Causal cross-attention with s_q > s_k: early q-blocks attend
        ZERO keys.  The causal block-skip must not skip there (l would
        be 0 → 0/0 NaN); the oracle emits finite uniform rows and the
        kernel must match them (r4 code-review finding #1)."""
        rng = np.random.RandomState(7)
        q = jnp.asarray(rng.randn(1, 256, 2, 64).astype("f"))
        k = jnp.asarray(rng.randn(1, 128, 2, 64).astype("f"))
        v = jnp.asarray(rng.randn(1, 128, 2, 64).astype("f"))
        got = fa_mod.flash_attention(q, k, v, causal=True)
        want = _sdpa_xla(q, k, v, None, 1 / np.sqrt(64), True)
        assert np.isfinite(np.asarray(got)).all()
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=_tol(2e-5), atol=_tol(2e-5))

    def test_backward_matches_xla(self, interpret):
        q, k, v = _rand_qkv(1, 128, 2, 64, seed=5)

        def f_flash(q, k, v):
            return fa_mod.flash_attention(q, k, v, causal=True).sum()

        def f_xla(q, k, v):
            return _sdpa_xla(q, k, v, None, 1 / np.sqrt(64), True).sum()

        g_flash = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
        g_xla = jax.grad(f_xla, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g_flash, g_xla):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=_tol(2e-5), atol=_tol(2e-5))

    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("sq,sk,d", [(128, 128, 64), (128, 256, 64),
                                         (256, 256, 128)])
    def test_pallas_backward_random_cotangent(self, interpret, causal,
                                              sq, sk, d):
        """The two-pass Pallas backward must match the XLA vjp for a
        RANDOM cotangent (catches dp/delta mistakes that uniform
        cotangents hide), across multi-block and cross-attention
        shapes, padded (64) and unpadded (128) head dims."""
        q, k, v = _rand_qkv(1, sq, 2, d, seed=9)
        k = k[:, :sk] if sk <= k.shape[1] else jnp.concatenate(
            [k] * (sk // k.shape[1]), axis=1)
        v = v[:, :sk] if sk <= v.shape[1] else jnp.concatenate(
            [v] * (sk // v.shape[1]), axis=1)
        rng = np.random.RandomState(11)
        ct = jnp.asarray(rng.randn(1, sq, 2, d).astype("f"))

        def loss_flash(q, k, v):
            return (fa_mod.flash_attention(q, k, v, causal=causal)
                    * ct).sum()

        def loss_xla(q, k, v):
            return (_sdpa_xla(q, k, v, None, 1 / np.sqrt(d), causal)
                    * ct).sum()

        g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        g_xla = jax.grad(loss_xla, argnums=(0, 1, 2))(q, k, v)
        for name, a, b in zip("qkv", g_flash, g_xla):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=_tol(5e-5), atol=_tol(5e-5),
                err_msg=f"d{name} mismatch")

    def test_gqa_routes_to_flash_and_matches(self, interpret):
        """GQA inputs (fewer KV heads) must still take the flash path
        (K/V repeated to full heads) and match the grouped XLA SDPA."""
        q, _, _ = _rand_qkv(1, 128, 4, 64, seed=13)
        rng = np.random.RandomState(14)
        k = jnp.asarray(rng.randn(1, 128, 2, 64).astype("f"))
        v = jnp.asarray(rng.randn(1, 128, 2, 64).astype("f"))
        from mxnet_tpu.ops.attention import dot_product_attention, \
            _flash_viable
        assert _flash_viable(q, k)
        got = dot_product_attention(q, k, v, causal=True)
        want = _sdpa_xla(q, k, v, None, 1 / np.sqrt(64), True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=_tol(2e-5), atol=_tol(2e-5))

    @pytest.mark.parametrize("causal", [False, True])
    def test_key_padding_mask_in_kernel(self, interpret, causal):
        """(B, 1, 1, S_k) padding masks run INSIDE the flash kernels:
        fwd and bwd must match the masked XLA oracle, with a random
        cotangent, for ragged valid lengths."""
        q, k, v = _rand_qkv(2, 128, 2, 64, seed=21)
        vlen = np.asarray([40, 128])
        mask_np = (np.arange(128)[None] < vlen[:, None])
        mask = jnp.asarray(mask_np[:, None, None, :].astype("f"))
        rng = np.random.RandomState(22)
        ct = jnp.asarray(rng.randn(1, 128, 2, 64).astype("f"))

        got = fa_mod.flash_attention(q, k, v, mask=mask, causal=causal)
        want = _sdpa_xla(q, k, v, mask, 1 / np.sqrt(64), causal)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=_tol(2e-5), atol=_tol(2e-5))

        def lf(q, k, v):
            return (fa_mod.flash_attention(q, k, v, mask=mask,
                                           causal=causal) * ct).sum()

        def lx(q, k, v):
            return (_sdpa_xla(q, k, v, mask, 1 / np.sqrt(64), causal)
                    * ct).sum()

        gf = jax.grad(lf, argnums=(0, 1, 2))(q, k, v)
        gx = jax.grad(lx, argnums=(0, 1, 2))(q, k, v)
        for name, a, b in zip("qkv", gf, gx):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=_tol(5e-5), atol=_tol(5e-5),
                err_msg=f"d{name}")
        # padded key positions get exactly zero dK/dV
        np.testing.assert_allclose(np.asarray(gf[1])[0, 40:], 0.0,
                                   atol=1e-6)

    def test_general_mask_still_falls_back(self, interpret):
        """Query-dependent masks cannot run in the kernel: dispatch
        must fall back to XLA (same numbers, no crash)."""
        q, k, v = _rand_qkv(1, 128, 2, 64, seed=23)
        rng = np.random.RandomState(24)
        mask = jnp.asarray(
            (rng.rand(1, 1, 128, 128) > 0.3).astype("f"))
        got = fa_mod.flash_attention(q, k, v, mask=mask)
        want = _sdpa_xla(q, k, v, mask, 1 / np.sqrt(64), False)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-6)

    def test_bert_head_dim_takes_flash_path(self, interpret):
        # bert_base: head_dim 64, seq 128 — the viability gate must
        # accept it (round-1 weak #4: the flagship could never reach
        # the flash path)
        q, k, v = _rand_qkv(1, 128, 12, 64)
        assert _flash_viable(q, k)

    def test_unaligned_seq_falls_back(self, interpret):
        # interpret fixture bypasses the backend gate so the shape
        # clause itself is exercised
        q, k, v = _rand_qkv(1, 100, 2, 64)
        assert not _flash_viable(q, k)


class TestFlashDispatch:
    def test_op_dispatches_to_flash(self, interpret, monkeypatch):
        """dot_product_attention must route through the kernel when
        the policy hands it the job (pinned here — the r5 default
        sends ordinary seqs to XLA)."""
        calls = []
        real = fa_mod._flash_fwd_pallas

        def spy(*a, **kw):
            calls.append(1)
            return real(*a, **kw)

        monkeypatch.setattr(fa_mod, "_flash_fwd_pallas", spy)
        monkeypatch.setenv("MXTPU_FLASH_MODE", "always")
        from mxnet_tpu.ops.attention import dot_product_attention
        q, k, v = _rand_qkv(1, 128, 2, 64)
        dot_product_attention(q, k, v)
        assert calls, "flash path not taken"


@pytest.mark.tpu
class TestFlashOnChip:
    def test_matches_xla_on_tpu(self):
        from mxnet_tpu.base import on_accelerator
        assert on_accelerator()
        q, k, v = _rand_qkv(2, 128, 4, 64, dtype="float32")
        got = fa_mod.flash_attention(q, k, v, causal=True)
        want = _sdpa_xla(q, k, v, None, 1 / np.sqrt(64), True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-2, atol=2e-2)


class TestSlidingWindow:
    """Mistral-style banded causal attention: kernels skip out-of-band
    blocks (O(S·W) compute); oracle is the banded XLA mask."""

    @pytest.mark.parametrize("w", [32, 128, 200])
    def test_fwd_matches_banded_oracle(self, interpret, w):
        q, k, v = _rand_qkv(2, 256, 2, 64, seed=41)
        got = fa_mod.flash_attention(q, k, v, causal=True, window=w)
        want = _sdpa_xla(q, k, v, None, 1 / np.sqrt(64), True, window=w)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=_tol(2e-5), atol=_tol(2e-5))

    def test_window_wider_than_seq_is_causal(self, interpret):
        q, k, v = _rand_qkv(1, 128, 2, 64, seed=42)
        got = fa_mod.flash_attention(q, k, v, causal=True, window=4096)
        want = fa_mod.flash_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-6)

    def test_bwd_matches_banded_oracle(self, interpret):
        q, k, v = _rand_qkv(1, 256, 2, 64, seed=43)
        rng = np.random.RandomState(44)
        ct = jnp.asarray(rng.randn(1, 256, 2, 64).astype("f"))

        def lf(q, k, v):
            return (fa_mod.flash_attention(q, k, v, causal=True,
                                           window=128) * ct).sum()

        def lx(q, k, v):
            return (_sdpa_xla(q, k, v, None, 1 / np.sqrt(64), True,
                              window=128) * ct).sum()

        gf = jax.grad(lf, argnums=(0, 1, 2))(q, k, v)
        gx = jax.grad(lx, argnums=(0, 1, 2))(q, k, v)
        for name, a, b in zip("qkv", gf, gx):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=_tol(5e-5),
                atol=_tol(5e-5), err_msg=f"d{name} (window)")

    def test_bwd_out_of_band_keys_zero_grad(self, interpret):
        """In SELF-attention every key has at least one in-band query,
        so exact-zero dK is only observable in cross-attention: with
        s_q=128, s_k=256 (offset=128) and W=64, key j is attended by
        queries [j-128, j-128+W-1] ∩ [0,127] — empty for j < 65.
        Those keys must get EXACTLY zero dK/dV, and the rest must
        match the banded oracle."""
        rng = np.random.RandomState(48)
        q = jnp.asarray(rng.randn(1, 128, 2, 64).astype("f"))
        k = jnp.asarray(rng.randn(1, 256, 2, 64).astype("f"))
        v = jnp.asarray(rng.randn(1, 256, 2, 64).astype("f"))
        ct = jnp.asarray(rng.randn(1, 128, 2, 64).astype("f"))

        def lf(q, k, v):
            return (fa_mod.flash_attention(q, k, v, causal=True,
                                           window=64) * ct).sum()

        def lx(q, k, v):
            return (_sdpa_xla(q, k, v, None, 1 / np.sqrt(64), True,
                              window=64) * ct).sum()

        gf = jax.grad(lf, argnums=(1, 2))(q, k, v)
        gx = jax.grad(lx, argnums=(1, 2))(q, k, v)
        for name, a, b in zip(("dk", "dv"), gf, gx):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=_tol(5e-5),
                atol=_tol(5e-5), err_msg=name)
            np.testing.assert_array_equal(np.asarray(a)[0, :65], 0.0)
            assert np.abs(np.asarray(a)[0, 65:]).max() > 0

    def test_window_with_key_padding(self, interpret):
        q, k, v = _rand_qkv(2, 128, 2, 64, seed=45)
        vlen = np.asarray([50, 128])
        mask = jnp.asarray(
            (np.arange(128)[None] < vlen[:, None])
            [:, None, None, :].astype("f"))
        got = fa_mod.flash_attention(q, k, v, mask=mask, causal=True,
                                     window=64)
        # oracle: banded causal + padding mask composed
        from mxnet_tpu.ops.attention import _causal_band
        band = _causal_band(128, 128, 64)
        full = mask.astype(bool) & band[None, None]
        want = _sdpa_xla(q, k, v, full.astype("float32"),
                         1 / np.sqrt(64), False)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=_tol(2e-5), atol=_tol(2e-5))

    def test_window_requires_causal(self, interpret):
        from mxnet_tpu.base import MXNetError
        q, k, v = _rand_qkv(1, 128, 2, 64)
        with pytest.raises(MXNetError, match="causal"):
            fa_mod.flash_attention(q, k, v, window=64)
        from mxnet_tpu.ops.attention import dot_product_attention
        with pytest.raises(MXNetError, match="causal"):
            dot_product_attention(q, k, v, window=64)

    def test_dispatch_prefers_flash_for_window(self, interpret,
                                               monkeypatch):
        """A banded call takes the kernel even at seqs where the dense
        policy picks XLA — r5 on-chip table: flash banded is 3.9x
        faster at seq 512/w256 and 6.6x at 1024/w256 (the band is
        O(S·W) in the kernel, a masked S×S on the XLA path)."""
        from mxnet_tpu.ops import attention as attn
        q, k, v = _rand_qkv(1, 256, 2, 64, seed=46)
        monkeypatch.setenv("MXTPU_FLASH_XLA_FROM", "256")
        before = attn.flash_dispatch_count()
        out = attn.dot_product_attention(q, k, v, causal=True,
                                         window=128)
        assert attn.flash_dispatch_count() == before + 1
        want = _sdpa_xla(q, k, v, None, 1 / np.sqrt(64), True,
                         window=128)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=_tol(2e-5), atol=_tol(2e-5))

    @pytest.mark.parametrize("bq,bk", [(64, 64), (64, 128)])
    def test_window_nondefault_blocks(self, interpret, monkeypatch,
                                      bq, bk):
        monkeypatch.setenv("MXTPU_FLASH_BLOCK_Q", str(bq))
        monkeypatch.setenv("MXTPU_FLASH_BLOCK_K", str(bk))
        q, k, v = _rand_qkv(1, 256, 2, 64, seed=47)
        got = fa_mod.flash_attention(q, k, v, causal=True, window=100)
        want = _sdpa_xla(q, k, v, None, 1 / np.sqrt(64), True,
                         window=100)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=_tol(2e-5), atol=_tol(2e-5))


class TestFlashSelection:
    def test_auto_policy_crossover(self, monkeypatch):
        """Auto mode, r5 IN-MODEL policy: XLA SDPA everywhere it can —
        the Pallas custom-call is a fusion barrier (bert_base b64 s128
        measured 956.9 flash vs 1535.3 XLA samples/sec) — and the
        kernel keeps the jobs XLA can't do: seq >= UNTIL, score
        tensors beyond the HBM budget (and windowed attention, routed
        before this policy).  The FROM knobs still carve out a
        prefer-flash band when set."""
        from mxnet_tpu.ops.attention import _flash_preferred
        monkeypatch.delenv("MXTPU_FLASH_MODE", raising=False)
        # defaults: XLA at every ordinary seq, causal or not
        for s in (128, 256, 512, 1024, 2048):
            assert not _flash_preferred(s, s, causal=True), s
            assert not _flash_preferred(s, s), s
        # ...flash again where XLA's O(S^2) scores become the problem
        assert _flash_preferred(4096, 4096, causal=True)
        assert _flash_preferred(4096, 4096)
        # cross-attention uses the max of the two lengths
        assert not _flash_preferred(128, 2048)
        assert _flash_preferred(128, 4096)
        # the tuning knobs retain their prefer-flash-below meaning
        monkeypatch.setenv("MXTPU_FLASH_XLA_FROM", "512")
        assert _flash_preferred(256, 256, causal=True)
        assert not _flash_preferred(256, 256)      # own knob unset
        monkeypatch.setenv("MXTPU_FLASH_XLA_FROM_NONCAUSAL", "512")
        assert _flash_preferred(256, 256)

    def test_xla_window_yields_to_hbm_budget(self, monkeypatch):
        """Inside the measured XLA-win window the policy must still
        fall back to flash when the f32 score tensor it would
        materialize exceeds the HBM budget (ADVICE r4: a policy tuned
        at small batch must not OOM a large-batch flash=True caller).
        b32·h12·2048² f32 = 6 GiB > the 2 GiB default budget."""
        from mxnet_tpu.ops.attention import _flash_preferred
        monkeypatch.delenv("MXTPU_FLASH_MODE", raising=False)
        assert not _flash_preferred(2048, 2048, batch=1, heads=8)
        assert _flash_preferred(2048, 2048, batch=32, heads=12)
        # budget is env-tunable
        monkeypatch.setenv("MXTPU_FLASH_XLA_MAX_SCORE_GB", "0.1")
        assert _flash_preferred(2048, 2048, batch=1, heads=8)

    def test_mode_env_overrides(self, monkeypatch):
        from mxnet_tpu.ops.attention import _flash_preferred
        monkeypatch.setenv("MXTPU_FLASH_MODE", "never")
        assert not _flash_preferred(128, 128)
        monkeypatch.setenv("MXTPU_FLASH_MODE", "always")
        assert _flash_preferred(2048, 2048)

    def test_window_env_tunable(self, monkeypatch):
        from mxnet_tpu.ops.attention import _flash_preferred
        monkeypatch.setenv("MXTPU_FLASH_XLA_FROM", "1024")
        monkeypatch.setenv("MXTPU_FLASH_XLA_FROM_NONCAUSAL", "1024")
        monkeypatch.setenv("MXTPU_FLASH_XLA_UNTIL", "8192")
        assert not _flash_preferred(1024, 1024, causal=True)
        assert not _flash_preferred(1024, 1024)
        assert _flash_preferred(8192, 8192, causal=True)
        assert _flash_preferred(8192, 8192)

    def test_dispatch_respects_policy(self, interpret, monkeypatch):
        """Default dispatch is the XLA path (no flash count) for both
        causal and non-causal ordinary seqs; each FROM knob carves its
        own prefer-flash band back out."""
        from mxnet_tpu.ops import attention as attn
        q, k, v = _rand_qkv(1, 256, 2, 64)
        before = attn.flash_dispatch_count()
        attn.dot_product_attention(q, k, v, causal=True)
        attn.dot_product_attention(q, k, v)
        assert attn.flash_dispatch_count() == before
        monkeypatch.setenv("MXTPU_FLASH_XLA_FROM", "512")
        attn.dot_product_attention(q, k, v, causal=True)
        assert attn.flash_dispatch_count() == before + 1
        attn.dot_product_attention(q, k, v)      # own knob unset
        assert attn.flash_dispatch_count() == before + 1
        monkeypatch.setenv("MXTPU_FLASH_XLA_FROM_NONCAUSAL", "512")
        attn.dot_product_attention(q, k, v)
        assert attn.flash_dispatch_count() == before + 2

    @pytest.mark.parametrize("bq,bk", [(64, 128), (128, 64), (64, 256)])
    def test_block_size_env_numerics(self, interpret, monkeypatch,
                                     bq, bk):
        """Tunable block sizes change tiling only — fwd and bwd match
        the oracle at non-default (block_q, block_k)."""
        monkeypatch.setenv("MXTPU_FLASH_BLOCK_Q", str(bq))
        monkeypatch.setenv("MXTPU_FLASH_BLOCK_K", str(bk))
        q, k, v = _rand_qkv(1, 256, 2, 64, seed=31)
        rng = np.random.RandomState(32)
        ct = jnp.asarray(rng.randn(1, 256, 2, 64).astype("f"))

        def lf(q, k, v):
            return (fa_mod.flash_attention(q, k, v, causal=True)
                    * ct).sum()

        def lx(q, k, v):
            return (_sdpa_xla(q, k, v, None, 1 / np.sqrt(64), True)
                    * ct).sum()

        np.testing.assert_allclose(
            np.asarray(fa_mod.flash_attention(q, k, v, causal=True)),
            np.asarray(_sdpa_xla(q, k, v, None, 1 / np.sqrt(64), True)),
            rtol=_tol(2e-5), atol=_tol(2e-5))
        gf = jax.grad(lf, argnums=(0, 1, 2))(q, k, v)
        gx = jax.grad(lx, argnums=(0, 1, 2))(q, k, v)
        for name, a, b in zip("qkv", gf, gx):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=_tol(5e-5),
                atol=_tol(5e-5), err_msg=f"d{name} (bq={bq}, bk={bk})")

    def test_block_size_invalid_falls_back(self, interpret, monkeypatch):
        """Block sizes that don't divide the seq len are clamped to the
        128 default instead of crashing mid-launch."""
        monkeypatch.setenv("MXTPU_FLASH_BLOCK_Q", "96")
        monkeypatch.setenv("MXTPU_FLASH_BLOCK_K", "0")
        q, k, v = _rand_qkv(1, 128, 2, 64, seed=33)
        got = fa_mod.flash_attention(q, k, v)
        want = _sdpa_xla(q, k, v, None, 1 / np.sqrt(64), False)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=_tol(2e-5), atol=_tol(2e-5))


class TestKeyPaddingDispatch:
    def test_2d_attention_mask_not_misread(self, interpret=None):
        """A (S_q, S_k) 2-D attention mask is ambiguous with key
        padding and must stay on the XLA broadcast path."""
        import importlib
        fa = importlib.import_module("mxnet_tpu.ops.flash_attention")
        import jax.numpy as jnp
        tri = jnp.asarray(np.tril(np.ones((128, 128), "float32")))
        assert fa._as_key_padding(tri, batch=1, s_k=128) is None
        # unambiguous (B, S_k) with B != S_k is accepted and broadcast
        km = fa._as_key_padding(jnp.ones((2, 128)), batch=2, s_k=128)
        assert km is not None and km.shape == (2, 128)
        # broadcast batch-1 4-D masks expand to the query batch
        km = fa._as_key_padding(jnp.ones((1, 1, 1, 128)), batch=4,
                                s_k=128)
        assert km is not None and km.shape == (4, 128)
        # batch mismatch rejected
        assert fa._as_key_padding(jnp.ones((3, 1, 1, 128)), batch=4,
                                  s_k=128) is None


def test_ambiguous_2d_mask_raises():
    """A 2-D mask readable as BOTH (B, S_k) key padding and an
    (S_q, S_k) attention matrix (B == S_q) raises instead of silently
    picking a binding (ADVICE r2); the explicit 4-D forms still work."""
    import importlib
    import pytest
    import jax.numpy as jnp
    from mxnet_tpu.base import MXNetError
    from mxnet_tpu.ops.attention import dot_product_attention, _sdpa_xla
    rng = np.random.RandomState(30)
    B = S = 4
    q = jnp.asarray(rng.randn(B, S, 2, 8).astype("f"))
    pad = jnp.asarray(
        (np.arange(S)[None] < np.asarray([1, 2, 3, 4])[:, None])
        .astype("f"))
    with pytest.raises(MXNetError, match="ambiguous 2-D"):
        dot_product_attention(q, q, q, pad, use_mask=True)
    # the explicit key-padding reshape is accepted and correct
    got = dot_product_attention(q, q, q, pad.reshape(B, 1, 1, S),
                                use_mask=True)
    want = _sdpa_xla(q, q, q, pad.reshape(B, 1, 1, S),
                     1 / np.sqrt(8), False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)
    # non-square cross-attention ambiguity (B == S_q != S_k) raises too
    q3 = jnp.asarray(rng.randn(2, 2, 2, 8).astype("f"))
    kv3 = jnp.asarray(rng.randn(2, 4, 2, 8).astype("f"))
    with pytest.raises(MXNetError, match="ambiguous 2-D"):
        dot_product_attention(q3, kv3, kv3, jnp.ones((2, 4)),
                              use_mask=True)
    # GQA + legacy (S_q, S_k) broadcast mask: no crash, matches oracle
    kv = jnp.asarray(rng.randn(2, 4, 1, 8).astype("f"))
    q2 = jnp.asarray(rng.randn(2, 4, 2, 8).astype("f"))
    tri = jnp.asarray(np.tril(np.ones((4, 4), "float32")))
    got2 = dot_product_attention(q2, kv, kv, tri, use_mask=True)
    want2 = _sdpa_xla(q2, jnp.repeat(kv, 2, 2), jnp.repeat(kv, 2, 2),
                      tri[None, None], 1 / np.sqrt(8), False)
    np.testing.assert_allclose(np.asarray(got2), np.asarray(want2),
                               rtol=1e-5, atol=1e-6)
