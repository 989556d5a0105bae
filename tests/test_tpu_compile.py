"""The repo's Pallas kernels, compiled by the TPU's own compiler for a
DESCRIBED v5e chip — no chip attached (on-chip-measurement guide, §2).

Interpret mode cannot see what Mosaic refuses (a slice off the tiling,
too much VMEM, a kernel that cannot be partitioned); this file can, at
about two seconds a kernel and no chip time.  Shapes are the main
path's real widths: BERT-base, a Mistral prefill, 4096-causal, the
``window=`` band, the ``kmask=`` variant, and one ``rtc.PallasKernel``.
Nothing runs, so nothing here says a kernel is RIGHT or FAST — that is
``chip_smoke.py`` and ``tests/test_on_tpu.py``.

The topology is described inside a module-scoped fixture (only the
xdist worker that is handed this file loads libtpu), never at import,
in a ``skipif`` or in ``parametrize``; every compile happens in this
process; jax's persistent compilation cache is off around them (an
entry written for a described device cannot be read back without one).
"""
import os

import jax
import jax.numpy as jnp
import pytest

from mxnet_tpu.ops import flash_attention as fa


@pytest.fixture(scope="module")
def one_chip():
    """A ``SingleDeviceSharding`` on the first device of a described
    ``v5e:2x2``; skips where the TPU compiler cannot describe one."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _mosaic_calls(compiled):
    """How many Mosaic kernels the compiled program holds."""
    return compiled.as_text().count(
        'custom_call_target="tpu_custom_call"')


def _compile(fn, *args):
    return _mosaic_calls(jax.jit(fn).lower(*args).compile())


# (batch, seq, q heads, kv heads, head dim, causal, window, kmask)
_FLASH = {
    "bert_base_s128": (8, 128, 12, 12, 64, False, None, False),
    "bert_base_s128_kmask": (8, 128, 12, 12, 64, False, None, True),
    "mistral_prefill_s2048": (2, 2048, 32, 32, 128, True, None, False),
    "causal_s4096": (1, 4096, 32, 32, 128, True, None, False),
    "window4096_s8192": (1, 8192, 32, 32, 128, True, 4096, False),
    "window512_s2048": (1, 2048, 8, 8, 128, True, 512, False),
}


@pytest.mark.parametrize("name", sorted(_FLASH))
def test_flash_fwd_and_bwd_compile_for_v5e(one_chip, name, monkeypatch):
    """Forward is one ``pallas_call``; ``jax.grad`` adds the dq and the
    dk/dv kernels — all three must lower through Mosaic in bf16."""
    monkeypatch.setattr(fa, "_INTERPRET", False)
    b, s, h, kv, d, causal, window, kmask = _FLASH[name]

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = [sds((b, s, h, d)), sds((b, s, kv, d)), sds((b, s, kv, d))]
    if kmask:
        args.append(sds((b, s), jnp.bool_))

    def fwd(q, k, v, *m):
        return fa.flash_attention(q, k, v, kmask=m[0] if m else None,
                                  causal=causal, window=window)

    def grad(q, k, v, *m):
        return jax.grad(
            lambda q, k, v: fwd(q, k, v, *m).astype(jnp.float32).sum(),
            argnums=(0, 1, 2))(q, k, v)

    assert _compile(fwd, *args) == 1
    assert _compile(grad, *args) == 3


def test_rtc_user_kernel_compiles_for_v5e(one_chip):
    """``rtc.PallasKernel`` with ``interpret=False`` — what a TPU run
    builds — lowers a gridded user kernel through Mosaic."""
    from jax.experimental import pallas as pl
    from mxnet_tpu import rtc

    def scale_rows(x_ref, o_ref, *, alpha):
        o_ref[...] = x_ref[...] * alpha + pl.program_id(0)

    k = rtc.PallasModule({"scale_rows": scale_rows}).get_kernel(
        "scale_rows", interpret=False, alpha=2.0)
    spec = pl.BlockSpec((128, 256), lambda i: (i, 0))
    fn = k._build([(1024, 256)], ["float32"], (8,), [spec], [spec], ())
    x = jax.ShapeDtypeStruct((1024, 256), jnp.float32, sharding=one_chip)
    assert _mosaic_calls(fn.lower(x).compile()) == 1
