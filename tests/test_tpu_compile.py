"""The repo's Pallas kernels, compiled by the TPU's own compiler for a
DESCRIBED v5e chip — no chip attached (on-chip-measurement guide, §2).

Interpret mode cannot see what Mosaic refuses (a slice off the tiling,
too much VMEM, a kernel that cannot be partitioned); this file can, at
about two seconds a kernel and no chip time.  Shapes are the main
path's real widths: BERT-base, a Mistral prefill, 4096-causal, the
``window=`` band, the ``kmask=`` variant, one ``rtc.PallasKernel``, the
per-row K,V page write of every serving cell's decode program, and the
absorbed latent attention of a Pangu decode round.
Nothing runs, so nothing here says a kernel is RIGHT or FAST — that is
``chip_smoke.py`` and ``tests/test_on_tpu.py``.

The topology is described inside a module-scoped fixture (only the
xdist worker that is handed this file loads libtpu), never at import,
in a ``skipif`` or in ``parametrize``; every compile happens in this
process; jax's persistent compilation cache is off around them (an
entry written for a described device cannot be read back without one).
"""
import math
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


# the K or V page of one layer in each serving cell's decode program
_PAGES = {
    "trinity_160x1536": (160, 1536, 8, 128),
    "phi4_window_96x512": (96, 512, 20, 64),
    "phi4_full_96x1536": (96, 1536, 20, 64),
    "mistral_48x384": (48, 384, 8, 128),
    "mistral_24x1152": (24, 1152, 8, 128),
    # a latent page: one 576-wide row a position, positions-minor
    "pangu_latent_160x3072": (160, 3072, 576),
    # no cell's: a positions-minor page that ends in a partial lane block
    "ragged_96x600": (96, 600, 20, 64),
}


@pytest.mark.parametrize("name", sorted(_PAGES) + ["scalar_offset"])
def test_page_write_is_one_in_place_op_for_v5e(one_chip, name,
                                               monkeypatch):
    """``_cache_update`` with a (B,) offset, the page donated: NO
    ``while`` over the rows (what a vmap of ``dynamic_update_slice``
    compiles to: 20% of a Trinity decode round, PERF.md section 6,
    PR 34), no copy of anything page-sized (what the scatter compiles
    to on a page the TPU stores positions-minor, phi4's), the page
    aliased to the output.  The scalar offset (prefill, ``generate``)
    keeps its ``dynamic-update-slice``."""
    import re
    from mxnet_tpu.ops import page_write
    from mxnet_tpu.ops.tensor import _cache_update
    # the program asks the runtime how the TPU stores a page; here the
    # described chip answers
    chip, = one_chip.device_set
    monkeypatch.setattr(page_write, "_tpu_device", lambda: chip)
    shape = _PAGES.get(name, _PAGES["mistral_48x384"])
    per_row = name in _PAGES

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    page = sds(shape, jnp.bfloat16)
    new = sds((shape[0], 1 if per_row else 128) + shape[2:],
              jnp.bfloat16)
    # offsets as ``decode_step`` hands them: float32
    off = sds((shape[0],) if per_row else (), jnp.float32)
    compiled = jax.jit(_cache_update, donate_argnums=0).lower(
        page, new, off).compile()
    text = compiled.as_text()
    assert not re.search(r" while\(", text)
    mem = compiled.memory_analysis()
    page_bytes = 2 * math.prod(shape)
    # the device's bytes: a page off the tiling is padded up to it
    assert page_bytes <= mem.alias_size_in_bytes < page_bytes * 1.1
    assert mem.temp_size_in_bytes < page_bytes // 8
    dims = ",".join(map(str, shape))
    assert not re.search(r"= bf16\[%s\]\S* copy\(" % dims, text)
    if not per_row:
        assert "dynamic-update-slice" in text
    elif shape[2:] == (8, 128):
        assert re.search(r" scatter\(", text)         # row-major
        assert _mosaic_calls(compiled) == 0
    else:
        assert _mosaic_calls(compiled) == 1           # positions-minor


@pytest.mark.parametrize("lowering", ["walk", "dense"])
def test_absorbed_latent_decode_expands_no_key_or_value_for_v5e(
        one_chip, lowering, monkeypatch):
    """The decode mode of ``_contrib_LatentAttention`` at the Pangu cell's
    shapes (160 slots x 3,072 positions, 128 heads of 128 + 64 / 128 over
    576-wide rows): nothing shaped like a per-head key or value of the
    cached positions (``(slots, positions, 128, ...)``) exists in the
    compiled program and the page is read as it is stored (no page-sized
    copy).  ``walk``: the described chip answers for how a page is
    stored (positions-minor), as an attached one does: the attention is
    exactly ONE Mosaic call, no float32 score array exists anywhere and
    the temporaries stay under 64 MB.  ``dense``: what a host with no
    TPU attached lowers for one: the definition, whose temporaries are
    the scores' (float32 and, rounded, bfloat16), not an expansion's
    25 GB."""
    import re
    from mxnet_tpu.ops import page_write
    from mxnet_tpu.ops.latent_attention import latent_attention
    slots, positions, heads, row = 160, 3072, 128, 576
    if lowering == "walk":
        chip, = one_chip.device_set
        monkeypatch.setattr(page_write, "_tpu_device", lambda: chip)

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(
        lambda q, page, w, off: latent_attention(
            q, page, w, off, nope_dim=128, v_dim=128, use_offset=True)
    ).lower(sds((slots, 1, heads, 192)), sds((slots, positions, row)),
            sds((heads * 256, 512)), sds((slots,), jnp.float32)).compile()
    text = compiled.as_text()
    assert not re.search(r"\[%d,%d,%d,\d+\]" % (slots, positions, heads),
                         text)
    assert not re.search(r"= bf16\[%d,%d,%d\]\S* copy\(" % (
        slots, positions, row), text)
    scores = slots * heads * positions
    temp = compiled.memory_analysis().temp_size_in_bytes
    if lowering == "walk":
        assert _mosaic_calls(compiled) == 1
        # the page seen positions-minor is the stored bytes
        assert not re.search(r"= bf16\[%d,%d,%d\]\S* (copy|transpose)\(" % (
            slots, row, positions), text)
        assert not re.search(r"f32\[%d,%d,%d\]" % (slots, heads, positions),
                             text)
        assert temp < 64 * 2**20
    else:
        assert _mosaic_calls(compiled) == 0
        assert temp < 2 * (4 + 2) * scores


# -- whole programs: every product carries a device scope ---------------------

class _Captured(Exception):
    """Raised by the capturing seam: nothing is compiled for or run on
    the CPU, the program is compiled for the described chip instead."""


def _unscoped_products(compiled):
    """Instruction names of the ``dot`` / ``convolution`` (what the TPU
    compiler makes of a dot) / Mosaic ``custom-call`` instructions of a
    compiled program, every computation of it, whose ``op_name`` names no
    ``mxtpu.*`` scope; and how many such instructions there are."""
    import re
    from mxnet_tpu import profiler
    product = re.compile(
        r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s.*?\s"
        r"(dot|convolution|custom-call)\(")
    text = compiled.as_text()
    _module, table = profiler.scopes_of_text(text)
    seen, bare = 0, []
    for line in text.splitlines():
        hit = product.match(line)
        if hit is None or (hit.group(2) == "custom-call"
                           and "tpu_custom_call" not in line):
            continue
        seen += 1
        if hit.group(1) not in table:
            bare.append(line.strip()[:200])
    return seen, bare, {scope for scope, _bwd, _inh in table.values()}


def _for_chip(arrays, one_chip):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        arrays)


def test_every_product_of_a_bert_train_step_carries_a_scope(one_chip):
    """BERT-base widths (two layers of the twelve: the scopes do not
    depend on depth), the fused step as ``DataParallelTrainer`` traces
    it, compiled for the described chip: forward, backward, the Adam
    rule and the health plane are all named."""
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu import models, nd, parallel
    from mxnet_tpu.gluon.loss import SoftmaxCrossEntropyLoss

    b, s, m, vocab = 8, 128, 20, 8192
    np.random.seed(0)
    net = models.BERTForPretrain(models.bert_base(
        vocab_size=vocab, max_length=s, num_layers=2))
    net.initialize(mx.init.Xavier())
    sce = SoftmaxCrossEntropyLoss()

    def loss_fn(outs, label):
        mlm_scores, nsp_scores = outs
        return sce(mlm_scores, label[:, :m].reshape((-1,))).mean() \
            + sce(nsp_scores, label[:, m]).mean()

    dpt = parallel.DataParallelTrainer(
        net, loss_fn, "adam", {"learning_rate": 1e-4},
        mesh=parallel.make_mesh({"dp": 1}, devices=jax.devices()[:1]),
        fuse_step=True)
    got = {}

    def capture(suffix, jitted, pyfn, vals, donate):
        got["fn"], got["vals"] = pyfn, vals
        raise _Captured()

    dpt._tiered_exec = capture
    rng = np.random.RandomState(0)
    data = tuple(nd.array(a.astype("f4")) for a in (
        rng.randint(0, vocab, (b, s)), rng.randint(0, 2, (b, s)),
        np.full((b,), s), rng.randint(0, s, (b, m))))
    label = nd.array(np.concatenate(
        [rng.randint(0, vocab, (b, m)), rng.randint(0, 2, (b, 1))],
        axis=1).astype("f4"))
    with pytest.raises(_Captured):
        dpt.step(data, label)
    assert got["fn"].__name__ == "full_step"
    compiled = jax.jit(got["fn"]).lower(
        *_for_chip(got["vals"], one_chip)).compile()
    assert compiled.as_text().startswith("HloModule jit_full_step")
    seen, bare, scopes = _unscoped_products(compiled)
    assert seen >= 30 and bare == []
    assert {"mxtpu.embed", "mxtpu.mixer.full", "mxtpu.mlp", "mxtpu.head",
            "mxtpu.loss", "mxtpu.step.optimizer",
            "mxtpu.step.health"} <= scopes


def test_every_product_of_a_llama_decode_program_carries_a_scope(
        one_chip, monkeypatch):
    """A windowed Llama's decode program, as ``Server`` traces it for a
    bucket, compiled for the described chip."""
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu import engine
    from mxnet_tpu.models import LlamaForCausalLM, LlamaModel
    from mxnet_tpu.serving import Server

    np.random.seed(0)
    lm = LlamaForCausalLM(LlamaModel(
        2048, 512, 1408, 2, 4, num_kv_heads=2, sliding_window=4096),
        tie_embeddings=False)
    lm.initialize(mx.init.Xavier())
    srv = Server(lm, buckets=[(8, 128)], max_new_tokens=128)
    got = {}

    def capture(name, pure, attrs, *flat, **_kw):
        got["fn"], got["flat"] = pure, flat
        raise _Captured()

    with monkeypatch.context() as m:    # the model's ops go through it
        m.setattr(engine, "invoke_compiled", capture)
        with pytest.raises(_Captured):
            srv._decode_impl(srv.sched.buckets[0], 1)
    compiled = jax.jit(got["fn"]).lower(
        *_for_chip(list(got["flat"]), one_chip)).compile()
    assert compiled.as_text().startswith("HloModule jit_decode_b8x128")
    seen, bare, scopes = _unscoped_products(compiled)
    assert seen >= 10 and bare == []
    assert {"mxtpu.embed", "mxtpu.mixer.swa", "mxtpu.mlp", "mxtpu.head",
            "mxtpu.serving.pick"} <= scopes
