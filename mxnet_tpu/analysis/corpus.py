"""Lint corpus: the shipped graphs mxlint gates CI against.

Two sources:

* hand-built symbols exercising the classic layer mix (MLP; conv +
  BatchNorm aux-state graph), fast enough for every CI run;
* traced model symbols — gluon model-zoo vision nets and the
  ``mxnet_tpu.models`` families — obtained through the same
  ``block(sym.var(...))`` seam ``HybridBlock.export`` uses, so the linted
  graph is byte-for-byte the graph a user would serialize.

Every entry is ``(name, Symbol, input_shapes)`` where the shapes feed the
MXL105 contract validator.
"""
from __future__ import annotations

from typing import Dict, Iterator, List, Tuple

__all__ = ["builtin_symbols", "traced_model_symbols", "model_corpus",
           "wire_defect_corpus"]


def builtin_symbols() -> List[Tuple[str, object, Dict[str, tuple]]]:
    from .. import symbol as sym

    data = sym.var("data")
    h = sym.FullyConnected(data, sym.var("fc1_weight"),
                           sym.var("fc1_bias"), num_hidden=64, name="fc1")
    h = sym.Activation(h, act_type="relu", name="relu1")
    h = sym.FullyConnected(h, sym.var("fc2_weight"), sym.var("fc2_bias"),
                           num_hidden=10, name="fc2")
    mlp = sym.softmax(h, name="softmax")

    x = sym.var("img")
    c = sym.Convolution(x, sym.var("conv1_weight"), sym.var("conv1_bias"),
                        kernel=(3, 3), num_filter=8, pad=(1, 1),
                        name="conv1")
    bn = sym.BatchNorm(c, sym.var("bn1_gamma"), sym.var("bn1_beta"),
                       sym.var("bn1_mean"), sym.var("bn1_var"),
                       name="bn1")
    a = sym.Activation(bn, act_type="relu", name="relu_c")
    p = sym.Pooling(a, kernel=(2, 2), stride=(2, 2), pool_type="max",
                    name="pool1")
    f = sym.flatten(p, name="flat")
    out = sym.FullyConnected(f, sym.var("fco_weight"),
                             sym.var("fco_bias"), num_hidden=10,
                             name="fc_out")
    convnet = sym.softmax(out, name="prob")

    grouped = sym.Group([mlp, sym.FullyConnected(
        data, sym.var("aux_weight"), sym.var("aux_bias"),
        num_hidden=4, name="aux_head")])

    return [("mlp", mlp, {"data": (2, 784)}),
            ("convnet_bn", convnet, {"img": (2, 3, 8, 8)}),
            ("mlp_group", grouped, {"data": (2, 784)})]


def _trace(net, *input_shapes, names=None) -> Tuple[object, Dict]:
    """Initialize a HybridBlock and trace it to a Symbol (export seam)."""
    import mxnet_tpu as mx
    from .. import symbol as sym
    net.initialize(mx.init.Xavier())
    names = names or (["data"] if len(input_shapes) == 1 else
                      [f"data{i}" for i in range(len(input_shapes))])
    out = net(*[sym.var(n) for n in names])
    return out, dict(zip(names, input_shapes))


def traced_model_symbols(full: bool = False) \
        -> Iterator[Tuple[str, object, Dict[str, tuple]]]:
    """Traced symbols for the shipped model zoo.

    The default set keeps tier-1 CI fast; ``full=True`` adds more
    families (``tools/mxlint.py --models`` uses it).  The
    ``mxnet_tpu.models`` transformer families (BERT/Llama/NMT/SSD) read
    ``x.shape`` inside ``hybrid_forward`` — imperative-only, like the
    reference — so they have no Symbol form to lint; their graphs are
    covered imperatively by their own test files.
    """
    from ..gluon.model_zoo import get_model

    net = get_model("resnet18_v1", classes=10, thumbnail=True)
    yield ("zoo:resnet18_v1",) + _trace(net, (1, 3, 32, 32))

    if not full:
        return

    net = get_model("alexnet", classes=10)
    yield ("zoo:alexnet",) + _trace(net, (1, 3, 224, 224))

    net = get_model("mobilenet0.25", classes=10)
    yield ("zoo:mobilenet0.25",) + _trace(net, (1, 3, 224, 224))


def model_corpus(full: bool = False) \
        -> List[Tuple[str, object, Dict[str, tuple]]]:
    out = list(builtin_symbols())
    out.extend(traced_model_symbols(full=full))
    return out


def wire_defect_corpus() -> List[dict]:
    """Seeded wire defects + clean twins for the MXL8xx auditor.

    Each entry is everything :func:`..analysis.analyze_wire`'s explicit
    entry point needs — a closed jaxpr (small shard_map'd step bodies
    on the process dp=8 mesh, traced abstractly), the plan, and the
    registration kwargs — plus the expectation::

        {"name": ..., "rule": "MXL801", "clean": False,
         "jaxpr": <ClosedJaxpr>, "plan": <ShardingPlan|None>,
         "kwargs": {...}}

    The four defects (ISSUE 16 satellite): an fp32 grad leg under an
    ``int8`` plan declaration (MXL801), a full psum smuggled onto the
    ZeRO-2 grad leg (MXL802), an ungated fingerprint row in a sampled
    variant (MXL803), and a cooked observatory counter (MXL804); each
    twin repairs exactly the seeded defect.  Needs the 8-virtual-device
    CPU mesh (``tests/conftest.py`` sets it up; gate with
    ``needs_mesh(8)``).
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from .. import parallel
    from jax import shard_map
    from ..parallel.planner import ShardingPlan

    mesh = parallel.make_mesh({"dp": 8})
    N = 65536                       # global f4 grad: 8192 elems/device
    g_aval = jax.ShapeDtypeStruct((N,), jnp.float32)

    def _psum_grads(g):             # the dense wire: one full psum
        return jax.lax.psum(g, "dp")

    def _quantized_grads(g):        # int8 codes + an fp32 scale lane
        scale = jax.lax.pmax(jnp.max(jnp.abs(g)), "dp") / 127.0 + 1e-8
        codes = jnp.clip(jnp.round(g / scale), -127, 127) \
            .astype(jnp.int8)
        wide = jax.lax.psum(codes, "dp")        # int8 on the wire
        return wide.astype(jnp.float32) * scale

    def _stage2_grads(g):           # the ZeRO-2 contract shape
        part = jax.lax.psum_scatter(g, "dp", scatter_dimension=0,
                                    tiled=True)
        return jax.lax.all_gather(part, "dp", tiled=True)

    def _fingerprint(g):            # one u32 integrity row, UNGATED
        row = jnp.sum(g).astype(jnp.uint32)[None]
        return jax.lax.all_gather(row, "dp")

    def _step_ungated(g, due):
        del due                     # the seeded defect: gate ignored
        return g * 0.9, _fingerprint(g)

    def _step_gated(g, due):
        fp = jax.lax.cond(
            due, lambda: _fingerprint(g),
            lambda: jnp.zeros((8, 1), jnp.uint32))
        return g * 0.9, fp

    def _smap(f, n_in=1):
        specs = (P("dp"), P())[:n_in]
        outs = P() if n_in == 1 else (P("dp"), P())
        return shard_map(f, mesh=mesh, in_specs=specs, out_specs=outs,
                         check_vma=False)

    due = jax.ShapeDtypeStruct((), jnp.bool_)
    jx_psum = jax.make_jaxpr(_smap(_psum_grads))(g_aval)
    jx_quant = jax.make_jaxpr(_smap(_quantized_grads))(g_aval)
    jx_stage2 = jax.make_jaxpr(_smap(_stage2_grads))(g_aval)
    jx_ungated = jax.make_jaxpr(_smap(_step_ungated, 2))(g_aval, due)
    jx_gated = jax.make_jaxpr(_smap(_step_gated, 2))(g_aval, due)

    # static bytes the psum variant puts on the wire (the ring model):
    # per-device payload x 2(k-1)/k — what a truthful observatory
    # counter reports for the same program
    payload = (N // 8) * 4
    psum_wire = 2 * payload * 7 // 8

    int8_plan = ShardingPlan({"dp": 8}, precision={"dp_grad": "int8"})
    obs_kw = {"sampled": True, "obs_outputs": (-1,)}
    return [
        {"name": "fp32_widened_int8_leg", "rule": "MXL801",
         "clean": False, "jaxpr": jx_psum, "plan": int8_plan,
         "kwargs": {}},
        {"name": "quantized_leg_matches_plan", "rule": "MXL801",
         "clean": True, "jaxpr": jx_quant, "plan": int8_plan,
         "kwargs": {}},
        {"name": "psum_on_zero2_grad_leg", "rule": "MXL802",
         "clean": False, "jaxpr": jx_psum, "plan": None,
         "kwargs": {"zero_stage": 2}},
        {"name": "stage2_contract_shape", "rule": "MXL802",
         "clean": True, "jaxpr": jx_stage2, "plan": None,
         "kwargs": {"zero_stage": 2}},
        {"name": "ungated_fingerprint_row", "rule": "MXL803",
         "clean": False, "jaxpr": jx_ungated, "plan": None,
         "kwargs": dict(obs_kw)},
        {"name": "fingerprint_under_cond_gate", "rule": "MXL803",
         "clean": True, "jaxpr": jx_gated, "plan": None,
         "kwargs": dict(obs_kw)},
        {"name": "cooked_observatory_counter", "rule": "MXL804",
         "clean": False, "jaxpr": jx_psum, "plan": None,
         "kwargs": {"measured_wire_bytes": psum_wire * 2}},
        {"name": "reconciled_observatory_counter", "rule": "MXL804",
         "clean": True, "jaxpr": jx_psum, "plan": None,
         "kwargs": {"measured_wire_bytes": psum_wire}},
    ]
