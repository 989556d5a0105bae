"""Custom operators in Python (parity: ``python/mxnet/operator.py`` over
``src/operator/custom/custom.cc`` — SURVEY.md §2.2 "Loss/misc legacy
ops": the plugin mechanism that calls back into user Python).

The reference ran custom ops on a dedicated worker thread with GIL
juggling; here the eager path calls the user code directly, and under
``hybridize``/jit the op is bridged with ``jax.pure_callback`` (the
host-callback escape hatch SURVEY.md §7 P6 names), so custom ops remain
usable inside compiled graphs — they just execute host-side.

Usage (reference-identical)::

    @mx.operator.register("sigmoid")
    class SigmoidProp(mx.operator.CustomOpProp):
        def list_arguments(self): return ["data"]
        def list_outputs(self): return ["output"]
        def infer_shape(self, in_shape):
            return in_shape, [in_shape[0]], []
        def create_operator(self, ctx, shapes, dtypes):
            return Sigmoid()

    y = mx.nd.Custom(x, op_type="sigmoid")
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

from .base import MXNetError
from . import ndarray as nd_mod
from .ndarray.ndarray import NDArray

__all__ = ["CustomOp", "CustomOpProp", "register", "get_registered"]

_REGISTRY: Dict[str, type] = {}


class CustomOp:
    """User op: implement forward/backward over NDArrays."""

    def forward(self, is_train, req, in_data, out_data, aux):
        raise NotImplementedError

    def backward(self, req, out_grad, in_data, out_data, in_grad, aux):
        raise NotImplementedError

    def assign(self, dst, req, src):
        if req in ("write", "inplace", None):
            dst._set_data(src._data if isinstance(src, NDArray)
                          else np.asarray(src, dtype=dst.dtype))
        elif req == "add":
            dst._set_data(dst._data + (src._data if isinstance(src,
                                                               NDArray)
                                       else np.asarray(src)))


class CustomOpProp:
    """Op metadata + factory (parity: CustomOpProp)."""

    def __init__(self, need_top_grad=True, **kwargs):
        self.need_top_grad_ = need_top_grad
        self._kwargs = kwargs

    def list_arguments(self) -> List[str]:
        return ["data"]

    def list_outputs(self) -> List[str]:
        return ["output"]

    def list_auxiliary_states(self) -> List[str]:
        return []

    def infer_shape(self, in_shape):
        return in_shape, [in_shape[0]], []

    def infer_type(self, in_type):
        return in_type, [in_type[0]] * len(self.list_outputs()), []

    def create_operator(self, ctx, shapes, dtypes) -> CustomOp:
        raise NotImplementedError


def register(op_type: str):
    """Class decorator registering a CustomOpProp (parity:
    mx.operator.register)."""

    def deco(prop_cls):
        if not issubclass(prop_cls, CustomOpProp):
            raise MXNetError("register() expects a CustomOpProp subclass")
        _REGISTRY[op_type] = prop_cls
        return prop_cls

    return deco


def get_registered(op_type: str):
    try:
        return _REGISTRY[op_type]
    except KeyError:
        raise MXNetError(f"custom op {op_type!r} is not registered") \
            from None


def _invoke_custom(*inputs, op_type=None, **kwargs):
    """nd.Custom implementation (the MXImperativeInvoke path for
    op='Custom')."""
    from . import autograd

    prop_cls = get_registered(op_type)
    prop = prop_cls(**kwargs)
    in_shapes = [list(i.shape) for i in inputs]
    in_shapes2, out_shapes, aux_shapes = prop.infer_shape(in_shapes)
    in_types, out_types, _ = prop.infer_type(
        [i.dtype for i in inputs])
    ctx = inputs[0].context if inputs else None
    op = prop.create_operator(ctx, in_shapes2, out_types)

    # under CachedOp/jit tracing the inputs hold tracers: bridge to the
    # host with jax.pure_callback (+ custom_vjp through a second
    # callback for backward), so custom Python ops stay usable inside
    # compiled graphs — the reference's dedicated-worker-thread role
    from .gluon.block import _is_tracing
    if _is_tracing():
        return _invoke_custom_traced(op, inputs, out_shapes, out_types,
                                     ctx, autograd.is_training())

    out_arrays = [nd_mod.zeros(tuple(s), ctx=ctx,
                               dtype=np.dtype(t).name)
                  for s, t in zip(out_shapes, out_types)]

    with autograd.pause():
        op.forward(is_train=autograd.is_training(),
                   req=["write"] * len(out_arrays),
                   in_data=list(inputs), out_data=out_arrays, aux=[])

    if not autograd.is_recording():
        return out_arrays[0] if len(out_arrays) == 1 else out_arrays

    # tape node: backward calls the user's backward()
    node = autograd._Node(None, list(inputs), 0,
                          [o._data.aval for o in out_arrays])

    def vjp_fn(cots):
        cots = cots if isinstance(cots, tuple) else (cots,)
        out_grads = [NDArray(c, ctx=ctx) for c in cots]
        in_grads = [nd_mod.zeros(i.shape, ctx=ctx, dtype=i.dtype.name)
                    for i in inputs]
        with autograd.pause():
            op.backward(req=["write"] * len(inputs),
                        out_grad=out_grads, in_data=list(inputs),
                        out_data=out_arrays, in_grad=in_grads, aux=[])
        return tuple(g._data for g in in_grads)

    node.vjp_fn = vjp_fn
    node.outputs = list(out_arrays)
    for i, o in enumerate(out_arrays):
        o._ag_node = node
        o._ag_out_idx = i
    return out_arrays[0] if len(out_arrays) == 1 else out_arrays


def _invoke_custom_traced(op, inputs, out_shapes, out_types, ctx,
                          is_train):
    """pure_callback bridge: the op's forward/backward run HOST-side at
    execution time (not trace time), wrapped in jax.custom_vjp so
    gradients flow through compiled graphs.  ``is_train`` is captured
    at trace time — correct because CachedOp caches per training mode.
    """
    import jax

    out_spec = tuple(jax.ShapeDtypeStruct(tuple(s), np.dtype(t))
                     for s, t in zip(out_shapes, out_types))
    n_out = len(out_spec)

    def host_forward(*np_ins):
        ins = [nd_mod.array(a, dtype=a.dtype) for a in np_ins]
        outs = [nd_mod.zeros(tuple(s), dtype=np.dtype(t).name)
                for s, t in zip(out_shapes, out_types)]
        op.forward(is_train=is_train, req=["write"] * n_out,
                   in_data=ins, out_data=outs, aux=[])
        return tuple(o.asnumpy().astype(np.dtype(t))
                     for o, t in zip(outs, out_types))

    def host_backward(*np_args):
        n_in = len(inputs)
        cots = np_args[:n_out]
        np_ins = np_args[n_out:n_out + n_in]
        np_outs = np_args[n_out + n_in:]
        ins = [nd_mod.array(a, dtype=a.dtype) for a in np_ins]
        outs = [nd_mod.array(a, dtype=a.dtype) for a in np_outs]
        ogs = [nd_mod.array(a, dtype=a.dtype) for a in cots]
        igs = [nd_mod.zeros(i.shape, dtype=i.dtype.name)
               for i in inputs]
        op.backward(req=["write"] * n_in, out_grad=ogs, in_data=ins,
                    out_data=outs, in_grad=igs, aux=[])
        return tuple(g.asnumpy().astype(np.dtype(i.dtype.name))
                     for g, i in zip(igs, inputs))

    in_spec = tuple(jax.ShapeDtypeStruct(tuple(i.shape),
                                         np.dtype(i.dtype.name))
                    for i in inputs)

    @jax.custom_vjp
    def f(*xs):
        return jax.pure_callback(host_forward, out_spec, *xs)

    def f_fwd(*xs):
        outs = jax.pure_callback(host_forward, out_spec, *xs)
        return outs, (xs, outs)

    def f_bwd(res, cots):
        xs, outs = res
        grads = jax.pure_callback(host_backward, in_spec,
                                  *(tuple(cots) + xs + outs))
        return tuple(grads)

    f.defvjp(f_fwd, f_bwd)
    res = f(*(i._data for i in inputs))
    out_arrays = [NDArray(r, ctx=ctx) for r in res]
    return out_arrays[0] if len(out_arrays) == 1 else out_arrays


# expose as nd.Custom (parity: mx.nd.Custom)
nd_mod.Custom = _invoke_custom
