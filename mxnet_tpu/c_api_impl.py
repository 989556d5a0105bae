"""Python-side implementation of the flat C API.

Capability parity: reference ``src/c_api/c_api.cc`` + ``c_api_ndarray.cc``
+ ``c_api_symbolic.cc`` + ``c_api_executor.cc`` (SURVEY.md §2.1 "C API").
The C++ layer in ``src/c_api.cc`` embeds CPython, holds opaque handles
(PyObject*), and marshals flat C types; every function here takes/returns
only simple Python types so the C++ side stays thin.  Op parameters
arrive as STRINGS and are parsed here — the same contract as the
reference's ``MXImperativeInvokeEx``, whose param values are strings
parsed by dmlc::Parameter.

The TPU-native story: a non-Python frontend (C, C++, any FFI-capable
language) drives the SAME XLA compute path as the Python frontend — the
embedded interpreter is the runtime, XLA executes everything.
"""
from __future__ import annotations

import ast
import json

import numpy as np

_DTYPE_CODES = {0: "float32", 1: "float64", 2: "float16", 3: "uint8",
                4: "int32", 5: "int8", 6: "int64", 7: "bool",
                12: "bfloat16"}
_DTYPE_NAMES = {v: k for k, v in _DTYPE_CODES.items()}


def _dtype_name(code: int) -> str:
    try:
        return _DTYPE_CODES[code]
    except KeyError:
        raise ValueError(f"unknown dtype code {code}")


def dtype_code(name) -> int:
    return _DTYPE_NAMES[np.dtype(name).name if name != "bfloat16"
                        else "bfloat16"]


def _parse_param(v: str):
    """Parse a string-valued op param (reference: dmlc::Parameter)."""
    s = v.strip()
    low = s.lower()
    if low in ("true", "false"):
        return low == "true"
    if low in ("none", "null"):
        return None
    try:
        return ast.literal_eval(s)
    except (ValueError, SyntaxError):
        return s


# -- NDArray ----------------------------------------------------------------

def _ctx(ctx_type: int, ctx_id: int):
    """ctx codes (include/mxtpu/c_api.h): 1=cpu 2=tpu."""
    import mxnet_tpu as mx
    if ctx_type == 1:
        return mx.cpu(ctx_id)
    if ctx_type == 2:
        return mx.tpu(ctx_id)
    raise ValueError(f"unknown ctx_type {ctx_type}")


def ndarray_create(shape, dtype_code_, ctx_type, ctx_id):
    from mxnet_tpu import nd
    return nd.zeros(tuple(shape), ctx=_ctx(ctx_type, ctx_id),
                    dtype=_dtype_name(dtype_code_))


def ndarray_from_bytes(shape, dtype_code_, data: bytes, ctx_type, ctx_id):
    from mxnet_tpu import nd
    a = np.frombuffer(data, dtype=_dtype_name(dtype_code_)).reshape(
        tuple(shape)).copy()
    return nd.array(a, ctx=_ctx(ctx_type, ctx_id), dtype=a.dtype)


def ndarray_to_bytes(arr) -> bytes:
    return np.ascontiguousarray(arr.asnumpy()).tobytes()


def ndarray_shape(arr):
    return list(arr.shape)


def ndarray_dtype(arr) -> int:
    return _DTYPE_NAMES[np.dtype(arr.dtype).name]


def ndarray_wait(arr):
    arr.wait_to_read()


def ndarray_copy(arr):
    return arr.copy()


def waitall():
    from mxnet_tpu import nd
    nd.waitall()


# -- imperative invoke ------------------------------------------------------

def imperative_invoke(op_name: str, inputs, keys, vals):
    """Invoke a registered op by name; returns a list of NDArrays."""
    from mxnet_tpu.ops.registry import get_op
    from mxnet_tpu.ndarray.ndarray import invoke
    kwargs = {k: _parse_param(v) for k, v in zip(keys, vals)}
    out = invoke(get_op(op_name), list(inputs), **kwargs)
    if isinstance(out, (list, tuple)):
        return list(out)
    return [out]


def list_ops():
    from mxnet_tpu.ops.registry import list_ops as _lo
    return sorted(_lo())


# -- Symbol -----------------------------------------------------------------

def symbol_create_variable(name: str):
    from mxnet_tpu import sym
    return sym.Variable(name)


def symbol_from_json(js: str):
    from mxnet_tpu.symbol.symbol import load_json
    return load_json(js)


def symbol_to_json(s) -> str:
    return s.tojson()


def symbol_list_arguments(s):
    return list(s.list_arguments())


def symbol_list_outputs(s):
    return list(s.list_outputs())


def symbol_list_aux(s):
    return list(s.list_auxiliary_states())


def symbol_infer_shape_json(s, shapes_json: str) -> str:
    """Input: {"name": [dims...]} known shapes; output JSON with
    arg_shapes/out_shapes/aux_shapes."""
    known = {k: tuple(v) for k, v in json.loads(shapes_json).items()}
    arg, out, aux = s.infer_shape(**known)
    return json.dumps({
        "arg_shapes": [list(x) for x in (arg or [])],
        "out_shapes": [list(x) for x in (out or [])],
        "aux_shapes": [list(x) for x in (aux or [])],
    })


def symbol_invoke(op_name: str, in_syms, in_names, name, keys, vals):
    """Symbolic compose of a registered op (reference:
    MXSymbolCreateAtomicSymbol + Compose)."""
    from mxnet_tpu import sym as sym_mod
    kwargs = {k: _parse_param(v) for k, v in zip(keys, vals)}
    op = getattr(sym_mod, op_name)
    pos = list(in_syms)
    if in_names and len(in_names) == len(pos):
        for n, s in zip(in_names, pos):
            kwargs[n] = s
        pos = []
    if name:
        kwargs["name"] = name
    return op(*pos, **kwargs)


# -- Executor ---------------------------------------------------------------

def executor_simple_bind_json(s, shapes_json: str, ctx_type, ctx_id,
                              grad_req: str):
    shapes = {k: tuple(v) for k, v in json.loads(shapes_json).items()}
    return s.simple_bind(ctx=_ctx(ctx_type, ctx_id), grad_req=grad_req,
                         **shapes)


def executor_arg_dict(ex):
    return ex.arg_dict


def executor_set_arg(ex, name: str, arr):
    ex.arg_dict[name][:] = arr


def executor_forward(ex, is_train: int):
    ex.forward(is_train=bool(is_train))
    return list(ex.outputs)


def executor_backward(ex, head_grads):
    ex.backward(head_grads if head_grads else None)


def executor_grad(ex, name: str):
    return ex.grad_dict[name]


# -- KVStore ----------------------------------------------------------------

def kvstore_create(kv_type: str):
    from mxnet_tpu import kv
    return kv.create(kv_type)


def kvstore_init(kvs, key: int, arr):
    kvs.init(key, arr)


def kvstore_push(kvs, key: int, arr):
    kvs.push(key, arr)


def kvstore_pull(kvs, key: int, out):
    kvs.pull(key, out=out)


# -- misc -------------------------------------------------------------------

def random_seed(seed: int):
    import mxnet_tpu as mx
    mx.random.seed(seed)


def num_tpus() -> int:
    import mxnet_tpu as mx
    return mx.num_tpus()


# -- Predict API (deploy surface) -------------------------------------------
# Parity: reference src/c_api/c_predict_api.cc + include/mxnet/c_predict_api.h
# (SURVEY.md §2.1 "C API": "predict API is a minimal deploy surface").
# A predictor = exported symbol JSON + params blob bound for inference.

class _Predictor:
    def __init__(self, symbol_json, param_bytes, ctx_type, ctx_id,
                 input_names, input_shapes):
        from mxnet_tpu import nd
        from mxnet_tpu import symbol as sym_mod
        self._sym = sym_mod.load_json(symbol_json)
        params = nd.load_buffer(param_bytes) if param_bytes else {}
        if not isinstance(params, dict):
            raise ValueError(
                "predictor params blob must be name->array (saved via "
                "nd.save(path, dict) / Block.export), got an unnamed "
                "list")
        clean = {}
        for k, v in params.items():
            clean[k[4:] if k.startswith(("arg:", "aux:")) else k] = v
        shapes = {n: tuple(int(d) for d in s)
                  for n, s in zip(input_names, input_shapes)}
        self._ex = self._sym.simple_bind(
            ctx=_ctx(ctx_type, ctx_id), grad_req="null", **shapes)
        for name, arr in clean.items():
            if name in self._ex.arg_dict:
                self._ex.arg_dict[name][:] = arr
            elif name in self._ex.aux_dict:
                self._ex.aux_dict[name][:] = arr
        self._input_names = list(input_names)
        self._outputs = None
        # static output shapes so MXPredGetOutputShape works BEFORE the
        # first forward (the canonical c_predict_api buffer-sizing flow)
        try:
            _, self._static_out_shapes, _ = self._sym.infer_shape(**shapes)
        except Exception:
            self._static_out_shapes = None

    def set_input(self, key, data_bytes):
        if key not in self._input_names:
            raise KeyError(
                f"{key!r} is not a declared input "
                f"(inputs: {self._input_names}); parameters cannot be "
                "overwritten through MXPredSetInput")
        arr = self._ex.arg_dict[key]
        np_arr = np.frombuffer(data_bytes, dtype="float32").reshape(
            arr.shape)
        arr[:] = np_arr

    def forward(self):
        self._outputs = self._ex.forward(is_train=False)

    def output_shape(self, index):
        if self._outputs is not None:
            return tuple(int(d) for d in self._outputs[index].shape)
        if self._static_out_shapes is None:
            raise RuntimeError("output shape unavailable before forward "
                               "(shape inference failed at bind time)")
        return tuple(int(d) for d in self._static_out_shapes[index])

    def get_output(self, index):
        if self._outputs is None:
            self.forward()
        return self._outputs[index].astype("float32").asnumpy().tobytes()


def pred_create(symbol_json, param_bytes, ctx_type, ctx_id,
                input_names, input_shapes):
    return _Predictor(symbol_json, param_bytes, ctx_type, ctx_id,
                      input_names, input_shapes)


def pred_set_input(p, key, data_bytes):
    p.set_input(key, data_bytes)


def pred_forward(p):
    p.forward()


def pred_output_shape(p, index):
    return p.output_shape(index)


def pred_get_output(p, index):
    return p.get_output(index)
