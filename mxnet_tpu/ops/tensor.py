"""Tensor operators (elemwise / broadcast / reduce / matrix / init / ordering).

Capability parity: reference ``src/operator/tensor/`` — elemwise_unary_op*,
elemwise_binary_op*, broadcast_reduce_op*, matrix_op*, init_op*, ordering_op*,
indexing_op* (SURVEY.md §2.2).  Each op here is a pure JAX function; XLA
supplies the kernels, fusion and layout, so ~60k LoC of mshadow template
kernels in the reference collapse into jnp/lax calls with MXNet's names,
attributes and numerics (reduce ``exclude``, dot's last-first contraction,
reshape magic codes, ...).

MXNet numerics notes honoured here (SURVEY.md §7 hard-part 4):
  * elemwise ops do NOT implicitly broadcast — the ``broadcast_*`` family
    does; the NDArray operator sugar maps ``+`` to broadcast_add etc.
  * default dtype is float32 everywhere.
  * reductions keep dtype (no NumPy int upcasting).
"""
from __future__ import annotations

import builtins
import functools
import math
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from .page_write import write_rows
from .registry import register, alias

# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------


def _norm_axis(axis, ndim, exclude=False):
    """Normalize MXNet reduce axis attr (None/int/tuple, exclude flag)."""
    if axis is None or axis == ():
        axes = tuple(range(ndim))
    elif isinstance(axis, int):
        axes = (axis % ndim,)
    else:
        axes = tuple(a % ndim for a in axis)
    if exclude:
        axes = tuple(a for a in range(ndim) if a not in axes)
    return axes


def _reduce(fn, data, *, axis, keepdims, exclude):
    axes = _norm_axis(axis, data.ndim, exclude)
    return fn(data, axis=axes, keepdims=keepdims)


# ---------------------------------------------------------------------------
# init ops (no tensor inputs): zeros / ones / full / arange / eye
# reference: src/operator/tensor/init_op.{h,cc}
# ---------------------------------------------------------------------------


@register("_zeros", num_inputs=0, wrap_ctx=True)
def _zeros(*, shape=(), dtype="float32"):
    return jnp.zeros(shape, dtype=dtype)


@register("_ones", num_inputs=0, wrap_ctx=True)
def _ones(*, shape=(), dtype="float32"):
    return jnp.ones(shape, dtype=dtype)


@register("_full", num_inputs=0, wrap_ctx=True)
def _full(*, shape=(), value=0.0, dtype="float32"):
    return jnp.full(shape, value, dtype=dtype)


@register("_arange", num_inputs=0, wrap_ctx=True)
def _arange(*, start=0.0, stop=None, step=1.0, repeat=1, dtype="float32"):
    out = jnp.arange(start, stop, step, dtype=dtype)
    if repeat != 1:
        out = jnp.repeat(out, repeat)
    return out


@register("_eye", num_inputs=0, wrap_ctx=True)
def _eye(*, N=0, M=0, k=0, dtype="float32"):
    return jnp.eye(N, M if M else None, k=k, dtype=dtype)


@register("zeros_like")
def zeros_like(data):
    return jnp.zeros_like(data)


@register("ones_like")
def ones_like(data):
    return jnp.ones_like(data)


# ---------------------------------------------------------------------------
# elemwise unary — reference elemwise_unary_op_basic.cc etc.
# ---------------------------------------------------------------------------

_UNARY = {
    "abs": jnp.abs, "sign": jnp.sign, "rint": jnp.rint,
    "ceil": jnp.ceil, "floor": jnp.floor, "trunc": jnp.trunc,
    "fix": jnp.trunc, "square": jnp.square, "sqrt": jnp.sqrt,
    "rsqrt": lambda x: lax.rsqrt(x), "cbrt": jnp.cbrt,
    "exp": jnp.exp, "log": jnp.log, "log10": jnp.log10,
    "log2": jnp.log2, "log1p": jnp.log1p, "expm1": jnp.expm1,
    "sin": jnp.sin, "cos": jnp.cos, "tan": jnp.tan,
    "arcsin": jnp.arcsin, "arccos": jnp.arccos, "arctan": jnp.arctan,
    "sinh": jnp.sinh, "cosh": jnp.cosh, "tanh": jnp.tanh,
    "arcsinh": jnp.arcsinh, "arccosh": jnp.arccosh, "arctanh": jnp.arctanh,
    "erf": jax.scipy.special.erf, "erfinv": jax.scipy.special.erfinv,
    "gamma": lambda x: jnp.exp(jax.scipy.special.gammaln(x)),
    "gammaln": jax.scipy.special.gammaln,
    "negative": jnp.negative, "reciprocal": jnp.reciprocal,
    "logical_not": lambda x: jnp.logical_not(x).astype(x.dtype),
    "sigmoid": jax.nn.sigmoid,
    "softsign": jax.nn.soft_sign,
    "relu": jax.nn.relu,
    "round": jnp.round,
}

for _name, _fn in _UNARY.items():
    register(_name)(functools.partial(lambda x, _f=None: _f(x), _f=_fn))


@register("rcbrt")
def rcbrt(x):
    return 1.0 / jnp.cbrt(x)


@register("degrees")
def degrees(x):
    return jnp.degrees(x)


@register("radians")
def radians(x):
    return jnp.radians(x)


@register("_copy")
def _copy(x):
    return x + jnp.zeros((), x.dtype) if jnp.issubdtype(x.dtype, jnp.number) else jnp.array(x)


@register("cast")
def cast(x, *, dtype="float32"):
    return x.astype(dtype)


@register("clip", scalar_attrs=("a_min", "a_max"))
def clip(x, a_min, a_max):
    return jnp.clip(x, a_min, a_max)


# ---------------------------------------------------------------------------
# scalar arithmetic (dynamic scalar passed as trailing 0-d array so that the
# compile cache does not key on the value)
# ---------------------------------------------------------------------------

_SCALAR_BIN = {
    "_plus_scalar": lambda x, s: x + s,
    "_minus_scalar": lambda x, s: x - s,
    "_rminus_scalar": lambda x, s: s - x,
    "_mul_scalar": lambda x, s: x * s,
    "_div_scalar": lambda x, s: x / s,
    "_rdiv_scalar": lambda x, s: s / x,
    "_mod_scalar": lambda x, s: jnp.mod(x, s),
    "_rmod_scalar": lambda x, s: jnp.mod(s, x),
    "_power_scalar": lambda x, s: jnp.power(x, s),
    "_rpower_scalar": lambda x, s: jnp.power(s, x),
    "_maximum_scalar": lambda x, s: jnp.maximum(x, s),
    "_minimum_scalar": lambda x, s: jnp.minimum(x, s),
    "_equal_scalar": lambda x, s: (x == s).astype(x.dtype),
    "_not_equal_scalar": lambda x, s: (x != s).astype(x.dtype),
    "_greater_scalar": lambda x, s: (x > s).astype(x.dtype),
    "_greater_equal_scalar": lambda x, s: (x >= s).astype(x.dtype),
    "_lesser_scalar": lambda x, s: (x < s).astype(x.dtype),
    "_lesser_equal_scalar": lambda x, s: (x <= s).astype(x.dtype),
}

for _name, _fn in _SCALAR_BIN.items():
    # the positional param carrying the dynamic scalar must be NAMED
    # "scalar" to match scalar_attrs (register() enforces this: the
    # frontend maps scalar kwargs/defaults to positions by name)
    register(_name, num_inputs=1, scalar_attrs=("scalar",))(
        functools.partial(lambda x, scalar, _f=None: _f(x, scalar),
                          _f=_fn))


# ---------------------------------------------------------------------------
# broadcast binary — reference elemwise_binary_broadcast_op*.cc
# ---------------------------------------------------------------------------

_BROADCAST_BIN = {
    "broadcast_add": jnp.add,
    "broadcast_sub": jnp.subtract,
    "broadcast_mul": jnp.multiply,
    "broadcast_div": jnp.divide,
    "broadcast_mod": jnp.mod,
    "broadcast_power": jnp.power,
    "broadcast_maximum": jnp.maximum,
    "broadcast_minimum": jnp.minimum,
    "broadcast_hypot": jnp.hypot,
    "broadcast_equal": lambda a, b: (a == b).astype(a.dtype),
    "broadcast_not_equal": lambda a, b: (a != b).astype(a.dtype),
    "broadcast_greater": lambda a, b: (a > b).astype(a.dtype),
    "broadcast_greater_equal": lambda a, b: (a >= b).astype(a.dtype),
    "broadcast_lesser": lambda a, b: (a < b).astype(a.dtype),
    "broadcast_lesser_equal": lambda a, b: (a <= b).astype(a.dtype),
    "broadcast_logical_and": lambda a, b: jnp.logical_and(a, b).astype(a.dtype),
    "broadcast_logical_or": lambda a, b: jnp.logical_or(a, b).astype(a.dtype),
    "broadcast_logical_xor": lambda a, b: jnp.logical_xor(a, b).astype(a.dtype),
}

for _name, _fn in _BROADCAST_BIN.items():
    register(_name, num_inputs=2)(
        functools.partial(lambda a, b, _f=None: _f(a, b), _f=_fn))

# strict (same-shape) elemwise variants, MXNet internal names
for _name, _canon in [("elemwise_add", jnp.add), ("elemwise_sub", jnp.subtract),
                      ("elemwise_mul", jnp.multiply), ("elemwise_div", jnp.divide)]:
    register(_name, num_inputs=2)(
        functools.partial(lambda a, b, _f=None: _f(a, b), _f=_canon))


# ---------------------------------------------------------------------------
# reductions — reference broadcast_reduce_op*.cc.  MXNet attrs: axis (int or
# tuple), keepdims, exclude.
# ---------------------------------------------------------------------------

def _make_reduce(jfn):
    def fcompute(data, *, axis=None, keepdims=False, exclude=False):
        return _reduce(jfn, data, axis=axis, keepdims=keepdims,
                       exclude=exclude)
    return fcompute


for _name, _jfn in [("sum", jnp.sum), ("mean", jnp.mean), ("prod", jnp.prod),
                    ("max", jnp.max), ("min", jnp.min),
                    ("nansum", jnp.nansum), ("nanprod", jnp.nanprod)]:
    register(_name)(_make_reduce(_jfn))

alias("sum_axis", "sum")


@register("norm")
def norm(data, *, ord=2, axis=None, keepdims=False):
    axes = None if axis is None else _norm_axis(axis, data.ndim)
    if ord == 1:
        return jnp.sum(jnp.abs(data), axis=axes, keepdims=keepdims)
    return jnp.sqrt(jnp.sum(jnp.square(data), axis=axes, keepdims=keepdims))


@register("argmax")
def argmax(data, *, axis=None, keepdims=False):
    out = jnp.argmax(data, axis=axis, keepdims=keepdims)
    return out.astype("float32")  # MXNet returns float32 indices


@register("argmin")
def argmin(data, *, axis=None, keepdims=False):
    return jnp.argmin(data, axis=axis, keepdims=keepdims).astype("float32")


@register("argmax_channel")
def argmax_channel(data):
    return jnp.argmax(data, axis=1).astype("float32")


# ---------------------------------------------------------------------------
# matrix / shape ops — reference matrix_op*.cc, dot.cc
# ---------------------------------------------------------------------------


def _int8_acc(a, b):
    """int8×int8 contractions accumulate in int32 (the MXU-native
    quantized path, reference quantized_dot/quantized_conv semantics):
    the HLO must carry s8 operands with an s32 result — upcasting the
    OPERANDS to s32 first would both overflow-differ from the
    reference and miss the MXU int8 units."""
    return (jnp.int32 if a.dtype == jnp.int8 and b.dtype == jnp.int8
            else None)


@register("dot", num_inputs=2)
def dot(a, b, *, transpose_a=False, transpose_b=False):
    """MXNet dot: contract LAST axis of a with FIRST axis of b."""
    if transpose_a:
        a = jnp.transpose(a)
    if transpose_b:
        b = jnp.transpose(b)
    return jnp.tensordot(a, b, axes=1,
                         preferred_element_type=_int8_acc(a, b))


@register("batch_dot", num_inputs=2)
def batch_dot(a, b, *, transpose_a=False, transpose_b=False):
    if transpose_a:
        a = jnp.swapaxes(a, -1, -2)
    if transpose_b:
        b = jnp.swapaxes(b, -1, -2)
    return jnp.matmul(a, b, preferred_element_type=_int8_acc(a, b))


@register("linalg_gemm2", num_inputs=2)
def linalg_gemm2(a, b, *, transpose_a=False, transpose_b=False, alpha=1.0):
    if transpose_a:
        a = jnp.swapaxes(a, -1, -2)
    if transpose_b:
        b = jnp.swapaxes(b, -1, -2)
    return alpha * jnp.matmul(a, b)


def _reshape_target(shape_attr: Tuple[int, ...], src: Tuple[int, ...],
                    reverse=False):
    """Implement MXNet reshape magic codes 0, -1, -2, -3, -4."""
    if reverse:
        shape_attr = tuple(reversed(shape_attr))
        src = tuple(reversed(src))
    out = []
    src_i = 0
    i = 0
    attr = list(shape_attr)
    while i < len(attr):
        d = attr[i]
        if d == 0:
            out.append(src[src_i]); src_i += 1
        elif d == -1:
            out.append(-1); src_i += 1
        elif d == -2:
            out.extend(src[src_i:]); src_i = len(src)
        elif d == -3:
            out.append(src[src_i] * src[src_i + 1]); src_i += 2
        elif d == -4:
            d1, d2 = attr[i + 1], attr[i + 2]
            cur = src[src_i]; src_i += 1
            if d1 == -1:
                d1 = cur // d2
            if d2 == -1:
                d2 = cur // d1
            out.extend([d1, d2]); i += 2
        else:
            out.append(d); src_i += 1
        i += 1
    if reverse:
        out = list(reversed(out))
    return tuple(out)


@register("reshape")
def reshape(data, *, shape=(), reverse=False):
    return jnp.reshape(data, _reshape_target(tuple(shape), data.shape,
                                             reverse))


alias("Reshape", "reshape")


@register("transpose")
def transpose(data, *, axes=()):
    return jnp.transpose(data, axes if axes else None)


@register("expand_dims")
def expand_dims(data, *, axis=0):
    return jnp.expand_dims(data, axis)


@register("squeeze")
def squeeze(data, *, axis=None):
    return jnp.squeeze(data, axis)


@register("flatten")
def flatten(data):
    return jnp.reshape(data, (data.shape[0], -1))


alias("Flatten", "flatten")


@register("broadcast_to")
def broadcast_to(data, *, shape=()):
    # MXNet semantics: 0 in target shape means "keep source dim"
    tgt = tuple(s if t == 0 else t for t, s in zip(shape, data.shape)) \
        if len(shape) == data.ndim else tuple(shape)
    return jnp.broadcast_to(data, tgt)


@register("broadcast_axis")
def broadcast_axis(data, *, axis=(), size=()):
    axes = (axis,) if isinstance(axis, int) else tuple(axis)
    sizes = (size,) if isinstance(size, int) else tuple(size)
    tgt = list(data.shape)
    for a, s in zip(axes, sizes):
        tgt[a] = s
    return jnp.broadcast_to(data, tuple(tgt))


@register("broadcast_like", num_inputs=2)
def broadcast_like(lhs, rhs):
    return jnp.broadcast_to(lhs, rhs.shape)


@register("slice")
def slice_op(data, *, begin=(), end=(), step=()):
    nd = data.ndim
    begin = tuple(begin) + (None,) * (nd - len(begin))
    end = tuple(end) + (None,) * (nd - len(end))
    step = tuple(step) + (None,) * (nd - len(step)) if step else (None,) * nd
    idx = tuple(builtins.slice(b, e, s) for b, e, s in zip(begin, end, step))
    return data[idx]


@register("slice_axis")
def slice_axis(data, *, axis=0, begin=0, end=None):
    idx = [builtins.slice(None)] * data.ndim
    idx[axis] = builtins.slice(begin, end)
    return data[tuple(idx)]


@register("slice_like", num_inputs=2)
def slice_like(data, shape_like, *, axes=()):
    axes = tuple(axes) if axes else tuple(range(shape_like.ndim))
    idx = [builtins.slice(None)] * data.ndim
    for a in axes:
        idx[a] = builtins.slice(0, shape_like.shape[a])
    return data[tuple(idx)]


@register("concat", num_inputs=None)
def concat(*args, dim=1):
    return jnp.concatenate(args, axis=dim)


alias("Concat", "concat")


@register("stack", num_inputs=None)
def stack(*args, axis=0):
    return jnp.stack(args, axis=axis)


@register("split", num_outputs=-1)
def split(data, *, num_outputs=1, axis=1, squeeze_axis=False):
    parts = jnp.split(data, num_outputs, axis=axis)
    if squeeze_axis:
        parts = [jnp.squeeze(p, axis=axis) for p in parts]
    return tuple(parts)


alias("SliceChannel", "split")


@register("take", num_inputs=2)
def take(a, indices, *, axis=0, mode="clip"):
    if mode == "raise":
        raise NotImplementedError(
            "take(mode='raise'): data-dependent bounds checking cannot run "
            "inside a compiled XLA program; use mode='clip' or 'wrap' "
            "(documented capability gap)")
    idx = indices.astype("int32")
    if mode == "clip":
        idx = jnp.clip(idx, 0, a.shape[axis] - 1)
    elif mode == "wrap":
        idx = jnp.mod(idx, a.shape[axis])
    return jnp.take(a, idx, axis=axis)


@register("pick", num_inputs=2)
def pick(data, index, *, axis=-1, keepdims=False, mode="clip"):
    if mode == "raise":
        raise NotImplementedError(
            "pick(mode='raise'): use mode='clip' or 'wrap' (no "
            "data-dependent raising inside compiled XLA programs)")
    if mode == "wrap":
        idx = jnp.mod(index.astype("int32"), data.shape[axis])
    else:
        idx = jnp.clip(index.astype("int32"), 0, data.shape[axis] - 1)
    out = jnp.take_along_axis(data, jnp.expand_dims(idx, axis), axis=axis)
    if not keepdims:
        out = jnp.squeeze(out, axis=axis)
    return out


@register("embedding", num_inputs=2)
def embedding(data, weight, *, input_dim=0, output_dim=0, dtype="float32",
              sparse_grad=False):
    """reference: src/operator/tensor/indexing_op.cc (Embedding)."""
    return jnp.take(weight, data.astype("int32"), axis=0)


alias("Embedding", "embedding")


@register("gather_nd", num_inputs=2)
def gather_nd(data, indices):
    idx = tuple(indices.astype("int32"))
    return data[idx]


@register("one_hot")
def one_hot(indices, *, depth=0, on_value=1.0, off_value=0.0,
            dtype="float32"):
    return jax.nn.one_hot(indices.astype("int32"), depth,
                          dtype=dtype) * (on_value - off_value) + off_value


@register("tile")
def tile(data, *, reps=()):
    return jnp.tile(data, tuple(reps))


@register("repeat")
def repeat(data, *, repeats=1, axis=None):
    return jnp.repeat(data, repeats, axis=axis)


@register("reverse")
def reverse(data, *, axis=()):
    axes = (axis,) if isinstance(axis, int) else tuple(axis)
    return jnp.flip(data, axis=axes)


alias("flip", "reverse")


@register("where", num_inputs=3)
def where(condition, x, y):
    return jnp.where(condition.astype(bool), x, y)


@register("diag")
def diag(data, *, k=0):
    if data.ndim == 1:
        return jnp.diag(data, k=k)
    return jnp.diagonal(data, offset=k, axis1=-2, axis2=-1)


@register("swapaxes")
def swapaxes(data, *, dim1=0, dim2=0):
    return jnp.swapaxes(data, dim1, dim2)


alias("SwapAxis", "swapaxes")


@register("depth_to_space")
def depth_to_space(data, *, block_size=1):
    n, c, h, w = data.shape
    b = block_size
    x = jnp.reshape(data, (n, b, b, c // (b * b), h, w))
    x = jnp.transpose(x, (0, 3, 4, 1, 5, 2))
    return jnp.reshape(x, (n, c // (b * b), h * b, w * b))


@register("space_to_depth")
def space_to_depth(data, *, block_size=1):
    n, c, h, w = data.shape
    b = block_size
    x = jnp.reshape(data, (n, c, h // b, b, w // b, b))
    x = jnp.transpose(x, (0, 3, 5, 1, 2, 4))
    return jnp.reshape(x, (n, c * b * b, h // b, w // b))


@register("pad")
def pad(data, *, mode="constant", pad_width=(), constant_value=0.0):
    pw = tuple(pad_width)
    pairs = [(pw[2 * i], pw[2 * i + 1]) for i in range(len(pw) // 2)]
    jmode = {"constant": "constant", "edge": "edge",
             "reflect": "reflect"}[mode]
    if jmode == "constant":
        return jnp.pad(data, pairs, mode=jmode,
                       constant_values=constant_value)
    return jnp.pad(data, pairs, mode=jmode)


alias("Pad", "pad")


# ---------------------------------------------------------------------------
# ordering ops — reference ordering_op.cc
# ---------------------------------------------------------------------------


@register("sort")
def sort(data, *, axis=-1, is_ascend=True):
    out = jnp.sort(data, axis=axis)
    if not is_ascend:
        out = jnp.flip(out, axis=axis)
    return out


@register("argsort")
def argsort(data, *, axis=-1, is_ascend=True, dtype="float32"):
    out = jnp.argsort(data, axis=axis)
    if not is_ascend:
        out = jnp.flip(out, axis=axis)
    return out.astype(dtype)


@register("topk", num_outputs=-1)
def topk(data, *, axis=-1, k=1, ret_typ="indices", is_ascend=False,
         dtype="float32"):
    src = -data if is_ascend else data
    if axis != -1 and axis != data.ndim - 1:
        src = jnp.moveaxis(src, axis, -1)
    vals, idx = lax.top_k(src, k)
    if is_ascend:
        vals = -vals
    if axis != -1 and axis != data.ndim - 1:
        vals = jnp.moveaxis(vals, -1, axis)
        idx = jnp.moveaxis(idx, -1, axis)
    if ret_typ == "value":
        return vals
    if ret_typ == "both":
        return vals, idx.astype(dtype)
    return idx.astype(dtype)


# ---------------------------------------------------------------------------
# sequence ops — reference src/operator/sequence_*.cc
# ---------------------------------------------------------------------------


@register("SequenceMask", num_inputs=None)
def sequence_mask(data, *rest, use_sequence_length=False, value=0.0, axis=0):
    if not use_sequence_length:
        return data
    seqlen = rest[0]
    maxlen = data.shape[axis]
    steps = jnp.arange(maxlen)
    if axis == 0:
        mask = steps[:, None] < seqlen[None, :].astype("int32")
        mask = mask.reshape(mask.shape + (1,) * (data.ndim - 2))
    else:
        mask = steps[None, :] < seqlen[:, None].astype("int32")
        mask = mask.reshape(mask.shape + (1,) * (data.ndim - 2))
    return jnp.where(mask, data, value)


@register("SequenceLast", num_inputs=None)
def sequence_last(data, *rest, use_sequence_length=False, axis=0):
    if not use_sequence_length:
        idx = [builtins.slice(None)] * data.ndim
        idx[axis] = -1
        return data[tuple(idx)]
    seqlen = rest[0].astype("int32") - 1
    data_t = jnp.moveaxis(data, axis, 0)
    batch = jnp.arange(data_t.shape[1])
    return data_t[seqlen, batch]


@register("SequenceReverse", num_inputs=None)
def sequence_reverse(data, *rest, use_sequence_length=False, axis=0):
    if not use_sequence_length:
        return jnp.flip(data, axis=0)
    seqlen = rest[0].astype("int32")
    T = data.shape[0]
    steps = jnp.arange(T)[:, None]
    rev_idx = jnp.where(steps < seqlen[None, :], seqlen[None, :] - 1 - steps,
                        steps)
    batch = jnp.arange(data.shape[1])[None, :]
    return data[rev_idx, batch]


# ---------------------------------------------------------------------------
# variadic sum — reference src/operator/tensor/elemwise_sum.cc
# ---------------------------------------------------------------------------


@register("add_n", num_inputs=None)
def add_n(*arrays):
    out = arrays[0]
    for a in arrays[1:]:
        out = out + a
    return out


alias("ElementWiseSum", "add_n")


@register("square_sum")
def square_sum(data, *, axis=None, keepdims=False, exclude=False):
    return _reduce(lambda d, axis, keepdims: jnp.sum(jnp.square(d),
                                                     axis=axis,
                                                     keepdims=keepdims),
                   data, axis=axis, keepdims=keepdims, exclude=exclude)


@register("log_sum_exp")
def log_sum_exp(data, *, axis=None, keepdims=False):
    axes = None if axis is None else _norm_axis(axis, data.ndim)
    return jax.nn.logsumexp(data, axis=axes, keepdims=keepdims)


# ---------------------------------------------------------------------------
# round-2 gap closure: remaining reference tensor/linalg ops
# (reference src/operator/tensor/{matrix_op,ordering_op,init_op}.cc,
#  src/operator/tensor/la_op.cc, src/operator/contrib/krprod.cc)
# ---------------------------------------------------------------------------


@register("cumsum")
def cumsum(a, *, axis=None, dtype=None):
    out = jnp.cumsum(a if axis is not None else a.ravel(),
                     axis=axis if axis is not None else 0)
    return out.astype(dtype) if dtype else out


@register("cumprod")
def cumprod(a, *, axis=None, dtype=None):
    out = jnp.cumprod(a if axis is not None else a.ravel(),
                      axis=axis if axis is not None else 0)
    return out.astype(dtype) if dtype else out


@register("trace")
def trace(data, *, offset=0, axis1=0, axis2=1):
    return jnp.trace(data, offset=offset, axis1=axis1, axis2=axis2)


@register("triu")
def triu(data, *, k=0):
    return jnp.triu(data, k=k)


@register("tril")
def tril(data, *, k=0):
    return jnp.tril(data, k=k)


@register("roll")
def roll(data, *, shift=0, axis=None):
    shift = tuple(shift) if isinstance(shift, (tuple, list)) else shift
    axis = tuple(axis) if isinstance(axis, (tuple, list)) else axis
    return jnp.roll(data, shift, axis=axis)


@register("linspace", num_inputs=0, wrap_ctx=True)
def linspace(*, start=0.0, stop=1.0, num=50, endpoint=True,
             dtype="float32"):
    return jnp.linspace(start, stop, int(num), endpoint=endpoint,
                        dtype=dtype)


@register("logspace", num_inputs=0, wrap_ctx=True)
def logspace(*, start=0.0, stop=1.0, num=50, endpoint=True, base=10.0,
             dtype="float32"):
    return jnp.logspace(start, stop, int(num), endpoint=endpoint,
                        base=base, dtype=dtype)


@register("hard_sigmoid")
def hard_sigmoid(data, *, alpha=0.2, beta=0.5):
    return jnp.clip(alpha * data + beta, 0.0, 1.0)


@register("digamma")
def digamma(data):
    import jax.scipy.special as jsp
    return jsp.digamma(data)


@register("smooth_l1")
def smooth_l1(data, *, scalar=1.0):
    """Reference smooth_l1: transition point at 1/scalar**2."""
    s2 = scalar * scalar
    a = jnp.abs(data)
    return jnp.where(a < 1.0 / s2, 0.5 * s2 * data * data,
                     a - 0.5 / s2)


@register("batch_take", num_inputs=2)
def batch_take(a, indices):
    """a (N, K), indices (N,) → picks a[i, indices[i]] per row."""
    idx = indices.astype("int32")
    return jnp.take_along_axis(a, idx[:, None], axis=1)[:, 0]


@register("scatter_nd", num_inputs=2)
def scatter_nd(data, indices, *, shape=()):
    """Reference scatter_nd: indices (M, N) leading coords for N data
    items into an output of ``shape``.  Duplicate indices are
    implementation-defined (as in the reference)."""
    out = jnp.zeros(tuple(shape), data.dtype)
    idx = tuple(indices.astype("int32"))
    return out.at[idx].set(data)


@register("gather_nd_raw", num_inputs=2)
def gather_nd_raw(data, indices):
    idx = tuple(indices.astype("int32"))
    return data[idx]


@register("ravel_multi_index")
def ravel_multi_index(data, *, shape=()):
    """data (N, M): N coordinate rows → (M,) flat indices."""
    dims = jnp.asarray(shape, jnp.int32)
    idx = data.astype(jnp.int32)
    # strides[i] = prod(dims[i+1:]); last stride is 1
    rev_cp = jnp.cumprod(dims[::-1])
    strides = jnp.concatenate(
        [rev_cp[-2::-1], jnp.ones((1,), dims.dtype)])
    return (idx * strides[:, None]).sum(axis=0).astype(data.dtype)


@register("unravel_index")
def unravel_index(data, *, shape=()):
    """(M,) flat indices → (N, M) coordinate rows."""
    idx = data.astype(jnp.int32)
    coords = jnp.stack(jnp.unravel_index(idx, tuple(shape)))
    return coords.astype(data.dtype)


@register("khatri_rao", num_inputs=None)
def khatri_rao(*mats):
    """Column-wise Kronecker product (reference contrib krprod.cc):
    inputs (r_i, k) → output (prod r_i, k)."""
    out = mats[0]
    for m in mats[1:]:
        out = (out[:, None, :] * m[None, :, :]).reshape(
            -1, out.shape[-1])
    return out


# -- linalg family (reference la_op.cc; mshadow-lapack there, XLA here) ----


@register("linalg_potrf")
def linalg_potrf(a):
    """Cholesky factor (lower), batched."""
    return jnp.linalg.cholesky(a)


@register("linalg_potri")
def linalg_potri(a):
    """Inverse from the Cholesky factor: inv(L Lᵀ)."""
    eye = jnp.broadcast_to(jnp.eye(a.shape[-1], dtype=a.dtype), a.shape)
    linv = jax.scipy.linalg.solve_triangular(a, eye, lower=True)
    return jnp.matmul(jnp.swapaxes(linv, -1, -2), linv)


@register("linalg_syrk")
def linalg_syrk(a, *, transpose=False, alpha=1.0):
    at = jnp.swapaxes(a, -1, -2)
    return alpha * (jnp.matmul(at, a) if transpose
                    else jnp.matmul(a, at))


@register("linalg_trmm", num_inputs=2)
def linalg_trmm(a, b, *, transpose=False, rightside=False, lower=True,
                alpha=1.0):
    tri = jnp.tril(a) if lower else jnp.triu(a)
    if transpose:
        tri = jnp.swapaxes(tri, -1, -2)
    return alpha * (jnp.matmul(b, tri) if rightside
                    else jnp.matmul(tri, b))


@register("linalg_trsm", num_inputs=2)
def linalg_trsm(a, b, *, transpose=False, rightside=False, lower=True,
                alpha=1.0):
    """Solve op(A) X = alpha B (or X op(A) = alpha B with rightside),
    A triangular; op(A) = Aᵀ when transpose."""
    import jax.scipy.linalg as jsl
    if rightside:
        # X op(A) = alpha B  →  op(A)ᵀ Xᵀ = alpha Bᵀ
        opat = a if transpose else jnp.swapaxes(a, -1, -2)
        low = lower if transpose else not lower
        xt = jsl.solve_triangular(opat, jnp.swapaxes(alpha * b, -1, -2),
                                  lower=low)
        return jnp.swapaxes(xt, -1, -2)
    opa = jnp.swapaxes(a, -1, -2) if transpose else a
    low = (not lower) if transpose else lower
    return jsl.solve_triangular(opa, alpha * b, lower=low)


@register("linalg_gelqf", num_outputs=2)
def linalg_gelqf(a):
    """LQ factorization: A = L Q with Q orthonormal rows."""
    q, r = jnp.linalg.qr(jnp.swapaxes(a, -1, -2), mode="reduced")
    return jnp.swapaxes(r, -1, -2), jnp.swapaxes(q, -1, -2)


@register("linalg_sumlogdiag")
def linalg_sumlogdiag(a):
    diag = jnp.diagonal(a, axis1=-2, axis2=-1)
    return jnp.sum(jnp.log(diag), axis=-1)


alias("power", "broadcast_power")
alias("logical_and", "broadcast_logical_and")
alias("logical_or", "broadcast_logical_or")
alias("logical_xor", "broadcast_logical_xor")


@register("_slice_basic")
def _slice_basic(x, *, key=()):
    """Differentiable basic indexing (tape path for NDArray.__getitem__
    under autograd.record; outside recording, views serve reads).

    key: per-axis entries ('s', start, stop, step), ('i', index),
    ('e',) for Ellipsis, or ('n',) for None/newaxis; trailing axes are
    implicitly full slices.
    """
    def dec(e):
        if e[0] == "s":
            return builtins.slice(e[1], e[2], e[3])
        if e[0] == "e":
            return Ellipsis
        if e[0] == "n":
            return None
        return int(e[1])

    return x[tuple(dec(e) for e in key)]


@register("_cache_update", num_inputs=2, scalar_attrs=("offset",),
          scalar_ref_input=None)
def _cache_update(cache, new, offset=0):
    """Write ``new`` into ``cache`` at position ``offset`` along axis 1
    (KV-cache decode).  ``offset`` is a dynamic scalar attr so every
    decode step reuses ONE compiled scatter instead of compiling a new
    program per position.  A (B,)-shaped offset scatters each batch
    row at its OWN position (per-slot decode in the serving plane):
    one position a row is ONE in-place write a page
    (``page_write.write_rows``); the vmap below, which the TPU
    compiler turns into a serial loop over the rows, is left for
    several positions a row, which nothing served asks for."""
    off = jnp.asarray(offset, jnp.int32)
    if off.ndim:
        if new.shape[1] == 1:
            return write_rows(cache, new, off.reshape(-1))
        return jax.vmap(
            lambda c, n, o: lax.dynamic_update_slice_in_dim(
                c, n.astype(c.dtype), o, axis=0)
        )(cache, new, off.reshape(-1))
    return lax.dynamic_update_slice_in_dim(
        cache, new.astype(cache.dtype), off, axis=1)


@register("_contrib_arange_like", num_inputs=1)
def arange_like(data, *, start=0.0, step=1.0, repeat=1, axis=None):
    """Arange shaped like ``data`` (parity: mx.nd.contrib.arange_like;
    hybridizable position indices without a shape-dependent constant).
    """
    # repeat holds each value ``repeat`` times WITHIN the output
    # length (reference semantics: total length stays n)
    if axis is None:
        n = 1
        for d in data.shape:
            n *= d
        out = start + step * (jnp.arange(n) // repeat)
        return out.reshape(data.shape).astype(data.dtype)
    n = data.shape[axis]
    return (start + step * (jnp.arange(n) // repeat)) \
        .astype(data.dtype)


@register("_contrib_index_array", num_inputs=1)
def index_array(data, *, axes=None):
    """Per-element N-D indices of ``data`` (parity:
    mx.nd.contrib.index_array): output (*data.shape, len(axes))."""
    shape = data.shape
    sel = tuple(range(len(shape))) if axes is None else tuple(axes)
    grids = jnp.meshgrid(*[jnp.arange(s) for s in shape],
                         indexing="ij")
    return jnp.stack([grids[a] for a in sel], axis=-1).astype("int32")


@register("_contrib_index_copy", num_inputs=3)
def index_copy(old, index, new):
    """Copy rows of ``new`` into ``old`` at ``index`` along axis 0
    (parity: mx.nd.contrib.index_copy; out-of-place like the
    reference's functional form)."""
    return old.at[index.astype(jnp.int32)].set(new.astype(old.dtype))


@register("_contrib_AdaptiveAvgPooling2D", num_inputs=1)
def adaptive_avg_pooling(data, *, output_size=()):
    """NCHW adaptive average pooling to ``output_size`` (parity:
    mx.nd.contrib.AdaptiveAvgPooling2D; reference
    ``src/operator/contrib/adaptive_avg_pooling.cc``).  Matches the
    reference's variable-window semantics (start = floor(i*H/h'),
    end = ceil((i+1)*H/h')) via a normalized matmul per axis — dense
    MXU work instead of ragged windows.
    """
    b, c, h, w = data.shape
    if not output_size:
        oh = ow = 1
    elif isinstance(output_size, int):
        oh = ow = int(output_size)
    elif len(output_size) == 1:
        oh = ow = int(output_size[0])
    else:
        oh, ow = int(output_size[0]), int(output_size[1])

    def pool_matrix(n_in, n_out):
        i = jnp.arange(n_out)
        starts = jnp.floor(i * n_in / n_out).astype(jnp.int32)
        ends = jnp.ceil((i + 1) * n_in / n_out).astype(jnp.int32)
        pos = jnp.arange(n_in)
        m = ((pos[None, :] >= starts[:, None])
             & (pos[None, :] < ends[:, None])).astype(data.dtype)
        return m / m.sum(axis=1, keepdims=True)

    mh = pool_matrix(h, oh)                     # (oh, h)
    mw = pool_matrix(w, ow)                     # (ow, w)
    out = jnp.einsum("oh,bchw->bcow", mh, data)
    return jnp.einsum("pw,bcow->bcop", mw, out)
