"""Symbol: declarative graph construction + the graph executor.

Capability parity: reference ``python/mxnet/symbol/symbol.py`` + nnvm graph
IR (``3rdparty/nnvm``) + ``src/executor/graph_executor.cc`` — SURVEY.md
§2.1 ("nnvm graph + passes", "Graph executor"), §2.5 ("Symbol API"), §3.4.

TPU-native design: a Symbol is a pure-Python DAG of op nodes over the SAME
op registry the imperative layer uses.  ``bind`` does not run nnvm passes —
shape/type inference is ``jax.eval_shape`` over the traced graph, memory
planning/fusion/layout belong to XLA, and the whole graph compiles to ONE
XLA program (the reference needed per-node OpExecutors + engine bulking to
approximate this; SURVEY.md §3.4's "segment & bulk" is free here).
Gradients: ``jax.vjp`` over the traced graph replaces the nnvm ``Gradient``
pass.  Auxiliary states (BatchNorm moving stats) reproduce the reference's
aux-array mutation observably via CachedOp-style version tracking.
"""
from __future__ import annotations

import json
import threading
from collections import OrderedDict
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..base import MXNetError, numeric_types
from ..context import Context, current_context
from ..ops.registry import get_op
from ..ndarray.ndarray import NDArray

__all__ = ["Symbol", "Executor", "var", "Variable", "Group", "load",
           "load_json"]


# ---------------------------------------------------------------------------
# naming
# ---------------------------------------------------------------------------

class _NameManager(threading.local):
    def __init__(self):
        self.counts = {}

    def get(self, hint: str) -> str:
        hint = hint.lower()
        n = self.counts.get(hint, 0)
        self.counts[hint] = n + 1
        return f"{hint}{n}"


_NAMES = _NameManager()

# ops whose nth..mth inputs are auxiliary states (not gradient targets);
# mirrors the reference's per-op aux declarations in src/operator/nn/*
_AUX_INPUTS = {"BatchNorm": (3, 4)}


class _Node:
    """One graph node: an op application or a variable."""

    __slots__ = ("op", "name", "attrs", "inputs", "num_outputs",
                 "_user_attrs")

    def __init__(self, op: Optional[str], name: str, attrs: dict,
                 inputs: List[Tuple["_Node", int]], num_outputs: int = 1):
        self.op = op          # nd-namespace callable name; None for vars
        self.name = name
        self.attrs = attrs
        self.inputs = inputs
        self.num_outputs = num_outputs
        self._user_attrs = {}


def _aux_ids(heads: Sequence[_Node]):
    """Ids of variable nodes consumed in auxiliary-state positions.

    Aux-ness is a property of THIS graph's consuming edges — never a
    mutation of the (possibly shared) variable node, so using the same
    var in another graph keeps it an ordinary argument there.
    """
    out = set()
    for node in _topo(heads):
        positions = _AUX_INPUTS.get(node.op)
        if not positions:
            continue
        for pos in positions:
            if pos < len(node.inputs):
                inp = node.inputs[pos][0]
                if inp.op is None:
                    out.add(id(inp))
    return out


def _topo(heads: Sequence[_Node]) -> List[_Node]:
    seen = set()
    order: List[_Node] = []

    def visit(node):
        if id(node) in seen:
            return
        seen.add(id(node))
        for inp, _ in node.inputs:
            visit(inp)
        order.append(node)

    for h in heads:
        visit(h)
    return order


class Symbol:
    """A (possibly multi-output) symbolic expression."""

    def __init__(self, outputs: List[Tuple[_Node, int]]):
        self._outputs = outputs

    # -- construction helpers --------------------------------------------
    @property
    def name(self):
        if len(self._outputs) == 1:
            return self._outputs[0][0].name
        return None

    def __repr__(self):
        outs = ", ".join(self.list_outputs())
        return f"<Symbol {outs}>"

    def __iter__(self):
        return (Symbol([o]) for o in self._outputs)

    def __len__(self):
        return len(self._outputs)

    def __getitem__(self, index):
        if isinstance(index, str):
            names = self.list_outputs()
            if index not in names:
                raise MXNetError(f"no output named {index!r}; outputs are "
                                 f"{names}")
            index = names.index(index)
        if isinstance(index, (int, np.integer)):
            return Symbol([self._outputs[index]])
        raise TypeError("index must be int or str")

    def attr(self, key):
        if len(self._outputs) == 1:
            return self._outputs[0][0]._user_attrs.get(key)
        return None

    def _set_attr(self, **kwargs):
        for node, _ in self._outputs:
            node._user_attrs.update(kwargs)

    def attr_dict(self):
        out = {}
        for node in _topo([n for n, _ in self._outputs]):
            if node._user_attrs:
                out[node.name] = dict(node._user_attrs)
        return out

    # -- introspection ----------------------------------------------------
    def _head_nodes(self):
        return [n for n, _ in self._outputs]

    def list_arguments(self) -> List[str]:
        heads = self._head_nodes()
        aux = _aux_ids(heads)
        return [n.name for n in _topo(heads)
                if n.op is None and id(n) not in aux]

    def list_outputs(self) -> List[str]:
        names = []
        for node, idx in self._outputs:
            if node.op is None:
                names.append(node.name)
            elif node.num_outputs == 1:
                names.append(node.name + "_output")
            else:
                names.append(f"{node.name}_output{idx}")
        return names

    def list_auxiliary_states(self) -> List[str]:
        heads = self._head_nodes()
        aux = _aux_ids(heads)
        return [n.name for n in _topo(heads)
                if n.op is None and id(n) in aux]

    def list_inputs(self) -> List[str]:
        return [n.name for n in _topo(self._head_nodes()) if n.op is None]

    def get_internals(self) -> "Symbol":
        outs = []
        for node in _topo(self._head_nodes()):
            for i in range(node.num_outputs):
                outs.append((node, i))
        return Symbol(outs)

    def get_children(self) -> Optional["Symbol"]:
        kids = []
        for node, _ in self._outputs:
            kids.extend(node.inputs)
        return Symbol(kids) if kids else None

    # -- composition ------------------------------------------------------
    def __call__(self, *args, **kwargs):
        """Compose: replace this symbol's variable inputs (parity:
        ``Symbol.__call__`` / nnvm graph compose)."""
        if args and kwargs:
            raise MXNetError("compose accepts positional OR keyword "
                             "arguments, not both")
        arg_names = self.list_inputs()
        mapping: Dict[str, Symbol] = {}
        if args:
            if len(args) > len(arg_names):
                raise MXNetError("too many positional arguments to compose")
            mapping = dict(zip(arg_names, args))
        else:
            for k, v in kwargs.items():
                if k not in arg_names:
                    raise MXNetError(f"no input named {k!r}")
                mapping[k] = v
        for v in mapping.values():
            if not isinstance(v, Symbol) or len(v._outputs) != 1:
                raise MXNetError("compose values must be 1-output Symbols")

        memo: Dict[int, _Node] = {}

        def clone(node: _Node) -> Tuple[_Node, int]:
            if node.op is None and node.name in mapping:
                return mapping[node.name]._outputs[0]
            if id(node) in memo:
                return memo[id(node)], -1
            new_inputs = []
            for inp, idx in node.inputs:
                rep, ridx = clone(inp)
                new_inputs.append((rep, idx if ridx == -1 else ridx))
            if node.op is None:
                memo[id(node)] = node
                return node, -1
            nn = _Node(node.op, node.name, dict(node.attrs), new_inputs,
                       node.num_outputs)
            nn._user_attrs = dict(node._user_attrs)
            memo[id(nn)] = nn
            memo[id(node)] = nn
            return nn, -1

        outs = []
        for node, idx in self._outputs:
            rep, ridx = clone(node)
            outs.append((rep, idx if ridx == -1 else ridx))
        return Symbol(outs)

    # -- arithmetic sugar -------------------------------------------------
    def _binary(self, other, opname, scalar_op, reverse=False):
        if isinstance(other, Symbol):
            a, b = (other, self) if reverse else (self, other)
            return _invoke(opname, [a, b], {})
        if isinstance(other, numeric_types):
            return _invoke(scalar_op, [self], {"scalar": other})
        return NotImplemented

    def __add__(self, o):
        return self._binary(o, "broadcast_add", "_plus_scalar")

    __radd__ = __add__

    def __sub__(self, o):
        return self._binary(o, "broadcast_sub", "_minus_scalar")

    def __rsub__(self, o):
        if isinstance(o, numeric_types):
            return self._binary(o, None, "_rminus_scalar")
        return self._binary(o, "broadcast_sub", "_minus_scalar",
                            reverse=True)

    def __mul__(self, o):
        return self._binary(o, "broadcast_mul", "_mul_scalar")

    __rmul__ = __mul__

    def __truediv__(self, o):
        return self._binary(o, "broadcast_div", "_div_scalar")

    def __rtruediv__(self, o):
        if isinstance(o, numeric_types):
            return self._binary(o, None, "_rdiv_scalar")
        return self._binary(o, "broadcast_div", "_div_scalar", reverse=True)

    def __pow__(self, o):
        return self._binary(o, "broadcast_power", "_power_scalar")

    def __neg__(self):
        return self._binary(-1.0, None, "_mul_scalar")

    # -- reshaping sugar (mirrors NDArray methods) ------------------------
    def reshape(self, shape):
        return _invoke("reshape", [self], {"shape": tuple(shape)})

    def transpose(self, *axes):
        if len(axes) == 1 and isinstance(axes[0], (tuple, list)):
            axes = tuple(axes[0])
        return _invoke("transpose", [self], {"axes": axes})

    # -- shape / type inference ------------------------------------------
    def infer_shape(self, *args, **kwargs):
        """Returns (arg_shapes, out_shapes, aux_shapes), aligned with
        list_arguments()/list_outputs()/list_auxiliary_states()."""
        try:
            return self._infer_shape_impl(*args, **kwargs)
        except MXNetError:
            raise
        except Exception as e:
            raise MXNetError(f"infer_shape error: {e}") from e

    def infer_shape_partial(self, *args, **kwargs):
        try:
            return self._infer_shape_impl(*args, **kwargs)
        except Exception:
            return None, None, None

    def _infer_shape_impl(self, *args, **kwargs):
        import jax

        arg_names = self.list_arguments()
        aux_names = self.list_auxiliary_states()
        if args:
            kwargs = dict(zip(arg_names, args))
        known = {k: tuple(v) for k, v in kwargs.items() if v is not None}

        # aux shapes follow from the ops that consume them (BN stats share
        # the gamma/beta channel dim); infer by evaluating with zeros of a
        # guessed channel size is fragile — instead walk BN nodes directly
        shapes = dict(known)
        out_struct, arg_shapes, aux_shapes = _infer_via_eval_shape(
            self, shapes, arg_names, aux_names)
        out_shapes = [tuple(int(d) for d in s.shape) for s in out_struct]
        return ([arg_shapes.get(n) for n in arg_names], out_shapes,
                [aux_shapes.get(n) for n in aux_names])

    def infer_type(self, *args, **kwargs):
        arg_names = self.list_arguments()
        if args:
            kwargs = dict(zip(arg_names, args))
        dtypes = {k: np.dtype(v).name for k, v in kwargs.items()
                  if v is not None}
        default = "float32"
        arg_types = [np.dtype(dtypes.get(n, default)) for n in arg_names]
        # outputs: evaluate shapes+types together would need shapes; keep
        # the reference's common case (homogeneous float graphs)
        out_types = [np.dtype(default)] * len(self.list_outputs())
        aux_types = [np.dtype(default)] * len(self.list_auxiliary_states())
        return arg_types, out_types, aux_types

    # -- serialization ----------------------------------------------------
    def tojson(self) -> str:
        nodes = _topo(self._head_nodes())
        idx = {id(n): i for i, n in enumerate(nodes)}
        jnodes = []
        for n in nodes:
            jnodes.append({
                "op": n.op if n.op is not None else "null",
                "name": n.name,
                "attrs": {k: repr(v) for k, v in n.attrs.items()},
                "inputs": [[idx[id(i)], oi, 0] for i, oi in n.inputs],
                "num_outputs": n.num_outputs,
                "user_attrs": {k: repr(v)
                               for k, v in n._user_attrs.items()},
            })
        heads = [[idx[id(n)], oi, 0] for n, oi in self._outputs]
        return json.dumps({"nodes": jnodes, "heads": heads,
                           "arg_nodes": [i for i, n in enumerate(nodes)
                                         if n.op is None],
                           "mxtpu_version": 1}, indent=2)

    def save(self, fname: str):
        with open(fname, "w") as f:
            f.write(self.tojson())

    # -- evaluation / binding --------------------------------------------
    def eval(self, ctx=None, **kwargs):
        ex = self.bind(ctx or current_context(), kwargs)
        return ex.forward()

    def bind(self, ctx, args, args_grad=None, grad_req="write",
             aux_states=None, **_ignored) -> "Executor":
        return Executor(self, ctx, args, args_grad, grad_req, aux_states)

    def simple_bind(self, ctx=None, grad_req="write", **kwargs) -> "Executor":
        """Allocate argument/grad/aux arrays from inferred shapes."""
        from .. import ndarray as nd
        ctx = ctx or current_context()
        arg_names = self.list_arguments()
        arg_shapes, _, aux_shapes = self.infer_shape(**kwargs)
        args = {}
        for name, shape in zip(arg_names, arg_shapes):
            if shape is None:
                raise MXNetError(f"simple_bind: cannot infer shape of "
                                 f"argument {name!r}; pass it explicitly")
            args[name] = nd.zeros(shape, ctx=ctx)
        aux = {}
        for name, shape in zip(self.list_auxiliary_states(), aux_shapes):
            aux[name] = nd.zeros(shape, ctx=ctx)
        args_grad = None
        if grad_req != "null":
            args_grad = {n: nd.zeros(a.shape, ctx=ctx)
                         for n, a in args.items()}
        return Executor(self, ctx, args, args_grad, grad_req, aux)


# ---------------------------------------------------------------------------
# graph evaluation (shared by Executor / infer_shape / SymbolBlock)
# ---------------------------------------------------------------------------


def _eval_graph(sym: Symbol, value_of: Dict[str, NDArray]):
    """Evaluate the DAG by dispatching through the nd namespace, so every
    frontend behaviour (RNG keys, BN aux mutation, scalar attrs) is shared
    with the imperative path."""
    from .. import ndarray as nd_mod

    cache: Dict[int, Tuple] = {}

    def ev(node: _Node) -> Tuple:
        got = cache.get(id(node))
        if got is not None:
            return got
        if node.op is None:
            try:
                val = value_of[node.name]
            except KeyError:
                raise MXNetError(
                    f"bind: no value provided for input {node.name!r}")
            res = (val,)
        else:
            ins = [ev(inp)[oi] for inp, oi in node.inputs]
            fn = getattr(nd_mod, node.op)
            out = fn(*ins, **node.attrs)
            res = tuple(out) if isinstance(out, (list, tuple)) else (out,)
        cache[id(node)] = res
        return res

    return [ev(node)[oi] for node, oi in sym._outputs]


def _param_shape_rules():
    """Per-op rules inferring unknown *parameter* input shapes from known
    data shapes + attrs (the nnvm InferShape pass's essential half; output
    shapes then fall out of jax.eval_shape)."""

    def fc(in_shapes, attrs, n_inputs):
        data = in_shapes[0]
        if data is None:
            return {}
        h = attrs.get("num_hidden")
        flatten = attrs.get("flatten", True)
        d = int(np.prod(data[1:])) if flatten else data[-1]
        out = {1: (h, d)}
        if n_inputs > 2:
            out[2] = (h,)
        return out

    def conv(in_shapes, attrs, n_inputs):
        data = in_shapes[0]
        if data is None:
            return {}
        f = attrs.get("num_filter")
        g = attrs.get("num_group", 1)
        kernel = tuple(attrs.get("kernel", ()))
        out = {1: (f, data[1] // g) + kernel}
        if n_inputs > 2:
            out[2] = (f,)
        return out

    def deconv(in_shapes, attrs, n_inputs):
        data = in_shapes[0]
        if data is None:
            return {}
        f = attrs.get("num_filter")
        g = attrs.get("num_group", 1)
        kernel = tuple(attrs.get("kernel", ()))
        out = {1: (data[1], f // g) + kernel}
        if n_inputs > 2:
            out[2] = (f,)
        return out

    def bn(in_shapes, attrs, n_inputs):
        data = in_shapes[0]
        if data is None:
            return {}
        c = data[attrs.get("axis", 1)]
        return {1: (c,), 2: (c,), 3: (c,), 4: (c,)}

    def norm_lastaxis(in_shapes, attrs, n_inputs):
        data = in_shapes[0]
        if data is None:
            return {}
        c = data[attrs.get("axis", -1)]
        return {i: (c,) for i in range(1, n_inputs)}

    def embedding(in_shapes, attrs, n_inputs):
        return {1: (attrs.get("input_dim"), attrs.get("output_dim"))}

    return {"FullyConnected": fc, "Convolution": conv,
            "Deconvolution": deconv, "BatchNorm": bn,
            "LayerNorm": norm_lastaxis, "InstanceNorm": norm_lastaxis,
            "RMSNorm": norm_lastaxis, "embedding": embedding}


_PARAM_SHAPE_RULES = _param_shape_rules()


def _propagate_shapes(sym, shapes, on_node_error=None, out_shapes=None):
    """Walk the graph in topo order, inferring unknown var shapes via the
    param rules and node output shapes via jax.eval_shape per node.

    ``on_node_error(node, in_shapes, exc)`` is invoked when a node's
    abstract evaluation raises (shape/dtype contract violation); the
    default keeps the historical behavior of skipping the node silently.
    ``out_shapes`` may be a dict to receive the per-(node, output-index)
    inferred shapes — the static analyzer uses it to tell "skipped
    because inputs unknown" from "evaluated clean".
    """
    import jax
    from .. import autograd
    from .. import ndarray as nd_mod

    if out_shapes is None:
        out_shapes: Dict[Tuple[int, int], tuple] = {}

    def in_shape(node, i):
        inp, oi = node.inputs[i]
        if inp.op is None:
            return shapes.get(inp.name)
        return out_shapes.get((id(inp), oi))

    for node in _topo(sym._head_nodes()):
        if node.op is None:
            # var(shape=...) hints participate in inference, matching
            # the reference's Symbol.var(shape=) behavior.  Dims <= 0
            # mean "unknown" (deferred-init params stamp e.g. (8, 0));
            # such hints must not pre-empt the param-shape rules below.
            hint = node._user_attrs.get("__shape__")
            if node.name not in shapes and hint is not None and \
                    all(int(d) > 0 for d in hint):
                shapes[node.name] = tuple(int(d) for d in hint)
            if node.name in shapes:
                out_shapes[(id(node), 0)] = tuple(shapes[node.name])
            continue
        ins = [in_shape(node, i) for i in range(len(node.inputs))]
        rule = _PARAM_SHAPE_RULES.get(node.op)
        if rule is not None:
            for pos, shape in rule(ins, node.attrs,
                                   len(node.inputs)).items():
                if pos < len(node.inputs):
                    vnode = node.inputs[pos][0]
                    if vnode.op is None and vnode.name not in shapes:
                        shapes[vnode.name] = tuple(
                            int(d) for d in shape)
                        ins[pos] = shapes[vnode.name]
        if any(s is None for s in ins):
            continue  # cannot evaluate this node yet

        def one_node(*vals, _node=node):
            value_of = {}
            shells = [NDArray(v, ctx=current_context()) for v in vals]
            fn = getattr(nd_mod, _node.op)
            with autograd.pause():
                out = fn(*shells, **_node.attrs)
            outs = tuple(out) if isinstance(out, (list, tuple)) else (out,)
            return tuple(o._data for o in outs)

        try:
            structs = [jax.ShapeDtypeStruct(s, np.dtype("float32"))
                       for s in ins]
            res = jax.eval_shape(one_node, *structs)
            for i, r in enumerate(res):
                out_shapes[(id(node), i)] = tuple(
                    int(d) for d in r.shape)
        except Exception as e:
            if on_node_error is not None:
                on_node_error(node, ins, e)
            continue
    return shapes


def _infer_via_eval_shape(sym, shapes, arg_names, aux_names):
    """Shape inference = jax.eval_shape over the traced graph."""
    import jax
    from .. import autograd

    all_names = arg_names + aux_names
    missing = [n for n in all_names if n not in shapes]
    if missing:
        _propagate_shapes(sym, shapes)
        missing = [n for n in all_names if n not in shapes]
        if missing:
            raise MXNetError(f"infer_shape: missing shapes for {missing}")

    structs = [jax.ShapeDtypeStruct(shapes[n], np.dtype("float32"))
               for n in all_names]

    def fn(*vals):
        value_of = {n: NDArray(v, ctx=current_context())
                    for n, v in zip(all_names, vals)}
        with autograd.pause():  # inference mode: no RNG keys, no mutation
            outs = _eval_graph(sym, value_of)
        return tuple(o._data for o in outs)

    out_struct = jax.eval_shape(fn, *structs)
    arg_shapes = {n: tuple(int(d) for d in shapes[n]) for n in arg_names}
    aux_shapes = {n: tuple(int(d) for d in shapes[n]) for n in aux_names}
    return out_struct, arg_shapes, aux_shapes


# ---------------------------------------------------------------------------
# Executor
# ---------------------------------------------------------------------------


class Executor:
    """Bound, compiled symbolic graph (parity: mx.executor.Executor).

    The forward (and fused forward+backward) run as single jitted XLA
    programs cached per (shapes, dtypes, train-mode); aux-state mutation
    (BN running stats) is detected via buffer-version tracking and written
    back after execution, reproducing engine-side aux updates.
    """

    def __init__(self, sym: Symbol, ctx, args, args_grad, grad_req,
                 aux_states):
        self._sym = sym
        self._ctx = ctx if isinstance(ctx, Context) else current_context()
        self.arg_names = sym.list_arguments()
        self.aux_names = sym.list_auxiliary_states()
        self.output_names = sym.list_outputs()

        self.arg_dict = self._to_dict(self.arg_names, args, "argument")
        self.aux_dict = self._to_dict(self.aux_names, aux_states or {},
                                      "auxiliary state", allow_missing=True)
        for name in self.aux_names:
            if name not in self.aux_dict:
                raise MXNetError(f"bind: missing auxiliary state {name!r}")

        if isinstance(grad_req, str):
            self.grad_req = {n: grad_req for n in self.arg_names}
        elif isinstance(grad_req, (list, tuple)):
            self.grad_req = dict(zip(self.arg_names, grad_req))
        else:
            self.grad_req = {n: grad_req.get(n, "null")
                             for n in self.arg_names}
        self.grad_dict = self._to_dict(
            self.arg_names, args_grad or {}, "gradient",
            allow_missing=True)

        self.outputs: List[NDArray] = []
        self._monitor_callback = None
        self._compiled = {}
        self._saved_inputs = None
        self._cached_grads = None

    def _to_dict(self, names, values, what, allow_missing=False):
        if isinstance(values, dict):
            out = OrderedDict()
            for n in names:
                if n in values:
                    out[n] = values[n]
                elif not allow_missing:
                    raise MXNetError(f"bind: missing {what} {n!r}")
            return out
        values = list(values)
        if not allow_missing and len(values) != len(names):
            raise MXNetError(
                f"bind: expected {len(names)} {what}s, got {len(values)}")
        return OrderedDict(zip(names, values))

    # -- compiled-program cache ------------------------------------------
    def _get_compiled(self, training: bool, with_grad: bool):
        import jax
        import jax.numpy as jnp
        from .. import autograd
        from .. import random as _rnd

        arg_vals = [self.arg_dict[n]._data for n in self.arg_names]
        aux_vals = [self.aux_dict[n]._data for n in self.aux_names]
        key = (tuple((v.shape, str(v.dtype)) for v in arg_vals),
               tuple((v.shape, str(v.dtype)) for v in aux_vals),
               training, with_grad)
        entry = self._compiled.get(key)
        if entry is not None:
            return entry

        sym = self._sym
        arg_names, aux_names = self.arg_names, self.aux_names
        ctx = self._ctx
        grad_mask = [self.grad_req.get(n, "null") != "null"
                     for n in arg_names]
        aux_mutated: List[int] = []
        monitor = self._monitor_callback
        monitor_names: List[str] = []

        def run_graph(avals, xvals, key_raw):
            key_counter = [0]

            def key_provider(_ctx):
                k = jax.random.fold_in(
                    jax.random.wrap_key_data(key_raw), key_counter[0])
                key_counter[0] += 1
                return NDArray(jax.random.key_data(k), ctx=ctx)

            value_of = {n: NDArray(v, ctx=ctx)
                        for n, v in zip(arg_names, avals)}
            aux_shells = {n: NDArray(v, ctx=ctx)
                          for n, v in zip(aux_names, xvals)}
            value_of.update(aux_shells)
            _rnd._push_key_provider(key_provider)
            prev = autograd.set_training(training)
            try:
                vers = {n: s._version for n, s in aux_shells.items()}
                outs = _eval_graph(sym, value_of)
                aux_mutated.clear()
                aux_mutated.extend(
                    i for i, n in enumerate(aux_names)
                    if aux_shells[n]._version != vers[n])
                new_aux = tuple(aux_shells[aux_names[i]]._data
                                for i in aux_mutated)
            finally:
                autograd.set_training(prev)
                _rnd._pop_key_provider()
            return tuple(o._data for o in outs), new_aux

        if not with_grad:
            def fwd(avals, xvals, key_raw):
                return run_graph(avals, xvals, key_raw)
            fn = jax.jit(fwd)
        else:
            def fwd_bwd(avals, xvals, key_raw, cots):
                def of_args(diff_vals):
                    full = list(avals)
                    di = iter(diff_vals)
                    for i, m in enumerate(grad_mask):
                        if m:
                            full[i] = next(di)
                    outs, new_aux = run_graph(tuple(full), xvals, key_raw)
                    return outs, new_aux

                diff_in = tuple(v for v, m in zip(avals, grad_mask) if m)
                outs, vjp, new_aux = jax.vjp(of_args, diff_in,
                                             has_aux=True)
                if cots is None:
                    cots = tuple(jnp.ones_like(o) for o in outs)
                (grads,) = vjp(cots)
                return outs, new_aux, grads
            fn = jax.jit(fwd_bwd)
        entry = (fn, aux_mutated)
        self._compiled[key] = entry
        return entry

    # -- API --------------------------------------------------------------
    def forward(self, is_train=False, **kwargs):
        """Run forward.  With ``is_train=True`` the FUSED fwd+bwd program
        runs once (default head cotangents) and the gradients are cached
        for ``backward()`` — the classic forward();backward() idiom costs
        one XLA execution, not two."""
        from .. import profiler
        with profiler.span(f"Executor.forward[train={bool(is_train)}]",
                           "executor"):
            return self._forward_impl(is_train, **kwargs)

    def _forward_impl(self, is_train=False, **kwargs):
        from .. import random as _rnd
        for k, v in kwargs.items():
            if k not in self.arg_dict:
                raise MXNetError(f"forward: unknown argument {k!r}")
            self.arg_dict[k]._set_data(
                v._data.astype(self.arg_dict[k].dtype.name)
                if isinstance(v, NDArray) else
                np.asarray(v, dtype=self.arg_dict[k].dtype))
        self._saved_inputs = None
        self._cached_grads = None
        if is_train:
            self.forward_backward(_write_grads=False)
            return self.outputs
        fn, aux_mutated = self._get_compiled(False, with_grad=False)
        key = _rnd._next_key_nd(self._ctx)
        avals = tuple(self.arg_dict[n]._data for n in self.arg_names)
        xvals = tuple(self.aux_dict[n]._data for n in self.aux_names)
        outs, new_aux = fn(avals, xvals, key._data)
        self._write_aux(aux_mutated, new_aux)
        self.outputs = [NDArray(o, ctx=self._ctx) for o in outs]
        if self._monitor_callback is not None:
            for name, o in zip(self.output_names, self.outputs):
                self._monitor_callback(name, o)
        return self.outputs

    def backward(self, out_grads=None):
        if out_grads is None and self._cached_grads is not None:
            self._write_grads(self._cached_grads)
            self._cached_grads = None
            return
        if self._saved_inputs is None:
            raise MXNetError(
                "backward called before forward(is_train=True)")
        # re-run the fused program (explicit cotangents, or default ones
        # when the cached grads were already consumed)
        fn, _ = self._get_compiled(True, with_grad=True)
        avals, xvals, keyraw = self._saved_inputs
        if out_grads is None:
            cots = None
        else:
            if isinstance(out_grads, NDArray):
                out_grads = [out_grads]
            cots = tuple(g._data for g in out_grads)
        outs, new_aux, grads = fn(avals, xvals, keyraw, cots)
        self._cached_grads = None
        self._write_grads(grads)
        return

    def forward_backward(self, out_grads=None, _write_grads=True,
                         **kwargs):
        """Fused one-program forward+backward (the Module.fit hot path)."""
        from .. import random as _rnd
        for k, v in kwargs.items():
            self.arg_dict[k]._set_data(
                v._data if isinstance(v, NDArray)
                else np.asarray(v, dtype=self.arg_dict[k].dtype))
        fn, aux_mutated = self._get_compiled(True, with_grad=True)
        key = _rnd._next_key_nd(self._ctx)
        avals = tuple(self.arg_dict[n]._data for n in self.arg_names)
        xvals = tuple(self.aux_dict[n]._data for n in self.aux_names)
        cots = None
        if out_grads is not None:
            cots = tuple(g._data for g in out_grads)
        outs, new_aux, grads = fn(avals, xvals, key._data, cots)
        self._write_aux(aux_mutated, new_aux)
        if _write_grads:
            self._write_grads(grads)
            self._cached_grads = None
        else:
            self._cached_grads = grads
        self._saved_inputs = (avals, xvals, key._data)
        self.outputs = [NDArray(o, ctx=self._ctx) for o in outs]
        if self._monitor_callback is not None:
            for name, o in zip(self.output_names, self.outputs):
                self._monitor_callback(name, o)
        return self.outputs

    def _write_aux(self, aux_mutated, new_aux):
        # aux_mutated holds the aux indices that mutated, captured at trace
        # time by run_graph (populated during the jit's first execution)
        for i, v in zip(aux_mutated, new_aux):
            self.aux_dict[self.aux_names[i]]._set_data(v)

    def _write_grads(self, grads):
        gi = iter(grads)
        for n in self.arg_names:
            if self.grad_req.get(n, "null") == "null":
                continue
            g = next(gi)
            dst = self.grad_dict.get(n)
            if dst is None:
                continue
            if self.grad_req[n] == "add":
                dst._set_data(dst._data + g)
            else:
                dst._set_data(g.astype(dst.dtype.name))

    def copy_params_from(self, arg_params, aux_params=None,
                         allow_extra_params=False):
        for k, v in arg_params.items():
            if k in self.arg_dict:
                v.copyto(self.arg_dict[k])
            elif not allow_extra_params:
                raise MXNetError(f"unknown argument {k!r}")
        if aux_params:
            for k, v in aux_params.items():
                if k in self.aux_dict:
                    v.copyto(self.aux_dict[k])
                elif not allow_extra_params:
                    raise MXNetError(f"unknown aux state {k!r}")

    def set_monitor_callback(self, callback, monitor_all=False):
        self._monitor_callback = callback
        self._compiled.clear()

    def reshape(self, **kwargs):
        return self  # shapes re-specialize automatically via the jit cache


# ---------------------------------------------------------------------------
# free functions
# ---------------------------------------------------------------------------


def var(name, attr=None, shape=None, lr_mult=None, wd_mult=None, dtype=None,
        init=None, stype=None, **kwargs) -> Symbol:
    node = _Node(None, name, {}, [])
    if attr:
        node._user_attrs.update(attr)
    for k, v in (("__shape__", shape), ("__lr_mult__", lr_mult),
                 ("__wd_mult__", wd_mult), ("__dtype__", dtype)):
        if v is not None:
            node._user_attrs[k] = v
    return Symbol([(node, 0)])


Variable = var


def Group(symbols) -> Symbol:
    outs = []
    for s in symbols:
        outs.extend(s._outputs)
    return Symbol(outs)


def _invoke(opname, sym_inputs, attrs, name=None, num_outputs=None):
    """Create an op node (shared by generated sym.* wrappers)."""
    nodes = []
    for s in sym_inputs:
        if not isinstance(s, Symbol):
            raise MXNetError(f"{opname}: symbolic op inputs must be "
                             f"Symbols, got {type(s)}")
        if len(s._outputs) != 1:
            raise MXNetError(f"{opname}: multi-output Symbol used as input;"
                             " select an output first")
        nodes.append(s._outputs[0])
    if num_outputs is None:
        try:
            num_outputs = get_op(opname).num_outputs
        except KeyError:
            num_outputs = 1
    name = name or _NAMES.get(opname.lstrip("_"))
    node = _Node(opname, name, dict(attrs), nodes, num_outputs)
    return Symbol([(node, i) for i in range(num_outputs)]) \
        if num_outputs > 1 else Symbol([(node, 0)])


# re-export for __init__ namespace generation
def _invoke_sym(opname, sym_inputs, attrs, name=None):
    return _invoke(opname, sym_inputs, attrs, name=name)


def load_json(json_str: str) -> Symbol:
    import ast
    data = json.loads(json_str)
    nodes: List[_Node] = []
    for jn in data["nodes"]:
        attrs = {}
        for k, v in jn.get("attrs", {}).items():
            try:
                attrs[k] = ast.literal_eval(v)
            except (ValueError, SyntaxError):
                attrs[k] = v
        op = jn["op"]
        node = _Node(None if op == "null" else op, jn["name"], attrs,
                     [(nodes[i], oi) for i, oi, _ in jn["inputs"]],
                     jn.get("num_outputs", 1))
        for k, v in jn.get("user_attrs", {}).items():
            try:
                node._user_attrs[k] = ast.literal_eval(v)
            except (ValueError, SyntaxError):
                node._user_attrs[k] = v
        nodes.append(node)
    return Symbol([(nodes[i], oi) for i, oi, _ in data["heads"]])


def load(fname: str) -> Symbol:
    with open(fname) as f:
        return load_json(f.read())
