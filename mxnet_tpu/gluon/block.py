"""Gluon Block / HybridBlock and the CachedOp (hybridize → XLA seam).

Capability parity: reference ``python/mxnet/gluon/block.py`` +
``src/imperative/cached_op.cc`` (SURVEY.md §2.1, §2.5, call stack §3.3).

TPU-native design — THE seam (SURVEY.md §3.3): ``hybridize()`` does not
build an nnvm graph; instead ``CachedOp`` traces the block's imperative
forward (pure JAX ops under the hood) into one jitted executable, cached per
(input shapes, dtypes, train-mode) exactly like the reference caches
GraphInfo per (shape, dtype, ctx).  XLA then owns memory planning, fusion
and layout — the jobs nnvm's PlanMemory/bulking did.

Mechanics worth knowing:
* Parameter/aux mutation inside the graph (BatchNorm moving stats) is
  functionalized: the trace detects buffer-version bumps and returns the new
  values as extra outputs, which ``CachedOp.__call__`` writes back after the
  compiled call — reproducing the reference's aux-array update semantics.
* RNG (Dropout) is threaded as a *base key input* + per-request ``fold_in``,
  so each compiled call uses fresh masks without recompiling.
* Under ``autograd.record()`` the whole cached op joins the tape as ONE node
  via ``jax.vjp`` over the jitted function (compiled forward AND backward) —
  the analog of ``CachedOp::Backward``'s cached gradient graph.
"""
from __future__ import annotations

import contextlib
import copy
import re
import threading
from collections import OrderedDict
from typing import List, Optional

import numpy as np

from ..base import MXNetError
from ..context import Context, cpu, current_context
from .. import ndarray as nd
from ..ndarray.ndarray import NDArray
from .parameter import (Parameter, ParameterDict, Constant,
                        DeferredInitializationError)

__all__ = ["Block", "HybridBlock", "SymbolBlock", "CachedOp"]

_naming = threading.local()


class _BlockScope:
    """Name manager: gives blocks unique prefixes (parity: _BlockScope)."""

    _counters = {}

    def __init__(self, block):
        self._block = block
        self._counter = {}
        self._old_scope = None

    @staticmethod
    def create(prefix, params, hint):
        current = getattr(_naming, "current", None)
        if current is None:
            if prefix is None:
                count = _BlockScope._counters.setdefault(hint, 0)
                prefix = f"{hint}{count}_"
                _BlockScope._counters[hint] += 1
            if params is None:
                params = ParameterDict(prefix)
            else:
                params = ParameterDict(params.prefix, params)
            return prefix, params
        if prefix is None:
            count = current._counter.setdefault(hint, 0)
            prefix = f"{hint}{count}_"
            current._counter[hint] += 1
        if params is None:
            parent = current._block.params
            params = ParameterDict(parent.prefix + prefix, parent._shared)
        else:
            params = ParameterDict(params.prefix, params)
        return current._block.prefix + prefix, params

    def __enter__(self):
        if self._block._empty_prefix:
            return self
        self._old_scope = getattr(_naming, "current", None)
        _naming.current = self
        return self

    def __exit__(self, *exc):
        if self._block._empty_prefix:
            return
        _naming.current = self._old_scope


class Block:
    """Base class for all neural-network layers and models."""

    def __init__(self, prefix=None, params=None):
        self._empty_prefix = prefix == ""
        self._prefix, self._params = _BlockScope.create(
            prefix, params, self._alias())
        self._name = self._prefix[:-1] if self._prefix.endswith("_") \
            else self._prefix
        self._scope = _BlockScope(self)
        self._children = OrderedDict()
        self._reg_params = {}
        self._forward_hooks = OrderedDict()
        self._forward_pre_hooks = OrderedDict()

    def _alias(self):
        return self.__class__.__name__.lower()

    # -- attribute magic: auto-register children & params -----------------
    def __setattr__(self, name, value):
        if hasattr(self, name):
            existing = getattr(self, name)
            if isinstance(existing, (Parameter, Block)) and \
                    not isinstance(value, type(existing)) and \
                    not isinstance(existing, type(value)):
                raise TypeError(
                    f"Changing attribute type for {self.name!r} from "
                    f"{type(existing)} to {type(value)} is not allowed.")
        if isinstance(value, Block):
            self.register_child(value, name)
        elif isinstance(value, Parameter):
            assert name not in self._reg_params or \
                self._reg_params[name] is value, \
                "Overriding Parameter attribute is not allowed. " \
                "If you want to share parameters between blocks, please " \
                "pass `params` at construction."
            self._reg_params[name] = value
        super().__setattr__(name, value)

    def __repr__(self):
        s = "{name}(\n{modstr}\n)"
        modstr = "\n".join(
            f"  ({key}): {_indent(repr(block), 2)}"
            for key, block in self._children.items())
        return s.format(name=self.__class__.__name__, modstr=modstr) \
            if modstr else f"{self.__class__.__name__}()"

    # -- identity ----------------------------------------------------------
    @property
    def prefix(self):
        return self._prefix

    @property
    def name(self):
        return self._name

    def name_scope(self):
        return self._scope

    @property
    def params(self) -> ParameterDict:
        return self._params

    def collect_params(self, select=None) -> ParameterDict:
        """All Parameters of this block and children (regex filterable)."""
        ret = ParameterDict(self._params.prefix)
        if not select:
            ret.update(self.params)
        else:
            pattern = re.compile(select)
            ret.update({name: value for name, value in self.params.items()
                        if pattern.match(name)})
        for child in self._children.values():
            ret.update(child.collect_params(select=select))
        return ret

    def register_child(self, block, name=None):
        if name is None:
            name = str(len(self._children))
        self._children[name] = block

    def register_forward_hook(self, hook):
        handle = _HookHandle(self._forward_hooks)
        self._forward_hooks[handle._id] = hook
        return handle

    def register_forward_pre_hook(self, hook):
        handle = _HookHandle(self._forward_pre_hooks)
        self._forward_pre_hooks[handle._id] = hook
        return handle

    def apply(self, fn):
        for child in self._children.values():
            child.apply(fn)
        fn(self)
        return self

    # -- lifecycle ---------------------------------------------------------
    def initialize(self, init=None, ctx=None, verbose=False,
                   force_reinit=False):
        self.collect_params().initialize(init, ctx, verbose, force_reinit)

    def cast(self, dtype):
        for child in self._children.values():
            child.cast(dtype)
        for _, param in self.params.items():
            param.cast(dtype)

    def zero_grad(self):
        self.collect_params().zero_grad()

    def reset_ctx(self, ctx):
        self.collect_params().reset_ctx(ctx)

    # -- (de)serialization -------------------------------------------------
    def _collect_params_with_prefix(self, prefix=""):
        if prefix:
            prefix += "."
        ret = {prefix + key: val for key, val in self._reg_params.items()}
        for name, child in self._children.items():
            ret.update(child._collect_params_with_prefix(prefix + name))
        return ret

    def save_parameters(self, filename, deduplicate=False):
        """Save params keyed by attribute path (robust to prefix changes)."""
        params = self._collect_params_with_prefix()
        arg_dict = {}
        seen = {}
        for key, param in params.items():
            if deduplicate and id(param) in seen:
                continue
            seen[id(param)] = key
            arg_dict[key] = param._check_and_get(param._data, None)
        nd.save(filename, arg_dict)

    def load_parameters(self, filename, ctx=None, allow_missing=False,
                        ignore_extra=False, cast_dtype=False,
                        dtype_source="current"):
        loaded = nd.load(filename)
        if not isinstance(loaded, dict):
            raise MXNetError(
                f"file {filename!r} holds an unnamed NDArray list, not "
                "named parameters")
        # reference Module checkpoints prefix keys with arg:/aux: —
        # upstream load_parameters strips these, so we must too
        loaded = {(k[4:] if k.startswith(("arg:", "aux:")) else k): v
                  for k, v in loaded.items()}
        params = self._collect_params_with_prefix()
        if not loaded and not params:
            return
        # legacy fallback: file saved with FULL param names (reference
        # Module checkpoints, nd.save of collect_params()) — detect by
        # a key that resolves as a param name but not as an attribute
        # path, or by the dotted-path shape heuristic
        by_name = {p.name: p for p in self.collect_params().values()}
        if (any(k in by_name and k not in params for k in loaded)
                or (not any("." in k for k in loaded.keys())
                    and any("." in k for k in params.keys()))):
            for name, value in loaded.items():
                if name in by_name:
                    by_name[name]._load_init(value, ctx,
                                             cast_dtype=cast_dtype)
                elif not ignore_extra:
                    raise MXNetError(
                        f"Parameter {name!r} loaded from file {filename!r} "
                        "is not present in this Block")
            return
        if not allow_missing:
            for name in params.keys():
                if name not in loaded:
                    raise MXNetError(
                        f"Parameter {name!r} is missing in file "
                        f"{filename!r}, which contains parameters: "
                        f"{_brief_print_list(loaded.keys())}")
        for name in loaded:
            if name not in params:
                if not ignore_extra:
                    raise MXNetError(
                        f"Parameter {name!r} loaded from file {filename!r} "
                        "is not present in this Block")
                continue
            params[name]._load_init(loaded[name], ctx, cast_dtype=cast_dtype)

    # -- call path ---------------------------------------------------------
    def __call__(self, *args):
        for hook in self._forward_pre_hooks.values():
            hook(self, args)
        out = self.forward(*args)
        for hook in self._forward_hooks.values():
            hook(self, args, out)
        return out

    def forward(self, *args):
        raise NotImplementedError

    def hybridize(self, active=True, **kwargs):
        """Recursively activate hybridization on HybridBlock children."""
        for child in self._children.values():
            child.hybridize(active, **kwargs)

    def summary(self, *inputs):
        """Print a per-layer summary given sample inputs."""
        summary = OrderedDict()
        hooks = []

        def _register(block):
            def _hook(blk, _, outputs):
                cname = blk.__class__.__name__
                key = f"{cname}-{len(summary) + 1}"
                outs = outputs if isinstance(outputs, (list, tuple)) \
                    else [outputs]
                summary[key] = (tuple(getattr(o, "shape", ()) for o in outs),
                                sum(int(np.prod(p.shape))
                                    for p in blk._reg_params.values()
                                    if p.shape))
            hooks.append(block.register_forward_hook(_hook))

        self.apply(_register)
        try:
            self(*inputs)
            print(f"{'Layer':<30}{'Output Shape':<30}{'Params':<15}")
            print("-" * 75)
            total = 0
            for key, (shapes, nparams) in summary.items():
                print(f"{key:<30}{str(shapes):<30}{nparams:<15}")
                total += nparams
            print("-" * 75)
            print(f"Total params: {total}")
        finally:
            for h in hooks:
                h.detach()


class _HookHandle:
    _counter = [0]

    def __init__(self, hooks_dict):
        self._hooks_dict = hooks_dict
        _HookHandle._counter[0] += 1
        self._id = _HookHandle._counter[0]

    def detach(self):
        self._hooks_dict.pop(self._id, None)


def _indent(s, num):
    lines = s.split("\n")
    return ("\n" + " " * num).join(lines)


def _brief_print_list(lst, limit=7):
    lst = list(lst)
    if len(lst) > limit:
        return ", ".join(map(repr, lst[:limit // 2])) + ", ..., " + \
            ", ".join(map(repr, lst[-limit // 2:]))
    return ", ".join(map(repr, lst))


# ---------------------------------------------------------------------------
# CachedOp
# ---------------------------------------------------------------------------

_trace_state = threading.local()


def _is_tracing() -> bool:
    return getattr(_trace_state, "active", False)


@contextlib.contextmanager
def tracing_scope(param_nds=(), param_vals=None):
    """Enter the trace seam: NDArray ops apply directly on jax tracers
    instead of dispatching compiled programs.

    Optionally swaps each NDArray in ``param_nds`` to the traced value
    at the same position of ``param_vals``; buffers AND versions are
    restored on exit, so in-place mutation during the trace cannot
    leak into the imperative state.  Yields the saved
    ``[(buf, version), ...]`` list so callers can detect in-trace
    mutation (version drift).  Used by CachedOp's ``pure()``, the
    fused trainer, ``deploy._functionalize``, and fused generation
    loops — the save/restore choreography lives in ONE place.
    """
    saved = [(r._buf, r._version) for r in param_nds]
    prev = getattr(_trace_state, "active", False)
    _trace_state.active = True
    try:
        if param_vals is not None:
            for r, v in zip(param_nds, param_vals):
                r._buf = v
        yield saved
    finally:
        _trace_state.active = prev
        for r, (buf, ver) in zip(param_nds, saved):
            r._buf = buf
            r._version = ver


class _CacheEntry:
    __slots__ = ("jitted", "n_real_out", "mutated_idx", "out_tree",
                 "out_avals")

    def __init__(self):
        self.jitted = None
        self.n_real_out = 0
        self.mutated_idx = ()
        self.out_tree = None
        self.out_avals = None


def _flatten_args(args):
    """Flatten nested (list/tuple of) NDArray args into leaves + treedef
    (cells pass state lists; attention passes mask tuples).  numpy arrays
    become NDArray leaves (data, not compile-time constants); other
    non-array values are static and keyed by repr — like jit static args,
    a changing static value recompiles."""
    import numpy as _np
    from .. import ndarray as _nd
    leaves = []

    def go(x):
        if isinstance(x, _np.ndarray):
            x = _nd.array(x, dtype=x.dtype)
        if isinstance(x, NDArray):
            leaves.append(x)
            return ("L", len(leaves) - 1)
        if isinstance(x, (list, tuple)):
            return ("l" if isinstance(x, list) else "t",
                    tuple(go(y) for y in x))
        return ("C", x)  # static constant (None, scalars, strings)

    tree = tuple(go(a) for a in args)
    return leaves, tree


def _tree_cache_key(tree):
    """Hashable form of a treedef (constants may be unhashable)."""

    def go(t):
        tag = t[0]
        if tag in ("l", "t"):
            return (tag, tuple(go(y) for y in t[1]))
        if tag == "C":
            try:
                hash(t[1])
                return ("C", t[1])
            except TypeError:
                return ("C", repr(t[1]))
        return t

    return tuple(go(t) for t in tree)


def _unflatten_args(tree, leaves):
    def go(t):
        tag = t[0]
        if tag == "L":
            return leaves[t[1]]
        if tag == "C":
            return t[1]
        seq = [go(y) for y in t[1]]
        return seq if tag == "l" else tuple(seq)

    return [go(t) for t in tree]


class CachedOp:
    """Compiled-executable cache for a HybridBlock (parity: CachedOp)."""

    _uid = [0]

    def __init__(self, block: "HybridBlock", static_alloc=False,
                 static_shape=False):
        self.block = block
        self.static_alloc = static_alloc      # accepted for API parity;
        self.static_shape = static_shape      # XLA always plans statically
        self._entries = {}
        self._param_list: Optional[List[Parameter]] = None
        CachedOp._uid[0] += 1
        self.name = f"cachedop_{block.name}_{CachedOp._uid[0]}"

    def _collect_param_arrays(self, leaves, call_args):
        """Stable ordered list of param NDArray replicas for the call ctx."""
        if self._param_list is None:
            params = list(self.block.collect_params().values())
            if any(p._deferred_init for p in params):
                # one imperative warm-up run resolves every deferred shape
                from .. import autograd
                with autograd.pause():
                    self.block._call_unhybridized(*call_args)
            self._param_list = params
        ctx = leaves[0].context if leaves else None
        out = []
        for p in self._param_list:
            d = p._check_and_get(p._data, None)
            if ctx is not None and ctx != d.context:
                d = p.data(ctx)
            out.append(d)
        return out

    def _get_entry(self, param_nds, leaves, tree, ctx,
                   training) -> _CacheEntry:
        key = (tuple((a.shape, a.dtype.name) for a in leaves),
               _tree_cache_key(tree),
               tuple((p.shape, p.dtype.name) for p in param_nds),
               training, ctx)
        entry = self._entries.get(key)
        if entry is not None:
            return entry
        import jax
        entry = _CacheEntry()
        block = self.block
        params = self._param_list
        n_params = len(param_nds)
        n_args = len(leaves)

        def pure(*flat):
            """Functionalized forward: (params…, inputs…, base_key) →
            (outputs…, mutated-param-values…)."""
            from .. import random as _rnd
            # resolve the per-context replica NDArrays at trace time from
            # the Parameter objects, so the closure never pins stale
            # buffers across load_parameters/reset_ctx
            reps = [p.data(ctx) for p in params]
            param_vals = flat[:n_params]
            input_vals = flat[n_params:n_params + n_args]
            base_key_raw = flat[-1]
            key_counter = [0]

            def key_provider(_ctx):
                k = jax.random.fold_in(
                    jax.random.wrap_key_data(base_key_raw), key_counter[0])
                key_counter[0] += 1
                return NDArray(jax.random.key_data(k), ctx=ctx)

            _rnd._push_key_provider(key_provider)
            try:
                with tracing_scope(reps, param_vals) as saved:
                    shells = [NDArray(v, ctx=ctx) for v in input_vals]
                    call_args = _unflatten_args(tree, shells)
                    outs = block._call_unhybridized(*call_args)
                    # outputs may nest (RNN layers return (seq,
                    # [h, c])) — flatten with the same tree scheme as
                    # the inputs
                    out_leaves, out_tree = _flatten_args((outs,))
                    out_data = tuple(o._data for o in out_leaves)
                    mutated_idx = tuple(
                        i for i, (r, s) in enumerate(zip(reps, saved))
                        if r._version != s[1])
                    mutated_vals = tuple(reps[i]._buf
                                         for i in mutated_idx)
            finally:
                _rnd._pop_key_provider()
            entry.n_real_out = len(out_data)
            entry.mutated_idx = mutated_idx
            entry.out_tree = out_tree
            return out_data + mutated_vals

        from .. import autograd

        def pure_in_mode(*flat):
            prev = autograd.set_training(training)
            try:
                return pure(*flat)
            finally:
                autograd.set_training(prev)

        entry.jitted = jax.jit(pure_in_mode)
        self._entries[key] = entry
        return entry

    def __call__(self, *args):
        from .. import profiler
        with profiler.span(f"CachedOp[{self.block.name}]", "cachedop"):
            return self._execute(args)

    def _execute(self, args):
        from .. import autograd
        from .. import random as _rnd
        import jax

        leaves, tree = _flatten_args(args)
        param_nds = self._collect_param_arrays(leaves, args)
        training = autograd.is_training()
        ctx = leaves[0].context if leaves else current_context()
        entry = self._get_entry(param_nds, leaves, tree, ctx, training)
        base_key = _rnd._next_key_nd(ctx)

        flat = [p._data for p in param_nds] + [a._data for a in leaves] \
            + [base_key._data]

        try:
            if autograd.is_recording():
                out_all, vjp_fn = jax.vjp(entry.jitted, *flat)

                def vjp_tuple(cots, _fn=vjp_fn):
                    # the traced fn always returns a tuple; the tape
                    # passes a bare cotangent for a single output slot
                    return _fn(cots if isinstance(cots, tuple)
                               else (cots,))

                node = autograd._Node(
                    vjp_tuple, list(param_nds) + list(leaves), 1,
                    [o.aval for o in out_all])
            else:
                out_all = entry.jitted(*flat)
                node = None
        except jax.errors.JaxRuntimeError as e:
            # device/callback failure during execution: same error TYPE
            # whether it surfaces here (sync backend) or at the consumer
            # sync point (async backend) — the reference's
            # exception-teleporting contract is MXNetError either way
            raise MXNetError(
                f"execution error in CachedOp[{self.block.name}]: {e}"
            ) from e

        real = out_all[:entry.n_real_out]
        aux = out_all[entry.n_real_out:]
        # write mutated params back (outside the tape, like aux updates) —
        # into the per-context replicas used for this call
        for i, val in zip(entry.mutated_idx, aux):
            param_nds[i]._set_data(val)

        outs = []
        for i, o in enumerate(real):
            o_nd = NDArray(o, ctx=ctx)
            if node is not None:
                o_nd._ag_node = node
                o_nd._ag_out_idx = i
            outs.append(o_nd)
        if node is not None:
            node.outputs = list(outs)
        return _unflatten_args(entry.out_tree, outs)[0]


# ---------------------------------------------------------------------------
# HybridBlock
# ---------------------------------------------------------------------------


class HybridBlock(Block):
    """Block that can be hybridized: traced once, compiled by XLA."""

    def __init__(self, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._active = False
        self._cached_op: Optional[CachedOp] = None
        self._flags = {}

    def hybridize(self, active=True, static_alloc=False, static_shape=False,
                  **kwargs):
        self._active = active
        self._flags = dict(static_alloc=static_alloc,
                           static_shape=static_shape, **kwargs)
        self._cached_op = None
        super().hybridize(active, static_alloc=static_alloc,
                          static_shape=static_shape, **kwargs)

    def _clear_cached_op(self):
        self._cached_op = None

    def register_child(self, block, name=None):
        if not isinstance(block, HybridBlock):
            if not isinstance(block, SymbolBlock):
                # non-hybrid children make the parent fall back to
                # imperative for itself but stay callable
                pass
        super().register_child(block, name)
        if self._cached_op is not None:
            self._clear_cached_op()

    def cast(self, dtype):
        self._clear_cached_op()
        super().cast(dtype)

    # -- shape inference for deferred params -------------------------------
    def infer_shape(self, *args):
        """Subclasses with deferred params override to set param shapes."""
        raise MXNetError(
            f"Cannot infer shapes of deferred-initialized parameters for "
            f"{self.name!r}: layer does not implement infer_shape(). "
            "Specify in_units/in_channels explicitly.")

    def infer_type(self, *args):
        pass

    def _call_unhybridized(self, *args):
        """Run hybrid_forward imperatively, resolving deferred init."""
        ctx = args[0].context if args and isinstance(args[0], NDArray) \
            else None
        try:
            params = {k: p.data(ctx) for k, p in self._reg_params.items()}
        except DeferredInitializationError:
            self._deferred_infer_shape(*args)
            for p in self._reg_params.values():
                p._finish_deferred_init()
            params = {k: p.data(ctx) for k, p in self._reg_params.items()}
        return self.hybrid_forward(nd, *args, **params)

    def _deferred_infer_shape(self, *args):
        self.infer_shape(*args)

    def forward(self, x, *args):
        if isinstance(x, NDArray):
            # record which positions carry arrays (None/other stays
            # literal at export time)
            self._in_sig = tuple(
                isinstance(a, NDArray) or (
                    isinstance(a, (list, tuple)) and
                    any(isinstance(e, NDArray) for e in a))
                for a in (x,) + args)
            if self._active and not _is_tracing():
                if self._cached_op is None:
                    self._cached_op = CachedOp(self, **{
                        k: v for k, v in self._flags.items()
                        if k in ("static_alloc", "static_shape")})
                return self._cached_op(x, *args)
            return self._call_unhybridized(x, *args)
        # symbolic input (Symbol tracing) — delegated to hybrid_forward
        from .. import symbol as sym_mod
        params = {k: p.var() for k, p in self._reg_params.items()}
        with _name_prefix(self.prefix):
            return self.hybrid_forward(sym_mod, x, *args, **params)

    def hybrid_forward(self, F, x, *args, **kwargs):
        raise NotImplementedError

    def lint(self, *input_shapes, names=None):
        """Run the mxlint graph passes (``mxnet_tpu.analysis``) over this
        block's traced graph — the same ``block(sym.var(...))`` seam
        ``export()`` serializes — without executing anything on device.

        ``input_shapes`` (optional) enables the MXL105 shape/dtype
        contract validator; ``names`` overrides the default input names
        (``data`` / ``data0..N``).  Returns the list of findings (empty
        = clean).  Imperative-only blocks (those reading ``x.shape``
        inside ``hybrid_forward``) cannot be traced and raise, exactly
        as ``export()`` would fail for them.
        """
        from .. import analysis
        from .. import symbol as sym_mod
        n = max(len(input_shapes), 1)
        names = list(names) if names else (
            ["data"] if n == 1 else [f"data{i}" for i in range(n)])
        out = self(*[sym_mod.var(nm) for nm in names])
        shapes = dict(zip(names, input_shapes)) if input_shapes else None
        return analysis.analyze_symbol(
            out, shapes=shapes, check_shapes=bool(input_shapes),
            name=self.name or type(self).__name__)

    def export(self, path, epoch=0, remove_amp_cast=True):
        """Export (parity: HybridBlock.export): writes
        ``path-symbol.json`` (the traced graph — load with
        ``SymbolBlock.imports`` or ``mx.sym.load``, no model code needed)
        and ``path-%04d.params`` (``arg:``/``aux:``-prefixed arrays, the
        reference's checkpoint layout shared with Module).
        """
        from .. import symbol as sym_mod
        sig = getattr(self, "_in_sig", None)
        if sig is None:
            raise MXNetError(
                "export() needs the input signature: run the block on "
                "real data once before exporting (parity: the reference "
                "exports the cached graph)")
        n_arrays = sum(sig)
        in_names = ["data"] if n_arrays == 1 else \
            [f"data{i}" for i in range(n_arrays)]
        it = iter(in_names)
        call_args = [sym_mod.var(next(it)) if is_arr else None
                     for is_arr in sig]
        out = self(*call_args)
        if isinstance(out, (list, tuple)):
            out = sym_mod.Group(list(out))
        out.save(f"{path}-symbol.json")
        aux_names = set(out.list_auxiliary_states())
        payload = {}
        for name, param in self.collect_params().items():
            arr = param._check_and_get(param._data, None)
            tag = "aux:" if name in aux_names else "arg:"
            payload[tag + name] = arr
        nd.save(f"{path}-{epoch:04d}.params", payload)


class _name_prefix:
    def __init__(self, prefix):
        self.prefix = prefix

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return None


class SymbolBlock(HybridBlock):
    """Block wrapping a symbolic graph (parity: gluon.SymbolBlock).

    Runs an exported model without its Python model code: the graph
    executes through a cached whole-graph Executor (one XLA program), the
    same seam ``HybridBlock.hybridize`` uses.
    """

    def __init__(self, outputs, inputs, params=None):
        super().__init__(prefix="", params=params)
        from .. import symbol as sym_mod
        if isinstance(outputs, (list, tuple)):
            outputs = sym_mod.Group(list(outputs))
        if isinstance(inputs, sym_mod.Symbol):
            inputs = list(inputs)
        self._sym_outputs = outputs
        self._sym_inputs = [i.name for i in inputs]
        input_set = set(self._sym_inputs)
        self._aux_names = outputs.list_auxiliary_states()
        for name in outputs.list_arguments():
            if name not in input_set:
                self.params.get(name, allow_deferred_init=True)
        for name in self._aux_names:
            self.params.get(name, grad_req="null",
                            allow_deferred_init=True)
        self._executors = {}  # (shapes, dtypes) → Executor

    @staticmethod
    def imports(symbol_file, input_names, param_file=None, ctx=None):
        """Load an exported model (parity: SymbolBlock.imports)."""
        from .. import symbol as sym_mod
        from ..context import current_context
        sym = sym_mod.load(symbol_file)
        if isinstance(input_names, str):
            input_names = [input_names]
        inputs = [sym_mod.var(n) for n in input_names]
        block = SymbolBlock(sym, inputs)
        if param_file is not None:
            saved = nd.load(param_file)
            arg_params = {}
            for k, v in saved.items():
                name = k.split(":", 1)[1] if ":" in k else k
                arg_params[name] = v
            for name, param in block.collect_params().items():
                if name in arg_params:
                    param._load_init(arg_params[name], ctx)
                else:
                    raise MXNetError(
                        f"Parameter {name!r} missing in {param_file!r}")
        return block

    def forward(self, x, *args):
        from ..context import current_context
        inputs = [x] + list(args)
        if len(inputs) != len(self._sym_inputs):
            raise MXNetError(
                f"SymbolBlock expects {len(self._sym_inputs)} inputs "
                f"({self._sym_inputs}), got {len(inputs)}")
        key = tuple((i.shape, i.dtype.name) for i in inputs)
        executor = self._executors.get(key)
        if executor is None:
            ctx = x.context
            arg_dict = {}
            for n, i in zip(self._sym_inputs, inputs):
                arg_dict[n] = nd.zeros(i.shape, ctx=ctx,
                                       dtype=i.dtype.name)
            aux_dict = {}
            for name, p in self.collect_params().items():
                if name in self._aux_names:
                    aux_dict[name] = p.data()
                else:
                    arg_dict[name] = p.data()
            executor = self._sym_outputs.bind(
                ctx, arg_dict, grad_req="null", aux_states=aux_dict)
            self._executors[key] = executor
        kwargs = {n: i for n, i in zip(self._sym_inputs, inputs)}
        outs = executor.forward(is_train=False, **kwargs)
        return outs[0] if len(outs) == 1 else list(outs)
