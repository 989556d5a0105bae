"""``mx.rtc``: user-authored device kernels.

Capability parity: reference ``python/mxnet/rtc.py`` — ``CudaModule``
runtime-compiles user CUDA source via NVRTC and launches kernels on
NDArrays (SURVEY.md §2.2 "Fused pointwise codegen ... user-facing RTC
via mx.rtc.CudaModule").

TPU-native design: the kernel language is **Pallas** (the TPU kernel
DSL that plays NVRTC/CUDA-C's role here), so a "module" holds Python
kernel *functions* operating on ``Ref``s instead of CUDA source
strings.  ``get_kernel(...).launch(args, ctx, ...)`` keeps the
reference's launch surface: grid in units of blocks, one output spec
per output, compile-once caching per (kernel, shapes, grid).  On a
non-TPU backend kernels run under the Pallas interpreter, so user
kernels are testable on the CPU suite exactly like the in-tree flash
attention kernel.

    import mxnet_tpu as mx
    from mxnet_tpu import nd, rtc

    def axpy(x_ref, y_ref, o_ref, *, alpha):
        o_ref[...] = alpha * x_ref[...] + y_ref[...]

    mod = rtc.PallasModule({"axpy": axpy})
    k = mod.get_kernel("axpy", alpha=2.0)
    (out,) = k.launch([x, y], out_shapes=[x.shape])
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

from .base import MXNetError

__all__ = ["PallasModule", "CudaModule"]


def _interpret_default() -> bool:
    """Mosaic exists only on a TPU backend; elsewhere a kernel can only
    be interpreted.  On a TPU the answer is always "compiled": a kernel
    that cannot lower there raises."""
    from .base import on_accelerator
    return not on_accelerator()


def _specs_key(specs) -> Tuple:
    """Structural cache key for BlockSpec lists: block shape + the
    index_map's compiled code/closure.  Rebuilding an *equal* spec per
    launch (the idiomatic pattern) therefore hits the cache instead of
    recompiling the kernel each step."""
    if specs is None:
        return ()
    out = []
    for s in specs:
        bs = getattr(s, "block_shape", None)
        im = getattr(s, "index_map", None)
        # pallas wraps the user function in _IndexMapFunc; unwrap to
        # reach the code object
        im = getattr(im, "index_map", im)
        code = getattr(im, "__code__", None)
        if code is not None:
            closure = getattr(im, "__closure__", None) or ()
            imk = (code.co_code, repr(code.co_consts),
                   tuple(repr(c.cell_contents) for c in closure))
        else:
            imk = repr(im)
        out.append((tuple(bs) if bs is not None else None, imk))
    return tuple(out)


_RTC_SEQ = functools.partial(next, __import__("itertools").count())


class PallasKernel:
    """A launchable kernel (parity: ``CudaKernel``); compile-once per
    (shapes, dtypes, out spec, grid, BlockSpecs) via ``jax.jit`` over
    ``pallas_call``."""

    def __init__(self, name: str, fn: Callable, static_kwargs: dict,
                 interpret: Optional[bool]):
        self._name = name
        self._fn = fn
        self._static = dict(static_kwargs)
        self._interpret = interpret
        # key (incl. structural BlockSpec keys) -> OpDef; structural
        # keying means idiomatic callers that rebuild equal specs each
        # launch still hit the cache instead of recompiling per step
        self._compiled: Dict[Tuple, Any] = {}

    def _build(self, out_shapes, out_dtypes, grid, in_specs, out_specs,
               scratch_shapes):
        import jax
        import jax.numpy as jnp
        from jax.experimental import pallas as pl

        body = (functools.partial(self._fn, **self._static)
                if self._static else self._fn)
        interpret = (self._interpret if self._interpret is not None
                     else _interpret_default())
        out_shape = [jax.ShapeDtypeStruct(s, jnp.dtype(d))
                     for s, d in zip(out_shapes, out_dtypes)]
        kwargs: Dict[str, Any] = {"interpret": interpret}
        if grid is not None:
            kwargs["grid"] = grid
        if in_specs is not None:
            kwargs["in_specs"] = in_specs
        if out_specs is not None:
            kwargs["out_specs"] = (out_specs if len(out_shapes) > 1
                                   else out_specs[0])
        if scratch_shapes:
            kwargs["scratch_shapes"] = scratch_shapes
        call = pl.pallas_call(
            body,
            out_shape=out_shape if len(out_shape) > 1 else out_shape[0],
            **kwargs)
        return jax.jit(call)

    def launch(self, args: Sequence, ctx=None, grid=None,
               out_shapes: Sequence[Tuple[int, ...]] = (),
               out_dtypes: Sequence = (), in_specs=None, out_specs=None,
               scratch_shapes=()):
        """Run the kernel on NDArray/array args; returns NDArray tuple.

        ``grid`` plays the reference launch config's grid role (block
        shape lives in the BlockSpecs); ``out_shapes`` sizes each
        output (the reference mutated pre-allocated args instead).
        """
        from .ndarray.ndarray import NDArray, invoke
        from .ops.registry import OpDef

        if not out_shapes:
            raise MXNetError("PallasKernel.launch: out_shapes required")
        nds = [a if isinstance(a, NDArray) else NDArray(a) for a in args]
        if ctx is not None:  # reference launch semantics: ctx places it
            nds = [a.as_in_context(ctx) for a in nds]
        arrs = [a._data for a in nds]
        if not out_dtypes:
            out_dtypes = [arrs[0].dtype if arrs else "float32"] \
                * len(out_shapes)
        grid = tuple(grid) if isinstance(grid, (list, tuple)) else grid
        key = (tuple(a.shape for a in arrs),
               tuple(str(a.dtype) for a in arrs),
               tuple(tuple(s) for s in out_shapes),
               tuple(str(d) for d in out_dtypes), grid,
               _specs_key(in_specs), _specs_key(out_specs),
               repr(scratch_shapes))
        op = self._compiled.get(key)
        if op is None:
            fn = self._build([tuple(s) for s in out_shapes],
                             list(out_dtypes), grid, in_specs, out_specs,
                             scratch_shapes)
            fn._mxtpu_no_jit = True  # already jitted above
            # monotonic op names: never collide even across gc'd kernels
            op = OpDef(f"_rtc_{self._name}_{_RTC_SEQ()}", fn, len(arrs),
                       len(out_shapes), (), False, None)
            self._compiled[key] = op
        out = invoke(op, nds, ctx=ctx)
        return out if isinstance(out, (list, tuple)) else (out,)


class PallasModule:
    """A named collection of Pallas kernels (parity: ``CudaModule``)."""

    def __init__(self, kernels: Dict[str, Callable]):
        if not isinstance(kernels, dict) or not kernels:
            raise MXNetError(
                "PallasModule takes {name: kernel_fn}; kernel source "
                "strings are a CUDA/NVRTC concept — on TPU, kernels are "
                "Pallas functions")
        self._kernels = dict(kernels)

    def get_kernel(self, name: str, interpret: Optional[bool] = None,
                   **static_kwargs) -> PallasKernel:
        """Bind static kwargs now; shapes/grid resolve at launch."""
        try:
            fn = self._kernels[name]
        except KeyError:
            raise MXNetError(
                f"kernel {name!r} not in module "
                f"(have {sorted(self._kernels)})") from None
        return PallasKernel(name, fn, static_kwargs, interpret)


class CudaModule:
    """Reference-name shim: CUDA source cannot run on TPU."""

    def __init__(self, *args, **kwargs):
        raise MXNetError(
            "mx.rtc.CudaModule compiles CUDA source via NVRTC and has "
            "no TPU equivalent; author the kernel as a Pallas function "
            "and use mx.rtc.PallasModule (same launch surface)")
