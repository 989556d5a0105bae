"""Native PJRT dispatch core — Python handle layer.

``src/pjrt_executor.cc`` is the C++ core (SURVEY.md §7 hard-part 7,
VERDICT r2 Missing #2): it dlopens a PJRT plugin, compiles serialized
StableHLO, and executes with device-resident buffers — no interpreter
in the dispatch loop.  This module is deliberately thin: Python only
LOWERS programs (via jax, once per model) and moves handles; compile
and every subsequent execute/buffer operation happen natively.

Typical deploy loop::

    client = NativeClient()               # loads libtpu
    exe = client.compile_jax(fn, example_args)
    dev_args = [client.buffer_from_host(a) for a in arrays]
    outs = exe(*dev_args)                 # device buffers in/out
    result = outs[0].to_numpy()

The plugin talks to real TPU hardware; on a chip-less host
``NativeClient`` raises (tests gate on the ``tpu`` marker).
"""
from __future__ import annotations

import ctypes
import os
from typing import List, Optional, Sequence

import numpy as np

from .base import MXNetError

__all__ = ["NativeClient", "NativeExecutable", "NativeBuffer",
           "plugin_candidates", "lib_available"]

from ._native import _PJRT_LIB_PATH as _LIB_PATH

_lib = None

# PJRT_Buffer_Type enum (pjrt_c_api.h)
_DTYPES = {
    np.dtype(np.bool_): 1, np.dtype(np.int8): 2, np.dtype(np.int16): 3,
    np.dtype(np.int32): 4, np.dtype(np.int64): 5, np.dtype(np.uint8): 6,
    np.dtype(np.uint16): 7, np.dtype(np.uint32): 8,
    np.dtype(np.uint64): 9, np.dtype(np.float16): 10,
    np.dtype(np.float32): 11, np.dtype(np.float64): 12,
}
_DTYPES_BACK = {v: k for k, v in _DTYPES.items()}
_BF16 = 13  # jax ml_dtypes bfloat16 maps here


def plugin_candidates() -> List[str]:
    """Where PJRT plugins live in this environment, best first."""
    cands = []
    env = os.environ.get("MXTPU_PJRT_PLUGIN")
    if env:
        cands.append(env)
    try:
        import libtpu
        cands.append(os.path.join(os.path.dirname(libtpu.__file__),
                                  "libtpu.so"))
    except ImportError:
        pass
    return [c for c in cands if os.path.exists(c)]


def _load():
    global _lib
    if _lib is None:
        from . import _native
        _native.available()     # triggers the make that builds us too
        if not os.path.exists(_LIB_PATH):
            raise MXNetError("libmxtpu_pjrt.so not built (PJRT C API "
                             "headers absent at build time?)")
        L = ctypes.CDLL(_LIB_PATH)
        L.MXTPUPjrtLastError.restype = ctypes.c_char_p
        L.MXTPUPjrtLoad.restype = ctypes.c_void_p
        L.MXTPUPjrtLoad.argtypes = [ctypes.c_char_p]
        L.MXTPUPjrtDeviceCount.argtypes = [ctypes.c_void_p]
        L.MXTPUPjrtPlatformName.argtypes = [ctypes.c_void_p,
                                            ctypes.c_char_p, ctypes.c_int]
        L.MXTPUPjrtFree.argtypes = [ctypes.c_void_p]
        L.MXTPUPjrtCompile.restype = ctypes.c_void_p
        L.MXTPUPjrtCompile.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int64,
            ctypes.c_char_p, ctypes.c_char_p, ctypes.c_int64]
        L.MXTPUPjrtExecNumOutputs.argtypes = [ctypes.c_void_p]
        L.MXTPUPjrtExecFree.argtypes = [ctypes.c_void_p]
        L.MXTPUPjrtBufferFromHost.restype = ctypes.c_void_p
        L.MXTPUPjrtBufferFromHost.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int,
            ctypes.POINTER(ctypes.c_int64), ctypes.c_int, ctypes.c_int]
        L.MXTPUPjrtBufferFree.argtypes = [ctypes.c_void_p]
        L.MXTPUPjrtBufferType.argtypes = [ctypes.c_void_p]
        L.MXTPUPjrtBufferDims.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_int64), ctypes.c_int]
        L.MXTPUPjrtBufferToHost.restype = ctypes.c_int64
        L.MXTPUPjrtBufferToHost.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64]
        L.MXTPUPjrtExecute.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_void_p),
            ctypes.c_int, ctypes.POINTER(ctypes.c_void_p), ctypes.c_int]
        _lib = L
    return _lib


def lib_available() -> bool:
    try:
        return _load() is not None
    except MXNetError:
        return False


def _err(L) -> str:
    return L.MXTPUPjrtLastError().decode("utf-8", "replace")


class NativeBuffer:
    """A device-resident PJRT buffer handle.

    Lifetime contract (standard PJRT): close every buffer and
    executable BEFORE closing the client that produced them."""

    def __init__(self, client, handle):
        self._client = client
        self._h = handle

    def to_numpy(self) -> np.ndarray:
        L = self._client._L
        dims = (ctypes.c_int64 * 16)()
        nd_ = L.MXTPUPjrtBufferDims(self._h, dims, 16)
        if nd_ < 0:
            raise MXNetError("BufferDims: " + _err(L))
        t = L.MXTPUPjrtBufferType(self._h)
        if t == _BF16:
            import ml_dtypes
            dt = np.dtype(ml_dtypes.bfloat16)
        elif t in _DTYPES_BACK:
            dt = _DTYPES_BACK[t]
        else:
            raise MXNetError(f"unsupported output dtype enum {t}")
        shape = tuple(dims[i] for i in range(nd_))
        out = np.empty(shape, dt)
        got = L.MXTPUPjrtBufferToHost(
            self._h, out.ctypes.data_as(ctypes.c_void_p), out.nbytes)
        if got < 0:
            raise MXNetError("BufferToHost: " + _err(L))
        return out

    def close(self):
        if self._h:
            self._client._L.MXTPUPjrtBufferFree(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


class NativeExecutable:
    """A compiled program; __call__ runs entirely in native code."""

    def __init__(self, client, handle):
        self._client = client
        self._h = handle
        self.num_outputs = client._L.MXTPUPjrtExecNumOutputs(handle)

    def __call__(self, *args) -> List[NativeBuffer]:
        L = self._client._L
        bufs = []
        tmp: List[NativeBuffer] = []
        try:
            for a in args:
                if isinstance(a, NativeBuffer):
                    bufs.append(a._h)
                else:
                    b = self._client.buffer_from_host(np.asarray(a))
                    tmp.append(b)
                    bufs.append(b._h)
            argv = (ctypes.c_void_p * len(bufs))(*bufs)
            outv = (ctypes.c_void_p * max(self.num_outputs, 1))()
            n = L.MXTPUPjrtExecute(self._h, argv, len(bufs), outv,
                                   max(self.num_outputs, 1))
            if n < 0:
                raise MXNetError("Execute: " + _err(L))
            return [NativeBuffer(self._client, outv[i])
                    for i in range(n)]
        finally:
            for b in tmp:
                b.close()

    def close(self):
        if self._h:
            self._client._L.MXTPUPjrtExecFree(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


class NativeClient:
    """A PJRT client created through the C API — no Python runtime in
    the dispatch path after construction."""

    def __init__(self, plugin_path: Optional[str] = None):
        self._L = _load()
        cands = [plugin_path] if plugin_path else plugin_candidates()
        if not cands:
            raise MXNetError("no PJRT plugin found (set "
                             "MXTPU_PJRT_PLUGIN)")
        last = "no candidates tried"
        self._h = None
        for c in cands:
            h = self._L.MXTPUPjrtLoad(c.encode())
            if h:
                self._h = h
                self.plugin_path = c
                break
            last = f"{c}: {_err(self._L)}"
        if self._h is None:
            raise MXNetError(f"PJRT client creation failed ({last})")

    @property
    def device_count(self) -> int:
        return self._L.MXTPUPjrtDeviceCount(self._h)

    @property
    def platform(self) -> str:
        buf = ctypes.create_string_buffer(64)
        n = self._L.MXTPUPjrtPlatformName(self._h, buf, 64)
        return buf.value.decode() if n >= 0 else "unknown"

    def compile(self, code: bytes, fmt: str = "mlir",
                options: Optional[bytes] = None) -> NativeExecutable:
        if options is None:
            from jaxlib.xla_client import CompileOptions
            options = CompileOptions().SerializeAsString()
        h = self._L.MXTPUPjrtCompile(self._h, code, len(code),
                                     fmt.encode(), options,
                                     len(options))
        if not h:
            raise MXNetError("Compile: " + _err(self._L))
        return NativeExecutable(self, h)

    def compile_jax(self, fn, example_args: Sequence) -> NativeExecutable:
        """Lower a jittable fn with jax (trace once, host-side), then
        compile + run it natively."""
        import jax
        from jax.interpreters import mlir as jmlir
        lowered = jax.jit(fn).lower(*example_args)
        module = lowered.compiler_ir(dialect="stablehlo")
        return self.compile(jmlir.module_to_bytecode(module), "mlir")

    def buffer_from_host(self, arr: np.ndarray,
                         device_index: int = 0) -> NativeBuffer:
        arr = np.ascontiguousarray(arr)
        dt = _DTYPES.get(arr.dtype)
        if dt is None:
            import ml_dtypes
            if arr.dtype == np.dtype(ml_dtypes.bfloat16):
                dt = _BF16
            else:
                raise MXNetError(f"unsupported dtype {arr.dtype}")
        dims = (ctypes.c_int64 * max(arr.ndim, 1))(*arr.shape)
        h = self._L.MXTPUPjrtBufferFromHost(
            self._h, arr.ctypes.data_as(ctypes.c_void_p), dt, dims,
            arr.ndim, device_index)
        if not h:
            raise MXNetError("BufferFromHost: " + _err(self._L))
        return NativeBuffer(self, h)

    def close(self):
        if self._h:
            self._L.MXTPUPjrtFree(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass
