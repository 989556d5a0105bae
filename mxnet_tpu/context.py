"""Device contexts: ``mx.cpu()``, ``mx.tpu()`` (and the ``mx.gpu()`` stub).

Capability parity: reference ``python/mxnet/context.py`` (``Context``,
``mx.cpu()/mx.gpu(i)``, ``current_context``, ``num_gpus``).  The rebuild's
central extension point per SURVEY.md §2.5: ``mx.tpu(i)`` maps to a PJRT TPU
device; ``mx.cpu(i)`` maps to an XLA host device (with
``--xla_force_host_platform_device_count`` several exist, which is how
multi-device logic is tested without a pod).
"""
from __future__ import annotations

import threading
from typing import Optional

from .base import MXNetError

__all__ = [
    "Context", "cpu", "gpu", "tpu", "cpu_pinned", "current_context",
    "num_gpus", "num_tpus",
]


def _jax():
    import jax  # deferred so importing mxnet_tpu stays cheap
    return jax


class Context:
    """A device context.  Compared by (device_type, device_id).

    Unlike the reference there is no stream/engine state held here; the
    context resolves to a ``jax.Device`` and placement is delegated to PJRT.
    """

    # device-type codes follow the reference's numbering where it exists
    devtype2str = {1: "cpu", 2: "gpu", 3: "cpu_pinned", 5: "tpu"}
    devstr2type = {v: k for k, v in devtype2str.items()}
    _default_ctx = threading.local()

    def __init__(self, device_type: str, device_id: int = 0):
        if isinstance(device_type, Context):
            self.device_type, self.device_id = (
                device_type.device_type, device_type.device_id)
        else:
            if device_type not in self.devstr2type:
                raise MXNetError(f"unknown device type {device_type!r}")
            self.device_type = device_type
            self.device_id = int(device_id)
        self._device = None

    # -- identity ---------------------------------------------------------
    @property
    def device_typeid(self) -> int:
        return self.devstr2type[self.device_type]

    def __eq__(self, other):
        return (isinstance(other, Context)
                and self.device_type == other.device_type
                and self.device_id == other.device_id)

    def __hash__(self):
        return hash((self.device_type, self.device_id))

    def __repr__(self):
        return f"{self.device_type}({self.device_id})"

    __str__ = __repr__

    # -- resolution to a PJRT device -------------------------------------
    @property
    def device(self):
        """The underlying ``jax.Device``. Resolved lazily and cached."""
        if self._device is None:
            jax = _jax()
            # local_devices, not devices: in multi-process SPMD the
            # global list contains other workers' (non-addressable)
            # devices; ctx ids are per-worker-local like mx.gpu(i)
            if self.device_type == "gpu":
                raise MXNetError(
                    "This build targets TPU; mx.gpu() is not available "
                    "(feature flag GPU=off, see mx.runtime.Features).")
            backend = "tpu" if self.device_type == "tpu" else "cpu"
            try:
                devs = jax.local_devices(backend=backend)
            except RuntimeError as e:
                raise MXNetError(
                    f"context {self}: no {backend} backend is "
                    f"available to jax: {e}") from e
            if self.device_id >= len(devs):
                raise MXNetError(
                    f"context {self} out of range: only {len(devs)} "
                    f"{self.device_type} device(s) present")
            self._device = devs[self.device_id]
        return self._device

    # -- default-context scope (`with mx.tpu(0):`) ------------------------
    def __enter__(self):
        if not hasattr(Context._default_ctx, "stack"):
            Context._default_ctx.stack = []
        Context._default_ctx.stack.append(self)
        return self

    def __exit__(self, *exc):
        Context._default_ctx.stack.pop()

    def empty_cache(self):
        """Parity no-op: XLA owns the device allocator."""


def cpu(device_id: int = 0) -> Context:
    return Context("cpu", device_id)


def cpu_pinned(device_id: int = 0) -> Context:
    return Context("cpu_pinned", device_id)


def gpu(device_id: int = 0) -> Context:
    return Context("gpu", device_id)


def tpu(device_id: int = 0) -> Context:
    return Context("tpu", device_id)


def current_context() -> Context:
    stack = getattr(Context._default_ctx, "stack", None)
    if stack:
        return stack[-1]
    return Context("cpu", 0)


def num_gpus() -> int:
    return 0


def num_tpus() -> int:
    try:
        return len(_jax().local_devices(backend="tpu"))
    except RuntimeError:
        return 0
