"""Base utilities: errors, type helpers, env-flag registry access.

Capability parity: reference ``python/mxnet/base.py`` (ctypes plumbing,
``MXNetError``, ``check_call``).  There is no C ABI boundary on the hot path
here — dispatch goes straight to PJRT through JAX — so this module only keeps
the user-visible pieces: the exception type and small shared helpers.
"""
from __future__ import annotations

import numpy as np

__all__ = ["MXNetError", "numeric_types", "string_types", "integer_types",
           "on_accelerator"]


class MXNetError(RuntimeError):
    """Error raised by the framework (parity with mxnet.base.MXNetError)."""


def on_accelerator() -> bool:
    """True when jax's default backend is a TPU."""
    import jax
    return jax.default_backend() == "tpu"


numeric_types = (float, int, np.generic)
integer_types = (int, np.integer)
string_types = (str,)


def _as_list(obj):
    """Return obj as a list: lists/tuples pass through, scalars wrap."""
    if isinstance(obj, (list, tuple)):
        return list(obj)
    return [obj]
