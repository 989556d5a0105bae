"""Runtime feature detection (parity: ``python/mxnet/runtime.py`` over
``src/libinfo.cc`` — SURVEY.md §5 "Config / flag system").

``Features()`` reports this build's capability matrix with the
reference's feature names (CUDA off, TPU/PJRT/PALLAS on, ...), so
feature-gated user code ports unchanged.
"""
from __future__ import annotations

from collections import OrderedDict

__all__ = ["Feature", "Features", "feature_list"]


class Feature:
    def __init__(self, name, enabled):
        self.name = name
        self.enabled = enabled

    def __repr__(self):
        return f"[{'✔' if self.enabled else '✖'} {self.name}]"


def _detect():
    feats = OrderedDict()

    def add(name, enabled):
        feats[name] = Feature(name, bool(enabled))

    try:
        import jax
        has_jax = True
    except ImportError:
        has_jax = False
    add("TPU", has_jax and jax.default_backend() == "tpu")
    add("PJRT", has_jax)
    add("PALLAS", has_jax)
    add("DIST", has_jax)
    add("DIST_KVSTORE", True)
    add("INT64_TENSOR_SIZE", True)
    add("F16C", True)           # bf16/fp16 compute via XLA
    add("OPENCV", _has("cv2"))
    add("ORBAX", _has("orbax.checkpoint"))
    # reference features that are off in the TPU build — recorded
    # explicitly so `is_enabled('CUDA')` answers honestly
    for off in ("CUDA", "CUDNN", "NCCL", "CUDA_RTC", "TENSORRT",
                "MKLDNN", "OPENMP", "SSE", "CAFFE", "PROFILER_NVTX"):
        add(off, False)
    add("SIGNAL_HANDLER", True)
    add("PROFILER", True)
    return feats


def _has(mod):
    try:
        __import__(mod)
        return True
    except ImportError:
        return False


class Features(OrderedDict):
    """Check with ``mx.runtime.Features().is_enabled('TPU')``."""

    instance = None

    def __new__(cls):
        if cls.instance is None:
            cls.instance = super().__new__(cls)
            OrderedDict.__init__(cls.instance, _detect())
        return cls.instance

    def __init__(self):
        pass

    def __repr__(self):
        return str(list(self.values()))

    def is_enabled(self, feature_name: str) -> bool:
        feature_name = feature_name.upper()
        if feature_name not in self:
            raise RuntimeError(f"Feature {feature_name!r} does not exist")
        return self[feature_name].enabled


def feature_list():
    return list(Features().values())
