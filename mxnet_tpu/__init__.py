"""mxnet_tpu: a TPU-native deep-learning framework with MXNet's capabilities.

User-facing API mirrors the reference's Python surface (``mx.nd``,
``mx.autograd``, ``mx.gluon``, ``mx.kv``, ``mx.io``, ``mx.metric``,
``mx.optimizer``, ``ctx=mx.tpu()``); internals are idiomatic XLA —
see SURVEY.md §7 for the design stance.

    import mxnet_tpu as mx
    x = mx.nd.ones((2, 3), ctx=mx.tpu())
"""
__version__ = "0.2.0"

import sys as _sys

# deep trace stacks (custom_vjp → jit → pallas_call) exceed CPython's
# default 1000-frame limit; the reference's Python frontend does the same
# for deep graphs
if _sys.getrecursionlimit() < 3000:
    _sys.setrecursionlimit(3000)

import jax as _jax_config_only

# MXNet supports int64/float64 tensors; JAX demotes them unless x64 is
# on.  x64 is OPT-IN (MXTPU_ENABLE_X64=1): on TPU it risks silent f64
# promotion on hot paths where the MXU wants bf16/f32.  Weak-type
# promotion keeps float32 as the working default (MXNet rule) in both
# modes; without x64, f64/i64 requests are demoted to f32/i32.
from . import envs as _envs
if _envs.get("MXTPU_ENABLE_X64"):
    _jax_config_only.config.update("jax_enable_x64", True)

# Join the launcher's multi-process rendezvous NOW, before anything can
# initialize the XLA backend (jax.distributed.initialize refuses after
# that).  tools/launch.py exports MXTPU_DIST_*; single-process runs skip
# this.  kvstore.init_distributed() recognizes the joined state.
import os as _os
if _os.environ.get("MXTPU_DIST_COORDINATOR"):
    _jax_config_only.distributed.initialize(
        coordinator_address=_os.environ["MXTPU_DIST_COORDINATOR"],
        num_processes=int(_os.environ.get("MXTPU_DIST_NUM_PROCS", "1")),
        process_id=int(_os.environ.get("MXTPU_DIST_PROC_ID", "0")))

from .base import MXNetError
from .context import (Context, cpu, gpu, tpu, cpu_pinned, current_context,
                      num_gpus, num_tpus)
from . import engine
from . import ndarray
from . import ndarray as nd
from . import autograd
from . import random
from . import context
from . import initializer
from . import initializer as init
from . import lr_scheduler
from . import optimizer
from . import metric
from . import io
from . import gluon
from . import deploy
from . import visualization
from . import visualization as viz
from . import test_utils
from . import kvstore
from . import kvstore as kv
from . import numpy as np  # noqa: shadow of builtin numpy is the parity point
from . import numpy_extension as npx
from . import parallel
from . import symbol
from . import symbol as sym
from . import module
from . import module as mod
from . import recordio
from . import image
from . import models
from . import profiler
from . import telemetry
from . import monitor
from . import runtime
from . import envs
from . import callback
from . import checkpoint
from . import checkpoint as model  # mx.model.save_checkpoint parity
from . import elastic
from . import serving
from . import operator
from . import contrib
from . import rtc
from . import analysis

# mxsan (docs/static_analysis.md, "The sanitizer"): arm the
# donation-lifetime & lock-order sanitizer when the env opts in.  Off
# (the default) this costs nothing beyond the registry read — the
# engine seams pay one attribute load per dispatch either way.
if int(envs.get("MXTPU_SANITIZE") or 0):
    analysis.sanitizer.configure()

__all__ = ["nd", "ndarray", "autograd", "random", "context", "rtc",
           "cpu", "gpu", "tpu", "cpu_pinned", "current_context",
           "num_gpus", "num_tpus", "Context", "MXNetError", "engine",
           "initializer", "init", "lr_scheduler", "optimizer", "gluon",
           "metric", "io", "test_utils", "kvstore", "kv", "parallel",
           "symbol", "sym", "module", "mod", "recordio", "image",
           "models", "profiler", "telemetry", "monitor", "runtime",
           "envs",
           "callback", "checkpoint", "model", "operator", "contrib",
           "analysis", "elastic", "serving"]
