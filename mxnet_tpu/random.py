"""Global RNG state: ``mx.random.seed`` and sampling entry points.

Capability parity: reference ``python/mxnet/random.py`` + the per-device
parallel PRNG (``include/mxnet/random_generator.h``).  A threefry key is
kept per context; each sampling call splits it — the functional analog of
the reference's per-device counter-based generators, with identical
user-visible semantics (``mx.random.seed(s)`` makes runs reproducible,
optionally per-context via ``ctx=``).
"""
from __future__ import annotations

from typing import Optional

import numpy as np

from .context import Context, current_context

__all__ = ["seed", "uniform", "normal", "randn", "randint", "exponential",
           "gamma", "poisson", "multinomial", "shuffle", "bernoulli"]

_keys = {}
_DEFAULT_SEED = 0
# bumped by every `seed()`: a holder of a key drawn from the stream
# (`serving.Server`'s resident base key) compares it to know that the
# stream was reseeded since, at the cost of one int compare
_seed_epoch = 0

# CachedOp tracing hook: while a hybridized graph is being traced, RNG keys
# must be *inputs* to the graph (a constant key would freeze every dropout
# mask).  CachedOp pushes a provider that derives per-request keys from a
# traced base key; `_next_key_nd` consults it first.
import threading as _threading

_key_provider = _threading.local()


def _push_key_provider(fn):
    stack = getattr(_key_provider, "stack", None)
    if stack is None:
        stack = _key_provider.stack = []
    stack.append(fn)


def _pop_key_provider():
    _key_provider.stack.pop()


def _jax():
    import jax
    return jax


_prng_impl_set = False


def _ensure_prng_impl(required=True):
    """Pick the key implementation ONCE, before the first key exists.

    Threefry (jax's default) burns real MXU/VPU time generating dropout
    masks on TPU; the hardware-friendly ``rbg`` generator is the analog
    of the reference's counter-based per-device PRNG
    (``include/mxnet/random_generator.h``) and is what large TPU
    trainers use.  ``MXTPU_PRNG_IMPL`` ∈ {auto, threefry2x32, rbg,
    unsafe_rbg}; auto = rbg on an accelerator backend, threefry on CPU
    (keeps the CPU test suite's sampled values stable).  Keys created
    before and after a flag flip don't mix, hence the once-latch.
    """
    global _prng_impl_set
    if _prng_impl_set:
        return
    from . import envs
    impl = envs.get("MXTPU_PRNG_IMPL")
    jax = _jax()
    if impl == "auto":
        try:
            impl = ("rbg" if jax.default_backend() != "cpu"
                    else "threefry2x32")
        except Exception as e:
            # backend not up yet.  When the caller is about to CREATE
            # a key (required=True), a key born under the default
            # threefry impl would mix with rbg keys after a later
            # successful latch — the exact mixing the once-latch
            # exists to prevent (ADVICE r3) — so raise instead of
            # materializing one.  Key-free callers (seed(ctx=None)
            # just stores an int) pass required=False and defer.
            if not required:
                return
            from .base import MXNetError
            raise MXNetError(
                "cannot pick MXTPU_PRNG_IMPL=auto before a jax "
                "backend is initialized; initialize the backend (any "
                "device op) or set MXTPU_PRNG_IMPL explicitly") from e
    if impl not in ("rbg", "unsafe_rbg", "threefry2x32"):
        raise ValueError(
            f"MXTPU_PRNG_IMPL={impl!r}: expected auto, threefry2x32, "
            "rbg, or unsafe_rbg")
    jax.config.update("jax_default_prng_impl", impl)
    _prng_impl_set = True


def seed(seed_state: int, ctx: Optional[Context] = None):
    """Reset the RNG. ``ctx=None`` reseeds every context (parity: 'all')."""
    global _keys, _seed_epoch
    _seed_epoch += 1
    # the all-contexts path stores only an int — no key is created, so
    # a not-yet-initialized backend must not make seed-at-startup fail
    _ensure_prng_impl(required=ctx is not None and ctx != "all")
    if ctx is None or ctx == "all":
        _keys = {"__seed__": int(seed_state)}
    else:
        _keys[Context(ctx.device_type, ctx.device_id)] = \
            _jax().random.key(int(seed_state))


def _next_key(ctx: Context):
    jax = _jax()
    _ensure_prng_impl()
    base_seed = _keys.get("__seed__", _DEFAULT_SEED)
    k = _keys.get(ctx)
    if k is None:
        # derive per-context stream: fold device id into the seed
        k = jax.random.fold_in(jax.random.key(base_seed),
                               ctx.device_id + 997 * ctx.device_typeid)
    k, sub = jax.random.split(k)
    _keys[ctx] = k
    return sub


def _next_key_nd(ctx: Context):
    """Key as a raw-data NDArray on ctx (ops re-wrap via wrap_key_data)."""
    from .ndarray.ndarray import NDArray
    stack = getattr(_key_provider, "stack", None)
    if stack:
        return stack[-1](ctx)
    jax = _jax()
    sub = _next_key(ctx)
    raw = jax.random.key_data(sub)
    return NDArray(jax.device_put(raw, ctx.device), ctx=ctx)


def _sample(opname, ctx, out, shape, dtype, extra_inputs=(), **attrs):
    from .ndarray.ndarray import invoke
    from .ops.registry import get_op
    if out is not None:
        ctx = out.context
        shape = shape if shape is not None else out.shape
        dtype = dtype or out.dtype.name
    ctx = ctx or current_context()
    shape = () if shape is None else (
        (shape,) if isinstance(shape, (int, np.integer)) else tuple(shape))
    key = _next_key_nd(ctx)
    return invoke(get_op(opname), [key, *extra_inputs], out=out,
                  shape=shape, dtype=np.dtype(dtype or "float32").name,
                  **attrs)


def uniform(low=0.0, high=1.0, shape=None, dtype="float32", ctx=None,
            out=None):
    return _sample("_random_uniform", ctx, out, shape, dtype,
                   low=low, high=high)


def normal(loc=0.0, scale=1.0, shape=None, dtype="float32", ctx=None,
           out=None):
    return _sample("_random_normal", ctx, out, shape, dtype,
                   loc=loc, scale=scale)


def randn(*shape, dtype="float32", ctx=None):
    return normal(0.0, 1.0, shape=shape, dtype=dtype, ctx=ctx)


def randint(low, high, shape=None, dtype="int32", ctx=None, out=None):
    from .ndarray.ndarray import invoke
    from .ops.registry import get_op
    if int(high) > 2**31 - 1 or int(low) < -2**31:
        import jax
        if not jax.config.jax_enable_x64:
            from .base import MXNetError
            raise MXNetError(
                f"randint bounds [{low}, {high}) need 64-bit integers; "
                "set MXTPU_ENABLE_X64=1 to enable int64 tensors")
    ctx = (out.context if out is not None else ctx) or current_context()
    shp = () if shape is None else (
        (shape,) if isinstance(shape, (int, np.integer)) else tuple(shape))
    key = _next_key_nd(ctx)
    return invoke(get_op("_random_randint"), [key], out=out, low=int(low),
                  high=int(high), shape=shp, dtype=np.dtype(dtype).name)


def exponential(scale=1.0, shape=None, dtype="float32", ctx=None, out=None):
    return _sample("_random_exponential", ctx, out, shape, dtype,
                   lam=1.0 / scale)


def gamma(alpha=1.0, beta=1.0, shape=None, dtype="float32", ctx=None,
          out=None):
    return _sample("_random_gamma", ctx, out, shape, dtype,
                   alpha=alpha, beta=beta)


def poisson(lam=1.0, shape=None, dtype="float32", ctx=None, out=None):
    return _sample("_random_poisson", ctx, out, shape, dtype, lam=lam)


def bernoulli(prob=0.5, shape=None, dtype="float32", ctx=None, out=None):
    return _sample("_random_bernoulli", ctx, out, shape, dtype, prob=prob)


def multinomial(data, shape=(), get_prob=False, dtype="int32"):
    from .ndarray.ndarray import invoke
    from .ops.registry import get_op
    ctx = data.context
    key = _next_key_nd(ctx)
    shp = (shape,) if isinstance(shape, (int, np.integer)) else tuple(shape)
    return invoke(get_op("_sample_multinomial"), [key, data], shape=shp,
                  get_prob=get_prob, dtype=np.dtype(dtype).name)


def shuffle(data, out=None):
    from .ndarray.ndarray import invoke
    from .ops.registry import get_op
    key = _next_key_nd(data.context)
    return invoke(get_op("_shuffle"), [key, data], out=out)
