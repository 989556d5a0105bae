"""Where this checkout's entry points keep compiled programs.

``chip_smoke.py``, ``chipbench/run.py`` and the ``example/`` scripts
they stand for call :func:`place` BEFORE importing jax.  The rule is one line: a
``JAX_COMPILATION_CACHE_DIR`` set from outside wins and nothing here
sets another; unset, the cache is the fixed ``<checkout>/.jax_cache``
(git-ignored).  The path is part of a cache entry's key, so a
directory made from a pid, a time or ``mkdtemp`` never hits.

Imports nothing but ``os``/``shutil`` — importing ``mxnet_tpu`` would
import jax first.
"""
import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def place() -> str:
    """Point jax's persistent compilation cache at the directory the
    rule above gives, and return it.  Call before ``import jax``."""
    return os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                                 os.path.join(REPO, ".jax_cache"))


def fresh_subdir(name: str) -> str:
    """A fixed, emptied sub-directory of the cache directory — for a
    phase that measures cold-then-warm through the repo's own second
    tier (``MXTPU_COMPILE_CACHE_DIR``) and so must start cold."""
    d = os.path.join(place(), name)
    shutil.rmtree(d, ignore_errors=True)
    os.makedirs(d)
    return d
