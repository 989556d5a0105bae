#!/usr/bin/env python
"""Environment diagnostics (parity: reference tools/diagnose.py —
SURVEY.md §2.6 "Tools"): prints platform, package versions, feature
flags, device inventory, and native-runtime status, for bug reports.

Usage: python tools/diagnose.py
"""
from __future__ import annotations

import os
import platform
import sys


def main():
    print("----------Python Info----------")
    print("Version      :", platform.python_version())
    print("Compiler     :", platform.python_compiler())
    print("Arch         :", platform.machine())

    print("----------System Info----------")
    print("Platform     :", platform.platform())
    print("system       :", platform.system())
    print("release      :", platform.release())

    print("----------Package Info----------")
    for mod in ("numpy", "jax", "jaxlib", "flax", "optax", "orbax"):
        try:
            m = __import__(mod)
            print(f"{mod:<13}: {getattr(m, '__version__', '?')}")
        except ImportError:
            print(f"{mod:<13}: not installed")

    # everything touching jax/mxnet_tpu below runs in SUBPROCESSES with
    # a deadline, one after the other (a chip belongs to one process at
    # a time): a backend that does not come up must not hang the
    # diagnostic tool itself, and this process never touches jax
    import subprocess

    def probe(title, code, timeout=60):
        print(f"----------{title}----------")
        sys.stdout.flush()
        try:
            out = subprocess.run([sys.executable, "-c", code],
                                 capture_output=True, text=True,
                                 timeout=timeout)
            sys.stdout.write(out.stdout)
            if out.returncode != 0:
                print(f"{title} probe failed:",
                      out.stderr.strip()[-300:])
        except subprocess.TimeoutExpired:
            print(f"{title} probe TIMED OUT after {timeout}s "
                  "(wedged/contended PJRT plugin?)")

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    prelude = (
        "import sys\n"
        f"sys.path.insert(0, {repo!r})\n")

    probe("mxnet_tpu Info", prelude + (
        "import mxnet_tpu as mx\n"
        "print('version      :', mx.__version__)\n"
        "feats = mx.runtime.Features()\n"
        "enabled = sorted(str(f) for f in feats if feats.is_enabled(\n"
        "    getattr(f, 'name', str(f))))\n"
        "print('features     :', ', '.join(enabled) or '-')\n"
        "from mxnet_tpu import _native\n"
        "print('native lib   :', 'built' if _native.available() else\n"
        "      'NOT built (pure-Python fallbacks active)')\n"
        "from mxnet_tpu.engine import pipeline\n"
        "print('native IO    :', 'active' if"
        " pipeline.native_io_active() else 'off')\n"
        "print('native image :', 'built' if _native.image_available()"
        " else 'NOT built (no OpenCV dev headers)')\n"
        "from mxnet_tpu import pjrt_native\n"
        "print('pjrt core    :', ('built; plugins: ' + "
        "(', '.join(pjrt_native.plugin_candidates()) or 'none found'))"
        " if pjrt_native.lib_available() else 'NOT built')\n"),
        timeout=120)

    probe("Device Info", prelude + (
        "import jax\n"
        "print('backend      :', jax.default_backend())\n"
        "for d in jax.local_devices():\n"
        "    ver = getattr(d.client, 'platform_version', '')\n"
        "    print('device       :', d, '(', d.platform, ';',\n"
        "          ver.splitlines()[0] if ver else '?', ')')\n"
        "print('process      :', jax.process_index(), '/',"
        " jax.process_count())\n"))

    print("----------Environment----------")
    for k in sorted(os.environ):
        if k.startswith(("MXTPU_", "MXNET_", "JAX_", "XLA_")):
            print(f"{k}={os.environ[k]}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
