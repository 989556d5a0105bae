"""Test harness configuration.

Per SURVEY.md §4 (rebuild test plan): tests run on the CPU backend with 8
virtual XLA host devices, so multi-device/collective logic is exercised
without TPU hardware; a `tpu` marker gates tests that want the real chip.
The env vars MUST be set before jax is first imported.
"""
import os

# tests force the CPU backend unless explicitly opted onto the chip with
# MXTPU_TEST_ON_TPU=1
if not os.environ.get("MXTPU_TEST_ON_TPU"):
    os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import numpy as np
import pytest


@pytest.fixture(autouse=True)
def _seeded():
    """Parity with the reference's @with_seed(): deterministic per test."""
    import mxnet_tpu as mx
    np.random.seed(0)
    mx.random.seed(0)
    yield


def needs_devices(n=8):
    """Runtime skip for tests that build an n-device mesh — the
    on-chip tier (MXTPU_TEST_ON_TPU=1) runs on ONE real chip, where
    the CPU-virtual-mesh tests must skip rather than fail.  Mixed
    modules call this inside individual tests; all-mesh modules use
    ``pytestmark = pytest.mark.needs_mesh`` instead."""
    import jax
    have = len(jax.devices())
    if have < n:
        pytest.skip(f"needs {n} devices (have {have})")


def pytest_configure(config):
    config.addinivalue_line("markers", "tpu: needs the real TPU chip")
    config.addinivalue_line("markers", "slow: long-running")
    config.addinivalue_line(
        "markers",
        "needs_mesh(n=8): whole module/test needs an n-device mesh — "
        "auto-skipped on backends with fewer devices")
    config.addinivalue_line(
        "markers",
        "time_limit(seconds): the test FAILS once it has run this long, "
        "instead of holding up the run")


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_call(item):
    """``@pytest.mark.time_limit(s)``: an interval timer raises in the
    test's (main) thread; what the test holds is released by its own
    ``finally`` blocks and fixtures."""
    import signal
    marker = item.get_closest_marker("time_limit")
    if marker is None:
        yield
        return
    seconds = float(marker.args[0])

    def on_alarm(_signum, _frame):
        raise TimeoutError(f"{item.nodeid} ran past its {seconds:g} s limit")

    previous = signal.signal(signal.SIGALRM, on_alarm)
    signal.setitimer(signal.ITIMER_REAL, seconds)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, previous)


def pytest_collection_modifyitems(config, items):
    on_tpu = bool(os.environ.get("MXTPU_TEST_ON_TPU"))
    if not on_tpu:
        skip_tpu = pytest.mark.skip(
            reason="needs real TPU (set MXTPU_TEST_ON_TPU=1)")
        for item in items:
            if "tpu" in item.keywords:
                item.add_marker(skip_tpu)
    # needs_mesh gating runs in BOTH tiers (the CPU tier always has 8
    # virtual devices, so it only ever bites on-chip); device count is
    # read lazily so collection without any mesh-marked test never
    # initializes a backend
    marked = [it for it in items if "needs_mesh" in it.keywords]
    if marked:
        import jax
        have = len(jax.devices())
        for item in marked:
            m = item.get_closest_marker("needs_mesh")
            n = m.args[0] if m.args else m.kwargs.get("n", 8)
            if have < n:
                item.add_marker(pytest.mark.skip(
                    reason=f"needs {n}-device mesh (have {have})"))


def pjrt_include_dir():
    """The vendored PJRT C API headers, shared with tools/amalgamate."""
    import importlib.util
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "mxtpu_amalgamate", os.path.join(repo, "tools", "amalgamate.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.pjrt_include_dir()


@pytest.fixture(scope="session")
def mock_plugin(tmp_path_factory):
    """Build the in-memory mock PJRT plugin (echo executable)."""
    import subprocess
    inc = pjrt_include_dir()
    if not inc:
        pytest.skip("PJRT headers not present")
    out = str(tmp_path_factory.mktemp("mockpjrt") / "mock_pjrt.so")
    src = os.path.join(os.path.dirname(__file__), "c_smoke",
                       "mock_pjrt_plugin.cc")
    r = subprocess.run(
        ["g++", "-O1", "-std=c++17", "-fPIC", "-shared",
         "-I" + inc + "/tensorflow/compiler", "-o", out, src],
        capture_output=True, text=True, timeout=240)
    if r.returncode != 0:
        pytest.fail("mock plugin build failed:\n" + r.stderr[-2000:])
    return out


def compile_and_run_c(sources, exe_path, compiler="gcc",
                      extra_flags=(), timeout=300, run_args=()):
    """Shared scaffold for standalone C/C++ programs linked against
    libmxtpu.so (used by test_c_api.py and test_cpp_package.py): builds
    with the repo include dirs + rpath, runs with the embedded
    interpreter's PYTHONPATH, returns CompletedProcess."""
    import subprocess
    import sys as _sys
    import numpy as _np
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    cmd = [compiler, "-O1", "-Wall",
           "-I", os.path.join(repo, "include"),
           "-I", os.path.join(repo, "cpp-package", "include"),
           *extra_flags, "-o", exe_path, *sources,
           "-L", os.path.join(repo, "mxnet_tpu", "lib"), "-lmxtpu",
           f"-Wl,-rpath,{os.path.join(repo, 'mxnet_tpu/lib')}"]
    subprocess.run(cmd, check=True)
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    site = os.path.dirname(os.path.dirname(_np.__file__))
    env["PYTHONPATH"] = os.pathsep.join([repo, site] + _sys.path[1:])
    return subprocess.run([exe_path, *run_args], env=env,
                          capture_output=True, text=True, timeout=timeout)
