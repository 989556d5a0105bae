"""Native PJRT dispatch core (src/pjrt_executor.cc — SURVEY.md §7
hard-part 7, VERDICT r2 Missing #2).

Host-side tests always run: the lib must build, load, declare its
symbols, and fail loudly (not crash) on bad plugins.  The execute path
needs real hardware behind a PJRT plugin — covered by the tpu-marked
class (``MXTPU_TEST_ON_TPU=1``, on the machine with the chip)."""
import os

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import pjrt_native
from mxnet_tpu.base import MXNetError


def test_lib_builds_and_loads():
    assert pjrt_native.lib_available(), \
        "libmxtpu_pjrt.so must build (PJRT headers are in the image)"
    L = pjrt_native._load()
    for sym in ("MXTPUPjrtLoad", "MXTPUPjrtCompile", "MXTPUPjrtExecute",
                "MXTPUPjrtBufferFromHost", "MXTPUPjrtBufferToHost",
                "MXTPUPjrtLastError"):
        assert hasattr(L, sym)


def test_bogus_plugin_raises_not_crashes(tmp_path):
    with pytest.raises(MXNetError, match="dlopen|PJRT"):
        pjrt_native.NativeClient(str(tmp_path / "nope.so"))
    # a real .so without GetPjrtApi is rejected with the right message
    lib = str(tmp_path / "empty.so")
    src = str(tmp_path / "empty.c")
    with open(src, "w") as f:
        f.write("int mxtpu_not_pjrt(void) { return 0; }\n")
    import subprocess
    r = subprocess.run(["gcc", "-shared", "-fPIC", "-o", lib, src],
                       capture_output=True)
    if r.returncode == 0:
        with pytest.raises(MXNetError, match="GetPjrtApi"):
            pjrt_native.NativeClient(lib)


def test_plugin_candidates_exist_in_image():
    cands = pjrt_native.plugin_candidates()
    assert any("libtpu" in c for c in cands), cands


@pytest.mark.tpu
class TestOnChip:
    """Real-hardware path: compile StableHLO through the C API and run
    with device-resident buffers, no Python in the dispatch loop."""

    def test_matmul_end_to_end(self):
        import jax.numpy as jnp
        client = pjrt_native.NativeClient()
        assert client.device_count >= 1
        rng = np.random.RandomState(0)
        a = rng.randn(64, 64).astype("float32")
        b = rng.randn(64, 64).astype("float32")
        exe = client.compile_jax(
            lambda x, y: jnp.dot(x, y) + 1.0, (a, b))
        assert exe.num_outputs == 1
        (out,) = exe(a, b)
        # bf16-operand MXU matmul: absolute error scales with the
        # result magnitude, so anchor atol to it
        ref = a @ b + 1.0
        np.testing.assert_allclose(np.asarray(out.to_numpy()), ref,
                                   rtol=2e-2,
                                   atol=2e-2 * np.abs(ref).max())

    def test_device_buffers_chain_without_host_hops(self):
        import jax.numpy as jnp
        client = pjrt_native.NativeClient()
        x = np.ones((32, 32), np.float32)
        exe = client.compile_jax(lambda v: v * 2.0, (x,))
        buf = client.buffer_from_host(x)
        for _ in range(3):           # device->device chaining
            (buf,) = exe(buf)
        np.testing.assert_allclose(buf.to_numpy(), x * 8.0, rtol=1e-5)


class TestAgainstMockPlugin:
    """The full native loop — load, client, compile, host->device,
    execute, device->host, chaining, teardown — through the REAL PJRT
    C ABI structs, no hardware needed."""

    def test_full_loop_echo(self, mock_plugin):
        client = pjrt_native.NativeClient(mock_plugin)
        assert client.platform == "mockpjrt"
        assert client.device_count == 1
        exe = client.compile(b"fake-stablehlo", "mlir", options=b"")
        assert exe.num_outputs == 1
        x = np.arange(24, dtype=np.float32).reshape(2, 3, 4)
        (out,) = exe(x)
        got = out.to_numpy()
        assert got.dtype == np.float32 and got.shape == (2, 3, 4)
        np.testing.assert_array_equal(got, x)
        # device->device chaining: NativeBuffer in, NativeBuffer out
        buf = client.buffer_from_host(x)
        for _ in range(3):
            (buf,) = exe(buf)
        np.testing.assert_array_equal(buf.to_numpy(), x)
        # int dtype round-trip
        xi = np.arange(6, dtype=np.int32)
        (oi,) = exe(xi)
        assert oi.to_numpy().dtype == np.int32
        np.testing.assert_array_equal(oi.to_numpy(), xi)
        # teardown order matters (PJRT contract): every buffer dies
        # before its client — a live NativeBuffer.__del__ after
        # client.close() would free through the dead client
        for b in (out, oi, buf):
            b.close()
        exe.close()
        client.close()

    def test_compile_error_propagates(self, mock_plugin):
        client = pjrt_native.NativeClient(mock_plugin)
        with pytest.raises(MXNetError, match="empty program"):
            client.compile(b"", "mlir", options=b"")
        client.close()


@pytest.mark.tpu
def test_exported_bundle_runs_natively(tmp_path):
    """mx.deploy bundle -> NativeClient.compile -> execute on the
    real chip; output matches the Python forward."""
    from mxnet_tpu.gluon import nn as gnn
    from mxnet_tpu import nd
    net = gnn.Dense(4, in_units=8)
    net.initialize(mx.init.Xavier())
    x = nd.array(np.random.RandomState(0).randn(2, 8)
                 .astype("float32"))
    want = net(x).asnumpy()
    p = str(tmp_path / "m.mxshlo")
    mx.deploy.export_stablehlo(net, [x], p)
    client = pjrt_native.NativeClient()
    exe = client.compile(mx.deploy.read_stablehlo(p), "mlir")
    (out,) = exe(x.asnumpy())
    np.testing.assert_allclose(out.to_numpy(), want, rtol=2e-2,
                               atol=1e-2)
    out.close()
    exe.close()
    client.close()


def test_c_predict_smoke_against_mock(mock_plugin, tmp_path):
    """The COMPLETE Python-free deploy story in CI: a standalone C
    program loads libmxtpu_pjrt.so + a PJRT plugin + an exported
    bundle and runs predict — no interpreter anywhere in that
    process's dispatch path."""
    import subprocess
    from mxnet_tpu.gluon import nn as gnn
    from mxnet_tpu import nd, _native

    # ensure the lib under test is built fresh (this diff may have
    # changed pjrt_executor.cc; a stale .so would lack symbols)
    assert pjrt_native.lib_available()

    net = gnn.Dense(4, in_units=8)
    net.initialize(mx.init.Xavier())
    x = nd.ones((2, 8))
    net(x)
    bundle = str(tmp_path / "m.mxshlo")
    mx.deploy.export_stablehlo(net, [x], bundle)

    exe = str(tmp_path / "predict_smoke")
    src = os.path.join(os.path.dirname(__file__), "c_smoke",
                       "pjrt_predict_smoke.c")
    r = subprocess.run(["gcc", "-O1", "-o", exe, src, "-ldl"],
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr
    res = subprocess.run(
        [exe, _native._PJRT_LIB_PATH, mock_plugin, bundle],
        capture_output=True, text=True, timeout=120)
    assert res.returncode == 0, res.stdout + res.stderr
    assert "C PJRT PREDICT PASSED" in res.stdout
    # the mock's echo executable returns the input: 2x8 f32 = 64 bytes
    assert "output bytes: 64" in res.stdout


def test_header_links_against_library(tmp_path):
    """include/mxtpu/pjrt_c_api.h must match the built library: a C
    program compiled against the prototypes and LINKED (not dlsym'd)
    runs and gets a proper error for a bogus plugin."""
    import subprocess
    from mxnet_tpu import _native
    assert pjrt_native.lib_available()
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    exe = str(tmp_path / "hdr_smoke")
    libdir = os.path.dirname(_native._PJRT_LIB_PATH)
    r = subprocess.run(
        ["gcc", "-O1", "-I" + os.path.join(repo, "include"),
         "-o", exe,
         os.path.join(repo, "tests/c_smoke/pjrt_header_smoke.c"),
         "-L" + libdir, "-lmxtpu_pjrt",
         "-Wl,-rpath," + libdir],
        capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr
    res = subprocess.run([exe], capture_output=True, text=True,
                         timeout=60)
    assert res.returncode == 0, res.stdout + res.stderr
    assert "HEADER SMOKE PASSED" in res.stdout
    assert "dlopen" in res.stdout
