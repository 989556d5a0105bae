"""``chip_smoke.py`` rehearsed on the CPU, and the no-fallback rules it
stands on (ISSUE 24): the script's phases pass at ``--tiny`` shapes and
the run then FAILS on the platform check; a context whose backend is
absent raises; the compile cache goes where the environment says; the
launcher does not start two workers that would share a chip."""
import json
import os
import subprocess
import sys

import pytest

import mxnet_tpu as mx
from mxnet_tpu import MXNetError

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)
from tools import jax_cache, launch


@pytest.fixture
def smoke(monkeypatch, tmp_path):
    """The script as a module.  Importing it places the cache directory
    in ``os.environ`` — right for an entry point, wrong to leak into the
    children later tests of this worker start — so the variable it
    would set is set here, where monkeypatch restores it."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    import chip_smoke
    return chip_smoke


def _rows(capsys):
    out = capsys.readouterr().out
    assert '"ok"' not in out
    return [json.loads(line) for line in out.splitlines()
            if line.startswith("{")]


def test_tiny_train_passes_then_fails_platform_check(smoke, capsys):
    with pytest.raises(smoke.SmokeFailure, match="not tpu"):
        smoke.main(["--tiny", "--phase", "train"])
    rows = {r["phase"]: r for r in _rows(capsys)}
    train = rows["train"]            # printed only after its checks
    assert train["dispatches_per_warm_step"] == 1
    assert train["aot_demotions"] == 0
    assert train["losses"][-1] < train["losses"][0]
    assert train["first_loss_rel_diff"] <= train["rel_tolerance"]
    assert "done" in rows


def test_tiny_serve_passes_then_fails_platform_check(smoke, capsys,
                                                     monkeypatch):
    # the CPU has no Mosaic: the kernel phase is ASKED into interpret
    # mode here, which a TPU run never slides into
    from mxnet_tpu.ops import flash_attention as fa
    monkeypatch.setattr(fa, "_INTERPRET", True)
    with pytest.raises(smoke.SmokeFailure, match="not tpu"):
        smoke.main(["--tiny", "--phase", "serve"])
    rows = _rows(capsys)
    served = [r for r in rows if r.get("step") == "requests"][0]
    assert served["requests"] == 8
    assert served["post_warm_fresh_compiles"] == 0
    assert served["greedy_worst_regret_share"] <= served["gap_share"]
    assert served["second_tier_dir"].startswith(
        os.environ["JAX_COMPILATION_CACHE_DIR"])
    flash = [r for r in rows if r["phase"] == "flash"]
    assert flash and all(r["policy_routed_to_kernel"] for r in flash)
    assert rows[-1]["phase"] == "done"


def test_tiny_four_chips_passes_then_fails_platform_check(smoke, capsys):
    """``--chips 4`` on four of conftest's virtual devices: dp=4 and
    ZeRO-2 agree with the one-device step, and nothing else runs."""
    with pytest.raises(smoke.SmokeFailure, match="not tpu"):
        smoke.main(["--tiny", "--chips", "4"])
    rows = _rows(capsys)
    assert [r["phase"] for r in rows] == ["start", "dp", "dp", "dp",
                                          "done"]
    one, dense, zero2 = rows[1:4]
    assert (one["dp"], dense["dp"], zero2["dp"]) == (1, 4, 4)
    assert (dense["zero_stage"], zero2["zero_stage"]) == (0, 2)
    for row in (dense, zero2):
        assert row["loss_rel_diff_vs_one_chip"] <= \
            row["tolerances"]["loss"]
        assert row["norm_rel_diff_vs_one_chip"] <= \
            row["tolerances"]["norm"]
        assert len(row["peak_bytes_in_use"]) == 4
    assert dense["collectives_in_compiled_step"]["all-reduce"]
    assert {"reduce-scatter", "all-gather"} <= \
        set(zero2["collectives_asked"])


def test_a_failed_check_is_a_failed_run(smoke, capsys, monkeypatch):
    from mxnet_tpu.ops import flash_attention as fa
    monkeypatch.setattr(fa, "_INTERPRET", True)
    monkeypatch.setattr(smoke, "FLASH_RTOL", -1.0)
    with pytest.raises(smoke.SmokeFailure, match="vs _sdpa_xla"):
        smoke.main(["--tiny", "--phase", "serve"])
    assert _rows(capsys)[-1]["phase"] == "flash"    # never "done"


def test_no_accelerator_no_result():
    """As the driver runs it, where jax finds no accelerator: non-zero
    exit, nothing that looks like a result, no phase attempted."""
    res = subprocess.run([sys.executable,
                          os.path.join(_REPO, "chip_smoke.py")],
                         capture_output=True, text=True, timeout=240,
                         env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert res.returncode != 0
    assert res.stdout.strip() == ""
    assert "not a TPU" in res.stderr


def test_tpu_context_raises_without_a_tpu_backend():
    with pytest.raises(MXNetError, match="no tpu backend"):
        mx.tpu().device
    assert mx.num_tpus() == 0


def test_cpu_context_raises_without_a_cpu_backend(monkeypatch):
    import jax

    def only_accelerator(backend=None, **_kw):
        raise RuntimeError(f"Unknown backend {backend}")

    monkeypatch.setattr(jax, "local_devices", only_accelerator)
    with pytest.raises(MXNetError, match="no cpu backend"):
        mx.cpu().device


def test_on_accelerator_is_false_for_a_made_up_platform(monkeypatch):
    import jax
    from mxnet_tpu.base import on_accelerator
    monkeypatch.setattr(jax, "default_backend", lambda: "neuron")
    assert not on_accelerator()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert on_accelerator()


def test_cache_helper_obeys_the_environment(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert jax_cache.place() == str(tmp_path)
    sub = jax_cache.fresh_subdir("phase")
    assert sub == str(tmp_path / "phase")
    open(os.path.join(sub, "stale"), "w").close()
    assert os.listdir(jax_cache.fresh_subdir("phase")) == []


def test_cache_helper_defaults_to_the_checkout(monkeypatch):
    # setenv first: delenv of an absent variable registers nothing to
    # undo, and place() is about to create it
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "")
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert jax_cache.place() == os.path.join(_REPO, ".jax_cache")
    assert os.environ["JAX_COMPILATION_CACHE_DIR"] == \
        os.path.join(_REPO, ".jax_cache")


def test_launcher_refuses_two_workers_off_the_cpu(monkeypatch):
    monkeypatch.delenv("JAX_PLATFORMS")
    with pytest.raises(SystemExit, match="one process"):
        launch.launch_local(2, [sys.executable, "-c", "pass"])
    # one worker may have the chips; two may share the CPU
    assert launch.launch_local(1, [sys.executable, "-c", "pass"]) == [0]
    assert launch.launch_local(
        2, [sys.executable, "-c", "pass"],
        extra_env={"JAX_PLATFORMS": "cpu"}) == [0, 0]
