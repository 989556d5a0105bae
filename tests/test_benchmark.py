"""Smoke tests for the benchmark harness.

Keeps `benchmark/` importable and runnable — numbers themselves are not
asserted (CPU backend), only that each harness completes and emits
well-formed rows.
"""
import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def test_opperf_smoke():
    from benchmark import opperf
    rows = opperf.main(["--ops", "exp,sum"])
    assert {r["op"] for r in rows} == {"exp", "sum"}
    for r in rows:
        assert r["dispatch_us"] > 0
        assert r["compile_ms"] > 0
        assert r["large_ms"] > 0


def test_allreduce_bench_smoke():
    from benchmark import allreduce_bench
    rows, n = allreduce_bench.bench_allreduce([0.1], iters=2)
    assert n >= 1
    assert rows[0]["busbw_gbps"] >= 0
    assert rows[0]["time_ms"] > 0


@pytest.mark.slow
def test_resnet_bench_smoke():
    from benchmark import resnet_bench
    ips, _ = resnet_bench.bench("resnet18_v1", batch=2, image_size=32,
                                steps=2, warmup=1, train=False)
    assert ips > 0


def test_a_stage_that_raises_exits_nonzero(monkeypatch, capsys):
    """No stage failure is carried past: traceback, the best-so-far
    line with ``failed``, exit code 1."""
    import json
    import bench

    class _Exit(Exception):
        pass

    def refused(budget):
        raise RuntimeError("stage refused by the chip")

    def fake_exit(code):
        raise _Exit(code)

    monkeypatch.setenv("MXTPU_BENCH_BUDGET", "1e8")   # watchdog asleep
    monkeypatch.setattr(bench, "_LOG_DIR", "")
    monkeypatch.setattr(bench, "_run", refused)
    monkeypatch.setattr(bench.os, "_exit", fake_exit)
    monkeypatch.setitem(bench._state, "result",
                        dict(bench._state["result"]))
    monkeypatch.setitem(bench._state, "emitted", False)
    with pytest.raises(_Exit) as exc:
        bench.main()
    assert exc.value.args == (1,)
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert "stage refused by the chip" in line["failed"]


def test_peak_flops_come_from_the_device_kind_table(monkeypatch):
    """An unknown ``device_kind`` is an error, never a default."""
    import jax
    import bench

    class _Dev:
        device_kind = "TPU v5 lite"

    monkeypatch.setattr(jax, "devices", lambda *a, **k: [_Dev()])
    assert bench._peak_flops() == 197e12
    _Dev.device_kind = "TPU v9 imaginary"
    with pytest.raises(RuntimeError, match="no peak FLOP/s on record"):
        bench._peak_flops()


def test_bench_without_accelerator_or_cpu_request_fails(monkeypatch):
    """``_run`` asks jax once which platform it has; "cpu" counts only
    when JAX_PLATFORMS=cpu asked for it."""
    import bench
    monkeypatch.delenv("JAX_PLATFORMS")
    with pytest.raises(RuntimeError, match="no accelerator"):
        bench._run(60.0)
