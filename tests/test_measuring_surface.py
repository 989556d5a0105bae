"""The measuring surface around the program: ONE benchmark
(``BENCHMARK.json`` + ``chipbench/``), ONE registry of switches
(``mxnet_tpu/envs.py``), and documents that cite only what exists.

None of this runs a model: no unregistered ``MXTPU_*`` switch can
appear, ``docs/env_vars.md`` is the registry rendered, no document cites
a file or a test that does not exist, the README sends its reader to the
benchmark the driver runs, and that benchmark has no CPU mode.
"""
import functools
import glob
import json
import os
import re
import subprocess
import sys

import pytest

from mxnet_tpu import envs

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Read from ``os.environ`` before ``envs`` can load (the rendezvous of
# ``tools/launch.py`` in ``mxnet_tpu/__init__.py``, the PJRT plug-in
# path of ``pjrt_native``), so they are not registered knobs.  Three
# more are read raw at bootstrap AND registered: MXTPU_FAULT_SEED,
# MXTPU_FAULT_INJECT, MXTPU_ENGINE_TYPE.
BOOTSTRAP = {"MXTPU_DIST_COORDINATOR", "MXTPU_DIST_PROC_ID",
             "MXTPU_DIST_NUM_PROCS", "MXTPU_PJRT_PLUGIN"}


@functools.lru_cache(maxsize=None)
def _read(rel):
    with open(os.path.join(ROOT, rel), encoding="utf-8") as f:
        return f.read()


def _sources():
    for top in ("mxnet_tpu", "tools"):
        for path in glob.glob(os.path.join(ROOT, top, "**", "*.py"),
                              recursive=True):
            yield os.path.relpath(path, ROOT)
    yield "chip_smoke.py"


def test_every_mxtpu_name_in_the_program_is_a_registered_knob():
    """Comments and docstrings count: a name that is not a knob must
    not be written as if it were one.  A name ending in ``_`` is a
    prefix that filters the environment, not a read."""
    known = set(envs.registry()) | BOOTSTRAP
    strays = {}
    for rel in _sources():
        for name in re.findall(r"MXTPU_[A-Z0-9_]+", _read(rel)):
            if not name.endswith("_") and name not in known:
                strays.setdefault(name, set()).add(rel)
    assert not strays, strays


def test_env_vars_doc_is_the_registry_rendered():
    assert _read("docs/env_vars.md") == envs.to_markdown(), \
        "regenerate: python -m mxnet_tpu.envs > docs/env_vars.md"


DOCUMENTS = ["README.md", "PERF.md"] + sorted(
    os.path.relpath(p, ROOT)
    for p in glob.glob(os.path.join(ROOT, "docs", "*.md")))

# `dir/file.py`, `dir/file.md::name`, `tests/test_x.py::TestC::test_y`
_CITED = re.compile(r"`([\w.\-/]+/[\w.\-]+\.(?:py|md|json))((?:::\w+)*)`")


def _resolve(path):
    for base in ("", "mxnet_tpu", "chipbench"):
        rel = os.path.join(base, path)
        if os.path.isfile(os.path.join(ROOT, rel)):
            return rel
    return None


@pytest.mark.parametrize("doc", DOCUMENTS)
def test_a_document_cites_only_files_and_tests_that_exist(doc):
    """Every backticked path with a ``/`` that ends in ``.py``, ``.md``
    or ``.json`` resolves from the repo root, ``mxnet_tpu/`` or
    ``chipbench/``; where it goes on as ``::test_name``, that file
    defines the name."""
    missing = []
    for path, names in _CITED.findall(_read(doc)):
        rel = _resolve(path)
        if rel is None:
            missing.append(path)
            continue
        for name in filter(None, names.split("::")):
            if not re.search(rf"^\s*(?:def|class) {name}\b", _read(rel),
                             re.M):
                missing.append(f"{path}::{name}")
    assert not missing, missing


def test_readme_sends_its_reader_to_the_benchmark_the_driver_runs():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        command = json.load(f)["command"]
    script = command[-1]
    assert os.path.isfile(os.path.join(ROOT, script)), command
    readme = _read("README.md")
    block = readme[readme.index("```bash"):]
    block = block[:block.index("```", 3)]
    assert " ".join(command) in block
    for name in ("BENCHMARK.json", "PERF.md", "PERF_LEDGER.jsonl",
                 "chipbench/README.md"):
        assert name in readme, name


def test_the_benchmark_has_no_cpu_mode_and_sourced_peaks():
    """No accelerator, no result: on the CPU the benchmark's command
    exits 2 and prints no result line; and every device its peak table
    knows comes with its source."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    done = subprocess.run(
        [sys.executable, *bench["command"][1:], "--workload",
         bench["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300)
    assert done.returncode == 2, done.stderr[-400:]
    assert "not a TPU" in done.stderr
    assert '"metrics"' not in done.stdout
    peaks = json.loads(_read("chipbench/peaks.json"))
    assert peaks["TPU v5 lite"]["bf16_flops_per_s"] == 197e12
    assert all(row["source"] for row in peaks.values())
