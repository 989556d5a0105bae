"""Find a cell's files by the names ``BENCHMARK.json`` gives.

Nothing here lists configurations, traffic mixes, drivers or metrics: a
name IS a path.  ``<config>`` -> the ``file`` of its ``configs`` entry;
``<traffic>`` -> ``chipbench/traffic/<traffic>.json``; the traffic file's
``driver`` -> ``chipbench/drivers/<driver>.py``; the config file's
``builder`` -> ``chipbench/models/<builder>.py``; a metric ->
``chipbench/end_to_end/<name>.py`` or ``chipbench/layer_metrics/<name>.py``.
A later PR adds files and entries and edits none that is there.
"""
import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
GROUP_DIRS = {"end_to_end": "end_to_end", "per_layer": "layer_metrics"}


class ResolveError(Exception):
    """A name in ``BENCHMARK.json`` leads to no file."""


def load_json(*parts):
    path = os.path.join(*parts)
    try:
        with open(path) as f:
            return json.load(f)
    except OSError as e:
        raise ResolveError(f"no such file: {path}") from e


def load_benchmark():
    return load_json(ROOT, "BENCHMARK.json")


def load_module(subdir, name):
    """``chipbench/<subdir>/<name>.py`` as a module, by path (a metric's
    name may hold dots, which an ``import`` statement could not spell)."""
    path = os.path.join(BENCH_DIR, subdir, name + ".py")
    if not os.path.isfile(path):
        raise ResolveError(f"{subdir} {name!r}: no file {path}")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{subdir}_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell(bench, workload):
    """(workload entry, config file's content, traffic file's content)."""
    for w in bench["workloads"]:
        if w["name"] == workload:
            break
    else:
        raise ResolveError(
            f"workload {workload!r} is not in BENCHMARK.json; it has "
            f"{[w['name'] for w in bench['workloads']]}")
    for c in bench["configs"]:
        if c["name"] == w["config"]:
            break
    else:
        raise ResolveError(f"config {w['config']!r} is not in "
                           "BENCHMARK.json's configs")
    config = load_json(ROOT, c["file"])
    traffic = load_json(BENCH_DIR, "traffic", w["traffic"] + ".json")
    return w, config, traffic


def metrics_of(bench, group, workload):
    """The entries of ``end_to_end`` or ``per_layer`` that this cell
    reports: those with no ``workloads`` key, or with the cell in it."""
    return [m for m in bench[group]
            if "workloads" not in m or workload in m["workloads"]]


def read_metrics(bench, group, workload, obs):
    """Run each of the cell's readers over ``obs``.  A reader that finds
    nothing to read returns None, and that metric is left out."""
    out = {}
    for m in metrics_of(bench, group, workload):
        value = load_module(GROUP_DIRS[group], m["name"]).read(obs)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out
