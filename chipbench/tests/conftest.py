"""``bench``: what every assertion about the SHAPE of ``BENCHMARK.json``
takes: the accepted file, and two copies of it, built in memory, that
hold what a later PR would append (a serving cell with its configuration
and its own metric; the same with a reduction of every closed loop and a
metric of every cell beside them).

A cell's test holds what its cell must report AT LEAST, and what the PR
that brought it added, wherever later PRs leave it in the lists: it
finds entries by name, holds a metric set or a ``workloads`` list as a
floor, and never reads a position from the end of a list.  An assertion
that passes on the accepted file alone would stop the next addition;
``test_additions.py`` holds the copies to what they claim to be.
"""
import copy
import os
import sys
import types

import pytest

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(BENCH_DIR))

from chipbench.harness import resolve  # noqa: E402

ADDED_CONFIG = "added_hybrid"
ADDED_CELL = "added_hybrid.state_closed"
ADDED_METRIC = "added_update_ms.added"
# the wider copy's two more: a reduction named ``.offline`` that lists
# every closed cell (PR 37's were such entries), and an entry with no
# ``workloads`` key, which every cell reports
ADDED_REDUCTION = "added_scan_ms.offline"
ADDED_EVERYWHERE = "added_rss_bytes"
# what a closed-loop serving cell appends ITSELF to: the end-to-end rate,
# every reduction named ``.offline``, and the state plane's gauge
STATE_BYTES = "state_bytes_per_slot.reason"


def joins(metric):
    return metric["name"].endswith(".offline") \
        or metric["name"] in ("serve_tokens_per_s", STATE_BYTES)


def with_an_addition(bench, wide=False):
    """A deep copy of ``bench`` with one configuration, one closed-loop
    serving cell on it and one per-layer metric of its own appended, the
    cell added to the ``workloads`` of every metric such a cell joins.
    ``wide``: two more per-layer entries, a reduction of every closed
    loop and a metric of every cell.  No file stands behind the names
    (``bench`` gives the added metrics a reader that finds nothing to
    read): only shapes are held here."""
    out = copy.deepcopy(bench)
    # the names are the copies' own: an entry of the file that took one
    # would be read as added
    assert not {ADDED_CONFIG, ADDED_CELL, ADDED_METRIC, ADDED_REDUCTION,
                ADDED_EVERYWHERE} & {
        m["name"] for group in ("configs", "workloads", "per_layer")
        for m in out[group]}
    out["configs"].append({
        "name": ADDED_CONFIG, "source": "https://example.org/config.json",
        "file": f"chipbench/configs/{ADDED_CONFIG}.json",
        "reduced": ["num_hidden_layers"], "why": "a later PR's decoder"})
    out["workloads"].append({
        "name": ADDED_CELL, "config": ADDED_CONFIG,
        "traffic": "state_closed", "chips": 1,
        "why": "a later PR's closed loop over a new kind of state"})
    for group in ("end_to_end", "per_layer"):
        for metric in out[group]:
            if joins(metric):
                metric["workloads"].append(ADDED_CELL)
    entry = {"unit": "ms", "better": "lower", "source": "device_trace",
             "layer": "ops / kernels", "moves": "serve_tokens_per_s"}
    out["per_layer"].append(
        dict(entry, name=ADDED_METRIC, workloads=[ADDED_CELL]))
    if wide:
        closed = next(m for m in out["per_layer"]
                      if m["name"] == "decode_round_ms.offline")
        out["per_layer"].append(dict(
            entry, name=ADDED_REDUCTION, workloads=list(closed["workloads"])))
        out["per_layer"].append({
            "name": ADDED_EVERYWHERE, "unit": "bytes", "better": "lower",
            "source": "program_counter", "layer": "engine + tiers",
            "moves": "setup_s"})
    return out


ACCEPTED = resolve.load_benchmark()
BENCHES = {"accepted": ACCEPTED, "appended": with_an_addition(ACCEPTED),
           "appended_wide": with_an_addition(ACCEPTED, wide=True)}


def _reads_nothing(_obs):
    return None


@pytest.fixture(params=sorted(BENCHES))
def bench(request, monkeypatch):
    """One of the three; an added metric's reader is found by its name,
    as a file's would be, and finds nothing to read."""
    added = {ADDED_METRIC, ADDED_REDUCTION, ADDED_EVERYWHERE}
    real = resolve.load_module

    def load_module(subdir, name):
        if subdir == "layer_metrics" and name in added:
            return types.SimpleNamespace(read=_reads_nothing)
        return real(subdir, name)
    if request.param != "accepted":
        monkeypatch.setattr(resolve, "load_module", load_module)
    return BENCHES[request.param]


@pytest.fixture(params=sorted(set(BENCHES) - {"accepted"}))
def appended(request):
    return BENCHES[request.param]


@pytest.fixture
def benches():
    return BENCHES


@pytest.fixture
def addition():
    """The names the copies add, and the rule by which a cell joins."""
    return types.SimpleNamespace(
        joins=joins, reduction=ADDED_REDUCTION, everywhere=ADDED_EVERYWHERE)
