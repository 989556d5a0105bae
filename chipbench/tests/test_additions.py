"""``BENCHMARK.json`` takes additions as data: a later PR appends a
configuration, a cell and per-layer entries, adds files, and edits no file
that is there, the benchmark's own tests among them.

``conftest.py`` gives every assertion about the benchmark's SHAPE (the
three newer cells' tests, ``test_program_spans.py``,
``test_device_scopes.py``) the accepted file and two copies with such an
addition appended: an exact set, a count or a list's tail fails on a
copy.  Here: the copies are what they claim to be (held by floors too),
and entries and reader files stand one to one.

Run by hand: ``JAX_PLATFORMS=cpu python -m pytest chipbench/tests -q``.
"""
import glob
import os
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(BENCH_DIR))

from chipbench.harness import resolve  # noqa: E402

# what a closed-loop serving cell reports AT LEAST (PR 39's list; a later
# reduction of every closed loop joins it without an edit here)
CLOSED_LOOP = {
    "serve_tokens_per_s", "setup_s", "compile_s", "decode_round_ms.offline",
    "occupancy.offline", "steady_tokens_per_s.offline",
    "device_idle_share.offline", "state_bytes_per_slot.reason",
    "decode_host_ms.offline", "admit_host_ms.offline",
    "unscoped_device_share.offline", "prefill_busy_share.offline",
    "prefill_device_ms.offline", "decode_device_ms.offline",
    "decode_mixer_ms.offline", "decode_ffn_ms.offline"}
GROUPS = ("configs", "workloads", "end_to_end", "per_layer")


def test_a_copy_is_the_accepted_benchmark_with_additions_at_the_end(
        benches, appended, addition):
    accepted = benches["accepted"]
    for key in ("command", "paths", "run_seconds"):
        assert appended[key] == accepted[key]
    added = {g: appended[g][len(accepted[g]):] for g in GROUPS}
    (config,), (cell,) = added["configs"], added["workloads"]
    assert cell["config"] == config["name"] and not added["end_to_end"]
    assert added["per_layer"] and added["per_layer"][0]["workloads"] == [
        cell["name"]]
    # every entry that was there is there, in its place, and differs at
    # most by the new cell at the END of its ``workloads``
    joined = set()
    for group in GROUPS:
        for old, new in zip(accepted[group], appended[group]):
            if old != new:
                assert new == dict(
                    old, workloads=old["workloads"] + [cell["name"]])
                joined.add(new["name"])
    assert joined == {m["name"] for g in ("end_to_end", "per_layer")
                      for m in accepted[g] if addition.joins(m)}
    assert joined >= CLOSED_LOOP - {"setup_s", "compile_s"}
    # what the new cell reports: the closed loops' own reductions, the
    # metrics of every cell, and what came with it; nothing of another
    # kind of cell
    names = {m["name"] for g in ("end_to_end", "per_layer")
             for m in resolve.metrics_of(appended, g, cell["name"])}
    assert names >= CLOSED_LOOP | {added["per_layer"][0]["name"]}
    assert names == joined | {
        m["name"] for g in ("end_to_end", "per_layer") for m in appended[g]
        if "workloads" not in m} | {m["name"] for m in added["per_layer"]}
    # an accepted cell reports what it did, its entries in their order,
    # and whatever of the additions lists it or lists no cell
    fresh = {m["name"] for m in added["per_layer"]}
    for w in accepted["workloads"]:
        for g in ("end_to_end", "per_layer"):
            was = resolve.metrics_of(accepted, g, w["name"])
            now = resolve.metrics_of(appended, g, w["name"])
            assert [m for m in now if m["name"] not in fresh] == [
                dict(m, workloads=m["workloads"] + [cell["name"]])
                if m["name"] in joined else m for m in was]
            assert [m for m in now if m["name"] in fresh] == [
                m for m in added[g] if w["name"] in m.get(
                    "workloads", [w["name"]])]


def test_the_wider_copy_holds_what_stopped_a_pr_before(benches, addition):
    """PR 37's 15 were entries named ``.offline`` over every closed cell
    and entries of one cell: a test that held a cell's EXACT metric set
    refused them.  The wider copy holds one such entry and one that
    every cell reports, so an exact set fails on it in every cell."""
    accepted, wide = benches["accepted"], benches["appended_wide"]
    by = {m["name"]: m for m in wide["per_layer"]}
    assert by[addition.reduction]["workloads"] == \
        by["decode_round_ms.offline"]["workloads"]
    assert "workloads" not in by[addition.everywhere]
    closed = set(by["decode_round_ms.offline"]["workloads"])
    for w in wide["workloads"]:
        names = {m["name"] for m in resolve.metrics_of(
            wide, "per_layer", w["name"])}
        assert addition.everywhere in names
        assert (addition.reduction in names) == (w["name"] in closed)
        if w in accepted["workloads"]:
            assert names > {m["name"] for m in resolve.metrics_of(
                accepted, "per_layer", w["name"])}


def test_every_per_layer_entry_has_a_reader_and_every_reader_an_entry(
        benches):
    """A reader nobody lists is never run by the driver (PR 37's 15 were,
    for two PRs); an entry without its file fails the run that reads it."""
    entries = [m["name"] for m in benches["accepted"]["per_layer"]]
    readers = [os.path.basename(p)[:-len(".py")] for p in glob.glob(
        os.path.join(BENCH_DIR, "layer_metrics", "*.py"))]
    assert len(set(entries)) == len(entries)
    assert sorted(entries) == sorted(readers)
    e2e = [m["name"] for m in benches["accepted"]["end_to_end"]]
    assert sorted(e2e) == sorted(
        os.path.basename(p)[:-len(".py")] for p in glob.glob(
            os.path.join(BENCH_DIR, "end_to_end", "*.py")))
