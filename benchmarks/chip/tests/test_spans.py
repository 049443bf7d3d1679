"""The readers of the program's stage spans: each window update's interval
found by version, overlapping spans of several threads counted once,
nothing read where the spans are absent; and, through ``run.main`` at
tiny widths on the CPU, a number in every cell each metric lists."""

import json
from pathlib import Path
from types import SimpleNamespace

import pytest

import run
import spans
from test_cells import CELLS, tiny_run  # noqa: F401  (fixture)

ROOT = Path(__file__).resolve().parents[3]
#: the per-layer metrics that read program spans
NEW = ("base_snapshot_s", "manifest_checksum_s", "verify_wall_s", "copy_wall_s",
       "reshard_plan_s", "interval_reads")


def ev(name, track, t0, t1, **attrs):
    return (name, track, t0, t1, None, attrs or None)


def ctx(events, versions):
    rec = SimpleNamespace(events=events)
    return {"cell": SimpleNamespace(recorder=rec), "updates": [{"version": v} for v in versions]}


def read(name, c):
    return run.load_reader(name)(c)


def secs(x):
    return (pytest.approx(x), "s")


#: two updates (v1 over [10, 20], v2 over [30, 45]) with the set-up's v0
#: before them, whose spans no metric of the window may count
EVENTS = [
    ev("update", "r/shard0", 0.0, 5.0, version=0),
    ev("snapshot_base", "r/shard0", 1.0, 4.0, bytes=8),
    ev("unpublish", "pub/shard0", 10.0, 12.0, version=0),
    ev("unpublish", "pub/shard1", 11.0, 13.0, version=0),
    ev("snapshot_base", "pub/shard0", 10.0, 12.0, bytes=8),
    ev("snapshot_base", "pub/shard1", 11.0, 13.0, bytes=8),  # overlaps the other
    ev("manifest", "pub/shard0", 14.0, 15.0, bytes=8),
    ev("update", "r/shard0", 16.0, 20.0, version=1),
    ev("verify", "r/shard0/w0", 17.0, 18.0),
    ev("verify", "r/shard0/w1", 17.5, 18.5),  # another pull thread, at once
    ev("wire_copy", "r/shard0/w0", 16.5, 17.0),
    ev("write", "r/shard0/w0", 18.0, 19.0),
    ev("fetch_unit", "r/shard0", 16.0, 17.0, intervals=100, bytes=8),
    ev("fetch_unit", "r/shard0", 17.0, 19.0, intervals=50, bytes=8),
    ev("unpublish", "pub/shard0", 30.0, 31.0, version=1),
    ev("snapshot_base", "pub/shard0", 30.0, 31.0, bytes=8),
    ev("plan_shard", "r/shard0", 40.0, 42.0, intervals=150),
    ev("update", "r/shard0", 35.0, 45.0, version=2),
    ev("fetch_unit", "r/shard0", 42.0, 44.0, intervals=150, bytes=8),
]


def test_updates_are_windowed_by_version():
    assert spans.update_intervals(EVENTS, [1, 2]) == [(10.0, 20.0), (30.0, 45.0)]
    assert spans.update_intervals(EVENTS, [3]) is None
    # v0's snapshot, before the window, is not counted
    assert read("base_snapshot_s", ctx(EVENTS, [1, 2])) == secs(((13 - 10) + 1) / 2)
    assert read("manifest_checksum_s", ctx(EVENTS, [1, 2])) == secs(0.5)
    assert read("reshard_plan_s", ctx(EVENTS, [1, 2])) == secs(1.0)
    assert read("interval_reads", ctx(EVENTS, [1, 2])) == (150.0, "reads")
    assert read("interval_reads", ctx(EVENTS, [2])) == (150.0, "reads")


def test_overlapping_spans_of_several_threads_count_once():
    # verify: [17, 18] and [17.5, 18.5] on two threads -> 1.5 s, not 2
    assert read("verify_wall_s", ctx(EVENTS, [1])) == secs(1.5)
    assert read("copy_wall_s", ctx(EVENTS, [1])) == secs(1.5)
    assert read("base_snapshot_s", ctx(EVENTS, [1])) == secs(3.0)


def test_readers_return_none_where_their_spans_are_absent():
    # the update in the window ran none of the named stages
    for name in ("verify_wall_s", "copy_wall_s", "manifest_checksum_s"):
        assert read(name, ctx(EVENTS, [2])) is None, name
    # a program without the stage spans: no unpublish, so no interval
    old = [e for e in EVENTS if e[0] not in ("unpublish",)]
    for name in NEW:
        assert read(name, ctx(old, [1, 2])) is None, name
    # no recorder at all (a --trace 0 cell)
    c = {"cell": SimpleNamespace(recorder=None), "updates": [{"version": 1}]}
    for name in NEW:
        assert read(name, c) is None, name


def test_every_new_metric_is_listed_for_the_cells_it_reads():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        m = by_name[name]
        assert m["source"] == "program_span" and m["moves"] == "weight_sync_s"
        assert set(m["workloads"]) <= set(CELLS)


@pytest.mark.parametrize("workload", CELLS)
def test_traced_run_reads_each_listed_span_metric(workload, tiny_run):  # noqa: F811
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    listed = {m["name"] for m in bench["per_layer"]
              if m["name"] in NEW and workload in m["workloads"]}
    out = tiny_run(workload, "--trace", "1")
    assert out["correct"] is True
    assert listed and listed <= set(out["metrics"])
    assert all(out["metrics"][n]["value"] > 0 for n in listed)
    # unlisted span metrics are not reported in the cell
    assert not (set(NEW) - listed) & set(out["metrics"])
