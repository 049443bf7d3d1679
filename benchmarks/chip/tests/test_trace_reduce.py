"""The trace reduction, on intervals written by hand and on a trace
recorded on a TPU v5e (``testdata/v5e_probe.xplane.pb``)."""

from pathlib import Path

import pytest

import trace_reduce as tr

TESTDATA = Path(__file__).resolve().parents[1] / "testdata"


def test_union_clips_and_merges():
    assert tr.union([(5, 8), (0, 3), (2, 4), (7, 12)], 1, 10) == [(1, 4), (5, 10)]
    assert tr.union([(0, 1)], 2, 5) == []


def test_gaps_are_the_complement():
    assert tr.gaps([(1, 4), (5, 10)], 0, 12) == [(0, 1), (4, 5), (10, 12)]
    assert tr.gaps([], 0, 3) == [(0, 3)]


def test_gaps_are_named_by_the_innermost_span():
    spans = [("publish", 0, 10), ("rewrite", 2, 4)]
    named = tr.name_gaps([(0, 6), (12, 14)], spans, "window")
    assert named == pytest.approx({"publish": 4e-9, "rewrite": 2e-9, "window": 2e-9})


def test_reduce_on_hand_written_trace():
    t = tr.Trace(
        ops={"/device:TPU:0": [("fusion.1", 100, 200), ("fusion.2", 150, 300), ("copy", 500, 600)]},
        modules={"/device:TPU:0": [("jit_f(12)", 100, 300), ("jit_g(3)", 500, 600)]},
        spans=[("window", 0, 1000), ("pull", 0, 400), ("land", 400, 1000)],
    )
    r = tr.reduce(t)
    assert r["window_s"] == pytest.approx(1e-6)
    assert r["busy_s"] == pytest.approx(300e-9)
    assert r["kernel_s"] == pytest.approx({"jit_f": 200e-9, "jit_g": 100e-9})
    assert dict((k, v) for k, v in r["breakdown"]["idle_gaps"]) == pytest.approx(
        {"pull": 200e-9, "land": 500e-9}
    )
    assert r["breakdown"]["device_ops"][0][0] == "jit_f"


def test_reduce_without_device_or_window_reads_nothing():
    assert tr.reduce(tr.Trace(ops={}, modules={}, spans=[("window", 0, 5)])) is None
    assert tr.reduce(tr.Trace(ops={"d": [("x", 0, 1)]}, modules={}, spans=[])) is None


def test_reduce_on_a_recorded_v5e_trace():
    """A window on one TPU v5e: one int8 quantize of a [71680, 256] f32
    block, one landing, one digest."""
    t = tr.load(str(TESTDATA / "v5e_probe.xplane.pb"), ["window", "pull", "land", "digest"])
    assert list(t.ops) == ["/device:TPU:0"]
    r = tr.reduce(t)
    assert 0 < r["busy_s"] < r["window_s"]
    assert set(r["kernel_s"]) == {"jit_quantize_ref", "jit_digest"}
    assert {name for name, _ in r["breakdown"]["idle_gaps"]} == {"pull", "land", "digest", "window"}
    idle = sum(s for _, s in r["breakdown"]["idle_gaps"])
    assert idle == pytest.approx(r["window_s"] - r["busy_s"])
