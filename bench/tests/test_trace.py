"""Trace reduction: synthetic intervals, and a committed trace."""

from pathlib import Path

import pytest

from bench import trace as tr


def test_union_merges_and_clips():
    ivs = [(5, 10, "a"), (0, 3, "b"), (8, 12, "c"), (20, 30, "d")]
    assert tr.union(ivs, 1, 25) == [(1, 3), (5, 12), (20, 25)]


def test_gaps_complement_busy():
    assert tr.gaps([(1, 3), (5, 12)], 0, 15) == [(0, 1), (3, 5), (12, 15)]
    assert tr.gaps([], 0, 4) == [(0, 4)]


def test_gaps_labelled_by_innermost_span():
    spans = [(0, 100, "job"), (10, 40, "store.init"), (60, 70, "sched.resolve")]
    got = tr.label_gaps([(20, 30), (62, 64), (80, 90), (200, 210)], spans)
    assert got == pytest.approx({"store.init": 10e-9, "sched.resolve": 2e-9,
                                 "job": 10e-9, "unlabelled": 10e-9})


def test_summarize_busy_ops_and_idle():
    dev = {"/device:TPU:0": [(10, 30, "fusion"), (25, 40, "kernel"),
                             (70, 80, "fusion")]}
    spans = [(0, 100, "job"), (40, 70, "sched.assemble")]
    s = tr.summarize(dev, spans, (0, 100))
    assert s["busy_s"] == pytest.approx(40e-9)
    assert s["window_s"] == pytest.approx(100e-9)
    assert s["device_ops"][0] == ["fusion", pytest.approx(30e-9)]
    assert dict(s["idle_gaps"]) == pytest.approx(
        {"sched.assemble": 30e-9, "job": 30e-9})


def test_summarize_needs_device_work():
    assert tr.summarize({}, [], (0, 10)) is None
    assert tr.summarize({"/device:TPU:0": [(20, 30, "x")]}, [], (0, 10)) is None


def test_device_plane_names():
    assert tr.DEVICE_PLANE.match("/device:TPU:0")
    assert not tr.DEVICE_PLANE.match("/host:CPU")


def test_committed_cpu_trace():
    # Recorded on the CPU: three "job" spans inside a "window" span
    # around a small jitted op.  It has host spans and no device plane,
    # so it tests the reading path and yields no device number.
    d = str(Path(__file__).parent / "data" / "cpu_trace")
    devices, spans = tr.extract(tr.load(d), {"window", "job"})
    assert devices == {}
    jobs = [(s, e) for s, e, n in spans if n == "job"]
    (w0, w1), = [(s, e) for s, e, n in spans if n == "window"]
    assert len(jobs) == 3 and all(w0 <= s < e <= w1 for s, e in jobs)
    assert tr.reduce_dir(d, ["job"], "window") is None
