"""Reduction of the program's own spans and scopes: synthetic
intervals, synthetic job records, and one small traced run on the CPU."""

import json
from pathlib import Path

import pytest

from bench import harness
from bench import program_trace as pt
from bench import trace as tr


def test_op_scope_reads_the_op_name_path():
    stats = [("hlo_op", "fusion.1"),
             ("tf_op", "jit(_impl)/dispatch.kernel/jit(k)/pallas_call")]
    assert pt.op_scope(stats) == ("dispatch.kernel", "tf_op")
    assert pt.op_scope([("hlo_op", "copy.20"), ("program_id", 5)]) == ("", "")
    # A scope name inside another word is not a scope.
    assert pt.op_scope([("x", "a/dispatch.kernels/b")]) == ("", "")


def _varint(n):
    out = b""
    while True:
        out += bytes([(n & 0x7F) | (0x80 if n > 0x7F else 0)])
        n >>= 7
        if not n:
            return out


def _msg(*fields):
    """Protobuf bytes of ``(field number, int | str | bytes)`` pairs."""
    out = b""
    for num, v in fields:
        if isinstance(v, int):
            out += _varint(num << 3) + _varint(v)
        else:
            v = v.encode() if isinstance(v, str) else v
            out += _varint(num << 3 | 2) + _varint(len(v)) + v
    return out


def test_metadata_stats_of_device_planes(tmp_path):
    op = "jit(f)/dispatch.kernel/pallas_call"
    stat_meta = [_msg((1, sid), (2, _msg((1, sid), (2, name))))
                 for sid, name in ((7, "tf_op"), (8, "long_name"),
                                   (9, "jit(f)/dispatch.scatter/scatter"))]
    kernel = _msg((1, 3), (2, "%kernel.1 = custom-call()"),
                  (5, _msg((1, 8), (5, "x"))), (5, _msg((1, 7), (5, op))))
    scatter = _msg((1, 4), (2, "%fusion.2"), (4, "fusion.2"),
                   (5, _msg((1, 7), (7, 9))))
    device = _msg((1, 1), (2, "/device:TPU:0"), (3, _msg((2, "XLA Ops"))),
                  *[(4, _msg((1, k), (2, m)))
                    for k, m in ((3, kernel), (4, scatter))],
                  *[(5, e) for e in stat_meta])
    host = _msg((1, 2), (2, "/host:CPU"), (4, _msg((1, 1), (2, kernel))))
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_msg((1, device), (1, host)))
    got = pt.metadata_stats(str(path))
    assert list(got) == ["/device:TPU:0"]
    plane = got["/device:TPU:0"]
    assert plane["%kernel.1 = custom-call()"] == [("long_name", "x"),
                                                  ("tf_op", op)]
    assert pt.op_scope(plane["fusion.2"]) == ("dispatch.scatter", "tf_op")
    assert plane["%fusion.2"] == plane["fusion.2"]


def test_metadata_stats_of_a_cpu_trace():
    # The committed CPU trace parses, and has no device plane.
    d = Path(__file__).parent / "data" / "cpu_trace" / "window.xplane.pb"
    assert pt.metadata_stats(str(d)) == {}


def test_upload_wait_is_the_gap_to_the_next_op():
    uploads = [(100, 130, "store.upload", {"job": 0}),
               (1000, 1010, "store.upload", {"job": 1}),
               (5000, 5010, "store.upload", {"job": 2})]   # no op after it
    ops = [(50, 90, "a", ""), (400, 450, "b", ""), (1200, 1300, "c", "")]
    assert pt.upload_waits_ms(uploads, ops) == pytest.approx([3e-4, 2e-4])


def test_scope_seconds_group_and_clip():
    ops = [(0, 10, "k1", "dispatch.kernel"), (10, 30, "g", "dispatch.gather"),
           (25, 45, "k2", "dispatch.kernel"), (40, 60, "c", "")]
    got = pt.scope_seconds(ops, 5, 50)
    assert got == pytest.approx({"dispatch.kernel": 25e-9,
                                 "dispatch.gather": 20e-9, "": 10e-9})


def test_innermost_pieces_and_idle_attribution():
    spans = [(0, 100, "mine", {}), (10, 60, "sched.resolve", {}),
             (20, 50, "sched.wait", {}), (62, 64, "sched.readback", {})]
    pieces = pt.innermost(spans, 0, 120)
    assert pieces == [(0, 10, "mine"), (10, 20, "sched.resolve"),
                      (20, 50, "sched.wait"), (50, 60, "sched.resolve"),
                      (60, 62, "mine"), (62, 64, "sched.readback"),
                      (64, 100, "mine"), (100, 120, None)]
    idle = [(15, 25), (55, 70), (110, 115)]
    assert pt.attribute(idle, pieces) == pytest.approx(
        {"sched.resolve": 10e-9, "sched.wait": 5e-9, "mine": 8e-9,
         "sched.readback": 2e-9, "none": 5e-9})
    # The midpoint rule gives the whole 55..70 gap to the 2 ns span at
    # its midpoint.
    assert tr.label_gaps(idle, [s[:3] for s in spans])[
        "sched.readback"] == pytest.approx(15e-9)


def test_job_values_from_accounting():
    jobs = [{"upload_bytes": 2**30, "wait_s": 0.5, "retire_s": 0.01,
             "pad_lanes": 100, "pair_lanes": 400, "assemble_s": 0.1,
             "resolve_s": 0.6}] * 2
    got = pt.job_values(jobs)
    assert got == pytest.approx({
        "store.upload_gib": 1.0, "sched.wait_ms": 500.0,
        "sched.retire_ms": 10.0, "dispatch.pad_frac": 0.25,
        "sched.assemble_ms": 100.0, "sched.resolve_ms": 600.0})


def test_reduce_synthetic_window():
    ms = 1_000_000
    devices = {"/device:TPU:0": [
        (30 * ms, 40 * ms, "suffix", ""),
        (50 * ms, 80 * ms, "kernel", "dispatch.kernel"),
        (80 * ms, 85 * ms, "scatter", "dispatch.scatter")]}
    spans = [(0, 100 * ms, "window", {}),
             (0, 100 * ms, "mine", {"job": 0}),
             (0, 5 * ms, "store.build", {"job": 0}),
             (5 * ms, 10 * ms, "store.upload", {"job": 0}),
             (45 * ms, 95 * ms, "sched.resolve", {"job": 0}),
             (45 * ms, 90 * ms, "sched.wait", {"job": 0})]
    got = pt.reduce(devices, spans, (0, 100 * ms), n_jobs=1)
    assert got["busy_s"] == pytest.approx(0.045)
    assert got["store.upload_ms"] == pytest.approx(25.0)
    assert got["dispatch.kernel_ms"] == pytest.approx(30.0)
    assert got["idle_ms"] == pytest.approx(
        {"mine": 30.0, "store.build": 5.0, "store.upload": 5.0,
         "sched.wait": 10.0, "sched.resolve": 5.0})
    assert pt.reduce({}, spans, (0, 100 * ms), 1) is None


def test_existing_idle_gaps_are_unchanged():
    # bench/trace.py is untouched: its midpoint labels still read the
    # cases of test_trace.py.
    dev = {"/device:TPU:0": [(10, 30, "fusion"), (25, 40, "kernel"),
                             (70, 80, "fusion")]}
    spans = [(0, 100, "job"), (40, 70, "sched.assemble")]
    assert dict(tr.summarize(dev, spans, (0, 100))["idle_gaps"]) == \
        pytest.approx({"sched.assemble": 30e-9, "job": 30e-9})


def test_cpu_run_prints_the_job_values(monkeypatch, tmp_path, capsys):
    monkeypatch.setattr(harness, "chips_ok", lambda chips: True)
    out = tmp_path / "line.json"
    assert pt.main(["--workload", "kosarak-paper.top", "--seed",
                    str(2**31 + 11), "--seconds", "0.2", "--scale", "0.01",
                    "--out", str(out)]) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line == json.loads(out.read_text())
    assert line["jobs"] >= 1 and line["dispatch.pad_frac"] > 0
    assert line["store.upload_gib"] > 0
    assert 0 < line["sched.wait_ms"] <= line["sched.resolve_ms"]
    assert "busy_s" not in line            # a CPU trace has no device plane
