"""BENCHMARK.json names only files that exist, and keeps its shape."""

import json
import re

import pytest

from bench import harness

SPEC = harness.load_json(harness.ROOT / "BENCHMARK.json")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
E2E = {m["name"] for m in SPEC["end_to_end"]}
CELLS = {w["name"] for w in SPEC["workloads"]}


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert "setup_s" in E2E
    assert len(json.dumps(SPEC)) < 64 * 1024


def test_names():
    names = ([c["name"] for c in SPEC["configs"]] + sorted(CELLS)
             + [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
             + [w["traffic"] for w in SPEC["workloads"]])
    assert all(NAME.match(n) for n in names), names


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_cell_files_resolve(cell):
    c = harness.Cell(cell)
    assert c.config["engine"]
    assert callable(c.engine.job) and callable(c.engine.build)
    assert "rung" in c.traffic
    assert set(c.metric_readers())
    assert {"setup_s", "mine_s"} <= set(c.units("end_to_end"))


def test_configs_used_and_files_under_paths():
    used = {w["config"] for w in SPEC["workloads"]}
    assert used == {c["name"] for c in SPEC["configs"]}
    for c in SPEC["configs"]:
        assert c["file"].startswith(tuple(p + "/" for p in SPEC["paths"]))
        cfg = harness.load_json(harness.ROOT / c["file"])
        assert cfg["reduced"] == c["reduced"]


def test_per_layer_metrics():
    for m in SPEC["per_layer"]:
        assert m["moves"] in E2E
        assert set(m["workloads"]) <= CELLS
        assert (harness.BENCH / "metrics" / f"{m['name']}.py").exists()


def test_bounds():
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
