"""The PrePost+ cell's engine adapter and its per-layer readers.

The adapter's job runs a tiny packed replica on the CPU and must match
the plain reference; each of the cell's own readers is fed hand-made
records and returns None where what it reads is absent (a program
without the counters).  The cell also reports five metrics of the
bitmap cells, read from the same job record and device trace.
"""

import pytest

from bench import data, harness, reference

CELL = "pumsb-paper.mid"
OWN = {"ppc.build_ms", "nlist.pad_frac", "nlist.es_abort_frac"}
SHARED = {"miners.device_calls", "sched.assemble_ms", "sched.resolve_ms"}
DEVICE = {"dispatch.device_ms", "device.idle_frac"}


def _reader(name):
    return harness.load_module(harness.BENCH / "metrics" / f"{name}.py")


def test_cell_names_the_prepost_engine_and_its_metrics():
    cell = harness.Cell(CELL)
    assert cell.config["engine"] == "prepost"
    assert set(cell.metric_readers()) == OWN | SHARED | DEVICE
    assert cell.config["params"]["n_cols"] == 74


@pytest.mark.parametrize("early_stop", [True, False])
def test_engine_job_matches_the_reference(early_stop):
    cell = harness.Cell(CELL)
    params = dict(cell.config["params"], n_trans=400, n_cols=12)
    bdb, (minsup,) = data.pack("dense", params, [0.3],
                               seed=params.pop("data_seed"),
                               order_seed=2**31 + 5)
    miner = cell.engine.build({"early_stop": early_stop})
    out, st = cell.engine.job(miner, bdb, minsup)
    assert out == reference.frequent_itemsets(bdb.bitmaps, bdb.items,
                                              minsup)
    assert st["itemsets"] == len(out)
    assert st["build_s"] > 0 and st["nl_codes"] <= st["nl_gather_codes"]
    assert (st["es_aborts"] > 0) == early_stop


def test_build_ms():
    read = _reader("ppc.build_ms").read
    assert read({"jobs": [{"build_s": 0.5}, {"build_s": 1.5}]}) == 1000.0
    assert read({"jobs": [{"build_s": None}, {}]}) is None


def test_pad_frac():
    read = _reader("nlist.pad_frac").read
    jobs = [{"nl_codes": 10, "nl_gather_codes": 40},
            {"nl_codes": 30, "nl_gather_codes": 40}]
    assert read({"jobs": jobs}) == 0.5
    assert read({"jobs": [{"nl_codes": None, "nl_gather_codes": None}]}) \
        is None


def test_es_abort_frac():
    read = _reader("nlist.es_abort_frac").read
    jobs = [{"candidates": 10, "es_aborts": 1},
            {"candidates": 30, "es_aborts": 9}]
    assert read({"jobs": jobs}) == 0.25
    assert read({"jobs": [{"device_calls": 3}]}) is None


def test_traced_cpu_run_reports_the_counter_metrics():
    """A CPU trace has no device plane: the readers of the device trace
    are silent, and those of the job's counters and timers report."""
    cell = harness.Cell(CELL)
    cell.config = dict(cell.config, rungs=[0.25, 0.3],
                       params=dict(cell.config["params"], n_trans=40_000,
                                   n_cols=10))
    line = harness.run_cell(cell, 2**31 + 7, 0.2, True, t_start=0.0,
                            scale=0.01)
    assert line["correct"] is True
    assert set(line["metrics"]) == OWN | SHARED
