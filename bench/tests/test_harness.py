"""The harness's set-up, job loop, check and metric readers, driven at
a tiny scale on the CPU without the chip check, and with the timed path
broken underneath so that ``correct`` must come out false."""

import time
import types

import numpy as np
import pytest

from bench import harness

SCALE = 0.01
SEED = 2**31 + 11          # beyond 32 signed bits


def _run(cell, trace=False, seconds=0.2):
    return harness.run_cell(cell, SEED, seconds, trace,
                            t_start=time.perf_counter(), scale=SCALE)


@pytest.mark.parametrize("workload", ["kosarak-paper.mid",
                                      "accidents-paper.low",
                                      "kosarak-paper.top"])
def test_cell_runs_correct(workload):
    line = _run(harness.Cell(workload))
    assert line["correct"] is True
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert set(line["metrics"]) == {"setup_s", "mine_s"}   # CPU: no HBM
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert line["window_compiles"] == 0
    assert list(line)[-1] == "checks"
    assert line["checks"]["wrong_itemsets"] == {"value": 0, "limit": 0}


def test_traced_run_reports_counter_metrics():
    line = _run(harness.Cell("kosarak-paper.mid"), trace=True)
    assert line["correct"] is True
    got = line["metrics"]
    # A CPU trace has no device plane: the trace readers return nothing.
    assert set(got) == {"miners.device_calls", "miners.es_saved_frac",
                        "sched.assemble_ms", "sched.resolve_ms",
                        "alloc.slab_gib"}
    assert got["miners.device_calls"]["unit"] == "calls"
    assert 0 < got["miners.es_saved_frac"]["value"] < 1
    assert "busy_s" not in line["device"]


def _broken(cell, corrupt):
    """The cell with its engine's job result passed through ``corrupt``."""
    real = cell.engine

    def job(miner, bdb, minsup):
        out, st = real.job(miner, bdb, minsup)
        return corrupt(dict(out)), st

    cell.engine = types.SimpleNamespace(build=real.build, job=job,
                                        SPANS=real.SPANS)
    return cell


def _altered(out):
    key = max(out, key=len)
    out[key] += 1
    return out


def _dropped(out):
    del out[max(out, key=len)]
    return out


@pytest.mark.parametrize("corrupt", [_altered, _dropped],
                         ids=["answer_altered", "itemset_dropped"])
def test_broken_answer_is_not_correct(corrupt):
    line = _run(_broken(harness.Cell("kosarak-paper.top"), corrupt))
    assert line["correct"] is False
    assert line["failed"] == line["attempted"]
    assert line["checks"]["wrong_itemsets"]["value"] >= line["attempted"]


def test_half_the_data_left_out_is_not_correct():
    cell = harness.Cell("kosarak-paper.top")
    real = cell.engine

    def job(miner, bdb, minsup):
        half = bdb.bitmaps.copy()
        half[..., half.shape[-1] // 2:] = 0
        return real.job(miner, type(bdb)(
            items=bdb.items, bitmaps=half, supports=bdb.supports,
            n_trans=bdb.n_trans, minsup=bdb.minsup,
            block_words=bdb.block_words), minsup)

    cell.engine = types.SimpleNamespace(build=real.build, job=job, SPANS=[])
    line = _run(cell)
    assert line["correct"] is False


def test_control_is_not_correct():
    # Supports must pass 32,767 for int16 to wrap: accidents at 20%
    # scale has 68,037 records and minsup 23,132.
    cell = harness.Cell("accidents-paper.low", engine="reference_int16")
    line = harness.run_cell(cell, SEED, 0.1, False,
                            t_start=time.perf_counter(), scale=0.2)
    assert line["correct"] is False
    assert line["checks"]["wrong_itemsets"]["value"] > 0


def test_unknown_workload_is_refused():
    with pytest.raises(KeyError):
        harness.Cell("no-such.cell")


def test_instrumented_restores_methods():
    class Owner:
        def f(self):
            return 3

    before = Owner.f
    with harness.instrumented([("x", Owner, "f"), ("y", Owner, "missing")]):
        assert Owner.f is not before and Owner().f() == 3
    assert Owner.f is before


def test_pack_is_seeded():
    cell = harness.Cell("kosarak-paper.top")
    cfg = cell.config
    params = dict(cfg["params"])
    data_seed = params.pop("data_seed")
    a, _ = harness.data.pack(cfg["generator"], params, cfg["rungs"],
                             seed=data_seed, order_seed=SEED, scale=SCALE)
    b, _ = harness.data.pack(cfg["generator"], params, cfg["rungs"],
                             seed=data_seed, order_seed=SEED, scale=SCALE)
    np.testing.assert_array_equal(a.bitmaps, b.bitmaps)
