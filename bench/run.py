#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is a ``workloads`` entry of ``BENCHMARK.json``.  Set-up packs
the cell's replica from ``--seed``, builds one miner and runs warm-up
jobs until a job compiles nothing; the window then runs mining jobs
back to back for ``--seconds``.  ``--trace 0`` reports the end-to-end
metrics, ``--trace 1`` the per-layer ones, read from the jobs' counters
and a profiler trace of the window.  Every run compares every job's
result with the plain reference and prints each compared number
beside its limit, on standard error and under ``checks``.

The last line of standard output is the JSON result.  The command exits
non-zero and prints no result when JAX finds no TPU or fewer chips
than the cell asks for.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    from bench import harness
    from repro.cache import configure_compile_cache

    cell = harness.Cell(args.workload)
    if not harness.chips_ok(int(cell.workload["chips"])):
        return 2
    configure_compile_cache()
    line = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                            t_start=T_START)
    harness.print_result(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
