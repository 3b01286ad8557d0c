#!/usr/bin/env python3
"""Run a cell with the correctness check's control in the program's
place, on several seeds in one process, and print each run's compared
numbers.

    python3 bench/control.py --workload <cell> --seeds 11 12 13 [--seconds 1]

The control is ``engines/reference_int16.py``: the plain reference
summing supports in int16 where the configurations state int32.  Each
run goes through the same set-up, window and comparison as
``run.py``; its ``checks`` must exceed their limits.  The benchmark's
own runs never run it.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
CONTROL = "reference_int16"
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=1.0)
    args = ap.parse_args(argv)

    from bench import harness
    from repro.cache import configure_compile_cache

    cell = harness.Cell(args.workload, engine=CONTROL)
    if not harness.chips_ok(int(cell.workload["chips"])):
        return 2
    configure_compile_cache()
    for seed in args.seeds:
        line = harness.run_cell(cell, seed, args.seconds, False,
                                t_start=time.perf_counter())
        print(json.dumps({"workload": args.workload, "engine": CONTROL,
                          "seed": seed, "correct": line["correct"],
                          "attempted": line["attempted"],
                          "checks": line["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
