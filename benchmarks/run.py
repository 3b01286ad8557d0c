"""Benchmark harness: one section per paper table/figure + kernel micro.

Prints ``name,us_per_call,derived`` CSV.  By default the paper sweep
runs the two largest minsups per dataset; ``--full`` runs the ladder.
"""

from __future__ import annotations

import argparse
import sys


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="all 4 minsup levels on all 9 datasets")
    ap.add_argument("--datasets", default="",
                    help="comma-separated replica names (default: all)")
    ap.add_argument("--sections", default="paper,kernels")
    args = ap.parse_args()
    sections = set(args.sections.split(","))

    print("name,us_per_call,derived")

    if "paper" in sections:
        from benchmarks.bench_paper import run_dataset, csv_rows, \
            table_iv, figures
        from repro.data import make_dataset, DATASET_REPLICAS
        names = (args.datasets.split(",") if args.datasets
                 else list(DATASET_REPLICAS))
        all_rows = []
        for name in names:
            _, minsups = make_dataset(name)
            levels = minsups[1:] if args.full else minsups[2:]
            rows = run_dataset(name, levels)
            all_rows.extend(rows)
            for line in csv_rows(rows):
                print(line)
        print("\n# Table IV analogue", file=sys.stderr)
        print(table_iv(all_rows), file=sys.stderr)
        print("\n# Figures 7-15 analogue", file=sys.stderr)
        print(figures(all_rows), file=sys.stderr)

    if "kernels" in sections:
        from benchmarks.bench_kernels import bench_bitmap, bench_nlist
        for line in bench_bitmap() + bench_nlist():
            print(line)


if __name__ == "__main__":
    main()
