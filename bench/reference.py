"""Plain reference: every frequent itemset of packed rows, with its support.

Derived from ``chip_smoke.py::host_check`` (support by AND + popcount
over the packed rows, level-wise apriori-gen candidates with the
subset prune), turned into a stand-alone miner so that a run compares
whole itemset -> support maps.  It imports nothing of the program and
reads only the numpy rows and the item ids the benchmark packed.

``support_dtype`` is the integer type supports are summed in.  The
configurations state exact int32 supports; the control of the check
is this same reference summing in int16, the nearest precision below,
which wraps past 32,767 and so must fail the comparison.
"""

from __future__ import annotations

from typing import Dict, FrozenSet, Hashable, Sequence

import numpy as np

Supports = Dict[FrozenSet[Hashable], int]


def _supports(acc: np.ndarray, rows: np.ndarray, dtype) -> np.ndarray:
    """Support of ``acc & rows[k]`` for every ``k``."""
    return np.bitwise_count(acc[None] & rows).reshape(
        rows.shape[0], -1).sum(axis=1, dtype=dtype)


def frequent_itemsets(bitmaps: np.ndarray, items: Sequence[Hashable],
                      minsup: int, support_dtype=np.int64) -> Supports:
    """Apriori over bit rows: ``bitmaps[r]`` holds the transactions of
    ``items[r]`` as packed bits of any shape."""
    rows = np.ascontiguousarray(bitmaps).reshape(len(items), -1)
    ones = np.full(rows.shape[1], np.iinfo(rows.dtype).max, rows.dtype)
    sup1 = _supports(ones, rows, support_dtype)
    out: Supports = {}
    level: Dict[tuple, np.ndarray] = {}      # sorted row tuple -> its AND
    for r in range(len(items)):
        if sup1[r] >= minsup:
            out[frozenset((items[r],))] = int(sup1[r])
            level[(r,)] = rows[r]
    while level:
        by_prefix: Dict[tuple, list] = {}
        for t in level:
            by_prefix.setdefault(t[:-1], []).append(t[-1])
        nxt: Dict[tuple, np.ndarray] = {}
        for prefix, lasts in by_prefix.items():
            lasts.sort()
            for i, a in enumerate(lasts[:-1]):
                cands = [prefix + (a, b) for b in lasts[i + 1:]]
                cands = [c for c in cands                 # apriori prune
                         if all(c[:k] + c[k + 1:] in level
                                for k in range(len(c) - 2))]
                if not cands:
                    continue
                acc = level[prefix + (a,)]
                sups = _supports(acc, rows[[c[-1] for c in cands]],
                                 support_dtype)
                for c, s in zip(cands, sups, strict=True):
                    if s >= minsup:
                        nxt[c] = acc & rows[c[-1]]
                        out[frozenset(items[x] for x in c)] = int(s)
        level = nxt
    return out


def compare(got: Supports, want: Supports) -> Dict[str, int]:
    """Itemsets missing from ``got``, extra in it, and reported with a
    support other than ``want``'s."""
    missing = sum(1 for s in want if s not in got)
    extra = sum(1 for s in got if s not in want)
    wrong = sum(1 for s, v in got.items() if s in want and want[s] != v)
    return {"missing": missing, "extra": extra, "wrong_support": wrong}
