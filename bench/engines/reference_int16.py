"""Control of the correctness check: the plain reference put in the
program's place, summing supports in int16 where the configurations
state int32.  Its supports wrap past 32,767, so a run with it must come
out not correct.  ``bench/control.py`` runs it; no cell names it.
"""

from __future__ import annotations

import numpy as np

from bench import reference

SPANS: list = []


def build(settings: dict):
    return None


def job(_miner, bdb, minsup: int):
    out = reference.frequent_itemsets(bdb.bitmaps, bdb.items, minsup,
                                      support_dtype=np.int16)
    return out, {"itemsets": len(out)}
