"""The plain reference against the program's host oracle, a brute
force, and planted faults."""

import itertools

import numpy as np
import pytest

from bench import reference
from repro.core.bitmap import BitmapDB
from repro.core.oracle import mine_eclat
from repro.data.transactions import make_dataset


def _ref(db, minsup):
    bdb = BitmapDB.from_db(db, minsup, 4)
    return reference.frequent_itemsets(bdb.bitmaps, bdb.items, minsup)


@pytest.mark.parametrize("name", ["chess-like", "accidents-like",
                                  "kosarak-like", "t40-like"])
def test_reference_accepts_oracle(name):
    db, minsups = make_dataset(name, seed=0)
    minsup = minsups[-1]
    want, _ = mine_eclat(db, minsup)
    got = _ref(db, minsup)
    assert reference.compare(got, want) == {
        "missing": 0, "extra": 0, "wrong_support": 0}


def test_reference_equals_brute_force():
    rng = np.random.default_rng(5)
    db = [sorted(set(rng.integers(0, 9, rng.integers(1, 7)).tolist()))
          for _ in range(60)]
    minsup = 6
    items = sorted({i for t in db for i in t})
    want = {}
    for k in range(1, len(items) + 1):
        for combo in itertools.combinations(items, k):
            sup = sum(1 for t in db if set(combo) <= set(t))
            if sup >= minsup:
                want[frozenset(combo)] = sup
    assert _ref(db, minsup) == want


@pytest.fixture(scope="module")
def chess():
    db, minsups = make_dataset("chess-like", seed=0)
    want, _ = mine_eclat(db, minsups[-1])
    return want


def test_reference_rejects_wrong_support(chess):
    got = dict(chess)
    key = max(got, key=len)
    got[key] += 1
    assert reference.compare(got, chess)["wrong_support"] == 1


def test_reference_rejects_dropped_itemset(chess):
    got = dict(chess)
    del got[max(got, key=len)]
    assert reference.compare(got, chess)["missing"] == 1


def test_reference_rejects_extra_itemset(chess):
    got = dict(chess)
    got[frozenset({-1})] = 10**6
    assert reference.compare(got, chess)["extra"] == 1


def test_int16_control_wraps():
    # 40,000 transactions all holding item 0: an int16 sum wraps.
    rows = np.full((1, 40_000 // 32 + 1), 0xFFFFFFFF, np.uint32)
    rows[0, -1] = 0
    exact = reference.frequent_itemsets(rows, [0], 100)
    assert exact == {frozenset({0}): 40_000 // 32 * 32}
    assert reference.frequent_itemsets(rows, [0], 100,
                                       support_dtype=np.int16) == {}
