"""The benchmark's copy of the streams and packer against the program's."""

import numpy as np
import pytest

from bench import data, reference
from repro.data import transactions


@pytest.mark.parametrize("name", sorted(transactions.PAPER_REPLICAS))
def test_copied_packer_matches_program(name):
    gen, params, rungs = transactions.PAPER_REPLICAS[name]
    want, want_ms = transactions.stream_paper_dataset(name, scale=0.01,
                                                      seed=0)
    got, got_ms = data.pack(gen, params, rungs, seed=0, scale=0.01)
    assert got_ms == want_ms
    assert got.items == want.items
    assert got.n_trans == want.n_trans and got.minsup == want.minsup
    np.testing.assert_array_equal(got.supports, want.supports)
    np.testing.assert_array_equal(got.bitmaps, want.bitmaps)


def test_order_seed_permutes_the_same_transactions():
    # Two run seeds: the same items and supports, so the same itemsets
    # and the same work, in transactions whose order differs.
    gen, params, rungs = transactions.PAPER_REPLICAS["kosarak-paper"]
    a, ms = data.pack(gen, params, rungs, seed=0, order_seed=11, scale=0.01)
    b, _ = data.pack(gen, params, rungs, seed=0, order_seed=12, scale=0.01)
    plain, _ = data.pack(gen, params, rungs, seed=0, scale=0.01)
    assert a.items == b.items == plain.items
    np.testing.assert_array_equal(a.supports, b.supports)
    assert not np.array_equal(a.bitmaps, b.bitmaps)
    assert not np.array_equal(a.bitmaps, plain.bitmaps)
    want = reference.frequent_itemsets(plain.bitmaps, plain.items, ms[-1])
    assert want
    for db in (a, b):
        assert reference.frequent_itemsets(db.bitmaps, db.items,
                                           ms[-1]) == want


def test_rung_is_the_frequent_suffix():
    gen, params, rungs = transactions.PAPER_REPLICAS["kosarak-paper"]
    bdb, ms = data.pack(gen, params, rungs, seed=4, scale=0.01)
    sub = data.rung(bdb, ms[-1])
    assert sub.n_items and (sub.supports >= ms[-1]).all()
    assert sub.n_items == int((bdb.supports >= ms[-1]).sum())
