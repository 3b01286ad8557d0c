"""PrePost+ on packed rows: the N-lists built from the bits, the packed
job against the plain reference and the oracle's work counters, and
the device-memory cap on the N-list pair chunk.

* ``prepost.build_nlists`` builds the PPC-tree's N-lists from a
  ``BitmapDB`` with no loop over transactions; they must equal
  ``oracle.PPCTree(db, minsup).nlists`` code for code, item order and
  tie-breaks included;
* ``DevicePrePost.mine_packed`` must return the plain reference's
  itemsets and supports, and the oracle's ``comparisons`` and
  ``es_checks`` (the merge runs on the same tree in the same order);
* ``mine(db)`` is packing plus ``mine_packed``;
* a chunk whose operands sit in the largest N-list bucket is narrowed
  on a 16 GiB device; smaller buckets keep ``pair_chunk``.
"""

import random
import sys
from pathlib import Path

import numpy as np
import pytest

from repro.core.bitmap import BitmapDB, NL_PAIR_CHUNK_BUCKETS, nl_hbm_pair_cap
from repro.core.oracle import PPCTree, mine_prepost
from repro.core.prepost import DevicePrePost, build_nlists

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from bench import reference  # noqa: E402

GIB16 = 16 * 2 ** 30


def _dense(seed=0, n_trans=300, n_cols=74, vals=3):
    """Fixed-width categorical rows: one of ``vals`` values per column,
    skewed towards value 0 so that some itemsets are long."""
    rng = np.random.default_rng(seed)
    v = np.minimum(rng.geometric(0.7, size=(n_trans, n_cols)) - 1, vals - 1)
    return (np.arange(n_cols) * vals + v).tolist()


def _powerlaw(seed=1, n_trans=400, n_items=60):
    rng = np.random.default_rng(seed)
    pop = 1.0 / np.arange(1, n_items + 1) ** 1.1
    pop /= pop.sum()
    return [sorted(set(rng.choice(n_items, size=rng.poisson(6) + 1,
                                  p=pop).tolist())) for _ in range(n_trans)]


def _ties(seed=2):
    """Many items of equal support: the order falls to the repr
    tie-break, whose order differs from the integer order (10 < 9)."""
    rng = random.Random(seed)
    base = [[i for i in range(14) if rng.random() < 0.5] for _ in range(40)]
    return base + base[::-1]


CASES = {
    "dense74": (_dense(), 150),
    "powerlaw": (_powerlaw(), 12),
    "ties": (_ties(), 10),
    "empty": ([[0, 1], [2], [1, 3]], 5),
}


def _small(seed):
    rng = random.Random(seed)
    db = [[i for i in range(10) if rng.random() < 0.45] for _ in range(60)]
    return [t for t in db if t], 8


@pytest.mark.parametrize("case", sorted(CASES))
def test_vectorised_nlists_equal_the_oracle_tree(case):
    db, minsup = CASES[case]
    lists = build_nlists(BitmapDB.from_db(db, minsup), minsup)
    tree = PPCTree(db, minsup)
    assert lists.items == tree.order_desc
    assert lists.supports.tolist() == [tree.item_support[it]
                                       for it in tree.order_desc]
    ends = np.cumsum(lists.lengths)
    got = [lists.codes[e - n:e] for n, e in zip(lists.lengths, ends)]
    for it, codes in zip(lists.items, got, strict=True):
        assert [tuple(c) for c in codes.tolist()] == tree.nlists[it], it
    if case == "empty":
        assert lists.items == [] and lists.codes.shape == (0, 3)


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
@pytest.mark.parametrize("early_stop", [True, False])
def test_mine_packed_matches_reference_and_oracle_counts(backend,
                                                         early_stop):
    db, minsup = _small(5)
    bdb = BitmapDB.from_db(db, minsup)
    miner = DevicePrePost(early_stop=early_stop, backend=backend,
                          pair_chunk=16)
    got, st = miner.mine_packed(bdb, minsup)
    assert got == reference.frequent_itemsets(bdb.bitmaps, bdb.items,
                                              minsup)
    want, ost = mine_prepost(db, minsup, early_stop=early_stop)
    assert got == want
    assert (st.comparisons, st.es_checks) == (ost.comparisons,
                                              ost.es_checks)
    assert st.candidates == ost.candidates
    assert 0 < st.nl_codes <= st.nl_gather_codes
    assert st.build_s > 0


def test_mine_is_packing_then_mine_packed():
    db, minsup = _small(6)
    a, sa = DevicePrePost(pair_chunk=16).mine(db, minsup)
    b, sb = DevicePrePost(pair_chunk=16).mine_packed(
        BitmapDB.from_db(db, minsup), minsup)
    assert a == b
    assert (sa.comparisons, sa.device_calls) == (sb.comparisons,
                                                 sb.device_calls)


def test_mine_packed_leaves_out_rows_below_minsup():
    """A database packed at a lower minsup mines the same as one packed
    at the job's minsup."""
    db, minsup = _small(7)
    low = BitmapDB.from_db(db, minsup - 4)
    got, _ = DevicePrePost(pair_chunk=16).mine_packed(low, minsup)
    assert got == mine_prepost(db, minsup)[0]


def test_hbm_cap_narrows_only_the_largest_bucket():
    assert nl_hbm_pair_cap(32768, GIB16) < 8192
    assert nl_hbm_pair_cap(32768, GIB16) in NL_PAIR_CHUNK_BUCKETS
    for bucket in (8, 128, 2048, 8192):
        assert nl_hbm_pair_cap(bucket, GIB16) >= 8192
    miner = DevicePrePost()                      # pair_chunk 8192
    miner._memory_bytes = GIB16
    small = {"u_len": np.array([5, 300, 7000]),
             "v_len": np.array([40, 20, 8000])}
    assert miner.chunk_widths(small) is None     # fixed pair_chunk strides
    mixed = {"u_len": np.array([5, 20000, 30]),
             "v_len": np.array([40, 10, 22253])}
    widths = miner.chunk_widths(mixed)
    cap = nl_hbm_pair_cap(32768, GIB16)
    assert widths.tolist() == [8192, cap, cap]


def test_capped_chunks_keep_results_and_counts():
    """Narrowed chunks split the work differently, never the result or
    the oracle's counts."""
    db, minsup = _small(8)
    want, ost = mine_prepost(db, minsup, early_stop=True)
    wide, sw = DevicePrePost(pair_chunk=256).mine(db, minsup)
    miner = DevicePrePost(pair_chunk=256)
    miner._memory_bytes = 2 * 64 * 12 * 4 * 8     # 64-pair chunks
    got, st = miner.mine(db, minsup)
    assert miner._widths and set(miner._widths.values()) == {64}
    assert got == wide == want
    assert st.comparisons == sw.comparisons == ost.comparisons
    assert st.device_calls > sw.device_calls
