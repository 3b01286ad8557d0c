"""The paper's running example + headline-effect checks.

The invariants I1-I5 (every miner x {ES on/off} x backend == brute
force, ES never increases comparisons, device PrePost+ counters equal
the oracle's) are pinned by the property-based cross-engine harness in
tests/test_equivalence.py; this module keeps the paper's worked example
(Table I / Examples 3.1-4.2) and the qualitative ES-savings claims.
"""

import pytest

from repro.core.oracle import (mine, mine_bruteforce, MINERS)
from repro.core.eclat import mine_bitmap

PAPER_DB = [list(t) for t in
            ["ade", "bcd", "ace", "acde", "ae", "acd", "bc", "acde",
             "bce", "ade"]]


# ---------------------------------------------------------------------------
# the paper's running example (Table I, minSup=3, 15 frequent itemsets)
# ---------------------------------------------------------------------------

def test_paper_example_bruteforce():
    out = mine_bruteforce(PAPER_DB, 3)
    assert len(out) == 15
    assert out[frozenset("ac")] == 4
    assert out[frozenset("dac")] == 3
    assert out[frozenset("ec")] == 4          # Example 4.1
    assert frozenset("bd") not in out         # Examples 3.1 / 4.2


@pytest.mark.parametrize("scheme", sorted(MINERS))
@pytest.mark.parametrize("es", [False, True])
def test_paper_example_all_schemes(scheme, es):
    expected = mine_bruteforce(PAPER_DB, 3)
    out, stats = mine(PAPER_DB, 3, scheme, early_stop=es)
    assert out == expected
    assert stats.nodes == 15


@pytest.mark.parametrize("scheme", sorted(MINERS))
def test_es_reduces_comparisons(scheme):
    _, std = mine(PAPER_DB, 3, scheme, early_stop=False)
    _, es = mine(PAPER_DB, 3, scheme, early_stop=True)
    assert es.comparisons <= std.comparisons          # I3
    assert es.es_aborts > 0                           # ES actually fired


def test_bitmap_word_ops_savings_on_sparse_data():
    """The paper's headline effect: sparse, high candidate/node-ratio data
    shows large ES work savings in the device engine."""
    from repro.data import make_dataset
    db, minsups = make_dataset("retail-like")
    out_es, st_es = mine_bitmap(db, minsups[2], "eclat", True, block_words=8)
    out_no, st_no = mine_bitmap(db, minsups[2], "eclat", False,
                                block_words=8)
    assert out_es == out_no
    assert st_es.word_ops < st_no.word_ops
    assert st_es.word_ops_saved_frac > 0.15
    assert st_es.kernel_aborts > 0 and st_es.screened_out > 0


def test_distributed_screen_bound_tighter_than_central():
    """Sum of per-shard minima <= min of sums: the distributed screen is
    at least as tight as the centralized bound (docs/ARCHITECTURE.md)."""
    import numpy as np
    from repro.core.bitmap import popcount32_np
    rng = np.random.default_rng(0)
    u = rng.integers(0, 2**32, (8, 16), dtype=np.uint64).astype(np.uint32)
    v = rng.integers(0, 2**32, (8, 16), dtype=np.uint64).astype(np.uint32)
    # two shards of 4 blocks each; bound from block 1.. (suffix after blk0)
    cu = popcount32_np(u).reshape(2, 4, -1).sum(-1)
    cv = popcount32_np(v).reshape(2, 4, -1).sum(-1)
    local = sum(min(cu[s, 1:].sum(), cv[s, 1:].sum()) for s in range(2))
    central = min(cu[:, 1:].sum(), cv[:, 1:].sum())
    assert local <= central
