"""Dispatch-pipeline edge cases (ISSUE 7).

Covers the in-flight ring in ``core.frontier.FrontierScheduler``:
leaf-only drain groups, compaction landing while groups are in flight
(remap must reach pending handles, and only pending ones), the
deterministic-order guard (pipelined vs serial ``inflight=1`` emit the
same itemsets with identical order-invariant accounting), the reserve
invariant generalised over pending groups, the occupancy metric, and
per-bucket chunk-width autotuning (same results, fewer device calls,
bucketed dispatch widths only).
"""

import random

import numpy as np
import pytest

import repro.core.eclat as eclat_mod
from repro.core.bitmap import (NL_PAIR_CHUNK_BUCKETS, PAIR_CHUNK_BUCKETS,
                               chunk_width_for)
from repro.core.eclat import BitmapMiner, mine_bitmap
from repro.core.frontier import ClassNode, FrontierScheduler
from repro.core.oracle import mine_bruteforce
from repro.core.prepost import mine_prepost_device
from repro.data.transactions import gen_powerlaw_baskets


def _random_db(seed, n_items=12, n_trans=80, p=0.35):
    rng = random.Random(seed)
    db = [[i for i in range(n_items) if rng.random() < p]
          for _ in range(n_trans)]
    return [t for t in db if t]


# Counters that are invariant to drain-group composition (each pair's
# device work is independent of which chunk it rides in); the
# composition-dependent ones — device_calls, grows, compactions,
# peak_live — may legitimately differ between pipelined and serial runs.
_BITMAP_INVARIANT = ("candidates", "nodes", "word_ops", "word_ops_full",
                     "screened_out", "kernel_aborts", "child_scatters",
                     "scatter_words")
_NLIST_INVARIANT = ("candidates", "nodes", "comparisons", "es_checks",
                    "es_aborts", "child_scatters", "scatter_words")


# ---------------------------------------------------------------------------
# deterministic-order guard: pipelined == serial results + accounting
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("scheme", ["eclat", "declat", "adaptive"])
def test_pipelined_matches_serial_bitmap(scheme):
    """inflight=3 vs inflight=1 on chunk sizes small enough to force
    real overlap: identical itemsets (== brute force) and identical
    order-invariant counters; occupancy is the discriminator (0.0
    serial, > 0 pipelined)."""
    kw = dict(diff_density=0.3) if scheme == "adaptive" else {}
    for seed in (0, 1):
        db = _random_db(seed)
        ms = 4
        expected = mine_bruteforce(db, ms)
        out1, st1 = mine_bitmap(db, ms, scheme=scheme, block_words=1,
                                pair_chunk=8, inflight=1, **kw)
        out3, st3 = mine_bitmap(db, ms, scheme=scheme, block_words=1,
                                pair_chunk=8, inflight=3, **kw)
        assert out1 == expected and out3 == expected, (scheme, seed)
        for f in _BITMAP_INVARIANT:
            assert getattr(st1, f) == getattr(st3, f), (scheme, seed, f)
        assert st1.ring_overlap_frac == 0.0
        assert st3.ring_overlap_frac > 0.0
        assert st1.inflight_groups == 1 and st3.inflight_groups == 3


def test_pipelined_matches_serial_prepost():
    for seed in (0, 1):
        db = _random_db(seed)
        ms = 4
        expected = mine_bruteforce(db, ms)
        out1, st1 = mine_prepost_device(db, ms, pair_chunk=4, inflight=1)
        out3, st3 = mine_prepost_device(db, ms, pair_chunk=4, inflight=3)
        assert out1 == expected and out3 == expected, seed
        for f in _NLIST_INVARIANT:
            assert getattr(st1, f) == getattr(st3, f), (seed, f)
        assert st1.ring_overlap_frac == 0.0
        assert st3.ring_overlap_frac > 0.0


def test_pipelined_traversal_is_deterministic():
    """Two identical pipelined runs emit the same itemsets in the same
    order with the same full accounting dict (timing fields aside) —
    the ring changes batching, never determinism."""
    db = _random_db(2)
    ms = 4
    runs = []
    for _ in range(2):
        out, st = mine_bitmap(db, ms, block_words=1, pair_chunk=8,
                              inflight=3)
        d = st.as_dict()
        for timing in ("runtime_s", "assemble_s", "resolve_s"):
            d.pop(timing, None)
        runs.append((list(out.items()), d))
    assert runs[0] == runs[1]


# ---------------------------------------------------------------------------
# leaf-only drain groups
# ---------------------------------------------------------------------------

class _LeafClient:
    """Minimal client: records releases; evaluate_pairs must never run."""

    def __init__(self):
        self.released = []
        self.evaluated = 0

    def release(self, klass):
        self.released.append(klass.itemsets)

    def evaluate_pairs(self, cols):
        self.evaluated += 1
        return []

    def pair_columns(self, klass, ia, ib):
        return {"x": np.zeros(ia.size, np.int32)}

    def make_class(self, parent, children):
        raise AssertionError("no children expected")

    def emit(self, itemset, support):
        raise AssertionError("nothing to emit")

    def maybe_compact(self, reserve):
        return None


def test_leaf_only_drain_groups_terminate_cleanly():
    """A frontier of only leaf classes (< 2 members) produces empty
    drain groups: the pipelined loop must release every leaf and
    terminate without dispatching or hanging the ring."""
    client = _LeafClient()
    sched = FrontierScheduler(client, pair_chunk=4, inflight=2)
    for k in range(5):
        sched.push(ClassNode(itemsets=[(k,)],
                             rows=np.asarray([k], np.int32),
                             supports=np.asarray([1], np.int32)))
    root = ClassNode(itemsets=[(99,)], rows=np.asarray([99], np.int32),
                     supports=np.asarray([1], np.int32))
    sched.run(root)
    assert client.evaluated == 0
    assert len(client.released) == 6
    assert sched.groups_dispatched == 0
    assert sched.ring_overlap_frac == 0.0


def test_leaf_groups_interleaved_with_real_groups():
    """Leaves interleaved in the stack are released inline during the
    refill loop while real groups pipeline around them — results still
    exact."""
    db = _random_db(3, n_items=10, p=0.3)
    ms = 3
    out, st = mine_bitmap(db, ms, block_words=1, pair_chunk=4, inflight=3)
    assert out == mine_bruteforce(db, ms)
    assert st.ring_overlap_frac > 0.0


# ---------------------------------------------------------------------------
# compaction while groups are in flight
# ---------------------------------------------------------------------------

def test_compaction_remaps_pending_handles_only(monkeypatch):
    """Forced compaction (threshold 1.0) with a deep ring: the old->new
    slot mapping must rewrite the pending result handles of in-flight
    groups (their child slots move) and the mined output must stay
    exact.  Retired handles are popped before the next compaction point,
    so remap never touches one — asserted via remap call bookkeeping."""
    remap_calls = {"pending": 0, "resolved": 0}
    real_remap = eclat_mod.PendingPairResult.remap
    real_resolve = eclat_mod.PendingPairResult.resolve

    def remap_spy(self, mapping):
        if getattr(self, "_resolved", False):
            remap_calls["resolved"] += 1
        else:
            remap_calls["pending"] += 1
        return real_remap(self, mapping)

    def resolve_spy(self):
        self._resolved = True
        return real_resolve(self)

    monkeypatch.setattr(eclat_mod.PendingPairResult, "remap", remap_spy)
    monkeypatch.setattr(eclat_mod.PendingPairResult, "resolve", resolve_spy)
    # __slots__ on the handle has no _resolved; widen via a subclass.
    class _Handle(eclat_mod.PendingPairResult):
        _resolved = False
    monkeypatch.setattr(eclat_mod, "PendingPairResult", _Handle)

    db = gen_powerlaw_baskets(n_trans=120, n_items=60, avg_trans_len=5,
                              seed=0)
    ms = 3
    out, st = BitmapMiner(scheme="eclat", early_stop=True, block_words=2,
                          pair_chunk=16, compact_occupancy=1.0,
                          inflight=3).mine(db, ms)
    assert out == mine_bruteforce(db, ms)
    assert st.compactions > 0
    assert remap_calls["pending"] > 0      # a compaction crossed the ring
    assert remap_calls["resolved"] == 0    # never a retired handle


def test_forced_compaction_pipelined_all_small_chunks():
    """Compaction landing mid-pipeline on every engine: exact results."""
    db = _random_db(4, n_items=10, p=0.35)
    ms = 3
    expected = mine_bruteforce(db, ms)
    out, _ = mine_bitmap(db, ms, scheme="adaptive", diff_density=0.3,
                         block_words=1, pair_chunk=8, inflight=3,
                         compact_occupancy=1.0)
    assert out == expected
    out, _ = mine_prepost_device(db, ms, pair_chunk=4, inflight=3,
                                 compact_occupancy=1.0)
    assert out == expected


def test_pipelined_reserve_covers_pending_groups(monkeypatch):
    """ISSUE 5's reserve invariant, generalised: with groups in flight
    the reserve passed to ``maybe_compact`` must cover the new group's
    pairs PLUS every pending group's, so a fired compaction never
    forces a grow before the group's own chunks finish allocating."""
    events = []
    real_eval = BitmapMiner.evaluate_pairs
    real_comp = BitmapMiner.maybe_compact

    def eval_spy(self, cols):
        r = real_eval(self, cols)
        events.append(("eval", self._store.grows, int(cols["ua"].size)))
        return r

    def comp_spy(self, reserve):
        m = real_comp(self, reserve)
        events.append(("compact", self._store.grows, m is not None,
                       int(reserve)))
        return m

    monkeypatch.setattr(BitmapMiner, "evaluate_pairs", eval_spy)
    monkeypatch.setattr(BitmapMiner, "maybe_compact", comp_spy)

    db = gen_powerlaw_baskets(n_trans=120, n_items=60, avg_trans_len=5,
                              seed=0)
    out, stats = BitmapMiner(
        scheme="eclat", early_stop=True, block_words=2, pair_chunk=64,
        compact_occupancy=1.0, inflight=2).mine(db, 3)
    assert out == mine_bruteforce(db, 3)
    assert stats.compactions > 0

    groups, cur = [], None
    for ev in events:
        if ev[0] == "compact":
            if cur is not None:
                groups.append(cur)
            cur = {"grows": ev[1], "fired": ev[2], "reserve": ev[3],
                   "pairs": 0, "grows_after": ev[1]}
        else:
            cur["pairs"] += ev[2]
            cur["grows_after"] = ev[1]
    groups.append(cur)
    for g in groups:
        assert g["reserve"] >= g["pairs"], g   # >= : pending groups add
        if g["fired"]:
            assert g["grows_after"] == g["grows"], g


# ---------------------------------------------------------------------------
# chunk-width autotuning
# ---------------------------------------------------------------------------

def test_chunk_width_for_properties():
    # reference-size operands keep the base width (snapped to a bucket)
    assert chunk_width_for(1024, 1024, PAIR_CHUNK_BUCKETS, 1024) == 1024
    # operands 16x smaller than reference widen 16x
    assert chunk_width_for(64, 1024, PAIR_CHUNK_BUCKETS, 1024) == 16384
    # bigger-than-reference operands never narrow below base
    assert chunk_width_for(4096, 1024, PAIR_CHUNK_BUCKETS, 1024) == 1024
    # widths are monotone non-increasing in operand size
    widths = [chunk_width_for(w, 256, NL_PAIR_CHUNK_BUCKETS, 384)
              for w in (24, 96, 384, 1536, 6144)]
    assert widths == sorted(widths, reverse=True)
    # and always members of the bucket table (or the base floor)
    for w in widths:
        assert w in NL_PAIR_CHUNK_BUCKETS or w == 256
    # capped at the table maximum
    assert (chunk_width_for(1, 262144, PAIR_CHUNK_BUCKETS, 1024)
            == PAIR_CHUNK_BUCKETS[-1])


def test_autotune_same_results_fewer_dispatches():
    """Autotuning widens small-operand chunks: device_calls drop while
    the per-pair work counters (word_ops / comparisons / scatter_words)
    are unchanged — grouping moves padding, never work."""
    db = _random_db(0)
    ms = 4
    expected = mine_bruteforce(db, ms)

    out_off, st_off = mine_bitmap(db, ms, block_words=1, pair_chunk=8,
                                  autotune_chunk=False)
    out_on, st_on = mine_bitmap(db, ms, block_words=1, pair_chunk=8,
                                autotune_chunk=True)
    assert out_off == expected and out_on == expected
    assert st_on.device_calls < st_off.device_calls
    assert st_on.word_ops == st_off.word_ops
    assert st_on.scatter_words == st_off.scatter_words

    p_off, sp_off = mine_prepost_device(db, ms, pair_chunk=4,
                                        autotune_chunk=False)
    p_on, sp_on = mine_prepost_device(db, ms, pair_chunk=4,
                                      autotune_chunk=True)
    assert p_off == expected and p_on == expected
    assert sp_on.device_calls < sp_off.device_calls
    assert sp_on.comparisons == sp_off.comparisons
    assert sp_on.scatter_words == sp_off.scatter_words


def test_scheduler_chunk_slices_respect_width_caps():
    """The greedy slicer never builds a chunk bigger than the width cap
    of any member (caps are non-increasing post-sort)."""
    sched = FrontierScheduler(object(), pair_chunk=64)
    widths = np.asarray([8] * 10 + [4] * 7 + [2] * 5)
    slices = sched._chunk_slices(widths.size, widths)
    covered = []
    for _lo, sl in slices:
        size = sl.stop - sl.start
        assert size <= int(widths[sl.start:sl.stop].min())
        covered.extend(range(sl.start, sl.stop))
    assert covered == list(range(widths.size))


def test_autotuned_dispatch_widths_stay_bucketed(monkeypatch):
    """With autotuning on, every fused bitmap dispatch still receives a
    width from PAIR_CHUNK_BUCKETS — the compile cache stays bounded."""
    from repro.kernels import ops

    seen = set()
    real = ops.screen_and_intersect

    def spy(rows, suffix, ua, *a, **k):
        seen.add(int(ua.size))
        return real(rows, suffix, ua, *a, **k)

    monkeypatch.setattr(ops, "screen_and_intersect", spy)
    db = _random_db(1)
    out, _ = mine_bitmap(db, 4, block_words=1, pair_chunk=8,
                         autotune_chunk=True)
    assert out == mine_bruteforce(db, 4)
    assert seen and seen <= set(PAIR_CHUNK_BUCKETS)


# ---------------------------------------------------------------------------
# occupancy metric semantics
# ---------------------------------------------------------------------------

def test_occupancy_zero_iff_serial():
    db = _random_db(5)
    ms = 4
    _, st1 = mine_bitmap(db, ms, block_words=1, pair_chunk=8, inflight=1)
    _, st2 = mine_bitmap(db, ms, block_words=1, pair_chunk=8, inflight=2)
    assert st1.ring_overlap_frac == 0.0
    assert 0.0 < st2.ring_overlap_frac <= 1.0
    d = st2.as_dict()
    assert d["inflight_groups"] == 2
    assert d["ring_overlap_frac"] == round(st2.ring_overlap_frac, 4)
    assert "assemble_s" in d and "resolve_s" in d
