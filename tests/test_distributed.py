"""Distributed mining: the sharded row store, the fused shard_map
dispatch and the count-distribution miner.

The multi-device pieces run in a subprocess with
``--xla_force_host_platform_device_count=8`` so the main pytest process
keeps the real single-device view.  Mesh construction goes through
``repro.compat.make_mesh``.
"""

import random
import subprocess
import sys
import textwrap

import numpy as np
import pytest
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.compat import make_mesh


def _mesh11():
    return make_mesh((1, 1), ("data", "model"))


# ---------------------------------------------------------------------------
# Unified distributed miner (ISSUE 2): shared DeviceRowStore + one fused
# shard_map dispatch per pair chunk
# ---------------------------------------------------------------------------


def _random_db(seed, n_items=(4, 9), n_trans=(10, 60)):
    rng = random.Random(seed)
    ni = rng.randint(*n_items)
    nt = rng.randint(*n_trans)
    db = [[i for i in range(ni) if rng.random() < 0.5] for _ in range(nt)]
    db = [t for t in db if t] or [[0]]
    minsup = rng.randint(2, max(2, len(db) // 3))
    return db, minsup


@pytest.mark.parametrize("mode", ["and", "andnot"])
@pytest.mark.parametrize("early_stop", [False, True])
def test_fused_sharded_dispatch_matches_ref(early_stop, mode):
    """ops.make_screen_and_intersect_sharded == kernels.ref oracle,
    bit-exact across minsup values, the in-dispatch ES flag and both
    representations (tidset "and" / diffset "andnot", ISSUE 6) — 1
    shard here; the 8-shard version runs in the subprocess test below."""
    from repro.core.bitmap import popcount32_np
    from repro.core.rowstore import DeviceRowStore
    from repro.kernels import ops, ref

    mesh = _mesh11()
    r = np.random.default_rng(3)
    rows_np = r.integers(0, 2 ** 32, (16, 4, 4), dtype=np.uint64
                         ).astype(np.uint32)
    n = 12
    ua = r.integers(0, 16, n).astype(np.int32)
    vb = r.integers(0, 16, n).astype(np.int32)
    slots = np.arange(16, 16 + n, dtype=np.int32)
    if mode == "and":
        rho = r.integers(0, 100, n).astype(np.int32)
    else:
        # diffset invariant: |U & ~V| <= |U| = rho, so support >= 0
        rho = popcount32_np(rows_np).reshape(16, -1).sum(1).astype(
            np.int32)[ua]

    fused = ops.make_screen_and_intersect_sharded(
        mesh, tid_axes=("data", "model"), mode=mode,
        early_stop=early_stop)
    for minsup in (0, 8, 40, 200):
        store = DeviceRowStore(rows_np, capacity=32, mesh=mesh)
        rows0 = np.asarray(store.rows)
        suf0 = np.asarray(store.suffix)
        er, esuf, eb, ec, ebl, eal = ref.screen_and_intersect_sharded_ref(
            rows0, suf0, ua, vb, slots, rho, jnp.int32(minsup),
            n_shards=store.n_shards, mode=mode, early_stop=early_stop)
        gr, gs, gb, gc, gbl, gal = fused(store.rows, store.suffix, ua, vb,
                                         slots, rho, minsup)
        key = (early_stop, mode, minsup)
        assert np.array_equal(np.asarray(gb), np.asarray(eb)), key
        assert np.array_equal(np.asarray(gc), np.asarray(ec)), key
        assert np.array_equal(np.asarray(gbl), np.asarray(ebl)), key
        assert np.array_equal(np.asarray(gal), np.asarray(eal)), key
        assert np.array_equal(np.asarray(gr), np.asarray(er)), key
        assert np.array_equal(np.asarray(gs), np.asarray(esuf)), key
        # screen soundness for alive pairs (dead counts are frozen
        # partials): "and" bounds the count from above, "andnot" bounds
        # the support rho - count from above
        gb_, gc_, gal_ = np.asarray(gb), np.asarray(gc), np.asarray(gal)
        if mode == "and":
            assert (gb_[gal_] >= gc_[gal_]).all(), key
        else:
            assert (gb_[gal_] >= (rho - gc_)[gal_]).all(), key
        if not early_stop:
            assert np.asarray(gal).all(), key
            gbl_ = np.asarray(gbl)
            if mode == "and":
                # ES off: every pair walks every local block, all shards
                assert (gbl_ == store.n_blocks).all(), key
            else:
                # diffset work counter is skip-aware even with ES off:
                # only visited blocks with positive U mass are charged
                mass = popcount32_np(rows0).sum(axis=2)
                assert np.array_equal(gbl_, (mass[ua] > 0).sum(1)), key


def test_sharded_row_store_grow_preserves_sharding_and_contents():
    from repro.core.rowstore import DeviceRowStore, _local_suffix_tables

    mesh = _mesh11()
    tid_spec = ("data", "model")
    rng = np.random.default_rng(0)
    rows = rng.integers(0, 2 ** 32, (3, 2, 4), dtype=np.uint64
                        ).astype(np.uint32)
    store = DeviceRowStore(rows, capacity=4, mesh=mesh)
    # block axis padded to a multiple of the shard count
    assert store.n_blocks % store.n_shards == 0
    cap0 = store.capacity
    expected_rows = NamedSharding(mesh, P(None, tid_spec, None))
    expected_suffix = NamedSharding(mesh, P(None, tid_spec))
    assert store.rows.sharding == expected_rows
    assert store.suffix.sharding == expected_suffix
    big = store.alloc(cap0)
    assert store.capacity > cap0 and store.grows == 1
    # sharding survives growth; contents + suffix layout preserved
    assert store.rows.sharding == expected_rows
    assert store.suffix.sharding == expected_suffix
    padded = np.zeros((3, store.n_blocks, 4), np.uint32)
    padded[:, :2] = rows
    assert np.array_equal(np.asarray(store.rows[:3]), padded)
    assert np.array_equal(np.asarray(store.suffix[:3]),
                          _local_suffix_tables(padded, store.n_shards))
    store.free(big)


def test_unified_miner_one_fused_dispatch_per_chunk(monkeypatch):
    """Mirror of test_fused_engine.py's dispatch guard: every pair chunk
    is exactly ONE fused shard_map dispatch; no separate screen / count /
    materialize program exists or is called."""
    import repro.core.distributed as D
    from repro.core.oracle import mine
    from repro.kernels import ops

    for name in ("make_round_fns", "screen_round", "count_round",
                 "materialize_rep"):
        assert not hasattr(D, name), f"legacy round program {name} back"

    calls = {"fused": 0}
    real_maker = ops.make_screen_and_intersect_sharded

    def counting_maker(mesh, **kw):
        fn = real_maker(mesh, **kw)

        def wrapper(*a, **k):
            calls["fused"] += 1
            return fn(*a, **k)

        return wrapper

    def forbidden(*a, **k):
        raise AssertionError("single-device / legacy dispatch used")

    monkeypatch.setattr(ops, "make_screen_and_intersect_sharded",
                        counting_maker)
    monkeypatch.setattr(ops, "screen_and_intersect", forbidden)
    monkeypatch.setattr(ops, "screen_pairs", forbidden)
    monkeypatch.setattr(ops, "bitmap_intersect_es", forbidden)
    monkeypatch.setattr(ops, "bitmap_intersect_full", forbidden)

    db, minsup = _random_db(3, n_items=(8, 8), n_trans=(25, 30))
    m = D.DistributedMiner(_mesh11(), early_stop=True, block_words=1,
                           pair_chunk=4)
    out, stats = m.mine(db, minsup)
    assert calls["fused"] == stats.device_calls
    assert stats.device_calls >= 2     # small pair_chunk forces chunking
    expected, _ = mine(db, minsup, "eclat", early_stop=True)
    assert out == expected


@pytest.mark.parametrize("es", [False, True])
def test_unified_miner_matches_oracle_single_device(es):
    from repro.core.distributed import DistributedMiner
    from repro.core.eclat import BitmapMiner
    from repro.core.oracle import mine

    mesh = _mesh11()
    for seed in range(6):
        db, minsup = _random_db(seed)
        expected, _ = mine(db, minsup, "eclat", early_stop=es)
        out, stats = DistributedMiner(mesh, early_stop=es, capacity=512,
                                      block_words=2).mine(db, minsup)
        assert out == expected, (seed, es)
        # work + scatter telemetry is engine-invariant (ISSUE 5): the
        # non-ES work baseline comes from the REAL block count and the
        # survivor-only scatter count equals the frequent children
        _, st1 = BitmapMiner(scheme="eclat", early_stop=es,
                             block_words=2).mine(db, minsup)
        assert stats.word_ops_full == st1.word_ops_full, (seed, es)
        n_children = sum(1 for s in out if len(s) >= 2)
        assert stats.child_scatters == st1.child_scatters == n_children
        assert stats.scatter_words == st1.scatter_words, (seed, es)
        if es:
            # the distributed screen is attributed, even single-block
            assert stats.screened_out >= 0
            assert stats.candidates >= stats.screened_out


MULTI_DEVICE_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys
    sys.path.insert(0, "src")
    import random
    import numpy as np
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.compat import make_mesh
    from repro.core.oracle import mine_bruteforce
    from repro.core.distributed import DistributedMiner
    from repro.core.rowstore import DeviceRowStore, _local_suffix_tables
    from repro.core.bitmap import popcount32_np
    from repro.kernels import ops, ref

    assert jax.device_count() == 8
    mesh = make_mesh((4, 2), ("data", "model"))

    # unified miner == oracle on 8 devices, ES on/off, ONE fused dispatch
    # per pair chunk (wrapped counter vs stats.device_calls); work and
    # scatter telemetry must be shard-count invariant (ISSUE 5):
    # word_ops_full from the REAL block count (the 8-shard store pads
    # its block axis, which used to inflate it) and child_scatters ==
    # frequent children, equal to the single-device run on the same DB
    from repro.core.eclat import BitmapMiner
    rng = random.Random(7)
    nonzero_wof = 0
    for trial in range(4):
        n_items = rng.randint(4, 9)
        n_trans = rng.randint(10, 60)
        db = [[i for i in range(n_items) if rng.random() < 0.5]
              for _ in range(n_trans)]
        db = [t for t in db if t]
        minsup = rng.randint(2, max(2, len(db) // 3))
        bf = mine_bruteforce(db, minsup)
        n_children = sum(1 for s in bf if len(s) >= 2)
        for es in (False, True):
            m = DistributedMiner(mesh, early_stop=es, capacity=512,
                                 block_words=2)
            calls = [0]
            inner = m._fused
            def counted(*a, _i=inner, _c=calls, **k):
                _c[0] += 1
                return _i(*a, **k)
            m._fused = counted
            out, st = m.mine(db, minsup)
            assert out == bf, (trial, es)
            assert calls[0] == st.device_calls >= 1, (trial, es)
            assert st.child_scatters == n_children, (trial, es)
            _, st1 = BitmapMiner(scheme="eclat", early_stop=es,
                                 block_words=2).mine(db, minsup)
            assert st.word_ops_full == st1.word_ops_full, (trial, es)
            assert st.child_scatters == st1.child_scatters, (trial, es)
            assert st.scatter_words == st1.scatter_words, (trial, es)
            # the numerator is unpadded too: ES off scans exactly the
            # real blocks, ES on never scans more (saved_frac >= 0)
            if es:
                assert st.word_ops <= st.word_ops_full, (trial, es)
                assert st.word_ops_saved_frac >= 0.0, (trial, es)
            else:
                assert st.word_ops == st.word_ops_full, (trial, es)
            nonzero_wof += st.word_ops_full > 0
    assert nonzero_wof > 0      # the padding bug would have inflated these

    # density-adaptive representation switching (ISSUE 6) on 8 shards:
    # declat and adaptive miners match the bruteforce oracle exactly and
    # every pair chunk is still ONE fused dispatch ("and" + "andnot"
    # wrappers together account for all device calls)
    for trial in range(2):
        n_items = rng.randint(5, 8)
        n_trans = rng.randint(20, 50)
        db = [[i for i in range(n_items) if rng.random() < 0.6]
              for _ in range(n_trans)]
        db = [t for t in db if t]
        minsup = rng.randint(2, max(2, len(db) // 3))
        bf = mine_bruteforce(db, minsup)
        for scheme, dd in (("declat", None), ("adaptive", 0.3)):
            for es in (False, True):
                m = DistributedMiner(mesh, early_stop=es, capacity=512,
                                     block_words=2, scheme=scheme,
                                     diff_density=dd,
                                     diff_hysteresis=0.1)
                calls = [0]
                for attr in ("_fused", "_fused_diff"):
                    def counted(*a, _i=getattr(m, attr), _c=calls, **k):
                        _c[0] += 1
                        return _i(*a, **k)
                    setattr(m, attr, counted)
                out, st = m.mine(db, minsup)
                assert out == bf, (trial, scheme, es)
                assert calls[0] == st.device_calls >= 1, (trial, scheme, es)

    # fused dispatch is bit-exact against the 8-shard ref oracle,
    # in-dispatch shard-local ES on and off, both representations
    r = np.random.default_rng(0)
    rows_np = r.integers(0, 2**32, (16, 8, 4), dtype=np.uint64
                         ).astype(np.uint32)
    ua = r.integers(0, 16, 12).astype(np.int32)
    vb = r.integers(0, 16, 12).astype(np.int32)
    slots = np.arange(16, 28, dtype=np.int32)
    rho_and = r.integers(0, 100, 12).astype(np.int32)
    rho_diff = popcount32_np(rows_np).reshape(16, -1).sum(1).astype(
        np.int32)[ua]
    for mode, rho in (("and", rho_and), ("andnot", rho_diff)):
        for es in (False, True):
            for minsup in (0, 64, 400):
                store = DeviceRowStore(rows_np, capacity=32, mesh=mesh)
                assert store.n_shards == 8
                rows0, suf0 = np.asarray(store.rows), np.asarray(store.suffix)
                er, esuf, eb, ec, ebl, eal = ref.screen_and_intersect_sharded_ref(
                    rows0, suf0, ua, vb, slots, rho, np.int32(minsup),
                    n_shards=8, mode=mode, early_stop=es)
                fused = ops.make_screen_and_intersect_sharded(
                    mesh, tid_axes=("data", "model"), mode=mode,
                    early_stop=es)
                gr, gs, gb, gc, gbl, gal = fused(
                    store.rows, store.suffix, ua, vb, slots, rho, minsup)
                key = (mode, es, minsup)
                assert np.array_equal(np.asarray(gb), np.asarray(eb)), key
                assert np.array_equal(np.asarray(gc), np.asarray(ec)), key
                assert np.array_equal(np.asarray(gbl), np.asarray(ebl)), key
                assert np.array_equal(np.asarray(gal), np.asarray(eal)), key
                assert np.array_equal(np.asarray(gr), np.asarray(er)), key
                assert np.array_equal(np.asarray(gs), np.asarray(esuf)), key

    # sharded slab growth preserves the NamedSharding + contents
    store2 = DeviceRowStore(rows_np, capacity=32, mesh=mesh)
    cap0 = store2.capacity
    big = store2.alloc(cap0)
    assert store2.grows == 1
    assert store2.rows.sharding == NamedSharding(
        mesh, P(None, ("data", "model"), None))
    assert np.array_equal(np.asarray(store2.rows[:16]), rows_np)
    assert np.array_equal(np.asarray(store2.suffix[:16]),
                          _local_suffix_tables(rows_np, 8))

    # compaction SHRINKS the sharded slab back, preserving sharding,
    # live contents bit-for-bit, and remapping slots densely
    store2.free(big)
    before_rows = np.asarray(store2.rows[:16])
    before_suf = np.asarray(store2.suffix[:16])
    mapping = store2.compact(reserve=4)
    assert store2.capacity < cap0 * 2 and store2.compactions == 1
    assert store2.rows.sharding == NamedSharding(
        mesh, P(None, ("data", "model"), None))
    new_ids = mapping[np.arange(16)]
    assert (new_ids >= 0).all()
    assert np.array_equal(np.asarray(store2.rows)[new_ids], before_rows)
    assert np.array_equal(np.asarray(store2.suffix)[new_ids], before_suf)

    print("MULTI_DEVICE_OK")
""")


@pytest.mark.slow
def test_distributed_miner_multi_device():
    proc = subprocess.run([sys.executable, "-c", MULTI_DEVICE_SCRIPT],
                          capture_output=True, text=True, timeout=900,
                          cwd=".")
    assert "MULTI_DEVICE_OK" in proc.stdout, proc.stderr[-3000:]
