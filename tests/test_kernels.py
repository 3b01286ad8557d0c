"""Per-kernel allclose sweeps: Pallas (interpret) vs pure-jnp oracles."""

import numpy as np
import pytest
import jax.numpy as jnp
from hypothesis import given, settings, strategies as st

from repro.core.bitmap import (pack_tidlists, suffix_popcounts_np,
                               popcount32_np, unpack_row)
from repro.kernels import ops
from repro.kernels.ref import (bitmap_intersect_es_ref, bitmap_diff_es_ref,
                               bitmap_intersect_full_ref, bitmap_count_ref,
                               screen_pairs_ref, screen_and_intersect_ref,
                               screen_and_diff_ref, NL_SENTINEL)
from repro.kernels.bitmap_diff import bitmap_diff_es
from repro.kernels.nlist_merge import B_MAX


# ---------------------------------------------------------------------------
# bitmap intersection kernel: bit-exact across modes / shapes / minsup
# ---------------------------------------------------------------------------

def _random_bitmaps(rng, n_pairs, n_blocks, bw, density=0.25):
    u = rng.integers(0, 2 ** 32, (n_pairs, n_blocks, bw),
                     dtype=np.uint64).astype(np.uint32)
    m = rng.integers(0, 2 ** 32, (n_pairs, n_blocks, bw),
                     dtype=np.uint64).astype(np.uint32)
    if density < 0.5:
        u &= m
    return u


@pytest.mark.parametrize("mode", ["and", "andnot"])
@pytest.mark.parametrize("n_blocks,bw", [(1, 128), (3, 128), (5, 8)])
def test_bitmap_kernel_matches_ref(mode, n_blocks, bw):
    rng = np.random.default_rng(42)
    n_pairs = 7
    U = _random_bitmaps(rng, n_pairs, n_blocks, bw)
    V = _random_bitmaps(rng, n_pairs, n_blocks, bw)
    su = suffix_popcounts_np(U)
    sv = suffix_popcounts_np(V)
    rho = popcount32_np(U).reshape(n_pairs, -1).sum(1).astype(np.int32)
    n_trans = n_blocks * bw * 32
    for minsup in (0, 1, n_trans // 64, n_trans // 8, n_trans):
        r = bitmap_intersect_es_ref(U, V, su, sv, rho, jnp.int32(minsup),
                                    mode=mode)
        p = ops.bitmap_intersect_es(U, V, su, sv, rho, jnp.int32(minsup),
                                    mode=mode, backend="pallas")
        for name, a, b in zip(("Z", "cnt", "blocks", "alive"), r, p, strict=True):
            assert np.array_equal(np.asarray(a), np.asarray(b)), (
                mode, minsup, name)


def test_bitmap_kernel_es_aborts_and_freezes():
    """Dead pairs stop processing blocks and freeze counts (the paper's
    semantics quantised to blocks)."""
    rng = np.random.default_rng(0)
    U = _random_bitmaps(rng, 16, 6, 8, density=0.2)
    V = _random_bitmaps(rng, 16, 6, 8, density=0.2)
    su, sv = suffix_popcounts_np(U), suffix_popcounts_np(V)
    rho = np.zeros(16, np.int32)
    minsup = 6 * 8 * 32 // 4   # high threshold: most pairs die early
    Z, cnt, blocks, alive = ops.bitmap_intersect_es(
        U, V, su, sv, rho, jnp.int32(minsup), mode="and", backend="pallas")
    blocks = np.asarray(blocks)
    assert (blocks < 6).any()
    # dead pairs: output blocks beyond the abort point are zeroed
    Z = np.asarray(Z)
    for i in range(16):
        if blocks[i] < 6:
            assert not Z[i, blocks[i]:].any()


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
@pytest.mark.parametrize("mode", ["and", "andnot"])
def test_full_intersect_and_count_match_refs(backend, mode):
    """DL002 pins for the no-ES dispatches: ``ops.bitmap_intersect_full``
    vs ``bitmap_intersect_full_ref`` and ``ops.bitmap_count`` vs
    ``bitmap_count_ref``, cross-checked against a numpy oracle (the
    pallas count path reuses the ES kernel with minsup=0)."""
    rng = np.random.default_rng(7)
    U = _random_bitmaps(rng, 5, 3, 8, density=0.3)
    V = _random_bitmaps(rng, 5, 3, 8, density=0.3)
    expect = (U & V) if mode == "and" else (U & ~V)
    expect_cnt = popcount32_np(expect).reshape(5, -1).sum(1)

    Z, cnt = ops.bitmap_intersect_full(U, V, mode=mode, backend=backend)
    rZ, rcnt = bitmap_intersect_full_ref(U, V, mode=mode)
    assert np.array_equal(np.asarray(Z), expect)
    assert np.array_equal(np.asarray(Z), np.asarray(rZ))
    assert np.array_equal(np.asarray(cnt), expect_cnt)
    assert np.array_equal(np.asarray(cnt), np.asarray(rcnt))

    if mode == "and":
        c = ops.bitmap_count(U, V, backend=backend)
        assert np.array_equal(np.asarray(c), expect_cnt)
        assert np.array_equal(np.asarray(c),
                              np.asarray(bitmap_count_ref(U, V)))


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
@pytest.mark.parametrize("es", [False, True])
@pytest.mark.parametrize("mode", ["and", "andnot"])
@pytest.mark.parametrize("n_blocks,bw", [(1, 128), (3, 128), (5, 8)])
def test_fused_screen_and_intersect_matches_ref(backend, es, mode,
                                                n_blocks, bw):
    """ops.screen_and_intersect == screen_and_intersect_ref bit-for-bit
    (the ref now pins the whole dispatch, survivor-gated scatter
    included): child rows and suffix tables land at `slots` ONLY for
    pairs whose support cleared minsup and that finished alive; dead
    pairs' slots, padding slots (>= cap) and untouched store rows are
    all left untouched (ISSUE 5)."""
    rng = np.random.default_rng(11)
    cap, n_pairs = 32, 9
    store0 = _random_bitmaps(rng, cap, n_blocks, bw)
    suffix0 = suffix_popcounts_np(store0)
    ua = rng.integers(0, 12, n_pairs).astype(np.int32)
    vb = rng.integers(0, 12, n_pairs).astype(np.int32)
    slots = np.arange(12, 12 + n_pairs, dtype=np.int32)
    slots[-1] = cap + 3          # OOB sentinel: must be dropped
    rho = suffix0[ua, 0].astype(np.int32)
    n_trans = n_blocks * bw * 32
    for minsup in (0, 1, n_trans // 64, n_trans // 8):
        rows_r, suf_r, cnt_r, blocks_r, alive_r = screen_and_intersect_ref(
            store0, suffix0, ua, vb, slots, rho, jnp.int32(minsup),
            mode=mode, early_stop=es)
        rows, suffix, cnt, blocks, alive = ops.screen_and_intersect(
            jnp.asarray(store0), jnp.asarray(suffix0), ua, vb, slots, rho,
            jnp.int32(minsup), mode=mode, early_stop=es, backend=backend)
        rows, suffix = np.asarray(rows), np.asarray(suffix)
        key = (backend, es, mode, minsup)
        assert np.array_equal(np.asarray(cnt), np.asarray(cnt_r)), key
        assert np.array_equal(np.asarray(blocks), np.asarray(blocks_r)), key
        assert np.array_equal(np.asarray(alive), np.asarray(alive_r)), key
        assert np.array_equal(rows, np.asarray(rows_r)), key
        assert np.array_equal(suffix, np.asarray(suf_r)), key
        # survivor-only scatter: recompute the expected Z and check each
        # slot was written iff its pair survived the frequency gate
        es_minsup = minsup if es else 0
        Zr, _, _, _ = bitmap_intersect_es_ref(
            store0[ua], store0[vb], suffix0[ua], suffix0[vb], rho,
            jnp.int32(es_minsup), mode=mode)
        Zr = np.asarray(Zr)
        support = (np.asarray(cnt) if mode == "and"
                   else rho - np.asarray(cnt))
        keep = np.logical_and(np.asarray(alive), support >= minsup)
        for i, s in enumerate(slots):
            if s >= cap:
                continue
            if keep[i]:
                assert np.array_equal(rows[s], Zr[i]), key
                assert np.array_equal(
                    suffix[s], suffix_popcounts_np(Zr[i:i+1])[0]), key
            else:
                assert np.array_equal(rows[s], store0[s]), (key, i)
                assert np.array_equal(suffix[s], suffix0[s]), (key, i)
        untouched = [r for r in range(cap) if r not in set(slots.tolist())]
        assert np.array_equal(rows[untouched], store0[untouched]), key
        assert np.array_equal(suffix[untouched], suffix0[untouched]), key


# ---------------------------------------------------------------------------
# diffset (dEclat) kernels: bit-exact vs the ref, skip-aware work counter
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n_blocks,bw", [(1, 128), (3, 128), (6, 8)])
def test_bitmap_diff_kernel_matches_ref(n_blocks, bw):
    """Pallas diff kernel == bitmap_diff_es_ref bit-for-bit across shapes
    and minsup (ISSUE 6): Z, count, skip-aware blocks and aliveness."""
    rng = np.random.default_rng(23)
    n_pairs = 9
    U = _random_bitmaps(rng, n_pairs, n_blocks, bw)
    V = _random_bitmaps(rng, n_pairs, n_blocks, bw)
    su = suffix_popcounts_np(U)
    rho = su[:, 0].astype(np.int32)     # parent support: |d| <= rho holds
    n_trans = n_blocks * bw * 32
    for minsup in (0, 1, n_trans // 64, n_trans // 8, n_trans):
        r = bitmap_diff_es_ref(U, V, su, rho, jnp.int32(minsup))
        p = bitmap_diff_es(U, V, su, rho, jnp.int32(minsup))
        for name, a, b in zip(("Z", "cnt", "blocks", "alive"), r, p, strict=True):
            assert np.array_equal(np.asarray(a), np.asarray(b)), (
                minsup, name)


def test_diff_scan_skips_zero_mass_u_blocks():
    """The diff scan is bit-identical to the legacy andnot scan on Z /
    count / aliveness, but its work counter charges only visited blocks
    whose U suffix mass is positive (Z = U & ~V is zero wherever U is) —
    the representation saving the dense word_ops win comes from."""
    rng = np.random.default_rng(31)
    n_pairs, n_blocks, bw = 12, 6, 8
    U = _random_bitmaps(rng, n_pairs, n_blocks, bw, density=0.2)
    U[:, 1] = 0                          # skippable zero-mass U blocks
    U[:, 4] = 0
    V = _random_bitmaps(rng, n_pairs, n_blocks, bw)
    su, sv = suffix_popcounts_np(U), suffix_popcounts_np(V)
    rho = su[:, 0].astype(np.int32)
    mass = (su[:, :-1] - su[:, 1:]).astype(np.int64)
    for minsup in (0, 5, 40):
        Zd, cd, bd, ad = bitmap_diff_es_ref(U, V, su, rho,
                                            jnp.int32(minsup))
        Za, ca, ba, aa = bitmap_intersect_es_ref(U, V, su, sv, rho,
                                                 jnp.int32(minsup),
                                                 mode="andnot")
        assert np.array_equal(np.asarray(Zd), np.asarray(Za)), minsup
        assert np.array_equal(np.asarray(cd), np.asarray(ca)), minsup
        assert np.array_equal(np.asarray(ad), np.asarray(aa)), minsup
        bd, ba = np.asarray(bd), np.asarray(ba)
        assert (bd <= ba).all(), minsup
        # aliveness is a prefix property, so the andnot scan's visited
        # set is exactly range(ba[i]); the diff counter drops the
        # zero-mass members of that set
        for i in range(n_pairs):
            expect = int(((np.arange(n_blocks) < ba[i])
                          & (mass[i] > 0)).sum())
            assert bd[i] == expect, (minsup, i)


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
@pytest.mark.parametrize("es", [False, True])
@pytest.mark.parametrize("n_blocks,bw", [(1, 128), (3, 128), (5, 8)])
def test_fused_screen_and_diff_matches_ref(backend, es, n_blocks, bw):
    """ops.screen_and_diff == screen_and_diff_ref bit-for-bit, survivor
    gated scatter included (ISSUE 6): difference rows and suffix tables
    land at `slots` ONLY for pairs whose support rho - |d| cleared
    minsup and that finished alive; dead pairs' slots, padding slots
    (>= cap) and untouched store rows are all left untouched."""
    rng = np.random.default_rng(13)
    cap, n_pairs = 32, 9
    store0 = _random_bitmaps(rng, cap, n_blocks, bw)
    suffix0 = suffix_popcounts_np(store0)
    ua = rng.integers(0, 12, n_pairs).astype(np.int32)
    vb = rng.integers(0, 12, n_pairs).astype(np.int32)
    slots = np.arange(12, 12 + n_pairs, dtype=np.int32)
    slots[-1] = cap + 3          # OOB sentinel: must be dropped
    rho = suffix0[ua, 0].astype(np.int32)
    n_trans = n_blocks * bw * 32
    for minsup in (0, 1, n_trans // 64, n_trans // 8):
        rows_r, suf_r, cnt_r, blocks_r, alive_r = screen_and_diff_ref(
            store0, suffix0, ua, vb, slots, rho, jnp.int32(minsup),
            early_stop=es)
        rows, suffix, cnt, blocks, alive = ops.screen_and_diff(
            jnp.asarray(store0), jnp.asarray(suffix0), ua, vb, slots, rho,
            jnp.int32(minsup), early_stop=es, backend=backend)
        rows, suffix = np.asarray(rows), np.asarray(suffix)
        key = (backend, es, minsup)
        assert np.array_equal(np.asarray(cnt), np.asarray(cnt_r)), key
        assert np.array_equal(np.asarray(blocks), np.asarray(blocks_r)), key
        assert np.array_equal(np.asarray(alive), np.asarray(alive_r)), key
        assert np.array_equal(rows, np.asarray(rows_r)), key
        assert np.array_equal(suffix, np.asarray(suf_r)), key
        es_minsup = minsup if es else 0
        Zr, _, _, _ = bitmap_diff_es_ref(
            store0[ua], store0[vb], suffix0[ua], rho, jnp.int32(es_minsup))
        Zr = np.asarray(Zr)
        support = rho - np.asarray(cnt)
        keep = np.logical_and(np.asarray(alive), support >= minsup)
        for i, s in enumerate(slots):
            if s >= cap:
                continue
            if keep[i]:
                assert np.array_equal(rows[s], Zr[i]), key
                assert np.array_equal(
                    suffix[s], suffix_popcounts_np(Zr[i:i+1])[0]), key
            else:
                assert np.array_equal(rows[s], store0[s]), (key, i)
                assert np.array_equal(suffix[s], suffix0[s]), (key, i)
        untouched = [r for r in range(cap) if r not in set(slots.tolist())]
        assert np.array_equal(rows[untouched], store0[untouched]), key
        assert np.array_equal(suffix[untouched], suffix0[untouched]), key


def test_screen_bound_is_sound():
    rng = np.random.default_rng(1)
    U = _random_bitmaps(rng, 32, 4, 16)
    V = _random_bitmaps(rng, 32, 4, 16)
    su, sv = suffix_popcounts_np(U), suffix_popcounts_np(V)
    true_count = popcount32_np(U & V).reshape(32, -1).sum(1)
    bound, _ = screen_pairs_ref(U[:, 0], V[:, 0], su[:, 1], sv[:, 1],
                                np.zeros(32, np.int32), jnp.int32(0))
    assert (np.asarray(bound) >= true_count).all()


def test_pack_unpack_roundtrip():
    rng = np.random.default_rng(2)
    tids = sorted(rng.choice(5000, size=700, replace=False).tolist())
    packed = pack_tidlists([tids], 5000, block_words=8)
    assert unpack_row(packed[0]).tolist() == tids


@settings(max_examples=20, deadline=None)
@given(st.lists(st.integers(0, 999), min_size=0, max_size=200,
                unique=True))
def test_pack_popcount_property(tids):
    packed = pack_tidlists([sorted(tids)], 1000, block_words=4)
    assert popcount32_np(packed).sum() == len(tids)


# ---------------------------------------------------------------------------
# allocator compaction gather: bit-exact across slab ranks and backends
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["jnp", "pallas"])
@pytest.mark.parametrize("new_cap", [4, 16, 48])
def test_compact_rows_matches_ref(backend, new_cap):
    """ops.compact_rows == compact_gather_ref on rows AND suffix slabs:
    live destinations carry their source bit-for-bit, dead destinations
    (perm < 0) come up zeroed."""
    from repro.kernels.ref import compact_gather_ref

    rng = np.random.default_rng(5)
    cap = 32
    rows = rng.integers(0, 2 ** 32, (cap, 3, 8), dtype=np.uint64
                        ).astype(np.uint32)
    suffix = suffix_popcounts_np(rows)
    perm = rng.permutation(cap)[:new_cap].astype(np.int32)
    perm[::3] = -1                       # scattered dead slots
    er = np.asarray(compact_gather_ref(jnp.asarray(rows), perm))
    es = np.asarray(compact_gather_ref(jnp.asarray(suffix), perm))
    gr, gs = ops.compact_rows(jnp.asarray(rows), jnp.asarray(suffix),
                              perm, backend=backend)
    assert np.array_equal(np.asarray(gr), er), (backend, new_cap)
    assert np.array_equal(np.asarray(gs), es), (backend, new_cap)
    for i, src in enumerate(perm):
        if src >= 0:
            assert np.array_equal(er[i], rows[src])
        else:
            assert not er[i].any()


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_compact_codes_matches_ref(backend):
    from repro.kernels.ref import compact_gather_ref

    rng = np.random.default_rng(6)
    codes = rng.integers(0, 1000, (64, 3)).astype(np.int32)
    perm = np.concatenate([rng.permutation(64)[:20],
                           np.full(12, -1)]).astype(np.int32)
    e = np.asarray(compact_gather_ref(jnp.asarray(codes), perm))
    g = np.asarray(ops.compact_codes(jnp.asarray(codes), perm,
                                     backend=backend))
    assert np.array_equal(g, e), backend


# ---------------------------------------------------------------------------
# N-list kernels (PrePost+): fused extend + standalone merge vs the ref
# ---------------------------------------------------------------------------

def _random_pool(rng, cap, offs_lens):
    """Random PPC-code slab with ascending-pre extents at (off, len)."""
    codes = np.stack([rng.integers(0, 1000, cap),
                      rng.integers(0, 1000, cap),
                      rng.integers(1, 20, cap)], axis=1).astype(np.int32)
    for off, ln in offs_lens:
        seg = codes[off:off + ln]
        codes[off:off + ln] = seg[np.argsort(seg[:, 0], kind="stable")]
    return codes


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
@pytest.mark.parametrize("es", [False, True])
@pytest.mark.parametrize("lu,lv", [(8, 8), (8, 32), (32, 8)])
def test_nlist_extend_matches_ref(backend, es, lu, lv):
    """ops.nlist_extend == ref.nlist_extend_ref bit-for-bit on both
    backends: scattered child extents, lengths, supports, comparison
    counts and aliveness (ISSUE 3 acceptance)."""
    from repro.kernels.ref import nlist_extend_ref

    rng = np.random.default_rng(7)
    cap, n_pairs = 1024, 9
    u_off = rng.integers(0, 256, n_pairs).astype(np.int32)
    v_off = rng.integers(256, 512 - lv, n_pairs).astype(np.int32)
    u_len = rng.integers(1, lu + 1, n_pairs).astype(np.int32)
    v_len = rng.integers(1, lv + 1, n_pairs).astype(np.int32)
    codes = _random_pool(rng, cap, list(zip(u_off, u_len, strict=True))
                         + list(zip(v_off, v_len, strict=True)))
    out_off = (512 + lu * np.arange(n_pairs)).astype(np.int32)
    out_off[-1] = cap + 5            # OOB sentinel: must be dropped
    rho = rng.integers(0, 120, n_pairs).astype(np.int32)

    for minsup in (0, 1, 10, 80):
        r = nlist_extend_ref(jnp.asarray(codes), u_off, u_len, v_off,
                             v_len, out_off, rho, jnp.int32(minsup),
                             lu=lu, lv=lv, early_stop=es)
        g = ops.nlist_extend(jnp.asarray(codes), u_off, u_len, v_off,
                             v_len, out_off, rho, jnp.int32(minsup),
                             lu=lu, lv=lv, early_stop=es, backend=backend)
        for name, a, b in zip(("codes", "child_len", "support",
                               "comparisons", "checks", "alive"), r, g,
                               strict=True):
            assert np.array_equal(np.asarray(a), np.asarray(b)), (
                backend, es, minsup, name)
        # survivor-only scatter (ISSUE 5): only extents of pairs whose
        # support cleared minsup are written; dead pairs' extents, OOB
        # extents and untouched pool rows all stay untouched
        new_codes = np.asarray(g[0])
        child_len = np.asarray(g[1])
        support = np.asarray(g[2])
        written = set()
        for p in range(n_pairs - 1):
            if support[p] >= minsup:
                written.update(range(out_off[p], out_off[p] + child_len[p]))
        untouched = [i for i in range(cap) if i not in written]
        assert np.array_equal(new_codes[untouched], codes[untouched]), (
            backend, es, minsup)


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
@pytest.mark.parametrize("es", [False, True])
def test_nlist_presize_scatter_split_matches_ref_and_extend(backend, es):
    """The two-dispatch split (ISSUE 5 tentpole) is pinned twice over:
    ops.nlist_presize == ref.nlist_presize_ref bit-for-bit on both
    backends, and presize + tight survivor-only nlist_scatter writes
    exactly the children the one-dispatch nlist_extend would have
    (same contents, read back from tight extents)."""
    from repro.kernels.ref import (nlist_presize_ref, nlist_scatter_ref,
                                   nlist_extend_ref)

    rng = np.random.default_rng(17)
    cap, n_pairs, lu, lv = 2048, 9, 8, 32
    u_off = rng.integers(0, 256, n_pairs).astype(np.int32)
    v_off = rng.integers(256, 512 - lv, n_pairs).astype(np.int32)
    u_len = rng.integers(1, lu + 1, n_pairs).astype(np.int32)
    v_len = rng.integers(1, lv + 1, n_pairs).astype(np.int32)
    codes = _random_pool(rng, cap, list(zip(u_off, u_len, strict=True))
                         + list(zip(v_off, v_len, strict=True)))
    rho = rng.integers(0, 120, n_pairs).astype(np.int32)

    for minsup in (0, 1, 10, 80):
        r = nlist_presize_ref(jnp.asarray(codes), u_off, u_len, v_off,
                              v_len, rho, jnp.int32(minsup),
                              lu=lu, lv=lv, early_stop=es)
        g = ops.nlist_presize(jnp.asarray(codes), u_off, u_len, v_off,
                              v_len, rho, jnp.int32(minsup),
                              lu=lu, lv=lv, early_stop=es, backend=backend)
        for name, a, b in zip(("out_slot", "child_len", "support",
                               "comparisons", "checks", "alive"), r, g,
                               strict=True):
            assert np.array_equal(np.asarray(a), np.asarray(b)), (
                backend, es, minsup, name)
        out_slot, child_len, support = (np.asarray(g[0]),
                                        np.asarray(g[1]),
                                        np.asarray(g[2]))
        # host side of the split: tight extents for survivors only
        keep = support >= minsup
        out_off = np.full(n_pairs, cap, np.int32)       # dropped
        bump = 512
        for p in np.nonzero(keep)[0]:
            out_off[p] = bump
            bump += int(child_len[p])                   # TIGHT: exact len
        sc_codes, sc_len = ops.nlist_scatter(
            jnp.asarray(codes), g[0], u_off, u_len, v_off, v_len,
            out_off, lu=lu, lv=lv, backend=backend)
        rc_codes, rc_len = nlist_scatter_ref(
            jnp.asarray(codes), r[0], u_off, u_len, v_off, v_len,
            out_off, lu=lu, lv=lv)
        assert np.array_equal(np.asarray(sc_codes), np.asarray(rc_codes))
        assert np.array_equal(np.asarray(sc_len), np.asarray(rc_len))
        # the one-dispatch composition scatters the same children
        ex_off = (512 + lu * np.arange(n_pairs)).astype(np.int32)
        ex = nlist_extend_ref(jnp.asarray(codes), u_off, u_len, v_off,
                              v_len, ex_off, rho, jnp.int32(minsup),
                              lu=lu, lv=lv, early_stop=es)
        ex_codes = np.asarray(ex[0])
        sc_codes = np.asarray(sc_codes)
        assert np.array_equal(np.asarray(ex[1]), child_len)
        for p in np.nonzero(keep)[0]:
            ln = int(child_len[p])
            assert np.array_equal(sc_codes[out_off[p]:out_off[p] + ln],
                                  ex_codes[ex_off[p]:ex_off[p] + ln]), (
                backend, es, minsup, p)
        # non-survivors and the rest of the slab stay untouched
        written = set()
        for p in np.nonzero(keep)[0]:
            written.update(range(out_off[p], out_off[p] + int(child_len[p])))
        untouched = [i for i in range(cap) if i not in written]
        assert np.array_equal(sc_codes[untouched], codes[untouched])


B = B_MAX                            # the merge kernel's widest block


def _merge_batch(rng, lu, lv):
    """Padded operand rows for the merge kernel: random pairs whose
    lengths sit on and beside block edges (0, 1, b - 1, b, b + 1, the
    whole row), then five shaped pairs (on rows of several blocks):
    V walks every block while U stays on its first code (0, and 2-4
    with unit V frequencies, which the caller turns into early-stop
    aborts around V's first block edge), and U walks every block while
    V stays on its first (1)."""
    n_pairs = 16
    bu, bv = min(lu, B), min(lv, B)

    def mk(n, width):
        pre = np.sort(rng.integers(0, 4 * width, (n, width)), 1)
        post = rng.integers(0, 4 * width, (n, width))
        freq = rng.integers(1, 10, (n, width))
        return [x.astype(np.int32) for x in (pre, post, freq)]

    def edges(b, width):
        near = [0, 1, b - 1, b, b + 1, width]
        return np.asarray([x for x in near if 0 <= x <= width], np.int32)

    (up, upo, uf), (vp, vpo, vf) = mk(n_pairs, lu), mk(n_pairs, lv)
    ul = rng.choice(edges(bu, lu), n_pairs).astype(np.int32)
    vl = rng.choice(edges(bv, lv), n_pairs).astype(np.int32)
    rho = rng.integers(0, 100, n_pairs).astype(np.int32)
    # U's codes lie after every V code, never below one: V advances.
    for p in (0, 2, 3, 4):
        up[p], upo[p], vl[p] = 10 * lv, 10 * lv, lv
        ul[p] = min(lu, bu + 1)
        rho[p] = 10 ** 6
    vf[2:5] = 1
    # U's codes all precede V's first: U advances.
    up[1], vp[1] = np.arange(lu), lu + np.arange(lv)
    ul[1], vl[1] = lu, min(lv, bv + 1)
    return (up, upo, uf, vp, vpo, vf), ul, vl, rho


@pytest.mark.parametrize("es", [False, True])
@pytest.mark.parametrize("lu,lv", [(8, 32), (3 * B, 3 * B), (32, 2 * B),
                                   (2 * B, 128)])
def test_nlist_merge_pallas_matches_ref(es, lu, lv):
    """Standalone padded-batch merge: the Pallas kernel, which fetches
    its operands block by block, vs the jnp ref on all five outputs,
    with block edges crossed by either pointer and early-stop aborts
    just before, at and just after V's first block edge."""
    from repro.kernels.ref import nlist_intersect_ref

    rng = np.random.default_rng(3)
    rows, ul, vl, rho = _merge_batch(rng, lu, lv)
    bv = min(lv, B)
    for minsup in (0, 1, 20):
        # With unit frequencies and no match, the abort comes on the
        # step that skips V code rho - minsup: bv - 2, bv - 1 and bv.
        rho[2:5] = minsup + bv + np.arange(-2, 1)
        r = nlist_intersect_ref(*rows, ul, vl, rho, jnp.int32(minsup),
                                early_stop=es)
        p = ops.nlist_intersect(*rows, ul, vl, rho, jnp.int32(minsup),
                                early_stop=es, backend="pallas")
        for name, a, b in zip(("out_slot", "support", "cmps", "checks",
                               "alive"), r, p, strict=True):
            assert np.array_equal(np.asarray(a), np.asarray(b)), (
                es, minsup, name)
        checks = np.asarray(p[3])
        if lv > B:
            assert checks[0] == vl[0]           # V crossed every block
            assert (checks[2:5] == bv + np.arange(-1, 2)).all() or not es
        if lu > B:
            assert np.asarray(p[2])[1] - checks[1] == lu   # so did U


@pytest.mark.parametrize("case", ["none", "u_ends", "v_walks", "v_edge",
                                  "past_edge", "v_ends", "narrow"])
def test_fetched_codes_counts_blocks(case):
    """``fetched_codes`` against blocks counted by hand: block 0 up to
    the one after the last block read, at most the list's blocks."""
    from repro.kernels.nlist_merge import fetched_codes

    w = 4 * B
    u_adv, v_adv, u_len, v_len, lu, want = {
        "none": (0, 0, 0, 5, w, 0),                  # no comparison
        "u_ends": (1, 0, 1, w, w, 1 * B + 2 * B),    # U read 0, V read 0
        "v_walks": (0, 2 * B + 6, w, w, w, 2 * B + 4 * B),   # V read 2B+5
        "v_edge": (0, B, w, w, w, 2 * B + 2 * B),    # V read B - 1
        "past_edge": (0, B + 1, w, w, w, 2 * B + 3 * B),     # V read B
        "v_ends": (3, B, w, B, w, 2 * B + 1 * B),    # V ran out at B
        "narrow": (0, 0, 0, 0, 8, 8 + 0),            # U comes whole
    }[case]
    got = fetched_codes(np.asarray([u_adv]), np.asarray([v_adv]),
                        np.asarray([u_len]), np.asarray([v_len]), lu, w)
    assert got.tolist() == [want]


def test_fetched_codes_zero_for_empty_chunk():
    """A chunk whose lengths are all 0 fetches nothing: the kernel's
    comparisons and checks are 0, and so is the helper's count."""
    from repro.kernels.nlist_merge import fetched_codes, nlist_merge

    w = 2 * B
    rng = np.random.default_rng(4)
    rows = [rng.integers(0, 99, (4, w)).astype(np.int32) for _ in range(6)]
    zero = np.zeros(4, np.int32)
    _, _, cmps, checks, _ = nlist_merge(*rows, zero, zero, zero,
                                        jnp.int32(1), interpret=True)
    cmps, checks = np.asarray(cmps), np.asarray(checks)
    assert not cmps.any()
    assert not fetched_codes(cmps - checks, checks, zero, zero, w, w).any()


def _zmerge_loop(out_slot, u_freq, v_pre, v_post, u_len):
    """Z-merge written as the plain loop over each pair's U slots."""
    P, lu = out_slot.shape
    z = np.zeros((3, P, lu), np.int32)
    clen = np.zeros(P, np.int32)
    for p in range(P):
        k, last = 0, -1
        for i in range(u_len[p]):
            j = out_slot[p, i]
            if j == NL_SENTINEL:
                continue
            if j != last:
                k, last = k + 1, j
                z[:, p, k - 1] = v_pre[p, j], v_post[p, j], 0
            z[2, p, k - 1] += u_freq[p, i]
        clen[p] = k
    return z[0], z[1], z[2], clen


@pytest.mark.parametrize("lu,lv", [(8, 32), (32, 8), (128, 128)])
def test_nlist_zmerge_pallas_matches_ref(lu, lv):
    """The Pallas Z-merge (interpret mode) == ref._nl_zmerge == a plain
    loop on match tables with runs of slots sharing one V code,
    unmatched slots between them and rows of every length."""
    from repro.kernels.nlist_merge import nlist_zmerge
    from repro.kernels.ref import _nl_zmerge

    rng = np.random.default_rng(23)
    P = 12
    u_len = rng.integers(0, lu + 1, P).astype(np.int32)
    u_len[:2] = (0, lu)
    out_slot = np.full((P, lu), NL_SENTINEL, np.int32)
    for p in range(P):
        n = int(u_len[p])
        steps = rng.integers(0, 3, n)          # 0: same V code as before
        js = np.minimum(np.cumsum(steps), lv - 1)
        hit = rng.random(n) < 0.7
        out_slot[p, :n] = np.where(hit, js, NL_SENTINEL)
    u_freq = rng.integers(1, 9, (P, lu)).astype(np.int32)
    v_pre = rng.integers(0, 10**6, (P, lv)).astype(np.int32)
    v_post = rng.integers(0, 10**6, (P, lv)).astype(np.int32)

    want = _zmerge_loop(out_slot, u_freq, v_pre, v_post, u_len)
    got_ref = _nl_zmerge(jnp.asarray(out_slot), jnp.asarray(u_freq),
                         jnp.asarray(v_pre), jnp.asarray(v_post))
    got_pl = nlist_zmerge(jnp.asarray(out_slot), jnp.asarray(u_freq),
                          jnp.asarray(v_pre), jnp.asarray(v_post),
                          jnp.asarray(u_len), interpret=True)
    assert want[3].max() > 1 and (want[3] < u_len).any()
    for name, w, r, g in zip(("zpre", "zpost", "zfreq", "child_len"), want,
                             got_ref, got_pl, strict=True):
        assert np.array_equal(np.asarray(r), w), ("ref", name)
        assert np.array_equal(np.asarray(g), w), ("pallas", name)


@pytest.mark.parametrize("cap,lu", [(256, 8), (64, 32), (24, 32)])
def test_nl_write_matches_per_code_scatter(cap, lu):
    """ref._nl_write (one window per child, clamped into the slab) ==
    the per-code scatter it replaced: children anywhere in the slab,
    children that end at its capacity, and offsets past it dropped."""
    from repro.kernels.ref import _nl_write

    rng = np.random.default_rng(29)
    P = 9
    codes = rng.integers(0, 1000, (cap, 3)).astype(np.int32)
    zs = rng.integers(1, 1000, (3, P, lu)).astype(np.int32)
    child_len = rng.integers(0, min(lu, cap // 4) + 1, P).astype(np.int32)
    child_len[0] = min(lu, cap // 4) - 1
    # disjoint extents from the front; pair 0 ends at the capacity,
    # the last two lie past it
    out_off = np.full(P, cap, np.int32)
    out_off[0] = cap - child_len[0]
    bump = 0
    for p in range(1, P - 2):
        if bump + child_len[p] <= out_off[0]:
            out_off[p], bump = bump, bump + int(child_len[p])
    out_off[-1] = cap + 7
    zs = np.where(np.arange(lu) < child_len[:, None], zs, 0)

    dest = np.where(np.arange(lu) < child_len[:, None],
                    out_off[:, None] + np.arange(lu), cap)
    want = jnp.asarray(codes).at[jnp.asarray(dest.ravel())].set(
        jnp.asarray(np.stack(zs, axis=-1).reshape(-1, 3)), mode="drop")
    got = _nl_write(jnp.asarray(codes), *(jnp.asarray(z) for z in zs),
                    jnp.asarray(child_len), jnp.asarray(out_off))
    assert out_off[0] > cap - min(lu, cap)      # the clamped window
    assert np.array_equal(np.asarray(got), np.asarray(want))
