"""Pure-jnp oracles for every Pallas kernel in this package.

Each ``*_ref`` function defines the exact semantics its kernel must
reproduce bit-for-bit (integer kernels) or within tolerance (float
kernels).  The refs are also the *production CPU path*: on hosts without
a TPU the miners and models call these (they are fully vectorized jnp),
while ``ops.py`` routes to the Pallas kernels on TPU.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.core.bitmap import (popcount32, suffix_popcounts,
                               NL_SENTINEL as _NL)

# ---------------------------------------------------------------------------
# Blocked early-stopping bitmap intersection (Eclat "and" / dEclat "andnot")
# ---------------------------------------------------------------------------
#
# Semantics (shared with kernels/bitmap_intersect.py):
#   * blocks are processed in order; a pair is "alive" until its ES bound
#     drops below minsup;
#   * block k's output/count/work are produced iff the pair is alive at the
#     START of block k;
#   * counts freeze at death (a dead pair is *provably* infrequent, its
#     partial count is never interpreted as a support);
#   * mode "and":    Z = U & V,  bound_k = count_k + min(sufU[k+1], sufV[k+1])
#   * mode "andnot": Z = U & ~V, bound_k = rho_parent - count_k
#     (dEclat: support(Pxy) = rho(Px) - |D(Pxy)| decreases as diffs emit)
#   * minsup <= 0 disables early stopping (the non-ES baselines).


def _blocked_es_scan(U, V, suffix_u, suffix_v, rho_parent, thr, *,
                     mode: str):
    """Shared blocked-ES scan with a PER-PAIR threshold vector.

    ``thr int32 (n_pairs,)``: a pair dies when its running bound drops
    below its own threshold.  The single-device path passes the
    broadcast scalar minsup; the sharded path passes the conservative
    shard-local threshold ``minsup - slack`` derived from the screen's
    per-pair slack (see ``screen_and_intersect_sharded_ref``).  A
    threshold of INT32_MIN never kills (bounds are >= 0): that is the
    ES-disabled path.  Returns ``(Z, counts, blocks_done, alive)``."""
    if mode not in ("and", "andnot"):
        raise ValueError(f"bad mode {mode!r}")
    n_pairs = U.shape[0]
    thr = jnp.asarray(thr, jnp.int32)

    u_t = jnp.swapaxes(U, 0, 1)                     # (nb, n_pairs, bw)
    v_t = jnp.swapaxes(V, 0, 1)
    su_next = jnp.swapaxes(suffix_u[:, 1:], 0, 1)   # (nb, n_pairs)
    sv_next = jnp.swapaxes(suffix_v[:, 1:], 0, 1)

    def step(carry, xs):
        cnt, alive, blocks = carry
        u_k, v_k, su_k, sv_k = xs
        z_k = u_k & (v_k if mode == "and" else ~v_k)
        pc = popcount32(z_k).sum(axis=-1)
        cnt_new = jnp.where(alive, cnt + pc, cnt)
        blocks = blocks + alive.astype(jnp.int32)
        if mode == "and":
            bound = cnt_new + jnp.minimum(su_k, sv_k)
        else:
            bound = rho_parent.astype(jnp.int32) - cnt_new
        alive_new = jnp.logical_and(alive, bound >= thr)
        z_out = jnp.where(alive[:, None], z_k, jnp.uint32(0))
        return (cnt_new, alive_new, blocks), z_out

    init = (jnp.zeros((n_pairs,), jnp.int32),
            jnp.ones((n_pairs,), jnp.bool_),
            jnp.zeros((n_pairs,), jnp.int32))
    (cnt, alive, blocks), z_stack = jax.lax.scan(
        step, init, (u_t, v_t, su_next, sv_next))
    Z = jnp.swapaxes(z_stack, 0, 1)
    return Z, cnt, blocks, alive


@functools.partial(jax.jit, static_argnames=("mode",))
def bitmap_intersect_es_ref(
    U: jnp.ndarray,            # uint32 (n_pairs, n_blocks, bw)
    V: jnp.ndarray,            # uint32 (n_pairs, n_blocks, bw)
    suffix_u: jnp.ndarray,     # int32  (n_pairs, n_blocks + 1)
    suffix_v: jnp.ndarray,     # int32  (n_pairs, n_blocks + 1)
    rho_parent: jnp.ndarray,   # int32  (n_pairs,)  (used by "andnot")
    minsup: jnp.ndarray,       # int32  scalar
    *,
    mode: str = "and",
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Returns (Z, counts, blocks_done, alive_final)."""
    n_pairs = U.shape[0]
    thr = jnp.broadcast_to(jnp.asarray(minsup, jnp.int32), (n_pairs,))
    return _blocked_es_scan(U, V, suffix_u, suffix_v, rho_parent, thr,
                            mode=mode)


# ---------------------------------------------------------------------------
# Blocked diffset difference with zero-block skipping (dEclat, ISSUE 6)
# ---------------------------------------------------------------------------
#
# The dedicated diffset scan shares Z/count/alive semantics with
# ``_blocked_es_scan(mode="andnot")`` bit-for-bit; only the *work
# counter* differs.  ``Z = U & ~V`` is identically zero on any block
# where the U operand has no set bits, and diffset rows are exactly the
# operands that go sparse on dense data (|d| = sup(parent) - sup(child)
# shrinks as the class deepens), so a diffset engine skips those blocks
# outright — the per-block U mass is free from the suffix table
# (``su[k] - su[k+1]``).  ``blocks_done`` therefore counts only the
# *nonzero-mass* blocks a live pair visits: that is the word_ops
# numerator, the device analogue of the paper's #comparisons for the
# DIFFERENCE_ES path.  Zero-mass blocks contribute no popcount and
# cannot change the bound, so skipping them never perturbs counts,
# aliveness or the scatter.


def _blocked_diff_scan(U, V, suffix_u, rho_parent, thr):
    """Blocked dEclat difference scan with a PER-PAIR threshold vector.

    ``thr int32 (n_pairs,)``: a pair dies when its running difference
    bound ``rho_parent - count`` drops below its own threshold
    (``sup(Pxy) = rho(Px) - |D(Pxy)|`` only decreases as diff words
    emit).  In valid mining ``count <= rho_parent`` always, so a
    threshold of 0 never kills: that is the ES-disabled path.  Returns
    ``(Z, counts, blocks_done, alive)`` where ``blocks_done`` is the
    skip-aware work counter documented above."""
    n_pairs = U.shape[0]
    thr = jnp.asarray(thr, jnp.int32)
    rho = rho_parent.astype(jnp.int32)

    u_t = jnp.swapaxes(U, 0, 1)                     # (nb, n_pairs, bw)
    v_t = jnp.swapaxes(V, 0, 1)
    mass = (suffix_u[:, :-1] - suffix_u[:, 1:]).astype(jnp.int32)
    m_t = jnp.swapaxes(mass, 0, 1)                  # (nb, n_pairs)

    def step(carry, xs):
        cnt, alive, blocks = carry
        u_k, v_k, m_k = xs
        z_k = u_k & ~v_k
        pc = popcount32(z_k).sum(axis=-1)
        cnt_new = jnp.where(alive, cnt + pc, cnt)
        blocks = blocks + jnp.logical_and(alive, m_k > 0).astype(jnp.int32)
        bound = rho - cnt_new
        alive_new = jnp.logical_and(alive, bound >= thr)
        z_out = jnp.where(alive[:, None], z_k, jnp.uint32(0))
        return (cnt_new, alive_new, blocks), z_out

    init = (jnp.zeros((n_pairs,), jnp.int32),
            jnp.ones((n_pairs,), jnp.bool_),
            jnp.zeros((n_pairs,), jnp.int32))
    (cnt, alive, blocks), z_stack = jax.lax.scan(
        step, init, (u_t, v_t, m_t))
    Z = jnp.swapaxes(z_stack, 0, 1)
    return Z, cnt, blocks, alive


@jax.jit
def bitmap_diff_es_ref(
    U: jnp.ndarray,            # uint32 (n_pairs, n_blocks, bw)
    V: jnp.ndarray,            # uint32 (n_pairs, n_blocks, bw)
    suffix_u: jnp.ndarray,     # int32  (n_pairs, n_blocks + 1)
    rho_parent: jnp.ndarray,   # int32  (n_pairs,)
    minsup: jnp.ndarray,       # int32  scalar; <= 0 disables ES
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Blocked dEclat difference ``Z = U & ~V`` on the difference bound
    ``rho_parent - count`` with zero-block skipping.  Z/count/alive are
    bit-identical to ``bitmap_intersect_es_ref(mode="andnot")``; only
    ``blocks_done`` differs (it skips zero-mass U blocks).  Returns
    ``(Z, counts, blocks_done, alive_final)``."""
    n_pairs = U.shape[0]
    thr = jnp.broadcast_to(jnp.asarray(minsup, jnp.int32), (n_pairs,))
    return _blocked_diff_scan(U, V, suffix_u, rho_parent, thr)


def _survivor_mask(cnt, alive, rho_parent, minsup, *, mode: str):
    """The scatter gate shared by every fused dispatch (ISSUE 5).

    A pair's child is materialised iff its exact support clears minsup
    AND it finished its scan alive — a dead pair's count is a frozen
    partial, which in "andnot" mode *overestimates* the support
    (``rho - cnt``), so aliveness is load-bearing, not an optimisation.
    With ES disabled ``alive`` is identically True and the mask reduces
    to plain frequency."""
    support = cnt if mode == "and" else rho_parent.astype(jnp.int32) - cnt
    return jnp.logical_and(alive, support >= jnp.asarray(minsup, jnp.int32))


@functools.partial(jax.jit, static_argnames=("mode", "early_stop"))
def screen_and_intersect_ref(
    rows: jnp.ndarray,         # uint32 (capacity, n_blocks, bw) row store
    suffix: jnp.ndarray,       # int32  (capacity, n_blocks + 1)
    ua: jnp.ndarray,           # int32  (n_pairs,)  U operand row indices
    vb: jnp.ndarray,           # int32  (n_pairs,)  V operand row indices
    slots: jnp.ndarray,        # int32  (n_pairs,)  child dest rows (OOB drop)
    rho_parent: jnp.ndarray,   # int32  (n_pairs,)
    minsup: jnp.ndarray,       # int32  scalar (ES threshold AND scatter gate)
    *,
    mode: str = "and",
    early_stop: bool = True,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray,
           jnp.ndarray]:
    """Fused screen + blocked ES intersection over a device row store —
    the full single-device dispatch oracle, scatter included.

    Operands are *gathered by row index* from ``rows``/``suffix`` instead of
    being materialised by the host.  The one-block screen of the old
    two-dispatch path is exactly the ``k = 0`` iteration of the blocked ES
    scan — after block 0 the running bound equals the screen bound
    ``|U0 op V0| (+ min(sufU[1], sufV[1]) | rho - c0)`` — so fusing them
    changes the dispatch count, never the semantics: a screened-out pair is
    simply one that dies with ``blocks_done == 1``.

    The child scatter is **survivor-only** (ISSUE 5): the count phase of
    the dispatch completes first and gates the scatter phase — a child
    row and its suffix table are written at ``slots[i]`` only when pair
    ``i``'s support clears ``minsup`` (and, under ES, it finished its
    scan alive).  Dead candidates cost zero scatter words; their slots
    (and slots ``>= capacity`` — pair padding) are left untouched.
    ``early_stop=False`` disables the in-scan abort but NOT the
    frequency gate.

    Returns ``(rows, suffix, counts, blocks_done, alive)``.
    """
    U = jnp.take(rows, ua, axis=0)
    V = jnp.take(rows, vb, axis=0)
    su = jnp.take(suffix, ua, axis=0)
    sv = jnp.take(suffix, vb, axis=0)
    es_minsup = minsup if early_stop else jnp.int32(0)
    Z, cnt, blocks, alive = bitmap_intersect_es_ref(
        U, V, su, sv, rho_parent, es_minsup, mode=mode)
    keep = _survivor_mask(cnt, alive, rho_parent, minsup, mode=mode)
    cap = rows.shape[0]
    slots_eff = jnp.where(keep, slots, jnp.int32(cap))
    child_suffix = suffix_popcounts(Z)
    rows = rows.at[slots_eff].set(Z, mode="drop")
    suffix = suffix.at[slots_eff].set(child_suffix, mode="drop")
    return rows, suffix, cnt, blocks, alive


@functools.partial(jax.jit, static_argnames=("early_stop",))
def screen_and_diff_ref(
    rows: jnp.ndarray,         # uint32 (capacity, n_blocks, bw) row store
    suffix: jnp.ndarray,       # int32  (capacity, n_blocks + 1)
    ua: jnp.ndarray,           # int32  (n_pairs,)  U operand row indices
    vb: jnp.ndarray,           # int32  (n_pairs,)  V operand row indices
    slots: jnp.ndarray,        # int32  (n_pairs,)  child dest rows (OOB drop)
    rho_parent: jnp.ndarray,   # int32  (n_pairs,)  parent support
    minsup: jnp.ndarray,       # int32  scalar (ES threshold AND scatter gate)
    *,
    early_stop: bool = True,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray,
           jnp.ndarray]:
    """Fused screen + blocked dEclat difference over a device row store —
    the diffset dispatch oracle (ISSUE 6), scatter included.

    The diffset sibling of :func:`screen_and_intersect_ref`: operands are
    gathered by row index, the blocked scan runs on the *difference*
    bound ``rho_parent - count`` (block 0 IS the one-block screen — the
    bound after block 0 equals the screen bound ``rho - |U0 & ~V0|``),
    and the child scatter is survivor-only under exactly the same mask.
    Because ``support = rho - count`` the very same dispatch converts a
    tidset subtree to diffsets: pass tidset operands ``U = T(a),
    V = T(b)`` and the scattered child ``Z = T(a) & ~T(b)`` is the
    level-2 diffset ``d(ab)`` (complement against the parent), so an
    adaptive representation flip costs no extra round trip.

    ``blocks_done`` is the skip-aware work counter of
    :func:`bitmap_diff_es_ref`: only *nonzero-mass* U blocks a live pair
    visits are charged (zero-mass blocks can never change the output —
    that sparsity is exactly why diffsets win on dense data).
    Z/count/alive — and therefore the result set — stay bit-identical
    to ``screen_and_intersect_ref(mode="andnot")`` on the same
    operands.

    Returns ``(rows, suffix, counts, blocks_done, alive)``.
    """
    U = jnp.take(rows, ua, axis=0)
    V = jnp.take(rows, vb, axis=0)
    su = jnp.take(suffix, ua, axis=0)
    es_minsup = minsup if early_stop else jnp.int32(0)
    Z, cnt, blocks, alive = bitmap_diff_es_ref(
        U, V, su, rho_parent, es_minsup)
    keep = _survivor_mask(cnt, alive, rho_parent, minsup, mode="andnot")
    cap = rows.shape[0]
    slots_eff = jnp.where(keep, slots, jnp.int32(cap))
    child_suffix = suffix_popcounts(Z)
    rows = rows.at[slots_eff].set(Z, mode="drop")
    suffix = suffix.at[slots_eff].set(child_suffix, mode="drop")
    return rows, suffix, cnt, blocks, alive


@functools.partial(jax.jit,
                   static_argnames=("n_shards", "n_cls", "mode",
                                    "early_stop"))
def screen_and_intersect_sharded_ref(
    rows: jnp.ndarray,         # uint32 (capacity, n_blocks, bw) row store
    suffix: jnp.ndarray,       # int32  (capacity, n_shards*(nb_local+1))
    ua: jnp.ndarray,           # int32  (n_pairs,)  U operand row indices
    vb: jnp.ndarray,           # int32  (n_pairs,)  V operand row indices
    slots: jnp.ndarray,        # int32  (n_pairs,)  child dest rows (OOB drop)
    rho_parent: jnp.ndarray,   # int32  (n_pairs,)  parent support ("andnot")
    minsup: jnp.ndarray,       # int32  scalar (in-dispatch ES threshold)
    n_real_blocks=None,        # int32  scalar: unpadded block count
    *,
    n_shards: int,
    n_cls: int = 1,
    mode: str = "and",
    early_stop: bool = True,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray,
           jnp.ndarray, jnp.ndarray]:
    """Oracle for the sharded fused dispatch (ISSUE 2 unification,
    in-dispatch block ES added by ISSUE 4).

    Pins the exact semantics ``ops.make_screen_and_intersect_sharded``
    must reproduce bit-for-bit when the block axis of ``rows`` is sharded
    into ``n_shards`` contiguous shards of ``nb_local = n_blocks //
    n_shards`` blocks each, and ``suffix`` holds the per-shard local
    suffix tables concatenated along axis 1 (shard ``s`` owns columns
    ``[s*(nbl+1), (s+1)*(nbl+1))`` — ``DeviceRowStore``'s sharded
    layout).  One dispatch per pair chunk computes, per pair:

    * ``count`` — the psum of per-shard popcounts of ``Z = U op V``
      (the exact global support whenever the pair stayed alive);
    * ``bound`` — the *two-level distributed screen*: each shard refines
      with its own block 0, so the global bound is the psum of per-shard
      one-block bounds — mode "and":
      ``sum_s (|U0_s op V0_s| + min(sufU_s[1], sufV_s[1]))``
      (sum of per-shard minima <= minimum of sums: tighter than the
      centralized screen), mode "andnot": ``rho_parent - sum_s |U0_s &
      ~V0_s|``;
    * **shard-local block ES** (``early_stop=True``): each shard walks
      its local blocks with the shared blocked-ES scan, but against the
      conservative threshold ``thr_s = minsup - slack_s`` where
      ``slack_s = sum_{s' != s} min(sufU_s'[0], sufV_s'[0])`` is the
      screen's per-pair slack — the mass every OTHER shard could still
      contribute.  A shard whose local bound drops below ``thr_s`` has
      *proven* the pair globally infrequent and stops scanning
      mid-dispatch (the sharded instantiation of the paper's
      INTERSECT_ES); its count freezes and the blocks past the abort
      point scatter as zeros.  For "andnot" the local bound
      ``rho_parent - cnt_s`` already dominates the global support, so
      ``thr_s = minsup`` with no slack term.

    and scatters the child rows plus their per-shard suffix tables into
    the store at ``slots`` — **survivor-only** (ISSUE 5): the psum'd
    count/alive phase of the dispatch completes first and gates the
    shard-local scatter phase, so a child is written only when its
    exact global support clears minsup and every shard finished its
    scan alive.  A pair whose ``bound`` misses minsup, or that any
    shard aborted, is provably infrequent: it costs zero scatter words
    and the host never materialises its class (its slot, like slots
    ``>= capacity`` — pair padding — is left untouched).

    Returns ``(rows, suffix, bound, count, blocks, alive)`` where
    ``blocks`` is the total *real* local blocks scanned across shards —
    the distributed word-op numerator.  The store pads its block axis up
    to the shard count, and a viable pair scans its shard's all-zero
    pad tail (pads can never change counts or aliveness: their operand
    mass is zero); ``n_real_blocks`` (default: no padding) lets the
    dispatch clamp each shard's scan count to its real blocks, so
    ``word_ops`` and ``word_ops_full`` are consistently unpadded and
    an ES-off run reports exactly ``word_ops == word_ops_full``.
    In mode "andnot" ``blocks`` is instead the skip-aware diffset work
    counter (ISSUE 6): only the *nonzero-mass* U blocks each shard's
    scan visited are charged, matching :func:`bitmap_diff_es_ref` —
    pads are zero-mass, so they discount themselves.
    ``alive`` is True iff every shard finished its scan alive.

    2-D ``(block, cls)`` mesh (ISSUE 9): ``n_cls`` pins the cls-split
    semantics.  The pair chunk is cut into ``n_cls`` *contiguous*
    slices (``n_pairs`` must divide); slice ``c`` is evaluated by cls
    shard ``c`` over every block shard, with the slack/count psums
    running over the **block axis only** — the per-pair math never
    crosses a slice boundary, so every per-pair output (bound, count,
    blocks, alive) and every scattered child is bit-for-bit identical
    to the ``n_cls=1`` run.  That invariance IS the contract: this ref
    evaluates the slices separately and concatenates, so a 2-D
    ``ops.make_screen_and_intersect_sharded`` program that all-gathers
    its per-slice survivors along ``cls`` before the block-shard-local
    scatter is pinned against it at any mesh shape.
    """
    if mode not in ("and", "andnot"):
        raise ValueError(f"bad mode {mode!r}")
    n_pairs = ua.shape[0]
    cap, nb, bw = rows.shape
    nbl = nb // n_shards
    minsup = jnp.asarray(minsup, jnp.int32)
    if n_cls < 1 or n_pairs % n_cls:
        raise ValueError(
            f"pair chunk of {n_pairs} does not divide n_cls={n_cls}")
    if n_real_blocks is None:
        n_real_blocks = nb

    def eval_slice(ua_s, vb_s, rho_s):
        """One cls shard's pair slice: everything up to (but excluding)
        the scatter, exactly the 1-D per-pair math."""
        n_loc = ua_s.shape[0]
        U = jnp.take(rows, ua_s, axis=0).reshape(n_loc, n_shards, nbl, bw)
        V = jnp.take(rows, vb_s, axis=0).reshape(n_loc, n_shards, nbl, bw)
        su = jnp.take(suffix, ua_s, axis=0).reshape(n_loc, n_shards,
                                                    nbl + 1)
        sv = jnp.take(suffix, vb_s, axis=0).reshape(n_loc, n_shards,
                                                    nbl + 1)

        if not early_stop:
            thr = jnp.full((n_loc, n_shards), jnp.iinfo(jnp.int32).min,
                           jnp.int32)
        elif mode == "and":
            m = jnp.minimum(su[:, :, 0], sv[:, :, 0])  # (n, S) local mass
            slack = m.sum(axis=1, keepdims=True) - m   # psum(m, block) - m
            thr = minsup - slack
        else:
            thr = jnp.broadcast_to(minsup, (n_loc, n_shards))

        flat = (n_loc * n_shards,)
        Zf, cnt_f, blocks_f, alive_f = _blocked_es_scan(
            U.reshape(flat + (nbl, bw)), V.reshape(flat + (nbl, bw)),
            su.reshape(flat + (nbl + 1,)), sv.reshape(flat + (nbl + 1,)),
            jnp.repeat(rho_s.astype(jnp.int32), n_shards),
            thr.reshape(flat), mode=mode)
        Z = Zf.reshape(n_loc, n_shards, nbl, bw)
        zpc = popcount32(Z).sum(axis=-1)            # (n, S, nbl)
        count = cnt_f.reshape(n_loc, n_shards).sum(axis=1)
        if mode == "andnot":
            # Diffset work counter (ISSUE 6): charge only the
            # *nonzero-mass* U blocks each shard's scan visited, like
            # the single-device ``_blocked_diff_scan``.  ``blocks_f``
            # counts the alive-visited prefix, so ``k < blocks_f``
            # marks visited local blocks; pad blocks are all-zero
            # (zero mass) and discount themselves, so no real-block
            # clamp is needed.
            umass = su[:, :, :-1] - su[:, :, 1:]    # (n, S, nbl)
            visited = (jnp.arange(nbl, dtype=jnp.int32)[None, None, :]
                       < blocks_f.reshape(n_loc, n_shards)[:, :, None])
            blocks = jnp.logical_and(umass > 0, visited).sum(
                axis=(1, 2)).astype(jnp.int32)
        else:
            # Pad blocks live at each tail shard's local END (the global
            # pad is the tail of the block axis), so clamping a shard's
            # scan count to its real-block count discounts them exactly.
            real_local = jnp.clip(
                jnp.asarray(n_real_blocks, jnp.int32)
                - jnp.arange(n_shards, dtype=jnp.int32) * nbl, 0, nbl)
            blocks = jnp.minimum(blocks_f.reshape(n_loc, n_shards),
                                 real_local[None, :]).sum(axis=1)
        alive = alive_f.reshape(n_loc, n_shards).all(axis=1)
        c0 = zpc[:, :, 0]                           # (n, S) per-shard blk 0
        if mode == "and":
            bound = (c0 + jnp.minimum(su[:, :, 1],
                                      sv[:, :, 1])).sum(axis=1)
        else:
            bound = rho_s.astype(jnp.int32) - c0.sum(axis=1)
        child_suffix = jnp.concatenate(
            [jnp.cumsum(zpc[:, :, ::-1], axis=-1)[:, :, ::-1],
             jnp.zeros((n_loc, n_shards, 1), jnp.int32)],
            axis=-1).reshape(n_loc, n_shards * (nbl + 1))
        return (Z.reshape(n_loc, nb, bw), child_suffix, bound, count,
                blocks, alive)

    n_loc = n_pairs // n_cls
    parts = [eval_slice(ua[c * n_loc:(c + 1) * n_loc],
                        vb[c * n_loc:(c + 1) * n_loc],
                        rho_parent[c * n_loc:(c + 1) * n_loc])
             for c in range(n_cls)]
    Z, child_suffix, bound, count, blocks, alive = (
        jnp.concatenate([p[i] for p in parts]) for i in range(6))

    keep = _survivor_mask(count, alive, rho_parent, minsup, mode=mode)
    slots_eff = jnp.where(keep, slots, jnp.int32(cap))
    rows = rows.at[slots_eff].set(Z, mode="drop")
    suffix = suffix.at[slots_eff].set(child_suffix, mode="drop")
    return rows, suffix, bound, count, blocks, alive


@jax.jit
def bitmap_count_ref(U: jnp.ndarray, V: jnp.ndarray) -> jnp.ndarray:
    """Plain AND + popcount support counting (no ES, no Z materialised)."""
    return popcount32(U & V).reshape(U.shape[0], -1).sum(axis=-1)


@functools.partial(jax.jit, static_argnames=("mode",))
def bitmap_intersect_full_ref(U: jnp.ndarray, V: jnp.ndarray,
                              *, mode: str = "and",
                              ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Fused full intersection: one AND/ANDNOT + popcount pass, no block
    scan.  The fast production path when per-block work metrics are not
    being collected (the screen still provides the ES savings)."""
    Z = U & (V if mode == "and" else ~V)
    cnt = popcount32(Z).reshape(U.shape[0], -1).sum(axis=-1)
    return Z, cnt


@functools.partial(jax.jit, static_argnames=("mode",))
def screen_pairs_ref(first_u: jnp.ndarray, first_v: jnp.ndarray,
                     suffix1_u: jnp.ndarray, suffix1_v: jnp.ndarray,
                     rho_parent: jnp.ndarray, minsup: jnp.ndarray,
                     *, mode: str = "and",
                     ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Inter-call screening: one-block refinement of the support bound.

    ``first_*``  : uint32 (n_pairs, bw)  — block 0 of each operand
    ``suffix1_*``: int32  (n_pairs,)     — popcount mass from block 1 on
    ``rho_parent``: int32 (n_pairs,)     — parent support ("andnot" mode)

    mode "and":    bound = |U0 & V0|  + min(sufU[1], sufV[1])
    mode "andnot": bound = rho_parent - |U0 & ~V0|

    Returns (bound, alive): pairs with ``bound < minsup`` are provably
    infrequent and are never materialised for full intersection.  This is
    the batched analogue of the paper's "detect infrequent candidates
    early" applied *before* work is scheduled."""
    if mode == "and":
        c0 = popcount32(first_u & first_v).sum(axis=-1)
        bound = c0 + jnp.minimum(suffix1_u, suffix1_v)
    elif mode == "andnot":
        c0 = popcount32(first_u & ~first_v).sum(axis=-1)
        bound = rho_parent.astype(jnp.int32) - c0
    else:
        raise ValueError(f"bad mode {mode!r}")
    return bound, bound >= jnp.asarray(minsup, jnp.int32)


@jax.jit
def compact_gather_ref(slab: jnp.ndarray, perm: jnp.ndarray) -> jnp.ndarray:
    """Allocator compaction gather: ``new[i] = slab[perm[i]]`` for
    ``0 <= perm[i] < capacity``, zeros elsewhere.

    ``perm int32 (new_capacity,)`` maps each *destination* slot to its
    source slot (-1 marks slots that come up free/zeroed).  Because the
    destination side is contiguous, the scatter half of the
    gather-scatter is the identity — one fused gather IS the whole
    compaction dispatch.  Works for any leading-axis slab: bitmap rows
    ``uint32 (cap, nb, bw)``, suffix tables ``int32 (cap, S)``, N-list
    code slabs ``int32 (cap, 3)``.  The OOB handling is spelled out
    (clip + mask) rather than relying on ``jnp.take`` fill-mode
    semantics so the result is identical across JAX versions."""
    cap = slab.shape[0]
    idx = jnp.clip(perm, 0, cap - 1)
    g = jnp.take(slab, idx, axis=0)
    ok = jnp.logical_and(perm >= 0, perm < cap)
    ok = ok.reshape((perm.shape[0],) + (1,) * (slab.ndim - 1))
    return jnp.where(ok, g, jnp.zeros((), slab.dtype))


# ---------------------------------------------------------------------------
# N-list intersection (PrePost+) — device variant
# ---------------------------------------------------------------------------
#
# Padded two-pointer merge per pair (vmap over pairs).  PP-codes are stored
# as three parallel int32 arrays (pre, post, freq) padded with PRE=INT32_MAX
# sentinels.  Early stopping uses the *corrected* criterion
# z_mass + (rho_V - skip) < minsup (see core/oracle.py erratum note).

NL_SENTINEL = _NL


def _nl_merge_vmapped(u_pre, u_post, u_freq, v_pre, v_post, v_freq,
                      u_len, v_len, rho_v, minsup, *, early_stop: bool):
    """Batched two-pointer NL merge with the ``rho_V - skip`` ES guard.

    Shared body of :func:`nlist_intersect_ref` and
    :func:`nlist_extend_ref`; the Pallas kernel
    (``kernels/nlist_merge.py``) must reproduce it bit-for-bit.
    Returns ``(out_slot, support, comparisons, checks, alive)`` where
    slot ``i`` of ``out_slot`` holds the V index matched by U code ``i``
    (or sentinel), in U order.  ``checks`` counts skip-branch
    (j-advance) iterations — exactly the oracle's ``es_checks`` when ES
    is on (the bound is evaluated once per skipped V code)."""
    minsup = jnp.asarray(minsup, jnp.int32)
    _, Lu = u_pre.shape

    def one_pair(up, upost, uf, vp, vpost, vf, nu, nv, rv):
        def cond(st):
            i, j, _, _, _, _, alive, _ = st
            return jnp.logical_and(jnp.logical_and(i < nu, j < nv), alive)

        def body(st):
            i, j, z_mass, skip, cmps, checks, alive, out_slot = st
            cmps = cmps + 1
            xi_pre, xi_post, xi_f = up[i], upost[i], uf[i]
            yj_pre, yj_post, yj_f = vp[j], vpost[j], vf[j]
            is_desc = jnp.logical_and(xi_pre > yj_pre, xi_post < yj_post)
            adv_i_nomatch = xi_pre <= yj_pre
            adv_i = jnp.logical_or(is_desc, adv_i_nomatch)
            # match: record ancestor code at slot i, advance i
            out_slot = out_slot.at[i].set(
                jnp.where(is_desc, j, out_slot[i]))
            z_mass = z_mass + jnp.where(is_desc, xi_f, 0)
            skip = skip + jnp.where(adv_i, 0, yj_f)
            checks = checks + jnp.where(adv_i, 0, 1)
            if early_stop:
                alive = jnp.logical_and(
                    alive, z_mass + (rv - skip) >= minsup)
            i = i + jnp.where(adv_i, 1, 0)
            j = j + jnp.where(adv_i, 0, 1)
            return i, j, z_mass, skip, cmps, checks, alive, out_slot

        init = (jnp.int32(0), jnp.int32(0), jnp.int32(0), jnp.int32(0),
                jnp.int32(0), jnp.int32(0), jnp.bool_(True),
                jnp.full((Lu,), NL_SENTINEL, jnp.int32))
        (i, j, z_mass, skip, cmps, checks, alive,
         out_slot) = jax.lax.while_loop(cond, body, init)
        support = jnp.where(alive, z_mass, 0)  # aborted => certified < minsup
        return out_slot, support, cmps, checks, alive

    return jax.vmap(one_pair)(
        u_pre, u_post, u_freq, v_pre, v_post, v_freq,
        u_len.astype(jnp.int32), v_len.astype(jnp.int32),
        rho_v.astype(jnp.int32))


@functools.partial(jax.jit, static_argnames=("early_stop",))
def nlist_intersect_ref(
    u_pre: jnp.ndarray, u_post: jnp.ndarray, u_freq: jnp.ndarray,  # (P, Lu)
    v_pre: jnp.ndarray, v_post: jnp.ndarray, v_freq: jnp.ndarray,  # (P, Lv)
    u_len: jnp.ndarray, v_len: jnp.ndarray,                        # (P,)
    rho_v: jnp.ndarray, minsup: jnp.ndarray,
    *, early_stop: bool = True,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray,
           jnp.ndarray]:
    """Padded-batch NL merge: returns (out_slot, support, comparisons,
    checks, alive).  Kernel-bench entry point; the mining hot path uses
    :func:`nlist_extend_ref` / ``ops.nlist_extend`` which add the pool
    gather, Z-merge compaction and scatter around this merge."""
    return _nl_merge_vmapped(u_pre, u_post, u_freq, v_pre, v_post, v_freq,
                             u_len, v_len, rho_v, minsup,
                             early_stop=early_stop)


def _nl_gather(codes, off, length, width: int):
    """Gather padded (pre, post, freq) rows from the pool slab.

    ``codes int32 (cap, 3)``, ``off/length int32 (P,)`` -> three
    ``(P, width)`` arrays, sentinel-padded past each row's length.  Each
    row is one contiguous window of the slab (a slice gather, which the
    TPU moves as one copy per row, not one per code); the slab is padded
    by ``width`` so that no window is clamped."""
    cols = jnp.pad(codes, ((0, width), (0, 0))).T              # (3, cap+w)
    g = jax.vmap(lambda o: jax.lax.dynamic_slice_in_dim(
        cols, o, width, axis=1))(off)                          # (P, 3, w)
    mask = jnp.arange(width, dtype=jnp.int32)[None, :] < length[:, None]
    pre = jnp.where(mask, g[:, 0], NL_SENTINEL)
    post = jnp.where(mask, g[:, 1], 0)
    freq = jnp.where(mask, g[:, 2], 0)
    return pre, post, freq


def _nl_group_starts(out_slot):
    """Z-merge group detection (Alg. 3 line 31) shared by the scatter
    and the presize pre-pass.  ``out_slot`` is non-decreasing over
    matched slots (two-pointer order), so group starts are exactly the
    positions where the slot value exceeds the running maximum of
    previous matched slots.  Returns ``(valid, start, child_len)``."""
    P, _ = out_slot.shape
    valid = out_slot != NL_SENTINEL
    js = jnp.where(valid, out_slot, -1)
    running = jax.lax.cummax(js, axis=1)
    prev = jnp.concatenate(
        [jnp.full((P, 1), -1, js.dtype), running[:, :-1]], axis=1)
    start = jnp.logical_and(valid, out_slot != prev)
    child_len = jnp.sum(start.astype(jnp.int32), axis=1)
    return valid, start, child_len


def _nl_zmerge(out_slot, u_freq, v_pre, v_post):
    """Device Z-merge (Alg. 3 line 31): the compacted child N-lists.

    Consecutive U slots matching the same V ancestor code are one child
    element whose frequency is the group's U-frequency mass (see
    :func:`_nl_group_starts`).  Returns ``(zpre, zpost, zfreq,
    child_len)``: children at the front of each ``(P, Lu)`` row, zero
    past ``child_len``.  ``kernels/nlist_merge.py::nlist_zmerge`` must
    reproduce it bit-for-bit."""
    P, Lu = out_slot.shape
    valid, start, child_len = _nl_group_starts(out_slot)
    gid = jnp.cumsum(start.astype(jnp.int32), axis=1) - 1

    rows = jnp.broadcast_to(jnp.arange(P)[:, None], (P, Lu))
    # per-group U-frequency mass (scatter-add; invalid slots -> dropped)
    zfreq = jnp.zeros((P, Lu), jnp.int32).at[
        rows, jnp.where(valid, gid, Lu)].add(
        jnp.where(valid, u_freq, 0), mode="drop")
    # per-group representative V slot (unique per group: set at starts)
    rep = jnp.zeros((P, Lu), jnp.int32).at[
        rows, jnp.where(start, gid, Lu)].set(
        jnp.where(start, out_slot, 0), mode="drop")
    live = jnp.arange(Lu, dtype=jnp.int32)[None, :] < child_len[:, None]
    zpre = jnp.where(live, jnp.take_along_axis(v_pre, rep, axis=1), 0)
    zpost = jnp.where(live, jnp.take_along_axis(v_post, rep, axis=1), 0)
    return zpre, zpost, zfreq, child_len


def _nl_write(codes, zpre, zpost, zfreq, child_len, out_off):
    """Write each child row's first ``child_len`` codes into the pool at
    ``out_off`` (offsets past the slab capacity are dropped — pair
    padding / non-survivors); every other code of the slab is kept.

    One step per written child, in a loop: read the ``Lu``-code slab
    window at the child's offset (clamped into the slab), overlay the
    child and write the window back, so the slab is updated in place
    and no code outside ``[out_off, out_off + child_len)`` changes."""
    cap = codes.shape[0]
    P, Lu = zpre.shape
    width = min(Lu, cap)
    keep = out_off < cap
    order = jnp.nonzero(keep, size=P, fill_value=0)[0]
    k = jnp.arange(width, dtype=jnp.int32)[:, None]

    def body(n, codes):
        p = order[n]
        start = jnp.minimum(out_off[p], cap - width)
        shift = out_off[p] - start
        child = jnp.pad(jnp.stack([zpre[p], zpost[p], zfreq[p]], axis=-1),
                        ((width, 0), (0, 0)))             # (w + Lu, 3)
        new = jax.lax.dynamic_slice_in_dim(child, width - shift, width)
        old = jax.lax.dynamic_slice_in_dim(codes, start, width)
        mine = jnp.logical_and(k >= shift, k < shift + child_len[p])
        return jax.lax.dynamic_update_slice_in_dim(
            codes, jnp.where(mine, new, old), start, axis=0)

    return jax.lax.fori_loop(0, jnp.sum(keep.astype(jnp.int32)), body, codes)


def _nl_zmerge_scatter(codes, out_slot, u_freq, v_pre, v_post, out_off):
    """Device Z-merge (:func:`_nl_zmerge`) + child write into the pool
    (:func:`_nl_write`).  Returns ``(codes, child_len)``."""
    zpre, zpost, zfreq, child_len = _nl_zmerge(out_slot, u_freq, v_pre,
                                               v_post)
    return _nl_write(codes, zpre, zpost, zfreq, child_len, out_off), \
        child_len


@functools.partial(jax.jit, static_argnames=("lu", "lv", "early_stop"))
def nlist_presize_ref(
    codes: jnp.ndarray,        # int32 (capacity, 3) N-list pool slab
    u_off: jnp.ndarray, u_len: jnp.ndarray,    # int32 (P,)
    v_off: jnp.ndarray, v_len: jnp.ndarray,    # int32 (P,)
    rho_v: jnp.ndarray,        # int32 (P,) sibling supports (ES bound)
    minsup: jnp.ndarray,       # int32 scalar
    *, lu: int, lv: int, early_stop: bool = True,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray,
           jnp.ndarray, jnp.ndarray]:
    """Merge-only pre-pass: the bound/count phase of the PrePost+ class
    extension WITHOUT the scatter (ISSUE 5 tentpole).

    Gathers both operand N-lists by extent offset and runs the
    two-pointer merge with the corrected ``z_mass + (rho_V - skip)`` ES
    guard — comparison counts are exactly the oracle's — plus the
    Z-merge group count, so the host learns each surviving child's
    *exact* length (and support) before allocating its extent.  The
    match table ``out_slot`` stays on device and feeds
    :func:`nlist_scatter_ref`, which re-derives the Z-merge from it —
    the merge loop runs exactly once per candidate.

    Returns ``(out_slot, child_len, support, comparisons, checks,
    alive)``; aborted pairs report support 0 (certified infrequent)."""
    u_pre, u_post, u_freq = _nl_gather(codes, u_off, u_len, lu)
    v_pre, v_post, v_freq = _nl_gather(codes, v_off, v_len, lv)
    out_slot, support, cmps, checks, alive = _nl_merge_vmapped(
        u_pre, u_post, u_freq, v_pre, v_post, v_freq,
        u_len, v_len, rho_v, minsup, early_stop=early_stop)
    _, _, child_len = _nl_group_starts(out_slot)
    return out_slot, child_len, support, cmps, checks, alive


@functools.partial(jax.jit, static_argnames=("lu", "lv"))
def nlist_scatter_ref(
    codes: jnp.ndarray,        # int32 (capacity, 3) N-list pool slab
    out_slot: jnp.ndarray,     # int32 (P, lu) presize match table
    u_off: jnp.ndarray, u_len: jnp.ndarray,    # int32 (P,)
    v_off: jnp.ndarray, v_len: jnp.ndarray,    # int32 (P,)
    out_off: jnp.ndarray,      # int32 (P,) child extents (OOB -> dropped)
    *, lu: int, lv: int,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Scatter pass of the two-dispatch PrePost+ extension (ISSUE 5).

    Re-gathers the operand codes (cheap — no merge loop), Z-merges
    consecutive same-ancestor slots of ``out_slot`` and scatters the
    compacted child N-lists into the pool at ``out_off``.  Callers pass
    ``out_off >= capacity`` for every non-survivor (and for pair
    padding), so dead candidates cost zero scatter words and the pool
    only ever receives children whose tight extents were allocated from
    their exact pre-pass lengths.

    ``lu``/``lv`` must be the presize dispatch's gather widths.
    Returns ``(codes, child_len)``."""
    _, _, u_freq = _nl_gather(codes, u_off, u_len, lu)
    v_pre, v_post, _ = _nl_gather(codes, v_off, v_len, lv)
    return _nl_zmerge_scatter(codes, out_slot, u_freq, v_pre, v_post,
                              jnp.asarray(out_off, jnp.int32))


@functools.partial(jax.jit, static_argnames=("lu", "lv", "early_stop"))
def nlist_extend_ref(
    codes: jnp.ndarray,        # int32 (capacity, 3) N-list pool slab
    u_off: jnp.ndarray, u_len: jnp.ndarray,    # int32 (P,)
    v_off: jnp.ndarray, v_len: jnp.ndarray,    # int32 (P,)
    out_off: jnp.ndarray,      # int32 (P,) child extents (OOB -> dropped)
    rho_v: jnp.ndarray,        # int32 (P,) sibling supports (ES bound)
    minsup: jnp.ndarray,       # int32 scalar
    *, lu: int, lv: int, early_stop: bool = True,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray,
           jnp.ndarray, jnp.ndarray]:
    """Fused PrePost+ class extension over a device N-list pool.

    The exact semantics ``ops.nlist_extend`` must reproduce bit-for-bit
    (jnp and pallas backends) — the N-list analogue of
    :func:`screen_and_intersect_ref`.  One dispatch per pair chunk:

      * gather both operand N-lists from ``codes`` by extent offset
        (``lu``/``lv`` are the bucketed gather widths, static);
      * run the two-pointer merge with the corrected
        ``z_mass + (rho_V - skip) < minsup`` ES guard (see
        core/oracle.py erratum note) — comparison counts are exactly the
        oracle's;
      * Z-merge consecutive same-ancestor slots on device and scatter the
        compacted child N-lists into the pool at ``out_off`` —
        **survivor-only** (ISSUE 5): the merge phase completes first and
        gates the scatter, so a child is written only when its support
        clears minsup (aborted pairs report support 0, so ES deaths are
        covered by the same gate).

    The mining hot path uses the two-dispatch split
    (:func:`nlist_presize_ref` + :func:`nlist_scatter_ref`) so extents
    can be allocated from exact child lengths; this one-dispatch
    composition remains the micro-bench / pessimistic-extent API.

    Returns ``(codes, child_len, support, comparisons, checks, alive)``;
    non-survivors report ``child_len`` from the merge but scatter
    nothing."""
    u_pre, u_post, u_freq = _nl_gather(codes, u_off, u_len, lu)
    v_pre, v_post, v_freq = _nl_gather(codes, v_off, v_len, lv)
    out_slot, support, cmps, checks, alive = _nl_merge_vmapped(
        u_pre, u_post, u_freq, v_pre, v_post, v_freq,
        u_len, v_len, rho_v, minsup, early_stop=early_stop)
    keep = support >= jnp.asarray(minsup, jnp.int32)
    cap = codes.shape[0]
    out_off_eff = jnp.where(keep, jnp.asarray(out_off, jnp.int32),
                            jnp.int32(cap))
    codes, child_len = _nl_zmerge_scatter(
        codes, out_slot, u_freq, v_pre, v_post, out_off_eff)
    return codes, child_len, support, cmps, checks, alive
