"""Distributed mining: count distribution over the TID axis.

Scaling story (DESIGN.md §2.4): transactions (TID bitmap *blocks*) are
sharded across the mesh axes; candidate pairs are replicated.  Each
device computes partial popcounts over its block shard; one ``psum`` of
``int32[n_pairs]`` vectors produces global bounds/supports.  The
transaction data never moves — the only cross-device traffic is the
per-candidate count vectors, which is why the scheme scales to
thousands of chips (Count Distribution, Agrawal & Shafer '96, adapted
to Eclat).

Early stopping distributes twice over (the sharded instantiation of
the paper's INTERSECT_ES).  Between dispatches it is the *two-level
screen*: each shard computes its block-0 partial count plus its local
suffix bound; the psum of per-shard bounds is a *tighter* global bound
than the centralized one (sum of per-shard minima <= minimum of sums),
and pairs whose global bound misses minsup are never expanded.  Inside
a dispatch it is *shard-local block ES* (ISSUE 4): the screen's
per-pair slack — the mass every OTHER shard could still contribute —
is psum'd up front, and each shard walks its local blocks against the
conservative threshold ``minsup - slack``, aborting mid-scan the
moment the pair is provably infrequent globally, exactly like the
single-device blocked scan.

Since ISSUE 2 the ``DistributedMiner`` is a thin subclass of
``core.eclat.BitmapMiner``: both engines share one allocator
(``core.rowstore.DeviceRowStore``, block-sharded here) and one fused
gather→screen→intersect→scatter dispatch per pair chunk
(``kernels.ops.make_screen_and_intersect_sharded``, bit-exact against
``kernels.ref.screen_and_intersect_sharded_ref``).  The legacy three
round programs (screen/count/materialize — 3 dispatches + 2
collectives per round, with their own ad-hoc slab and duplicated
free-list plumbing) are gone; a mining round is ONE dispatch with ONE
psum, and the row store grows on demand instead of dead-ending in a
"row store exhausted" error.  Since ISSUE 5 the psum is also the
dispatch's internal dependency edge for **survivor-only
materialization**: every shard knows the global count/alive before its
shard-local scatter phase, so a candidate the screen or scan killed is
never written to the slab — child scatter traffic scales with frequent
children, not candidates (``stats.child_scatters``).

``make_mining_round`` / ``make_mining_round_v2`` remain: they are the
standalone round programs used by the dry-run/roofline harness (cost
analysis wants an isolated lowerable SPMD program, not a live miner).
"""

from __future__ import annotations

from typing import Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.compat import shard_map
from repro.core.bitmap import DEFAULT_BLOCK_WORDS, BitmapDB, popcount32
from repro.core.eclat import (BitmapMiner, DeviceMiningStats, _bucket_pad,
                              _count_lanes, _read_dispatch,
                              ItemsetSupports)  # noqa: F401 (re-export)
from repro.core.rowstore import DeviceRowStore
from repro.kernels import ops

# Back-compat alias: the unified engine reports the same stats object as
# the single-device miner (``rounds`` became ``device_calls``).
DistributedStats = DeviceMiningStats


# ---------------------------------------------------------------------------
# Standalone round programs (dry-run / roofline harness)
# ---------------------------------------------------------------------------

def _local_suffix(bitmaps: jnp.ndarray) -> jnp.ndarray:
    """Suffix popcounts over the LOCAL block shard: (rows, nb_local+1)."""
    per_block = popcount32(bitmaps).sum(axis=-1).astype(jnp.int32)
    rev = jnp.cumsum(per_block[:, ::-1], axis=1)[:, ::-1]
    zeros = jnp.zeros((bitmaps.shape[0], 1), jnp.int32)
    return jnp.concatenate([rev, zeros], axis=1)


def make_mining_round(mesh: Mesh, *, pair_chunk: int = 2048):
    """Fused screen+count round used by the dry-run/roofline harness.

    Pure Count Distribution (Agrawal & Shafer '96 adapted to Eclat): the
    bitmap store's BLOCK axis is sharded across EVERY mesh axis (a 1.07B-
    transaction store is 4GB/chip on 256 chips); the candidate pair list
    is replicated; each chip computes partial popcounts + local suffix
    screen bounds on its block shard, and one psum of two int32[n_pairs]
    vectors produces global bounds/counts.  The transaction data never
    moves.

    Pairs are processed in ``pair_chunk``-sized slices with ``lax.scan``
    so per-chunk gather buffers are provably reused (an unrolled loop let
    the scheduler keep every chunk's 3GB gather alive).  cost_analysis
    counts the scan body once; the dry-run reconstructs totals from two
    reduced-pair compiles (same fit as the LM cells)."""
    all_axes = tuple(mesh.axis_names)
    tid_spec = all_axes if len(all_axes) > 1 else all_axes[0]

    def mining_round(store, pairs, rho):
        del rho
        n = pairs.shape[0]
        chunk = min(pair_chunk, n)
        pc = pairs.reshape(n // chunk, chunk, 2)

        def body(_, p):
            u = store[p[:, 0]]            # (chunk, nb_local, bw)
            v = store[p[:, 1]]
            z0 = u[:, 0] & v[:, 0]
            c0 = popcount32(z0).sum(axis=-1)
            su = _local_suffix(u)[:, 1]
            sv = _local_suffix(v)[:, 1]
            bound_c = c0 + jnp.minimum(su, sv)
            count_c = popcount32(u & v).sum(axis=(-1, -2))
            return None, (bound_c, count_c)

        _, (bounds, counts) = jax.lax.scan(body, None, pc)
        bound = jax.lax.psum(bounds.reshape(n), all_axes)
        count = jax.lax.psum(counts.reshape(n), all_axes)
        return bound, count

    return shard_map(
        mining_round, mesh=mesh,
        in_specs=(P(None, tid_spec, None), P(None, None), P(None)),
        out_specs=(P(None), P(None)), check_vma=False)


def make_mining_round_v2(mesh: Mesh, *, pair_chunk: int = 2048):
    """Optimised mining round (hillclimb variant, EXPERIMENTS.md §Perf).

    Two changes over ``make_mining_round``, both beyond-paper engineering
    on top of the paper's criterion:

      1. PRECOMPUTED shard-local suffix masses: the baseline recomputes
         each operand's suffix popcounts from its full gathered row (per
         pair!).  The mass "popcount of blocks 1.. on shard s" is a
         per-(row, shard) invariant maintained when rows materialise, so
         the round takes it as ``suffix1 (rows, n_shards)`` (each shard
         owns its column) and the screen touches only block 0 + one
         scalar per operand.
      2. SHARED-``a`` chunking: the host already batches sibling pairs of
         one class member 'a'; with ``pairs[c, :, 0]`` constant per chunk
         the u-row is gathered ONCE per chunk instead of per pair
         (u-traffic / pair_chunk).
    """
    all_axes = tuple(mesh.axis_names)
    tid_spec = all_axes if len(all_axes) > 1 else all_axes[0]

    def mining_round(store, suffix1, pairs, rho):
        del rho
        n = pairs.shape[0]
        chunk = min(pair_chunk, n)
        pc = pairs.reshape(n // chunk, chunk, 2)

        def body(_, p):
            a_row = p[0, 0]               # shared-'a' chunk
            u = store[a_row]              # (nb_local, bw) — ONE gather
            su = suffix1[a_row, 0]        # local column of this shard
            v = store[p[:, 1]]            # (chunk, nb_local, bw)
            sv = suffix1[p[:, 1], 0]
            z0 = u[0][None] & v[:, 0]
            c0 = popcount32(z0).sum(axis=-1)
            bound_c = c0 + jnp.minimum(su, sv)
            count_c = popcount32(u[None] & v).sum(axis=(-1, -2))
            return None, (bound_c, count_c)

        _, (bounds, counts) = jax.lax.scan(body, None, pc)
        bound = jax.lax.psum(bounds.reshape(n), all_axes)
        count = jax.lax.psum(counts.reshape(n), all_axes)
        return bound, count

    return shard_map(
        mining_round, mesh=mesh,
        in_specs=(P(None, tid_spec, None), P(None, tid_spec), P(None, None),
                  P(None)),
        out_specs=(P(None), P(None)), check_vma=False)


# ---------------------------------------------------------------------------
# Unified distributed miner
# ---------------------------------------------------------------------------

class DistributedMiner(BitmapMiner):
    """Count-distribution Eclat / dEclat / adaptive over a device mesh.

    The host/DFS split, drain-group batching, free-list bookkeeping,
    allocator compaction scheduling, representation policy
    (``scheme``/``diff_density``/``diff_hysteresis`` — ISSUE 6) and
    stats all come from ``BitmapMiner`` driving
    ``core.frontier.FrontierScheduler``; this class only swaps in

      * a block-sharded ``DeviceRowStore`` (slab + per-shard suffix
        tables under ``NamedSharding``s, growing on demand), and
      * the fused shard_map dispatches — one device call and one psum
        per pair chunk (per representation present in the chunk), no
        separate screen/count/materialize programs.  Tidset chunks run
        the ``mode="and"`` program; diffset chunks the ``mode="andnot"``
        program, whose shard-local scan walks the difference bound
        ``rho - count`` and charges only nonzero-mass U blocks.

    ``tid_axes`` defaults to every mesh axis not named by ``cls_axes``
    (maximum block parallelism); ``cls_axes`` defaults to ``("cls",)``
    when the mesh has an axis of that name (the ``make_mining_mesh``
    convention) and to none otherwise.  ``capacity`` is an initial-size
    hint only: the slab grows instead of raising.  ``pair_axis`` is
    accepted for backward compatibility and ignored — pairs are
    replicated over the block axes; under a 2-D mesh (ISSUE 9) each
    cls-shard evaluates its contiguous slice of the chunk's pair
    vectors, so the psum'd per-pair vectors shrink by n_cls and the
    frontier scan itself parallelizes.
    """

    def __init__(self, mesh: Mesh, *,
                 tid_axes: Tuple[str, ...] = None,
                 cls_axes: Tuple[str, ...] = None,
                 pair_axis: str = None,
                 scheme: str = "eclat",
                 early_stop: bool = True,
                 capacity: int = 4096, pair_chunk: int = 4096,
                 block_words: int = DEFAULT_BLOCK_WORDS,
                 compact_occupancy: float = 0.25,
                 diff_density: "float | None" = None,
                 diff_hysteresis: float = 0.05, inflight: int = 2,
                 autotune_chunk: bool = False):
        super().__init__(scheme=scheme, early_stop=early_stop,
                         block_words=block_words, pair_chunk=pair_chunk,
                         backend="jnp",
                         compact_occupancy=compact_occupancy,
                         diff_density=diff_density,
                         diff_hysteresis=diff_hysteresis,
                         inflight=inflight,
                         autotune_chunk=autotune_chunk)
        del pair_axis
        self.mesh = mesh
        if cls_axes is None:
            # make_mining_mesh names its pair axis "cls"; honour that by
            # default so callers don't have to thread axis tuples.
            cls_axes = ("cls",) if (tid_axes is None
                                    and "cls" in mesh.axis_names) else ()
        self.cls_axes = tuple(cls_axes)
        if tid_axes is None:
            tid_axes = tuple(a for a in mesh.axis_names
                             if a not in self.cls_axes)
        self.tid_axes = tuple(tid_axes)
        if set(self.tid_axes) & set(self.cls_axes):
            raise ValueError("tid_axes and cls_axes overlap")
        self.n_cls = 1
        for ax in self.cls_axes:
            self.n_cls *= mesh.shape[ax]
        # Chunk slices must land on cls-shard boundaries so each shard's
        # pair slice is a contiguous, bucket-sorted run (core.frontier
        # reads this attribute).
        self.chunk_quantum = self.n_cls
        self.capacity = capacity
        # Two fused shard_map programs share the factory's lru_cache:
        # ``_fused`` ("and") extends tidset classes — it keeps its
        # pre-ISSUE-6 name so call-counting harnesses that wrap the
        # attribute still see every tidset dispatch — and
        # ``_fused_diff`` ("andnot") is the diffset difference with the
        # skip-aware work counter.
        self._fused = ops.make_screen_and_intersect_sharded(
            mesh, tid_axes=self.tid_axes, mode="and",
            early_stop=early_stop, cls_axes=self.cls_axes)
        self._fused_diff = ops.make_screen_and_intersect_sharded(
            mesh, tid_axes=self.tid_axes, mode="andnot",
            early_stop=early_stop, cls_axes=self.cls_axes)

    def _autotune_words_per_pair(self, bdb: BitmapDB) -> int:
        # Each cls-shard holds 1/n_cls of the chunk's gathered rows, so
        # the per-device VMEM budget divides by n_cls (satellite 6) —
        # ceil so the width never overshoots the budget.
        return -(-(bdb.n_blocks * self.block_words) // self.n_cls)

    def _device_words_per_pair(self, store: DeviceRowStore) -> int:
        # A device holds one block shard of each row; the cls all-gather
        # brings the whole chunk's child slices to every device, so the
        # bound does not divide by n_cls.
        return -(-store.words_per_row // store.n_shards)

    def _make_store(self, bdb: BitmapDB) -> DeviceRowStore:
        return DeviceRowStore(
            bdb.bitmaps,
            capacity=max(self.capacity,
                         bdb.n_items + min(self.pair_chunk, 4096)),
            mesh=self.mesh, tid_axes=self.tid_axes)

    def _dispatch_launch(self, store: DeviceRowStore, ua: np.ndarray,
                         vb: np.ndarray, slots: np.ndarray,
                         rho: np.ndarray, mode: str) -> Tuple:
        """Launch the fused shard_map dispatch; NO host sync (the
        blocking readbacks live in ``_dispatch_resolve``, ISSUE 7)."""
        # "and" -> tidset intersect program, "diff" -> diffset
        # difference program (ISSUE 6: declat/adaptive schemes route
        # their diff chunks here; both programs were built in __init__).
        fused = self._fused if mode == "and" else self._fused_diff
        n = int(ua.size)
        cap = store.capacity
        (store.rows, store.suffix, bound, count, blocks,
         scan_alive) = fused(
            store.rows, store.suffix,
            _bucket_pad(ua, n), _bucket_pad(vb, n),
            _bucket_pad(slots, n, fill=cap),   # OOB pad -> dropped
            _bucket_pad(rho, n), np.int32(self._minsup),
            np.int32(self._n_blocks))   # real (unpadded) block count
        self._stats.device_calls += 1
        _count_lanes(self._stats, n)
        return bound, count, blocks, scan_alive

    def _dispatch_resolve(self, raw: Tuple, n: int,
                          ) -> Tuple[np.ndarray, np.ndarray]:
        """Blocking readback of one sharded dispatch + attribution."""
        stats = self._stats
        bound, count, blocks, scan_alive = _read_dispatch(stats, raw, n)
        # In-dispatch shard-local block ES (ISSUE 4): each shard walks its
        # local blocks against the conservative threshold
        # ``minsup - slack`` (slack = the screen mass every OTHER shard
        # could still contribute) and aborts mid-scan once the pair is
        # provably infrequent globally.  ``blocks`` is the psum of REAL
        # local blocks scanned (the dispatch discounts the store's
        # all-zero block padding — ISSUE 5), so word_ops and
        # word_ops_full are consistently unpadded: an ES-off run reports
        # word_ops == word_ops_full and saved_frac is never negative.
        stats.word_ops += int(blocks.sum()) * self.block_words
        if self.early_stop:
            screen_alive = bound >= self._minsup
            alive = np.logical_and(screen_alive, scan_alive)
            # Attribution: the psum'd two-level screen claims its deaths
            # first; pairs it passed but a shard's scan aborted are
            # in-dispatch kernel aborts.
            stats.screened_out += int((~screen_alive).sum())
            stats.kernel_aborts += int(
                np.logical_and(screen_alive, ~scan_alive).sum())
        else:
            alive = np.ones(n, bool)
        return count, alive
