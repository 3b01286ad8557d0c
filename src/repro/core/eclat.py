"""Device bitmap miners: Eclat and dEclat with block-level early stopping.

Host/DFS split (docs/ARCHITECTURE.md): the equivalence-class depth-first search
stays on the host (Python), but the host only ever handles row *indices*
and small int vectors — every bitmap row lives in a device-resident
``DeviceRowStore`` slab (core/rowstore.py) from the moment the level-1
TID bitmaps are uploaded until the slot is free-listed.  Candidate
evaluation is batched at the *class* level: every sibling pair (a, b),
a<b, of one equivalence class goes to the device in chunked calls, and
each chunk is exactly **one** device dispatch
(``kernels.ops.screen_and_intersect``):

  * gather: operand rows + suffix tables are picked out of the slab by
    index (no host U/V materialisation, no re-upload);
  * screen: the kernel evaluates the one-block bound first — a pair whose
    block-0 bound misses minsup dies with ``blocks_done == 1`` and costs
    no further blocks;
  * blocked ES: surviving pairs walk TID blocks and abort the moment the
    suffix bound drops below minsup (the paper's INTERSECT_ES /
    DIFFERENCE_ES quantised to blocks);
  * scatter: child rows *and* their suffix-popcount tables are computed
    on device and written into preallocated slots of the same slab —
    **survivor-only** (ISSUE 5): the count phase completes before the
    scatter phase and gates it, so dead candidates cost zero scatter
    words (``stats.child_scatters`` counts frequent children exactly).

Slots are still *reserved* pessimistically (one per candidate pair —
the scatter destinations must exist before the dispatch) and the dead
ones are returned to the free list right after, but nothing was ever
written to them: free-list traffic is pure host bookkeeping, so
infrequent candidates cost zero extra device work.  When occupancy
drops far enough the scheduler compacts the slab at a drain-group
boundary (``DeviceRowStore.compact_if_sparse``) and remaps the
frontier's slot handles through the returned mapping.

Representations (ISSUE 6): the same slab holds BOTH bitmap
representations.  A class is tagged ``tidset`` (rows are TID bitmaps,
pairs dispatch through ``ops.screen_and_intersect``) or ``diffset``
(rows are dEclat difference bitmaps ``d(Pxy)``, pairs dispatch through
``ops.screen_and_diff`` on the difference bound ``sup(parent) -
|diff|``).  ``scheme="eclat"`` stays tidset everywhere,
``scheme="declat"`` flips at level 2, and ``scheme="adaptive"`` flips a
subtree tidset→diffset when its density (mean member support /
n_trans) clears ``diff_density + diff_hysteresis`` at ``make_class``
time — dense classes are where diffsets shrink operands the most
(|d| = sup(parent) - sup(child)).  The flip is one-way (the parent
tidset rows are freed when the class drains) and costs no extra round
trip: the very same diff dispatch that extends diffset classes converts
a tidset pair ``T(a), T(b)`` into the level-2 diffset ``d(ab) = T(a) &
~T(b)`` inside its child scatter.  Mixed drain groups carry a per-pair
``op`` column; ``chunk_sort_key`` orders pairs by it so chunks stay
mode-homogeneous and pure schemes keep exactly one dispatch per chunk.

Work metric: ``word_ops`` — uint32 word operations actually performed
(blocks_done x block_words per pair; the fused screen is block 0 of the
same scan).  This is the device analogue of the paper's #comparisons and
is what benchmarks/bench_paper.py reports next to the oracle's exact
counter.  Diff dispatches charge only nonzero-mass U blocks (a zero
block of a sparse diffset operand cannot contribute to ``U & ~V`` and
is skipped), while ``word_ops_full`` stays the dense tidset full-scan
cost ``n_pairs * n_blocks * block_words`` — the paper's non-ES
baseline — so ``word_ops_saved_frac`` folds in both the ES savings and
the representation savings.

The traversal policy (work stack, cross-class drain-group batching,
chunk slicing, operand free-listing, compaction scheduling) lives in
``core.frontier.FrontierScheduler`` — this module only implements the
scheduler's client protocol on top of the fused bitmap dispatches.
"""

from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Any, Dict, FrozenSet, Hashable, List, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from repro.core.bitmap import (BITMAP_REF_ROW_WORDS, BitmapDB,
                               DEFAULT_BLOCK_WORDS, PAIR_CHUNK_BUCKETS,
                               bucket_pad, chunk_width_for,
                               device_memory_bytes, hbm_pair_cap)
from repro.core.frontier import (Child, ClassNode, EngineAccounting,
                                 FrontierScheduler)
from repro.core.guards import host_sync
from repro.core.rowstore import DeviceRowStore
from repro.core.trace import span
from repro.kernels import ops

ItemsetSupports = Dict[FrozenSet[Hashable], int]

# Canonical table lives in core.bitmap next to bucket_pad (ISSUE 5
# consolidation) so the pair-chunk clamp and the pad logic cannot drift.
_PAIR_BUCKETS = PAIR_CHUNK_BUCKETS

# Per-pair dispatch-mode codes carried in the ``op`` column (int8):
# chunk_sort_key orders mixed drain groups by this so chunks stay
# mode-homogeneous.
_OP_AND = 0                    # tidset intersect (ops.screen_and_intersect)
_OP_DIFF = 1                   # diffset difference (ops.screen_and_diff)

# Default density threshold for scheme="adaptive": a class whose mean
# member support exceeds this fraction of n_trans (plus the hysteresis
# band) materialises its children as diffsets.
DEFAULT_DIFF_DENSITY = 0.5


@dataclass
class DeviceMiningStats(EngineAccounting):
    """Work accounting for the bitmap engine (device analogue of
    ``oracle.MiningStats``; the shared device/allocator counters come
    from ``frontier.EngineAccounting``)."""

    screened_out: int = 0        # pairs killed by the one-block screen
    kernel_aborts: int = 0       # pairs killed past block 0
    word_ops: int = 0            # uint32 ops actually performed
    word_ops_full: int = 0       # what a non-ES engine would have performed

    # Legacy names kept as read-only views of the shared accounting.
    @property
    def store_grows(self) -> int:
        return self.grows

    @property
    def peak_rows(self) -> int:
        return self.peak_live

    @property
    def deaths(self) -> int:
        return self.screened_out + self.kernel_aborts

    @property
    def ratio(self) -> float:
        return self.candidates / max(self.nodes, 1)

    @property
    def word_ops_saved_frac(self) -> float:
        if self.word_ops_full == 0:
            return 0.0
        return 1.0 - self.word_ops / self.word_ops_full

    def as_dict(self) -> Dict[str, float]:
        return {
            "candidates": self.candidates,
            "nodes": self.nodes,
            "ratio": round(self.ratio, 4),
            "screened_out": self.screened_out,
            "kernel_aborts": self.kernel_aborts,
            "word_ops": self.word_ops,
            "word_ops_full": self.word_ops_full,
            "word_ops_saved_frac": round(self.word_ops_saved_frac, 4),
            "store_grows": self.store_grows,
            "peak_rows": self.peak_rows,
            "runtime_s": round(self.runtime_s, 6),
            **self.accounting_dict(),
        }


def _bucket_pad(arr: np.ndarray, n: int, fill=0) -> np.ndarray:
    return bucket_pad(arr, n, _PAIR_BUCKETS, fill)


def _count_lanes(stats: EngineAccounting, n: int) -> None:
    """Charge one dispatch of ``n`` real pairs at its bucketed width."""
    width = next(b for b in _PAIR_BUCKETS if n <= b)
    stats.pair_lanes += width
    stats.pad_lanes += width - n


def _read_dispatch(stats: EngineAccounting, raw: Tuple, n: int,
                   ) -> List[np.ndarray]:
    """Blocking readback of one launched dispatch's outputs, trimmed to
    its ``n`` real pairs: first the wait for the device (``sched.wait``,
    summed into ``stats.wait_s``), then the copies (``sched.readback``).
    Waiting first adds no synchronisation — the copies block anyway."""
    # host-sync: the audited group-retirement readback (PR 7) — one
    # deliberate d2h per retired dispatch, deferred via the handle
    with host_sync("group-retirement accounting readback"):
        with span("sched.wait", acc=(stats, "wait_s")):
            # host-sync: waits on the dispatch the copies below read
            jax.block_until_ready(raw)
        with span("sched.readback"):
            return [np.asarray(a[:n]) for a in raw]


class PendingPairResult:
    """Lazy result handle for one bitmap ``evaluate_pairs`` dispatch
    (ISSUE 7 pipeline).

    The fused dispatches were already launched (JAX async dispatch —
    the device is busy); what is deferred here is every *blocking*
    ``np.asarray`` readback of count/blocks/alive plus the stats
    attribution and dead-slot frees that depend on them.  The scheduler
    calls ``resolve()`` exactly once when the owning drain group
    retires; if a slab compaction lands while the group is in flight it
    calls ``remap(mapping)`` so the child slot ids this handle will
    report stay valid (the dispatches themselves are unaffected — their
    operands travel by value through the donation chain)."""

    __slots__ = ("_miner", "_n", "_slots", "_segments")

    def __init__(self, miner: "BitmapMiner", n: int, slots: np.ndarray,
                 segments: List[Tuple[np.ndarray, str, np.ndarray, Any]]):
        self._miner = miner
        self._n = n
        self._slots = slots
        self._segments = segments

    def remap(self, mapping: np.ndarray) -> None:
        self._slots = mapping[self._slots]

    def resolve(self) -> List[Tuple[int, int, int, Any]]:
        miner = self._miner
        stats, store = miner._stats, miner._store
        n, slots = self._n, self._slots
        support = np.zeros(n, np.int64)
        freq = np.zeros(n, bool)
        for sel, mode, rho_sel, raw in self._segments:
            cnt, alive = miner._dispatch_resolve(raw, int(sel.size))
            sup = cnt if mode == "and" else rho_sel - cnt
            support[sel] = sup
            # Dead pairs carry frozen (partial) counts; in diff mode a
            # frozen count *overestimates* the support (rho - cnt), so
            # aliveness is load-bearing.  This mask is exactly the
            # dispatch's in-kernel scatter gate (ref._survivor_mask):
            # only these children were materialised.
            freq[sel] = np.logical_and(sup >= miner._minsup, alive)

        kept_idx = np.nonzero(freq)[0]
        stats.child_scatters += int(kept_idx.size)
        # Real (unpadded) blocks, like word_ops/word_ops_full: the
        # telemetry stays shard-count invariant even though a sharded
        # store physically pads each child row's block axis with zeros.
        stats.scatter_words += (int(kept_idx.size) * miner._n_blocks
                                * miner.block_words)
        store.free(slots[~freq])                  # dead children: recycle
        self._segments = []                       # drop device refs
        return [(int(ki), int(slots[ki]), int(support[ki]), None)
                for ki in kept_idx]


class BitmapMiner:
    """Eclat / dEclat / density-adaptive mining over a device-resident
    row store with fused screen+intersect(+difference) early stopping.

    The DFS itself is ``core.frontier.FrontierScheduler`` — this class is
    its client: it turns one class's sibling-pair triangle into store
    slot columns, evaluates a pair-chunk slice as ONE fused device
    dispatch, and recycles spent slots.  ``compact_occupancy`` is the
    allocator memory-tuning knob: when live rows fall below that
    fraction of the slab (and the slab would at least halve), the
    scheduler compacts it between drain groups; 0 disables compaction.

    ``scheme="adaptive"`` (ISSUE 6) mines tidsets but flips a subtree
    to diffsets when its class density (mean member support / n_trans)
    clears ``diff_density + diff_hysteresis`` — the flip is one-way and
    rides the normal child scatter (see the module docstring), so it
    costs no extra device round trip.
    """

    def __init__(self, scheme: str = "eclat", early_stop: bool = True,
                 block_words: int = DEFAULT_BLOCK_WORDS,
                 pair_chunk: int = 65536, backend: str = "auto",
                 metrics: bool = True, compact_occupancy: float = 0.25,
                 diff_density: "float | None" = None,
                 diff_hysteresis: float = 0.05, inflight: int = 2,
                 autotune_chunk: bool = False):
        if scheme not in ("eclat", "declat", "adaptive"):
            raise ValueError(f"bad scheme {scheme!r}")
        if scheme == "adaptive":
            if diff_density is None:
                diff_density = DEFAULT_DIFF_DENSITY
        elif diff_density is not None:
            raise ValueError(
                "diff_density only applies to scheme='adaptive' "
                "(eclat is tidset-only, declat flips unconditionally)")
        self.scheme = scheme
        self.early_stop = early_stop
        self.block_words = block_words
        self.pair_chunk = min(pair_chunk, _PAIR_BUCKETS[-1])
        self.backend = backend
        self.compact_occupancy = compact_occupancy
        # Density-adaptive representation knobs (ISSUE 6): a class flips
        # its children tidset->diffset when density clears
        # ``diff_density + diff_hysteresis``; classes straddling the bare
        # threshold stay tidset (the band plus the one-way flip rule is
        # what makes the choice stable across consecutive drain groups).
        self.diff_density = diff_density
        self.diff_hysteresis = diff_hysteresis
        # Dispatch-pipeline knobs (ISSUE 7): ``inflight`` is the ring
        # depth (2 = double-buffered; 1 reproduces the serial engine's
        # accounting bit-for-bit); ``autotune_chunk`` derives the chunk
        # width from the row size (small-operand runs dispatch wider at
        # equal VMEM footprint — see core.bitmap.chunk_width_for).
        self.inflight = max(1, int(inflight))
        self.autotune_chunk = bool(autotune_chunk)
        # The fused dispatch returns exact blocks_done/word_ops for free;
        # ``metrics`` is kept for API compatibility and no longer selects
        # a separate (two-dispatch) fast path.
        self.metrics = metrics
        self._jobs = 0           # sequence number of the next job's spans

    # Dispatch chunks are sliced in units of this many pairs so each
    # cls-shard's slice stays aligned; the 2-D DistributedMiner sets it
    # to its cls-axis size (see core.frontier._chunk_slices).
    chunk_quantum = 1

    def mine(self, db: Sequence[Sequence[Hashable]], minsup: int,
             ) -> Tuple[ItemsetSupports, DeviceMiningStats]:
        if minsup < 1:
            raise ValueError("minsup must be an absolute count >= 1")
        return self.mine_packed(
            BitmapDB.from_db(db, minsup, self.block_words), minsup)

    def mine_packed(self, bdb: BitmapDB, minsup: int,
                    ) -> Tuple[ItemsetSupports, DeviceMiningStats]:
        """Mine a pre-packed :class:`BitmapDB` (the paper-scale bench
        streams transactions straight into one, skipping the host-side
        list-of-lists detour that ``mine`` takes)."""
        if minsup < 1:
            raise ValueError("minsup must be an absolute count >= 1")
        job = self._jobs
        self._jobs += 1
        with span("mine", job=job):
            return self._mine_packed(bdb, minsup)

    def _mine_packed(self, bdb: BitmapDB, minsup: int,
                     ) -> Tuple[ItemsetSupports, DeviceMiningStats]:
        stats = DeviceMiningStats()
        t0 = time.perf_counter()

        out: ItemsetSupports = {}
        for r, item in enumerate(bdb.items):
            out[frozenset((item,))] = int(bdb.supports[r])
            stats.nodes += 1

        store = self._make_store(bdb)
        self._minsup = minsup
        self._n_trans = bdb.n_trans
        supports = bdb.supports.astype(np.int32)
        root = ClassNode(
            itemsets=[(it,) for it in bdb.items],
            rows=np.arange(bdb.n_items, dtype=np.int32),
            supports=supports,
            representation="tidset",       # level-1 rows are TID bitmaps
            # payload: the representation this class's CHILDREN will be
            # materialised in (declat flips at level 2; adaptive flips
            # when the density threshold clears).
            payload=self._child_representation("tidset", supports))
        # Work metrics use the REAL block count: a sharded store pads
        # its block axis up to the shard count, and charging those
        # all-zero pad blocks to ``word_ops_full`` inflated every
        # DistributedMiner run's saved-fraction (ISSUE 5 bugfix).
        self._n_blocks = bdb.n_blocks
        self._store = store
        self._out = out
        self._stats = stats
        # No chunk may form a dispatch whose temporaries outgrow the
        # device: bound the width by memory at this run's row width.
        cap = hbm_pair_cap(self._device_words_per_pair(store),
                           device_memory_bytes(), _PAIR_BUCKETS)
        pair_chunk = min(self.pair_chunk, cap)
        # Autotuned chunk width: every bitmap pair in a run moves the
        # same per-pair word mass, so the width is one run-wide value
        # (the N-list engine's is per length bucket).
        self._chunk_width = (min(chunk_width_for(
            self._autotune_words_per_pair(bdb), pair_chunk,
            _PAIR_BUCKETS, BITMAP_REF_ROW_WORDS), cap)
            if self.autotune_chunk else None)
        sched = FrontierScheduler(self, pair_chunk,
                                  inflight=self.inflight,
                                  drain_target=self._chunk_width)
        sched.run(root)
        stats.note_allocator(store)
        stats.note_scheduler(sched)
        stats.runtime_s = time.perf_counter() - t0
        return out, stats

    def _autotune_words_per_pair(self, bdb: BitmapDB) -> int:
        """Per-DEVICE word mass one pair moves — the autotune budget's
        numerator.  The 2-D distributed miner overrides this to divide
        by its cls-axis size: each cls-shard only evaluates 1/n_cls of
        the chunk, so at equal per-device VMEM the chunk can be n_cls
        times wider (ISSUE 9 satellite 6)."""
        return bdb.n_blocks * self.block_words

    def _device_words_per_pair(self, store: DeviceRowStore) -> int:
        """Words of one slab row on one device — the unit of the memory
        bound on the pair chunk (the distributed miner's rows are split
        over its block shards)."""
        return store.words_per_row

    def _make_store(self, bdb: BitmapDB) -> DeviceRowStore:
        """Allocate the device slab.  Subclasses (the distributed miner)
        override this to place it under a sharded layout."""
        return DeviceRowStore(
            bdb.bitmaps,
            capacity=bdb.n_items + min(self.pair_chunk, 4096))

    # -- representation policy (ISSUE 6) ------------------------------------

    def _child_representation(self, member_rep: str,
                              supports: np.ndarray) -> str:
        """Decide, once per class, the representation its children are
        materialised in.  Flips are ONE-WAY (a diffset subtree never
        reverts — its parent tidset rows are freed when the class
        drains), and the adaptive rule only fires when the class
        density clears ``diff_density + diff_hysteresis``: a class
        straddling the bare threshold keeps its tidsets, so the choice
        cannot oscillate across consecutive drain groups."""
        if member_rep == "diffset":
            return "diffset"               # one-way: stay diffset
        if self.scheme == "declat":
            return "diffset"               # unconditional level-2 flip
        if self.diff_density is None:
            return "tidset"                # eclat: tidset everywhere
        if supports.size == 0:
            return "tidset"
        density = float(np.mean(supports)) / max(self._n_trans, 1)
        if density >= self.diff_density + self.diff_hysteresis:
            return "diffset"
        return "tidset"

    # -- FrontierScheduler client protocol ----------------------------------

    def pair_columns(self, klass: ClassNode, ia: np.ndarray,
                     ib: np.ndarray) -> Dict[str, np.ndarray]:
        # Operand orientation (paper Alg. 1/2), keyed off what the
        # member rows HOLD (klass.representation) and what the children
        # should BECOME (klass.payload — fixed at make_class time):
        #   tidset -> tidset:   Z = T(Px) & T(Py)          (op AND)
        #   tidset -> diffset:  D(xy)  = T(x)  & ~T(y)     (op DIFF,
        #       the in-scatter representation conversion: U=x, V=y)
        #   diffset members:    D(Pxy) = D(Py) & ~D(Px)    (op DIFF,
        #       U=Py, V=Px)
        if klass.representation == "diffset":
            ua, vb, op = ib, ia, _OP_DIFF
        elif klass.payload == "diffset":
            ua, vb, op = ia, ib, _OP_DIFF
        else:
            ua, vb, op = ia, ib, _OP_AND
        return {"ua": klass.rows[ua].astype(np.int32),
                "vb": klass.rows[vb].astype(np.int32),
                "rho": klass.supports[ia].astype(np.int32),
                "op": np.full(ia.size, op, np.int8)}

    def chunk_sort_key(self, cols: Dict[str, np.ndarray],
                       ) -> "np.ndarray | None":
        """Stable-sort mixed drain groups by dispatch mode so chunk
        slices stay mode-homogeneous: pure schemes (and most adaptive
        groups) keep exactly ONE fused dispatch per chunk; only a chunk
        that genuinely straddles the AND/DIFF boundary splits in two."""
        op = cols["op"]
        if op.size and int(op.min()) != int(op.max()):
            return op
        return None                        # homogeneous: keep order

    def chunk_widths(self, cols: Dict[str, np.ndarray],
                     ) -> "np.ndarray | None":
        """Per-pair chunk-width cap (ISSUE 7): uniform for the bitmap
        engine — every pair moves ``n_blocks * block_words`` operand
        words, so the equal-VMEM width is one run-wide bucket."""
        if self._chunk_width is None:
            return None
        return np.full(cols["ua"].size, self._chunk_width, np.int64)

    def evaluate_pairs(self, cols: Dict[str, np.ndarray],
                       ) -> PendingPairResult:
        """One pair-chunk slice -> ONE fused device dispatch per
        representation present (exactly one for mode-homogeneous
        chunks — the common case, see ``chunk_sort_key``).

        Returns a :class:`PendingPairResult` whose ``resolve()`` yields
        the frequent children as ``(ki, slot, support, None)`` tuples
        (``ki`` = chunk-local pair index).  The dispatches launch here
        (async); the readbacks happen at resolve."""
        store, stats = self._store, self._stats
        ua, vb, rho, op = cols["ua"], cols["vb"], cols["rho"], cols["op"]
        n = int(ua.size)
        stats.candidates += n
        # word_ops_full is the dense tidset full-scan cost for EVERY
        # pair (the paper's non-ES baseline): diff dispatches that skip
        # zero-mass blocks show up as saved fraction, not a moving
        # baseline.
        stats.word_ops_full += n * self._n_blocks * self.block_words

        slots = store.alloc(n)
        segments = []
        for op_code, mode in ((_OP_AND, "and"), (_OP_DIFF, "diff")):
            sel = np.nonzero(op == op_code)[0]
            if sel.size == 0:
                continue
            raw = self._dispatch_launch(store, ua[sel], vb[sel],
                                        slots[sel], rho[sel], mode)
            segments.append((sel, mode, rho[sel].astype(np.int64), raw))
        return PendingPairResult(self, n, slots, segments)

    def make_class(self, parent: ClassNode,
                   children: List[Child]) -> ClassNode:
        # host-sync: host child metadata (python ints); no device value
        supports = np.asarray([c.support for c in children], np.int32)
        # The children were materialised in the representation the
        # parent committed to at ITS make_class time; decide the
        # grandchildren's representation here, once, so every sibling
        # pair of the new class agrees on a dispatch mode.
        rep = parent.payload
        return ClassNode(
            itemsets=[c.itemset for c in children],
            # host-sync: host child metadata; no device value touched
            rows=np.asarray([c.row for c in children], np.int32),
            supports=supports,
            representation=rep,
            payload=self._child_representation(rep, supports))

    def emit(self, itemset: Tuple[Hashable, ...], support: int) -> None:
        self._out[frozenset(itemset)] = support
        self._stats.nodes += 1

    def release(self, klass: ClassNode) -> None:
        self._store.free(klass.rows)

    def maybe_compact(self, reserve: int) -> "np.ndarray | None":
        """Drain-group boundary hook: compact the slab when occupancy
        warrants it.  Returns the slot mapping for the scheduler to
        remap every live frontier handle (or None)."""
        return self._store.compact_if_sparse(
            self.compact_occupancy, reserve=reserve, backend=self.backend)

    def _dispatch_launch(self, store: DeviceRowStore, ua: np.ndarray,
                         vb: np.ndarray, slots: np.ndarray,
                         rho: np.ndarray, mode: str) -> Tuple:
        """Launch one fused device dispatch and return its un-read
        device outputs ``(cnt, blocks, alive)`` — NO host sync here;
        JAX async dispatch returns immediately and the blocking
        readbacks live in ``_dispatch_resolve`` (the retire path).

        ``mode`` is "and" (tidset intersect) or "diff" (dEclat
        difference — ``ops.screen_and_diff``).  The distributed miner
        overrides the launch/resolve pair with the shard_map
        dispatches."""
        n = int(ua.size)
        cap = store.capacity
        # minsup is always the real threshold: the dispatch's
        # survivor-only scatter gate needs it even with ES disabled
        # (the ``early_stop`` flag alone controls the in-scan abort).
        if mode == "diff":
            store.rows, store.suffix, cnt, blocks, alive = \
                ops.screen_and_diff(
                    store.rows, store.suffix,
                    _bucket_pad(ua, n), _bucket_pad(vb, n),
                    _bucket_pad(slots, n, fill=cap),  # OOB pad -> dropped
                    _bucket_pad(rho, n), jnp.int32(self._minsup),
                    early_stop=self.early_stop, backend=self.backend)
        else:
            store.rows, store.suffix, cnt, blocks, alive = \
                ops.screen_and_intersect(
                    store.rows, store.suffix,
                    _bucket_pad(ua, n), _bucket_pad(vb, n),
                    _bucket_pad(slots, n, fill=cap),  # OOB pad -> dropped
                    _bucket_pad(rho, n), jnp.int32(self._minsup),
                    mode=mode, early_stop=self.early_stop,
                    backend=self.backend)
        self._stats.device_calls += 1
        _count_lanes(self._stats, n)
        return cnt, blocks, alive

    def _dispatch_resolve(self, raw: Tuple, n: int,
                          ) -> Tuple[np.ndarray, np.ndarray]:
        """Blocking readback of one launched dispatch + work/attribution
        stats (the retire path's deliberate host sync).  Returns
        ``(cnt, alive)`` trimmed to the chunk length, where ``cnt`` is
        the raw kernel count (support for "and", diffset size for
        "diff") and ``alive`` marks pairs that survived ES."""
        stats = self._stats
        cnt, blocks, alive = _read_dispatch(stats, raw, n)
        stats.word_ops += int(blocks.sum()) * self.block_words
        if self.early_stop:
            # Attribution: a dead pair that did at most one (charged)
            # block was killed by the fused one-block screen — including
            # on single-block datasets (nb == 1) and pairs that died on
            # the final block (blocks == nb), which the pre-ISSUE-2 code
            # dropped from both buckets.  The ``<= 1`` covers diff
            # dispatches, whose skip-aware counter may not charge the
            # screen block itself (zero-mass prefix).
            dead = ~alive
            stats.screened_out += int((dead & (blocks <= 1)).sum())
            stats.kernel_aborts += int((dead & (blocks > 1)).sum())
        return cnt, alive


def mine_bitmap(db: Sequence[Sequence[Hashable]], minsup: int,
                scheme: str = "eclat", early_stop: bool = True,
                **kw) -> Tuple[ItemsetSupports, DeviceMiningStats]:
    """Convenience front-end mirroring ``oracle.mine``."""
    return BitmapMiner(scheme=scheme, early_stop=early_stop, **kw).mine(
        db, minsup)
