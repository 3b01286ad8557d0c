"""Device-resident PrePost+: N-lists live in a pooled device slab.

A job takes a packed ``BitmapDB`` (:meth:`DevicePrePost.mine_packed`).
The PPC-tree's level-1 N-lists are built from the packed rows in numpy
(:func:`build_nlists`: two sorts of the transactions' item paths, no
loop over transactions), code for code the tree ``oracle.PPCTree``
builds, which stays the plain reference.  Everything after it is
device-resident (the ISSUE 3 unification — third engine on the shared
allocator): every N-list the DFS can still touch is an extent of one
persistent ``int32[capacity, 3]`` PPC-code slab
(``core.rowstore.NListPool``), and the host only ever moves row indices
and small int vectors around.  Each sibling pair chunk is TWO fused
device dispatches since ISSUE 5 (survivor-only, allocation-tight
materialization):

  * pre-pass (``kernels.ops.nlist_presize``): gather both operand
    N-lists out of the slab by extent offset, run the vmapped
    two-pointer merge carrying the paper's ``rho_V - skip``
    early-stopping criterion (with the Z-mass erratum fix, see
    core/oracle.py) inside the ``lax.while_loop`` guard, and count the
    Z-merge groups — the host learns every candidate's exact child
    length and support while the match table stays on device;
  * scatter pass (``kernels.ops.nlist_scatter``): Z-merge consecutive
    slots sharing a V ancestor code (Alg. 3 line 31) and write the
    compacted child N-lists straight into *tight* extents allocated
    for the surviving children only — dead candidates cost zero
    scatter words and zero pool mass, and a chunk with no survivors
    skips this dispatch entirely.

Comparison counts reported by the device path are exactly the oracle's
(same merge, same abort points); tests assert equality (invariant I4).
``backend`` selects the merge implementation: pure-jnp ``while_loop``
("jnp", the CPU production path) or the Pallas kernel
(``kernels/nlist_merge.py``, "pallas"/"auto"-on-TPU), both bit-exact vs
``kernels.ref.nlist_extend_ref``.

Since ISSUE 4 the DFS is the shared ``core.frontier.FrontierScheduler``
(the same cross-class drain-group batching as the bitmap engines), so
deep DFS regions no longer dispatch per class member, and the pool is
compacted/re-bucketed at drain-group boundaries.  Comparison counts are
batching-invariant (each pair's merge is independent), so they remain
exactly the oracle's (I4).
"""

from __future__ import annotations

import time
from dataclasses import dataclass
from typing import (Any, Dict, FrozenSet, Hashable, List, NamedTuple,
                    Sequence, Tuple)

import numpy as np

from repro.core.guards import host_sync
from repro.core.oracle import MiningStats
from repro.core.frontier import (Child, ClassNode, EngineAccounting,
                                 FrontierScheduler)
from repro.core.rowstore import NListPool
from repro.core.bitmap import (NL_LEN_BUCKETS, NL_PAIR_CHUNK_BUCKETS,
                               NL_REF_LEN, BitmapDB, bucket_pad,
                               chunk_width_for, device_memory_bytes,
                               nl_hbm_pair_cap, nl_pad_len, nl_pad_len_np)
from repro.core.trace import span
from repro.kernels import ops

ItemsetSupports = Dict[FrozenSet[Hashable], int]

# Canonical table lives in core.bitmap next to bucket_pad (ISSUE 5
# consolidation) so the pair-chunk clamp and the pad logic cannot drift.
_PAIR_BUCKETS = NL_PAIR_CHUNK_BUCKETS


def _pad_len(n: int) -> int:
    """Bucketed N-list gather width (power-of-two fallback past the
    largest tuned bucket — huge N-lists must not be a hard error)."""
    return nl_pad_len(n)


class PPCLists(NamedTuple):
    """Level-1 N-lists of a PPC-tree, items in decreasing support
    (``oracle.PPCTree.order_desc``): item ``k``'s codes are
    ``codes[start_k : start_k + lengths[k]]``, ascending pre-order rank."""

    items: List[Hashable]
    supports: np.ndarray      # int64 (F,)
    lengths: np.ndarray       # int64 (F,)
    codes: np.ndarray         # int32 (sum(lengths), 3): pre, post, freq


def _lex_order(mat: np.ndarray) -> np.ndarray:
    """Stable order of ``mat``'s rows, lexicographic on their values
    (unsigned, zero-padded on the right, so a prefix sorts first): each
    row read as one big-endian byte string."""
    be = np.ascontiguousarray(mat.astype(mat.dtype.newbyteorder(">")))
    return np.argsort(be.view(f"S{be.shape[1] * be.itemsize}")[:, 0],
                      kind="stable")


def _lcp(paths: np.ndarray, length: np.ndarray) -> np.ndarray:
    """Common prefix length of each row with the row before it (0 for
    the first row), of zero-padded rows."""
    diff = paths[1:] != paths[:-1]
    lcp = np.zeros(len(paths), np.int64)
    lcp[1:] = np.where(diff.any(axis=1), diff.argmax(axis=1),
                       paths.shape[1])
    return np.minimum(lcp, length)


def build_nlists(bdb: BitmapDB, minsup: int) -> PPCLists:
    """The PPC-tree of ``bdb``'s rows frequent at ``minsup``, as N-lists,
    built from the packed bits with no loop over transactions.

    A transaction's path is its frequent items in decreasing support,
    ties broken by ``repr`` (the oracle's order); a tree node is a
    distinct path prefix.  With the paths sorted so that every node's
    transactions are neighbours, a row opens the nodes at the depths
    past its common prefix with the row before it.  The oracle's tree
    visits children in the order transactions introduced them, so the
    rows are sorted twice: by item, which makes each node a run of rows
    whose first transaction is the run's smallest id, then by those
    first ids, depth by depth.  In that order the nodes, taken row by
    row and depth by depth, come in pre-order, and a node's run and the
    nodes the run opens give its frequency and subtree size:
    ``post = pre - depth + size - 1`` (the root is pre 0, depth 0)."""
    keep = np.flatnonzero(bdb.supports >= minsup)
    order = sorted(keep.tolist(), key=lambda r: (-int(bdb.supports[r]),
                                                 repr(bdb.items[r])))
    items = [bdb.items[r] for r in order]
    # host-sync: pack-time host supports; no device value touched
    supports = np.asarray(bdb.supports, np.int64)[order]
    n_items = len(order)
    if n_items == 0:
        return PPCLists(items, supports, np.zeros(0, np.int64),
                        np.zeros((0, 3), np.int32))

    # Each transaction's ranks + 1, ascending, zero-padded to one width.
    words = np.ascontiguousarray(bdb.bitmaps[order]).reshape(n_items, -1)
    bits = np.unpackbits(words.view(np.uint8), axis=1,
                         bitorder="little")[:, :bdb.n_trans]
    tid, rank = np.nonzero(np.ascontiguousarray(bits.T))
    length = np.bincount(tid, minlength=bdb.n_trans)
    tids = np.flatnonzero(length)
    length = length[tids]
    width = int(length.max())
    paths = np.zeros((tids.size, width), np.min_scalar_type(n_items))
    starts = np.cumsum(length) - length
    paths.reshape(-1)[np.arange(tid.size)
                      + np.repeat(np.arange(tids.size) * width - starts,
                                  length)] = rank + 1

    # Sorted by item, each node is a run of rows: per row and depth, the
    # first transaction and the frequency of the row's node there.
    by_item = _lex_order(paths)
    paths, length, tids = paths[by_item], length[by_item], tids[by_item]
    lcp = _lcp(paths, length)
    first = np.zeros((width, tids.size), np.uint32)   # filled by depth
    freq = np.zeros((width, tids.size), np.int32)
    for d in range(width):
        runs = np.flatnonzero(lcp <= d)
        count = np.diff(np.r_[runs, tids.size])
        first[d] = np.repeat(np.minimum.reduceat(tids, runs) + 1, count)
        freq[d] = np.repeat(count, count)
    first[np.arange(width)[:, None] >= length[None, :]] = 0
    first, freq = first.T, freq.T

    # Sorted by first transaction: pre-order.  Row i opens the nodes at
    # depths lcp[i] + 1 .. length[i]; a node's run starts at the row
    # that opens it.
    by_first = _lex_order(first)
    paths, length, freq = paths[by_first], length[by_first], freq[by_first]
    lcp = _lcp(paths, length)
    opened = length - lcp
    row = np.repeat(np.arange(paths.shape[0]), opened)
    cum = np.concatenate([[0], np.cumsum(opened)])
    depth = lcp[row] + 1 + np.arange(row.size) - cum[row]
    at = row * width + depth - 1
    pre = 1 + np.arange(row.size)
    count = freq.ravel()[at]
    size = length[row] - depth + 1 + cum[row + count] - cum[row + 1]
    post = pre - depth + size - 1

    item = paths.ravel()[at] - 1                   # narrow: a radix sort
    ids = np.argsort(item, kind="stable")          # pre ascending per item
    codes = np.stack([pre[ids], post[ids], count[ids]], axis=1)
    return PPCLists(items, supports, np.bincount(item, minlength=n_items),
                    codes.astype(np.int32))


class PendingMergeResult:
    """Lazy result handle for one N-list ``evaluate_pairs`` chunk
    (ISSUE 7 pipeline): the merge pre-pass has been *launched*; the
    blocking readbacks of child_len/support/cmps/checks/alive, the
    tight survivor extent allocation AND the scatter dispatch are all
    deferred to ``resolve()`` at group retirement.

    Deferring the scatter past other groups' dispatches is sound
    because ``ops.nlist_scatter`` re-gathers its operand windows from
    the *current* slab by offset and the device match table
    (``out_slot``) is window-relative: this group's operand extents are
    live until its own retirement, other groups only scatter into
    freshly allocated extents, and pool growth preserves offsets.  A
    compaction landing while the group is in flight DOES move extents —
    pool row ids are stable (``remap`` is a no-op) but offsets are not,
    so ``resolve()`` re-resolves every offset through the pool's host
    tables at scatter time instead of caching dispatch-time values."""

    __slots__ = ("_miner", "_n", "_u_row", "_v_row", "_u_len", "_v_len",
                 "_lu", "_lv", "_raw")

    def __init__(self, miner: "DevicePrePost", n: int,
                 u_row: np.ndarray, v_row: np.ndarray,
                 u_len: np.ndarray, v_len: np.ndarray,
                 lu: int, lv: int, raw: Tuple):
        self._miner = miner
        self._n = n
        self._u_row, self._v_row = u_row, v_row
        self._u_len, self._v_len = u_len, v_len
        self._lu, self._lv = lu, lv
        self._raw = raw

    def remap(self, mapping) -> None:
        """Pool row ids are compaction-stable; nothing to rewrite."""

    def resolve(self) -> List[Tuple[int, int, int, Any]]:
        miner = self._miner
        pool, stats = miner._pool, miner._stats
        n = self._n
        out_slot, child_len, support, cmps, checks, alive = self._raw
        # host-sync: the audited group-retirement readback (PR 7) — one
        # deliberate d2h per retired merge dispatch, deferred via the
        # handle so in-flight groups overlap
        with host_sync("group-retirement accounting readback"):
            child_len = np.asarray(child_len[:n])
            support = np.asarray(support[:n])
            alive = np.asarray(alive[:n])
            cmps_total = int(np.asarray(cmps[:n]).sum())
            checks_total = int(np.asarray(checks[:n]).sum())
        stats.comparisons += cmps_total
        if miner.early_stop:
            # One ES bound evaluation per skipped V code — exactly the
            # oracle's es_checks, and aborts are only attributed when
            # the guard was actually armed (the non-ES merge must
            # report zero deaths).
            stats.es_checks += checks_total
            stats.es_aborts += int((~alive).sum())

        freq = support >= miner._minsup  # aborted pairs report support 0
        kept = np.nonzero(freq)[0]
        if kept.size == 0:
            return []

        # HOST-SYNC (load-bearing): the tight survivor-extent
        # allocation is *data-dependent* — extent sizes are the
        # pre-pass's exact child lengths, so the host must block on the
        # ``child_len`` readback above before it can size ``alloc_rows``
        # (and grow the pool) for the scatter below.  This is why the
        # presize->scatter pair cannot be fused into one launch and why
        # the scatter rides the retire path.
        child_rows = pool.alloc_rows(child_len[kept])
        out_off = np.full(n, pool.capacity, np.int32)   # default: dropped
        out_off[kept] = pool.offsets(child_rows)
        # Offsets re-resolved at scatter time (NOT dispatch time): an
        # in-flight compaction may have moved every live extent.
        u_off = pool.offsets(self._u_row)
        v_off = pool.offsets(self._v_row)

        def pad(arr, fill=0):
            return bucket_pad(arr, n, _PAIR_BUCKETS, fill)
        pool.codes, _ = ops.nlist_scatter(
            pool.codes, out_slot, pad(u_off), pad(self._u_len),
            pad(v_off), pad(self._v_len),
            pad(out_off, fill=pool.capacity),
            lu=self._lu, lv=self._lv, backend=miner.backend)
        stats.device_calls += 1
        stats.child_scatters += int(kept.size)
        stats.scatter_words += 3 * int(child_len[kept].sum())
        self._raw = None                             # drop device refs
        return [(int(b), int(row), int(support[b]), int(child_len[b]))
                for b, row in zip(kept, child_rows, strict=True)]


@dataclass
class DevicePrePostStats(MiningStats, EngineAccounting):
    """Oracle-compatible counters plus the shared device-engine
    accounting struct (``frontier.EngineAccounting``).

    ``build_s`` is the host time of the ``ppc.build`` span (the PPC-tree
    build and the level-1 N-lists' upload).  ``nl_codes`` counts the real
    operand codes the merge pre-passes gathered (``|U| + |V|`` per
    candidate); ``nl_gather_codes`` counts what they gathered at the
    padded widths (padded pairs x (``lu`` + ``lv``))."""

    build_s: float = 0.0
    nl_codes: int = 0
    nl_gather_codes: int = 0

    # Legacy names kept as read-only views of the shared accounting.
    @property
    def pool_grows(self) -> int:
        return self.grows

    @property
    def peak_codes(self) -> int:
        return self.peak_live

    @property
    def deaths(self) -> int:
        return self.es_aborts

    def as_dict(self) -> Dict[str, float]:
        d = super().as_dict()
        d.update(pool_grows=self.pool_grows, peak_codes=self.peak_codes,
                 **self.accounting_dict())
        return d


class DevicePrePost:
    """PrePost+ over a device-resident N-list pool with a fused
    merge pre-pass + survivor-only scatter pass per pair chunk.

    The DFS is ``core.frontier.FrontierScheduler`` — the same work-stack
    + cross-class drain-group batching as the bitmap engines, so deep
    DFS regions no longer issue one dispatch per class member's sibling
    window: pairs from MANY classes (with heterogeneous U operands —
    the dispatches take per-pair extents) fill each chunk, and
    :meth:`chunk_sort_key` keeps each chunk's gather widths homogeneous
    by length bucket.  Child extents are allocated from the pre-pass's
    *exact* lengths for *survivors only* — the pool never holds a
    pessimistic ``min(|U|, |V|)`` extent.  ``compact_occupancy``: see
    ``BitmapMiner``; 0 disables.
    """

    def __init__(self, early_stop: bool = True, pair_chunk: int = 8192,
                 backend: str = "auto", compact_occupancy: float = 0.25,
                 inflight: int = 2, autotune_chunk: bool = False):
        self._jobs = 0           # sequence number of the next job's spans
        self.early_stop = early_stop
        self.pair_chunk = min(pair_chunk, _PAIR_BUCKETS[-1])
        self.backend = backend
        self.compact_occupancy = compact_occupancy
        # Dispatch-pipeline knobs (ISSUE 7): ring depth and per-bucket
        # chunk-width autotuning (short-operand chunks dispatch wider at
        # equal VMEM footprint; see core.bitmap.chunk_width_for).
        self.inflight = max(1, int(inflight))
        self.autotune_chunk = bool(autotune_chunk)
        self._widths: Dict[int, int] = {}
        self._memory_bytes: "int | None" = None

    def mine(self, db: Sequence[Sequence[Hashable]], minsup: int,
             ) -> Tuple[ItemsetSupports, DevicePrePostStats]:
        if minsup < 1:
            raise ValueError("minsup must be an absolute count >= 1")
        return self.mine_packed(BitmapDB.from_db(db, minsup), minsup)

    def mine_packed(self, bdb: BitmapDB, minsup: int,
                    ) -> Tuple[ItemsetSupports, DevicePrePostStats]:
        """Mine a packed :class:`BitmapDB` to the complete itemset ->
        support map on the host (``BitmapMiner.mine_packed``'s
        contract); rows below ``minsup`` are left out."""
        if minsup < 1:
            raise ValueError("minsup must be an absolute count >= 1")
        job = self._jobs
        self._jobs += 1
        with span("mine", job=job):
            return self._mine_packed(bdb, minsup)

    def _mine_packed(self, bdb: BitmapDB, minsup: int,
                     ) -> Tuple[ItemsetSupports, DevicePrePostStats]:
        stats = DevicePrePostStats()
        t0 = time.perf_counter()
        with span("ppc.build", acc=(stats, "build_s")):
            lists = build_nlists(bdb, minsup)
            pool = NListPool(capacity=max(64, 2 * int(
                nl_pad_len_np(np.maximum(lists.lengths, 1)).sum())))
            rows = pool.alloc_rows(lists.lengths)
            if rows.size:
                pool.write_rows(rows, np.split(
                    lists.codes, np.cumsum(lists.lengths)[:-1]))
        # The search runs in ascending support (oracle.mine_prepost).
        order_asc, rows = lists.items[::-1], rows[::-1]
        supports, lengths = lists.supports[::-1], lists.lengths[::-1]
        out: ItemsetSupports = {frozenset((it,)): int(s)
                                for it, s in zip(order_asc, supports,
                                                 strict=True)}
        stats.nodes += len(out)
        root = ClassNode(itemsets=[(it,) for it in order_asc], rows=rows,
                         supports=supports.astype(np.int32),
                         payload=lengths.astype(np.int32))

        self._minsup = minsup
        self._pool = pool
        self._out = out
        self._stats = stats
        # The widest autotuned chunk is the smallest bucket's width;
        # draining that many pairs keeps wide chunks full.
        drain_target = (self._width_for_bucket(NL_LEN_BUCKETS[0])
                        if self.autotune_chunk else None)
        sched = FrontierScheduler(self, self.pair_chunk,
                                  inflight=self.inflight,
                                  drain_target=drain_target)
        sched.run(root)
        stats.note_allocator(pool)
        stats.note_scheduler(sched)
        stats.runtime_s = time.perf_counter() - t0
        return out, stats

    # -- FrontierScheduler client protocol ----------------------------------

    def pair_columns(self, klass: ClassNode, ia: np.ndarray,
                     ib: np.ndarray) -> Dict[str, np.ndarray]:
        lens = klass.payload               # per-member exact N-list lengths
        return {"u_row": klass.rows[ia].astype(np.int32),
                "v_row": klass.rows[ib].astype(np.int32),
                "u_len": lens[ia].astype(np.int32),
                "v_len": lens[ib].astype(np.int32),
                "rho_v": klass.supports[ib].astype(np.int32)}

    def chunk_sort_key(self, cols: Dict[str, np.ndarray]) -> np.ndarray:
        """Length-aware drain-group composition (ISSUE 5): the scheduler
        stably sorts drained pairs by the bucket of their longest
        operand before chunk slicing, so one huge N-list widens the
        ``lu``/``lv`` gather only for its own (homogeneous) chunk."""
        return nl_pad_len_np(np.maximum(cols["u_len"], cols["v_len"]))

    def _width_for_bucket(self, bucket: int) -> int:
        """Chunk width for pairs whose longest operand sits in length
        bucket ``b``: ``pair_chunk``, or with ``autotune_chunk`` the
        autotuned width (a pair moves ~3*b code words, so short-operand
        chunks widen proportionally, floored at ``pair_chunk``); then
        capped by device memory, since such a chunk gathers at widths
        ``lu, lv <= b`` (``core.bitmap.nl_hbm_pair_cap``)."""
        w = self._widths.get(bucket)
        if w is None:
            w = self.pair_chunk
            if self.autotune_chunk:
                w = chunk_width_for(3 * bucket, self.pair_chunk,
                                    _PAIR_BUCKETS, 3 * NL_REF_LEN)
            if self._memory_bytes is None:
                self._memory_bytes = device_memory_bytes()
            w = min(w, nl_hbm_pair_cap(bucket, self._memory_bytes))
            self._widths[bucket] = w
        return w

    def chunk_widths(self, cols: Dict[str, np.ndarray],
                     ) -> "np.ndarray | None":
        """Per-pair chunk-width cap (ISSUE 7), evaluated on the sorted
        columns: pairs are already ordered by length bucket
        (``chunk_sort_key``), so the caps are non-increasing and the
        scheduler's greedy slicer packs each bucket at its own width.
        ``None`` (fixed ``pair_chunk`` strides) where no bucket of the
        group is narrowed and autotuning is off."""
        buckets = nl_pad_len_np(np.maximum(cols["u_len"], cols["v_len"]))
        keys = np.unique(buckets)
        # host-sync: host chunk-width table; no device value touched
        caps = np.asarray([self._width_for_bucket(int(b)) for b in keys],
                          np.int64)
        if not self.autotune_chunk and caps.min() >= self.pair_chunk:
            return None
        return caps[np.searchsorted(keys, buckets)]

    def evaluate_pairs(self, cols: Dict[str, np.ndarray],
                       ) -> PendingMergeResult:
        """One pair-chunk slice -> merge pre-pass + survivor-only
        scatter (ISSUE 5: two dispatches instead of one, pessimistic
        extents for none).

        The pre-pass (``ops.nlist_presize``) runs the gather + ES merge
        and returns each candidate's exact child length, support and
        aliveness — the merge loop runs exactly once, so comparison
        counts stay exactly the oracle's (I4).  The host then allocates
        extents for the *survivors only*, sized by their *actual*
        lengths (the pessimistic ``min(|U|, |V|)`` allocation is gone),
        and the scatter pass (``ops.nlist_scatter``) Z-merges the
        device-resident match table into those tight extents.  A chunk
        with no survivors skips the scatter dispatch entirely.

        Pipelined (ISSUE 7): only the pre-pass *launches* here.  The
        readbacks, the tight allocation (which must block on the exact
        child lengths) and the scatter dispatch live in the returned
        :class:`PendingMergeResult` and run at group retirement, whose
        ``resolve()`` yields the frequent children as
        ``(ki, row, support, length)`` tuples.  Operand U/V extents
        vary per pair (cross-class chunk): the gather widths are the
        buckets of the chunk maxima, kept homogeneous by
        :meth:`chunk_sort_key`."""
        pool, stats = self._pool, self._stats
        u_len, v_len = cols["u_len"], cols["v_len"]
        n = int(u_len.size)
        stats.candidates += n
        lu = nl_pad_len(int(u_len.max()))
        lv = nl_pad_len(int(v_len.max()))
        u_off = pool.offsets(cols["u_row"])
        v_off = pool.offsets(cols["v_row"])

        def pad(arr, fill=0):
            return bucket_pad(arr, n, _PAIR_BUCKETS, fill)
        u_len_p = pad(u_len)
        raw = ops.nlist_presize(
            pool.codes, pad(u_off), u_len_p, pad(v_off), pad(v_len),
            pad(cols["rho_v"]), np.int32(self._minsup),
            lu=lu, lv=lv, early_stop=self.early_stop,
            backend=self.backend)
        stats.device_calls += 1
        stats.nl_codes += int(u_len.sum()) + int(v_len.sum())
        stats.nl_gather_codes += u_len_p.size * (lu + lv)
        return PendingMergeResult(self, n, cols["u_row"], cols["v_row"],
                                  u_len, v_len, lu, lv, raw)

    def make_class(self, parent: ClassNode,
                   children: List[Child]) -> ClassNode:
        del parent
        return ClassNode(
            itemsets=[c.itemset for c in children],
            # host-sync: host child metadata; no device value touched
            rows=np.asarray([c.row for c in children], np.int32),
            supports=np.asarray([c.support for c in children], np.int32),
            payload=np.asarray([c.extra for c in children], np.int32))

    def emit(self, itemset: Tuple[Hashable, ...], support: int) -> None:
        self._out[frozenset(itemset)] = support
        self._stats.nodes += 1

    def release(self, klass: ClassNode) -> None:
        self._pool.free_rows(klass.rows)

    def maybe_compact(self, reserve: int) -> None:
        """Drain-group boundary hook.  Pool row ids are stable across
        compaction (offsets are indirected through the host tables), so
        the scheduler never needs to remap — always returns None.

        ``reserve`` arrives as the WHOLE drain group's pair count
        (ISSUE 5: a group's chunks allocate children cumulatively, so
        reserving one chunk's worth caused compact/grow thrash).  Child
        extents are now tight (exact lengths, survivors only), so the
        mean live extent size converts pairs into a *generous* code
        estimate; the would-halve hysteresis absorbs the remaining
        error."""
        pool = self._pool
        avg_extent = pool.live_codes // max(pool.n_live_rows, 1)
        pool.compact_if_sparse(self.compact_occupancy,
                               reserve=reserve * max(avg_extent, 1),
                               backend=self.backend)
        return None


def mine_prepost_device(db, minsup, early_stop: bool = True, **kw):
    return DevicePrePost(early_stop=early_stop, **kw).mine(db, minsup)
