"""Device-resident bitmap row store with a host-managed free list.

The frontier engine's hot-path data structure (DESIGN.md §2, ISSUE 1):
every TID bitmap / diffset row that the DFS can still touch lives in one
preallocated device slab ``uint32[capacity, n_blocks, block_words]`` with
a parallel suffix-popcount slab.  The host never sees row *contents* — it
only moves row *indices* around:

  * ``alloc(k)`` hands out ``k`` free slots (growing the slab on demand);
  * the fused kernels (``kernels.ops.screen_and_intersect``,
    ``kernels.ops.screen_and_diff`` or the shard_map variants) gather
    operands by index and scatter children back by slot index;
  * ``free(ids)`` returns slots of dead candidates / expanded classes.

The allocator is representation-agnostic (ISSUE 6): tidset and diffset
rows are both ``uint32`` bitmap rows with suffix tables, so one slab,
one free list and one compaction path serve both — what a row *means*
is tracked per class by the frontier's ``ClassNode.representation``
tag, never here.  Compaction's old->new mapping renumbers ``rows``
handles only, so representation tags survive compaction untouched.

Both mining engines allocate from this class (ISSUE 2 unification):

* **Single-device** (``mesh=None``): ``suffix`` is the global suffix
  table ``int32[capacity, n_blocks + 1]`` (``core.bitmap``'s layout).
* **Sharded** (``mesh`` given): the block axis of ``rows`` is sharded
  across ``tid_axes`` under a ``NamedSharding`` (``n_blocks`` is padded
  up to a multiple of the shard count), and ``suffix`` holds the
  *per-shard* suffix tables concatenated along axis 1 —
  ``int32[capacity, n_shards * (local_blocks + 1)]``, column-sharded so
  each shard owns exactly its own ``(local_blocks + 1)``-wide local
  suffix table.  With one shard the two layouts coincide.

Growth doubles capacity (device concat of a zero slab, re-placed under
the store's sharding).  Capacities are rounded to the next power of two
so the jit cache sees few distinct store shapes.  Exhaustion can no
longer happen: ``alloc`` grows instead of raising.

Compaction (ISSUE 4) is the inverse of growth: when occupancy drops
below a threshold, ``compact`` gathers the live rows / extents to the
front of a smaller slab in one fused device dispatch
(``kernels.ops.compact_rows`` / ``compact_codes``, pinned bit-exact by
``kernels.ref.compact_gather_ref``, Pallas variant available) and hands
the freed capacity back.  Row-store compaction *renumbers* slots and
returns an old->new mapping the frontier scheduler applies to every
live handle; N-list pool compaction keeps row ids stable (offsets are
indirected through the host tables) and additionally shrinks each
extent to the bucket of its *actual* length.  Both engines trigger
compaction only at drain-group boundaries (``core.frontier``), the one
point where the live row set is exactly the frontier.

Materialization is survivor-only since ISSUE 5: the fused dispatches
write a child row / extent only when its support cleared minsup, so a
freed slot of a dead candidate was never written (pure host
bookkeeping), and the N-list engine allocates child extents from the
pre-pass's *exact* lengths — the pessimistic ``min(|U|, |V|)`` extents
that compaction used to re-bucket away no longer exist, leaving
re-bucketing as a defragmentation detail (level-1 uploads and
``set_length`` users still benefit).
"""

from __future__ import annotations

import functools
from typing import Dict, Iterable, List, Optional, Sequence, Tuple, Union

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.guards import host_sync
from repro.core.trace import span
from repro.core.bitmap import (NL_LEN_BUCKETS, nl_pad_len, popcount32_np,
                               suffix_popcounts)


def _round_capacity(n: int) -> int:
    cap = 64
    while cap < n:
        cap *= 2
    return cap


def _largest_bucket_le(n: int) -> int:
    """Largest N-list bucket size <= ``n`` (``n`` >= the smallest bucket).

    Every bucket is a multiple of the smallest one, so splitting a free
    extent greedily with this always decomposes the tail exactly."""
    best = NL_LEN_BUCKETS[0]
    for b in NL_LEN_BUCKETS:
        if b <= n:
            best = b
    b = NL_LEN_BUCKETS[-1]
    while b * 2 <= n:                 # power-of-two fallback region
        b *= 2
        best = b
    return best


def _local_suffix_tables(rows_np: np.ndarray, n_shards: int) -> np.ndarray:
    """Per-shard suffix tables, concatenated: (n, n_shards*(nb_local+1)).

    Shard ``s`` owns columns ``[s*(nbl+1), (s+1)*(nbl+1))`` — its local
    analogue of :func:`repro.core.bitmap.suffix_popcounts_np`."""
    n, nb, _ = rows_np.shape
    nbl = nb // n_shards
    per_block = popcount32_np(rows_np).sum(axis=-1).astype(np.int32)
    pb = per_block.reshape(n, n_shards, nbl)
    suf = np.zeros((n, n_shards, nbl + 1), np.int32)
    suf[:, :, :-1] = pb[:, :, ::-1].cumsum(axis=-1)[:, :, ::-1]
    return suf.reshape(n, n_shards * (nbl + 1))


@functools.partial(jax.jit, static_argnames="cap")
def _padded_slab(rows, suffix, *, cap: int):
    """The store's initial slab and suffix table from its ``n`` real
    rows, both zero-padded to ``cap`` rows on the device.

    ``suffix`` is the real rows' per-shard suffix tables, or ``None`` for
    the global table computed here from the real rows (a zero row's
    suffix is all zeros, so padding the table equals computing it over
    the whole slab).  The output slab is the program's only slab-sized
    buffer."""
    if suffix is None:
        suffix = suffix_popcounts(rows)
    pad = cap - rows.shape[0]
    return (jnp.pad(rows, ((0, pad), (0, 0), (0, 0))),
            jnp.pad(suffix, ((0, pad), (0, 0))))


class DeviceRowStore:
    """Slab of bitmap rows + suffix tables resident on device.

    ``mesh``/``tid_axes``: when given, the block axis is sharded across
    the product of those mesh axes and both slabs live under
    ``NamedSharding``s (see module docstring for the suffix layout).

    Construction is two spans: ``store.build`` stages the ``n`` real
    rows on the host (block axis padded to the shard count, and their
    per-shard suffix tables, under a mesh) and ``store.upload`` covers
    their transfer and :func:`_padded_slab`, which zero-pads them to the
    ``capacity``-row slab on the device.  ``upload_bytes`` counts every
    host array the store puts on the device: the real rows, not the
    slab.
    """

    def __init__(self, rows_np: np.ndarray, *, capacity: int = 0,
                 mesh: Optional[Mesh] = None,
                 tid_axes: Optional[Tuple[str, ...]] = None):
        n, nb, bw = rows_np.shape
        cap = _round_capacity(max(capacity, n, 1))

        self.mesh = mesh
        self._rows_sharding = None
        self._suffix_sharding = None
        if mesh is None:
            self.n_shards = 1
        else:
            tid_axes = tuple(tid_axes) if tid_axes else tuple(mesh.axis_names)
            self.tid_axes = tid_axes
            self.n_shards = 1
            for ax in tid_axes:
                self.n_shards *= mesh.shape[ax]
            # Pad the block axis so it divides the tid shard count.
            nb = -(-nb // self.n_shards) * self.n_shards
            tid_spec: Union[str, Tuple[str, ...]] = (
                tid_axes if len(tid_axes) > 1 else tid_axes[0])
            self._rows_sharding = NamedSharding(mesh, P(None, tid_spec, None))
            self._suffix_sharding = NamedSharding(mesh, P(None, tid_spec))

        with span("store.build"):
            staged = rows_np
            if nb > rows_np.shape[1]:
                staged = np.pad(rows_np,
                                ((0, 0), (0, nb - rows_np.shape[1]), (0, 0)))
            host_suffix = (None if mesh is None
                           else _local_suffix_tables(staged, self.n_shards))
        self.n_blocks = nb
        self.local_blocks = nb // self.n_shards
        self.block_words = bw
        with span("store.upload"):
            # The staged device arrays are the call's arguments only, so
            # they are freed once the slab is built.
            rows, suffix = _padded_slab(
                jax.device_put(staged, self._rows_sharding),
                None if mesh is None
                else jax.device_put(host_suffix, self._suffix_sharding),
                cap=cap)
            if mesh is not None:
                # Re-place as ``_grow`` does: the pad keeps the inputs'
                # block sharding, so this only restores the store's
                # sharding objects (a one-device mesh normalises them).
                rows = jax.device_put(rows, self._rows_sharding)
                suffix = jax.device_put(suffix, self._suffix_sharding)
            self.rows = rows                   # uint32 (cap, nb, bw)
            self.suffix = suffix
            self.upload_bytes = staged.nbytes + (
                0 if mesh is None else host_suffix.nbytes)
        self._free: List[int] = list(range(cap - 1, n - 1, -1))
        self.grows = 0
        self.compactions = 0
        self.last_compaction_occupancy = 0.0
        self.peak_live = n
        self.peak_capacity = cap

    @property
    def capacity(self) -> int:
        return int(self.rows.shape[0])

    @property
    def words_per_row(self) -> int:
        """uint32 words one slab row pins on device (bitmap row + its
        suffix-table row)."""
        return self.n_blocks * self.block_words + int(self.suffix.shape[1])

    @property
    def peak_device_words(self) -> int:
        """High-water device footprint of the slab in uint32 words,
        summed over every shard (compaction can shrink the LIVE slab but
        not this peak).  Divide by ``jax.process_count()`` for the bench
        tier's per-host figure — the slab is sharded evenly over the
        block axis."""
        return self.peak_capacity * self.words_per_row

    @property
    def n_live(self) -> int:
        return self.capacity - len(self._free)

    @property
    def occupancy(self) -> float:
        return self.n_live / max(self.capacity, 1)

    def alloc(self, k: int) -> np.ndarray:
        """Pop ``k`` free slots (int32), growing the slab if needed."""
        if len(self._free) < k:
            self._grow(self.n_live + k)
        # host-sync: host-side free-list pop; no device value touched
        slots = np.asarray([self._free.pop() for _ in range(k)], np.int32)
        self.peak_live = max(self.peak_live, self.n_live)
        return slots

    def free(self, ids: Iterable[int]) -> None:
        self._free.extend(int(i) for i in ids)

    def _grow(self, need: int) -> None:
        with span("store.grow"):
            old = self.capacity
            new = _round_capacity(max(2 * old, need))
            rows = jnp.concatenate(
                [self.rows,
                 jnp.zeros((new - old, self.n_blocks, self.block_words),
                           jnp.uint32)])
            suffix = jnp.concatenate(
                [self.suffix,
                 jnp.zeros((new - old, self.suffix.shape[1]), jnp.int32)])
            if self._rows_sharding is not None:
                # Re-place explicitly: concat of a sharded slab with fresh
                # zeros must stay block-sharded for the shard_map dispatch.
                rows = jax.device_put(rows, self._rows_sharding)
                suffix = jax.device_put(suffix, self._suffix_sharding)
            self.rows = rows
            self.suffix = suffix
            self._free.extend(range(new - 1, old - 1, -1))
            self.grows += 1
            self.peak_capacity = max(self.peak_capacity, new)

    def compact(self, *, reserve: int = 0, backend: str = "jnp",
                ) -> np.ndarray:
        """Defragment: gather live rows to the front of a (usually
        smaller) slab in one fused device dispatch.

        Live rows keep their relative order and are preserved bit-for-bit
        (rows AND suffix tables); the slab shrinks to
        ``_round_capacity(n_live + reserve)`` and, under a mesh, is
        re-placed under the store's ``NamedSharding`` — this is what lets
        long sharded runs *shrink* again after a growth spike.

        Returns the old->new slot mapping ``int32[old_capacity]`` (-1 for
        slots that were free): callers MUST remap every live handle.

        HOST-SYNC (load-bearing, ISSUE 7 audit): the mapping is derived
        from the *host* free list (no device readback), but it must be
        applied to every frontier handle — stack, drain group AND
        in-flight pipeline handles — before the next group's columns
        are assembled, so compaction is a hard host-serialization point
        that cannot ride the pipeline ring.  Only the bookkeeping
        blocks: the ``ops.compact_rows`` gather itself is async and
        overlaps in-flight dispatches safely (they hold their operand
        values through the donation data-dependency chain).
        """
        from repro.kernels import ops

        with span("store.compact"):
            old_cap = self.capacity
            free_mask = np.zeros(old_cap, bool)
            # host-sync: host-side free-list mask; no device value touched
            free_mask[np.asarray(self._free, np.int64)] = True
            live = np.nonzero(~free_mask)[0].astype(np.int32)
            n_live = int(live.size)
            new_cap = _round_capacity(max(n_live + reserve, 1))

            perm = np.full(new_cap, -1, np.int32)   # dest slot -> src slot
            perm[:n_live] = live
            rows, suffix = ops.compact_rows(self.rows, self.suffix, perm,
                                            backend=backend)
            self.upload_bytes += perm.nbytes
            if self._rows_sharding is not None:
                rows = jax.device_put(rows, self._rows_sharding)
                suffix = jax.device_put(suffix, self._suffix_sharding)
            self.rows = rows
            self.suffix = suffix
            self._free = list(range(new_cap - 1, n_live - 1, -1))
            self.compactions += 1
            self.last_compaction_occupancy = n_live / max(new_cap, 1)

            mapping = np.full(old_cap, -1, np.int32)
            mapping[live] = np.arange(n_live, dtype=np.int32)
            return mapping

    def compact_if_sparse(self, occupancy_threshold: float, *,
                          reserve: int = 0, backend: str = "jnp",
                          ) -> Optional[np.ndarray]:
        """Compact when occupancy fell below ``occupancy_threshold`` AND
        the slab would shrink to at most half its size (hysteresis: a
        compaction that the next drain group would immediately regrow is
        worse than useless).  Returns the slot mapping, or ``None``."""
        if occupancy_threshold <= 0.0:
            return None
        new_cap = _round_capacity(max(self.n_live + reserve, 1))
        if (self.occupancy < occupancy_threshold
                and new_cap <= self.capacity // 2):
            return self.compact(reserve=reserve, backend=backend)
        return None


class NListPool:
    """Device-resident ragged pool of PPC codes (the PrePost+ analogue of
    the bitmap slab above).

    ``codes`` is one persistent ``int32[capacity, 3]`` device slab of
    ``(pre, post, freq)`` triples.  An N-list *row* is an extent
    ``[off, off + cap_len)`` of the slab, with ``cap_len`` bucketed to
    :func:`repro.core.bitmap.nl_pad_len` sizes; the host keeps the
    per-row offset/length tables plus one free list of extents per
    bucket size, and never sees code *contents* — the fused dispatch
    (``kernels.ops.nlist_extend``) gathers operand rows by offset and
    scatters child rows back by offset, all inside one jit.

    Growth mirrors ``DeviceRowStore``: capacity doubles (device concat
    of a zero slab, power-of-two rounded) and live extents are preserved
    bit-for-bit; exhaustion cannot happen.
    """

    def __init__(self, capacity: int = 4096):
        cap = _round_capacity(max(capacity, 1))
        self.codes = jnp.zeros((cap, 3), jnp.int32)
        self._free: Dict[int, List[int]] = {}   # bucket size -> extent offs
        self._bump = 0                          # slab high-water mark
        self.upload_bytes = 0                   # host arrays put on device
        self.grows = 0
        self.compactions = 0
        self.last_compaction_occupancy = 0.0
        self._row_off: List[int] = []
        self._row_len: List[int] = []           # actual (exact) lengths
        self._row_cap: List[int] = []           # bucketed extent sizes
        self._free_rows: List[int] = []
        self.live_codes = 0                     # sum of live extent sizes
        self.peak_codes = 0
        self.total_alloc_codes = 0              # cumulative extent mass

    @property
    def capacity(self) -> int:
        return int(self.codes.shape[0])

    @property
    def n_live_rows(self) -> int:
        return len(self._row_off) - len(self._free_rows)

    @property
    def occupancy(self) -> float:
        return self.live_codes / max(self.capacity, 1)

    @property
    def peak_live(self) -> int:
        """Uniform allocator-accounting alias (``EngineAccounting``)."""
        return self.peak_codes

    def _alloc_extent(self, bucket: int) -> int:
        stack = self._free.get(bucket)
        if stack:
            return stack.pop()
        # No exact-size extent: recycle a LARGER free extent by splitting
        # it — head becomes the requested bucket, the tail is released
        # back to smaller bucket free lists (greedy largest-bucket-first
        # decomposition; every bucket size is a multiple of the smallest,
        # so the tail always decomposes exactly).  Without this, capacity
        # freed in big buckets — e.g. the pessimistic extents a
        # compaction epoch shrinks away — could never serve the small
        # allocations that dominate deep in the DFS, and the slab leaked.
        bigger = sorted(b for b, s in self._free.items() if b > bucket and s)
        if bigger:
            src = bigger[0]                  # smallest sufficient extent
            off = self._free[src].pop()
            tail_off, rem = off + bucket, src - bucket
            while rem > 0:
                piece = _largest_bucket_le(rem)
                self._free.setdefault(piece, []).append(tail_off)
                tail_off += piece
                rem -= piece
            return off
        off = self._bump
        if off + bucket > self.capacity:
            self._grow(off + bucket)
        self._bump = off + bucket
        return off

    def alloc_rows(self, lengths: Sequence[int]) -> np.ndarray:
        """One row per requested length (its max capacity); returns int32
        row ids.  Actual lengths are refined later via set_length.

        HOST-SYNC (load-bearing, ISSUE 7 audit): on the mining hot path
        ``lengths`` are the presize pass's exact child lengths, so the
        caller must have blocked on that readback before this runs —
        extent placement (and any ``_grow``) is host bookkeeping that
        cannot be sized without the data.  This is why the N-list
        engine's scatter is a retire-time action, not a dispatch-time
        one (see ``core.prepost.PendingMergeResult``); the ``_grow``
        device concat itself stays async."""
        rows = np.empty(len(lengths), np.int32)
        for k, ln in enumerate(lengths):
            ln = int(ln)
            bucket = nl_pad_len(max(ln, 1))
            off = self._alloc_extent(bucket)
            if self._free_rows:
                r = self._free_rows.pop()
                self._row_off[r] = off
                self._row_len[r] = ln
                self._row_cap[r] = bucket
            else:
                r = len(self._row_off)
                self._row_off.append(off)
                self._row_len.append(ln)
                self._row_cap.append(bucket)
            self.live_codes += bucket
            self.total_alloc_codes += bucket
            rows[k] = r
        self.peak_codes = max(self.peak_codes, self.live_codes)
        return rows

    def free_rows(self, rows: Iterable[int]) -> None:
        for r in rows:
            r = int(r)
            bucket = self._row_cap[r]
            self._free.setdefault(bucket, []).append(self._row_off[r])
            self._free_rows.append(r)
            self.live_codes -= bucket

    def set_length(self, row: int, length: int) -> None:
        self._row_len[int(row)] = int(length)

    def offsets(self, rows: Sequence[int]) -> np.ndarray:
        # host-sync: host extent-table lookup; no device value touched
        return np.asarray([self._row_off[int(r)] for r in rows], np.int32)

    def lengths(self, rows: Sequence[int]) -> np.ndarray:
        # host-sync: host extent-table lookup; no device value touched
        return np.asarray([self._row_len[int(r)] for r in rows], np.int32)

    def write_rows(self, rows: Sequence[int],
                   code_arrays: Sequence[np.ndarray]) -> None:
        """Upload row contents from host (packing time only: the level-1
        N-lists come out of the PPC-tree build).  One scatter."""
        idx = np.concatenate([
            np.arange(self._row_off[int(r)],
                      self._row_off[int(r)] + len(a), dtype=np.int64)
            for r, a in zip(rows, code_arrays, strict=True)])
        # host-sync: pack-time host staging for the one h2d scatter below
        vals = np.concatenate([np.asarray(a, np.int32).reshape(-1, 3)
                               for a in code_arrays])
        self.codes = self.codes.at[jnp.asarray(idx)].set(jnp.asarray(vals))
        self.upload_bytes += idx.nbytes + vals.nbytes

    def read_row(self, row: int) -> np.ndarray:
        """Row contents as ``int32 (len, 3)`` — tests/debug only (the
        mining hot path never materialises N-lists on host)."""
        off = self._row_off[int(row)]
        ln = self._row_len[int(row)]
        # host-sync: genuine d2h readback, tests/debug only — the
        # mining hot path never calls read_row
        with host_sync("test/debug N-list readback"):
            return np.asarray(self.codes[off:off + ln])

    def _grow(self, need: int) -> None:
        old = self.capacity
        new = _round_capacity(max(2 * old, need))
        self.codes = jnp.concatenate(
            [self.codes, jnp.zeros((new - old, 3), jnp.int32)])
        self.grows += 1

    def _tight_mass(self) -> int:
        """Total bucketed mass after shrinking every live extent to the
        bucket of its actual length (what a compaction would leave)."""
        free_rows = set(self._free_rows)
        return sum(nl_pad_len(max(self._row_len[r], 1))
                   for r in range(len(self._row_off))
                   if r not in free_rows)

    def compact(self, *, reserve: int = 0, backend: str = "jnp") -> None:
        """Repack live extents to the front of a (usually smaller) slab
        in one fused device dispatch, shrinking each extent to the bucket
        of its *actual* length — this undoes the pessimistic
        ``min(|U|, |V|)`` child allocation for long-lived classes.

        Live code triples are preserved bit-for-bit and row ids stay
        stable (callers hold row ids, not offsets, so no remap is
        needed).  Free lists and the bump pointer are rebuilt from
        scratch: everything past the packed region is virgin capacity.
        """
        from repro.kernels import ops

        free_rows = set(self._free_rows)
        live = sorted((r for r in range(len(self._row_off))
                       if r not in free_rows),
                      key=lambda r: self._row_off[r])
        idx_parts: List[np.ndarray] = []
        bump = 0
        new_off: List[Tuple[int, int, int]] = []    # (row, off, bucket)
        for r in live:
            ln = self._row_len[r]
            bucket = nl_pad_len(max(ln, 1))
            idx = np.full(bucket, -1, np.int32)
            idx[:ln] = np.arange(self._row_off[r], self._row_off[r] + ln,
                                 dtype=np.int32)
            idx_parts.append(idx)
            new_off.append((r, bump, bucket))
            bump += bucket
        new_cap = _round_capacity(max(bump + reserve, 1))
        perm = np.full(new_cap, -1, np.int32)
        if bump:
            perm[:bump] = np.concatenate(idx_parts)
        self.codes = ops.compact_codes(self.codes, perm, backend=backend)
        self.upload_bytes += perm.nbytes
        for r, off, bucket in new_off:
            self._row_off[r] = off
            self._row_cap[r] = bucket
        self._bump = bump
        self._free = {}
        self.live_codes = bump
        self.compactions += 1
        self.last_compaction_occupancy = bump / max(new_cap, 1)

    def compact_if_sparse(self, occupancy_threshold: float, *,
                          reserve: int = 0, backend: str = "jnp") -> bool:
        """Compact when occupancy fell below ``occupancy_threshold`` AND
        the slab would shrink to at most half its size (same hysteresis
        as ``DeviceRowStore.compact_if_sparse``)."""
        if occupancy_threshold <= 0.0:
            return False
        if self.occupancy >= occupancy_threshold:
            return False
        new_cap = _round_capacity(max(self._tight_mass() + reserve, 1))
        if new_cap > self.capacity // 2:
            return False
        self.compact(reserve=reserve, backend=backend)
        return True
