"""Bitmap vertical format for TID-lists — the TPU-native data layout.

The paper's sorted-int TID-lists are pointer-chasing merges; on TPU we
re-represent every TID-list as a packed bitmap row so that intersection is
``AND`` + popcount (pure 8x128-lane VPU work) and dEclat's difference is
``ANDNOT``.  The early-stopping criterion survives the translation at block
granularity via per-row *suffix popcount* tables (see DESIGN.md §2).

Layout
------
``bitmaps: uint32[n_items, n_blocks, block_words]``
    bit ``b`` of word ``w`` of block ``k`` of row ``i``  ⇔  transaction
    ``(k*block_words + w) * 32 + b`` contains item ``i``.  TIDs here are
    0-based (the oracle is 1-based to match the paper's prose).
``suffix: int32[n_items, n_blocks + 1]``
    ``suffix[i, k] = popcount(bitmaps[i, k:, :])`` — the mass still
    achievable from block ``k`` onward.  ``suffix[i, 0]`` is the support.

``block_words`` defaults to 128 words = 4096 transactions per block so a
block is exactly one 8x128 VPU-aligned uint32 tile row-group.

Residency: on the mining hot path both slabs are *device-resident* — rows
and suffix tables live in ``core.rowstore.DeviceRowStore`` and are
gathered/scattered by row index inside the fused dispatch
(``kernels.ops.screen_and_intersect``).  :func:`suffix_popcounts` is the
device producer of the suffix slab; :func:`suffix_popcounts_np` is its
host mirror, kept for packing-time code and tests.
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Dict, Hashable, List, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

WORD_BITS = 32
DEFAULT_BLOCK_WORDS = 128  # 4096 TIDs per block; one lane-aligned tile.

# Padding sentinel for N-list arrays (shared by core.prepost and
# kernels.ref; lives here to keep the import graph acyclic).
NL_SENTINEL = np.iinfo(np.int32).max

# Bucketed N-list lengths: gather widths and pool extents are padded to
# these so the jit cache sees few distinct shapes.  Lengths past the
# largest tuned bucket fall back to next-power-of-two sizing (huge
# N-lists are rare but must not be a hard error).
NL_LEN_BUCKETS = (8, 32, 128, 512, 2048, 8192, 32768)

# Pair-chunk batch buckets, one table per dispatch family.  They live
# HERE, next to :func:`bucket_pad`, so the engines' pair-chunk clamp
# (``min(pair_chunk, BUCKETS[-1])``) and their pad calls can never
# drift apart again (pre-ISSUE-5 each engine kept a private, diverging
# ``_PAIR_BUCKETS`` copy).  The bitmap table tops out higher because a
# bitmap pair costs O(row) operand traffic regardless of batch width,
# while an N-list chunk's gather width is the bucket of its LONGEST
# operand — huge merge batches amplify padding instead of throughput.
PAIR_CHUNK_BUCKETS = (64, 256, 1024, 4096, 16384, 65536, 262144)
NL_PAIR_CHUNK_BUCKETS = (64, 256, 1024, 4096, 8192, 32768)

# Chunk-width autotuning (ISSUE 7): ``pair_chunk`` is calibrated at a
# *reference* per-pair operand size; smaller operands can dispatch in
# proportionally wider chunks at the same VMEM footprint.  These are
# the reference sizes the knob is understood to be tuned at — a bitmap
# pair moving ~1024 words (8 blocks x 128 words, the smoke shape), an
# N-list pair whose longest operand sits in the 128-length bucket
# (3 code words per PPC node).
BITMAP_REF_ROW_WORDS = 1024
NL_REF_LEN = 128


def chunk_width_for(words_per_pair: int, base_chunk: int,
                    bucket_table: Sequence[int], ref_words: int) -> int:
    """Per-bucket pair-chunk width at equal VMEM footprint.

    Returns the largest bucket ``w`` in ``bucket_table`` with
    ``w * words_per_pair <= base_chunk * ref_words`` — i.e. the widest
    bucketed chunk whose operand traffic stays within the budget the
    caller's ``base_chunk`` knob implies at the reference operand size.
    The result is floored at ``base_chunk`` (snapped into the table):
    autotuning only *widens* small-operand chunks, so ``device_calls``
    can never increase relative to the un-autotuned engine, and only
    bucketed widths reach the jit cache (one (width, op) variant per
    table entry, bounded)."""
    budget = max(1, int(base_chunk)) * max(1, int(ref_words))
    width = 0
    for b in bucket_table:
        if b * max(1, int(words_per_pair)) <= budget:
            width = b
    floor = min(int(base_chunk), bucket_table[-1])
    return max(width, floor)


# Row-sized device buffers a fused bitmap dispatch keeps live per pair:
# the gathered U and V rows, the child row Z and the scatter's update.
# The TPU compiler measures 4.07 rows per pair at kosarak width (4096
# pairs x 31219 words/row: 1.94 GiB of temporaries).
DISPATCH_ROWS_PER_PAIR = 4


def device_memory_bytes() -> int:
    """Memory of the default device: the accelerator's ``bytes_limit``,
    or host RAM for the CPU backend (which reports no limit)."""
    stats = jax.devices()[0].memory_stats() or {}
    limit = stats.get("bytes_limit")
    if limit:
        return int(limit)
    return os.sysconf("SC_PAGE_SIZE") * os.sysconf("SC_PHYS_PAGES")


def hbm_pair_cap(words_per_pair: int, memory_bytes: int,
                 bucket_table: Sequence[int]) -> int:
    """Widest bucketed pair chunk whose fused-dispatch temporaries
    (``DISPATCH_ROWS_PER_PAIR`` rows of ``words_per_pair`` uint32 words
    per pair) fit in half of ``memory_bytes`` — the other half holds the
    row store and the pipeline's in-flight outputs.  Never below the
    smallest bucket.  At kosarak width on a 15.75 GiB v5e this is 16384
    pairs, where an uncapped 65536-pair chunk needs 30.9 GB."""
    per_pair = DISPATCH_ROWS_PER_PAIR * 4 * max(1, int(words_per_pair))
    fits = [b for b in bucket_table if b * per_pair <= memory_bytes // 2]
    return fits[-1] if fits else bucket_table[0]


def nl_pad_len(n: int) -> int:
    """Smallest N-list bucket >= ``n`` (power-of-two fallback past the
    largest tuned bucket)."""
    for b in NL_LEN_BUCKETS:
        if n <= b:
            return b
    b = NL_LEN_BUCKETS[-1]
    while b < n:
        b *= 2
    return b


def nl_pad_len_np(lengths: np.ndarray) -> np.ndarray:
    """Vectorized :func:`nl_pad_len` (host): the per-pair length-bucket
    key the frontier scheduler sorts drained pairs by so one huge N-list
    cannot widen the gather for a whole chunk of small ones."""
    # host-sync: host length vectors (scheduler sort key); no device value
    lengths = np.asarray(lengths, np.int64)
    # host-sync: host bucket-table constant; no device value touched
    buckets = np.asarray(NL_LEN_BUCKETS, np.int64)
    idx = np.searchsorted(buckets, np.maximum(lengths, 0))
    out = buckets[np.minimum(idx, len(buckets) - 1)]
    big = lengths > buckets[-1]
    if big.any():
        out = out.copy()
        out[big] = [nl_pad_len(int(v)) for v in lengths[big]]
    return out


def bucket_pad(arr: np.ndarray, n: int, bucket_sizes: Sequence[int],
               fill=0) -> np.ndarray:
    """Pad ``arr`` (first ``n`` entries valid) to the smallest bucket >= n.

    Shared by every engine's pair-chunk dispatch so jit caches stay
    small; callers drop results past ``n``."""
    for b in bucket_sizes:
        if n <= b:
            if n == b:
                return arr
            pad_shape = (b - n,) + arr.shape[1:]
            return np.concatenate([arr, np.full(pad_shape, fill, arr.dtype)])
    raise ValueError(f"batch of {n} exceeds largest bucket "
                     f"{max(bucket_sizes)}")


def popcount32(x: jnp.ndarray) -> jnp.ndarray:
    """SWAR population count for uint32 arrays (returns int32)."""
    x = x.astype(jnp.uint32)
    x = x - ((x >> 1) & jnp.uint32(0x55555555))
    x = (x & jnp.uint32(0x33333333)) + ((x >> 2) & jnp.uint32(0x33333333))
    x = (x + (x >> 4)) & jnp.uint32(0x0F0F0F0F)
    return ((x * jnp.uint32(0x01010101)) >> 24).astype(jnp.int32)


def popcount32_np(x: np.ndarray) -> np.ndarray:
    """Host-side popcount (numpy mirror of :func:`popcount32`)."""
    x = x.astype(np.uint32)
    x = x - ((x >> 1) & np.uint32(0x55555555))
    x = (x & np.uint32(0x33333333)) + ((x >> 2) & np.uint32(0x33333333))
    x = (x + (x >> 4)) & np.uint32(0x0F0F0F0F)
    return ((x * np.uint32(0x01010101)) >> 24).astype(np.int32)


def pack_tidlists(tidlists: Sequence[Sequence[int]], n_trans: int,
                  block_words: int = DEFAULT_BLOCK_WORDS,
                  ) -> np.ndarray:
    """Pack 0-based TID lists into ``uint32[n_rows, n_blocks, block_words]``."""
    n_rows = len(tidlists)
    n_words = -(-n_trans // WORD_BITS)
    n_blocks = max(1, -(-n_words // block_words))
    flat = np.zeros((n_rows, n_blocks * block_words), dtype=np.uint32)
    for r, tids in enumerate(tidlists):
        if len(tids) == 0:
            continue
        # host-sync: pack-time host TID lists; no device value touched
        t = np.asarray(tids, dtype=np.int64)
        if t.min() < 0 or t.max() >= n_trans:
            raise ValueError("TID out of range")
        np.bitwise_or.at(flat[r], t // WORD_BITS,
                         np.uint32(1) << (t % WORD_BITS).astype(np.uint32))
    return flat.reshape(n_rows, n_blocks, block_words)


def unpack_row(row: np.ndarray) -> np.ndarray:
    """Inverse of :func:`pack_tidlists` for one row -> sorted 0-based TIDs."""
    # host-sync: tests/debug unpack helper (readback is the caller's choice)
    flat = np.asarray(row, dtype=np.uint32).reshape(-1)
    bits = np.unpackbits(flat.view(np.uint8), bitorder="little")
    return np.nonzero(bits)[0].astype(np.int64)


def suffix_popcounts_np(bitmaps: np.ndarray) -> np.ndarray:
    """``int32[n_rows, n_blocks+1]`` suffix popcount table (host)."""
    per_block = popcount32_np(bitmaps).sum(axis=-1)          # (rows, blocks)
    n_rows, n_blocks = per_block.shape
    out = np.zeros((n_rows, n_blocks + 1), dtype=np.int32)
    out[:, :-1] = per_block[:, ::-1].cumsum(axis=1)[:, ::-1]
    return out


@jax.jit
def suffix_popcounts(bitmaps: jnp.ndarray) -> jnp.ndarray:
    """Device version of :func:`suffix_popcounts_np`.  Jitted so the
    SWAR steps fuse: run op by op over a full slab (1 GB at
    kosarak-paper), each step would hold a slab-sized temporary."""
    per_block = popcount32(bitmaps).sum(axis=-1).astype(jnp.int32)
    rev = jnp.cumsum(per_block[:, ::-1], axis=1)[:, ::-1]
    zeros = jnp.zeros((bitmaps.shape[0], 1), dtype=jnp.int32)
    return jnp.concatenate([rev, zeros], axis=1)


@dataclass
class BitmapDB:
    """A transaction database packed for device mining.

    Rows are the frequent 1-itemsets in *increasing* frequency (the
    Eclat/dEclat search order from the paper §II-A).
    """

    items: List[Hashable]                 # row -> original item
    bitmaps: np.ndarray                   # uint32 (n_items, n_blocks, bw)
    supports: np.ndarray                  # int32 (n_items,)
    n_trans: int
    minsup: int
    block_words: int

    @property
    def n_items(self) -> int:
        return len(self.items)

    @property
    def n_blocks(self) -> int:
        return self.bitmaps.shape[1]

    @classmethod
    def from_db(cls, db: Sequence[Sequence[Hashable]], minsup: int,
                block_words: int = DEFAULT_BLOCK_WORDS) -> "BitmapDB":
        from .oracle import frequent_items_ascending

        items = frequent_items_ascending(db, minsup)
        index: Dict[Hashable, int] = {it: r for r, it in enumerate(items)}
        tidlists: List[List[int]] = [[] for _ in items]
        for tid, t in enumerate(db):
            for it in set(t):
                r = index.get(it)
                if r is not None:
                    tidlists[r].append(tid)
        bitmaps = pack_tidlists(tidlists, max(len(db), 1), block_words)
        # host-sync: pack-time host supports; no device value touched
        supports = np.array([len(t) for t in tidlists], dtype=np.int32)
        return cls(items=items, bitmaps=bitmaps, supports=supports,
                   n_trans=len(db), minsup=minsup, block_words=block_words)


def pad_pairs(ia: np.ndarray, ib: np.ndarray, bucket_sizes: Sequence[int],
              ) -> Tuple[np.ndarray, np.ndarray, int]:
    """Pad pair index vectors to the smallest bucket >= n (stable jit shapes).

    Padding replicates pair 0 (harmless: results beyond ``n`` are dropped by
    the caller).  Returns (ia_padded, ib_padded, n_valid)."""
    n = int(ia.shape[0])
    for b in bucket_sizes:
        if n <= b:
            pad = b - n
            if pad:
                ia = np.concatenate([ia, np.zeros(pad, ia.dtype)])
                ib = np.concatenate([ib, np.zeros(pad, ib.dtype)])
            return ia, ib, n
    raise ValueError(f"pair batch of {n} exceeds largest bucket "
                     f"{max(bucket_sizes)}; raise pair_chunk")
