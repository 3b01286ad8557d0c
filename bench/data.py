"""Seeded transaction streams and the bitmap packer of the benchmark.

Copied from the program's ``src/repro/data/transactions.py``
(``_powerlaw_stream``, ``_dense_stream``, ``_masked_unique_bincount``,
``stream_paper_dataset``), so that a change to the program cannot move
the data it is measured on.  Two departures:

* the packer generates the stream once and keeps its batches for the
  second pass, where the original regenerates it from the seed;
* ``order_seed`` shuffles the transaction ids.  A configuration's rows
  are one deployment's database, drawn from its own ``data_seed``; a
  run's ``--seed`` only permutes them.  Every seed then mines the same
  multiset of transactions, so the work (items, pairs, itemsets, the
  shapes the program compiles) is the same for every seed, while the
  bits that the kernels read differ.  With ``order_seed=None`` the
  packer is the original, bit for bit.

The packer returns the program's own input type, ``BitmapDB``: rows are
the frequent items in the engine's order (support ascending, ``repr``
tie-break), bits are transactions.
"""

from __future__ import annotations

from typing import Iterator, List, Optional, Tuple

import numpy as np

BatchStream = Iterator[Tuple[np.ndarray, np.ndarray]]
WORD_BITS = 32


def _powerlaw_stream(*, n_trans: int, n_items: int, avg_trans_len: float,
                     alpha: float, seed: int, batch: int) -> BatchStream:
    """Kosarak-family stream: Zipf(``alpha``) item popularity, Poisson
    row lengths.  Items are drawn with replacement; duplicates within a
    row collapse when counting and packing, so a row's distinct length
    lands under ``avg_trans_len``."""
    rng = np.random.default_rng(seed)
    pop = 1.0 / np.arange(1, n_items + 1) ** alpha
    pop /= pop.sum()
    cap = max(4, int(avg_trans_len * 3) + 8)   # Poisson tail clip
    for lo in range(0, n_trans, batch):
        b = min(batch, n_trans - lo)
        lens = np.minimum(np.maximum(1, rng.poisson(avg_trans_len, b)), cap)
        items = rng.choice(n_items, size=(b, cap), p=pop)
        mask = np.arange(cap)[None, :] < lens[:, None]
        yield items, mask


def _dense_structure(rng: np.random.Generator, n_cols: int,
                     vals_per_col: int, skew: float, n_classes: int):
    col_p = []
    for _c in range(n_cols):
        w = rng.pareto(skew, vals_per_col) + 0.2
        col_p.append(w / w.sum())
    class_vals = rng.integers(0, vals_per_col, size=(n_classes, n_cols))
    class_p = rng.dirichlet(np.full(n_classes, 2.0))
    return col_p, class_vals, class_p


def _dense_stream(*, n_trans: int, n_cols: int, vals_per_col: int,
                  skew: float, correlation: float = 0.9, n_classes: int = 3,
                  seed: int, batch: int) -> BatchStream:
    """Accidents/Pumsb-family stream: one item per column; each row
    draws a latent class and takes the class's value in a column with
    probability ``correlation``, else a skewed random value."""
    rng = np.random.default_rng(seed)
    col_p, class_vals, class_p = _dense_structure(rng, n_cols, vals_per_col,
                                                  skew, n_classes)
    for lo in range(0, n_trans, batch):
        b = min(batch, n_trans - lo)
        k = rng.choice(n_classes, size=b, p=class_p)
        use_class = rng.random((b, n_cols)) < correlation
        noise = np.stack([rng.choice(vals_per_col, size=b, p=col_p[c])
                          for c in range(n_cols)], axis=1)
        vals = np.where(use_class, class_vals[k], noise)
        items = np.arange(n_cols)[None, :] * vals_per_col + vals
        yield items, np.ones((b, n_cols), bool)


STREAMS = {"powerlaw": _powerlaw_stream, "dense": _dense_stream}


def _item_universe(generator: str, params: dict) -> int:
    if generator == "powerlaw":
        return int(params["n_items"])
    return int(params["n_cols"]) * int(params["vals_per_col"])


def _masked_unique_bincount(items: np.ndarray, mask: np.ndarray,
                            n_universe: int) -> np.ndarray:
    """Per-row-deduplicated item counts for one batch: sort each row,
    keep first occurrences, bincount the survivors."""
    x = np.where(mask, items, -1)
    x = np.sort(x, axis=1)
    first = np.ones(x.shape, bool)
    first[:, 1:] = x[:, 1:] != x[:, :-1]
    sel = first & (x >= 0)
    return np.bincount(x[sel].ravel(), minlength=n_universe)


def pack(generator: str, params: dict, rungs: List[float], *, seed: int,
         order_seed: Optional[int] = None, scale: float = 1.0,
         block_words: int = 128, batch: int = 8192):
    """Pack a seeded replica into a ``BitmapDB`` at its lowest rung.

    ``seed`` draws the transactions; ``order_seed``, where given,
    permutes their ids.  ``scale`` multiplies the transaction count; the
    rungs are relative, so minsups follow ``n_trans``.  Returns
    ``(BitmapDB, minsups)`` with minsups as absolute counts in the order
    of ``rungs``.
    """
    from repro.core.bitmap import BitmapDB

    kwargs = dict(params)
    kwargs["n_trans"] = n_trans = max(1, int(round(kwargs["n_trans"]
                                                   * scale)))
    minsups = [max(1, int(round(r * n_trans))) for r in rungs]
    minsup = min(minsups)
    n_universe = _item_universe(generator, kwargs)
    batches = list(STREAMS[generator](seed=seed, batch=batch, **kwargs))

    supports = np.zeros(n_universe, np.int64)
    for items, mask in batches:
        supports += _masked_unique_bincount(items, mask, n_universe)

    freq = np.flatnonzero(supports >= minsup)
    order = sorted(freq.tolist(), key=lambda i: (supports[i], repr(int(i))))
    row_of = np.full(n_universe, -1, np.int64)
    row_of[order] = np.arange(len(order))

    tid_of = (np.arange(n_trans) if order_seed is None else
              np.random.default_rng(order_seed).permutation(n_trans))
    block_tids = block_words * WORD_BITS
    n_blocks = max(1, -(-n_trans // block_tids))
    # Flat word axis during packing: a tid's word is tid >> 5.
    bitmaps = np.zeros((len(order), n_blocks * block_words), np.uint32)
    tid0 = 0
    for items, mask in batches:
        b, width = items.shape
        r = row_of[items]
        valid = mask & (r >= 0)
        tids = np.broadcast_to(tid_of[tid0:tid0 + b, None], (b, width))
        rr, tt = r[valid], tids[valid]
        np.bitwise_or.at(bitmaps, (rr, tt >> 5),
                         (1 << (tt & 31)).astype(np.uint32))
        tid0 += b
    bdb = BitmapDB(items=[int(i) for i in order],
                   bitmaps=bitmaps.reshape(len(order), n_blocks,
                                           block_words),
                   supports=supports[order].astype(np.int32),
                   n_trans=n_trans, minsup=minsup, block_words=block_words)
    return bdb, minsups


def rung(bdb, minsup: int):
    """The rows of ``bdb`` still frequent at ``minsup``: a suffix slice,
    since rows are support-ascending (copied from ``chip_smoke.rung``)."""
    from repro.core.bitmap import BitmapDB

    keep = np.flatnonzero(bdb.supports >= minsup)
    return BitmapDB(items=[bdb.items[i] for i in keep],
                    bitmaps=bdb.bitmaps[keep], supports=bdb.supports[keep],
                    n_trans=bdb.n_trans, minsup=minsup,
                    block_words=bdb.block_words)
