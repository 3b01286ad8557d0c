#!/usr/bin/env python3
"""Drive the mining main path once on a TPU chip, at paper scale, and
check every result.

    python chip_smoke.py               # one chip: kernels + three engines
    python chip_smoke.py --four-chips  # only the 2x2 (block, cls) mesh path

One-chip phases:

* ``kernels``   — the four Pallas kernels of the main path (ES intersect,
                  ES difference, compaction gather, N-list merge) at
                  kosarak-paper width: each compiled program must hold a
                  Mosaic kernel (``tpu_custom_call``) and its outputs must
                  equal its ``kernels/ref.py`` twin bit for bit.
* ``kosarak``   — kosarak-paper at scale 1.0 (990,000 transactions,
                  242 blocks of 128 words) mined by ``BitmapMiner``
                  (eclat, early stopping) through ``mine_packed`` at the
                  two top rungs (relative minsup 0.02 and 0.01).
* ``accidents`` — accidents-paper at scale 1.0 (340,183 transactions),
                  ``scheme="adaptive"`` at the top rung: reaches the
                  difference kernel; compared with eclat.
* ``pumsb``     — the seeded pumsb-paper stream (49,046 x 18) as
                  transactions, ``DevicePrePost`` at the top rung with
                  compaction forced; compared with ``core/oracle.py``.

Every mined result passes a host check in plain numpy over the packed
rows, independent of the engines: *soundness* (each reported support
recomputed by AND + popcount) and *completeness* (each apriori-gen
candidate of the result that is missing from it has support below
minsup).  The bitmap phases also run on ``backend="jnp"`` on the chip
and must agree exactly.

``--four-chips`` mines kosarak-paper (scale 1.0, top rung) with
``DistributedMiner`` on a 2x2 ``(block, cls)`` mesh and compares it, in
the same process, with a 1x1 mesh on device 0 (itemsets and supports),
with a 2x1 block-sharded mesh (the cls-invariant counters) and with the
host check.

Earlier lines are diagnostics; times are informational.  The last line
of stdout is ``{"ok": true, "device": {...}}``, printed only when every
phase passed.  The script exits non-zero, printing no such line, on any
failure and when JAX finds no TPU.  It is one process: it starts no
child, so the chip is never contended.
"""

from __future__ import annotations

import argparse
import itertools
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

SEED = 0


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


class SmokeFailure(Exception):
    """A phase produced a wrong or missing result."""


def require(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


class Compiles:
    """Counts fresh compiles and persistent-cache hits through JAX's
    monitoring events."""

    def __init__(self):
        import jax

        self.misses = self.hits = 0
        jax.monitoring.register_event_listener(self._event)

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_misses":
            self.misses += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.hits += 1


def peak_bytes() -> str:
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return str(stats.get("peak_bytes_in_use", "n/a"))


def synced_wall(t0: float) -> float:
    """Seconds since ``t0``, taken after the device queue drains."""
    import jax
    import jax.numpy as jnp

    jax.block_until_ready(jnp.zeros((), jnp.int32) + 1)
    return time.perf_counter() - t0


# ---------------------------------------------------------------------------
# Host check: plain numpy over the packed rows, independent of the engines
# ---------------------------------------------------------------------------

def _support(rows: dict, itemset) -> int:
    import numpy as np

    acc = None
    for it in itemset:
        acc = rows[it] if acc is None else acc & rows[it]
    return int(np.bitwise_count(acc).sum())


def host_check(bdb, result: dict, minsup: int) -> int:
    """Soundness and completeness of ``result`` against the packed rows
    of ``bdb`` (whose items are every item frequent at some minsup <=
    this one).  Returns the number of candidates checked."""
    rows = {it: bdb.bitmaps[r].reshape(-1) for r, it in enumerate(bdb.items)}
    for itemset, sup in result.items():
        got = _support(rows, itemset)
        require(got == sup, f"unsound: {sorted(itemset)} {sup} != {got}")
        require(sup >= minsup, f"reported infrequent {sorted(itemset)}")
    singles = {it for it in rows if _support(rows, (it,)) >= minsup}
    level = {frozenset((it,)) for it in singles}
    require(level == {s for s in result if len(s) == 1}, "level 1 differs")
    checked = len(rows)
    while level:
        by_prefix: dict = {}
        for s in level:
            t = tuple(sorted(s))
            by_prefix.setdefault(t[:-1], []).append(t[-1])
        nxt = set()
        for prefix, lasts in by_prefix.items():
            for a, b in itertools.combinations(sorted(lasts), 2):
                cand = frozenset(prefix + (a, b))
                if any(cand - {x} not in level for x in cand):
                    continue                      # apriori prune
                checked += 1
                if cand in result:
                    nxt.add(cand)
                else:
                    sup = _support(rows, cand)
                    require(sup < minsup,
                            f"incomplete: {sorted(cand)} has {sup} >= {minsup}")
        level = nxt
    return checked


def rung(bdb, minsup: int):
    """The rows of ``bdb`` still frequent at ``minsup`` (a suffix slice:
    rows are support-ascending)."""
    import numpy as np
    from repro.core.bitmap import BitmapDB

    keep = np.flatnonzero(bdb.supports >= minsup)
    return BitmapDB(items=[bdb.items[i] for i in keep],
                    bitmaps=bdb.bitmaps[keep], supports=bdb.supports[keep],
                    n_trans=bdb.n_trans, minsup=minsup,
                    block_words=bdb.block_words)


def report(label: str, backend: str, stats, n_found: int, wall: float):
    log(f"{label}: backend={backend} device_calls={stats.device_calls} "
        f"word_ops={getattr(stats, 'word_ops', 'n/a')} "
        f"compactions={stats.compactions} frequent_itemsets={n_found} "
        f"wall_s={wall:.3f} (informational) "
        f"peak_bytes_in_use={peak_bytes()}")


# ---------------------------------------------------------------------------
# Phases
# ---------------------------------------------------------------------------

def phase_kernels(n_blocks: int = 242, block_words: int = 128,
                  n_pairs: int = 256, nl_len: int = 2048) -> None:
    """Each main-path kernel: compiled with a Mosaic kernel in it, and
    bit-exact against its ref twin on the same device inputs."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core.bitmap import suffix_popcounts
    from repro.kernels import ops, ref
    from repro.kernels.bitmap_diff import bitmap_diff_es
    from repro.kernels.bitmap_intersect import bitmap_intersect_es
    from repro.kernels.compact import compact_gather
    from repro.kernels.nlist_merge import nlist_merge

    interpret = ops._pallas_interpret()
    rng = np.random.default_rng(SEED)

    def bitmaps(n, density):
        words = rng.integers(0, 2 ** 32, (n, n_blocks, block_words),
                             dtype=np.uint64).astype(np.uint32)
        for _ in range(density):           # each AND halves the bit density
            words &= rng.integers(0, 2 ** 32, words.shape,
                                  dtype=np.uint64).astype(np.uint32)
        return jnp.asarray(words)

    def check(name, fn, want, *args):
        text = jax.jit(fn).lower(*args).compile().as_text()
        if not interpret:
            require("tpu_custom_call" in text, f"{name}: no Mosaic kernel")
        got = jax.block_until_ready(fn(*args))
        for i, (g, w) in enumerate(zip(jax.tree.leaves(got),
                                       jax.tree.leaves(want), strict=True)):
            require(np.array_equal(np.asarray(g), np.asarray(w)),
                    f"{name}: output {i} differs from the ref")

    t0 = time.perf_counter()
    U = bitmaps(n_pairs, 2)
    V = bitmaps(n_pairs, 1)
    su, sv = suffix_popcounts(U), suffix_popcounts(V)
    rho = su[:, 0]
    n_trans = n_blocks * block_words * 32
    for minsup in (0, n_trans // 16, n_trans // 8, n_trans // 4):
        ms = jnp.int32(minsup)
        for mode in ("and", "andnot"):
            check(f"bitmap_intersect_es[{mode}, {minsup}]",
                  lambda *a, mode=mode: bitmap_intersect_es(
                      *a, mode=mode, interpret=interpret),
                  ref.bitmap_intersect_es_ref(U, V, su, sv, rho, ms,
                                              mode=mode),
                  U, V, su, sv, rho, ms)
        check(f"bitmap_diff_es[{minsup}]",
              lambda *a: bitmap_diff_es(*a, interpret=interpret),
              ref.bitmap_diff_es_ref(U, V, su, rho, ms), U, V, su, rho, ms)

    cap = 2 * n_pairs
    slab = bitmaps(cap, 1)
    perm = rng.permutation(cap)[:n_pairs].astype(np.int32)
    perm[::5] = -1
    codes = jnp.asarray(rng.integers(0, 1 << 20, (1 << 17, 3),
                                     dtype=np.int32))
    code_perm = rng.integers(-1, 1 << 17, 100_000).astype(np.int32)
    for name, arr, p in (("rows", slab, perm),
                         ("suffix", suffix_popcounts(slab), perm),
                         ("codes", codes, code_perm)):
        p = jnp.asarray(p)
        check(f"compact_gather[{name}]",
              lambda s, q: compact_gather(s, q, interpret=interpret),
              ref.compact_gather_ref(arr, p), arr, p)

    def nlists(width):
        pre = np.sort(rng.integers(0, 8 * width, (n_pairs, width)), 1)
        post = rng.integers(0, 8 * width, (n_pairs, width))
        freq = rng.integers(1, 10, (n_pairs, width))
        return [jnp.asarray(a, jnp.int32) for a in (pre, post, freq)]

    u, v = nlists(nl_len), nlists(nl_len)
    lens = [jnp.asarray(rng.integers(1, nl_len + 1, n_pairs), jnp.int32)
            for _ in range(2)]
    rho_v = jnp.asarray(rng.integers(0, 5 * nl_len, n_pairs), jnp.int32)
    for es in (False, True):
        for minsup in (0, nl_len, 4 * nl_len):
            ms = jnp.int32(minsup)
            args = (*u, *v, *lens, rho_v, ms)
            check(f"nlist_merge[es={es}, {minsup}]",
                  lambda *a, es=es: nlist_merge(*a, early_stop=es,
                                                interpret=interpret),
                  ref.nlist_intersect_ref(*args, early_stop=es), *args)
    log(f"kernels: bitmap_intersect_es, bitmap_diff_es, compact_gather "
        f"(rows, suffix, codes), nlist_merge compiled with tpu_custom_call "
        f"and bit-exact vs kernels/ref.py at {n_blocks} blocks x "
        f"{block_words} words, {n_pairs} pairs, N-lists of {nl_len}; "
        f"wall_s={synced_wall(t0):.3f} (informational) "
        f"peak_bytes_in_use={peak_bytes()}")


def mine_bitmap_both(label: str, sub, minsup: int, scheme: str,
                     **kw) -> dict:
    """Mine ``sub`` on ``backend="auto"`` and ``"jnp"``; require equality."""
    from repro.core.eclat import BitmapMiner
    from repro.kernels import ops

    outs = []
    for backend in ("auto", "jnp"):
        miner = BitmapMiner(scheme=scheme, early_stop=True, backend=backend,
                            **kw)
        t0 = time.perf_counter()
        out, st = miner.mine_packed(sub, minsup)
        report(f"{label} {scheme} minsup={minsup}",
               ops._resolve(backend), st, len(out), synced_wall(t0))
        outs.append(out)
    require(outs[0] == outs[1], f"{label}: pallas and jnp results differ")
    return outs[0]


def phase_kosarak(scale: float = 1.0) -> None:
    from repro.data.transactions import stream_paper_dataset

    t0 = time.perf_counter()
    bdb, minsups = stream_paper_dataset("kosarak-paper", scale=scale,
                                        seed=SEED)
    log(f"kosarak-paper: {bdb.n_trans} transactions, {bdb.n_items} items "
        f"frequent at {minsups[0]}, {bdb.n_blocks} blocks x "
        f"{bdb.block_words} words, packed in "
        f"{time.perf_counter() - t0:.3f}s")
    for ms in sorted(minsups, reverse=True)[:2]:
        sub = rung(bdb, ms)
        out = mine_bitmap_both("kosarak-paper", sub, ms, "eclat")
        n = host_check(sub, out, ms)
        log(f"kosarak-paper minsup={ms}: host check passed "
            f"({len(out)} itemsets, {n} candidates)")


def phase_accidents(scale: float = 1.0) -> None:
    from unittest import mock

    from repro.data.transactions import stream_paper_dataset
    from repro.kernels import ops

    bdb, minsups = stream_paper_dataset("accidents-paper", scale=scale,
                                        seed=SEED)
    ms = max(minsups)
    sub = rung(bdb, ms)
    log(f"accidents-paper: {bdb.n_trans} transactions, {sub.n_items} "
        f"items frequent at {ms}, {bdb.n_blocks} blocks")
    with mock.patch.object(ops, "screen_and_diff",
                           wraps=ops.screen_and_diff) as diff:
        adaptive = mine_bitmap_both("accidents-paper", sub, ms, "adaptive")
    require(diff.call_count > 0, "adaptive run never reached the diff kernel")
    eclat = mine_bitmap_both("accidents-paper", sub, ms, "eclat")
    require(adaptive == eclat, "adaptive and eclat results differ")
    n = host_check(sub, adaptive, ms)
    log(f"accidents-paper minsup={ms}: {diff.call_count} diff dispatches; "
        f"adaptive == eclat; host check passed ({len(adaptive)} itemsets, "
        f"{n} candidates)")


def phase_pumsb(scale: float = 1.0) -> None:
    from repro.core.oracle import mine_prepost
    from repro.core.prepost import DevicePrePost
    from repro.data.transactions import PAPER_REPLICAS, _STREAMS
    from repro.kernels import ops

    gen, kw, rels = PAPER_REPLICAS["pumsb-paper"]
    kw = dict(kw, n_trans=max(1, int(round(kw["n_trans"] * scale))))
    db = [row[m].tolist() for items, mask in
          _STREAMS[gen](seed=SEED, batch=8192, **kw)
          for row, m in zip(items, mask, strict=True)]
    ms = int(round(max(rels) * len(db)))
    want, ost = mine_prepost(db, ms, early_stop=True)
    # compact_occupancy=0.9: the pool compacts as soon as its live mass
    # would fit half of it, so the compaction kernel runs in this phase.
    miner = DevicePrePost(early_stop=True, backend="auto",
                          compact_occupancy=0.9)
    t0 = time.perf_counter()
    out, st = miner.mine(db, ms)
    report(f"pumsb-paper prepost minsup={ms}",
           ops._resolve(miner.backend), st, len(out), synced_wall(t0))
    require(out == want, "DevicePrePost differs from the oracle")
    require(st.comparisons == ost.comparisons,
            f"comparisons {st.comparisons} != oracle {ost.comparisons}")
    require(st.compactions > 0, "no compaction fired")
    log(f"pumsb-paper: {len(db)} transactions x {kw['n_cols']} "
        f"columns; DevicePrePost == oracle ({len(out)} itemsets, "
        f"{st.comparisons} comparisons, {st.compactions} compactions)")


def phase_four_chips(scale: float = 1.0) -> None:
    import jax
    from repro.compat import make_mesh
    from repro.core.distributed import DistributedMiner
    from repro.data.transactions import stream_paper_dataset
    from repro.launch.mesh import make_mining_mesh

    devices = jax.devices()
    require(len(devices) >= 4, f"--four-chips needs 4 devices, got {devices}")
    bdb, minsups = stream_paper_dataset("kosarak-paper", scale=scale,
                                        seed=SEED)
    ms = max(minsups)
    sub = rung(bdb, ms)
    meshes = {
        "2x2": make_mining_mesh(block=2, cls=2),
        "2x1": make_mesh((2, 1), ("block", "cls"), devices=devices[:2]),
        "1x1": make_mesh((1, 1), ("block", "cls"), devices=devices[:1]),
    }
    outs, counters = {}, {}
    for name, mesh in meshes.items():
        miner = DistributedMiner(mesh, scheme="eclat", early_stop=True)
        t0 = time.perf_counter()
        out, st = miner.mine_packed(sub, ms)
        report(f"four-chips kosarak-paper {name} minsup={ms}",
               "jnp (shard_map dispatch; it has no Pallas variant)", st,
               len(out), synced_wall(t0))
        store = miner._store
        log(f"four-chips {name}: rows on "
            f"{len(store.rows.sharding.device_set)} devices, suffix on "
            f"{len(store.suffix.sharding.device_set)} devices")
        outs[name] = out
        counters[name] = {k: v for k, v in st.as_dict().items()
                          if not k.endswith("_s")}
    require(outs["2x2"] == outs["1x1"], "2x2 and 1x1 results differ")
    require(outs["2x2"] == outs["2x1"], "2x2 and 2x1 results differ")
    require(counters["2x2"] == counters["2x1"],
            f"cls changed counters: {counters['2x2']} != {counters['2x1']}")
    n = host_check(sub, outs["2x2"], ms)
    log(f"four-chips: 2x2 == 1x1 == 2x1 ({len(outs['2x2'])} itemsets), "
        f"counters 2x2 == 2x1, host check passed ({n} candidates)")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 2x2 (block, cls) mesh path and what "
                         "it is compared with (needs four chips)")
    args = ap.parse_args()

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX finds no TPU (platform {dev.platform!r})",
              file=sys.stderr)
        return 1
    from repro.cache import configure_compile_cache

    cache_dir = configure_compile_cache()
    compiles = Compiles()
    log(f"jax {jax.__version__}; device {dev.device_kind} x "
        f"{jax.device_count()}; compile cache {cache_dir}")

    phases = ([phase_four_chips] if args.four_chips else
              [phase_kernels, phase_kosarak, phase_accidents, phase_pumsb])
    t_all = time.perf_counter()
    for phase in phases:
        t0 = time.perf_counter()
        phase()
        log(f"{phase.__name__} passed in {time.perf_counter() - t0:.1f}s")
    log(f"all phases passed in {time.perf_counter() - t_all:.1f}s; "
        f"fresh compiles {compiles.misses}, persistent-cache hits "
        f"{compiles.hits}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
