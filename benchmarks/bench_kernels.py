"""Kernel micro-benchmarks (jnp backends on CPU; the Pallas kernels are
TPU-target and validated in interpret mode, which is not a timing mode).

Reports us_per_call and derived throughput so regressions in the
hot-path ops are visible run over run.
"""

from __future__ import annotations

import time
from typing import List

import numpy as np
import jax
import jax.numpy as jnp

from repro.core.bitmap import suffix_popcounts_np, popcount32_np
from repro.kernels import ops


def _timeit(fn, *args, warmup: int = 2, iters: int = 10) -> float:
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def bench_bitmap(n_pairs=4096, n_blocks=8, bw=128) -> List[str]:
    rng = np.random.default_rng(0)
    U = rng.integers(0, 2 ** 32, (n_pairs, n_blocks, bw),
                     dtype=np.uint64).astype(np.uint32)
    V = (U & rng.integers(0, 2 ** 32, U.shape, dtype=np.uint64)
         .astype(np.uint32))
    su = jnp.asarray(suffix_popcounts_np(U))
    sv = jnp.asarray(suffix_popcounts_np(V))
    rho = jnp.asarray(popcount32_np(U).reshape(n_pairs, -1)
                      .sum(1).astype(np.int32))
    Uj, Vj = jnp.asarray(U), jnp.asarray(V)
    words = n_pairs * n_blocks * bw

    out = []
    dt = _timeit(lambda: ops.bitmap_intersect_full(Uj, Vj)[1])
    out.append(f"kernels/bitmap_full/{n_pairs}x{n_blocks}x{bw},"
               f"{dt*1e6:.0f},Gword_s={words/dt/1e9:.2f}")
    dt = _timeit(lambda: ops.bitmap_intersect_es(
        Uj, Vj, su, sv, rho, jnp.int32(64), mode="and")[1])
    out.append(f"kernels/bitmap_es_metrics/{n_pairs}x{n_blocks}x{bw},"
               f"{dt*1e6:.0f},Gword_s={words/dt/1e9:.2f}")
    dt = _timeit(lambda: ops.screen_pairs(
        Uj[:, 0], Vj[:, 0], su[:, 1], sv[:, 1], rho, jnp.int32(64))[0])
    out.append(f"kernels/bitmap_screen/{n_pairs}x{bw},"
               f"{dt*1e6:.0f},Gword_s={n_pairs*bw/dt/1e9:.2f}")
    out.extend(bench_fused_store(n_pairs=n_pairs, n_blocks=n_blocks, bw=bw))
    return out


def bench_fused_store(n_pairs=4096, n_blocks=8, bw=128) -> List[str]:
    """The mining hot path: one fused gather+screen+intersect+scatter
    dispatch against a device-resident row store.  Donation means fresh
    operand slabs per call, so this times the full chunk round-trip the
    miner actually pays (minus the tiny count/alive readback)."""
    from repro.core.rowstore import DeviceRowStore
    rng = np.random.default_rng(4)
    cap = 2 * n_pairs
    rows = rng.integers(0, 2 ** 32, (n_pairs, n_blocks, bw),
                        dtype=np.uint64).astype(np.uint32)
    ua = rng.integers(0, n_pairs, n_pairs).astype(np.int32)
    vb = rng.integers(0, n_pairs, n_pairs).astype(np.int32)
    slots = np.arange(n_pairs, 2 * n_pairs, dtype=np.int32)
    words = n_pairs * n_blocks * bw

    def run():
        store = DeviceRowStore(rows, capacity=cap)
        rho = np.asarray(store.suffix[ua, 0], np.int32)
        t0 = time.perf_counter()
        r = ops.screen_and_intersect(store.rows, store.suffix, ua, vb,
                                     slots, rho, jnp.int32(64), mode="and")
        jax.block_until_ready(r)
        return time.perf_counter() - t0

    run()                      # compile
    dt = min(run() for _ in range(5))
    return [f"kernels/fused_screen_intersect/{n_pairs}x{n_blocks}x{bw},"
            f"{dt*1e6:.0f},Gword_s={words/dt/1e9:.2f}"]


def bench_nlist(n_pairs=2048, lu=64, lv=64) -> List[str]:
    rng = np.random.default_rng(3)
    def mk(n, L):
        pre = np.sort(rng.integers(0, 10_000, (n, L)).astype(np.int32), 1)
        post = rng.integers(0, 10_000, (n, L)).astype(np.int32)
        freq = rng.integers(1, 50, (n, L)).astype(np.int32)
        return pre, post, freq
    up, upo, uf = mk(n_pairs, lu)
    vp, vpo, vf = mk(n_pairs, lv)
    ul = np.full(n_pairs, lu, np.int32)
    vl = np.full(n_pairs, lv, np.int32)
    rho = vf.sum(1).astype(np.int32)
    f = jax.jit(lambda *a: ops.nlist_intersect(*a, early_stop=True)[1])
    dt = _timeit(f, up, upo, uf, vp, vpo, vf, ul, vl, rho, jnp.int32(100))
    return [f"kernels/nlist_intersect/{n_pairs}x{lu},"
            f"{dt*1e6:.0f},Mpair_s={n_pairs/dt/1e6:.2f}"]
