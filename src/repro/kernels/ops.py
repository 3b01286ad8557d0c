"""Public jit'd wrappers around the Pallas kernels.

``backend`` selection:
  * ``"jnp"``      — the pure-jnp reference path (kernels/ref.py).  This is
                     the production path on CPU hosts and the oracle for
                     kernel tests.
  * ``"pallas"``   — the Pallas kernel: compiled for the chip on TPU, run
                     in the Pallas interpreter on CPU (tests), refused on
                     any other platform (:func:`_pallas_interpret`).
  * ``"auto"``     — pallas on TPU, jnp elsewhere (default).

:func:`_resolve` turns a requested backend into the one that
runs: ``"jnp"``, ``"pallas"`` (compiled) or ``"interpret"``; the jitted
bodies below take that resolved value as a static argument.

All wrappers keep shapes static-friendly: callers pad pair batches to
bucketed sizes (core/eclat.py::_bucket_pad) so jit caches stay small.
``screen_and_intersect`` is the mining hot path: one dispatch per pair
chunk against the device-resident row store, operand gather and child
row/suffix scatter included.

Compile-cache discipline (ISSUE 7, chunk-width autotuning): the jit
cache under every wrapper is keyed on input *shapes* plus the static
args — for the bitmap family effectively ``(padded pair width, mode,
early_stop, backend)``, for the N-list family ``(padded pair width,
lu, lv, early_stop, backend)``.  The engines keep the variant count
bounded by quantizing BOTH axes through ``core.bitmap``:  pair widths
through ``bucket_pad`` over ``PAIR_CHUNK_BUCKETS`` /
``NL_PAIR_CHUNK_BUCKETS`` and gather widths through ``nl_pad_len``
over ``NL_LEN_BUCKETS``.  Per-bucket autotuned chunk widths
(``chunk_width_for``) stay inside the same tables — autotuning changes
which bucket a chunk lands in, never introduces new shapes — so the
cache holds at most one entry per (width-bucket, op) pair regardless
of the width policy.  (Not asserted here: tests and the roofline
harness call these wrappers directly with arbitrary widths; the
discipline is the engines' contract, enforced by their use of
``bucket_pad``.)

Static-arg audit (ISSUE 10, rule DL003): every ``static_argnames``
entry in this module is a *bounded* static — ``mode`` / ``backend`` /
``early_stop`` are two- or three-valued enums fixed per engine run,
and ``lu`` / ``lv`` are gather widths already quantized through
``nl_pad_len`` onto ``NL_LEN_BUCKETS`` (so the value set is the bucket
table, not the data).  None is fed from a per-call-varying scalar —
that was exactly the PR 5 ``es_minsup`` bug (a traced threshold made
static doubled the cache and cost 1.17 s -> 0.04 s when fixed), and
``tools/devicelint`` now flags the pattern instead of reviewers.

Donation & pipelining (ISSUE 7): ``screen_and_intersect`` /
``screen_and_diff`` donate the rows/suffix slabs and ``nlist_scatter``
donates the codes slab.  The engines may keep several dispatches in
flight (the frontier scheduler's ring) — this is safe because each
dispatch consumes its operands *by value* at enqueue time and PJRT
sequences a donated buffer's aliasing after every outstanding read.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.compat import shard_map
from repro.core.bitmap import popcount32 as _popcount32
from repro.core.bitmap import suffix_popcounts as _suffix_popcounts

from . import ref as _ref
from .bitmap_diff import bitmap_diff_es as _pallas_diff
from .bitmap_intersect import bitmap_intersect_es as _pallas_bitmap


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _pallas_interpret() -> bool:
    """Whether a Pallas kernel runs in the interpreter — the one place
    this is decided.  False on the TPU (the kernel is compiled), True on
    the CPU (tests and CPU hosts validate the kernel bodies there), and
    an error on any other platform: the kernels are written for the TPU
    and nothing else may silently interpret them."""
    if _on_tpu():
        return False
    platform = jax.default_backend()
    if platform == "cpu":
        return True
    raise RuntimeError(
        f"Pallas kernels compile for the TPU and are interpreted only on "
        f"the CPU; this process runs on {platform!r}")


def _resolve(backend: str) -> str:
    """Requested backend -> the one that runs: ``"jnp"``, ``"pallas"``
    (compiled for the TPU) or ``"interpret"`` (the Pallas interpreter,
    CPU only)."""
    if backend == "auto":
        backend = "pallas" if _on_tpu() else "jnp"
    if backend == "jnp":
        return "jnp"
    if backend != "pallas":
        raise ValueError(f"unknown backend {backend!r}")
    return "interpret" if _pallas_interpret() else "pallas"


_PALLAS = ("pallas", "interpret")


def bitmap_intersect_es(U, V, suffix_u, suffix_v, rho_parent, minsup,
                        *, mode: str = "and", backend: str = "auto",
                        ) -> Tuple[jnp.ndarray, jnp.ndarray,
                                   jnp.ndarray, jnp.ndarray]:
    """Blocked early-stopping intersection.  See kernels/ref.py for the
    exact semantics.  Returns (Z, counts, blocks_done, alive)."""
    b = _resolve(backend)
    if b in _PALLAS:
        return _pallas_bitmap(U, V, suffix_u, suffix_v, rho_parent, minsup,
                              mode=mode, interpret=b == "interpret")
    return _ref.bitmap_intersect_es_ref(U, V, suffix_u, suffix_v,
                                        rho_parent, minsup, mode=mode)


# ``es_minsup`` (the scan-abort threshold: the real minsup, or 0 = ES
# disabled) is a TRACED scalar, separate from the scatter-gate
# ``minsup``, so the ES-on and ES-off paths share one compiled kernel
# per shape — a static flag here would double every jit cache entry.
@functools.partial(jax.jit, static_argnames=("mode", "backend"),
                   donate_argnums=(0, 1))
def _screen_and_intersect_impl(rows, suffix, ua, vb, slots, rho_parent,
                               minsup, es_minsup, *, mode: str,
                               backend: str):
    with jax.named_scope("dispatch.gather"):
        U = jnp.take(rows, ua, axis=0)
        V = jnp.take(rows, vb, axis=0)
        su = jnp.take(suffix, ua, axis=0)
        sv = jnp.take(suffix, vb, axis=0)
    with jax.named_scope("dispatch.kernel"):
        if backend in _PALLAS:
            Z, cnt, blocks, alive = _pallas_bitmap(
                U, V, su, sv, rho_parent, es_minsup, mode=mode,
                interpret=backend == "interpret")
        else:
            Z, cnt, blocks, alive = _ref.bitmap_intersect_es_ref(
                U, V, su, sv, rho_parent, es_minsup, mode=mode)
    # Survivor-only scatter (ISSUE 5): the count phase above completes
    # before the scatter phase, and gates it — non-survivors' slots are
    # redirected out of range so ``mode="drop"`` discards their writes
    # together with the pair padding.
    with jax.named_scope("dispatch.scatter"):
        rows, suffix = _survivor_scatter(rows, suffix, Z, cnt, alive, slots,
                                         rho_parent, minsup, mode=mode)
    return rows, suffix, cnt, blocks, alive


def _survivor_scatter(rows, suffix, Z, cnt, alive, slots, rho_parent,
                      minsup, *, mode: str):
    """Write surviving children and their suffix tables into the slab;
    the other slots are redirected out of range and dropped."""
    keep = _ref._survivor_mask(cnt, alive, rho_parent, minsup, mode=mode)
    slots_eff = jnp.where(keep, slots, jnp.int32(rows.shape[0]))
    child_suffix = _suffix_popcounts(Z)
    rows = rows.at[slots_eff].set(Z, mode="drop")
    suffix = suffix.at[slots_eff].set(child_suffix, mode="drop")
    return rows, suffix


def screen_and_intersect(rows, suffix, ua, vb, slots, rho_parent, minsup,
                         *, mode: str = "and", early_stop: bool = True,
                         backend: str = "auto",
                         ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray,
                                    jnp.ndarray, jnp.ndarray]:
    """Fused screen + blocked ES intersection over a device row store.

    One device dispatch per pair chunk: gathers operand rows/suffix tables
    by index from the store, runs the blocked early-stopping intersection
    (block-0 screen included — see ``ref.screen_and_intersect_ref``),
    computes child suffix-popcount tables on device and scatters both into
    the store at ``slots`` — **survivor-only**: a child row is written
    only when its support clears ``minsup`` (and, under ES, the pair
    finished its scan alive), so dead candidates cost zero scatter words.
    ``early_stop=False`` disables the in-scan abort but keeps the
    frequency gate (``minsup`` must always be the real threshold).

    ``rows``/``suffix`` buffers are DONATED: callers must replace their
    handles with the returned arrays.  Returns
    ``(rows, suffix, counts, blocks_done, alive)`` where
    ``rows[slots[i]]`` holds child ``Z_i`` for surviving pairs (bit-exact
    vs the ref) and ``suffix[slots[i]]`` its suffix table.  Slots of
    non-survivors and slots ``>= capacity`` (padding) are untouched.
    """
    b = _resolve(backend)
    minsup = jnp.asarray(minsup, jnp.int32)
    es_minsup = minsup if early_stop else jnp.int32(0)
    return _screen_and_intersect_impl(
        rows, suffix, jnp.asarray(ua, jnp.int32), jnp.asarray(vb, jnp.int32),
        jnp.asarray(slots, jnp.int32), jnp.asarray(rho_parent, jnp.int32),
        minsup, es_minsup, mode=mode, backend=b)


@functools.partial(jax.jit, static_argnames=("backend",),
                   donate_argnums=(0, 1))
def _screen_and_diff_impl(rows, suffix, ua, vb, slots, rho_parent,
                          minsup, es_minsup, *, backend: str):
    with jax.named_scope("dispatch.gather"):
        U = jnp.take(rows, ua, axis=0)
        V = jnp.take(rows, vb, axis=0)
        su = jnp.take(suffix, ua, axis=0)
    with jax.named_scope("dispatch.kernel"):
        if backend in _PALLAS:
            Z, cnt, blocks, alive = _pallas_diff(
                U, V, su, rho_parent, es_minsup,
                interpret=backend == "interpret")
        else:
            Z, cnt, blocks, alive = _ref.bitmap_diff_es_ref(
                U, V, su, rho_parent, es_minsup)
    with jax.named_scope("dispatch.scatter"):
        rows, suffix = _survivor_scatter(rows, suffix, Z, cnt, alive, slots,
                                         rho_parent, minsup, mode="andnot")
    return rows, suffix, cnt, blocks, alive


def screen_and_diff(rows, suffix, ua, vb, slots, rho_parent, minsup,
                    *, early_stop: bool = True, backend: str = "auto",
                    ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray,
                               jnp.ndarray, jnp.ndarray]:
    """Fused screen + blocked dEclat difference over a device row store
    (ISSUE 6) — the diffset sibling of :func:`screen_and_intersect` and
    the fourth ``evaluate_pairs`` dispatch behind the shared client
    protocol.

    One device dispatch per pair chunk: gathers the operand rows (and
    the U suffix table — the zero-block-skip mass source) by index,
    runs the blocked scan on the difference bound ``rho_parent - count``
    (block-0 screen included — see ``ref.screen_and_diff_ref``) and
    scatters surviving children ``Z = U & ~V`` plus their suffix tables
    into the store, survivor-only.  Feed it tidset operands and the
    scattered child is the level-2 diffset ``d(ab) = T(a) & ~T(b)``:
    the adaptive tidset→diffset flip rides the same dispatch.

    ``blocks_done`` charges only nonzero-mass U blocks (diffset sparsity
    is the win on dense data); counts/aliveness/results are bit-exact
    vs ``screen_and_intersect(mode="andnot")``.  Pinned by
    ``ref.screen_and_diff_ref`` on both backends.

    ``rows``/``suffix`` are DONATED: callers must replace their handles.
    Returns ``(rows, suffix, counts, blocks_done, alive)``.
    """
    b = _resolve(backend)
    minsup = jnp.asarray(minsup, jnp.int32)
    es_minsup = minsup if early_stop else jnp.int32(0)
    return _screen_and_diff_impl(
        rows, suffix, jnp.asarray(ua, jnp.int32), jnp.asarray(vb, jnp.int32),
        jnp.asarray(slots, jnp.int32), jnp.asarray(rho_parent, jnp.int32),
        minsup, es_minsup, backend=b)


@functools.lru_cache(maxsize=None)
def make_screen_and_intersect_sharded(mesh: Mesh,
                                      tid_axes: Tuple[str, ...] = (),
                                      mode: str = "and",
                                      early_stop: bool = True,
                                      cls_axes: Tuple[str, ...] = ()):
    """Build the fused sharded dispatch for ``mesh`` (ISSUE 2 tentpole;
    shard-local in-dispatch block ES added by ISSUE 4; 2-D
    ``(block, cls)`` candidate-class sharding added by ISSUE 9).

    Returns a jitted shard_map program
    ``fused(rows, suffix, ua, vb, slots, rho_parent, minsup,
    n_real_blocks=None) -> (rows, suffix, bound, count, blocks,
    alive)`` that is bit-exact against
    ``ref.screen_and_intersect_sharded_ref`` with ``n_shards`` = the
    product of ``tid_axes`` sizes and ``n_cls`` = the product of
    ``cls_axes`` sizes.  ``n_real_blocks`` is the unpadded
    block count: each shard's scan count is clamped to its real blocks
    so ``blocks`` (the word_ops numerator) never charges the all-zero
    pad tail the store adds to divide the shard count.  Layouts (``DeviceRowStore``
    sharded mode): ``rows uint32 (cap, nb, bw)`` block-sharded over
    ``tid_axes`` (replicated over ``cls_axes``); ``suffix int32
    (cap, n_shards*(nb_local+1))`` column-sharded so each block shard
    owns its local suffix table; pair index/rho vectors replicated
    over the block axes and **sharded over** ``cls_axes`` — each cls
    shard evaluates a disjoint contiguous slice of the chunk's pairs.

    One dispatch per pair chunk: gather operands from the block-sharded
    slab, psum the screen's per-pair slack over the **block axes only**
    (mode "and" with ES: one small ``int32[n_pairs / n_cls]``
    collective per cls shard), walk the local blocks with the
    shared blocked-ES scan against the conservative shard-local
    threshold ``minsup - slack`` (each shard aborts mid-scan exactly
    like the single-device path once it has *proven* the pair globally
    infrequent — see the ref docstring for the bound), then one fused
    psum of the per-shard ``(count, blocks, dead, screen-bound)``
    vectors — again over the block axes only, so the per-pair outputs
    come back ``cls``-sharded and the host sees the full chunk in pair
    order — and a **survivor-only** shard-local child scatter: the psum
    completes before the scatter phase and gates it, so candidates
    whose global support misses minsup (or that any shard aborted)
    cost zero scatter words.

    2-D scatter locality: each device writes only its *block* slice of
    each surviving child — scatter traffic never crosses block shards.
    Because the slab is replicated along ``cls``, the per-slice
    survivors ``(Z, slots, child suffix)`` are ``all_gather``-ed along
    the cls axes first (one tiled collective of the chunk's child rows
    per block-shard row of the mesh) so every cls replica performs the
    identical scatter and the slab stays replication-consistent.  The
    scan itself — the O(n_pairs * n_blocks * block_words) term — is
    split ``n_cls`` ways; the all-gather moves each child row once,
    which is the same order as the scatter it feeds.
    ``rows``/``suffix`` are DONATED: callers must replace their
    handles.
    """
    if mode not in ("and", "andnot"):
        raise ValueError(f"bad mode {mode!r}")
    cls_axes = tuple(cls_axes)
    tid_axes = (tuple(tid_axes) if tid_axes else
                tuple(a for a in mesh.axis_names if a not in cls_axes))
    if set(tid_axes) & set(cls_axes):
        raise ValueError(f"tid_axes {tid_axes} and cls_axes {cls_axes} "
                         f"overlap")
    tid_spec = tid_axes if len(tid_axes) > 1 else tid_axes[0]
    rows_spec = P(None, tid_spec, None)
    suffix_spec = P(None, tid_spec)
    n_cls = 1
    for ax in cls_axes:
        n_cls *= mesh.shape[ax]
    if cls_axes:
        vec = P(cls_axes if len(cls_axes) > 1 else cls_axes[0])
    else:
        vec = P(None)

    def fused(rows, suffix, ua, vb, slots, rho_parent, minsup, n_real):
        # Local shapes: rows (cap, nb_local, bw), suffix (cap, nb_local+1).
        n = ua.shape[0]
        U = jnp.take(rows, ua, axis=0)
        V = jnp.take(rows, vb, axis=0)
        su = jnp.take(suffix, ua, axis=0)
        sv = jnp.take(suffix, vb, axis=0)
        rho = rho_parent.astype(jnp.int32)
        minsup = jnp.asarray(minsup, jnp.int32)

        if not early_stop:
            thr = jnp.full((n,), jnp.iinfo(jnp.int32).min, jnp.int32)
        elif mode == "and":
            m = jnp.minimum(su[:, 0], sv[:, 0])     # local achievable mass
            slack = jax.lax.psum(m, tid_axes) - m   # every OTHER shard's
            thr = minsup - slack
        else:
            thr = jnp.broadcast_to(minsup, (n,))

        Z, cnt, blocks, alive = _ref._blocked_es_scan(
            U, V, su, sv, rho, thr, mode=mode)
        nbl = rows.shape[1]
        if mode == "andnot":
            # Diffset work counter (ISSUE 6): charge only the
            # *nonzero-mass* U blocks this shard's scan visited, like
            # the single-device ``_blocked_diff_scan`` — the scan's
            # ``blocks`` counts the alive-visited prefix, so
            # ``k < blocks`` marks visited blocks.  Pad blocks are
            # all-zero (zero mass), so they discount themselves and no
            # real-block clamp is needed.
            umass = su[:, :-1] - su[:, 1:]
            visited = (jnp.arange(nbl, dtype=jnp.int32)[None, :]
                       < blocks[:, None])
            blocks = jnp.logical_and(umass > 0, visited).sum(
                axis=1).astype(jnp.int32)
        else:
            # Discount this shard's all-zero pad tail from the scan
            # count (the store pads the block axis to the shard count;
            # pads never change counts or aliveness) so the psum'd
            # ``blocks`` — the word_ops numerator — is consistently
            # unpadded.
            sidx = jnp.int32(0)
            for ax in tid_axes:
                sidx = sidx * mesh.shape[ax] + jax.lax.axis_index(ax)
            real_local = jnp.clip(n_real.astype(jnp.int32) - sidx * nbl,
                                  0, nbl)
            blocks = jnp.minimum(blocks, real_local)
        zpc = _popcount32(Z).sum(axis=-1)           # (n, nb_local)
        c0 = zpc[:, 0]
        if mode == "and":
            bound_c = c0 + jnp.minimum(su[:, 1], sv[:, 1])
        else:
            bound_c = c0
        count, blocks, dead, bound = jax.lax.psum(
            (cnt, blocks, (~alive).astype(jnp.int32), bound_c), tid_axes)
        if mode == "andnot":
            bound = rho - bound
        alive_g = dead == 0

        # Survivor-only shard-local scatter (ISSUE 5): the psum above is
        # the extra in-dispatch dependency edge — every shard knows the
        # global count/alive before its scatter, so dead candidates'
        # child rows are never written (slots redirected out of range,
        # like the pair padding).
        keep = _ref._survivor_mask(count, alive_g, rho, minsup, mode=mode)
        slots_eff = jnp.where(keep, slots, jnp.int32(rows.shape[0]))
        child_suffix = jnp.concatenate(
            [jnp.cumsum(zpc[:, ::-1], axis=-1)[:, ::-1],
             jnp.zeros((zpc.shape[0], 1), jnp.int32)], axis=-1)
        if cls_axes:
            # 2-D mesh (ISSUE 9): the slab is replicated along cls, so
            # the scatter must be too.  Re-assemble the full chunk's
            # survivors from the per-slice results — one tiled
            # all_gather along the cls axes of each device's *local
            # block slice* of Z (traffic stays within a block-shard row
            # of the mesh; no data ever crosses block shards).  Slices
            # are contiguous in pair order, so the gathered chunk is in
            # the original pair order and every cls replica performs
            # the identical block-shard-local scatter.
            Z = jax.lax.all_gather(Z, cls_axes, axis=0, tiled=True)
            slots_eff = jax.lax.all_gather(slots_eff, cls_axes, axis=0,
                                           tiled=True)
            child_suffix = jax.lax.all_gather(child_suffix, cls_axes,
                                              axis=0, tiled=True)
        rows = rows.at[slots_eff].set(Z, mode="drop")
        suffix = suffix.at[slots_eff].set(child_suffix, mode="drop")
        return rows, suffix, bound, count, blocks, alive_g

    mapped = shard_map(
        fused, mesh=mesh,
        in_specs=(rows_spec, suffix_spec, vec, vec, vec, vec, P(), P()),
        out_specs=(rows_spec, suffix_spec, vec, vec, vec, vec),
        check_vma=False)
    jitted = jax.jit(mapped, donate_argnums=(0, 1))

    def dispatch(rows, suffix, ua, vb, slots, rho_parent, minsup,
                 n_real_blocks=None):
        if n_real_blocks is None:       # no padding: every block is real
            n_real_blocks = rows.shape[1]
        ua = jnp.asarray(ua, jnp.int32)
        vb = jnp.asarray(vb, jnp.int32)
        slots = jnp.asarray(slots, jnp.int32)
        rho_parent = jnp.asarray(rho_parent, jnp.int32)
        pad = -int(ua.shape[0]) % n_cls
        if pad:
            # The pair chunk must divide the cls axes (shard_map splits
            # axis 0 evenly).  Engine chunks are bucket-padded already
            # (every ``PAIR_CHUNK_BUCKETS`` width divides the practical
            # cls counts); this is the safety net for direct callers.
            # Pad slots point past the slab so the writes drop.
            ua = jnp.concatenate([ua, jnp.zeros(pad, jnp.int32)])
            vb = jnp.concatenate([vb, jnp.zeros(pad, jnp.int32)])
            slots = jnp.concatenate(
                [slots, jnp.full(pad, jnp.int32(rows.shape[0]))])
            rho_parent = jnp.concatenate(
                [rho_parent, jnp.zeros(pad, jnp.int32)])
        return jitted(rows, suffix, ua, vb, slots, rho_parent,
                      jnp.asarray(minsup, jnp.int32),
                      jnp.asarray(n_real_blocks, jnp.int32))

    dispatch.program = jitted       # the jitted program, for AOT lowering
    return dispatch


# No buffer donation here: compaction's whole point is that the output
# slab has a DIFFERENT (smaller) shape, so the input could never be
# reused in place anyway.
@functools.partial(jax.jit, static_argnames=("backend",))
def _compact_rows_impl(rows, suffix, perm, *, backend):
    if backend in _PALLAS:
        from .compact import compact_gather as _pg
        interp = backend == "interpret"
        return (_pg(rows, perm, interpret=interp),
                _pg(suffix, perm, interpret=interp))
    return (_ref.compact_gather_ref(rows, perm),
            _ref.compact_gather_ref(suffix, perm))


def compact_rows(rows, suffix, perm, *, backend: str = "auto",
                 ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Row-store compaction: gather live rows + suffix tables to the
    front of a fresh (usually smaller) slab in ONE fused device dispatch.

    ``perm int32 (new_capacity,)`` maps destination slots to source
    slots; negative entries come up zeroed (free slots).  Bit-exact vs
    ``ref.compact_gather_ref`` on both backends.  ``rows``/``suffix``
    are replaced wholesale: callers must swap in the returned slabs."""
    b = _resolve(backend)
    return _compact_rows_impl(rows, suffix, jnp.asarray(perm, jnp.int32),
                              backend=b)


@functools.partial(jax.jit, static_argnames=("backend",))
def _compact_codes_impl(codes, perm, *, backend):
    if backend in _PALLAS:
        from .compact import compact_gather as _pg
        return _pg(codes, perm, interpret=backend == "interpret")
    return _ref.compact_gather_ref(codes, perm)


def compact_codes(codes, perm, *, backend: str = "auto") -> jnp.ndarray:
    """N-list pool compaction: repack live extents to the front of a
    fresh slab in ONE fused device dispatch (``perm`` carries the
    per-code source index; -1 = zero fill).  Bit-exact vs
    ``ref.compact_gather_ref`` on both backends."""
    b = _resolve(backend)
    return _compact_codes_impl(codes, jnp.asarray(perm, jnp.int32),
                               backend=b)


def bitmap_intersect_full(U, V, *, mode: str = "and",
                          backend: str = "auto"):
    """Fused full intersection (Z, counts) without block metrics."""
    del backend
    return _ref.bitmap_intersect_full_ref(U, V, mode=mode)


def bitmap_count(U, V, *, backend: str = "auto") -> jnp.ndarray:
    """Support counting without ES and without materialising Z."""
    # The jnp path is already a single fused AND+popcount+reduce; the
    # pallas path reuses the ES kernel with minsup=0 (never aborts).
    b = _resolve(backend)
    if b in _PALLAS:
        n_pairs, n_blocks, _ = U.shape
        zeros = jnp.zeros((n_pairs, n_blocks + 1), jnp.int32)
        rho = jnp.zeros((n_pairs,), jnp.int32)
        _, cnt, _, _ = _pallas_bitmap(U, V, zeros, zeros, rho,
                                      jnp.int32(0), mode="and",
                                      interpret=b == "interpret")
        return cnt
    return _ref.bitmap_count_ref(U, V)


def screen_pairs(first_u, first_v, suffix1_u, suffix1_v, rho_parent, minsup,
                 *, mode: str = "and", backend: str = "auto"):
    """One-block screening bound (inter-call early stopping)."""
    del backend  # single cheap fused op; jnp path is optimal everywhere
    return _ref.screen_pairs_ref(first_u, first_v, suffix1_u, suffix1_v,
                                 rho_parent, minsup, mode=mode)


def flash_attention(q, k, v, *, causal: bool = True, softmax_scale=None,
                    backend: str = "auto"):
    """Fused attention: Pallas kernel on TPU, dense ref elsewhere."""
    b = _resolve(backend)
    if b in _PALLAS:
        from .flash_attention import flash_attention as _fa
        return _fa(q, k, v, causal=causal, softmax_scale=softmax_scale,
                   interpret=b == "interpret")
    return _ref.flash_attention_ref(q, k, v, causal=causal,
                                    softmax_scale=softmax_scale)


def embedding_bag(table, ids, mask, *, combiner: str = "mean",
                  backend: str = "auto"):
    """Fused EmbeddingBag: Pallas on TPU, take+reduce elsewhere."""
    b = _resolve(backend)
    if b in _PALLAS:
        from .segment_embed import embedding_bag as _eb
        return _eb(table, ids, mask, combiner=combiner,
                   interpret=b == "interpret")
    return _ref.embedding_bag_ref(table, ids, mask, combiner=combiner)


def nlist_intersect(u_pre, u_post, u_freq, v_pre, v_post, v_freq,
                    u_len, v_len, rho_v, minsup, *, early_stop: bool = True,
                    backend: str = "auto"):
    """Batched padded N-list merge (kernel micro-bench entry point).

    The mining hot path uses :func:`nlist_extend` — this standalone
    variant takes host-materialised padded batches."""
    b = _resolve(backend)
    if b in _PALLAS:
        from .nlist_merge import nlist_merge as _pallas_merge
        return _pallas_merge(u_pre, u_post, u_freq, v_pre, v_post, v_freq,
                             u_len, v_len, rho_v, minsup,
                             early_stop=early_stop,
                             interpret=b == "interpret")
    return _ref.nlist_intersect_ref(u_pre, u_post, u_freq,
                                    v_pre, v_post, v_freq,
                                    u_len, v_len, rho_v, minsup,
                                    early_stop=early_stop)


def _nl_merge_backend(codes, u_off, u_len, v_off, v_len, rho_v, minsup,
                      *, lu, lv, early_stop, backend):
    """Shared gather + two-pointer-merge body of the N-list dispatches."""
    u_pre, u_post, u_freq = _ref._nl_gather(codes, u_off, u_len, lu)
    v_pre, v_post, v_freq = _ref._nl_gather(codes, v_off, v_len, lv)
    if backend in _PALLAS:
        from .nlist_merge import nlist_merge as _pallas_merge
        merged = _pallas_merge(
            u_pre, u_post, u_freq, v_pre, v_post, v_freq,
            u_len, v_len, rho_v, minsup, early_stop=early_stop,
            interpret=backend == "interpret")
    else:
        merged = _ref._nl_merge_vmapped(
            u_pre, u_post, u_freq, v_pre, v_post, v_freq,
            u_len, v_len, rho_v, minsup, early_stop=early_stop)
    return merged, u_freq, v_pre, v_post


@functools.partial(jax.jit,
                   static_argnames=("lu", "lv", "early_stop", "backend"),
                   donate_argnums=(0,))
def _nlist_extend_impl(codes, u_off, u_len, v_off, v_len, out_off, rho_v,
                       minsup, *, lu, lv, early_stop, backend):
    merged, u_freq, v_pre, v_post = _nl_merge_backend(
        codes, u_off, u_len, v_off, v_len, rho_v, minsup,
        lu=lu, lv=lv, early_stop=early_stop, backend=backend)
    out_slot, support, cmps, checks, alive = merged
    # Survivor-only scatter: aborted pairs report support 0, so one
    # frequency gate covers both ES deaths and plain infrequency.
    keep = support >= minsup
    out_off_eff = jnp.where(keep, out_off, jnp.int32(codes.shape[0]))
    codes, child_len = _ref._nl_zmerge_scatter(
        codes, out_slot, u_freq, v_pre, v_post, out_off_eff)
    return codes, child_len, support, cmps, checks, alive


def nlist_extend(codes, u_off, u_len, v_off, v_len, out_off, rho_v, minsup,
                 *, lu: int, lv: int, early_stop: bool = True,
                 backend: str = "auto"):
    """Fused PrePost+ class extension over a device N-list pool.

    The N-list analogue of :func:`screen_and_intersect` (one dispatch per
    pair chunk): gathers both operand N-lists from the ``codes`` slab by
    extent offset, runs the two-pointer merge with the
    ``z_mass + (rho_V - skip)`` ES guard (bit-exact vs
    ``ref.nlist_extend_ref``, comparison counts exactly the oracle's),
    Z-merges consecutive same-ancestor slots on device and scatters the
    compacted child N-lists back into the pool at ``out_off`` — no host
    N-list materialisation between levels.  The scatter is
    **survivor-only** (ISSUE 5): pairs whose support misses minsup write
    nothing.  The mining hot path uses the two-dispatch split
    (:func:`nlist_presize` + :func:`nlist_scatter`) for exact-length
    extents; this one-dispatch form remains the micro-bench API.

    ``codes`` is DONATED: callers must replace their handle with the
    returned slab.  Returns
    ``(codes, child_len, support, comparisons, checks, alive)``.
    """
    b = _resolve(backend)
    return _nlist_extend_impl(
        codes, jnp.asarray(u_off, jnp.int32), jnp.asarray(u_len, jnp.int32),
        jnp.asarray(v_off, jnp.int32), jnp.asarray(v_len, jnp.int32),
        jnp.asarray(out_off, jnp.int32), jnp.asarray(rho_v, jnp.int32),
        jnp.asarray(minsup, jnp.int32), lu=lu, lv=lv,
        early_stop=early_stop, backend=b)


@functools.partial(jax.jit,
                   static_argnames=("lu", "lv", "early_stop", "backend"))
def _nlist_presize_impl(codes, u_off, u_len, v_off, v_len, rho_v,
                        minsup, *, lu, lv, early_stop, backend):
    merged, _, _, _ = _nl_merge_backend(
        codes, u_off, u_len, v_off, v_len, rho_v, minsup,
        lu=lu, lv=lv, early_stop=early_stop, backend=backend)
    out_slot, support, cmps, checks, alive = merged
    _, _, child_len = _ref._nl_group_starts(out_slot)
    return out_slot, child_len, support, cmps, checks, alive


def nlist_presize(codes, u_off, u_len, v_off, v_len, rho_v, minsup,
                  *, lu: int, lv: int, early_stop: bool = True,
                  backend: str = "auto"):
    """Merge-only pre-pass of the two-dispatch PrePost+ extension
    (ISSUE 5 tentpole; pinned by ``ref.nlist_presize_ref``).

    Runs the gather + two-pointer ES merge and the Z-merge group count
    but NO scatter: the host learns each candidate's exact child length,
    support and aliveness, allocates tight extents for the survivors
    only, and hands the device-resident ``out_slot`` match table to
    :func:`nlist_scatter` — the merge loop runs exactly once per
    candidate, and the pool never holds a pessimistic
    ``min(|U|, |V|)`` extent again.  ``codes`` is NOT donated (the
    pre-pass only reads the slab).

    Returns ``(out_slot, child_len, support, comparisons, checks,
    alive)``."""
    b = _resolve(backend)
    return _nlist_presize_impl(
        codes, jnp.asarray(u_off, jnp.int32), jnp.asarray(u_len, jnp.int32),
        jnp.asarray(v_off, jnp.int32), jnp.asarray(v_len, jnp.int32),
        jnp.asarray(rho_v, jnp.int32), jnp.asarray(minsup, jnp.int32),
        lu=lu, lv=lv, early_stop=early_stop, backend=b)


@functools.partial(jax.jit, static_argnames=("lu", "lv"),
                   donate_argnums=(0,))
def _nlist_scatter_impl(codes, out_slot, u_off, u_len, v_off, v_len,
                        out_off, *, lu, lv):
    _, _, u_freq = _ref._nl_gather(codes, u_off, u_len, lu)
    v_pre, v_post, _ = _ref._nl_gather(codes, v_off, v_len, lv)
    return _ref._nl_zmerge_scatter(codes, out_slot, u_freq, v_pre, v_post,
                                   out_off)


def nlist_scatter(codes, out_slot, u_off, u_len, v_off, v_len, out_off,
                  *, lu: int, lv: int, backend: str = "auto"):
    """Scatter pass of the two-dispatch PrePost+ extension (pinned by
    ``ref.nlist_scatter_ref``).

    Re-gathers the operand codes (no merge loop), Z-merges the
    :func:`nlist_presize` match table and scatters the compacted child
    N-lists into their tight extents at ``out_off``; callers pass
    ``out_off >= capacity`` for non-survivors and padding, which makes
    the scatter survivor-only by construction.  Gather/Z-merge/scatter
    are pure vectorized jnp on every backend.  ``codes`` is DONATED:
    callers must replace their handle.  Returns ``(codes, child_len)``.
    """
    del backend
    return _nlist_scatter_impl(
        codes, jnp.asarray(out_slot, jnp.int32),
        jnp.asarray(u_off, jnp.int32), jnp.asarray(u_len, jnp.int32),
        jnp.asarray(v_off, jnp.int32), jnp.asarray(v_len, jnp.int32),
        jnp.asarray(out_off, jnp.int32), lu=lu, lv=lv)
