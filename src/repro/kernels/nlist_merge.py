"""Pallas TPU kernel: batched PrePost+ N-list merge with early stopping.

The sequential heart of ``ops.nlist_extend`` (DESIGN.md §Errata for the
ES criterion): one grid program per candidate pair walks the two operand
PP-code lists with a two-pointer ``lax.while_loop``, records which V
ancestor code each U code matched (``out_slot``), and aborts the moment
the corrected bound ``z_mass + (rho_V - skip)`` drops below minsup.

Grid/layout mirrors ``bitmap_intersect.py``: one program per pair.  The
merge reads and writes single codes at data-dependent indices, which is
scalar work done in SMEM (scalar memory).  The operand rows and the
match table stay in HBM as ``(P, 1, L)`` views; the kernel copies the
operands into SMEM in fixed-width blocks as its two pointers advance,
one block ahead of each, and stops copying when the merge ends (by early
stopping, or at the end of either list), so a pair pays for the codes
it reads, not for its padded rows.  The match table goes back to HBM
block by block the same way.  Lengths and rho come in by scalar
prefetch; the per-pair outputs are ``(1, 1, 1)`` SMEM blocks.

Semantics are defined by ``kernels/ref.py::_nl_merge_vmapped`` (the body
of ``nlist_intersect_ref`` / ``nlist_presize_ref`` /
``nlist_extend_ref``) and must match it bit-for-bit;
tests/test_kernels.py sweeps shapes, lengths, ES on/off and minsup
values.  On the mining hot path this kernel is the merge phase of the
*pre-pass* dispatch (``ops.nlist_presize``, ISSUE 5): its match table
stays on device while the host allocates tight extents for the
surviving children only, and the separate scatter dispatch
(``ops.nlist_scatter``) Z-merges it with :func:`nlist_zmerge`, the
second kernel of this file, and writes the children into the pool —
the merge loop runs exactly once per candidate, and dead candidates
are never scattered.  The one-dispatch composition (``ops.nlist_extend``, same
kernel, survivor-gated scatter fused behind it) remains the
micro-bench API.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.bitmap import NL_SENTINEL

from .spec import (SMEM_BYTES, pair_spec, resolve_interpret, smem_out,
                   smem_table)


# Codes of one operand column per block fetched into scalar memory: a
# row of ``w`` codes is fetched in blocks of ``min(w, B_MAX)``.
B_MAX = 1024
# Rows narrower than a lane tile cannot be sliced out of HBM by a DMA
# (the TPU's "aligned to tiling (128)"): the grid's pipeline copies
# them whole into scalar memory instead, every pair's row.
LANES = 128
# Merge steps per trip of the inner loop, which pays its test and
# branch once a trip: four is where the v5e compiler's bundles per step
# stop falling.
UNROLL = 4


def _umin(a, b):
    """``min`` of two non-negative int32 scalars, as one unsigned min
    (a signed one is a compare and a select on the TPU's scalar unit)."""
    return jnp.minimum(a.astype(jnp.uint32), b.astype(jnp.uint32)).astype(
        jnp.int32)


class _Block:
    """Buffer ``s`` of an operand column's ``(2, 1, b)`` SMEM scratch,
    indexed by the code's place in its block.  A narrow row's
    ``(1, 1, w)`` pipeline block is indexed whole instead: Mosaic cannot
    slice a view of fewer than ``LANES`` codes out of it."""

    def __init__(self, ref, s, wide: bool):
        self._ref, self._lead = (ref.at[s, 0], ()) if wide else (ref, (0, 0))

    def __getitem__(self, k):
        return self._ref[self._lead + (k,)]

    def __setitem__(self, k, value):
        self._ref[self._lead + (k,)] = value


def _kernel(early_stop: bool, bu: int, bv: int,
            minsup_ref, nu_ref, nv_ref, rho_ref,
            up_in, upo_in, uf_in, vp_in, vpo_in, vf_in,
            slot_out, mass_ref, cmp_ref, chk_ref, alive_ref, end_ref,
            up_buf, upo_buf, uf_buf, vp_buf, vpo_buf, vf_buf, slot_buf,
            u_sem, v_sem, slot_sem):
    """One candidate pair: two-pointer NL merge over operand blocks.

    minsup/nu/nv/rho_ref: SMEM            — scalar prefetch: threshold,
                                            lengths, sibling support
    u*_in/v*_in: (P, 1, lu|lv) HBM        — U and V (pre, post, freq) rows
    slot_out: (P, 1, lu) HBM              — matched V index per U slot
    mass/cmp/chk/alive/end_ref: (1, 1, 1) SMEM outputs
    u*/v*_buf, slot_buf: (2, 1, bu|bv) SMEM — two blocks per column
    u_sem/v_sem: (3, 2), slot_sem: (2,)   — DMA semaphores per buffer

    Every access is a scalar at a data-dependent index, so the codes are
    read from SMEM (scalar memory).  Block ``k`` of a row sits in buffer
    ``k % 2``: entering it waits for its copy and starts block ``k + 1``
    (if the row has one), so the copy of the next block overlaps the
    merge of this one, and no block past the one after the merge's last
    is fetched.  The outer loop enters blocks; the inner loop runs the
    merge steps, ``UNROLL`` a trip, bounded by the two blocks' ends.
    Slot ``i`` holds the V index U code ``i`` matched, or the sentinel,
    once the merge leaves it; a slot block goes back to HBM when ``i``
    leaves it, the partial last one at the end, and slots from ``end``
    (the final ``i``) on are left for the caller to mask to the
    sentinel.  A row under ``LANES`` codes is instead one ``(1, 1, w)``
    SMEM block of the grid's pipeline, read and written in place (its
    buffers go unused).
    """
    p = pl.program_id(0)
    minsup = minsup_ref[0]
    nu = nu_ref[p]
    nv = nv_ref[p]
    rho = rho_ref[p]
    u_wide, v_wide = bu >= LANES, bv >= LANES
    u_in, v_in = (up_in, upo_in, uf_in), (vp_in, vpo_in, vf_in)
    u_bufs = (up_buf, upo_buf, uf_buf) if u_wide else u_in
    v_bufs = (vp_buf, vpo_buf, vf_buf) if v_wide else v_in
    slot_bufs = slot_buf if u_wide else slot_out

    def block(hbm, b, k):
        """Block ``k`` of pair ``p``'s row (the whole row if it is one)."""
        if hbm.shape[-1] == b:
            return hbm.at[p]
        return hbm.at[p, :, pl.ds(pl.multiple_of(k * b, b), b)]

    def copies(ins, bufs, sems, b, k):
        return [pltpu.make_async_copy(block(hbm, b, k), buf.at[k % 2],
                                      sems.at[c, k % 2])
                for c, (hbm, buf) in enumerate(zip(ins, bufs, strict=True))]

    def start(ins, bufs, sems, b, k):
        if b >= LANES:
            for cp in copies(ins, bufs, sems, b, k):
                cp.start()

    def wait(ins, bufs, sems, b, k):
        if b >= LANES:
            for cp in copies(ins, bufs, sems, b, k):
                cp.wait()

    def slot_copy(k):
        return pltpu.make_async_copy(slot_buf.at[k % 2],
                                     block(slot_out, bu, k),
                                     slot_sem.at[k % 2])

    u = (u_in, u_bufs, u_sem, bu)
    v = (v_in, v_bufs, v_sem, bv)
    n_ublocks = (nu + bu - 1) // bu
    n_vblocks = (nv + bv - 1) // bv

    def enter(row, k, cur, n_blocks):
        @pl.when(k != cur)
        def _():
            wait(*row, k)

            @pl.when(k + 1 < n_blocks)
            def _():
                start(*row, k + 1)

    @pl.when(jnp.logical_and(nu > 0, nv > 0))
    def _():
        start(*u, 0)
        start(*v, 0)

    def outer_cond(st):
        i, j, _, _, alive, _, _ = st
        return jnp.logical_and(jnp.logical_and(i < nu, j < nv), alive)

    def outer_body(st):
        i, j, z_mass, slack, alive, cu, cv = st
        ku, kv = i // bu, j // bv
        enter(u, ku, cu, n_ublocks)
        enter(v, kv, cv, n_vblocks)
        ou, ov = ku * bu, kv * bv
        xp, xpo, xf = (_Block(ref, ku % 2, u_wide) for ref in u_bufs)
        yp, ypo, yf = (_Block(ref, kv % 2, v_wide) for ref in v_bufs)
        slot = _Block(slot_bufs, ku % 2, u_wide)
        i_end = jnp.minimum(nu - ou, bu)
        j_end = jnp.minimum(nv - ov, bv)

        def cond(st):
            i, j, _, _, alive = st
            return jnp.logical_and(
                jnp.logical_and(i < i_end, j < j_end), alive)

        def step(i, j, z_mass, slack):  # i and j index the two blocks
            xi_pre, xi_post, xi_f = xp[i], xpo[i], xf[i]
            yj_pre, yj_post, yj_f = yp[j], ypo[j], yf[j]
            # U advances unless V's code comes first and is no ancestor
            # of U's (``is_desc or le`` is ``le or below``).
            le = xi_pre <= yj_pre
            below = xi_post < yj_post
            adv = jnp.logical_or(le, below)
            is_desc = jnp.logical_and(jnp.logical_not(le), below)
            slot[i] = jnp.where(is_desc, ov + j, jnp.int32(NL_SENTINEL))
            z_mass = z_mass + jnp.where(is_desc, xi_f, 0)
            alive = jnp.bool_(True)
            if early_stop:              # slack = rho - skip - minsup
                slack = slack - jnp.where(adv, 0, yj_f)
                alive = z_mass + slack >= 0
            return (jnp.where(adv, i + 1, i), jnp.where(adv, j, j + 1),
                    z_mass, slack, alive)

        # UNROLL steps a trip.  A step past the first runs at indices
        # clamped into the blocks and counts only where the steps
        # before it left the merge in bounds and alive: where they did
        # not, it repeats the last step's comparison (a pointer that
        # reached its block's end stands one back) or writes a slot at
        # the merge's end, which the caller masks.
        def body(st):
            st = step(*st[:4])
            for _ in range(UNROLL - 1):
                i, j, z_mass, slack, alive = st
                ok = jnp.logical_and(
                    jnp.logical_and(i < i_end, j < j_end), alive)
                nxt = step(_umin(i, i_end - 1), _umin(j, j_end - 1),
                           z_mass, slack)
                st = tuple(jnp.where(ok, b, a) for a, b in zip(st, nxt))
            return st

        i, j, z_mass, slack, alive = jax.lax.while_loop(
            cond, body, (i - ou, j - ov, z_mass, slack, alive))
        i, j = ou + i, ov + j

        if u_wide:
            @pl.when(i == ou + bu)          # U block ku written whole
            def _():
                @pl.when(ku > 0)
                def _():
                    slot_copy(ku - 1).wait()
                slot_copy(ku).start()

        return i, j, z_mass, slack, alive, ku, kv

    zero = jnp.int32(0)
    init = (zero, zero, zero, rho - minsup, jnp.bool_(True),
            jnp.int32(-1), jnp.int32(-1))
    # Each comparison advances one pointer: the final pointers count
    # the comparisons, and V's counts the bound checks.
    end, checks, z_mass, _, alive, cu, cv = jax.lax.while_loop(
        outer_cond, outer_body, init)

    # Drain the block fetched ahead of each operand's last one.
    @pl.when(jnp.logical_and(cu >= 0, cu + 1 < n_ublocks))
    def _():
        wait(*u, cu + 1)

    @pl.when(jnp.logical_and(cv >= 0, cv + 1 < n_vblocks))
    def _():
        wait(*v, cv + 1)

    if u_wide:
        # Flush the partial slot block; drain the last whole one.
        k_end = end // bu

        @pl.when(end % bu != 0)
        def _():
            slot_copy(k_end).start()
            slot_copy(k_end).wait()

        @pl.when(k_end > 0)
        def _():
            slot_copy(k_end - 1).wait()

    mass_ref[0, 0, 0] = z_mass
    cmp_ref[0, 0, 0] = end + checks
    chk_ref[0, 0, 0] = checks
    alive_ref[0, 0, 0] = alive.astype(jnp.int32)
    end_ref[0, 0, 0] = end


def fetched_codes(u_adv, v_adv, u_len, v_len, lu: int, lv: int):
    """Operand codes the merge kernel copies into scalar memory, per pair
    (host numpy), from its outputs: ``u_adv`` = comparisons - checks and
    ``v_adv`` = checks are the pointers' final values.

    The kernel's block rule: a pair whose merge makes no comparison
    fetches nothing; otherwise an operand of ``n`` codes fetches whole
    blocks of ``b = min(width, B_MAX)`` codes, from block 0 through the
    one after the block its last comparison read, at most
    ``ceil(n / b)``; a row under ``LANES`` codes comes whole for every
    pair.  The last comparison advanced U exactly when U ran out or V
    never advanced (an abort on a U step happens only on the first
    step), so it read U code ``u_adv - 1`` and V code ``v_adv``, or else
    U code ``u_adv`` and V code ``v_adv - 1``."""
    # host-sync: host count vectors the caller already read back
    u_adv, v_adv, u_len, v_len = (np.asarray(x, np.int64) for x in
                                  (u_adv, v_adv, u_len, v_len))
    runs = (u_len > 0) & (v_len > 0)
    last_u = (u_adv == u_len) | (v_adv == 0)

    def codes(n, read_at, width):
        if width < LANES:
            return np.full(n.shape, width)
        b = min(width, B_MAX)
        blocks = np.minimum(-(-n // b), read_at // b + 2)
        return np.where(runs, blocks * b, 0)

    return (codes(u_len, u_adv - last_u, lu)
            + codes(v_len, v_adv - ~last_u, lv))


@functools.partial(jax.jit, static_argnames=("early_stop", "interpret"))
def nlist_merge(
    u_pre: jnp.ndarray, u_post: jnp.ndarray, u_freq: jnp.ndarray,  # (P, Lu)
    v_pre: jnp.ndarray, v_post: jnp.ndarray, v_freq: jnp.ndarray,  # (P, Lv)
    u_len: jnp.ndarray, v_len: jnp.ndarray,                        # (P,)
    rho_v: jnp.ndarray,                                            # (P,)
    minsup: jnp.ndarray,                                           # scalar
    *,
    early_stop: bool = True,
    interpret: "bool | None" = None,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray,
           jnp.ndarray]:
    """Pallas NL merge.  Returns ``(out_slot, support, comparisons,
    checks, alive)`` bit-exact vs ``ref._nl_merge_vmapped``.
    ``interpret=None`` compiles for the TPU there and runs the Pallas
    interpreter on the CPU (``ops._pallas_interpret``)."""
    interpret = resolve_interpret(interpret)
    n_pairs, lu = u_pre.shape
    _, lv = v_pre.shape
    bu, bv = min(lu, B_MAX), min(lv, B_MAX)
    smem = pltpu.SMEM
    one = pair_spec((1, 1), smem)

    def row(width):
        if width < LANES:
            return pair_spec((1, width), smem)
        return pl.BlockSpec(memory_space=pltpu.HBM)

    dma = pltpu.SemaphoreType.DMA

    kernel = functools.partial(_kernel, early_stop, bu, bv)
    out_slot, z_mass, cmps, checks, alive_i, end = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(n_pairs,),
            in_specs=[row(lu)] * 3 + [row(lv)] * 3,
            out_specs=[row(lu), one, one, one, one, one],
            scratch_shapes=(
                [smem((2, 1, bu), jnp.int32)] * 3
                + [smem((2, 1, bv), jnp.int32)] * 3
                + [smem((2, 1, bu), jnp.int32),
                   dma((3, 2)), dma((3, 2)), dma((2,))]),
        ),
        out_shape=[
            jax.ShapeDtypeStruct((n_pairs, 1, lu), jnp.int32),
            smem_out(n_pairs),
            smem_out(n_pairs),
            smem_out(n_pairs),
            smem_out(n_pairs),
            smem_out(n_pairs),
        ],
        interpret=interpret,
        name="nlist_merge",
    )(jnp.reshape(jnp.asarray(minsup, jnp.int32), (1,)),
      *(jnp.asarray(x, jnp.int32) for x in (u_len, v_len, rho_v)),
      *(smem_table(x) for x in (u_pre, u_post, u_freq,
                                v_pre, v_post, v_freq)))
    alive = alive_i[:, 0, 0].astype(jnp.bool_)
    support = jnp.where(alive, z_mass[:, 0, 0], 0)  # aborted => < minsup
    left = jnp.arange(lu, dtype=jnp.int32)[None, :] < end[:, 0, :]
    out_slot = jnp.where(left, out_slot[:, 0, :], jnp.int32(NL_SENTINEL))
    return out_slot, support, cmps[:, 0, 0], checks[:, 0, 0], alive


def _zmerge_kernel(slot_ref, uf_ref, vp_ref, vpo_ref, nu_ref,
                   zpre_ref, zpost_ref, zfreq_ref, clen_ref):
    """One pair's Z-merge (Alg. 3 line 31) over its match table: walk
    the ``nu`` U slots in order; a matched slot whose V index differs
    from the previous match opens a child (that V code's pre and post),
    and every matched slot adds its U frequency to the open child.

    slot_ref/uf_ref: (1, 1, lu) SMEM     — match table, U frequencies
    vp/vpo_ref: (1, 1, lv) SMEM          — V pre and post
    nu_ref: (1, 1, 1) SMEM               — U length
    zpre/zpost/zfreq_ref: (1, 1, lu) SMEM — children, at the row's front
    clen_ref: (1, 1, 1) SMEM             — child count
    """
    def body(i, st):
        k, last, mass = st
        j = slot_ref[0, 0, i]
        matched = j != jnp.int32(NL_SENTINEL)
        opens = jnp.logical_and(matched, j != last)
        k = k + opens.astype(jnp.int32)
        mass = jnp.where(opens, 0, mass) + jnp.where(matched,
                                                     uf_ref[0, 0, i], 0)

        @pl.when(matched)
        def _():
            at = k - 1
            zpre_ref[0, 0, at] = vp_ref[0, 0, j]
            zpost_ref[0, 0, at] = vpo_ref[0, 0, j]
            zfreq_ref[0, 0, at] = mass

        return k, jnp.where(matched, j, last), mass

    k, _, _ = jax.lax.fori_loop(0, nu_ref[0, 0, 0], body,
                                (jnp.int32(0), jnp.int32(-1), jnp.int32(0)))
    clen_ref[0, 0, 0] = k


def _zmerge_buffers(lu: int, lv: int) -> int:
    """Pipeline buffers per SMEM row block of the Z-merge (the match
    table, U frequencies and three child rows of ``lu``, V pre and post
    of ``lv``): double-buffered while a pair's rows fit half of SMEM,
    single-buffered up to the largest tuned N-list bucket
    (``NL_LEN_BUCKETS[-1]``), and refused beyond — such rows cannot sit
    in scalar memory, and ``backend="jnp"`` merges them."""
    row_bytes = 4 * (5 * lu + 2 * lv)
    if 2 * row_bytes <= SMEM_BYTES // 2:
        return 2
    if row_bytes <= SMEM_BYTES - (SMEM_BYTES >> 3):
        return 1
    raise ValueError(
        f"N-list rows of {lu} and {lv} codes exceed the TPU's scalar "
        f"memory ({SMEM_BYTES} bytes); mine them with backend='jnp'")


@functools.partial(jax.jit, static_argnames=("interpret",))
def nlist_zmerge(out_slot: jnp.ndarray, u_freq: jnp.ndarray,   # (P, Lu)
                 v_pre: jnp.ndarray, v_post: jnp.ndarray,      # (P, Lv)
                 u_len: jnp.ndarray,                           # (P,)
                 *, interpret: "bool | None" = None,
                 ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray,
                            jnp.ndarray]:
    """Pallas Z-merge.  Returns ``(zpre, zpost, zfreq, child_len)``
    bit-exact vs ``ref._nl_zmerge``: the children at the front of each
    row, zero past ``child_len``.  The scalar walk costs ``|U|`` steps
    per pair where the vector form scatters and gathers every slot."""
    interpret = resolve_interpret(interpret)
    n_pairs, lu = out_slot.shape
    _, lv = v_pre.shape
    smem = pltpu.SMEM
    buffers = _zmerge_buffers(lu, lv)
    u_row = pair_spec((1, lu), smem, buffers=buffers)
    v_row = pair_spec((1, lv), smem, buffers=buffers)
    one = pair_spec((1, 1), smem)
    row = jax.ShapeDtypeStruct((n_pairs, 1, lu), jnp.int32)
    zpre, zpost, zfreq, clen = pl.pallas_call(
        _zmerge_kernel,
        grid=(n_pairs,),
        in_specs=[u_row, u_row, v_row, v_row, one],
        out_specs=[u_row, u_row, u_row, one],
        out_shape=[row, row, row, smem_out(n_pairs)],
        interpret=interpret,
        name="nlist_zmerge",
    )(*(smem_table(x) for x in (out_slot, u_freq, v_pre, v_post, u_len)))
    child_len = clen[:, 0, 0]
    live = jnp.arange(lu, dtype=jnp.int32)[None, :] < child_len[:, None]
    return (jnp.where(live, zpre[:, 0, :], 0),
            jnp.where(live, zpost[:, 0, :], 0),
            jnp.where(live, zfreq[:, 0, :], 0), child_len)
