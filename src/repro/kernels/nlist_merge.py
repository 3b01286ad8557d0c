"""Pallas TPU kernel: batched PrePost+ N-list merge with early stopping.

The sequential heart of ``ops.nlist_extend`` (DESIGN.md §Errata for the
ES criterion): one grid program per candidate pair walks the two operand
PP-code lists with a two-pointer ``lax.while_loop``, records which V
ancestor code each U code matched (``out_slot``), and aborts the moment
the corrected bound ``z_mass + (rho_V - skip)`` drops below minsup.

Grid/layout mirrors ``bitmap_intersect.py``: one program per pair.  The
merge reads and writes single codes at data-dependent indices, which is
scalar work, so operand rows, the match table and the per-pair scalars
(lengths, rho, outputs) all live in SMEM as ``(1, 1, L)`` / ``(1, 1, 1)``
blocks of ``(P, 1, L)`` views.  N-lists are short by construction —
PrePost+'s selling point — so the bucketed rows fit scalar memory.

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
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.bitmap import NL_SENTINEL

from .spec import (SMEM_BYTES, pair_spec, resolve_interpret, smem_out,
                   smem_table)


def _kernel(early_stop: bool,
            minsup_ref, up_ref, upo_ref, uf_ref, vp_ref, vpo_ref, vf_ref,
            nu_ref, nv_ref, rho_ref,
            slot_ref, mass_ref, cmp_ref, chk_ref, alive_ref, end_ref):
    """One candidate pair: two-pointer NL merge.

    minsup_ref: (1,) SMEM                — scalar threshold
    up/upo/uf_ref: (1, 1, lu) SMEM       — U (pre, post, freq) rows
    vp/vpo/vf_ref: (1, 1, lv) SMEM       — V rows
    nu/nv/rho_ref: (1, 1, 1) SMEM        — actual lengths + sibling support
    slot_ref: (1, 1, lu) SMEM            — matched V index per U slot
    mass_ref/cmp_ref/chk_ref/alive_ref/end_ref: (1, 1, 1) SMEM outputs

    Every access is a scalar at a data-dependent index, so the rows live
    in SMEM (scalar memory), not VMEM.  Slot ``i`` is written once, when
    the merge leaves U code ``i`` (its V index, or the sentinel); slots
    from ``end`` (the final ``i``) on are never written, and the caller
    masks them to the sentinel with one vector pass.
    """
    minsup = minsup_ref[0]
    nu = nu_ref[0, 0, 0]
    nv = nv_ref[0, 0, 0]
    rho = rho_ref[0, 0, 0]

    def cond(st):
        i, j, _, _, _, _, alive = st
        return jnp.logical_and(jnp.logical_and(i < nu, j < nv), alive)

    def body(st):
        i, j, z_mass, skip, cmps, checks, alive = st
        cmps = cmps + 1
        xi_pre = up_ref[0, 0, i]
        xi_post = upo_ref[0, 0, i]
        xi_f = uf_ref[0, 0, i]
        yj_pre = vp_ref[0, 0, j]
        yj_post = vpo_ref[0, 0, j]
        yj_f = vf_ref[0, 0, j]
        is_desc = jnp.logical_and(xi_pre > yj_pre, xi_post < yj_post)
        adv = jnp.logical_or(is_desc, xi_pre <= yj_pre)
        slot_ref[0, 0, i] = jnp.where(is_desc, j, jnp.int32(NL_SENTINEL))
        z_mass = z_mass + jnp.where(is_desc, xi_f, 0)
        skip = skip + jnp.where(adv, 0, yj_f)
        checks = checks + jnp.where(adv, 0, 1)
        if early_stop:
            alive = jnp.logical_and(alive, z_mass + (rho - skip) >= minsup)
        i = i + jnp.where(adv, 1, 0)
        j = j + jnp.where(adv, 0, 1)
        return i, j, z_mass, skip, cmps, checks, alive

    init = (jnp.int32(0), jnp.int32(0), jnp.int32(0), jnp.int32(0),
            jnp.int32(0), jnp.int32(0), jnp.bool_(True))
    end, _, z_mass, _, cmps, checks, alive = jax.lax.while_loop(
        cond, body, init)
    mass_ref[0, 0, 0] = z_mass
    cmp_ref[0, 0, 0] = cmps
    chk_ref[0, 0, 0] = checks
    alive_ref[0, 0, 0] = alive.astype(jnp.int32)
    end_ref[0, 0, 0] = end


def _row_buffers(lu: int, lv: int) -> int:
    """Pipeline buffers per SMEM row block: double-buffered while a pair's
    rows (three U, three V and the U-length match table) fit half of
    SMEM, single-buffered up to the largest tuned N-list bucket
    (``NL_LEN_BUCKETS[-1]``), and refused beyond — such rows cannot sit
    in scalar memory, and ``backend="jnp"`` merges them."""
    row_bytes = 4 * (4 * lu + 3 * lv)
    if 2 * row_bytes <= SMEM_BYTES // 2:
        return 2
    if row_bytes <= SMEM_BYTES - (SMEM_BYTES >> 3):
        return 1
    raise ValueError(
        f"N-list rows of {lu} and {lv} codes exceed the TPU's scalar "
        f"memory ({SMEM_BYTES} bytes); mine them with backend='jnp'")


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
    minsup_arr = jnp.reshape(jnp.asarray(minsup, jnp.int32), (1,))
    smem = pltpu.SMEM
    buffers = _row_buffers(lu, lv)
    u_row = pair_spec((1, lu), smem, buffers=buffers)
    v_row = pair_spec((1, lv), smem, buffers=buffers)
    one = pair_spec((1, 1), smem)

    kernel = functools.partial(_kernel, early_stop)
    out_slot, z_mass, cmps, checks, alive_i, end = pl.pallas_call(
        kernel,
        grid=(n_pairs,),
        in_specs=[
            pl.BlockSpec(memory_space=smem),  # minsup (whole array)
            u_row, u_row, u_row, v_row, v_row, v_row, one, one, one,
        ],
        out_specs=[u_row, one, one, one, one, one],
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
    )(minsup_arr, *(smem_table(x) for x in (
        u_pre, u_post, u_freq, v_pre, v_post, v_freq, u_len, v_len, rho_v)))
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
    """As :func:`_row_buffers`, for the Z-merge's rows: the match table,
    U frequencies and three child rows of ``lu``, V pre and post of
    ``lv``."""
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
