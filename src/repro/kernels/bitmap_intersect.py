"""Pallas TPU kernel: blocked bitmap intersection with early stopping.

This is the paper's contribution lowered to the TPU execution model
(docs/ARCHITECTURE.md): TID-lists are packed ``uint32`` bitmap rows, intersection
is ``AND`` (+ ``ANDNOT`` for dEclat diffsets) + SWAR popcount on the VPU,
and the Early-Stopping criterion is evaluated once per *block* using
precomputed suffix-popcount tables.  A pair that is provably infrequent
stops consuming VPU cycles at the next block boundary.

Grid/layout
-----------
grid = (n_pairs,) — one program per candidate pair.  Each program pulls
its two operand rows ``(1, n_blocks, block_words)`` into VMEM (BlockSpec),
walks the blocks with a ``lax.while_loop`` carrying
``(block_idx, count, alive)``, writes the intersection blocks it actually
processed, and publishes ``count`` / ``blocks_done`` through SMEM outputs.

``block_words`` is 128 by default so each block is a lane-aligned
``(8, 128)``-tileable uint32 slab of 4096 transactions.

VMEM budget: 3 rows x n_blocks x block_words x 4B; at the default block
size a 1M-transaction database is ~3 x 125KB — far under the ~16MB/core
VMEM of v5e.  For larger databases the TID axis is sharded across the mesh
first (count distribution, core/distributed.py), so per-device rows stay
small; the kernel never needs an HBM-resident row.

Semantics are defined by ``kernels/ref.py::bitmap_intersect_es_ref`` and
must match it bit-for-bit (tests/test_kernels.py sweeps shapes, modes and
minsup values, including minsup<=0 == ES disabled).

Fused dispatch contract
-----------------------
The mining hot path no longer calls this kernel on host-materialised
operand batches.  ``ops.screen_and_intersect`` wraps it in a single jit
with a store-index gather in front and a child-row + suffix-table
scatter behind, so that one ``pallas_call`` plus its surrounding
gather/scatter lowers to ONE device dispatch per pair chunk and all row
traffic stays in HBM/VMEM.  The block-0 iteration of the while_loop IS
the old one-block screen (the bound after block 0 equals the screen
bound), which is why no separate screen kernel exists anymore.  The
scatter half of that contract is **survivor-only** (ISSUE 5): the
kernel's count/alive outputs are produced first and gate the scatter —
a pair this kernel killed (or that finished below minsup) has its
child-slot write dropped, on both the Pallas and jnp backends, so dead
candidates stop consuming scatter bandwidth the same way they already
stopped consuming VPU cycles.  Inside the kernel nothing changes: the
while_loop already writes only the blocks it actually processed.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .spec import pair_spec, resolve_interpret, smem_out, smem_table


def _popcount_sum(z: jnp.ndarray) -> jnp.ndarray:
    """SWAR popcount of a uint32 block, summed to a scalar int32."""
    x = z.astype(jnp.uint32)
    x = x - ((x >> 1) & jnp.uint32(0x55555555))
    x = (x & jnp.uint32(0x33333333)) + ((x >> 2) & jnp.uint32(0x33333333))
    x = (x + (x >> 4)) & jnp.uint32(0x0F0F0F0F)
    pc = ((x * jnp.uint32(0x01010101)) >> 24).astype(jnp.int32)
    return pc.sum()


def _kernel(mode: str, n_blocks: int,
            minsup_ref, u_ref, v_ref, su_ref, sv_ref, rho_ref,
            z_ref, cnt_ref, blocks_ref):
    """One candidate pair: blocked ES intersection.

    minsup_ref: (1,) SMEM        — scalar threshold (whole array)
    u_ref/v_ref: (1, nb, bw)     VMEM operand rows
    su_ref/sv_ref: (1, 1, nb+1)  SMEM suffix popcount rows
    rho_ref: (1, 1, 1) SMEM      — parent support (andnot mode)
    z_ref: (1, nb, bw) VMEM      — intersection/diffset row (zeros past abort)
    cnt_ref, blocks_ref: (1, 1, 1) SMEM outputs

    Per-pair tables carry a unit middle axis so every block's last two
    dims equal the array's — the TPU lowering's tiling rule for blocks
    that are not (8, 128)-divisible.
    """
    minsup = minsup_ref[0]

    # Dead blocks must read back as zero: clear the output row first.
    z_ref[...] = jnp.zeros_like(z_ref)

    def cond(carry):
        k, _, alive = carry
        return jnp.logical_and(k < n_blocks, alive)

    def body(carry):
        k, cnt, alive = carry
        u_k = u_ref[0, pl.ds(k, 1), :]
        v_k = v_ref[0, pl.ds(k, 1), :]
        z_k = u_k & (v_k if mode == "and" else ~v_k)
        z_ref[0, pl.ds(k, 1), :] = z_k
        cnt = cnt + _popcount_sum(z_k)
        if mode == "and":
            bound = cnt + jnp.minimum(su_ref[0, 0, k + 1],
                                      sv_ref[0, 0, k + 1])
        else:
            bound = rho_ref[0, 0, 0] - cnt
        alive = bound >= minsup
        return k + 1, cnt, alive

    k_end, cnt, _ = jax.lax.while_loop(
        cond, body, (jnp.int32(0), jnp.int32(0), jnp.bool_(True)))
    cnt_ref[0, 0, 0] = cnt
    blocks_ref[0, 0, 0] = k_end


@functools.partial(jax.jit, static_argnames=("mode", "interpret"))
def bitmap_intersect_es(
    U: jnp.ndarray,           # uint32 (n_pairs, n_blocks, bw)
    V: jnp.ndarray,           # uint32 (n_pairs, n_blocks, bw)
    suffix_u: jnp.ndarray,    # int32  (n_pairs, n_blocks + 1)
    suffix_v: jnp.ndarray,    # int32  (n_pairs, n_blocks + 1)
    rho_parent: jnp.ndarray,  # int32  (n_pairs,)
    minsup: jnp.ndarray,      # int32  scalar; <= 0 disables ES
    *,
    mode: str = "and",
    interpret: "bool | None" = None,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Pallas ES intersection.  Returns (Z, counts, blocks_done, alive).

    ``interpret=None`` compiles for the TPU there and runs the Pallas
    interpreter on the CPU (``ops._pallas_interpret``).
    """
    interpret = resolve_interpret(interpret)
    if mode not in ("and", "andnot"):
        raise ValueError(f"bad mode {mode!r}")
    n_pairs, n_blocks, bw = U.shape
    minsup_arr = jnp.reshape(jnp.asarray(minsup, jnp.int32), (1,))
    smem = pltpu.SMEM

    kernel = functools.partial(_kernel, mode, n_blocks)
    z, cnt, blocks = pl.pallas_call(
        kernel,
        grid=(n_pairs,),
        in_specs=[
            pl.BlockSpec(memory_space=smem),  # minsup (whole array)
            pair_spec((n_blocks, bw)),
            pair_spec((n_blocks, bw)),
            pair_spec((1, n_blocks + 1), smem),
            pair_spec((1, n_blocks + 1), smem),
            pair_spec((1, 1), smem),
        ],
        out_specs=[
            pair_spec((n_blocks, bw)),
            pair_spec((1, 1), smem),
            pair_spec((1, 1), smem),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_pairs, n_blocks, bw), jnp.uint32),
            smem_out(n_pairs),
            smem_out(n_pairs),
        ],
        interpret=interpret,
    )(minsup_arr, U, V, smem_table(suffix_u), smem_table(suffix_v),
      smem_table(rho_parent))
    cnt, blocks = cnt[:, 0, 0], blocks[:, 0, 0]
    # Recover the ref's ``alive`` flag: a pair that processed every block is
    # alive iff its *final* bound clears minsup (the final "and" bound is
    # exactly ``cnt`` since the suffix table ends in 0); a pair that exited
    # early is certified dead.
    if mode == "and":
        final_ok = cnt >= jnp.asarray(minsup, jnp.int32)
    else:
        final_ok = (rho_parent.astype(jnp.int32) - cnt) >= jnp.asarray(
            minsup, jnp.int32)
    alive = jnp.logical_and(blocks >= n_blocks, final_ok)
    return z, cnt, blocks, alive
