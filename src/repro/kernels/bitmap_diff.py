"""Pallas TPU kernel: blocked dEclat difference with early stopping and
zero-block skipping (ISSUE 6).

The diffset sibling of ``kernels/bitmap_intersect.py``: one program per
candidate pair walks the blocks of ``Z = U & ~V`` with a
``lax.while_loop`` and aborts the moment the *difference* bound
``rho_parent - count`` drops below minsup (dEclat:
``sup(Pxy) = sup(Px) - |D(Pxy)|`` only decreases as diff words emit —
the paper's DIFFERENCE_ES quantised to blocks).  The block-0 iteration
IS the one-block screen, exactly like the intersect kernel.

What earns diffsets their own kernel is the *work counter*: a block
where the U operand has no set bits can never contribute to ``Z``
(``U & ~V`` is zero wherever ``U`` is), and diffset rows are exactly
the operands that go sparse on dense data — ``|d|`` shrinks as classes
deepen.  The per-block U mass is free from the operand's suffix table
(``su[k] - su[k+1]``), so ``blocks_done`` charges only the
*nonzero-mass* blocks a live pair visits.  Counts, aliveness and the
scattered ``Z`` stay bit-identical to
``bitmap_intersect_es(mode="andnot")`` on the same operands; only the
word-op numerator differs.

Because skipping decouples ``blocks_done`` from the abort point, the
ref's ``alive`` flag can no longer be recovered from ``blocks_done >=
n_blocks`` the way the intersect wrapper does — this kernel publishes
``alive`` explicitly through a fourth SMEM output.

Semantics are defined by ``kernels/ref.py::bitmap_diff_es_ref`` and must
match it bit-for-bit (tests/test_kernels.py sweeps shapes and minsup
values, including minsup<=0 == ES disabled).  The mining hot path wraps
this kernel in ``ops.screen_and_diff`` (gather + survivor-only child
scatter around one ``pallas_call``), mirroring the intersect path's
fused dispatch contract.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .bitmap_intersect import _popcount_sum
from .spec import pair_spec, resolve_interpret, smem_out, smem_table


def _kernel(n_blocks: int,
            minsup_ref, u_ref, v_ref, su_ref, rho_ref,
            z_ref, cnt_ref, blocks_ref, alive_ref):
    """One candidate pair: blocked ES difference with zero-block skip.

    minsup_ref: (1,) SMEM        — scalar threshold (whole array)
    u_ref/v_ref: (1, nb, bw)     VMEM operand rows
    su_ref: (1, 1, nb+1)         SMEM U suffix popcount row (mass source)
    rho_ref: (1, 1, 1) SMEM      — parent support (difference bound)
    z_ref: (1, nb, bw) VMEM      — diffset row (zeros past abort)
    cnt_ref, blocks_ref, alive_ref: (1, 1, 1) SMEM outputs
    """
    minsup = minsup_ref[0]
    rho = rho_ref[0, 0, 0]

    # Dead blocks must read back as zero: clear the output row first.
    z_ref[...] = jnp.zeros_like(z_ref)

    def cond(carry):
        k, _, _, alive = carry
        return jnp.logical_and(k < n_blocks, alive)

    def body(carry):
        k, cnt, blocks, alive = carry
        z_k = u_ref[0, pl.ds(k, 1), :] & ~v_ref[0, pl.ds(k, 1), :]
        z_ref[0, pl.ds(k, 1), :] = z_k
        cnt = cnt + _popcount_sum(z_k)
        mass = su_ref[0, 0, k] - su_ref[0, 0, k + 1]
        blocks = blocks + (mass > 0).astype(jnp.int32)
        alive = (rho - cnt) >= minsup
        return k + 1, cnt, blocks, alive

    _, cnt, blocks, alive = jax.lax.while_loop(
        cond, body,
        (jnp.int32(0), jnp.int32(0), jnp.int32(0), jnp.bool_(True)))
    cnt_ref[0, 0, 0] = cnt
    blocks_ref[0, 0, 0] = blocks
    alive_ref[0, 0, 0] = alive.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("interpret",))
def bitmap_diff_es(
    U: jnp.ndarray,           # uint32 (n_pairs, n_blocks, bw)
    V: jnp.ndarray,           # uint32 (n_pairs, n_blocks, bw)
    suffix_u: jnp.ndarray,    # int32  (n_pairs, n_blocks + 1)
    rho_parent: jnp.ndarray,  # int32  (n_pairs,)
    minsup: jnp.ndarray,      # int32  scalar; <= 0 disables ES
    *,
    interpret: "bool | None" = None,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Pallas ES difference.  Returns (Z, counts, blocks_done, alive).

    ``interpret=None`` compiles for the TPU there and runs the Pallas
    interpreter on the CPU (``ops._pallas_interpret``).
    """
    interpret = resolve_interpret(interpret)
    n_pairs, n_blocks, bw = U.shape
    minsup_arr = jnp.reshape(jnp.asarray(minsup, jnp.int32), (1,))
    smem = pltpu.SMEM

    kernel = functools.partial(_kernel, n_blocks)
    z, cnt, blocks, alive = pl.pallas_call(
        kernel,
        grid=(n_pairs,),
        in_specs=[
            pl.BlockSpec(memory_space=smem),  # minsup (whole array)
            pair_spec((n_blocks, bw)),
            pair_spec((n_blocks, bw)),
            pair_spec((1, n_blocks + 1), smem),
            pair_spec((1, 1), smem),
        ],
        out_specs=[
            pair_spec((n_blocks, bw)),
            pair_spec((1, 1), smem),
            pair_spec((1, 1), smem),
            pair_spec((1, 1), smem),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_pairs, n_blocks, bw), jnp.uint32),
            smem_out(n_pairs),
            smem_out(n_pairs),
            smem_out(n_pairs),
        ],
        interpret=interpret,
    )(minsup_arr, U, V, smem_table(suffix_u), smem_table(rho_parent))
    return (z, cnt[:, 0, 0], blocks[:, 0, 0],
            alive[:, 0, 0].astype(jnp.bool_))
