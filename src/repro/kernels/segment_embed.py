"""Pallas TPU kernel: EmbeddingBag (gather + masked segment reduce).

JAX has no native EmbeddingBag; the jnp path (models/recsys.embedding_bag)
materialises the gathered (B, L, D) tensor in HBM before reducing.  This
kernel fuses gather+reduce: each program owns a bag tile, gathers rows
from the (VMEM-resident shard of the) table with dynamic slices and
accumulates in VMEM — the (B, L, D) intermediate never exists.

Grid = (B // bag_block,); combiners: sum / mean.

Validated in interpret mode against kernels/ref.py::embedding_bag_ref.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .spec import resolve_interpret

DEFAULT_BAG_BLOCK = 8


def _kernel(combiner: str, ids_ref, mask_ref, table_ref, o_ref):
    """ids/mask: (bb, L) SMEM; table: (V, D) VMEM(whole); o: (bb, D)."""
    bb, L = ids_ref.shape
    D = table_ref.shape[1]

    def bag(i, _):
        def slot(j, carry):
            acc, cnt = carry
            idx = ids_ref[i, j]
            valid = mask_ref[i, j]
            row = table_ref[idx, :].astype(jnp.float32)
            acc = acc + jnp.where(valid != 0, row, 0.0)
            cnt = cnt + jnp.where(valid != 0, 1.0, 0.0)
            return acc, cnt

        acc, cnt = jax.lax.fori_loop(
            0, L, slot, (jnp.zeros((D,), jnp.float32), jnp.float32(0)))
        if combiner == "mean":
            acc = acc / jnp.maximum(cnt, 1.0)
        o_ref[i, :] = acc.astype(o_ref.dtype)
        return ()

    jax.lax.fori_loop(0, bb, bag, ())


@functools.partial(jax.jit, static_argnames=("combiner", "bag_block",
                                             "interpret"))
def embedding_bag(table: jnp.ndarray,        # (V, D)
                  ids: jnp.ndarray,          # (B, L) int32
                  mask: jnp.ndarray,         # (B, L) int32/bool
                  *, combiner: str = "mean",
                  bag_block: int = DEFAULT_BAG_BLOCK,
                  interpret: "bool | None" = None) -> jnp.ndarray:
    interpret = resolve_interpret(interpret)
    if combiner not in ("sum", "mean"):
        raise ValueError(combiner)
    B, L = ids.shape
    V, D = table.shape
    bag_block = min(bag_block, B)
    assert B % bag_block == 0

    kernel = functools.partial(_kernel, combiner)
    return pl.pallas_call(
        kernel,
        grid=(B // bag_block,),
        in_specs=[
            pl.BlockSpec((bag_block, L), lambda i: (i, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((bag_block, L), lambda i: (i, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((V, D), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bag_block, D), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, D), table.dtype),
        interpret=interpret,
    )(ids.astype(jnp.int32), mask.astype(jnp.int32), table)
