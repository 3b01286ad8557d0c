"""Pallas TPU kernel: allocator compaction gather (ISSUE 4).

Both device allocators (``core.rowstore.DeviceRowStore`` and
``core.rowstore.NListPool``) defragment by gathering their live rows /
extents to the front of a smaller slab.  The destination side is
contiguous, so the whole compaction is ONE gather indexed by a
host-built ``perm`` vector: ``out[i] = slab[perm[i]]`` (``perm[i] < 0``
means destination slot ``i`` comes up zeroed/free).

Grid/layout
-----------
grid = (new_capacity,) — one program per destination row.  ``perm`` is a
scalar-prefetch operand (``PrefetchScalarGridSpec``), so the input
BlockSpec's index_map can steer the DMA: program ``i`` pulls source row
``clip(perm[i], 0, cap-1)`` into VMEM and writes it to destination row
``i``, masking to zeros when ``perm[i] < 0``.  One row is
``slab.shape[1:]`` — ``(n_blocks, block_words)`` uint32 for bitmap rows,
``(n_shards*(nb_local+1),)`` int32 for suffix tables, ``(3,)`` int32 for
PPC-code triples (the last two viewed as ``(1, w)``) — small enough that
a row is always far under VMEM.

Semantics are defined by ``kernels/ref.py::compact_gather_ref`` and must
match it bit-for-bit (tests/test_kernels.py sweeps slab ranks, dtypes
and dead-slot patterns).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .spec import resolve_interpret


# Destinations per pallas_call: a 64K-entry int32 ``perm`` takes a
# quarter of SMEM, 256K would overflow it.
PERM_CHUNK = 1 << 16


def _kernel(perm_ref, slab_ref, out_ref):
    i = pl.program_id(0)
    live = perm_ref[i] >= 0
    blk = slab_ref[...]
    out_ref[...] = jnp.where(live, blk, jnp.zeros_like(blk))


@functools.partial(jax.jit, static_argnames=("interpret",))
def compact_gather(slab: jnp.ndarray, perm: jnp.ndarray, *,
                   interpret: "bool | None" = None) -> jnp.ndarray:
    """Pallas compaction gather: ``out[i] = slab[perm[i]]`` or zeros.

    ``slab`` is any (capacity, ...) device slab; ``perm int32
    (new_capacity,)`` maps destination to source rows (-1 = zero fill).
    ``interpret=None`` compiles for the TPU there and runs the Pallas
    interpreter on the CPU (``ops._pallas_interpret``).
    """
    interpret = resolve_interpret(interpret)
    cap = slab.shape[0]
    n_out = perm.shape[0]
    trailing = slab.shape[1:]
    # One row is the block; a row of rank < 2 (suffix tables, PPC-code
    # triples) gets a unit axis so its block's last two dims equal the
    # array's, as the TPU lowering requires.
    view = slab if len(trailing) >= 2 else slab.reshape(cap, 1, -1)
    row = view.shape[1:]
    zeros = (0,) * len(row)

    def in_map(i, perm_ref):
        return (jnp.clip(perm_ref[i], 0, cap - 1),) + zeros

    def out_map(i, perm_ref):
        del perm_ref
        return (i,) + zeros

    def gather(perm_part):
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(perm_part.shape[0],),
            in_specs=[pl.BlockSpec((1,) + row, in_map)],
            out_specs=pl.BlockSpec((1,) + row, out_map),
        )
        return pl.pallas_call(
            _kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((perm_part.shape[0],) + row,
                                           slab.dtype),
            interpret=interpret,
        )(perm_part, view)

    # The prefetched ``perm`` lives in SMEM (1 MiB): gather in pieces of
    # at most PERM_CHUNK destinations.
    perm = jnp.asarray(perm, jnp.int32)
    out = jnp.concatenate([gather(perm[lo:lo + PERM_CHUNK])
                           for lo in range(0, n_out, PERM_CHUNK)])
    return out.reshape((n_out,) + trailing)
