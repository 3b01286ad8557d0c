"""Block specs and platform plumbing shared by the Pallas kernels.

The TPU lowering requires the last two dims of every block to be
divisible by (8, 128) or equal to the array's.  The mining kernels run
one grid program per candidate pair, so a per-pair table ``(n_pairs, w)``
would need a ``(1, w)`` block, which is illegal; viewed as
``(n_pairs, 1, w)`` its one-pair block ``(1, 1, w)`` is legal.  The
helpers below build those views and specs.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


# Scalar memory per TensorCore on v5e (the compiler's limit: "Used 1.00M
# of 1.00M smem").  Scalar-prefetch vectors and SMEM blocks share it.
SMEM_BYTES = 1 << 20


def pair_spec(shape, memory_space=None, *, buffers: int = 2):
    """BlockSpec of one pair's slice ``(1,) + shape`` of a per-pair array
    (grid axis 0 = pair index).  ``buffers=1`` turns off the pipeline's
    double buffering, halving the block's footprint."""
    zeros = (0,) * len(shape)
    kw = {} if memory_space is None else {"memory_space": memory_space}
    if buffers != 2:
        kw["pipeline_mode"] = pl.Buffered(buffers)
    return pl.BlockSpec((1,) + tuple(shape), lambda p, *_: (p,) + zeros,
                        **kw)


def smem_table(x: jnp.ndarray) -> jnp.ndarray:
    """``(n_pairs, w)`` int32 -> ``(n_pairs, 1, w)``: a unit middle axis
    makes a one-pair block ``(1, 1, w)`` legal on the TPU (its last two
    dims equal the array's)."""
    x = jnp.asarray(x, jnp.int32)
    return x.reshape(x.shape[0], 1, -1)


def smem_out(n_pairs: int):
    """Shape of a per-pair int32 scalar output (read back as ``[:, 0, 0]``)."""
    return jax.ShapeDtypeStruct((n_pairs, 1, 1), jnp.int32)


def resolve_interpret(interpret: "bool | None") -> bool:
    """``None`` -> the platform's mode (``ops._pallas_interpret``)."""
    if interpret is None:
        from .ops import _pallas_interpret
        return _pallas_interpret()
    return interpret
