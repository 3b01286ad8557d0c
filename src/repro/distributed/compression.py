"""Gradient compression for the slow cross-pod hop.

int8 quantization with per-tensor scales and error feedback (1-bit Adam /
EF-SGD family).  Applied only to the reduction over the ``pod`` axis —
within a pod the ICI is fast enough that full-precision reduce-scatter is
the right call; across pods (DCN) an 8x shrink of the gradient payload is
worth the quantization noise, and the error-feedback buffer makes the
compression unbiased over time.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Tuple

import jax
import jax.numpy as jnp


def quantize_int8(x: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Symmetric per-tensor int8 quantization. Returns (q, scale)."""
    amax = jnp.max(jnp.abs(x)).astype(jnp.float32)
    scale = jnp.maximum(amax / 127.0, 1e-12)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127)
    return q.astype(jnp.int8), scale


def dequantize_int8(q: jnp.ndarray, scale: jnp.ndarray,
                    dtype=jnp.float32) -> jnp.ndarray:
    return (q.astype(jnp.float32) * scale).astype(dtype)


def compressed_psum_int8(x: jnp.ndarray, axis_name: str) -> jnp.ndarray:
    """psum with int8 payload: quantize -> psum(int32) -> dequant(mean scale).

    Usable inside shard_map over the pod axis.  The int32 accumulation of
    int8 payloads is exact; only the shared scale introduces error (each
    shard's scale is psum-averaged, standard practice)."""
    q, scale = quantize_int8(x)
    acc = jax.lax.psum(q.astype(jnp.int32), axis_name)
    scale_sum = jax.lax.psum(scale, axis_name)
    n = jax.lax.psum(jnp.ones((), jnp.float32), axis_name)
    return acc.astype(jnp.float32) * (scale_sum / n)


def compressed_crosspod_allreduce(tree, mesh, pod_axis: str = "pod"):
    """Mean-reduce a pytree across pods with int8 payloads.

    The within-pod reduction is assumed done (fast ICI, full precision);
    this is the slow DCN hop.  Per-leaf int8 quantization with psum'd
    scales — 4x (fp32) / 2x (bf16) payload shrink.  Pair with
    ``ErrorFeedback`` across steps to de-bias.

    Usage in a train step (multi-pod mesh): grads computed with batch
    sharded over ("pod","data") come out of value_and_grad already
    globally reduced by SPMD; to take ownership of the pod hop instead,
    constrain the loss's batch to "data" only and call this on the grads.
    """
    from functools import partial
    from repro.compat import shard_map
    from jax.sharding import PartitionSpec as P

    if pod_axis not in mesh.axis_names:
        return tree  # single-pod mesh: nothing to do

    def leaf(x):
        spec = P(*([None] * x.ndim))

        @partial(shard_map, mesh=mesh, in_specs=spec, out_specs=spec,
                 check_vma=False)
        def red(v):
            n = jax.lax.psum(jnp.ones((), jnp.float32), pod_axis)
            return compressed_psum_int8(v, pod_axis) / n

        return red(x)

    return jax.tree.map(leaf, tree)


@dataclass
class ErrorFeedback:
    """Error-feedback state: residual = x - dequant(quant(x)) carried into
    the next step so quantization error does not bias the optimizer."""

    @staticmethod
    def init(params) -> dict:
        return jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params)

    @staticmethod
    def apply(grads, residuals):
        """Returns (compressed_grads, new_residuals)."""
        def one(g, r):
            x = g.astype(jnp.float32) + r
            q, s = quantize_int8(x)
            deq = dequantize_int8(q, s)
            return deq.astype(g.dtype), x - deq

        flat = jax.tree.map(one, grads, residuals)
        comp = jax.tree.map(lambda t: t[0], flat,
                            is_leaf=lambda t: isinstance(t, tuple))
        res = jax.tree.map(lambda t: t[1], flat,
                           is_leaf=lambda t: isinstance(t, tuple))
        return comp, res
