"""Pallas TPU kernel: fused causal flash attention (GQA-aware).

The XLA-everywhere path (models/layers.chunked_attention) already avoids
materialising the score matrix via a lax.scan; this kernel is the TPU
hot path that additionally keeps the whole online-softmax state in VMEM
and tiles q/k/v for the MXU (128-aligned BlockSpecs).

Grid = (batch, kv_head, q_blocks); each program owns one q tile of one
(batch, kv-head-group) and walks the KV blocks with a fori_loop, carrying
(m, l, acc) in VMEM scratch.  Causality skips fully-masked KV blocks via
``pl.when`` (the causal analogue of the paper's "don't do provably
useless work").

Validated in interpret mode against kernels/ref.py::flash_attention_ref
(shape/dtype sweeps in tests/test_kernels.py).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .spec import resolve_interpret

DEFAULT_Q_BLOCK = 128
DEFAULT_KV_BLOCK = 128
NEG_INF = -1e30


def _kernel(causal: bool, scale: float, kv_len: int, kv_block: int,
            q_ref, k_ref, v_ref, o_ref, m_sc, l_sc, acc_sc):
    """One q tile (1, 1, bq, G, Dh) vs all KV blocks of one kv head.

    q_ref: (1, 1, bq, G, D)   — G = query heads per kv head
    k_ref: (1, 1, Skv, D)
    v_ref: (1, 1, Skv, Dv)
    o_ref: (1, 1, bq, G, Dv)
    scratch: m/l (bq, G), acc (bq, G, Dv) — fp32
    """
    bq = q_ref.shape[2]
    G = q_ref.shape[3]
    Dv = v_ref.shape[3]
    qi = pl.program_id(2)
    q_start = qi * bq

    m_sc[...] = jnp.full((bq, G), NEG_INF, jnp.float32)
    l_sc[...] = jnp.zeros((bq, G), jnp.float32)
    acc_sc[...] = jnp.zeros((bq, G, Dv), jnp.float32)

    q = q_ref[0, 0].astype(jnp.float32) * scale          # (bq, G, D)
    n_kv = kv_len // kv_block

    def body(j, _):
        kv_start = j * kv_block

        @pl.when(jnp.logical_or(not causal,
                                kv_start <= q_start + bq - 1))
        def process():
            k = k_ref[0, 0, pl.ds(kv_start, kv_block)].astype(jnp.float32)
            v = v_ref[0, 0, pl.ds(kv_start, kv_block)].astype(jnp.float32)
            s = jnp.einsum("qgd,kd->qgk", q, k,
                           preferred_element_type=jnp.float32)
            if causal:
                q_pos = q_start + jax.lax.broadcasted_iota(
                    jnp.int32, (bq, G, kv_block), 0)
                kv_pos = kv_start + jax.lax.broadcasted_iota(
                    jnp.int32, (bq, G, kv_block), 2)
                s = jnp.where(q_pos >= kv_pos, s, NEG_INF)
            m_prev = m_sc[...]
            m_new = jnp.maximum(m_prev, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m_prev - m_new)
            l_sc[...] = l_sc[...] * corr + p.sum(axis=-1)
            acc_sc[...] = (acc_sc[...] * corr[..., None]
                           + jnp.einsum("qgk,kv->qgv", p, v,
                                        preferred_element_type=jnp.float32))
            m_sc[...] = m_new

        return ()

    jax.lax.fori_loop(0, n_kv, body, ())
    out = acc_sc[...] / jnp.maximum(l_sc[...][..., None], 1e-30)
    o_ref[0, 0] = out.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "q_block",
                                             "kv_block", "interpret"))
def flash_attention(
    q: jnp.ndarray,          # (B, Sq, H, D)
    k: jnp.ndarray,          # (B, Skv, KH, D)
    v: jnp.ndarray,          # (B, Skv, KH, Dv)
    *,
    causal: bool = True,
    softmax_scale: Optional[float] = None,
    q_block: int = DEFAULT_Q_BLOCK,
    kv_block: int = DEFAULT_KV_BLOCK,
    interpret: "bool | None" = None,
) -> jnp.ndarray:
    interpret = resolve_interpret(interpret)
    B, Sq, H, D = q.shape
    _, Skv, KH, Dv = v.shape
    assert H % KH == 0
    G = H // KH
    scale = softmax_scale if softmax_scale is not None else D ** -0.5
    q_block = min(q_block, Sq)
    kv_block = min(kv_block, Skv)
    assert Sq % q_block == 0 and Skv % kv_block == 0

    qg = q.reshape(B, Sq, KH, G, D).transpose(0, 2, 1, 3, 4)  # B,KH,Sq,G,D
    kt = k.transpose(0, 2, 1, 3)                              # B,KH,Skv,D
    vt = v.transpose(0, 2, 1, 3)

    kernel = functools.partial(_kernel, causal, scale, Skv, kv_block)
    out = pl.pallas_call(
        kernel,
        grid=(B, KH, Sq // q_block),
        in_specs=[
            pl.BlockSpec((1, 1, q_block, G, D),
                         lambda b, h, i: (b, h, i, 0, 0)),
            pl.BlockSpec((1, 1, Skv, D), lambda b, h, i: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, Skv, Dv), lambda b, h, i: (b, h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, q_block, G, Dv),
                               lambda b, h, i: (b, h, i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, KH, Sq // q_block * q_block,
                                        G, Dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((q_block, G), jnp.float32),
            pltpu.VMEM((q_block, G), jnp.float32),
            pltpu.VMEM((q_block, G, Dv), jnp.float32),
        ],
        interpret=interpret,
    )(qg, kt, vt)
    return out.transpose(0, 2, 1, 3, 4).reshape(B, Sq, H, Dv)
