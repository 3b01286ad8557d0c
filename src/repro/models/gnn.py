"""GraphSAGE (Hamilton et al., arXiv:1706.02216) in pure JAX.

Message passing is built on ``jax.ops.segment_sum`` over an edge index —
JAX has no CSR SpMM, so the scatter/segment formulation IS the system
(kernel_taxonomy §GNN).  Two execution modes cover the four assigned
shapes:

  * full-batch (``full_graph_sm``, ``ogb_products``, ``molecule``):
    the whole edge list is aggregated per layer; nodes/edges shard over
    the (pod, data) mesh axes, features over "model".
  * sampled minibatch (``minibatch_lg``): the uniform fanout sampler in
    ``repro.data.graph_data`` materialises dense neighbor blocks
    (B, f2, f1, F) and aggregation is plain masked means — the
    GraphSAGE-paper training regime for Reddit-scale graphs.

Aggregator: mean (the assigned config).  Layer rule (paper Alg. 1):
    h_v^k = relu(W_k . concat(h_v^{k-1}, mean_{u in N(v)} h_u^{k-1}))
followed by L2 normalisation.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.distributed.sharding import constrain

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class SAGEConfig:
    name: str
    n_layers: int = 2
    d_hidden: int = 128
    d_feat: int = 602
    n_classes: int = 41
    aggregator: str = "mean"
    fanouts: Tuple[int, ...] = (25, 10)   # layer-1, layer-2 sample sizes
    dtype: str = "float32"
    l2_normalize: bool = True

    @property
    def param_dtype(self):
        return jnp.dtype(self.dtype)


def init_params(rng, cfg: SAGEConfig):
    dt = cfg.param_dtype
    params: Params = {"layers": []}
    logical: Params = {"layers": []}
    d_in = cfg.d_feat
    rngs = jax.random.split(rng, cfg.n_layers + 1)
    for i in range(cfg.n_layers):
        d_out = cfg.d_hidden
        s = 1.0 / (d_in ** 0.5)
        k = jax.random.split(rngs[i], 2)
        params["layers"].append({
            "w_self": (jax.random.normal(k[0], (d_in, d_out), jnp.float32)
                       * s).astype(dt),
            "w_neigh": (jax.random.normal(k[1], (d_in, d_out), jnp.float32)
                        * s).astype(dt),
            "bias": jnp.zeros((d_out,), dt),
        })
        logical["layers"].append({
            "w_self": ("feat", "hidden"),
            "w_neigh": ("feat", "hidden"),
            "bias": ("hidden",),
        })
        d_in = d_out
    s = 1.0 / (d_in ** 0.5)
    params["head"] = {
        "w": (jax.random.normal(rngs[-1], (d_in, cfg.n_classes), jnp.float32)
              * s).astype(dt),
        "bias": jnp.zeros((cfg.n_classes,), dt),
    }
    logical["head"] = {"w": ("hidden", None), "bias": (None,)}
    return params, logical


def _sage_combine(lp: Params, h_self: jnp.ndarray, h_neigh: jnp.ndarray,
                  cfg: SAGEConfig, last: bool) -> jnp.ndarray:
    y = (h_self @ lp["w_self"] + h_neigh @ lp["w_neigh"] + lp["bias"])
    if not last:
        y = jax.nn.relu(y)
    if cfg.l2_normalize:
        y = y / jnp.maximum(
            jnp.linalg.norm(y.astype(jnp.float32), axis=-1, keepdims=True),
            1e-12).astype(y.dtype)
    return y


# ---------------------------------------------------------------------------
# full-batch forward: segment_sum over the global edge list
# ---------------------------------------------------------------------------

def forward_full(params: Params, cfg: SAGEConfig, x: jnp.ndarray,
                 edge_src: jnp.ndarray, edge_dst: jnp.ndarray,
                 ) -> jnp.ndarray:
    """x (N, F); edge arrays (E,) int32 (src -> dst messages).

    Mean aggregation = segment_sum(messages) / segment_sum(1).  Self loops
    are NOT assumed; isolated nodes see a zero neighbor vector."""
    n = x.shape[0]
    h = x.astype(cfg.param_dtype)   # bf16 configs halve gather/collective
    deg = jax.ops.segment_sum(jnp.ones_like(edge_src, jnp.float32),
                              edge_dst, num_segments=n)
    inv_deg = (1.0 / jnp.maximum(deg, 1.0)).astype(h.dtype)
    for li, lp in enumerate(params["layers"]):
        msgs = h[edge_src]
        agg = jax.ops.segment_sum(msgs, edge_dst, num_segments=n)
        agg = agg * inv_deg[:, None]
        h = _sage_combine(lp, h, agg, cfg,
                          last=(li == cfg.n_layers - 1))
        h = constrain(h, ("nodes", "hidden"))
    return h @ params["head"]["w"] + params["head"]["bias"]


# ---------------------------------------------------------------------------
# sampled minibatch forward: dense fanout blocks
# ---------------------------------------------------------------------------

def forward_sampled(params: Params, cfg: SAGEConfig,
                    feats: Tuple[jnp.ndarray, ...],
                    masks: Optional[Tuple[jnp.ndarray, ...]] = None,
                    ) -> jnp.ndarray:
    """2-layer sampled forward (GraphSAGE minibatch regime).

    feats = (x_root (B,F), x_hop1 (B,f1,F), x_hop2 (B,f1,f2,F)) where f1 is
    the root fanout and f2 the second-hop fanout.  ``masks`` marks real
    (non-padded) samples.  Aggregation collapses hop2 -> hop1 -> root."""
    assert cfg.n_layers == 2, "sampled path implements the assigned 2-layer net"
    x_root, x_h1, x_h2 = feats
    if masks is None:
        m1 = jnp.ones(x_h1.shape[:-1], x_root.dtype)
        m2 = jnp.ones(x_h2.shape[:-1], x_root.dtype)
    else:
        m1, m2 = (m.astype(x_root.dtype) for m in masks)

    lp1, lp2 = params["layers"]

    def mean_agg(xs, mask):  # (..., k, F), (..., k)
        s = (xs * mask[..., None]).sum(-2)
        d = jnp.maximum(mask.sum(-1, keepdims=True), 1.0)
        return s / d

    # layer 1 applied at depth-1 nodes (and root) using depth-2 neighbors
    agg2 = mean_agg(x_h2, m2)                      # (B, f1, F)
    h1 = _sage_combine(lp1, x_h1, agg2, cfg, last=False)   # (B, f1, H)
    agg1_root = mean_agg(x_h1, m1)                 # (B, F)
    h_root = _sage_combine(lp1, x_root, agg1_root, cfg, last=False)

    # layer 2 at root using depth-1 hidden states
    agg1 = mean_agg(h1, m1)                        # (B, H)
    h = _sage_combine(lp2, h_root, agg1, cfg, last=True)
    h = constrain(h, ("nodes", "hidden"))
    return h @ params["head"]["w"] + params["head"]["bias"]


# ---------------------------------------------------------------------------
# locality-partitioned full-batch forward (hillclimb variant)
# ---------------------------------------------------------------------------
#
# The GSPMD segment_sum over globally-sharded edges all-reduces the FULL
# node array per layer (the scatter-add cannot prove locality).  Real
# distributed GNN systems partition edges by destination shard and shard
# features, making aggregation shard-local:
#
#   * edges are pre-partitioned so shard s holds exactly the edges whose
#     dst lies in its node range (a data-pipeline invariant — the host
#     sorts edges once);
#   * node features are sharded (nodes x features) over (data x model);
#   * per layer: all-gather x over the NODE axis moves (N, F/16) per chip
#     (vs all-reducing (N, H) full); the W contraction over the sharded
#     feature axis psums a small (N_local, H) block.
#
# Exposed as a shard_map program builder; differentiable (psum transposes
# to psum), so the full train step works through it.

def make_sharded_loss(mesh, cfg: SAGEConfig, n_nodes: int, f_pad: int,
                      node_axes=("data",), feat_axis: str = "model"):
    import functools
    from jax.sharding import PartitionSpec as P
    from repro.compat import shard_map

    node_spec = node_axes if len(node_axes) > 1 else node_axes[0]
    h_dim = cfg.d_hidden

    def _layer(lp, x_local, x_feat_local, edge_src, edge_dst_local,
               inv_deg, n_local, last):
        # all-gather over the node axis: (N, F_local) everywhere
        xg = jax.lax.all_gather(x_feat_local, node_axes, axis=0,
                                tiled=True)
        msgs = xg[edge_src]                          # (E_local, F_local)
        agg = jax.ops.segment_sum(msgs, edge_dst_local,
                                  num_segments=n_local)
        agg = agg * inv_deg[:, None]
        # contraction over the sharded feature axis -> psum
        y = (x_local @ lp["w_self"] + agg @ lp["w_neigh"])
        y = jax.lax.psum(y, feat_axis) + lp["bias"]
        if not last:
            y = jax.nn.relu(y)
        if cfg.l2_normalize:
            y = y / jnp.maximum(jnp.linalg.norm(
                y.astype(jnp.float32), axis=-1, keepdims=True),
                1e-12).astype(y.dtype)
        return y                                     # (N_local, H) full H

    def _feat_slice(h, width):
        r = jax.lax.axis_index(feat_axis)
        return jax.lax.dynamic_slice_in_dim(h, r * width, width, axis=1)

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(P(), P(node_spec, feat_axis), P(node_spec), P(node_spec),
                  P(node_spec), P(node_spec)),
        out_specs=P(), check_vma=False)
    def loss_fn(params, x, edge_src, edge_dst_local, labels, mask):
        n_local = x.shape[0]
        deg = jax.ops.segment_sum(
            jnp.ones_like(edge_dst_local, jnp.float32), edge_dst_local,
            num_segments=n_local)
        inv_deg = (1.0 / jnp.maximum(deg, 1.0)).astype(x.dtype)

        # layer 1: params sliced to this shard's feature range
        f_local = x.shape[1]
        r = jax.lax.axis_index(feat_axis)
        lp1 = params["layers"][0]
        lp1 = {"w_self": jax.lax.dynamic_slice_in_dim(
                   lp1["w_self"], r * f_local, f_local, 0),
               "w_neigh": jax.lax.dynamic_slice_in_dim(
                   lp1["w_neigh"], r * f_local, f_local, 0),
               "bias": lp1["bias"]}
        h = _layer(lp1, x, x, edge_src, edge_dst_local, inv_deg,
                   n_local, last=False)              # (N_local, H)

        h_width = h_dim // _axis_size(mesh, feat_axis)
        hf = _feat_slice(h, h_width)
        lp2 = params["layers"][1]
        lp2 = {"w_self": jax.lax.dynamic_slice_in_dim(
                   lp2["w_self"], r * h_width, h_width, 0),
               "w_neigh": jax.lax.dynamic_slice_in_dim(
                   lp2["w_neigh"], r * h_width, h_width, 0),
               "bias": lp2["bias"]}
        h2 = _layer(lp2, hf, hf, edge_src, edge_dst_local, inv_deg,
                    n_local, last=True)
        logits = h2 @ params["head"]["w"] + params["head"]["bias"]

        logp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
        lp_tok = jnp.take_along_axis(logp, labels[..., None], -1)[..., 0]
        m = mask.astype(jnp.float32)
        loss_sum = jax.lax.psum(-(lp_tok * m).sum(), node_axes)
        n = jax.lax.psum(m.sum(), node_axes)
        return loss_sum / jnp.maximum(n, 1.0)

    return loss_fn


def _axis_size(mesh, name: str) -> int:
    return mesh.shape[name]


def loss_full(params, cfg: SAGEConfig, x, edge_src, edge_dst, labels,
              label_mask) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    logits = forward_full(params, cfg, x, edge_src, edge_dst)
    return _masked_ce(logits, labels, label_mask)


def loss_sampled(params, cfg: SAGEConfig, feats, masks, labels,
                 ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    logits = forward_sampled(params, cfg, feats, masks)
    return _masked_ce(logits, labels, jnp.ones_like(labels, jnp.bool_))


def _masked_ce(logits, labels, mask):
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    lp = jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    mask = mask.astype(jnp.float32)
    loss = -(lp * mask).sum() / jnp.maximum(mask.sum(), 1.0)
    acc = ((logits.argmax(-1) == labels) * mask).sum() / jnp.maximum(
        mask.sum(), 1.0)
    return loss, {"ce": loss, "acc": acc}
