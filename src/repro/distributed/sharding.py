"""Logical-axis sharding rules (MaxText-style) for the whole framework.

Model code never names mesh axes directly.  It tags tensors and params
with *logical* axis names ("batch", "heads", "embed", ...), and a rules
table maps logical names to mesh axes.  Swapping a rules table re-shards
the entire model — that is the knob the perf hillclimb turns.

Rules resolve lazily against the mesh that is current at trace time, so
the same model code lowers for the single-pod (data, model) mesh and the
multi-pod (pod, data, model) mesh without edits.
"""

from __future__ import annotations

import contextlib
import threading
from typing import Dict, Optional, Sequence, Tuple, Union

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


AxisName = Optional[Union[str, Tuple[str, ...]]]
AxisRules = Dict[str, AxisName]

# The baseline rules table.  "batch" resolves to every data-parallel axis
# present on the mesh; tensor-parallel dimensions resolve to "model".
DEFAULT_RULES: AxisRules = {
    # activations
    "batch": ("pod", "data"),
    "seq": None,
    "act_embed": None,          # activations' hidden dim stays replicated
    "act_heads": "model",
    "act_kv_heads": None,       # kv heads often < model size; replicate
    "act_ff": "model",
    "experts_act": "model",     # (E, C, D) expert buffers: E over model
    "vocab_act": "model",       # logits (B, S, V): V over model
    "kv_seq": None,
    # params — transformer
    "vocab": "model",
    "embed": None,
    "heads": "model",
    "kv_heads": "model",
    "head_dim": None,
    "ff": "model",              # MLP hidden (column parallel in, row out)
    "experts": "model",         # expert parallelism
    "expert_ff": None,
    "lora": None,               # MLA latent dims stay replicated
    # gnn
    "nodes": ("pod", "data"),
    "edges": ("pod", "data"),
    "feat": None,
    "hidden": "model",
    # recsys
    "table_rows": "model",      # huge embedding tables: row sharded
    "table_dim": None,
    "candidates": ("pod", "data"),
    "fields": None,
    # mining
    "tid_blocks": ("pod", "data"),
    "pairs": "model",
    # optimizer state (ZeRO): shard the largest param axis over data
    "zero": ("data",),
}

# Multi-pod override example: keep TP within a pod, push batch across pods.
MULTI_POD_RULES: AxisRules = dict(DEFAULT_RULES)

_STATE = threading.local()


def current_rules() -> AxisRules:
    return getattr(_STATE, "rules", DEFAULT_RULES)


@contextlib.contextmanager
def use_rules(rules: AxisRules):
    """Temporarily install a rules table (hillclimb / per-arch overrides).

    ``rules`` entries update a copy of the current table, so callers only
    specify the names they want to change."""
    prev = current_rules()
    merged = dict(prev)
    merged.update(rules)
    _STATE.rules = merged
    try:
        yield merged
    finally:
        _STATE.rules = prev


def _mesh_axes(mesh: Optional[Mesh]) -> Tuple[str, ...]:
    if mesh is not None:
        return tuple(mesh.axis_names)
    env = jax.sharding.get_abstract_mesh()
    if env.axis_names:
        return tuple(env.axis_names)
    return ()


def logical_spec(logical: Sequence[Optional[str]],
                 mesh: Optional[Mesh] = None,
                 rules: Optional[AxisRules] = None) -> P:
    """Map a tuple of logical axis names to a PartitionSpec for ``mesh``.

    Logical names missing from the rules table resolve to None
    (replicated).  Mesh axes that do not exist on the current mesh are
    silently dropped (e.g. "pod" on the single-pod mesh), and a mesh axis
    may be used by at most one tensor dimension (first wins)."""
    rules = rules or current_rules()
    avail = set(_mesh_axes(mesh))
    used: set = set()
    out = []
    for name in logical:
        axis = rules.get(name) if name is not None else None
        if axis is None:
            out.append(None)
            continue
        axes = (axis,) if isinstance(axis, str) else tuple(axis)
        axes = tuple(a for a in axes if a in avail and a not in used)
        used.update(axes)
        if not axes:
            out.append(None)
        elif len(axes) == 1:
            out.append(axes[0])
        else:
            out.append(axes)
    return P(*out)


def constrain(x: jax.Array, logical: Sequence[Optional[str]],
              mesh: Optional[Mesh] = None) -> jax.Array:
    """with_sharding_constraint by logical names (no-op without a mesh)."""
    mesh = mesh or _current_concrete_mesh()
    if mesh is None:
        return x
    spec = logical_spec(logical, mesh)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


_ACTIVE_MESH: threading.local = threading.local()


def _current_concrete_mesh() -> Optional[Mesh]:
    return getattr(_ACTIVE_MESH, "mesh", None)


@contextlib.contextmanager
def active_mesh(mesh: Optional[Mesh]):
    """Install the mesh used by ``constrain`` inside model code."""
    prev = _current_concrete_mesh()
    _ACTIVE_MESH.mesh = mesh
    try:
        yield mesh
    finally:
        _ACTIVE_MESH.mesh = prev


def make_param_shardings(mesh: Mesh, logical_tree,
                         rules: Optional[AxisRules] = None):
    """Map a pytree of logical-axis tuples to NamedShardings on ``mesh``."""
    return jax.tree.map(
        lambda names: NamedSharding(mesh, logical_spec(names, mesh, rules)),
        logical_tree,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            n is None or isinstance(n, str) for n in x),
    )


def shard_like(tree, shardings):
    """device_put a pytree according to a parallel tree of shardings."""
    return jax.tree.map(jax.device_put, tree, shardings)


def divisibility_report(shape: Tuple[int, ...], spec: P, mesh: Mesh):
    """Human-readable check that a shape divides its spec on the mesh."""
    problems = []
    # A PartitionSpec may omit trailing (unsharded) dims, so the spec is
    # allowed to be shorter than the shape.
    for dim, axis in zip(shape, spec, strict=False):
        if axis is None:
            continue
        axes = (axis,) if isinstance(axis, str) else axis
        total = int(np.prod([mesh.shape[a] for a in axes]))
        if dim % total:
            problems.append(f"dim {dim} % mesh{axes}={total} != 0")
    return problems
