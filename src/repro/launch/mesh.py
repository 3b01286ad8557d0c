"""Production meshes.

Single pod: (data=16, model=16) — 256 chips (one v5e pod).
Multi-pod:  (pod=2, data=16, model=16) — 512 chips across 2 pods.

Functions, not module-level constants: importing this module never
touches jax device state (the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import; tests and benches see the real single CPU device).

Mesh construction goes through :func:`repro.compat.make_mesh` (Auto
axis types on every axis).
"""

from __future__ import annotations

import jax

from repro.compat import make_mesh


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(shape=(1, 1), axes=("data", "model")) -> jax.sharding.Mesh:
    """Tiny mesh over whatever devices exist (tests / single-host runs)."""
    return make_mesh(shape, axes)


def make_mining_mesh(*, block: int | None = None, cls: int = 1,
                     multihost: bool = False) -> jax.sharding.Mesh:
    """2-D ``(block, cls)`` mesh for distributed mining (ISSUE 9).

    ``block`` shards the TID-bitmap axis (partial counts psum over it);
    ``cls`` shards the candidate-pair axis of each dispatch chunk (no
    reduction crosses it).  Train scaffolding keeps its ``(data, model)``
    helpers above — mining paths must not reuse those axis names.

    ``block=None`` takes every device not consumed by ``cls``.  With
    ``multihost=True`` the jax.distributed bootstrap runs first (no-op
    off-cluster), so ``jax.device_count()`` spans the whole slice.
    """
    if multihost:
        from repro.launch.multihost import init_distributed
        init_distributed()
    if cls < 1 or jax.device_count() % cls:
        raise ValueError(
            f"cls={cls} must divide device count {jax.device_count()}")
    if block is None:
        block = jax.device_count() // cls
    return make_mesh((block, cls), ("block", "cls"))
