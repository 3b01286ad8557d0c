"""The mining mesh.

A function, not a module-level constant: importing this module never
touches jax device state (multi-device tests set
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` through
``repro.launch.forcedevices`` before any backend starts).

Mesh construction goes through :func:`repro.compat.make_mesh` (Auto
axis types on every axis).
"""

from __future__ import annotations

import jax

from repro.compat import make_mesh


def make_mining_mesh(*, block: int | None = None, cls: int = 1,
                     multihost: bool = False) -> jax.sharding.Mesh:
    """2-D ``(block, cls)`` mesh for distributed mining (ISSUE 9).

    ``block`` shards the TID-bitmap axis (partial counts psum over it);
    ``cls`` shards the candidate-pair axis of each dispatch chunk (no
    reduction crosses it).

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
