"""Runtime device-purity enforcement (ISSUE 10).

Static rule DL001 (``tools/devicelint``) proves the *source* contains
no unannotated host-sync sites; this module is the *runtime* half of
the same contract:

* :func:`device_purity_guard` wraps a region (``FrontierScheduler.run``
  and the equivalence harness use it) in JAX's device->host transfer
  guard set to ``"disallow"`` — any readback not routed through
  :func:`host_sync` raises instead of silently stalling the dispatch
  pipeline.
* :func:`host_sync` is the narrow escape, placed at exactly the
  ``# host-sync:``-annotated sites, so the static rule and the runtime
  guard certify each other: devicelint fails if an escape loses its
  annotation, and the guard fires if a sync appears outside one.

Backend caveat (measured, not assumed): on the **CPU** backend JAX
device buffers alias host memory, so device->host "transfers" are
zero-copy and the guard never fires — there DL001 is the only
enforcement with teeth.  On TPU/GPU the guard is real: an unannotated
``np.asarray(device_value)`` inside a guarded region raises
``XlaRuntimeError``.  We deliberately do NOT disallow host->device
transfers: streaming host operand columns into fused dispatches is the
designed data flow (h2d is async and never stalls the pipeline).

Only the device->host direction is guarded; ``jax.transfer_guard`` (all
directions) would flag benign implicit h2d of python scalar constants
in eager ops.
"""

from __future__ import annotations

import contextlib

import jax

__all__ = ["device_purity_guard", "host_sync", "purity_guard_active"]

# Nesting depth of active disallow regions — lets tests (and the
# equivalence harness) assert the guard is actually armed, which is the
# CPU-backend-visible part of the contract.
_DEPTH = 0


def purity_guard_active() -> bool:
    """True while inside a :func:`device_purity_guard` region and not
    inside a :func:`host_sync` escape."""
    return _DEPTH > 0


@contextlib.contextmanager
def device_purity_guard():
    """Disallow unannotated device->host transfers in this region."""
    global _DEPTH
    _DEPTH += 1
    try:
        with jax.transfer_guard_device_to_host("disallow"):
            yield
    finally:
        _DEPTH -= 1


@contextlib.contextmanager
def host_sync(why: str):
    """Sanctioned host-sync escape — pair with a ``# host-sync:``
    annotation carrying the same justification."""
    assert why, "host_sync requires a non-empty justification"
    global _DEPTH
    saved, _DEPTH = _DEPTH, 0
    try:
        with jax.transfer_guard_device_to_host("allow"):
            yield
    finally:
        _DEPTH = saved
