"""Force a CPU host to present N virtual devices (ISSUE 9, satellite 2).

Subprocess tests and examples call :func:`force_host_device_count`
*before* importing anything that touches a jax backend, then build a
real 2-D ``(block, cls)`` mesh over the virtual devices.

Import of this module itself is backend-safe: ``repro``/``repro.launch``
``__init__`` files import nothing, so

    from repro.launch.forcedevices import force_host_device_count
    force_host_device_count(8)
    import jax   # sees 8 CPU devices

works in a fresh interpreter.  Calling it after a backend initialised
raises, because the flag would silently not apply.
"""

from __future__ import annotations

import os
import sys

_FLAG = "--xla_force_host_platform_device_count"


def force_host_device_count(n: int) -> None:
    """Set ``XLA_FLAGS=--xla_force_host_platform_device_count=n``.

    Must run before jax initialises a backend (first ``jax.devices()`` /
    first trace); the count is locked at backend init.  Any existing
    ``XLA_FLAGS`` content is preserved, with a previous instance of this
    flag replaced.
    """
    if n < 1:
        raise ValueError(f"device count must be >= 1, got {n}")
    jax_mod = sys.modules.get("jax")
    if jax_mod is not None:
        # jax being imported is fine; an initialised backend is not.
        try:
            # populated lazily at first backend use; reading it does NOT
            # trigger initialisation (unlike jax.devices()).
            from jax._src import xla_bridge
            initialised = bool(xla_bridge._backends)
        except Exception:
            initialised = False
        if initialised:
            raise RuntimeError(
                "force_host_device_count called after jax backend init; "
                "the flag would not take effect")
    flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
             if not f.startswith(_FLAG + "=")]
    flags.append(f"{_FLAG}={int(n)}")
    os.environ["XLA_FLAGS"] = " ".join(flags)
