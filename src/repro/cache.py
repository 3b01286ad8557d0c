"""JAX's persistent compilation cache, placed by the entry points.

A fresh process on the chip compiles every kernel and dispatch again;
the persistent cache lets a later process (or a later run of the same
command) load them instead.  The cache's key includes its directory, so
the directory is fixed: ``JAX_COMPILATION_CACHE_DIR`` when the caller
sets it (JAX reads that variable itself, and nothing here overrides
it), else ``<checkout>/.jax_cache``.  Never a temp, pid- or time-derived
path.

Entry points (``chip_smoke.py``, ``repro-mine``, ``bench_paper.py``)
call :func:`configure_compile_cache` once, before their first compile;
importing the library configures nothing.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def configure_compile_cache() -> str:
    """Turn the persistent cache on and return its directory."""
    path = os.environ.get(ENV)
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    # Cache every program: the mining kernels compile in about a second,
    # under JAX's default one-second floor for persisting an entry.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
