"""The row store builds its slab on the device from the real rows.

``DeviceRowStore`` puts only its ``n`` real rows on the device and
zero-pads them to ``capacity`` rows there, in one module-level jitted
builder (``rowstore._padded_slab``).  The slab and its suffix table must
equal, bit for bit, what the former construction gave: a zero-padded
host slab of ``capacity`` rows uploaded whole (kept inline below as the
reference).  That holds on one device, on a one-device mesh and on an
8-device CPU mesh that pads the block axis.  The builder compiles once
per shape and holds no second slab-sized buffer.
"""

import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.compat import make_mesh
from repro.core.bitmap import suffix_popcounts
from repro.core.rowstore import (DeviceRowStore, _local_suffix_tables,
                                 _padded_slab, _round_capacity)

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"

# (n real rows, blocks, block words, requested capacity)
CASES = {
    "capacity_above_n": (5, 3, 4, 16),
    "no_rows": (0, 3, 4, 8),
    "capacity_from_n": (70, 5, 8, 0),
    "one_block_wide_slab": (3, 1, 128, 4096),
}


def _rows(n, nb, bw, seed=0):
    return np.random.default_rng(seed).integers(
        0, 2 ** 32, (n, nb, bw), dtype=np.uint64).astype(np.uint32)


def _host_built(rows_np, capacity, n_shards=None):
    """The former construction: a zero-padded host slab of ``capacity``
    rows (block axis padded to ``n_shards`` under a mesh), uploaded
    whole, with its suffix table computed over the whole slab."""
    n, nb, bw = rows_np.shape
    cap = _round_capacity(max(capacity, n, 1))
    if n_shards is not None:
        nb = -(-nb // n_shards) * n_shards
    slab = np.zeros((cap, nb, bw), np.uint32)
    slab[:n, :rows_np.shape[1]] = rows_np
    if n_shards is None:
        return slab, np.asarray(suffix_popcounts(jnp.asarray(slab)))
    return slab, _local_suffix_tables(slab, n_shards)


@pytest.mark.parametrize("on_mesh", [False, True], ids=["device", "mesh"])
@pytest.mark.parametrize("case", list(CASES))
def test_device_built_store_equals_host_built_slab(case, on_mesh):
    n, nb, bw, capacity = CASES[case]
    rows = _rows(n, nb, bw)
    mesh = make_mesh((1, 1), ("data", "model")) if on_mesh else None
    store = DeviceRowStore(rows, capacity=capacity, mesh=mesh)
    slab, suffix = _host_built(rows, capacity,
                               store.n_shards if on_mesh else None)
    assert store.rows.dtype == jnp.uint32 and store.suffix.dtype == jnp.int32
    assert np.array_equal(np.asarray(store.rows), slab)
    assert np.array_equal(np.asarray(store.suffix), suffix)
    if on_mesh:
        assert store.rows.sharding == store._rows_sharding
        assert store.suffix.sharding == store._suffix_sharding
    # Only the real rows (and, under a mesh, their suffix tables) cross.
    assert store.upload_bytes == rows.nbytes + (
        n * suffix.shape[1] * 4 if on_mesh else 0)
    assert store.upload_bytes < slab.nbytes
    assert store.n_live == n and store.capacity == slab.shape[0]


MESH_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys
    sys.path.insert(0, "src")
    sys.path.insert(0, "tests")
    import jax
    import numpy as np
    from repro.compat import make_mesh
    from repro.core.rowstore import DeviceRowStore
    from test_rowstore_build import CASES, _host_built, _rows

    assert jax.device_count() == 8
    mesh = make_mesh((4, 2), ("data", "model"))
    for name, (n, nb, bw, capacity) in CASES.items():
        rows = _rows(n, nb, bw)
        store = DeviceRowStore(rows, capacity=capacity, mesh=mesh)
        assert store.n_shards == 8 and store.n_blocks % 8 == 0, name
        slab, suffix = _host_built(rows, capacity, 8)
        assert np.array_equal(np.asarray(store.rows), slab), name
        assert np.array_equal(np.asarray(store.suffix), suffix), name
        assert store.rows.sharding == store._rows_sharding, name
        assert store.suffix.sharding == store._suffix_sharding, name
        # real rows with the block pad, and their per-shard suffixes
        assert store.upload_bytes == (n * store.n_blocks * bw
                                      + n * suffix.shape[1]) * 4, name
    print("MESH_STORE_OK")
""")


def test_device_built_store_equals_host_built_slab_on_8_device_mesh():
    proc = subprocess.run([sys.executable, "-c", MESH_SCRIPT],
                          capture_output=True, text=True, timeout=600,
                          cwd=".")
    assert "MESH_STORE_OK" in proc.stdout, proc.stderr[-3000:]


def test_second_store_of_a_shape_compiles_nothing():
    rows = _rows(7, 3, 5)
    compiles = []

    def listener(event, _secs, **_kw):
        if event == BACKEND_COMPILE:
            compiles.append(event)

    jax.monitoring.register_event_duration_secs_listener(listener)
    try:
        first = DeviceRowStore(rows, capacity=64)
        jax.block_until_ready(first.rows)
        n_first = len(compiles)
        second = DeviceRowStore(rows ^ np.uint32(1), capacity=64)
        jax.block_until_ready(second.rows)
    finally:
        jax.monitoring.unregister_event_duration_listener(listener)
    assert n_first >= 1          # the listener sees the builder compile
    assert len(compiles) == n_first


def test_builder_holds_no_second_slab():
    n, nb, bw, cap = 3, 16, 128, 1024
    compiled = _padded_slab.lower(
        jax.ShapeDtypeStruct((n, nb, bw), jnp.uint32), None,
        cap=cap).compile()
    mem = compiled.memory_analysis()
    slab_bytes = cap * nb * bw * 4
    assert mem.output_size_in_bytes >= slab_bytes
    assert mem.temp_size_in_bytes < slab_bytes // 64
