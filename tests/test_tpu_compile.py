"""Compile the mining kernels and dispatches for a TPU v5e, without one.

The TPU compiler is installed with JAX and compiles for a described
topology (``v5e:2x2``) that is not attached: what it refuses here —
a block shape that breaks the (8, 128) tiling rule, a kernel that
overflows scalar memory, a dispatch that outgrows HBM — it would refuse
on the chip.  Nothing runs, so these tests say nothing about results;
``chip_smoke.py`` checks those on the chip.

Shapes are kosarak-paper's real row width: 990,000 transactions ->
242 blocks of 128 words.  The topology is described inside a fixture
(never at import), so every pytest worker collects the same tests and
only the worker that runs this file loads the TPU library.
"""

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.compat import make_mesh
from repro.core.bitmap import PAIR_CHUNK_BUCKETS, hbm_pair_cap
from repro.core.rowstore import _padded_slab
from repro.kernels import ops
from repro.kernels.bitmap_diff import bitmap_diff_es
from repro.kernels.bitmap_intersect import bitmap_intersect_es
from repro.kernels.compact import compact_gather
from repro.kernels.nlist_merge import nlist_merge, nlist_zmerge

NB, BW = 242, 128                  # kosarak-paper: 990,000 TIDs
PAIRS = 256
SLAB = 8192                        # BitmapMiner's slab at kosarak-paper
V5E_HBM = int(15.75 * 2 ** 30)     # the v5e compiler's HBM limit
U32, I32 = jnp.uint32, jnp.int32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def chip(topo):
    """``shape, dtype -> ShapeDtypeStruct`` on the first described chip."""
    one = jax.sharding.SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype=I32: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the program"
    return compiled


@pytest.mark.parametrize("mode", ["and", "andnot"])
def test_intersect_kernel_compiles(chip, mode):
    _compile(lambda *a: bitmap_intersect_es(*a, mode=mode, interpret=False),
             chip((PAIRS, NB, BW), U32), chip((PAIRS, NB, BW), U32),
             chip((PAIRS, NB + 1)), chip((PAIRS, NB + 1)), chip((PAIRS,)),
             chip(()))


def test_diff_kernel_compiles(chip):
    _compile(lambda *a: bitmap_diff_es(*a, interpret=False),
             chip((PAIRS, NB, BW), U32), chip((PAIRS, NB, BW), U32),
             chip((PAIRS, NB + 1)), chip((PAIRS,)), chip(()))


@pytest.mark.parametrize("slab,n_out", [
    ((SLAB, NB, BW), 4096),          # bitmap rows
    ((SLAB, NB + 1), 4096),          # suffix tables
    ((1 << 18, 3), 300_000),         # PPC codes; perm > one SMEM piece
], ids=["rows", "suffix", "codes"])
def test_compact_kernel_compiles(chip, slab, n_out):
    dtype = U32 if len(slab) == 3 else I32
    _compile(lambda s, p: compact_gather(s, p, interpret=False),
             chip(slab, dtype), chip((n_out,)))


@pytest.mark.parametrize("length", [2048, 32768, 65536])
def test_nlist_merge_kernel_compiles(chip, length):
    """The merge fetches its rows from HBM in blocks, so scalar memory
    holds two blocks per column whatever the row width: rows past the
    largest tuned N-list bucket (65536, the power-of-two fallback)
    compile too."""
    row = chip((PAIRS, length))
    _compile(lambda *a: nlist_merge(*a, early_stop=True, interpret=False),
             row, row, row, row, row, row, chip((PAIRS,)), chip((PAIRS,)),
             chip((PAIRS,)), chip(()))


@pytest.mark.parametrize("length", [2048, 32768])
def test_nlist_zmerge_kernel_compiles(chip, length):
    """The Z-merge's rows fit scalar memory up to the largest bucket."""
    row = chip((PAIRS, length))
    _compile(lambda *a: nlist_zmerge(*a, interpret=False),
             row, row, row, row, chip((PAIRS,)))


def _fused_args(chip, n_pairs):
    return (chip((SLAB, NB, BW), U32), chip((SLAB, NB + 1)),
            chip((n_pairs,)), chip((n_pairs,)), chip((n_pairs,)),
            chip((n_pairs,)), chip(()), chip(()))


@pytest.mark.parametrize("impl", ["intersect", "diff"])
def test_fused_dispatch_runs_the_kernel(chip, monkeypatch, impl):
    """On a TPU ``backend="auto"`` resolves to the compiled kernel, and
    the fused gather + kernel + scatter program holds it (4096 pairs on
    an 8192-row slab)."""
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    backend = ops._resolve("auto")
    assert backend == "pallas"
    if impl == "intersect":
        fn = lambda *a: ops._screen_and_intersect_impl(  # noqa: E731
            *a, mode="and", backend=backend)
    else:
        fn = lambda *a: ops._screen_and_diff_impl(  # noqa: E731
            *a, backend=backend)
    _compile(fn, *_fused_args(chip, 4096))


def test_fused_dispatch_fits_hbm_at_the_capped_width(chip, monkeypatch):
    """The engine's memory bound on the pair chunk, evaluated for a v5e
    at kosarak width, gives a dispatch the compiler accepts (the
    uncapped 65536-pair chunk needs 30.9 GB of 15.75 GiB)."""
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    words = NB * BW + NB + 1
    width = hbm_pair_cap(words, V5E_HBM, PAIR_CHUNK_BUCKETS)
    assert 4096 <= width < 65536
    compiled = _compile(
        lambda *a: ops._screen_and_intersect_impl(*a, mode="and",
                                                  backend="pallas"),
        *_fused_args(chip, width))
    mem = compiled.memory_analysis()
    assert (mem.temp_size_in_bytes + mem.argument_size_in_bytes
            < V5E_HBM), mem


def test_store_builder_holds_one_slab(chip):
    """The row store's builder pads kosarak-paper's 154 real rows to the
    8192-row slab on the chip: the slab is its output and nothing
    slab-sized is a temporary."""
    compiled = _padded_slab.lower(chip((154, NB, BW), U32), None,
                                  cap=SLAB).compile()
    mem = compiled.memory_analysis()
    slab_bytes = SLAB * NB * BW * 4
    assert mem.output_size_in_bytes >= slab_bytes, mem
    assert mem.temp_size_in_bytes < slab_bytes // 100, mem


def test_sharded_dispatch_on_2x2_mesh_has_its_collectives(topo):
    """The (block, cls) program on four chips: the screen psum and the
    count psum become all-reduces, the cls re-assembly an all-gather."""
    mesh = make_mesh((2, 2), ("block", "cls"), devices=topo.devices)
    dispatch = ops.make_screen_and_intersect_sharded(
        mesh, tid_axes=("block",), mode="and", early_stop=True,
        cls_axes=("cls",))
    n, nbl = 4096, NB // 2

    def at(shape, spec, dtype=I32):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, spec))

    vec = at((n,), P("cls"))
    compiled = dispatch.program.lower(
        at((SLAB, NB, BW), P(None, "block", None), U32),
        at((SLAB, 2 * (nbl + 1)), P(None, "block")),
        vec, vec, vec, vec, at((), P()), at((), P())).compile()
    text = compiled.as_text()
    assert "all-reduce" in text
    assert "all-gather" in text
    assert np.isfinite(compiled.memory_analysis().temp_size_in_bytes)
