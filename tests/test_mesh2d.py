"""2-D (block x cls) mesh sharding (ISSUE 9).

In-process tests cover the pieces that don't need multiple devices: the
sharded ref's cls-slice invariance, the autotune budget division, the
frontier's chunk-quantum alignment, the mining-mesh builder and the
forced-device bootstrap.  The real multi-device equivalence sweep —
frequent sets, supports and every gated EngineAccounting counter
identical across 1x1 / 8x1 / 1x8 / 4x2 meshes for all schemes, ES
on/off, serial and pipelined — runs in a subprocess with 8 forced host
devices (``repro.launch.forcedevices``), like tests/test_distributed.py.
"""

import subprocess
import sys
import textwrap
from types import SimpleNamespace

import numpy as np
import pytest

import jax
import jax.numpy as jnp


# -- sharded ref: cls slicing is a pure reshuffle ---------------------------

@pytest.mark.parametrize("mode", ["and", "andnot"])
@pytest.mark.parametrize("early_stop", [False, True])
def test_sharded_ref_cls_slicing_invariant(early_stop, mode):
    """``n_cls > 1`` evaluates disjoint contiguous pair slices and
    concatenates — bit-identical outputs to ``n_cls=1`` by construction
    (this is the contract that makes 2-D meshes bit-identical to
    serial)."""
    from repro.core.bitmap import popcount32_np, suffix_popcounts_np
    from repro.kernels import ref

    r = np.random.default_rng(5)
    cap, nb, bw = 24, 4, 4
    rows = np.zeros((cap, nb, bw), np.uint32)
    rows[:16] = r.integers(0, 2 ** 32, (16, nb, bw), dtype=np.uint64
                           ).astype(np.uint32)
    suffix = suffix_popcounts_np(rows)
    n = 12
    ua = r.integers(0, 16, n).astype(np.int32)
    vb = r.integers(0, 16, n).astype(np.int32)
    slots = np.arange(16, 16 + n, dtype=np.int32)
    if mode == "and":
        rho = r.integers(0, 100, n).astype(np.int32)
    else:
        rho = popcount32_np(rows).reshape(cap, -1).sum(1).astype(
            np.int32)[ua]
    for minsup in (0, 8, 40):
        base = ref.screen_and_intersect_sharded_ref(
            rows, suffix, ua, vb, slots, rho, jnp.int32(minsup),
            n_shards=1, n_cls=1, mode=mode, early_stop=early_stop)
        for n_cls in (2, 4, 6):
            got = ref.screen_and_intersect_sharded_ref(
                rows, suffix, ua, vb, slots, rho, jnp.int32(minsup),
                n_shards=1, n_cls=n_cls, mode=mode,
                early_stop=early_stop)
            for b, g in zip(base, got):
                assert np.array_equal(np.asarray(b), np.asarray(g)), (
                    mode, early_stop, minsup, n_cls)


def test_sharded_ref_cls_must_divide_pairs():
    from repro.kernels import ref

    rows = np.zeros((4, 1, 2), np.uint32)
    suffix = np.zeros((4, 2), np.int32)
    v = np.zeros(3, np.int32)
    with pytest.raises(ValueError, match="n_cls"):
        ref.screen_and_intersect_sharded_ref(
            rows, suffix, v, v, v, v, jnp.int32(1), n_shards=1, n_cls=2)


# -- autotune budget: per-device words divide by the cls count --------------

def test_autotune_words_per_pair_divides_by_cls():
    """Satellite 6: ``chunk_width_for``'s VMEM budget is per DEVICE; a
    cls-shard gathers 1/n_cls of the chunk, so the distributed miner's
    words-per-pair divides (ceil) by n_cls and the autotuned chunk can
    widen at equal footprint."""
    from repro.core.bitmap import (BITMAP_REF_ROW_WORDS,
                                   PAIR_CHUNK_BUCKETS, chunk_width_for)
    from repro.core.distributed import DistributedMiner
    from repro.core.eclat import BitmapMiner

    bdb = SimpleNamespace(n_blocks=5, block_words=128)
    base = BitmapMiner._autotune_words_per_pair(
        SimpleNamespace(block_words=128), bdb)
    assert base == 5 * 128
    for n_cls in (1, 2, 8):
        fake = SimpleNamespace(block_words=128, n_cls=n_cls)
        wpp = DistributedMiner._autotune_words_per_pair(fake, bdb)
        assert wpp == -(-base // n_cls)
        w = chunk_width_for(wpp, 64, PAIR_CHUNK_BUCKETS,
                            BITMAP_REF_ROW_WORDS)
        assert w >= chunk_width_for(base, 64, PAIR_CHUNK_BUCKETS,
                                    BITMAP_REF_ROW_WORDS)
    # strictly wider once the division crosses a bucket boundary
    w1 = chunk_width_for(base, 64, PAIR_CHUNK_BUCKETS,
                         BITMAP_REF_ROW_WORDS)
    w8 = chunk_width_for(-(-base // 8), 64, PAIR_CHUNK_BUCKETS,
                         BITMAP_REF_ROW_WORDS)
    assert w8 > w1


# -- frontier: chunk boundaries align to the cls count ----------------------

def _slices(client, total, widths=None, pair_chunk=100):
    from repro.core.frontier import FrontierScheduler

    return FrontierScheduler(client, pair_chunk)._chunk_slices(
        total, widths)


def test_chunk_slices_quantum_alignment():
    """Satellite 6 regression: non-final chunk boundaries land on
    multiples of the client's ``chunk_quantum`` so every cls-shard's
    slice covers real pairs; the final chunk keeps the remainder (the
    dispatch pads it)."""
    q8 = SimpleNamespace(chunk_quantum=8)
    for lo, sl in _slices(q8, 1000, pair_chunk=100)[:-1]:
        assert (sl.stop - sl.start) % 8 == 0, (lo, sl)
    # widths-driven slicing: caps are respected AND boundaries aligned
    widths = np.full(1000, 70, np.int64)
    cuts = _slices(q8, 1000, widths=widths)
    assert cuts[-1][1].stop == 1000
    for i, (lo, sl) in enumerate(cuts):
        n = sl.stop - sl.start
        assert n <= 70
        if i < len(cuts) - 1:
            assert n % 8 == 0, (i, sl)
    # quantum 1 (every single-device client) is exactly the old slicing
    q1 = SimpleNamespace(chunk_quantum=1)
    assert _slices(q1, 1000, widths=widths) != []
    assert [s for s in _slices(q1, 250, pair_chunk=100)] == [
        (0, slice(0, 100)), (100, slice(100, 200)), (200, slice(200, 250))]
    # a width cap below the quantum still makes progress (degenerate
    # chunk, padded at dispatch rather than rounded to zero)
    tiny = np.full(40, 3, np.int64)
    cuts = _slices(q8, 40, widths=tiny)
    assert sum(s.stop - s.start for _lo, s in cuts) == 40
    assert all(s.stop - s.start >= 1 for _lo, s in cuts)


def test_chunk_quantum_defaults():
    from repro.core.eclat import BitmapMiner

    assert BitmapMiner.chunk_quantum == 1


# -- launch layer -----------------------------------------------------------

def test_make_mining_mesh_single_device():
    from repro.launch.mesh import make_mining_mesh

    mesh = make_mining_mesh()
    assert tuple(mesh.axis_names) == ("block", "cls")
    assert mesh.shape["cls"] == 1
    assert mesh.shape["block"] == jax.device_count()
    with pytest.raises(ValueError, match="cls"):
        make_mining_mesh(cls=jax.device_count() + 1)


def test_mining_mesh_auto_cls_detection():
    """DistributedMiner picks up the ``cls`` axis by name and keeps the
    TID axes disjoint from it (trivial sizes on one device, but the
    wiring is what's under test — the 8-device version runs in the
    subprocess sweep)."""
    from repro.core.distributed import DistributedMiner
    from repro.core.eclat import BitmapMiner
    from repro.launch.mesh import make_mining_mesh

    mesh = make_mining_mesh()
    m = DistributedMiner(mesh, block_words=2)
    assert m.cls_axes == ("cls",)
    assert m.tid_axes == ("block",)
    assert m.n_cls == 1 and m.chunk_quantum == 1
    db = [[0, 1, 2], [0, 1], [1, 2], [0, 2], [0, 1, 2]]
    out, _ = m.mine(db, 2)
    ref_out, _ = BitmapMiner(block_words=2).mine(db, 2)
    assert out == ref_out
    with pytest.raises(ValueError, match="overlap"):
        DistributedMiner(mesh, tid_axes=("block", "cls"),
                         cls_axes=("cls",))


def test_force_host_device_count_sets_flag(monkeypatch):
    from repro.launch import forcedevices

    monkeypatch.delitem(sys.modules, "jax", raising=False)
    monkeypatch.setenv(
        "XLA_FLAGS",
        "--foo=1 --xla_force_host_platform_device_count=2")
    forcedevices.force_host_device_count(8)
    import os

    assert os.environ["XLA_FLAGS"].split() == [
        "--foo=1", "--xla_force_host_platform_device_count=8"]
    with pytest.raises(ValueError):
        forcedevices.force_host_device_count(0)


def test_force_host_device_count_after_backend_init_raises():
    from repro.launch.forcedevices import force_host_device_count

    jax.devices()           # make sure the backend is up
    with pytest.raises(RuntimeError, match="backend init"):
        force_host_device_count(8)


# -- the multi-device equivalence sweep -------------------------------------

MESH2D_SCRIPT = textwrap.dedent("""
    import sys
    sys.path.insert(0, "src")
    from repro.launch.forcedevices import force_host_device_count
    force_host_device_count(8)

    import random
    import numpy as np
    import jax
    assert jax.device_count() == 8

    from repro.core.eclat import BitmapMiner
    from repro.core.distributed import DistributedMiner
    from repro.core.oracle import mine_bruteforce
    from repro.launch.mesh import make_mining_mesh

    SHAPES = [(1, 1), (8, 1), (1, 8), (4, 2)]
    meshes = {s: make_mining_mesh(block=s[0], cls=s[1]) for s in SHAPES}
    for s in SHAPES:
        assert dict(meshes[s].shape) == {"block": s[0], "cls": s[1]}, s

    def counters(st):
        d = st.as_dict()
        for k in ("runtime_s", "assemble_s", "resolve_s"):
            d.pop(k)
        return d

    # --- sweep 1: full counter identity, serial engine included.
    # Single-real-block DBs (block_words=2 => 64 TIDs/block, <= 60
    # transactions): the shard-local ES thresholds then see zero slack
    # on every mesh, so EVERY gated counter — word_ops, screened_out /
    # kernel_aborts (the bitmap engine's es_checks analogues),
    # scatter_words, candidates, nodes, device_calls, peak_rows,
    # compactions — is identical across all four mesh shapes AND the
    # single-device BitmapMiner.
    rng = random.Random(11)
    for trial in range(2):
        ni = rng.randint(5, 8)
        nt = rng.randint(20, 60)
        db = [[i for i in range(ni) if rng.random() < 0.5]
              for _ in range(nt)]
        db = [t for t in db if t]
        ms = rng.randint(2, max(2, len(db) // 3))
        bf = mine_bruteforce(db, ms)
        for scheme, dd in (("eclat", None), ("declat", None),
                           ("adaptive", 0.3)):
            for es in (False, True):
                for inflight in (1, 2):
                    _, st0 = BitmapMiner(
                        scheme=scheme, early_stop=es, block_words=2,
                        inflight=inflight, diff_density=dd).mine(db, ms)
                    want = counters(st0)
                    for shape in SHAPES:
                        m = DistributedMiner(
                            meshes[shape], scheme=scheme, early_stop=es,
                            capacity=256, block_words=2,
                            inflight=inflight, diff_density=dd)
                        out, st = m.mine(db, ms)
                        key = (trial, scheme, es, inflight, shape)
                        assert out == bf, key
                        assert counters(st) == want, (
                            key, counters(st), want)
    print("SWEEP1_OK")

    # --- sweep 2: multi-block DB.  Block-sharding legitimately changes
    # ES-on word_ops (shard-local thresholds), but the cls axis NEVER
    # does: 1x1 vs 1x8 (same block sharding, cls 1 vs 8) must agree on
    # every counter, for every scheme, ES on/off, serial and pipelined.
    rng2 = np.random.default_rng(2)
    db2 = [list(np.flatnonzero(rng2.random(30) < 0.35))
           for _ in range(300)]
    ms2 = 18
    ref2, _ = BitmapMiner(scheme="eclat", block_words=2).mine(db2, ms2)
    for scheme, dd in (("eclat", None), ("declat", None),
                       ("adaptive", 0.3)):
        for es in (False, True):
            for inflight in (1, 2):
                kw = dict(scheme=scheme, early_stop=es, capacity=512,
                          block_words=2, inflight=inflight,
                          diff_density=dd)
                out_a, st_a = DistributedMiner(
                    meshes[(1, 1)], **kw).mine(db2, ms2)
                out_b, st_b = DistributedMiner(
                    meshes[(1, 8)], **kw).mine(db2, ms2)
                key = (scheme, es, inflight)
                assert out_a == out_b, key
                if scheme == "eclat":
                    assert out_a == ref2, key
                assert counters(st_a) == counters(st_b), (
                    key, counters(st_a), counters(st_b))
    print("SWEEP2_OK")

    # --- satellite 6a: the autotune budget divides by n_cls, so the
    # cls-sharded run tunes a strictly wider chunk at equal per-device
    # footprint, at identical per-pair work and never more dispatches.
    kw = dict(scheme="eclat", early_stop=True, block_words=2,
              pair_chunk=64, autotune_chunk=True)
    m11 = DistributedMiner(meshes[(1, 1)], **kw)
    m18 = DistributedMiner(meshes[(1, 8)], **kw)
    out11, s11 = m11.mine(db2, ms2)
    out18, s18 = m18.mine(db2, ms2)
    assert out11 == out18 == ref2
    assert m18._chunk_width > m11._chunk_width, (
        m11._chunk_width, m18._chunk_width)
    assert s18.word_ops == s11.word_ops
    assert s18.scatter_words == s11.scatter_words
    assert s18.device_calls <= s11.device_calls
    print("SWEEP3_OK")

    # --- satellite 6b: compaction reserve under 2-D inflight.  Force
    # aggressive compaction on the 4x2 mesh with a pipelined ring: if
    # the reserve missed any cls-shard's pending handles the remapped
    # scatter slots would go out of bounds and children would be
    # silently dropped — result equality is the regression gate.
    m = DistributedMiner(meshes[(4, 2)], scheme="eclat",
                         early_stop=True, capacity=64, block_words=2,
                         inflight=2, compact_occupancy=0.9)
    out_c, st_c = m.mine(db2, ms2)
    assert out_c == ref2
    assert st_c.compactions > 0, "compaction never fired; gate is vacuous"
    print("SWEEP4_OK")

    print("MESH2D_OK")
""")


@pytest.mark.slow
def test_mesh2d_equivalence_sweep():
    proc = subprocess.run([sys.executable, "-c", MESH2D_SCRIPT],
                          capture_output=True, text=True, timeout=900,
                          cwd=".")
    assert "MESH2D_OK" in proc.stdout, (proc.stdout[-2000:],
                                        proc.stderr[-3000:])
