"""The engines' own host spans and the timers and counters behind them.

A small ``mine_packed`` under ``jax.profiler.trace`` must emit every
span of ``core/trace.py``'s vocabulary with its ids and nesting; the
scheduler's timers must keep their definitions (checked exactly on a
fake clock); and the dispatch and upload counters must count what the
engine did.
"""

import glob
import os
import random

import jax
import numpy as np
import pytest

import repro.core.eclat as eclat_mod
import repro.core.trace as trace_mod
from repro.core.bitmap import PAIR_CHUNK_BUCKETS, BitmapDB
from repro.core.eclat import BitmapMiner
from repro.core.prepost import DevicePrePost
from repro.core.frontier import ClassNode, FrontierScheduler
from repro.core.rowstore import DeviceRowStore
from repro.core.trace import span
from repro.kernels import ops

SPANS = ("mine", "store.build", "store.upload", "store.grow",
         "store.compact", "sched.launch", "sched.assemble",
         "sched.dispatch", "sched.retire", "sched.resolve", "sched.wait",
         "sched.readback")


def _db(seed=0, n_items=20, n_trans=80, p=0.35):
    rng = random.Random(seed)
    db = [[i for i in range(n_items) if rng.random() < p]
          for _ in range(n_trans)]
    return [t for t in db if t]


def _traced(fn, tmp_path, names=SPANS):
    """Run ``fn`` under the profiler; return its result and the host
    events named in ``names`` as ``(start, end, name, stats)``."""
    with jax.profiler.trace(str(tmp_path)):
        out = fn()
    path, = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    profile = jax.profiler.ProfileData.from_file(path)
    events = []
    for plane in profile.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                events.extend(
                    (e.start_ns, e.start_ns + e.duration_ns, e.name,
                     dict(e.stats))
                    for e in line.events if e.name in names)
    return out, events


def _inside(outer, inner, keys):
    return (outer[0] <= inner[0] and inner[1] <= outer[1]
            and all(outer[3].get(k) == inner[3][k] for k in keys))


def test_mine_emits_every_span_with_ids_and_nesting(tmp_path):
    db, ms = _db(), 4
    miner = BitmapMiner(block_words=1, pair_chunk=8, compact_occupancy=1.0)
    assert miner.inflight == 2                       # the default ring
    bdb = BitmapDB.from_db(db, ms, 1)

    def two_jobs():
        return [miner.mine_packed(bdb, ms) for _ in range(2)]

    (first, second), events = _traced(two_jobs, tmp_path)
    assert first[0] == second[0]
    assert first[1].grows and first[1].compactions
    by_name = {}
    for ev in events:
        by_name.setdefault(ev[2], []).append(ev)
    assert set(by_name) == set(SPANS)
    assert sorted(ev[3]["job"] for ev in by_name["mine"]) == [0, 1]
    assert all("job" in ev[3] for ev in events)
    for name in ("sched.dispatch", "sched.resolve", "sched.wait",
                 "sched.readback"):
        assert all({"group", "chunk"} <= set(ev[3])
                   for ev in by_name[name]), name
    assert all("group" in ev[3] for ev in by_name["sched.retire"])
    # sched.retire > sched.resolve > sched.wait, sched.readback, each
    # inner span naming the same dispatch as its outer one.
    resolves, retires = by_name["sched.resolve"], by_name["sched.retire"]
    for ev in by_name["sched.wait"] + by_name["sched.readback"]:
        assert any(_inside(r, ev, ("job", "group", "chunk"))
                   for r in resolves), ev
    for ev in resolves:
        assert any(_inside(r, ev, ("job", "group")) for r in retires), ev
    for ev in by_name["store.build"] + by_name["store.upload"]:
        assert any(_inside(m, ev, ("job",)) for m in by_name["mine"]), ev
    assert len(by_name["sched.wait"]) == first[1].device_calls * 2


def test_span_inherits_ids_and_adds_to_field():
    class Acc:
        t = 0.0

    acc = Acc()
    with span("outer", job=3):
        with span("inner", acc=(acc, "t"), chunk=1) as inner:
            assert trace_mod._IDS.get() == {"job": 3, "chunk": 1}
        assert trace_mod._IDS.get() == {"job": 3}
    assert trace_mod._IDS.get() == {}
    assert acc.t > 0.0 and inner is not None


# ---------------------------------------------------------------------------
# timers on a fake clock
# ---------------------------------------------------------------------------

class _ClockClient:
    """Scheduler client whose protocol calls advance a fake clock by
    known steps, so each timer's definition can be checked exactly.
    Every pair survives: the run is a full DFS over ``n`` items."""

    STEP = {"pair_columns": 1.0, "evaluate_pairs": 8.0, "resolve": 64.0,
            "emit": 512.0}

    def __init__(self, now):
        self.now = now
        self.calls = dict.fromkeys(self.STEP, 0)

    def _tick(self, what):
        self.calls[what] += 1
        self.now[0] += self.STEP[what]

    def pair_columns(self, klass, ia, ib):
        self._tick("pair_columns")
        return {"a": klass.rows[ia], "b": klass.rows[ib]}

    def evaluate_pairs(self, cols):
        self._tick("evaluate_pairs")
        client, n = self, cols["a"].size

        class Handle:
            def resolve(self):
                client._tick("resolve")
                return [(k, 0, 1, None) for k in range(n)]

        return Handle()

    def make_class(self, parent, children):
        return ClassNode(itemsets=[c.itemset for c in children],
                         rows=np.zeros(len(children), np.int32),
                         supports=np.ones(len(children), np.int32))

    def emit(self, itemset, support):
        self._tick("emit")

    def release(self, klass):
        pass

    def maybe_compact(self, reserve):
        return None


@pytest.mark.parametrize("inflight", [1, 2])
def test_scheduler_timers_keep_their_definitions(monkeypatch, inflight):
    now = [0.0]
    monkeypatch.setattr(trace_mod, "perf_counter", lambda: now[0])
    client = _ClockClient(now)
    sched = FrontierScheduler(client, pair_chunk=2, inflight=inflight)
    n = 5
    sched.run(ClassNode(itemsets=[(i,) for i in range(n)],
                        rows=np.arange(n, dtype=np.int32),
                        supports=np.ones(n, np.int32)))
    calls, step = client.calls, client.STEP
    assert calls["emit"] == 2 ** n - 1 - n            # every itemset > 1
    # assemble_s: column assembly and dispatch launch, resolves excluded
    # (inflight=1 resolves inside the launch loop).
    assert sched.assemble_s == (calls["pair_columns"] * step["pair_columns"]
                                + calls["evaluate_pairs"]
                                * step["evaluate_pairs"])
    # resolve_s: every handle's resolve.
    assert sched.resolve_s == calls["resolve"] * step["resolve"]
    # retire_s: retirement less the resolves inside it.
    assert sched.retire_s == calls["emit"] * step["emit"]


# ---------------------------------------------------------------------------
# counters
# ---------------------------------------------------------------------------

def _store_spy(monkeypatch):
    """Record ``(real row bytes, slab bytes)`` of every store built."""
    built = []
    init = DeviceRowStore.__init__

    def init_spy(self, rows_np, *a, **kw):
        init(self, rows_np, *a, **kw)
        built.append((rows_np.nbytes, self.rows.nbytes))

    monkeypatch.setattr(DeviceRowStore, "__init__", init_spy)
    return built


def test_lane_and_upload_counters(monkeypatch):
    widths = []
    launch = eclat_mod.BitmapMiner._dispatch_launch

    def launch_spy(self, store, ua, *a, **kw):
        widths.append(int(ua.size))
        return launch(self, store, ua, *a, **kw)

    monkeypatch.setattr(eclat_mod.BitmapMiner, "_dispatch_launch",
                        launch_spy)
    built = _store_spy(monkeypatch)
    out, st = BitmapMiner(block_words=1, pair_chunk=8,
                          compact_occupancy=0.0).mine(_db(1), 4)
    assert st.compactions == 0 and len(built) == 1
    # Only the real rows cross to the device; the slab is padded there.
    (real, slab), = built
    assert st.upload_bytes == real < slab
    buckets = [next(b for b in PAIR_CHUNK_BUCKETS if n <= b) for n in widths]
    assert st.pair_lanes == sum(buckets)
    assert st.pad_lanes == sum(b - n for b, n in
                               zip(buckets, widths, strict=True))
    assert 0 < st.pad_lanes < st.pair_lanes
    assert 0.0 < st.wait_s <= st.resolve_s
    assert st.retire_s > 0.0


def test_compaction_uploads_its_permutation(monkeypatch):
    perms = []
    compact = ops.compact_rows

    def compact_spy(rows, suffix, perm, **kw):
        perms.append(perm.nbytes)
        return compact(rows, suffix, perm, **kw)

    monkeypatch.setattr(ops, "compact_rows", compact_spy)
    built = _store_spy(monkeypatch)
    _, st = BitmapMiner(block_words=1, pair_chunk=8,
                        compact_occupancy=1.0).mine(_db(), 4)
    assert st.compactions == len(perms) > 0
    (real, slab), = built
    assert st.upload_bytes == real + sum(perms)
    assert real < slab


# ---------------------------------------------------------------------------
# PrePost+: tree build, pool and N-list dispatches
# ---------------------------------------------------------------------------

def test_prepost_job_spans_and_dispatch_scopes(tmp_path):
    """``mine`` and ``ppc.build`` open once per job; ``pool.grow`` and
    ``pool.compact`` once per growth and compaction, inside their job;
    the two N-list dispatches carry their ``dispatch.*`` scopes."""
    db, ms = _db(), 4
    miner = DevicePrePost(pair_chunk=8, compact_occupancy=1.0)
    bdb = BitmapDB.from_db(db, ms)

    def two_jobs():
        return [miner.mine_packed(bdb, ms) for _ in range(2)]

    names = ("mine", "ppc.build", "pool.grow", "pool.compact")
    (first, second), events = _traced(two_jobs, tmp_path, names)
    assert first[0] == second[0]
    by_name = {}
    for ev in events:
        by_name.setdefault(ev[2], []).append(ev)
    for name in ("mine", "ppc.build"):
        assert sorted(ev[3]["job"] for ev in by_name[name]) == [0, 1]
    for name, count in (("pool.grow", "grows"),
                        ("pool.compact", "compactions")):
        per_job = getattr(first[1], count)
        assert per_job > 0, count
        assert len(by_name[name]) == 2 * per_job
    for ev in events:
        assert any(_inside(m, ev, ("job",)) for m in by_name["mine"]), ev
    assert first[1].build_s > 0.0

    codes, v = np.zeros((64, 3), np.int32), np.zeros(8, np.int32)
    merge = ops._nlist_presize_impl.lower(
        codes, v, v, v, v, v, np.int32(1), lu=8, lv=8, early_stop=True,
        backend="interpret").compile().as_text()
    scatter = ops._nlist_scatter_impl.lower(
        codes, np.zeros((8, 8), np.int32), v, v, v, v, v, lu=8, lv=8,
        backend="interpret").compile().as_text()
    assert "dispatch.nlist_merge" in merge
    assert "dispatch.nlist_scatter" in scatter
