#!/usr/bin/env python3
"""The program's own spans and scopes in a profiler trace of a window.

    python3 bench/program_trace.py --workload <cell> --seed <n> --seconds <s>

The program marks its work itself (``repro.core.trace``): host spans
``mine``, ``store.build``, ``store.upload``, ``sched.launch``,
``sched.retire``, ``sched.resolve``, ``sched.wait``, ``sched.readback``
and the rest of ``PROGRAM_SPANS``, with ``job``, ``group`` and
``chunk`` ids, and ``jax.named_scope`` names (``SCOPES``) on the parts
of the fused dispatches.  This module reduces a trace by those marks:

* ``store.upload_ms``: per job, the first device operation starting
  after ``store.upload`` begins, less the start of ``store.upload``:
  in a closed loop the device is idle at a job's start, so this is
  the host-to-device transfer it waited for;
* ``dispatch.kernel_ms`` and the other scopes: device time per job of
  the operations whose metadata names the scope;
* the device's idle time cut at span boundaries and given, piece by
  piece, to the innermost program span over it (no midpoint rule);
* from the jobs' accounting: ``store.upload_gib``, ``sched.wait_ms``,
  ``sched.retire_ms`` and ``dispatch.pad_frac``.

The command runs a cell's set-up as ``bench/run.py`` does, then one
traced window of plain ``mine_packed`` calls (no wrapper spans), and
prints one JSON line; ``--out`` also writes it to a file.  It is a
measurement tool beside the benchmark: ``bench/run.py`` does not call
it, and its values are not metrics of ``BENCHMARK.json``.
"""

from __future__ import annotations

import bisect
from collections import Counter, defaultdict
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

try:
    from bench import trace as trace_mod
except ImportError:          # run as a script: put the checkout on the path
    import sys
    from pathlib import Path

    _ROOT = Path(__file__).resolve().parents[1]
    sys.path[:0] = [str(_ROOT), str(_ROOT / "src")]
    from bench import trace as trace_mod

PROGRAM_SPANS = ("mine", "store.build", "store.upload", "store.grow",
                 "store.compact", "sched.launch", "sched.assemble",
                 "sched.dispatch", "sched.retire", "sched.resolve",
                 "sched.wait", "sched.readback")
SCOPES = ("dispatch.gather", "dispatch.kernel", "dispatch.scatter")
WINDOW_SPAN = "window"

Span = Tuple[int, int, str, dict]      # start ns, end ns, name, ids
Op = Tuple[int, int, str, str]         # start ns, end ns, name, scope


def op_scope(stats: Sequence[Tuple[str, object]]) -> Tuple[str, str]:
    """The first of ``SCOPES`` named in an op's string stats (its
    ``op_name`` path), and the stat that names it; ``""`` for none."""
    for key, value in stats:
        if isinstance(value, str) and "dispatch." in value:
            parts = value.split("/")
            for scope in SCOPES:
                if scope in parts:
                    return scope, key
    return "", ""


def _varint(buf, i: int) -> Tuple[int, int]:
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf) -> Iterator[Tuple[int, object]]:
    """``(field number, value)`` of one protobuf message: an int for a
    varint, a slice of ``buf`` for the other wire types."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield key >> 3, value


def metadata_stats(path: str) -> Dict[str, Dict[str, List[Tuple[str, str]]]]:
    """The string stats of every event metadata of every device plane in
    an ``.xplane.pb``: plane name -> event name -> ``[(stat, value)]``.

    ``ProfileData`` gives an event's own stats only; on the TPU an op's
    HLO metadata (its ``op_name`` path among it) sits on the event's
    metadata.  Fields, from ``tsl/profiler/protobuf/xplane.proto``:
    XSpace.planes 1; XPlane.name 2, .event_metadata 4 and
    .stat_metadata 5 (map entries: key 1, value 2); XEventMetadata.name
    2, .display_name 4, .stats 5; XStatMetadata.id 1, .name 2;
    XStat.metadata_id 1, .str_value 5, .ref_value 7 (the name of a stat
    metadata)."""
    with open(path, "rb") as f:
        data = memoryview(f.read())
    out: Dict[str, Dict[str, List[Tuple[str, str]]]] = {}
    for num, plane in _fields(data):
        if num != 1:
            continue
        name, metas, stat_names = "", [], {}
        for f, v in _fields(plane):
            if f == 2:
                name = bytes(v).decode()
            elif f == 4:
                metas.extend(e for k, e in _fields(v) if k == 2)
            elif f == 5:
                for k, e in _fields(v):
                    if k == 2:
                        sm = dict(_fields(e))
                        stat_names[sm.get(1, 0)] = bytes(
                            sm.get(2, b"")).decode()
        if not trace_mod.DEVICE_PLANE.match(name):
            continue
        events: Dict[str, List[Tuple[str, str]]] = {}
        for meta in metas:
            names, stats = [], []
            for f, v in _fields(meta):
                if f in (2, 4):
                    names.append(bytes(v).decode())
                elif f == 5:
                    st = dict(_fields(v))
                    if 5 in st:
                        value = bytes(st[5]).decode(errors="replace")
                    elif 7 in st:
                        value = stat_names.get(st[7], "")
                    else:
                        continue
                    stats.append((stat_names.get(st.get(1, 0), ""), value))
            for n in names:
                if n:
                    events[n] = stats
        out[name] = events
    return out


def extract(profile, path: Optional[str] = None,
            ) -> Tuple[Dict[str, List[Op]], List[Span], Counter]:
    """Device operations per device plane with their scope, the program's
    host spans (and the window span) with their ids, and a count of the
    stat keys that carried a scope (on a v5e: ``tf_op``, a stat of the
    event's metadata).  ``path`` is the trace's file, for the ops'
    metadata stats."""
    meta = metadata_stats(path) if path else {}
    devices: Dict[str, List[Op]] = {}
    spans: List[Span] = []
    carriers: Counter = Counter()
    names = set(PROGRAM_SPANS) | {WINDOW_SPAN}
    for plane in profile.planes:
        if trace_mod.DEVICE_PLANE.match(plane.name):
            lines = {line.name: line for line in plane.lines}
            line = next((lines[n] for n in trace_mod.OPS_LINES
                         if n in lines), None)
            if line is None:
                continue
            ops = []
            plane_meta = meta.get(plane.name, {})
            for e in line.events:
                stats = list(e.stats) + plane_meta.get(e.name, [])
                scope, key = op_scope(stats)
                if key:
                    carriers[key] += 1
                ops.append((int(e.start_ns),
                            int(e.start_ns + e.duration_ns), e.name, scope))
            devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend(
                    (int(e.start_ns), int(e.start_ns + e.duration_ns),
                     e.name, dict(e.stats))
                    for e in line.events if e.name in names)
    return devices, spans, carriers


def upload_waits_ms(uploads: Sequence[Span], ops: Sequence[Op],
                    ) -> List[float]:
    """For each ``store.upload`` span, milliseconds from its start to
    the start of the first device operation that starts at or after
    it (spans with no later operation are left out)."""
    starts = sorted(s for s, _, _, _ in ops)
    out = []
    for s, _, _, _ in uploads:
        i = bisect.bisect_left(starts, s)
        if i < len(starts):
            out.append((starts[i] - s) / 1e6)
    return out


def scope_seconds(ops: Sequence[Op], lo: int, hi: int) -> Dict[str, float]:
    """Device seconds per scope (``""``: no scope) inside ``[lo, hi)``."""
    out: Dict[str, float] = defaultdict(float)
    for s, e, _, scope in ops:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            out[scope] += (e - s) / 1e9
    return dict(out)


def innermost(spans: Sequence[Span], lo: int, hi: int,
              ) -> List[Tuple[int, int, Optional[str]]]:
    """Cut ``[lo, hi)`` into pieces, each with the name of the innermost
    span over it (``None`` where no span is).  The program's spans are
    opened on one thread, so they nest."""
    pieces: List[Tuple[int, int, Optional[str]]] = []
    stack: List[Tuple[int, str]] = []          # (end, name), outer first
    t = lo

    def advance(until: int) -> None:
        nonlocal t
        while stack and stack[-1][0] <= until:
            end, name = stack.pop()
            if end > t:
                pieces.append((t, end, name))
                t = end
        if until > t:
            pieces.append((t, until, stack[-1][1] if stack else None))
            t = until

    for s, e, name, _ in sorted(spans, key=lambda sp: (sp[0], -sp[1])):
        advance(max(s, lo))
        stack.append((min(e, hi), name))
    advance(hi)
    return pieces


def attribute(idle: Sequence[Tuple[int, int]],
              pieces: Sequence[Tuple[int, int, Optional[str]]],
              ) -> Dict[str, float]:
    """Seconds of ``idle`` under each piece's name (both sorted, the
    pieces covering every idle stretch)."""
    out: Dict[str, float] = defaultdict(float)
    j = 0
    for s, e in idle:
        while j < len(pieces) and pieces[j][1] <= s:
            j += 1
        k = j
        while k < len(pieces) and pieces[k][0] < e:
            ps, pe, name = pieces[k]
            overlap = min(e, pe) - max(s, ps)
            if overlap > 0:
                out[name or "none"] += overlap / 1e9
            k += 1
    return dict(out)


def job_values(jobs: Sequence[dict]) -> Dict[str, float]:
    """Per-job values from the jobs' accounting: ``upload_bytes``,
    ``wait_s``, ``retire_s``, ``pad_lanes`` and ``pair_lanes``."""
    n = len(jobs)
    lanes = sum(j["pair_lanes"] for j in jobs)
    return {
        "store.upload_gib": sum(j["upload_bytes"] for j in jobs) / n / 2**30,
        "sched.wait_ms": 1e3 * sum(j["wait_s"] for j in jobs) / n,
        "sched.retire_ms": 1e3 * sum(j["retire_s"] for j in jobs) / n,
        "dispatch.pad_frac": (sum(j["pad_lanes"] for j in jobs) / lanes
                              if lanes else 0.0),
        "sched.assemble_ms": 1e3 * sum(j["assemble_s"] for j in jobs) / n,
        "sched.resolve_ms": 1e3 * sum(j["resolve_s"] for j in jobs) / n,
    }


def reduce(devices: Dict[str, List[Op]], spans: List[Span],
           window: Tuple[int, int], n_jobs: int) -> Optional[dict]:
    """Everything the trace says per job inside ``window``; ``None``
    where no device operation ran in it."""
    lo, hi = window
    if not devices or n_jobs <= 0:
        return None
    ops = next(iter(devices.values()))          # one chip per cell
    busy = trace_mod.union([(s, e, n) for s, e, n, _ in ops], lo, hi)
    busy_s = sum(e - s for s, e in busy) / 1e9
    if busy_s <= 0:
        return None
    inner = [sp for sp in spans if sp[2] != WINDOW_SPAN
             and lo <= sp[0] < hi]
    idle = trace_mod.gaps(busy, lo, hi)
    per_job = lambda d: {k: 1e3 * v / n_jobs  # noqa: E731
                         for k, v in sorted(d.items(), key=lambda kv: -kv[1])}
    span_s: Dict[str, float] = defaultdict(float)
    for s, e, name, _ in inner:
        span_s[name] += (e - s) / 1e9
    uploads = [sp for sp in inner if sp[2] == "store.upload"]
    waits = upload_waits_ms(uploads, ops)
    scopes = scope_seconds(ops, lo, hi)
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy_s,
        "device_idle_frac": 1.0 - busy_s * 1e9 / (hi - lo),
        "store.upload_ms": sum(waits) / len(waits) if waits else None,
        "dispatch.kernel_ms": 1e3 * scopes.get("dispatch.kernel", 0.0)
        / n_jobs,
        "scope_ms": per_job({k or "none": v for k, v in scopes.items()}),
        "span_ms": per_job(span_s),
        "idle_ms": per_job(attribute(idle, innermost(inner, lo, hi))),
    }


def main(argv=None) -> int:
    import argparse
    import glob
    import json
    import os
    import tempfile
    import time

    import jax

    from bench import compiles as compiles_mod
    from bench import data, harness
    from repro.cache import configure_compile_cache

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--out")
    ap.add_argument("--scale", type=float, default=1.0,
                    help="share of the configuration's transactions")
    args = ap.parse_args(argv)

    cell = harness.Cell(args.workload)
    if not harness.chips_ok(int(cell.workload["chips"])):
        return 2
    configure_compile_cache()
    cfg = cell.config
    params = dict(cfg["params"])
    bdb, minsups = data.pack(cfg["generator"], params, cfg["rungs"],
                             seed=params.pop("data_seed"),
                             order_seed=args.seed, scale=args.scale,
                             block_words=cfg["block_words"])
    minsup = minsups[cell.traffic["rung"]]
    rows = data.rung(bdb, minsup)
    del bdb
    miner = cell.engine.build(cfg["miner"])
    counter = compiles_mod.Compiles()
    for i in range(harness.MAX_WARMUP_JOBS):
        before = counter.backends
        miner.mine_packed(rows, minsup)
        harness.device_sync()
        if i > 0 and counter.backends == before:
            break

    stats = []
    with tempfile.TemporaryDirectory() as tmp:
        jax.profiler.start_trace(
            tmp, profiler_options=trace_mod.profiler_options())
        t0 = time.perf_counter()
        with harness.span(WINDOW_SPAN):
            while True:
                _, st = miner.mine_packed(rows, minsup)
                stats.append(vars(st).copy())
                if time.perf_counter() - t0 >= args.seconds:
                    break
            harness.device_sync()
        t1 = time.perf_counter()
        jax.profiler.stop_trace()
        path = max(glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
        devices, spans, carriers = extract(
            jax.profiler.ProfileData.from_file(path), path)

    windows = [(s, e) for s, e, n, _ in spans if n == WINDOW_SPAN]
    line = {"workload": args.workload, "seed": args.seed,
            "jobs": len(stats), "mine_s_traced": (t1 - t0) / len(stats),
            **job_values(stats), "scope_stats": dict(carriers)}
    if windows:
        line.update(reduce(devices, spans, windows[0], len(stats)) or {})
    if line.get("store.upload_ms"):
        line["upload_gb_per_s"] = (line["store.upload_gib"] * 2**30 / 1e9
                                   / (line["store.upload_ms"] / 1e3))
    text = json.dumps(line)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text + "\n")
    print(text, flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
