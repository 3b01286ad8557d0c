"""Reduction of a JAX profiler trace to device busy time, the device
operations that took most time, and idle gaps attributed to host spans.

All from ``jax.profiler.ProfileData``.  A device is a plane named
``/device:<KIND>:<n>``; its operations are the events of its
``XLA Ops`` line (``XLA Modules`` where a plane has no such line).
Busy time is the union of those intervals inside the window; an idle
gap is a stretch of the window that no operation covers, labelled
with the innermost host span (a ``TraceAnnotation`` the harness wrote)
that holds the gap's midpoint.  A trace recorded on the CPU has no
device plane: it yields no busy time, and nothing from it is a device
number.
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

Interval = Tuple[int, int, str]          # start ns, end ns, name
DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:\d+$")
OPS_LINES = ("XLA Ops", "XLA Modules")
TOP = 10


def profiler_options():
    """Host spans and device activity; no Python call tracing, which
    would slow the host path that is being measured."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return opts


def load(log_dir: str):
    """The newest ``.xplane.pb`` under ``log_dir``."""
    import jax

    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        return None
    return jax.profiler.ProfileData.from_file(max(paths,
                                                  key=os.path.getmtime))


def extract(profile, span_names: Iterable[str],
            ) -> Tuple[Dict[str, List[Interval]], List[Interval]]:
    """Device operation intervals per device plane, and the host spans
    whose names are in ``span_names``."""
    names = set(span_names)
    devices: Dict[str, List[Interval]] = {}
    spans: List[Interval] = []
    for plane in profile.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {line.name: line for line in plane.lines}
            line = next((lines[n] for n in OPS_LINES if n in lines), None)
            if line is not None:
                devices[plane.name] = [
                    (int(e.start_ns), int(e.start_ns + e.duration_ns), e.name)
                    for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend(
                    (int(e.start_ns), int(e.start_ns + e.duration_ns), e.name)
                    for e in line.events if e.name in names)
    return devices, spans


def union(intervals: Iterable[Interval], lo: int, hi: int,
          ) -> List[Tuple[int, int]]:
    """Merged ``[start, end)`` stretches of ``intervals`` clipped to
    ``[lo, hi)``."""
    merged: List[Tuple[int, int]] = []
    for s, e, _ in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1] = (merged[-1][0], max(merged[-1][1], e))
        else:
            merged.append((s, e))
    return merged


def gaps(busy: Sequence[Tuple[int, int]], lo: int, hi: int,
         ) -> List[Tuple[int, int]]:
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


def label_gaps(idle: Sequence[Tuple[int, int]], spans: Sequence[Interval],
               ) -> Dict[str, float]:
    """Idle seconds per innermost host span holding each gap's midpoint
    (``"unlabelled"`` where none does)."""
    totals: Dict[str, float] = defaultdict(float)
    if spans:
        st = np.array([s for s, _, _ in spans], np.int64)
        en = np.array([e for _, e, _ in spans], np.int64)
        length = en - st
    for s, e in idle:
        name = "unlabelled"
        if spans:
            mid = (s + e) // 2
            inside = np.flatnonzero((st <= mid) & (en >= mid))
            if inside.size:
                name = spans[int(inside[np.argmin(length[inside])])][2]
        totals[name] += (e - s) / 1e9
    return totals


def summarize(devices: Dict[str, List[Interval]], spans: List[Interval],
              window: Tuple[int, int]) -> Optional[dict]:
    """Busy seconds (averaged over devices), window seconds, the top
    device operations by time and the idle seconds by host span, all
    inside ``window``.  ``None`` where no device operation ran in it."""
    lo, hi = window
    if not devices or hi <= lo:
        return None
    busy_s, ops_s = [], defaultdict(float)
    idle_by_span: Dict[str, float] = defaultdict(float)
    for ivs in devices.values():
        busy = union(ivs, lo, hi)
        busy_s.append(sum(e - s for s, e in busy) / 1e9)
        for s, e, name in ivs:
            s, e = max(s, lo), min(e, hi)
            if e > s:
                ops_s[name] += (e - s) / 1e9
        for name, sec in label_gaps(gaps(busy, lo, hi), spans).items():
            idle_by_span[name] += sec / len(devices)
    busy = float(np.mean(busy_s))
    if busy <= 0:
        return None
    by_time = lambda kv: -kv[1]  # noqa: E731
    return {
        "busy_s": busy,
        "window_s": (hi - lo) / 1e9,
        "device_ops": [[n, s] for n, s in
                       sorted(ops_s.items(), key=by_time)[:TOP]],
        "idle_gaps": [[n, s] for n, s in
                      sorted(idle_by_span.items(), key=by_time)[:TOP]],
    }


def reduce_dir(log_dir: str, span_names: Iterable[str],
               window_span: str) -> Optional[dict]:
    """Load the trace under ``log_dir`` and summarize it over the
    extent of the host span named ``window_span``."""
    profile = load(log_dir)
    if profile is None:
        return None
    names = set(span_names) | {window_span}
    devices, spans = extract(profile, names)
    windows = [(s, e) for s, e, n in spans if n == window_span]
    if not windows:
        return None
    inner = [sp for sp in spans if sp[2] != window_span]
    return summarize(devices, inner, max(windows, key=lambda w: w[1] - w[0]))
