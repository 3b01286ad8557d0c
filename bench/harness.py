"""One benchmark cell: set-up, a closed-loop window of mining jobs, the
check against the plain reference, and the result line.

Everything that belongs to one configuration, traffic mix, engine or
per-layer metric is a file of its own, found by the name that
``BENCHMARK.json`` gives it:

* ``configs/<config>.json``  generator, sizes, rungs, engine, settings
* ``traffic/<traffic>.json`` which rung a cell mines, and how jobs arrive
* ``engines/<engine>.py``    ``build(settings)``, ``job(miner, bdb,
                             minsup) -> (result, stats)`` and ``SPANS``
* ``metrics/<name>.py``      ``read(record) -> value or None``

The window runs jobs back to back, one at a time, until ``seconds``
have passed; the job still running then is finished and the window
ends when it completes.  ``mine_s`` is the window's length over the
jobs it completed.  Once the window has closed and the device's peak
memory is read, the plain reference mines the same rows, and every
job's itemset -> support map is compared with it.
"""

from __future__ import annotations

import contextlib
import functools
import gc
import importlib.util
import json
import sys
import tempfile
import time
from pathlib import Path
from typing import Callable, Dict, List, Optional

from . import compiles as compiles_mod
from . import data, reference
from . import trace as trace_mod

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
MAX_WARMUP_JOBS = 6
WINDOW_SPAN = "window"


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def span(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)


@contextlib.contextmanager
def instrumented(targets):
    """Wrap each ``(span name, owner, attribute)`` call in a host span
    for the duration of the block."""
    saved = []
    try:
        for name, owner, attr in targets:
            fn = getattr(owner, attr, None)
            if fn is None:
                continue

            @functools.wraps(fn)
            def wrapped(*a, _fn=fn, _name=name, **kw):
                with span(_name):
                    return _fn(*a, **kw)

            saved.append((owner, attr, fn))
            setattr(owner, attr, wrapped)
        yield
    finally:
        for owner, attr, fn in reversed(saved):
            setattr(owner, attr, fn)


def device_sync() -> None:
    """Wait until every operation queued on the device has finished."""
    import jax
    import jax.numpy as jnp

    jax.block_until_ready(jnp.zeros((), jnp.int32) + 1)


def peak_bytes() -> Optional[int]:
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return int(peak) if peak is not None else None


def chips_ok(chips: int) -> bool:
    """The run needs ``chips`` TPU devices; report and refuse otherwise."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        log(f"needs {chips} TPU chip(s); JAX finds "
            f"{len(devices)} {devices[0].platform!r} device(s)")
        return False
    return True


def describe_device(peak: Optional[int]) -> dict:
    import jax

    dev = jax.devices()[0]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": jax.device_count(), "memory_peak_bytes": peak}


class Cell:
    """A workload of ``BENCHMARK.json`` with its configuration, traffic
    and engine loaded from their files."""

    def __init__(self, name: str, root: Path = ROOT,
                 engine: Optional[str] = None):
        self.spec = load_json(root / "BENCHMARK.json")
        by_name = {w["name"]: w for w in self.spec["workloads"]}
        if name not in by_name:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                           f"(have {sorted(by_name)})")
        self.name = name
        self.workload = by_name[name]
        cfg = {c["name"]: c for c in self.spec["configs"]}[
            self.workload["config"]]
        self.config = load_json(root / cfg["file"])
        self.traffic = load_json(BENCH / "traffic"
                                 / f"{self.workload['traffic']}.json")
        self.engine = load_module(BENCH / "engines"
                                  / f"{engine or self.config['engine']}.py")

    def applies(self, metric: dict) -> bool:
        return self.name in metric.get("workloads", [self.name])

    def metric_readers(self) -> Dict[str, Callable]:
        """The per-layer metrics this cell reports, by name."""
        return {m["name"]: load_module(BENCH / "metrics" / f"{m['name']}.py")
                for m in self.spec["per_layer"] if self.applies(m)}

    def units(self, kind: str) -> Dict[str, str]:
        return {m["name"]: m["unit"] for m in self.spec[kind]
                if self.applies(m)}


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             t_start: float, scale: float = 1.0) -> dict:
    """Run one cell and return its result line as a dict (``checks``
    last).  The caller has already made sure the device is the one the
    cell asks for."""
    cfg, traffic = cell.config, cell.traffic
    if traffic.get("loop") != "closed" or traffic.get("clients") != 1:
        raise ValueError(f"traffic {cell.workload['traffic']!r}: only a "
                         f"closed loop with one client is implemented")
    counter = compiles_mod.Compiles()

    with span("setup.pack"):
        t0 = time.perf_counter()
        params = dict(cfg["params"])
        bdb, minsups = data.pack(cfg["generator"], params, cfg["rungs"],
                                 seed=params.pop("data_seed"),
                                 order_seed=seed, scale=scale,
                                 block_words=cfg["block_words"])
        minsup = minsups[traffic["rung"]]
        rows = data.rung(bdb, minsup)
        del bdb
        log(f"{cell.name}: seed {seed}, {rows.n_trans} transactions, "
            f"{rows.n_items} items frequent at minsup {minsup}, "
            f"{rows.n_blocks} blocks x {rows.block_words} words; packed in "
            f"{time.perf_counter() - t0:.3f}s")

    engine = cell.engine
    targets = engine.SPANS if trace else []
    with instrumented(targets):
        miner = engine.build(cfg["miner"])
        with span("setup.warmup"):
            for i in range(MAX_WARMUP_JOBS):
                before = (counter.backends, counter.misses, counter.hits)
                t0 = time.perf_counter()
                engine.job(miner, rows, minsup)
                device_sync()
                built, fresh, hits = (a - b for a, b in zip(
                    (counter.backends, counter.misses, counter.hits), before,
                    strict=True))
                log(f"warm-up job {i}: {time.perf_counter() - t0:.3f}s, "
                    f"{built} compiles ({fresh} fresh, {hits} from the "
                    f"persistent cache)")
                if i > 0 and built == 0:
                    break

        results: List[dict] = []
        stats: List[dict] = []
        with contextlib.ExitStack() as stack:
            if trace:
                import jax

                tmp = stack.enter_context(tempfile.TemporaryDirectory())
                jax.profiler.start_trace(
                    tmp, profiler_options=trace_mod.profiler_options())
            compiled_before = counter.backends
            t_w0 = time.perf_counter()
            with span(WINDOW_SPAN):
                while True:
                    with span("job"):
                        out, st = engine.job(miner, rows, minsup)
                    results.append(out)
                    stats.append(st)
                    if time.perf_counter() - t_w0 >= seconds:
                        break
                device_sync()
            t_w1 = time.perf_counter()
            window_compiles = counter.backends - compiled_before
            summary = None
            if trace:
                import jax

                jax.profiler.stop_trace()
                names = [n for n, _, _ in targets] + ["job"]
                summary = trace_mod.reduce_dir(tmp, names, WINDOW_SPAN)

    peak = peak_bytes()
    del miner
    gc.collect()
    n_jobs = len(results)
    log(f"window: {n_jobs} jobs in {t_w1 - t_w0:.3f}s; compiles inside "
        f"the window: {window_compiles}")

    with span("reference"):
        t0 = time.perf_counter()
        want = reference.frequent_itemsets(rows.bitmaps, rows.items, minsup)
        diffs = [reference.compare(got, want) for got in results]
        log(f"reference: {len(want)} itemsets in "
            f"{time.perf_counter() - t0:.3f}s")
    wrong = [sum(d.values()) for d in diffs]
    failed = sum(1 for w in wrong if w)
    worst = max(range(n_jobs), key=lambda i: wrong[i])
    checks = {"wrong_itemsets": {"value": sum(wrong), "limit": 0}}
    log(f"worst job: {diffs[worst]} against {len(want)} reference "
        f"itemsets")

    metrics: Dict[str, dict] = {}
    device = describe_device(peak)
    if trace:
        rec = {"jobs": stats, "trace": summary}
        units = cell.units("per_layer")
        for name, reader in cell.metric_readers().items():
            value = reader.read(rec)
            if value is not None:
                metrics[name] = {"value": value, "unit": units[name]}
        if summary is not None:
            device["busy_s"] = summary["busy_s"]
            device["window_s"] = summary["window_s"]
    else:
        values = {"setup_s": t_w0 - t_start,
                  "mine_s": (t_w1 - t_w0) / n_jobs,
                  "peak_hbm_gib": None if peak is None else peak / 2**30}
        units = cell.units("end_to_end")
        metrics = {n: {"value": v, "unit": units[n]}
                   for n, v in values.items() if n in units and v is not None}

    correct = n_jobs > 0 and all(c["value"] <= c["limit"]
                                 for c in checks.values())
    line = {"correct": correct, "attempted": n_jobs, "failed": failed,
            "metrics": metrics, "device": device}
    if trace and summary is not None:
        line["breakdown"] = {"device_ops": summary["device_ops"],
                             "idle_gaps": summary["idle_gaps"]}
    line["window_compiles"] = window_compiles
    line["checks"] = checks
    return line


def print_result(line: dict) -> None:
    for name, c in line["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
