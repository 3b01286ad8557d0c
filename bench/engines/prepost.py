"""Engine adapter: one mining job is one ``DevicePrePost.mine_packed`` call.

A job takes the host-packed ``BitmapDB`` to the complete itemset ->
support dict on the host: the PPC-tree build and the upload of its
N-lists to the device pool, the frontier scheduler, the N-list merge
and scatter dispatches, and the readbacks.  ``settings`` are passed to
``DevicePrePost`` as keyword arguments; every other knob keeps the
program's default.

``SPANS`` names the calls into each layer that a traced run wraps in
host spans, so that the trace can say what the host was doing while
the device was idle.  An untraced run wraps nothing.
"""

from __future__ import annotations

import repro.core.prepost as prepost
from repro.core.frontier import FrontierScheduler
from repro.core.prepost import DevicePrePost
from repro.core.rowstore import NListPool

SPANS = [
    ("ppc.build", prepost, "build_nlists"),
    ("sched.assemble", FrontierScheduler, "_assemble"),
    ("sched.dispatch", DevicePrePost, "evaluate_pairs"),
    ("sched.resolve", FrontierScheduler, "_resolve"),
    ("pool.grow", NListPool, "_grow"),
    ("pool.compact", NListPool, "compact"),
]


def build(settings: dict):
    return DevicePrePost(**settings)


def job(miner, bdb, minsup: int):
    """Mine once; return the result and the job's accounting."""
    out, st = miner.mine_packed(bdb, minsup)
    return out, {
        "device_calls": st.device_calls,
        "candidates": st.candidates,
        "es_aborts": st.es_aborts,
        "comparisons": st.comparisons,
        "es_checks": st.es_checks,
        "nl_codes": getattr(st, "nl_codes", None),
        "nl_gather_codes": getattr(st, "nl_gather_codes", None),
        "build_s": getattr(st, "build_s", None),
        "assemble_s": st.assemble_s,
        "resolve_s": st.resolve_s,
        "peak_codes": st.peak_codes,
        "grows": st.grows,
        "compactions": st.compactions,
        "itemsets": len(out),
    }
