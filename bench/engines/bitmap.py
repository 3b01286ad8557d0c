"""Engine adapter: one mining job is one ``BitmapMiner.mine_packed`` call.

A job takes the host-packed ``BitmapDB`` to the complete itemset ->
support dict on the host: the row store's build and upload, the
frontier scheduler, the fused dispatches and their kernels, and the
readbacks.  ``settings`` are passed to ``BitmapMiner`` as keyword
arguments; every other knob keeps the program's default.

``SPANS`` names the calls into each layer that a traced run wraps in
host spans, so that the trace can say what the host was doing while
the device was idle.  An untraced run wraps nothing.
"""

from __future__ import annotations

from repro.core.eclat import BitmapMiner
from repro.core.frontier import FrontierScheduler
from repro.core.rowstore import DeviceRowStore

SPANS = [
    ("store.init", DeviceRowStore, "__init__"),
    ("store.grow", DeviceRowStore, "_grow"),
    ("store.compact", DeviceRowStore, "compact"),
    ("sched.assemble", FrontierScheduler, "_assemble"),
    ("sched.dispatch", BitmapMiner, "evaluate_pairs"),
    ("sched.resolve", FrontierScheduler, "_resolve"),
]


def build(settings: dict):
    return BitmapMiner(**settings)


def job(miner, bdb, minsup: int):
    """Mine once; return the result and the job's accounting."""
    out, st = miner.mine_packed(bdb, minsup)
    return out, {
        "device_calls": st.device_calls,
        "word_ops": st.word_ops,
        "word_ops_full": st.word_ops_full,
        "assemble_s": st.assemble_s,
        "resolve_s": st.resolve_s,
        "peak_device_words": st.peak_device_words,
        "grows": st.grows,
        "compactions": st.compactions,
        "itemsets": len(out),
    }
