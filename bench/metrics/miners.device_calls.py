"""Fused device dispatches per mining job (``stats.device_calls``)."""


def read(rec):
    calls = [j["device_calls"] for j in rec["jobs"] if "device_calls" in j]
    return sum(calls) / len(calls) if calls else None
