"""Host milliseconds per job that the frontier scheduler spends blocked
on readbacks when it retires dispatches (``stats.resolve_s``)."""


def read(rec):
    vals = [j["resolve_s"] for j in rec["jobs"] if "resolve_s" in j]
    return 1e3 * sum(vals) / len(vals) if vals else None
