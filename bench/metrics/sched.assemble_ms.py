"""Host milliseconds per job that the frontier scheduler spends
assembling pair columns and launching dispatches (``stats.assemble_s``)."""


def read(rec):
    vals = [j["assemble_s"] for j in rec["jobs"] if "assemble_s" in j]
    return 1e3 * sum(vals) / len(vals) if vals else None
