"""Host milliseconds per job of the PPC-tree build and the upload of its
N-lists to the device pool (``stats.build_s``, the ``ppc.build`` span)."""


def read(rec):
    vals = [j["build_s"] for j in rec["jobs"] if j.get("build_s") is not None]
    return 1e3 * sum(vals) / len(vals) if vals else None
