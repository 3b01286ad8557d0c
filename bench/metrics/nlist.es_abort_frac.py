"""Share of the candidate N-list merges that early stopping aborted:
``es_aborts / candidates`` over the window's jobs."""


def read(rec):
    jobs = [j for j in rec["jobs"]
            if j.get("candidates") and j.get("es_aborts") is not None]
    cands = sum(j["candidates"] for j in jobs)
    if cands <= 0:
        return None
    return sum(j["es_aborts"] for j in jobs) / cands
