"""Device milliseconds per job: the union of the device operations'
intervals in the traced window, over the jobs in it."""


def read(rec):
    tr = rec.get("trace")
    if not tr or not rec["jobs"]:
        return None
    return 1e3 * tr["busy_s"] / len(rec["jobs"])
