"""Share of the N-list codes the merge dispatches gathered that were
padding: ``1 - nl_codes / nl_gather_codes`` over the window's jobs
(real operand codes against codes gathered at the padded widths)."""


def read(rec):
    jobs = [j for j in rec["jobs"] if j.get("nl_gather_codes")]
    gathered = sum(j["nl_gather_codes"] for j in jobs)
    if gathered <= 0:
        return None
    return 1.0 - sum(j["nl_codes"] for j in jobs) / gathered
