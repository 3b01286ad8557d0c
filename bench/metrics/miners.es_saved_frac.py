"""Share of the word operations a non-early-stopping engine would do
that early stopping skipped: ``1 - word_ops / word_ops_full`` over the
window's jobs."""


def read(rec):
    full = sum(j.get("word_ops_full", 0) for j in rec["jobs"])
    if full <= 0:
        return None
    return 1.0 - sum(j["word_ops"] for j in rec["jobs"]) / full
