"""High-water size of the row store's device slab, bitmap rows and
suffix tables (``stats.peak_device_words`` x 4 bytes), in GiB."""


def read(rec):
    words = [j["peak_device_words"] for j in rec["jobs"]
             if j.get("peak_device_words")]
    return max(words) * 4 / 2**30 if words else None
