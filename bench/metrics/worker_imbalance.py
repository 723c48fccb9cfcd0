"""Serving: how unevenly the router spread rows over the workers: the
most rows one worker's batches that ended in the traced window held, over
the mean over the cell's ``chips`` workers, less one (%).  Read as
``worker_imbalance.throughput``, which moves ``images_per_s``."""


def read(r):
    s = r.get("spans")
    if not s:
        return None
    rows = [w["rows"] for w in s["workers"].values()]
    if not sum(rows):
        return None
    return 100 * (max(rows) * r["chips"] / sum(rows) - 1)
