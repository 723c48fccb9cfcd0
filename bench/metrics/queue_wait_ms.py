"""Serving: mean time a request queued before its batch formed, over the
requests of the batches that ended in the traced window (the
``wait_us_sum`` args of their ``serving.batch`` spans) (ms).  Read as
``queue_wait_ms.latency``, which moves ``p50_ms``."""


def read(r):
    s = r.get("spans")
    if not s or not s["requests"]:
        return None
    return s["wait_us_sum"] / s["requests"] / 1e3
