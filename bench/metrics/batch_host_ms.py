"""Serving: host time a batch costs each worker, from the program's spans
in the traced window: the window less the worker's ``serving.idle`` and
``serving.device_wait`` time, over the batches it ended there, averaged
over the workers that ended one (ms).  Read as
``batch_host_ms.throughput``, which moves ``images_per_s``."""


def read(r):
    s = r.get("spans")
    if not s:
        return None
    per = [(s["window_s"] - w["idle_s"] - w["device_wait_s"]) / w["batches"]
           for w in s["workers"].values() if w["batches"]]
    return 1e3 * sum(per) / len(per) if per else None
