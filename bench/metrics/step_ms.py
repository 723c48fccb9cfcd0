"""Executor: device time of one forward program, averaged over the
forwards that ran in the traced window (ms).  Read as
``step_ms.latency``, which moves ``p50_ms``."""


def read(r):
    t = r.get("traced")
    if not t or not t["forward_n"]:
        return None
    return t["forward_s"] * t["chips_traced"] / t["forward_n"] * 1e3
