"""Kernels: the decode steps' share of the chip's HBM bandwidth, over the
traced requests: the bytes the steps must move (``bench/lm_cost.py``:
every parameter once, and the keys and values each step attends to) over
their device time (``decode_step_ms``'s) times the peak bytes a second
(%).  Moves ``p50_ms``."""


def read(r):
    lm = (r.get("traced") or {}).get("lm")
    if not lm or not lm["decode_device_s"] or not lm["decode_bytes"]:
        return None
    return 100 * lm["decode_bytes"] / (lm["decode_device_s"]
                                       * r["peak_hbm_bytes_per_s"])
