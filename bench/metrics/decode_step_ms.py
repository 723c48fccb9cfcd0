"""Executor: device time of one decode step of the LM session, over the
traced requests: the device seconds of its decode program (the XLA
module ``jit_lm_decode``, whose ops carry the ``lm.decode`` scope) over
the decode steps run, catch-up steps included (ms).  Moves ``p50_ms``."""


def read(r):
    lm = (r.get("traced") or {}).get("lm")
    if not lm or not lm["decode_steps"] or not lm["decode_device_s"]:
        return None
    return 1e3 * lm["decode_device_s"] / lm["decode_steps"]
