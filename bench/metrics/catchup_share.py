"""Session: decode steps that caught a prompt's tail up past its prefill
bucket, over all decode steps, in the window, from the LM session's
counters (``LMSession.counters()``) read at the window's two ends
(``readings["window"]``) (%).  Moves ``p50_ms``."""


def read(r):
    w = r.get("window") or {}
    if not w.get("decode_steps"):
        return None
    return 100 * w["catchup_steps"] / w["decode_steps"]
