"""Host runtime: the share of the measured window the garbage collector
held a thread paused (%), from the program's own counters
(``repro.engine.telemetry.gc_pauses()``) read at the window's two ends:
``readings["gc"]`` (``spans.gc_window``) holds the pause seconds between
the two readings and the window's seconds.  Read as
``gc_pause_share.latency``, which moves ``p50_ms``."""


def read(r):
    g = r.get("gc")
    if not g or not g["window_s"]:
        return None
    return 100 * g["pause_s"] / g["window_s"]
