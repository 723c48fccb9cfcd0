"""Device: the share of the traced window in which no operation ran,
averaged over the chips (%).  Read as ``idle_share.throughput``, which
moves ``images_per_s``."""


def read(r):
    t = r.get("traced")
    if not t or not t["window_s"]:
        return None
    return 100 * (1 - t["busy_s"] / t["window_s"])
