"""Kernels: the stem convolution's share of the chip's bf16 peak: its
multiply-accumulates (the reference's first convolution, ``stem_macs``,
twice as operations) for the real rows of the batches that ended in the
traced window, over the device time of the operations in its node's scope
(``stem_node``) times the peak (%).  Read as ``stem_roofline.latency``
(moves ``p50_ms``) and ``stem_roofline.throughput`` (moves
``images_per_s``)."""


def read(r):
    s = r.get("spans")
    if not s:
        return None
    device_s = s["node_device_s"].get(r["stem_node"])
    rows = sum(w["rows"] for w in s["workers"].values())
    if not device_s or not rows:
        return None
    return 100 * 2 * r["stem_macs"] * rows / (device_s
                                              * r["peak_flops_per_s"])
