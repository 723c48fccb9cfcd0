"""Model step: the network's operations for the real (unpadded) images
the forwards executed in the traced window, over the forwards' device time
times the chip's bf16 peak (%).  Read as ``mfu.latency`` (moves
``p50_ms``) and ``mfu.throughput`` (moves ``images_per_s``)."""


def read(r):
    t = r.get("traced")
    if not t or not t["forward_s"] or not t["rows_executed"]:
        return None
    flops = 2 * r["macs_per_image"] * t["rows_executed"]
    device_s = t["forward_s"] * t["chips_traced"]
    return 100 * flops / (device_s * r["peak_flops_per_s"])
