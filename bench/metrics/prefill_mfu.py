"""Kernels: the prefills' share of the chip's bf16 peak, over the traced
requests: the operations the prefills must do (``bench/lm_cost.py``:
the layers' matrices for every prefilled token, windowed causal
attention, the head at the last position) over the device seconds of the
prefill programs (the XLA modules ``jit_lm_prefill``, whose ops carry
the ``lm.prefill`` scope) times the peak (%).  Moves ``p50_ms``."""


def read(r):
    lm = (r.get("traced") or {}).get("lm")
    if not lm or not lm["prefill_device_s"] or not lm["prefill_flops"]:
        return None
    return 100 * lm["prefill_flops"] / (lm["prefill_device_s"]
                                        * r["peak_flops_per_s"])
