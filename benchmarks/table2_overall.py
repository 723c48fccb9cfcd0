"""Paper Table 2: overall inference latency per network.

Columns here: measured XLA-CPU wall time for the NCHW baseline graph vs the
fully-planned (global-search) graph, and the v5e roofline-model predicted
latency for both — the prediction is what carries the paper's ladder to the
TPU target; the measured pair shows the planned graph is never semantically
or pathologically worse end-to-end on the host.

Measurement rides on ``benchmarks/harness.py`` (warmup-phase detection +
interleaved paired medians): both graphs of a network are timed round-robin
within each round, so a noisy phase on this shared host hits both equally
and the reported medians stay comparable.

Default: the paper's 5 ablation networks (one per family).  --full: all 15
(slow on 1 CPU core).  batch=1, full image sizes, as in the paper.
"""
from __future__ import annotations

import argparse

from benchmarks.common import emit, prepare
from benchmarks.harness import measure_paired


# measured subset for the default run (1 CPU core); --full = all 15
ABLATION_SET = ["resnet-50", "vgg-19", "inception-v3"]
FULL_SET = [f"resnet-{d}" for d in (18, 34, 50, 101, 152)] \
    + [f"vgg-{d}" for d in (11, 13, 16, 19)] \
    + [f"densenet-{d}" for d in (121, 161, 169, 201)] \
    + ["inception-v3", "ssd-resnet-50"]


def run(models, repeats: int = 3):
    rows = []
    for name in models:
        m0, x, p0 = prepare(name, "nchw")
        m1, _, p1 = prepare(name, "global-search")
        t0, t1 = measure_paired(
            [lambda: m0.predict(x), lambda: m1.predict(x)], repeats=repeats)
        rows.append((f"table2/{name}/nchw-measured", t0.median_ms * 1e3,
                     f"pred_v5e_us={p0.predicted_total_s * 1e6:.1f};"
                     f"min_ms={t0.min_ms:.2f};warmup={t0.warmup_rounds}"))
        rows.append((f"table2/{name}/planned-measured", t1.median_ms * 1e3,
                     f"pred_v5e_us={p1.predicted_total_s * 1e6:.1f};"
                     f"pred_speedup="
                     f"{p0.predicted_total_s / p1.predicted_total_s:.2f}x;"
                     f"measured_speedup={t0.median_ms / t1.median_ms:.2f}x;"
                     f"transforms={p1.planned.n_transforms};"
                     f"solver={p1.solution.method if p1.solution else '-'}"))
        print(f"# {name}: measured {t0.median_ms:.1f} -> {t1.median_ms:.1f} "
              f"ms (paired medians, {t0.warmup_rounds} warmup rounds) | "
              f"v5e predicted {p0.predicted_total_s * 1e3:.3f} -> "
              f"{p1.predicted_total_s * 1e3:.3f} ms", flush=True)
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--repeats", type=int, default=3)
    args = ap.parse_args(argv)
    rows = run(FULL_SET if args.full else ABLATION_SET, args.repeats)
    emit(rows)
    return rows


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    main()
