"""Int8 (W8 weight-only) vs fp32: agreement, artifact size, and speed.

Compiles the same zoo network twice — fp32 and ``dtype="int8"`` (per-
output-channel symmetric weight quantization, dequant folded into the
fused epilogue) — and reports into ``BENCH_quantized.json``:

* **top-1 agreement** over random calibration inputs (the quantization
  acceptance gate: >= 99% or this benchmark exits non-zero),
* **artifact weight payload** — int8 conv blobs must come in at <= 55%
  of the fp32 artifact (they land near 28%: conv weights are int8, the
  dense/BN tensors stay fp32),
* **paired speed** — interleaved A/B medians via ``harness.measure_paired``
  (phase-noise-resistant on this shared host),
* **mixed precision** — the per-conv schedule dtypes the search actually
  picked (from the artifact's ``quantized.json``: stage-1 convs stay
  fp32, the weight-heavier stages go int8) plus analytical-vs-measured
  dtype verdicts on one weight-heavy workload.

    PYTHONPATH=src python benchmarks/quantized.py --smoke --out .
"""
from __future__ import annotations

import argparse
import json
import shutil
import statistics
import sys
import tempfile
from pathlib import Path

import jax.numpy as jnp
import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent))
from harness import Timing, measure_paired            # noqa: E402

from repro.core.local_search import guided_local_search  # noqa: E402
from repro.core.schedule import ConvWorkload             # noqa: E402
from repro.engine import compile as compile_session      # noqa: E402
from repro.models.cnn import build                       # noqa: E402

MIN_AGREEMENT = 0.99
MAX_PAYLOAD_RATIO = 0.55


def conv_weight_bytes(art: Path) -> int:
    """Blocked conv weight payload of a saved artifact (the tensors the
    quantizer touches; dense/BN stay fp32 in both artifacts)."""
    total = 0
    for f in sorted((art / "weights").rglob("*.npy")):
        arr = np.load(f)
        if arr.ndim >= 5:
            total += arr.nbytes
    return total


def top1_agreement(f32, i8, shape, n_inputs: int, seed: int = 0) -> dict:
    rng = np.random.default_rng(seed)
    agree = 0
    max_rel = 0.0
    for _ in range(n_inputs):
        x = jnp.asarray(rng.normal(size=shape).astype(np.float32))
        yf = np.asarray(f32.predict(x))
        yq = np.asarray(i8.predict(x))
        agree += int(np.array_equal(np.argmax(yf, 1), np.argmax(yq, 1)))
        denom = float(np.max(np.abs(yf))) or 1.0
        max_rel = max(max_rel, float(np.max(np.abs(yf - yq))) / denom)
    return {"n_inputs": n_inputs, "agreement": agree / n_inputs,
            "max_rel_logit_diff": round(max_rel, 6)}


def measured_mixed_demo(smoke: bool) -> dict:
    """The dtype axis through both searches, on one weight-heavy conv.

    The *analytical* ranking prices int8's 4x lighter weight traffic and
    picks it on memory-bound workloads — that is where the mixed plan in
    the artifact comes from.  The *guided wall-clock* search then prices
    what the model cannot see: on this XLA:CPU the int8 weight upcast
    materializes a full fp32 copy per call, so measured cost usually
    keeps fp32 unless int8 lands within the noise floor (where the
    analytical tiebreak prefers its lighter traffic).  Both verdicts are
    recorded — the disagreement is the finding."""
    from repro.core.local_search import local_search, roofline_runner
    wl = ConvWorkload(batch=1, in_channels=256, out_channels=256,
                      height=14, width=14, kh=3, kw=3, pad=1,
                      fused_bn=True, fused_relu=True, quantize=True)
    analytical = local_search(wl, roofline_runner)
    res = guided_local_search(wl, top_k=2 if smoke else 4, per_variant=1,
                              repeats=2 if smoke else 3)
    ranked = [{"variant": r.schedule.resolved_variant(),
               "dtype": r.schedule.dtype,
               "ic_bn": r.schedule.ic_bn, "oc_bn": r.schedule.oc_bn,
               "cost_ms": round(r.cost_s * 1e3, 3)}
              for r in res.ranked]
    return {"workload": "n1_c256_k256_h14_w14_r3s3",
            "analytical_winner_dtype": analytical.best.dtype,
            "measured": ranked, "measured_winner_dtype": res.best.dtype}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--model", default="resnet-18")
    ap.add_argument("--image", type=int, default=80,
                    help="reduced input resolution (full 224 compiles "
                         "for minutes on this 2-vCPU host).  80 keeps the "
                         "global-pool window large enough (3x3 per stage) "
                         "that W8 logit noise averages out: top-1 flip "
                         "rate vs fp32 is ~0.6% here vs ~3% at 56-64, "
                         "where the 2x2 pool leaves single-position noise "
                         "in the logits")
    ap.add_argument("--batch", type=int, default=1)
    ap.add_argument("--inputs", type=int, default=100,
                    help="random calibration inputs for the agreement gate")
    ap.add_argument("--repeats", type=int, default=30)
    ap.add_argument("--smoke", action="store_true",
                    help="CI budget: fewer inputs/repeats, smaller search")
    ap.add_argument("--out", default=".",
                    help="directory for BENCH_quantized.json")
    args = ap.parse_args()
    if args.smoke:
        args.inputs = min(args.inputs, 8)
        args.repeats = min(args.repeats, 10)

    g, shapes = build(args.model, batch=args.batch, image=args.image)
    g2, _ = build(args.model, batch=args.batch, image=args.image)
    (shape,) = shapes.values()
    print(f"compiling {args.model} @ {shape} fp32 ...", flush=True)
    f32 = compile_session(g, shapes, seed=0)
    print("compiling int8 twin ...", flush=True)
    i8 = compile_session(g2, shapes, seed=0, dtype="int8")

    agreement = top1_agreement(f32, i8, shape, args.inputs)
    print(f"top-1 agreement {agreement['agreement']:.3f} over "
          f"{args.inputs} inputs "
          f"(max rel logit diff {agreement['max_rel_logit_diff']:.2e})",
          flush=True)

    tmp = Path(tempfile.mkdtemp(prefix="bench_quantized_"))
    try:
        a32 = f32.save(tmp / "fp32")
        a8 = i8.save(tmp / "int8")
        b32, b8 = conv_weight_bytes(a32), conv_weight_bytes(a8)
        ratio = b8 / b32
        dtypes = json.loads((a8 / "quantized.json").read_text())[
            "schedule_dtypes"][str(args.batch)]
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    n_i8 = sum(d == "int8" for d in dtypes.values())
    print(f"conv weight payload: int8 {b8 / 1e6:.2f} MB vs "
          f"fp32 {b32 / 1e6:.2f} MB ({ratio:.1%}); "
          f"plan: {n_i8}/{len(dtypes)} convs int8", flush=True)

    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=shape).astype(np.float32))
    t32, t8 = measure_paired([lambda: f32.predict(x),
                              lambda: i8.predict(x)],
                             repeats=args.repeats)
    speedup = t32.median_ms / t8.median_ms
    print(f"latency: fp32 {t32.median_ms:.2f} ms, int8 {t8.median_ms:.2f} "
          f"ms (paired-median speedup {speedup:.3f}x)", flush=True)

    mixed = measured_mixed_demo(args.smoke)
    print(f"search on {mixed['workload']}: analytical winner "
          f"{mixed['analytical_winner_dtype']}, measured winner "
          f"{mixed['measured_winner_dtype']}", flush=True)

    report = {
        "model": args.model, "image": args.image, "batch": args.batch,
        "smoke": args.smoke,
        "agreement": agreement,
        "artifact": {"conv_weight_bytes_fp32": b32,
                     "conv_weight_bytes_int8": b8,
                     "payload_ratio": round(ratio, 4),
                     "schedule_dtypes": dtypes,
                     "n_int8_convs": n_i8, "n_convs": len(dtypes)},
        "latency": {"fp32": t32.to_json(), "int8": t8.to_json(),
                    "speedup": round(speedup, 4)},
        "measured_mixed_precision": mixed,
    }
    out = Path(args.out) / "BENCH_quantized.json"
    out.write_text(json.dumps(report, indent=1))
    print(f"wrote {out}", flush=True)

    failures = []
    if agreement["agreement"] < MIN_AGREEMENT:
        failures.append(f"top-1 agreement {agreement['agreement']:.3f} "
                        f"< {MIN_AGREEMENT}")
    if ratio > MAX_PAYLOAD_RATIO:
        failures.append(f"int8 weight payload {ratio:.1%} of fp32 "
                        f"(> {MAX_PAYLOAD_RATIO:.0%})")
    if n_i8 == 0:
        failures.append("search selected int8 for zero convs")
    for f in failures:
        print(f"GATE FAILED: {f}", file=sys.stderr, flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    raise SystemExit(main())
