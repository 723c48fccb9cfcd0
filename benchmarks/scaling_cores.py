"""Multi-core scaling benchmark: intra-op vs inter-op parallelism over one
InferenceSession artifact — the repo's measured Figure 4.

NeoCPU's scalability figure sweeps thread counts over one CPU.  Here the
cores are JAX host devices (``launch.cpu.configure_cpu_devices``) and the
two ways to spend them are measured against each other from the *same*
saved artifact:

* **intra-op** — one sharded program per device count: the artifact is
  re-targeted with ``InferenceSession.load(art, devices=d)`` so each
  device executes the per-core NCHW[x]c program at sub-batch ``B/d``
  (``shard_map`` over the batch axis), and a full bucket is timed through
  ``predict``;
* **inter-op** — data-parallel replicas: the single-device artifact is
  served through ``AsyncServer(workers=w)``, whose workers execute
  whole-bucket batches concurrently on distinct devices.

Both curves come out of ``harness.measure_paired`` (interleaved paired
medians, phase-noise-robust) and land in ``BENCH_scaling.json``, along
with an fp32-tolerance equivalence check of every sharded program against
the single-device reference (different program shapes, so bit-equality is
not expected — row-level tolerance is).

``--smoke`` (CI, 2 host devices on the runner) asserts equivalence holds
and that the better of the two levers reaches ``--min-speedup`` (default
1.3x) over single-device at the largest bucket.

    PYTHONPATH=../src python scaling_cores.py --smoke \
        --out ../BENCH_scaling.json
"""
from __future__ import annotations

import argparse
import json
import time
from pathlib import Path

import numpy as np


def build_artifact(model: str, image: int, buckets, tmpdir: Path):
    """One source-packed single-device artifact with every bucket
    specialized — both curves re-target / serve this same directory."""
    from repro.engine import compile as compile_session

    sess = compile_session(model, (1, 3, image, image))
    for b in sorted(set(buckets)):
        sess.specialize(b)
    art = tmpdir / "artifact"
    sess.save(art)
    return art


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--model", default="resnet-18")
    ap.add_argument("--image", type=int, default=32)
    ap.add_argument("--buckets", default="4,8",
                    help="batch buckets for the intra-op curve; the "
                         "largest one carries the inter-op curve and the "
                         "smoke gate")
    ap.add_argument("--devices", default="1,2",
                    help="device counts for the intra-op (sharded) curve; "
                         "the max also bounds --workers replicas")
    ap.add_argument("--workers", default="1,2",
                    help="worker counts for the inter-op (replica) curve")
    ap.add_argument("--requests", type=int, default=8,
                    help="bucket-sized requests per inter-op stream")
    ap.add_argument("--repeats", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--artifact", default=None,
                    help="serve an existing artifact instead of building "
                         "one (must be source-packed and have --buckets "
                         "specialized)")
    ap.add_argument("--out", default="BENCH_scaling.json")
    ap.add_argument("--min-speedup", type=float, default=1.3,
                    help="--smoke gate: best multi-core speedup over "
                         "single-device at the largest bucket")
    ap.add_argument("--smoke", action="store_true",
                    help="CI mode: small sweep + hard assertions "
                         "(equivalence, >= --min-speedup scaling)")
    args = ap.parse_args()

    buckets = sorted({int(b) for b in args.buckets.split(",")})
    devices = sorted({int(d) for d in args.devices.split(",")})
    workers = sorted({int(w) for w in args.workers.split(",")})
    if args.smoke:
        args.repeats = min(args.repeats, 6)

    # Host devices must exist before the first jax computation; this
    # merges into any user-set XLA_FLAGS and only warns (never fails) on
    # oversubscribed hosts.
    from repro.launch.cpu import configure_cpu_devices
    configure_cpu_devices(max(devices + workers), warn_oversubscribe=False)

    import jax
    import jax.numpy as jnp

    import harness
    from repro.engine import (AsyncServer, DynamicBatchPolicy,
                              InferenceSession)

    if args.artifact is None:
        import tempfile
        tmp = tempfile.TemporaryDirectory(prefix="neocpu_scaling_bench_")
        art = build_artifact(args.model, args.image, buckets,
                             Path(tmp.name))
    else:
        art = Path(args.artifact)

    rng = np.random.default_rng(args.seed)
    top = max(buckets)

    # --- intra-op: one sharded session per device count --------------------
    t0 = time.perf_counter()
    sessions = {d: InferenceSession.load(art, devices=d) if d > 1
                else InferenceSession.load(art) for d in devices}
    t_load = time.perf_counter() - t0
    (name,) = sessions[devices[0]].input_spec
    tail = sessions[devices[0]].input_spec[name][1:]

    intra = []
    equivalence_ok = True
    for b in buckets:
        x = jnp.asarray(rng.normal(size=(b,) + tail).astype(np.float32))
        runnable = [d for d in devices if b % d == 0]
        models = {d: sessions[d].specialize(b) for d in runnable}
        ref = np.asarray(models[runnable[0]].predict(x))
        timings = harness.measure_paired(
            [lambda m=models[d]: m.predict(x) for d in runnable],
            repeats=args.repeats)
        base_ms = timings[0].median_ms
        for d, t in zip(runnable, timings):
            diff = float(np.abs(np.asarray(models[d].predict(x))
                                - ref).max())
            close = bool(np.allclose(np.asarray(models[d].predict(x)), ref,
                                     rtol=1e-4, atol=1e-4))
            equivalence_ok &= close
            intra.append({"bucket": b, "devices": d,
                          **t.to_json(),
                          "speedup": round(base_ms / t.median_ms, 3),
                          "max_abs_diff": diff,
                          "allclose_vs_single": close})
        skipped = sorted(set(devices) - set(runnable))
        if skipped:
            print(f"bucket {b}: skipped devices {skipped} "
                  f"(bucket not divisible)")

    # --- inter-op: replica workers over one single-device session ----------
    session1 = sessions[devices[0]]
    xs = [jnp.asarray(rng.normal(size=(top,) + tail).astype(np.float32))
          for _ in range(args.requests)]
    policy = DynamicBatchPolicy(max_batch=top, max_wait_ms=1.0,
                                fixed_bucket=top)

    def serve_stream(w):
        with AsyncServer(session1, policy, max_queue=len(xs),
                         workers=w) as srv:
            futs = [srv.submit(x) for x in xs]
            out = [f.result() for f in futs]
        return out[-1]

    inter_timings = harness.measure_paired(
        [lambda w=w: serve_stream(w) for w in workers],
        repeats=args.repeats)
    inter_base = inter_timings[0].median_ms
    inter = [{"bucket": top, "workers": w, **t.to_json(),
              "speedup": round(inter_base / t.median_ms, 3)}
             for w, t in zip(workers, inter_timings)]

    intra_top = [r for r in intra if r["bucket"] == top]
    best_intra = max((r["speedup"] for r in intra_top), default=1.0)
    best_inter = max((r["speedup"] for r in inter), default=1.0)
    record = {
        "benchmark": "scaling_cores",
        "artifact": str(art),
        "model": session1.model_name,
        "input_spec": {k: list(v)
                       for k, v in session1.input_spec.items()},
        "buckets": buckets,
        "device_counts": devices,
        "worker_counts": workers,
        "host_devices": len(jax.devices()),
        "load_ms": round(t_load * 1e3, 1),
        "intra_op": intra,
        "inter_op": inter,
        "equivalence_fp32_ok": equivalence_ok,
        "best_speedup": {"intra_op": best_intra, "inter_op": best_inter,
                         "bucket": top},
    }
    Path(args.out).write_text(json.dumps(record, indent=2))

    print(f"artifact={art} host_devices={len(jax.devices())} "
          f"buckets={buckets}")
    for r in intra:
        print(f"intra-op  bucket={r['bucket']:3d} devices={r['devices']} "
              f"{r['median_ms']:8.1f} ms  {r['speedup']:.2f}x  "
              f"max|diff|={r['max_abs_diff']:.2e}")
    for r in inter:
        print(f"inter-op  bucket={r['bucket']:3d} workers={r['workers']} "
              f"{r['median_ms']:8.1f} ms/stream  {r['speedup']:.2f}x")
    print(f"wrote {args.out}")

    if args.smoke:
        assert equivalence_ok, \
            "sharded programs drifted past fp32 tolerance of single-device"
        best = max(best_intra, best_inter)
        assert best >= args.min_speedup, \
            (f"multi-core scaling {best:.2f}x < {args.min_speedup}x at "
             f"bucket {top} (intra {best_intra:.2f}x, "
             f"inter {best_inter:.2f}x)")
        print(f"smoke assertions passed (equivalence ok, "
              f"{best:.2f}x >= {args.min_speedup}x)")


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    main()
