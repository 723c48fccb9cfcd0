"""Serving load benchmark: dynamic batching vs sequential serving of one
InferenceSession artifact, on the shared measurement harness.

Serves the same mixed-size request stream two ways from a cold-loaded
artifact and reports paired medians (``harness.measure_paired``) plus the
driver's latency percentiles into ``BENCH_serving.json``:

* **sequential** — one request at a time through ``padded_predict`` at the
  driver's bucket: the batch=1 serving baseline of the same deterministic
  artifact (every request pays a full bucket execution);
* **driver** — the ``AsyncServer`` packs the stream into bucket-sized
  batches (``DynamicBatchPolicy(fixed_bucket=...)``, so results are
  bit-reproducible regardless of packing);
* **sequential-native** (informational, not part of the acceptance pair) —
  per-request nearest-bucket execution, the fastest non-deterministic
  sequential path.

``--workers N`` runs the driver with N worker threads (per-device program
replicas when the host exposes that many devices — see
``launch.cpu.configure_cpu_devices``); packing stays FIFO and
bucket-fixed, so responses stay bit-identical regardless of worker count.

``--smoke`` (CI, against the ``session_smoke`` artifact) asserts the
driver's responses bit-match sequential serving, the whole serve ran zero
schedule searches, p50/p99 are reported, and the paired-median throughput
gain is >= ``--min-speedup`` (default 2x; the CI multi-core lane raises
it).

    PYTHONPATH=../src python serving_load.py --smoke \
        --artifact ../ARTIFACT_session --out ../BENCH_serving.json
"""
from __future__ import annotations

import argparse
import json
import time
from pathlib import Path

import numpy as np

import harness


def build_requests(session, sizes, n_requests, seed):
    import jax.numpy as jnp

    (name,) = session.input_spec
    tail = session.input_spec[name][1:]
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n_requests):
        rows = sizes[i % len(sizes)]
        out.append(jnp.asarray(
            rng.normal(size=(rows,) + tail).astype(np.float32)))
    return out


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--artifact", default=None,
                    help="saved InferenceSession artifact dir; omitted = "
                         "build one from --model on the fly")
    ap.add_argument("--model", default="resnet-18")
    ap.add_argument("--image", type=int, default=32)
    ap.add_argument("--bucket", type=int, default=8,
                    help="the driver's (and the sequential baseline's) "
                         "execution bucket; must be specialized in the "
                         "artifact")
    ap.add_argument("--sizes", default="1,2,3",
                    help="request row counts, cycled over the stream")
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--max-wait-ms", type=float, default=50.0)
    ap.add_argument("--workers", type=int, default=1,
                    help="AsyncServer worker threads; >1 needs as many "
                         "host devices (see launch.cpu) for the replicas "
                         "to land on distinct cores")
    ap.add_argument("--min-speedup", type=float, default=2.0,
                    help="--smoke gate on the paired-median driver-vs-"
                         "sequential throughput gain")
    ap.add_argument("--repeats", type=int, default=12)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default="BENCH_serving.json")
    ap.add_argument("--smoke", action="store_true",
                    help="CI mode: small stream + hard assertions "
                         "(bit-identical, zero search, >=2x throughput)")
    args = ap.parse_args()

    if args.workers > 1:
        # replicas need that many host devices; must precede the first
        # jax computation (imports alone don't lock the device count)
        from repro.launch.cpu import configure_cpu_devices
        configure_cpu_devices(args.workers, warn_oversubscribe=False)

    import jax
    import jax.numpy as jnp

    from repro.core.local_search import search_calls
    from repro.engine import (AsyncServer, DynamicBatchPolicy,
                              InferenceSession, nearest_bucket,
                              padded_predict)
    from repro.engine import compile as compile_session

    sizes = [int(s) for s in args.sizes.split(",")]
    if args.smoke:
        args.repeats = min(args.repeats, 6)

    if args.artifact is None:
        import tempfile
        tmp = tempfile.TemporaryDirectory(prefix="neocpu_serving_bench_")
        art = Path(tmp.name) / "artifact"
        sess = compile_session(args.model,
                               (1, 3, args.image, args.image))
        for b in sorted({1, args.bucket}):
            sess.specialize(b)
        sess.save(art)
    else:
        art = Path(args.artifact)

    n0 = search_calls()
    t0 = time.perf_counter()
    session = InferenceSession.load(art)
    t_load = time.perf_counter() - t0
    if args.bucket not in session.batch_sizes:
        raise SystemExit(f"--bucket {args.bucket} not specialized in "
                         f"{art} (has {session.batch_sizes})")

    requests = build_requests(session, sizes, args.requests, args.seed)
    total_rows = sum(int(x.shape[0]) for x in requests)

    def serve_sequential():
        out = None
        for x in requests:
            out = jax.block_until_ready(
                padded_predict(session, x, bucket=args.bucket))
        return out

    def serve_native():
        out = None
        for x in requests:
            out = jax.block_until_ready(padded_predict(session, x))
        return out

    policy = DynamicBatchPolicy(max_batch=args.bucket,
                                max_wait_ms=args.max_wait_ms,
                                fixed_bucket=args.bucket)

    def serve_driver():
        with AsyncServer(session, policy, max_queue=len(requests),
                         workers=args.workers) as srv:
            futs = [srv.submit(x) for x in requests]
            outs = [f.result() for f in futs]
        return outs[-1]

    # correctness first: driver responses bit-match sequential serving
    refs = [np.asarray(padded_predict(session, x, bucket=args.bucket))
            for x in requests]
    with AsyncServer(session, policy, max_queue=len(requests),
                     workers=args.workers) as probe:
        futs = [probe.submit(x) for x in requests]
        got = [np.asarray(f.result()) for f in futs]
    probe_stats = probe.stats
    bit_identical = all(a.shape == b.shape and a.tobytes() == b.tobytes()
                        for a, b in zip(got, refs))

    t_seq, t_drv, t_nat = harness.measure_paired(
        [serve_sequential, serve_driver, serve_native],
        repeats=args.repeats)
    n_searches = search_calls() - n0

    speedup = t_seq.median_ms / t_drv.median_ms
    record = {
        "benchmark": "serving_load",
        "artifact": str(art),
        "model": session.model_name,
        "input_spec": {k: list(v) for k, v in session.input_spec.items()},
        "buckets": session.batch_sizes,
        "bucket": args.bucket,
        "request_sizes": sizes,
        "n_requests": args.requests,
        "total_rows": total_rows,
        "max_wait_ms": args.max_wait_ms,
        "workers": args.workers,
        "load_ms": round(t_load * 1e3, 1),
        "sequential": t_seq.to_json(),
        "driver": t_drv.to_json(),
        "sequential_native": t_nat.to_json(),
        "throughput_req_s": {
            "sequential": round(args.requests / (t_seq.median_ms / 1e3), 1),
            "driver": round(args.requests / (t_drv.median_ms / 1e3), 1),
            "sequential_native": round(
                args.requests / (t_nat.median_ms / 1e3), 1),
        },
        "speedup_paired_median": round(speedup, 2),
        "latency_ms": {"p50": round(probe_stats.percentile_ms(50), 2),
                       "p90": round(probe_stats.percentile_ms(90), 2),
                       "p99": round(probe_stats.percentile_ms(99), 2)},
        "driver_stats": probe_stats.to_json(),
        "bit_identical_vs_sequential": bit_identical,
        "schedule_searches": n_searches,
    }
    Path(args.out).write_text(json.dumps(record, indent=2))
    print(f"artifact={art} buckets={session.batch_sizes} "
          f"load={t_load * 1e3:.0f} ms, stream of {args.requests} requests "
          f"({total_rows} rows, sizes {sizes})")
    print(f"sequential  {t_seq.median_ms:8.1f} ms/stream")
    print(f"driver      {t_drv.median_ms:8.1f} ms/stream  "
          f"({speedup:.2f}x, {probe_stats.n_batches} batches, "
          f"{probe_stats.rows_padded} padded rows)")
    print(f"native seq  {t_nat.median_ms:8.1f} ms/stream (informational)")
    print(f"latency p50={record['latency_ms']['p50']} "
          f"p99={record['latency_ms']['p99']} ms  "
          f"bit_identical={bit_identical}  searches={n_searches}")
    print(f"wrote {args.out}")

    if args.smoke:
        assert bit_identical, \
            "driver responses must bit-match sequential serving"
        assert n_searches == 0, \
            f"cold-artifact serving ran {n_searches} schedule searches"
        assert np.isfinite(record["latency_ms"]["p50"]), "p50 missing"
        assert np.isfinite(record["latency_ms"]["p99"]), "p99 missing"
        assert speedup >= args.min_speedup, \
            (f"dynamic batching speedup {speedup:.2f}x < "
             f"{args.min_speedup}x")
        print("smoke assertions passed (bit-identical, zero-search, "
              f"{speedup:.2f}x >= {args.min_speedup}x)")


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    main()
