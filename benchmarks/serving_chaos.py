"""Serving chaos benchmark: load under injected faults, gated on zero
lost requests and bit-identical completed responses.

Three probes over one artifact, written to ``BENCH_chaos.json``:

1. **Artifact integrity** — copy the artifact, flip one byte in a weight
   blob and (separately) a plan JSON (``engine.faults.corrupt_artifact``):
   both loads must raise ``ArtifactCorruptError``; the untouched artifact
   must still load and predict.
2. **Clean load run** — the request stream through a healthy 2-worker
   ``AsyncServer``: the p99 baseline.
3. **Chaos load run** — the same stream with scripted faults armed: a
   worker kill mid-stream (supervisor restarts the slot, requeues its
   batch), repeated predict failures (retry/backoff path), and an
   injected straggler batch (delay).  Gates:

   * **zero lost requests** — every submitted future resolves, with a
     result or a typed ``ServingError``; under a sufficient retry budget
     every one completes with a result;
   * **bit-identical** — each completed response equals sequential
     ``padded_predict`` of the same artifact (retried or not, packed or
     not: bucket-shaped programs make re-execution exact);
   * **bounded p99 inflation** — chaos p99 <= clean p99 + injected delay
     + worst-case retry backoff + scheduling slack (crash recovery costs
     bounded latency, not correctness).

``--smoke`` (CI) shrinks the stream and hard-asserts all three gates.

    PYTHONPATH=../src python serving_chaos.py --smoke \
        --out ../BENCH_chaos.json
"""
from __future__ import annotations

import argparse
import json
import shutil
import tempfile
import time
from pathlib import Path

import numpy as np


def build_requests(session, sizes, n_requests, seed):
    import jax.numpy as jnp

    (name,) = session.input_spec
    tail = session.input_spec[name][1:]
    rng = np.random.default_rng(seed)
    return [jnp.asarray(rng.normal(
        size=(sizes[i % len(sizes)],) + tail).astype(np.float32))
        for i in range(n_requests)]


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--artifact", default=None,
                    help="saved InferenceSession artifact dir; omitted = "
                         "build a small CNN artifact on the fly")
    ap.add_argument("--model", default="resnet-18")
    ap.add_argument("--image", type=int, default=32)
    ap.add_argument("--bucket", type=int, default=4,
                    help="driver execution bucket (must be specialized)")
    ap.add_argument("--sizes", default="1,2,1",
                    help="request row counts, cycled over the stream")
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--workers", type=int, default=2)
    ap.add_argument("--retry-budget", type=int, default=3)
    ap.add_argument("--backoff-ms", type=float, default=5.0)
    ap.add_argument("--kill-batch", type=int, default=1,
                    help="global batch sequence the worker kill fires on")
    ap.add_argument("--fail-batches", type=int, default=2,
                    help="number of injected predict failures")
    ap.add_argument("--delay-ms", type=float, default=60.0,
                    help="injected straggler batch delay")
    ap.add_argument("--p99-slack-ms", type=float, default=500.0,
                    help="scheduling slack allowed on top of the modeled "
                         "chaos p99 bound")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default="BENCH_chaos.json")
    ap.add_argument("--smoke", action="store_true",
                    help="CI mode: small stream + hard gate assertions")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from repro.engine import (ArtifactCorruptError, AsyncServer,
                              DelayBatch, DynamicBatchPolicy, FailBatch,
                              FaultInjector, InferenceSession, KillWorker,
                              RetryPolicy, ServingError, corrupt_artifact,
                              padded_predict)
    from repro.engine import compile as compile_session

    sizes = [int(s) for s in args.sizes.split(",")]
    if args.smoke:
        args.requests = min(args.requests, 24)

    tmp = tempfile.TemporaryDirectory(prefix="neocpu_chaos_")
    if args.artifact is None:
        art = Path(tmp.name) / "artifact"
        sess = compile_session(args.model, (1, 3, args.image, args.image))
        for b in sorted({1, args.bucket}):
            sess.specialize(b)
        sess.save(art)
    else:
        art = Path(args.artifact)

    # -- probe 1: artifact integrity ----------------------------------------
    integrity = {}
    for kind in ("weights", "plan"):
        victim = Path(tmp.name) / f"corrupt_{kind}"
        shutil.copytree(art, victim)
        flipped = corrupt_artifact(victim, kind=kind)
        try:
            InferenceSession.load(victim)
            integrity[kind] = "LOADED (gate fails: corruption accepted)"
        except ArtifactCorruptError as e:
            integrity[kind] = f"rejected: {type(e).__name__}"
        print(f"integrity[{kind}]: flipped {flipped.name} -> "
              f"{integrity[kind]}")
    integrity_ok = all(v.startswith("rejected") for v in integrity.values())

    session = InferenceSession.load(art)     # the clean artifact loads
    if args.bucket not in session.batch_sizes:
        raise SystemExit(f"--bucket {args.bucket} not specialized in "
                         f"{art} (has {session.batch_sizes})")

    requests = build_requests(session, sizes, args.requests, args.seed)
    refs = [np.asarray(padded_predict(session, x, bucket=args.bucket))
            for x in requests]
    for b in session.batch_sizes:            # pre-warm every bucket: JIT
        jax.block_until_ready(session.specialize(b).predict(jnp.zeros(
            (b,) + session.input_spec[next(iter(session.input_spec))][1:],
            jnp.float32)))

    policy = DynamicBatchPolicy(max_batch=args.bucket, max_wait_ms=2.0,
                                fixed_bucket=args.bucket)
    retry = RetryPolicy(budget=args.retry_budget,
                        backoff_ms=args.backoff_ms)

    def run(faults=None):
        srv = AsyncServer(session, policy, max_queue=len(requests),
                          workers=args.workers, retry=retry, faults=faults)
        t0 = time.perf_counter()
        futs = [srv.submit(x) for x in requests]
        outs = []
        for f in futs:
            try:
                outs.append(np.asarray(f.result(timeout=120)))
            except ServingError as e:
                outs.append(e)               # typed failure, not lost
        wall = time.perf_counter() - t0
        srv.close()
        return outs, srv, wall

    # -- probe 2: clean baseline --------------------------------------------
    clean_outs, clean_srv, clean_wall = run()
    clean_p99 = clean_srv.stats.percentile_ms(99)

    # -- probe 3: chaos run -------------------------------------------------
    injector = FaultInjector(
        KillWorker(on_batch=args.kill_batch),
        FailBatch(times=args.fail_batches),
        DelayBatch(on_batch=max(args.kill_batch + 2, 3),
                   delay_ms=args.delay_ms))
    chaos_outs, chaos_srv, chaos_wall = run(faults=injector)
    chaos_p99 = chaos_srv.stats.percentile_ms(99)

    n_lost = sum(1 for o in chaos_outs
                 if not isinstance(o, (np.ndarray, ServingError)))
    n_typed_failures = sum(isinstance(o, ServingError)
                           for o in chaos_outs)
    completed_identical = all(
        o.shape == r.shape and o.tobytes() == r.tobytes()
        for o, r in zip(chaos_outs, refs) if isinstance(o, np.ndarray))
    clean_identical = all(
        o.shape == r.shape and o.tobytes() == r.tobytes()
        for o, r in zip(clean_outs, refs) if isinstance(o, np.ndarray))
    # worst-case per-request chaos overhead: the injected delay, the full
    # backoff ladder, and scheduling slack on top of the clean p99
    backoff_total_ms = sum(
        retry.backoff_s(a) * 1e3 for a in range(1, retry.budget + 1))
    p99_bound_ms = clean_p99 + args.delay_ms + backoff_total_ms \
        + args.p99_slack_ms
    p99_ok = chaos_p99 <= p99_bound_ms

    record = {
        "benchmark": "serving_chaos",
        "artifact": str(art),
        "model": session.model_name,
        "buckets": session.batch_sizes,
        "bucket": args.bucket,
        "n_requests": args.requests,
        "request_sizes": sizes,
        "workers": args.workers,
        "retry_budget": args.retry_budget,
        "backoff_ms": args.backoff_ms,
        "faults_armed": {"kill_batch": args.kill_batch,
                         "fail_batches": args.fail_batches,
                         "delay_ms": args.delay_ms},
        "faults_fired": injector.fired,
        "integrity_probe": integrity,
        "clean": {"wall_s": round(clean_wall, 3),
                  "p99_ms": round(clean_p99, 2),
                  "stats": clean_srv.stats.to_json()},
        "chaos": {"wall_s": round(chaos_wall, 3),
                  "p99_ms": round(chaos_p99, 2),
                  "stats": chaos_srv.stats.to_json(),
                  "health": chaos_srv.health()},
        "gates": {
            "integrity_corruption_rejected": integrity_ok,
            "zero_lost_requests": n_lost == 0,
            "n_typed_failures": n_typed_failures,
            "completed_bit_identical": bool(completed_identical
                                            and clean_identical),
            "p99_bound_ms": round(p99_bound_ms, 2),
            "p99_within_bound": bool(p99_ok),
        },
    }
    Path(args.out).write_text(json.dumps(record, indent=2))
    cs = chaos_srv.stats
    print(f"clean: {args.requests} requests in {clean_wall:.2f} s, "
          f"p99={clean_p99:.1f} ms")
    print(f"chaos: {args.requests} requests in {chaos_wall:.2f} s, "
          f"p99={chaos_p99:.1f} ms (bound {p99_bound_ms:.1f}), "
          f"fired={injector.fired_kinds()}")
    print(f"  crashes={cs.n_worker_crashes} restarts={cs.n_worker_restarts}"
          f" retried={cs.n_retried} exhausted={cs.n_retries_exhausted} "
          f"failed={cs.n_failed} completed={cs.n_completed}")
    print(f"  lost={n_lost} typed_failures={n_typed_failures} "
          f"bit_identical={completed_identical} integrity={integrity}")
    print(f"wrote {args.out}")

    if args.smoke:
        assert integrity_ok, f"corruption probe accepted: {integrity}"
        assert n_lost == 0, f"{n_lost} requests lost (unresolved futures)"
        assert completed_identical and clean_identical, \
            "completed responses drifted from sequential padded_predict"
        assert injector.fired_kinds(), "no armed fault actually fired"
        assert cs.n_worker_crashes >= 1 or cs.n_retried >= 1, \
            "chaos run exercised no recovery path"
        assert n_typed_failures == 0, \
            (f"{n_typed_failures} requests failed typed — retry budget "
             f"{args.retry_budget} should absorb the scripted faults")
        assert p99_ok, (f"chaos p99 {chaos_p99:.1f} ms exceeds bound "
                        f"{p99_bound_ms:.1f} ms")
        print("smoke assertions passed (corruption rejected, zero lost, "
              "bit-identical, recovery exercised, p99 bounded)")


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    main()
