"""Trace-replay serving benchmark: learned bucket sets and multi-tenant
hosting under realistic traffic shapes, written to ``BENCH_trace.json``.

Two phases over artifacts built (or passed) on the fly:

1. **Single tenant, learned buckets** — replay a deterministic
   heavy-tail trace (``engine.traffic.synth_trace``) through an
   artifact saved with the hand-picked ``{1, 8}`` bucket set, measure
   the arrival-size histogram, then:

   * **solver gate** — ``solve_buckets`` on the measured histogram must
     have expected padded waste <= the hand-picked set's on the same
     distribution;
   * re-save the artifact with ``buckets="auto"`` (the learned set),
     reload it, and replay the same trace through it pinned to one
     fixed bucket — every completed response must be **bit-identical**
     to sequential ``padded_predict`` through the same (bucket,
     device-count) program, and p99 must stay within the modeled bound
     (baseline p99 + flush window + scheduling slack).

2. **Two-tenant fleet under memory pressure** — load the learned
   artifact twice (source-packed, so specializations are evictable)
   behind one ``FleetServer`` whose memory budget is set *below* the
   two tenants' combined resident footprint, then replay a bursty
   two-tenant trace routed by tenant name.  Gates:

   * **evictions happened** — the budget forced at least one LRU
     release (``fleet.n_evictions >= 1``);
   * **zero lost requests** — every submitted future resolves with a
     result or a typed ``ServingError`` (eviction trades latency, never
     availability: evicted buckets re-specialize on demand);
   * **bit-identical** — completed responses match sequential
     ``padded_predict`` per tenant;
   * **bounded p99** — each tenant's p99 within the phase-1 baseline
     plus flush window plus slack.

``--smoke`` (CI) shrinks both traces and hard-asserts every gate.

    PYTHONPATH=../src python serving_trace.py --smoke \
        --out ../BENCH_trace.json
"""
from __future__ import annotations

import argparse
import json
import tempfile
import time
from pathlib import Path

import numpy as np


def build_inputs(trace, tail, seed):
    """One deterministic input tensor per trace request."""
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    return [jnp.asarray(rng.normal(size=(r.rows,) + tail)
                        .astype(np.float32)) for r in trace]


def prewarm(session):
    import jax
    import jax.numpy as jnp

    (name,) = session.input_spec
    tail = session.input_spec[name][1:]
    for b in session.batch_sizes:
        jax.block_until_ready(session.specialize(b).predict(
            jnp.zeros((b,) + tail, jnp.float32)))


def replay(submit, trace, xs, time_scale):
    """Paced replay honouring the trace's arrival times (compressed by
    ``time_scale``); returns (futures, wall_s)."""
    t0 = time.perf_counter()
    futs = []
    for req, x in zip(trace, xs):
        target = req.t * time_scale
        lag = target - (time.perf_counter() - t0)
        if lag > 0:
            time.sleep(lag)
        futs.append(submit(req, x))
    wall = time.perf_counter() - t0
    return futs, wall


def settle(futs, ServingError, timeout=120):
    """Resolve every future: ndarray, typed ServingError, or lost."""
    outs = []
    for f in futs:
        try:
            outs.append(np.asarray(f.result(timeout=timeout)))
        except ServingError as e:
            outs.append(e)
    return outs


def check_identical(outs, refs):
    return all(o.shape == r.shape and o.tobytes() == r.tobytes()
               for o, r in zip(outs, refs) if isinstance(o, np.ndarray))


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--artifact", default=None,
                    help="saved artifact dir with hand-picked buckets; "
                         "omitted = build a small CNN artifact on the fly")
    ap.add_argument("--model", default="resnet-18")
    ap.add_argument("--image", type=int, default=32)
    ap.add_argument("--trace", default="bursty",
                    help="phase-2 trace kind (phase 1 always replays "
                         "heavytail — the distribution the solver gate "
                         "is about)")
    ap.add_argument("--requests", type=int, default=96,
                    help="requests per phase")
    ap.add_argument("--mean-rate", type=float, default=200.0,
                    help="trace arrival rate (req/s) before scaling")
    ap.add_argument("--time-scale", type=float, default=1.0,
                    help="replay pacing multiplier (<1 compresses)")
    ap.add_argument("--max-rows", type=int, default=8)
    ap.add_argument("--workers", type=int, default=2)
    ap.add_argument("--max-wait-ms", type=float, default=5.0)
    ap.add_argument("--p99-slack-ms", type=float, default=500.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default="BENCH_trace.json")
    ap.add_argument("--smoke", action="store_true",
                    help="CI mode: short traces + hard gate assertions")
    args = ap.parse_args()

    from repro.engine import (AsyncServer, DynamicBatchPolicy, FleetServer,
                              InferenceSession, ServingError,
                              expected_padded_waste, padded_predict,
                              solve_buckets, synth_trace)
    from repro.engine import compile as compile_session

    if args.smoke:
        args.requests = min(args.requests, 48)

    hand_buckets = [1, args.max_rows]
    tmp = tempfile.TemporaryDirectory(prefix="neocpu_trace_")
    if args.artifact is None:
        art = Path(tmp.name) / "artifact_hand"
        sess = compile_session(args.model, (1, 3, args.image, args.image))
        for b in hand_buckets:
            sess.specialize(b)
        sess.save(art, include_source=True)
    else:
        art = Path(args.artifact)

    session = InferenceSession.load(art)
    (in_name,) = session.input_spec
    tail = session.input_spec[in_name][1:]
    hand_buckets = sorted(session.batch_sizes)

    # -- phase 1: heavy-tail trace through the hand-picked set ---------------
    trace1 = synth_trace("heavytail", n=args.requests, seed=args.seed,
                         mean_rate=args.mean_rate, max_rows=args.max_rows)
    xs1 = build_inputs(trace1, tail, args.seed)
    prewarm(session)

    policy = DynamicBatchPolicy(max_batch=args.max_rows,
                                max_wait_ms=args.max_wait_ms,
                                fixed_bucket=max(hand_buckets))
    srv = AsyncServer(session, policy, max_queue=args.requests,
                      workers=args.workers)
    futs, wall_hand = replay(lambda r, x: srv.submit(x), trace1, xs1,
                             args.time_scale)
    outs_hand = settle(futs, ServingError)
    stats_hand = srv.stats
    srv.close()
    hand_p99 = stats_hand.percentile_ms(99)

    # solver gate on the histogram the replay actually measured — the
    # same call save(buckets="auto") makes, so learned == artifact set
    hist = {s: c for s, c in stats_hand.arrival_hist.counts().items()}
    learned = solve_buckets(hist, devices=session.devices)
    waste_hand = expected_padded_waste(hist, hand_buckets)
    waste_learned = expected_padded_waste(hist, learned)
    solver_ok = waste_learned <= waste_hand
    print(f"phase1: measured sizes {hist}")
    print(f"phase1: learned buckets {learned} waste={waste_learned} vs "
          f"hand-picked {hand_buckets} waste={waste_hand}")

    # re-save with the learned set and serve the same trace through it
    art_auto = Path(tmp.name) / "artifact_auto"
    session.save(art_auto, buckets="auto",
                 traffic=stats_hand.arrival_hist)
    auto_sess = InferenceSession.load(art_auto)
    assert sorted(auto_sess.batch_sizes) == sorted(learned), \
        (auto_sess.batch_sizes, learned)
    prewarm(auto_sess)
    pin = max(auto_sess.batch_sizes)
    refs1 = [np.asarray(padded_predict(auto_sess, x, bucket=pin))
             for x in xs1]
    srv = AsyncServer(auto_sess,
                      DynamicBatchPolicy(max_batch=pin,
                                         max_wait_ms=args.max_wait_ms,
                                         fixed_bucket=pin),
                      max_queue=args.requests, workers=args.workers)
    futs, wall_auto = replay(lambda r, x: srv.submit(x), trace1, xs1,
                             args.time_scale)
    outs_auto = settle(futs, ServingError)
    stats_auto = srv.stats
    srv.close()
    auto_p99 = stats_auto.percentile_ms(99)
    p99_bound = hand_p99 + args.max_wait_ms + args.p99_slack_ms
    auto_lost = sum(1 for o in outs_auto
                    if not isinstance(o, (np.ndarray, ServingError)))
    auto_identical = check_identical(outs_auto, refs1)
    print(f"phase1: hand p99={hand_p99:.1f} ms, auto p99={auto_p99:.1f} ms "
          f"(bound {p99_bound:.1f}), identical={auto_identical}")

    # -- phase 2: two-tenant fleet under memory pressure ---------------------
    tenants = ("alpha", "beta")
    trace2 = synth_trace(args.trace, n=args.requests, seed=args.seed + 1,
                         mean_rate=args.mean_rate, max_rows=args.max_rows,
                         tenants=tenants)
    xs2 = build_inputs(trace2, tail, args.seed + 1)
    sess_a = InferenceSession.load(art_auto)
    sess_b = InferenceSession.load(art_auto)
    prewarm(sess_a)
    prewarm(sess_b)
    refs2 = [np.asarray(padded_predict(
        sess_a if r.tenant == "alpha" else sess_b, x, bucket=pin))
        for r, x in zip(trace2, xs2)]
    resident = (sum(sess_a.memory_bytes().values())
                + sum(sess_b.memory_bytes().values()))
    budget = resident - min(sess_a.memory_bytes().values()) // 2

    fleet = FleetServer(memory_budget_bytes=budget,
                        max_queue=args.requests, workers=args.workers)
    tenant_policy = DynamicBatchPolicy(max_batch=pin,
                                       max_wait_ms=args.max_wait_ms,
                                       fixed_bucket=pin)
    fleet.add_model("alpha", sess_a, policy=tenant_policy)
    fleet.add_model("beta", sess_b, policy=tenant_policy)
    futs, wall_fleet = replay(
        lambda r, x: fleet.submit(r.tenant, x, priority=r.priority),
        trace2, xs2, args.time_scale)
    outs_fleet = settle(futs, ServingError)
    fleet_stats = fleet.stats()
    n_evictions = fleet.n_evictions
    fleet_health = fleet.health()
    fleet.close()

    fleet_lost = sum(1 for o in outs_fleet
                     if not isinstance(o, (np.ndarray, ServingError)))
    fleet_typed = sum(isinstance(o, ServingError) for o in outs_fleet)
    fleet_identical = check_identical(outs_fleet, refs2)
    fleet_p99 = {name: st.percentile_ms(99)
                 for name, st in fleet_stats.items()}
    fleet_p99_ok = all(p <= p99_bound for p in fleet_p99.values()
                       if np.isfinite(p))
    print(f"phase2 ({args.trace}): evictions={n_evictions} lost="
          f"{fleet_lost} typed={fleet_typed} identical={fleet_identical}")
    print(f"phase2: p99 per tenant "
          f"{ {k: round(v, 1) for k, v in fleet_p99.items()} } "
          f"(bound {p99_bound:.1f})")

    record = {
        "benchmark": "serving_trace",
        "model": session.model_name,
        "n_requests": args.requests,
        "max_rows": args.max_rows,
        "workers": args.workers,
        "time_scale": args.time_scale,
        "phase1": {
            "trace": "heavytail",
            "measured_hist": {str(k): v for k, v in sorted(hist.items())},
            "hand_buckets": hand_buckets,
            "learned_buckets": learned,
            "waste_hand": waste_hand,
            "waste_learned": waste_learned,
            "hand": {"wall_s": round(wall_hand, 3),
                     "p99_ms": round(hand_p99, 2),
                     "stats": stats_hand.to_json()},
            "auto": {"wall_s": round(wall_auto, 3),
                     "p99_ms": round(auto_p99, 2),
                     "stats": stats_auto.to_json()},
        },
        "phase2": {
            "trace": args.trace,
            "tenants": list(tenants),
            "memory_budget_bytes": budget,
            "n_evictions": n_evictions,
            "wall_s": round(wall_fleet, 3),
            "p99_ms": {k: round(v, 2) for k, v in fleet_p99.items()},
            "health": fleet_health,
        },
        "gates": {
            "solver_waste_not_worse": bool(solver_ok),
            "auto_zero_lost": auto_lost == 0,
            "auto_bit_identical": bool(auto_identical),
            "p99_bound_ms": round(p99_bound, 2),
            "auto_p99_within_bound": bool(auto_p99 <= p99_bound),
            "fleet_evictions": n_evictions,
            "fleet_zero_lost": fleet_lost == 0,
            "fleet_n_typed_failures": fleet_typed,
            "fleet_bit_identical": bool(fleet_identical),
            "fleet_p99_within_bound": bool(fleet_p99_ok),
        },
    }
    Path(args.out).write_text(json.dumps(record, indent=2))
    print(f"wrote {args.out}")

    if args.smoke:
        assert solver_ok, (f"learned waste {waste_learned} > hand-picked "
                           f"{waste_hand} on {hist}")
        assert auto_lost == 0 and fleet_lost == 0, \
            f"lost requests: auto={auto_lost} fleet={fleet_lost}"
        assert auto_identical, \
            "auto-bucket responses drifted from sequential padded_predict"
        assert fleet_identical, \
            "fleet responses drifted from sequential padded_predict"
        assert n_evictions >= 1, \
            f"budget {budget} < resident {resident} yet nothing evicted"
        assert fleet_typed == 0, \
            f"{fleet_typed} typed failures in an unfaulted fleet replay"
        assert auto_p99 <= p99_bound, \
            f"auto p99 {auto_p99:.1f} ms exceeds bound {p99_bound:.1f} ms"
        assert fleet_p99_ok, \
            f"fleet p99 {fleet_p99} exceeds bound {p99_bound:.1f} ms"
        print("smoke assertions passed (solver not worse, zero lost, "
              "bit-identical, evictions observed, p99 bounded)")


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    main()
