#!/usr/bin/env python3
"""Readings that set a cell's numbers, in one process on one machine.

    python3 bench/readings.py --workload <cell> --seeds 1,2,3 --seconds 5 \\
        [--rates 100,150] [--control] [--keep-trace DIR] [--trace]

Runs the cell's set-up and window once for each rate (open-loop mixes;
default the mix's own) and seed, and prints one JSON line per run: its
end-to-end metrics, the numbers the check compared and the window's
diagnostics.  ``--rates`` is the knee sweep: the highest rate at which the
latency of the window's last quarter stays near its first quarter's.
``--control`` also judges the control (the reference at "high"
precision, three bfloat16 passes, put in the program's place) on the same
requests: its gap is the upper reading of the correctness limit, the
program's own gaps over a dozen seeds the lower.  Benchmark runs (``bench/run.py``) never run the
control.

``--image`` runs a smaller image for a rehearsal on the CPU.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import shutil
import sys
import time
import traceback
import types
from pathlib import Path

import run as bench


def describe(path: str) -> None:
    """Planes, lines and their commonest events, to standard error."""
    import devtrace as T

    for plane, lines in T.events(path).items():
        for line, evs in lines.items():
            names: dict = {}
            for n, a, b in evs:
                names[n] = names.get(n, 0) + 1
            top = sorted(names.items(), key=lambda kv: -kv[1])[:4]
            span = (evs[0][1], evs[-1][2]) if evs else None
            print(f"trace {plane!r} {line!r} {len(evs)} {span} {top}",
                  file=sys.stderr)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawTextHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", default="")
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--trace", action="store_true")
    ap.add_argument("--keep-trace", default="")
    ap.add_argument("--image", type=int, default=None)
    args = ap.parse_args(argv)

    sys.path[:0] = [str(bench.BENCH), str(bench.ROOT / "src")]
    cell, cfg, mix, family, metrics = bench.resolve(
        bench.ROOT, args.workload, args.trace)
    import peaks

    if args.image is None:
        devs = bench.chips_or_exit(cell["chips"])
        peak = peaks.peak(devs[0].device_kind)
        # a cache the machine keeps between calls, where it has one
        bench.enable_cache(os.environ.get("JAX_COMPILATION_CACHE_DIR")
                           or bench.CACHE / "jax")
    else:
        peak = None
    rates = [float(r) for r in args.rates.split(",") if r] or [None]
    failures = 0
    for rate in rates:
        for seed in (int(s) for s in args.seeds.split(",")):
            run_mix = dict(mix) if rate is None else dict(mix, rate_per_s=rate)
            trace_dir = bench.CACHE / "trace" / f"readings-{seed}"
            ctx = types.SimpleNamespace(
                config=cfg, mix=run_mix, chips=cell["chips"], seed=seed,
                seconds=args.seconds, trace=args.trace,
                t0=time.perf_counter(), log=bench.log, cache=bench.CACHE,
                trace_dir=trace_dir, image=args.image, control=args.control)
            try:
                res = family.run(ctx)
            except Exception:                # noqa: BLE001 — next reading
                traceback.print_exc()
                failures += 1
                continue
            r = res["readings"]
            r["peak_flops_per_s"] = peak
            per_layer = {}
            if peak is not None:
                try:
                    per_layer = bench.result_metrics(res, metrics)
                except LookupError as e:
                    per_layer = {"error": str(e)}
            if args.keep_trace:
                keep = Path(args.keep_trace)
                keep.mkdir(parents=True, exist_ok=True)
                for f in glob.glob(str(trace_dir / "**" / "*.xplane.pb"),
                                   recursive=True):
                    shutil.copy(f, keep / f"{cell['name']}-{seed}.xplane.pb")
                    describe(f)
            shutil.rmtree(trace_dir, ignore_errors=True)
            print(json.dumps({
                "workload": cell["name"], "seed": seed,
                "rate_per_s": run_mix.get("rate_per_s"),
                "seconds": args.seconds, "correct": res["correct"],
                "metrics": res["metrics"], "per_layer": per_layer,
                "checks": {n: v for n, v, _ in res["checks"]},
                "control": res["control"],
                "diag": res["diag"], "window": r["window"],
                "traced": {k: v for k, v in (r["traced"] or {}).items()
                           if k != "breakdown"},
                "breakdown": (r["traced"] or {}).get("breakdown"),
                "memory_peak_bytes": res["memory_peak_bytes"],
                "window_compiles": res["window_compiles"],
                "searches": res["searches"]}), flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
