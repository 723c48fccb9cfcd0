"""Paper Table 3: the ablation ladder — baseline -> +layout ->
+transform-elimination -> +global-search.

Two modes:
* predicted (default): the v5e roofline objective per mode, normalized to
  the NCHW baseline — the ladder the planner optimizes for the TPU target.
* --measured: wall-clock ladder on the host CPU with the paper's own
  methodology — the local search *measures candidates on the deployment
  target* (guided: roofline prunes to top-6, measurement ranks), so the
  chosen schedules are CPU-optimal rather than TPU-optimal.  All five mode
  executables are timed round-robin through ``benchmarks/harness.py``
  (warmup-phase detection + interleaved paired medians), so one noisy
  phase cannot skew a single rung of the ladder.
"""
from __future__ import annotations

import argparse

from benchmarks.common import emit, prepare
from benchmarks.harness import measure_paired
from repro.core.local_search import (ScheduleDatabase, guided_local_search)
from repro.core.planner import MODES

LADDER_SET = ["resnet-50", "vgg-19", "densenet-201", "inception-v3",
              "ssd-resnet-50"]


def run_predicted(models):
    rows = []
    for name in models:
        base = None
        for mode in MODES:
            _, _, p = prepare(name, mode)
            t = p.predicted_total_s
            if mode == "nchw":
                base = t
            rows.append((f"table3/{name}/{mode}", t * 1e6,
                         f"speedup_vs_nchw={base / t:.2f}x;"
                         f"transforms={p.planned.n_transforms}"))
        print(f"# {name} predicted ladder done", flush=True)
    return rows


def run_measured(name: str, repeats: int = 3):
    """CPU-measured ladder with measured local search (paper methodology)."""
    rows = []
    db = ScheduleDatabase()

    class GuidedDB(ScheduleDatabase):
        def search(self, wl, runner=None, max_candidates=64):
            from repro.core.local_search import _wl_key
            key = _wl_key(wl)
            if key not in self._mem:
                self._mem[key] = guided_local_search(wl)
            return self._mem[key]

    gdb = GuidedDB()
    models = []
    for mode in MODES:
        # measured-on-CPU target: the paper's x=16 (AVX-512 fp32 lanes) is
        # the right constant block here, not the TPU's 128
        m, x, _ = prepare(name, mode, db=gdb, uniform_block=16)
        models.append((mode, m, x))
    # one interleaved paired run across the whole ladder: every mode is
    # sampled in every round, so medians are comparable rung to rung
    timings = measure_paired(
        [(lambda m=m, x=x: m.predict(x)) for _, m, x in models],
        repeats=repeats)
    base = timings[0].median_ms
    for (mode, _, _), t in zip(models, timings):
        rows.append((f"table3-measured/{name}/{mode}", t.median_ms * 1e3,
                     f"speedup_vs_nchw={base / t.median_ms:.2f}x;"
                     f"min_ms={t.min_ms:.2f};warmup={t.warmup_rounds}"))
        print(f"# measured {name}/{mode}: {t.median_ms:.1f} ms "
              f"({base / t.median_ms:.2f}x, paired medians)", flush=True)
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--measured", action="store_true")
    ap.add_argument("--model", default="resnet-18")
    ap.add_argument("--models", nargs="*", default=LADDER_SET)
    args = ap.parse_args(argv)
    rows = run_measured(args.model) if args.measured \
        else run_predicted(args.models)
    emit(rows)
    return rows


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    main()
