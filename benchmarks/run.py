"""Benchmark harness entry: one section per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--full] [--measured]

Sections:
  table2   — overall latency (paper Table 2): measured CPU + predicted v5e
  table3   — optimization-implication ladder (paper Table 3)
  figure4  — parallel-scaling efficiency (paper Figure 4, TPU analogue)
  roofline — per-(arch x shape) roofline terms from the dry-run artifacts

Output: ``name,us_per_call,derived`` CSV per section.
"""
from __future__ import annotations

import argparse
import time


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="all 15 table-2 models (slow on 1 core)")
    ap.add_argument("--measured", action="store_true",
                    help="also run the measured table-3 ladder")
    ap.add_argument("--skip-table2", action="store_true")
    args = ap.parse_args()
    t0 = time.time()

    from benchmarks import (figure4_scaling, roofline_report,
                            table2_overall, table3_breakdown)

    print("== roofline (from dry-run artifacts) ==", flush=True)
    roofline_report.main(["--mesh", "16x16"])

    print("\n== figure4: scaling ==", flush=True)
    figure4_scaling.main([])

    print("\n== table3: ablation ladder (predicted v5e) ==", flush=True)
    table3_breakdown.main([])

    if args.measured:
        print("\n== table3: measured ladder (guided search on host CPU) ==",
              flush=True)
        table3_breakdown.main(["--measured"])

    if not args.skip_table2:
        print("\n== table2: overall latency ==", flush=True)
        table2_overall.main(["--full"] if args.full else [])

    print(f"\n# benchmarks completed in {time.time() - t0:.0f}s",
          flush=True)


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    main()
