"""Aggregate the dry-run JSONs into the EXPERIMENTS.md roofline table.

Where an unrolled-scan measurement twin exists
(experiments/perf/<arch>__<shape>__<mesh>__baseline+unroll.json), its
collective bytes replace the scanned parse (marked *): the layer scan hides
per-layer collectives from the HLO text parse by ~n_layers (methodology in
EXPERIMENTS.md §Dry-run).
"""
from __future__ import annotations

import argparse
import json
from pathlib import Path

from repro.analysis.roofline import ICI_BW

ROOT = Path(__file__).resolve().parents[1] / "experiments"
DRYRUN_DIR = ROOT / "dryrun"
PERF_DIR = ROOT / "perf"


def load(mesh: str):
    rows = []
    for p in sorted(DRYRUN_DIR.glob(f"*__{mesh}.json")):
        rec = json.loads(p.read_text())
        twin = PERF_DIR / (f"{rec['arch']}__{rec['shape']}__{mesh}"
                           "__baseline+unroll.json")
        if rec.get("status") == "ok" and twin.exists():
            t = json.loads(twin.read_text())
            if t.get("status") == "ok":
                rec["roofline"]["collective_bytes_per_device"] = \
                    t["collective_bytes"]["total"]
                rec["roofline"]["collective_s"] = \
                    t["collective_bytes"]["total"] / ICI_BW
                rec["unrolled_twin"] = True
                rl = rec["roofline"]
                step = max(rl["compute_s"], rl["memory_s"]) \
                    + rl["collective_s"]
                rl["step_time_s"] = step
                rl["roofline_fraction"] = rl["ideal_step_s"] / step
                terms = {"compute": rl["compute_s"],
                         "memory": rl["memory_s"],
                         "collective": rl["collective_s"]}
                rl["bottleneck"] = max(terms, key=terms.get)
        rows.append(rec)
    return rows


def fmt_table(rows, skip_skipped=False):
    out = ["| arch | shape | status | compute_s | memory_s | collective_s |"
           " bottleneck | useful | roofline | HBM/dev |",
           "|---|---|---|---|---|---|---|---|---|---|"]
    for r in rows:
        if r["status"] != "ok":
            if not skip_skipped:
                out.append(f"| {r['arch']} | {r['shape']} | {r['status']} |"
                           " - | - | - | - | - | - | - |")
            continue
        rl = r["roofline"]
        mem = r["memory"]
        hbm = (mem.get("temp_bytes") or 0) / 2**30
        star = "*" if r.get("unrolled_twin") else ""
        out.append(
            f"| {r['arch']} | {r['shape']} | ok "
            f"| {rl['compute_s']:.3e} | {rl['memory_s']:.3e} "
            f"| {rl['collective_s']:.3e}{star} | {rl['bottleneck']} "
            f"| {rl['useful_flops_ratio']:.2f} "
            f"| {rl['roofline_fraction']:.3f} | {hbm:.1f} GiB |")
    return "\n".join(out)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--mesh", default="16x16")
    args = ap.parse_args(argv)
    rows = load(args.mesh)
    print(fmt_table(rows))
    print("name,us_per_call,derived")
    for r in rows:
        if r["status"] != "ok":
            continue
        rl = r["roofline"]
        print(f"roofline/{r['arch']}/{r['shape']}/{args.mesh},"
              f"{rl['step_time_s'] * 1e6:.1f},"
              f"bottleneck={rl['bottleneck']};"
              f"fraction={rl['roofline_fraction']:.3f}")
    return rows


if __name__ == "__main__":
    main()
