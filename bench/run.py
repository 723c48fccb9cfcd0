#!/usr/bin/env python3
"""Runs one cell of the benchmark once, on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Everything is found by name from ``BENCHMARK.json`` at the root of the
checkout: the cell (``workloads``) names its configuration (a file under
``bench/configs/``), its traffic mix (``bench/traffic/<mix>.json``) and
its chips; the configuration names its family's module
(``bench/families/<family>.py``); each per-layer metric has a reader,
``bench/metrics/<metric>.py`` or, where that is absent, the file named by
the metric's name up to its first dot (``mfu.py`` reads ``mfu.latency``
and ``mfu.throughput``).  Adding a configuration, mix or metric is adding
files and entries.

With ``--trace 0`` the result carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from a profiler trace of part of
the window.  The last line of standard output is the result; the last
lines of standard error are the numbers the correctness check compared,
each with its limit.  Without a TPU, or with fewer chips than the cell
asks for, the run exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE = BENCH / ".cache"


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def load_module(path: Path):
    """The Python file at ``path`` as a module (metric files are named
    after metrics, which may hold dots)."""
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(metrics_dir: Path, name: str):
    """The ``read`` function of the per-layer metric ``name``."""
    path = metrics_dir / f"{name}.py"
    if not path.is_file():
        path = metrics_dir / f"{name.split('.')[0]}.py"
    return load_module(path).read


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def find_cell(spec: dict, name: str):
    """The cell ``name`` and its configuration entry, or exit."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        sys.exit(f"bench/run.py: no workload {name!r}; BENCHMARK.json has "
                 f"{sorted(cells)}")
    cell = cells[name]
    (config,) = [c for c in spec["configs"] if c["name"] == cell["config"]]
    return cell, config


def resolve(root: Path, workload: str, trace: bool):
    """Everything a run of ``workload`` needs, found by name under
    ``root``: the cell, its configuration and mix, its family's module,
    and its metrics with (for ``trace``) their readers."""
    bench = root / "bench"
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cell, config = find_cell(spec, workload)
    import traffic

    cfg = json.loads((root / config["file"]).read_text())
    mix = traffic.load(bench / "traffic" / f"{cell['traffic']}.json")
    family = load_module(bench / "families" / f"{cfg['family']}.py")
    kind = "per_layer" if trace else "end_to_end"
    metrics = [dict(m, read=reader(bench / "metrics", m["name"])
                    if trace else None)
               for m in spec[kind] if applies(m, cell["name"])]
    return cell, cfg, mix, family, metrics


def chips_or_exit(chips: int):
    """The accelerators JAX finds, or exit: never the CPU."""
    import jax

    devs = jax.devices()
    if devs[0].platform == "cpu":
        sys.exit("bench/run.py: JAX finds no accelerator; this benchmark "
                 "runs only on a TPU")
    if len(devs) < chips:
        sys.exit(f"bench/run.py: the cell needs {chips} chips, JAX finds "
                 f"{len(devs)}")
    return devs


def enable_cache(path: Path = CACHE / "jax") -> None:
    """JAX's persistent compilation cache at a fixed directory (inside the
    checkout for benchmark runs), handed to the program through its own
    variable; small programs are kept too, so that a warm run compiles
    nothing."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(path)
    import jax
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def result_metrics(res: dict, metrics) -> dict:
    """Each metric of the cell with its unit; raises ``LookupError`` for
    one that reads nothing, so that a run never drops a metric of its
    cell unseen."""
    out = {}
    for m in metrics:
        if m["read"] is not None:
            value = m["read"](res["readings"])
        else:
            value = res["metrics"].get(m["name"])
        if value is None:
            raise LookupError(f"metric {m['name']} reads nothing in this "
                              "run")
        out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawTextHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path[:0] = [str(BENCH), str(ROOT / "src")]
    cell, cfg, mix, family, metrics = resolve(ROOT, args.workload,
                                              bool(args.trace))
    devs = chips_or_exit(cell["chips"])
    import peaks

    peak = peaks.peak(devs[0].device_kind)
    enable_cache()
    ctx = types.SimpleNamespace(
        config=cfg, mix=mix, chips=cell["chips"], seed=args.seed,
        seconds=args.seconds, trace=bool(args.trace), t0=T0, log=log,
        cache=CACHE, trace_dir=CACHE / "trace" / cell["name"], image=None,
        control=False)
    try:
        res = family.run(ctx)
    finally:
        shutil.rmtree(ctx.trace_dir, ignore_errors=True)
    res["readings"]["peak_flops_per_s"] = peak

    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs),
              "memory_peak_bytes": res["memory_peak_bytes"]}
    out = {"correct": res["correct"], "attempted": res["attempted"],
           "failed": res["failed"],
           "metrics": result_metrics(res, metrics),
           "device": device}
    traced = res["readings"].get("traced")
    if args.trace and traced is not None:
        device["busy_s"] = traced["busy_s"]
        device["window_s"] = traced["window_s"]
        out["breakdown"] = traced["breakdown"]
    out["window_compiles"] = res["window_compiles"]
    out["searches"] = res["searches"]
    out["checks"] = {name: {"value": v, "limit": lim}
                     for name, v, lim in res["checks"]}
    print("window " + json.dumps(res["diag"]), flush=True)
    for name, v, lim in res["checks"]:
        print(f"check {name} {v!r} limit {lim!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
