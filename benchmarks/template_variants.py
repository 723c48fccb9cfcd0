"""Template-variant benchmark (§3.2 + §3.3): per-layer and end-to-end
numbers for every conv lowering variant against the PR-1 baseline.

Three plans per model, all §3.1-fused, all on the jnp path:

* ``pr1``      — the PR-1 search space re-planned: blockings capped at the
                 128-lane factor, lowering fixed to the static ``auto``
                 heuristic (tap_stack below sublane ic_bn, per_tap
                 otherwise).  This is the shipped PR-1 template.
* ``searched`` — the variant-aware measured search: per workload, the
                 roofline model prunes the (blocking x variant) space and
                 wall-clock measurement on this host picks the winner
                 (``ScheduleDatabase.search_measured``); the global search
                 then assigns layouts as usual.  Winners (variant included)
                 persist in the workload-keyed schedule database
                 (``--db``, default BENCH_variants_db.json).
* ``forced:<v>`` — every conv forced to variant ``v`` at its best measured
                 blocking *for that variant*: the per-variant end-to-end
                 ablation.

Per-layer numbers come from the measured search's ranked lists: for each
unique conv workload, the best measured ms of every variant.

Measurement rides on ``benchmarks/harness.py`` (warmup-phase detection +
interleaved paired medians) — the same methodology as BENCH_fusion.json.
Emits ``BENCH_variants.json``.
"""
from __future__ import annotations

import argparse
import json
from typing import Dict, List

import jax.numpy as jnp
import numpy as np

from harness import measure_paired
from repro.core.cost import conv_schedule_cost
from repro.core.fusion import fuse_graph
from repro.core.local_search import (LocalSearchResult, ScheduleDatabase,
                                     _wl_key)
from repro.core.pipeline import Pipeline, make_workload
from repro.core.schedule import VARIANTS, ConvSchedule, ConvWorkload
from repro.engine import compile_model
from repro.engine.calibrate import measure_host_copy_bw
from repro.models.cnn import build
from repro.nn.init import init_params

_BIG = 1e9


def pr1_runner(wl: ConvWorkload, s: ConvSchedule) -> float:
    """Roofline cost restricted to the PR-1 search space: blockings up to
    the 128-lane cap, lowering = the static heuristic.  Everything outside
    that space is priced out, so the plan reproduces the PR-1 template."""
    if s.resolved_variant() != ("tap_stack" if s.ic_bn < 8 else "per_tap"):
        return _BIG
    if s.ic_bn > 128 or s.oc_bn > 128:
        return _BIG
    return conv_schedule_cost(wl, s).total_s


def _as_auto(planned_schedules: Dict[str, ConvSchedule]) -> None:
    """Rewrite a plan's schedules to variant='auto' in place — the engine
    then runs exactly the PR-1 kernel dispatch."""
    import dataclasses
    for name, s in list(planned_schedules.items()):
        planned_schedules[name] = dataclasses.replace(s, variant="auto")


def fused_workloads(model: str, batch: int, image: int):
    """(graph, shapes, [(node_name, workload)]) for the §3.1-fused model."""
    g, shapes = build(model, batch=batch, image=image)
    g.infer_shapes(shapes)
    fg, _ = fuse_graph(g)
    fg.infer_shapes(shapes)
    wls = [(n.name, make_workload(n, fg.nodes[n.inputs[0]].shape))
           for n in fg.conv_nodes()]
    return g, shapes, wls


def per_variant_best(res: LocalSearchResult) -> Dict[str, dict]:
    out: Dict[str, dict] = {}
    for r in res.ranked:
        v = r.schedule.resolved_variant()
        if v not in out:
            out[v] = {"ms": round(r.cost_s * 1e3, 3),
                      "ic_bn": r.schedule.ic_bn, "oc_bn": r.schedule.oc_bn}
    return out


def run_model(model: str, batch: int, image: int, repeats: int,
              db: ScheduleDatabase, top_k: int, per_variant: int,
              search_repeats: int, forced: bool, op_dispatch: bool) -> dict:
    g, shapes, wls = fused_workloads(model, batch, image)
    params = init_params(g, shapes, seed=0)
    x = jnp.asarray(np.random.default_rng(0)
                    .normal(size=shapes["data"]).astype(np.float32))

    # -- per-layer: variant-aware measured search per unique workload -------
    layers = {}
    for name, wl in wls:
        res = db.search_measured(wl, top_k=top_k, per_variant=per_variant,
                                 repeats=search_repeats)
        key = _wl_key(wl)
        if key not in layers:
            best = res.best
            layers[key] = {
                "example_node": name,
                "variants": per_variant_best(res),
                "winner": {"variant": best.resolved_variant(),
                           "ic_bn": best.ic_bn, "oc_bn": best.oc_bn},
            }
    n_non_per_tap = sum(1 for rec in layers.values()
                        if rec["winner"]["variant"] != "per_tap")
    print(f"{model}: {len(layers)} unique workloads, "
          f"{n_non_per_tap} non-per_tap winners", flush=True)

    # -- plans ---------------------------------------------------------------
    # the "searched"/"forced" runs hold measured db entries, so the fusion
    # pipeline auto-calibrates the host transform bandwidth itself (no more
    # hand-measured transform_bw threaded through every call)
    fusion = Pipeline.preset("fusion")
    base_plan = fusion.run(g, shapes, db=ScheduleDatabase(),
                           runner=pr1_runner)
    _as_auto(base_plan.planned.schedules)
    searched_plan = fusion.run(g, shapes, db=db, tuning="cached")

    plans = {"pr1": base_plan, "searched": searched_plan}
    if forced:
        for v in VARIANTS:
            db_v = ScheduleDatabase()
            for _, wl in wls:
                res = db.search_measured(wl)   # memoized
                ranked_v = [r for r in res.ranked
                            if r.schedule.resolved_variant() == v]
                db_v.put(wl, LocalSearchResult(wl, ranked_v or res.ranked,
                                               measured=True))
            plans[f"forced:{v}"] = fusion.run(g, shapes, db=db_v,
                                              tuning="cached")

    # -- end-to-end, whole-graph jit (headline) ------------------------------
    result = {"model": model, "batch": batch, "image": image,
              "repeats": repeats, "path": "jnp",
              "n_workloads": len(layers),
              "n_non_per_tap_winners": n_non_per_tap,
              "layers": layers}
    names = list(plans)
    models = {n: compile_model(plans[n], params) for n in names}
    timings = measure_paired([(lambda m=models[n]: m.predict(x))
                              for n in names], repeats=repeats)
    whole = {}
    base_ms = timings[names.index("pr1")].median_ms
    for n, t in zip(names, timings):
        whole[n] = t.to_json()
        whole[n]["speedup_vs_pr1"] = round(base_ms / t.median_ms, 3)
        print(f"{model} whole-jit {n:18s}: {t.median_ms:8.2f}ms "
              f"({base_ms / t.median_ms:.3f}x vs pr1)", flush=True)
    result["whole_jit"] = whole
    result["speedup"] = whole["searched"]["speedup_vs_pr1"]

    # -- end-to-end, graph-runtime dispatch (baseline execution model) -------
    if op_dispatch:
        mb = compile_model(base_plan, params, dispatch="op")
        ms = compile_model(searched_plan, params, dispatch="op")
        t_b, t_s = measure_paired(
            [lambda: mb.predict(x), lambda: ms.predict(x)], repeats=repeats)
        result["op_dispatch"] = {
            "pr1": t_b.to_json(), "searched": t_s.to_json(),
            "speedup": round(t_b.median_ms / t_s.median_ms, 3)}
        print(f"{model} op-dispatch searched: "
              f"{t_s.median_ms:.2f}ms vs pr1 {t_b.median_ms:.2f}ms "
              f"({result['op_dispatch']['speedup']:.3f}x)", flush=True)
    return result


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--models", default="resnet-18,vgg-16,densenet-121")
    ap.add_argument("--batch", type=int, default=1)
    ap.add_argument("--image", type=int, default=224)
    ap.add_argument("--repeats", type=int, default=20)
    ap.add_argument("--top-k", type=int, default=6)
    ap.add_argument("--per-variant", type=int, default=2)
    ap.add_argument("--search-repeats", type=int, default=5)
    ap.add_argument("--forced-models", default="resnet-18",
                    help="models that also get the per-variant forced "
                         "end-to-end ablation (6 whole-graph compiles)")
    ap.add_argument("--no-op-dispatch", action="store_true")
    ap.add_argument("--out", default="BENCH_variants.json")
    ap.add_argument("--db", default="BENCH_variants_db.json",
                    help="workload-keyed schedule database (persisted; "
                         "records the measured (variant, blocking) winners)")
    ap.add_argument("--smoke", action="store_true",
                    help="CI smoke: one small model, tiny search budget")
    args = ap.parse_args()
    if args.smoke:
        args.models, args.image, args.repeats = "resnet-18", 64, 3
        args.top_k, args.per_variant, args.search_repeats = 2, 1, 2
        args.forced_models = ""
        args.no_op_dispatch = True

    db = ScheduleDatabase(args.db)
    forced = set(filter(None, args.forced_models.split(",")))
    # the same process-cached probe the pipeline's GlobalLayoutPlan uses
    bw = measure_host_copy_bw()
    print(f"host relayout bandwidth: {bw / 1e9:.2f} GB/s "
          f"(auto-calibrated, reused by every plan below)", flush=True)
    out = {"harness": "paired-interleaved medians + warmup-phase detection",
           "host_transform_bw_gbps": round(bw / 1e9, 3),
           "models": {}}
    for model in filter(None, args.models.split(",")):
        out["models"][model] = run_model(
            model, args.batch, args.image, args.repeats, db,
            args.top_k, args.per_variant, args.search_repeats,
            forced=model in forced, op_dispatch=not args.no_op_dispatch)
    first = next(iter(out["models"]))
    out["speedup"] = out["models"][first]["speedup"]
    out["non_per_tap_winners"] = sum(
        m["n_non_per_tap_winners"] for m in out["models"].values())
    with open(args.out, "w") as f:
        json.dump(out, f, indent=2)
    print(f"wrote {args.out} (headline {first} whole-jit searched "
          f"{out['speedup']:.3f}x vs pr1; "
          f"{out['non_per_tap_winners']} non-per_tap workload winners)")


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    main()
