"""Fusion ablation (§3.1): unfused vs fused CONV epilogues, end to end.

Times the ResNet-18 workload set through the real engine on the jnp path,
with the fusion passes as the only variable (two ``engine.compile``
sessions sharing one parameter set):

    unfused  Pipeline.preset("global-search")  — conv2d / batch_norm / relu
                                                 / add as separate nodes
    fused    Pipeline.preset("fusion")         — the FuseEpilogues +
                                                 FuseConcatWrites passes in
                                                 front of the same planning

Both plans are executed in both engine dispatch modes:

* ``op``    — graph-runtime dispatch (one XLA executable per node,
              intermediates materialized between nodes): the execution model
              of the paper's framework baselines, and the mode where
              graph-level fusion is the only thing standing between a
              BN/ReLU/add and a full round trip through memory;
* ``whole`` — one jit over the model, XLA free to fuse across nodes.

Two focused ablation rows isolate the PR-3 epilogue extensions:

* ``pooled_stem``     — the ResNet stem ``conv7x7/2 -> bn -> relu ->
                        max_pool3x3/2`` alone: the fused plan collapses it
                        to ONE kernel (the pooling reduction runs over the
                        fp32 accumulator before the store), the unfused
                        plan is the PR-2 global-search plan dispatching
                        conv + bn + relu + max_pool;
* ``densenet_concat`` — a DenseNet dense-block: fused conv_blocks write
                        channel-offset slices straight into the shared
                        concat buffer, the unfused plan materializes every
                        conv output and copies it in a standalone concat.

Measurement rides on ``benchmarks/harness.py`` — warmup-phase detection +
interleaved paired A/B medians — the same methodology as
``BENCH_variants.json``.  Emits ``BENCH_fusion.json``.
"""
from __future__ import annotations

import argparse
import json

import jax.numpy as jnp
import numpy as np

from common import _DB  # shared ScheduleDatabase
from harness import measure_paired
from repro.core.graph import Graph
from repro.core.pipeline import Pipeline
from repro.engine import compile as compile_session
from repro.models.cnn import build
from repro.nn.init import init_params


def _stem_graph(image: int, batch: int = 1):
    """The ResNet stem in isolation — the pooled-epilogue headline chain."""
    g = Graph()
    g.add("data", "input")
    g.add("stem", "conv2d", ["data"], in_channels=3, out_channels=64,
          kh=7, kw=7, stride=2, pad=3)
    g.add("stem_bn", "batch_norm", ["stem"])
    g.add("stem_relu", "relu", ["stem_bn"])
    g.add("stem_pool", "max_pool", ["stem_relu"], k=3, stride=2, pad=1)
    g.mark_output("stem_pool")
    return g, {"data": (batch, 3, image, image)}


def _dense_block_graph(image: int, batch: int = 1, layers: int = 4,
                       feats: int = 64, growth: int = 32):
    """One DenseNet dense block — the concat-write headline chain."""
    g = Graph()
    g.add("data", "input")
    g.add("stem", "conv2d", ["data"], in_channels=3, out_channels=feats,
          kh=3, kw=3, pad=1)
    y, c = "stem", feats
    for i in range(layers):
        g.add(f"l{i}_bn", "batch_norm", [y])
        g.add(f"l{i}_relu", "relu", [f"l{i}_bn"])
        g.add(f"l{i}_conv", "conv2d", [f"l{i}_relu"], in_channels=c,
              out_channels=growth, kh=3, kw=3, pad=1)
        g.add(f"l{i}_cat", "concat", [y, f"l{i}_conv"])
        y = f"l{i}_cat"
        c += growth
    g.mark_output(y)
    return g, {"data": (batch, 3, image, image)}


def run_chain(tag: str, g, shapes, repeats: int) -> dict:
    """Fused vs unfused paired medians for one focused chain, op dispatch
    (the paper's execution model, where the fused kernel replaces the
    per-node round trips)."""
    params = init_params(g, shapes, seed=0)
    x = jnp.asarray(np.random.default_rng(0)
                    .normal(size=shapes["data"]).astype(np.float32))
    batch = shapes["data"][0]
    mu = compile_session(g, shapes, params=params, db=_DB, dispatch="op",
                         pipeline=Pipeline.preset("global-search"))
    mf = compile_session(g, shapes, params=params, db=_DB, dispatch="op",
                         pipeline=Pipeline.preset("fusion"))
    fused = mf.plan_for(batch)
    t_u, t_f = measure_paired(
        [lambda: mu.predict(x), lambda: mf.predict(x)], repeats=repeats)
    row = {"unfused": t_u.to_json(), "fused": t_f.to_json(),
           "speedup": round(t_u.median_ms / t_f.median_ms, 3),
           "n_blocks": fused.fusion.n_blocks,
           "n_pool_fused": fused.fusion.n_pool_fused,
           "n_concat_fused": fused.fusion.n_concat_fused}
    print(f"{tag}: unfused {t_u.median_ms:.2f}ms fused {t_f.median_ms:.2f}ms "
          f"speedup {row['speedup']:.3f}x "
          f"(pool_fused={row['n_pool_fused']}, "
          f"concat_fused={row['n_concat_fused']})")
    return row


def run(model: str, batch: int, image: int, repeats: int) -> dict:
    g, shapes = build(model, batch=batch, image=image)
    params = init_params(g, shapes, seed=0)
    x = jnp.asarray(np.random.default_rng(0)
                    .normal(size=shapes["data"]).astype(np.float32))

    unfused = Pipeline.preset("global-search").run(g, shapes, db=_DB)
    fused = Pipeline.preset("fusion").run(g, shapes, db=_DB)
    result = {
        "model": model, "batch": batch, "image": image, "repeats": repeats,
        "path": "jnp",
        "fusion": {"n_blocks": fused.fusion.n_blocks,
                   "n_absorbed": fused.fusion.n_absorbed},
        "predicted_epilogue_s": {"unfused": unfused.predicted_epilogue_s,
                                 "fused": fused.predicted_epilogue_s},
        "pipeline_report": {"unfused": unfused.report.to_json(),
                            "fused": fused.report.to_json()},
    }
    from repro.engine import compile_model
    for dispatch in ("op", "whole"):
        mu = compile_model(unfused, params, dispatch=dispatch)
        mf = compile_model(fused, params, dispatch=dispatch)
        t_u, t_f = measure_paired(
            [lambda: mu.predict(x), lambda: mf.predict(x)], repeats=repeats)
        key = "op_dispatch" if dispatch == "op" else "whole_jit"
        result[key] = {"unfused": t_u.to_json(), "fused": t_f.to_json(),
                       "speedup": round(t_u.median_ms / t_f.median_ms, 3),
                       "speedup_min": round(t_u.min_ms / t_f.min_ms, 3)}
        print(f"{model} b{batch} i{image} {dispatch:5s}: "
              f"unfused {t_u.median_ms:.2f}ms fused {t_f.median_ms:.2f}ms "
              f"speedup {t_u.median_ms / t_f.median_ms:.3f}x "
              f"(min-based {t_u.min_ms / t_f.min_ms:.3f}x, "
              f"warmup {t_u.warmup_rounds} rounds)")
    return result


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--model", default="resnet-18")
    ap.add_argument("--batch", type=int, default=1)
    # 224 = the ImageNet resolution of the paper's Table 2 workloads; at
    # this scale the unfused graph's ~45 materialized intermediates cost
    # real memory traffic (~90 MB of eliminated round trips per inference)
    ap.add_argument("--image", type=int, default=224)
    ap.add_argument("--repeats", type=int, default=40)
    ap.add_argument("--smoke", action="store_true",
                    help="CI mode: only the pooled-stem + densenet-concat "
                         "chains at small resolution, few repeats")
    ap.add_argument("--out", default="BENCH_fusion.json")
    args = ap.parse_args()
    if args.smoke:
        image, repeats = 56, 8
        result = {"smoke": True, "image": image, "repeats": repeats}
    else:
        image, repeats = args.image, args.repeats
        result = run(args.model, args.batch, args.image, args.repeats)
        # headline metric: graph-runtime dispatch, where fusion is the only
        # defense against per-node round trips (the paper's execution model)
        result["speedup"] = result["op_dispatch"]["speedup"]
    # PR-3 epilogue-extension rows: the pooled stem and the concat-write
    # dense block, each fused-vs-unfused under paired medians
    result["pooled_stem"] = run_chain(
        "pooled_stem", *_stem_graph(image, args.batch), repeats)
    result["densenet_concat"] = run_chain(
        "densenet_concat", *_dense_block_graph(image, args.batch), repeats)
    with open(args.out, "w") as f:
        json.dump(result, f, indent=2)
    if args.smoke:
        print(f"wrote {args.out} (smoke: pooled-stem "
              f"{result['pooled_stem']['speedup']:.3f}x, concat "
              f"{result['densenet_concat']['speedup']:.3f}x)")
    else:
        print(f"wrote {args.out} (headline speedup "
              f"{result['speedup']:.3f}x op-dispatch; pooled-stem "
              f"{result['pooled_stem']['speedup']:.3f}x, concat "
              f"{result['densenet_concat']['speedup']:.3f}x)")


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    main()
