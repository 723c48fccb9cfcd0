"""Paper Figure 4: parallel-scaling study.

NeoCPU's figure compares thread-pool vs OpenMP scalability on one CPU.
On the TPU target the analogue is scaling efficiency across chips: mesh
parallelism replaces the thread pool, and the cost of growing the "pool"
is the collective roofline term instead of fork-join overhead.  We sweep
chip counts, derive throughput from the three roofline terms for a fixed
per-chip workload (weak scaling, NeoCPU's images/sec framing), and report
efficiency vs the ideal linear line.  The collective term is computed for
ring reductions over the DP axis (gradient bytes = active params).

``--measured`` adds the host-CPU analogue of the figure through
``benchmarks/harness.py`` (warmup-phase detection + interleaved paired
medians): batch weak scaling of a planned CNN — all batch sizes timed
round-robin so the images/sec efficiency curve is phase-noise-robust, the
same framing (throughput vs ideal linear) as the paper's thread sweep.
"""
from __future__ import annotations

import argparse

from benchmarks.common import emit
from repro.analysis.roofline import HBM_BW, ICI_BW, PEAK_FLOPS
from repro.configs import ARCHS

CHIPS = (1, 2, 4, 8, 16, 32, 64, 128, 256)
BATCHES = (1, 2, 4)


def run_measured(model: str = "resnet-18", image: int = 112,
                 repeats: int = 10):
    """Batch weak scaling on the host: one planned executable per batch
    size, all sampled in every harness round (paired medians)."""
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.common import _DB
    from benchmarks.harness import measure_paired
    from repro.engine import compile as compile_session
    from repro.models.cnn import build

    # ONE session, specialized per batch size — the weak-scaling sweep is
    # exactly the per-batch specialization the InferenceSession owns
    g, shapes = build(model, batch=BATCHES[0], image=image)
    session = compile_session(g, shapes, db=_DB, eager=False)
    setups = []
    for b in BATCHES:
        m = session.specialize(b)
        x = jnp.asarray(np.random.default_rng(0)
                        .normal(size=(b,) + shapes["data"][1:])
                        .astype(np.float32))
        setups.append((b, m, x))
    timings = measure_paired(
        [(lambda m=m, x=x: m.predict(x)) for _, m, x in setups],
        repeats=repeats)
    rows = []
    base_ips = BATCHES[0] / (timings[0].median_ms * 1e-3)
    for (b, _, _), t in zip(setups, timings):
        ips = b / (t.median_ms * 1e-3)
        eff = ips / (base_ips * b / BATCHES[0])
        rows.append((f"figure4-measured/{model}/batch={b}",
                     t.median_ms * 1e3,
                     f"images_per_s={ips:.2f};efficiency={eff:.3f};"
                     f"warmup={t.warmup_rounds}"))
        print(f"# batch={b}: {t.median_ms:.1f} ms  {ips:.1f} img/s  "
              f"efficiency={eff:.3f} (paired medians)", flush=True)
    return rows


def throughput(cfg, n_chips: int, per_chip_batch: int, seq: int):
    """Weak-scaling tokens/sec: compute+memory fixed per chip; the ring
    all-reduce of the gradients adds 2 x bytes x (n-1)/n over ICI."""
    n_active = cfg.active_param_count()
    tokens = per_chip_batch * seq
    flops = 6.0 * n_active * tokens
    compute_s = flops / PEAK_FLOPS
    # params + grads + opt moments traffic, plus activations ~ 2 x flops/AI
    mem_bytes = 2 * n_active * 2 + 12 * n_active + tokens * cfg.d_model * 8
    memory_s = mem_bytes / HBM_BW
    grad_bytes = 2 * n_active
    coll_s = 0.0 if n_chips == 1 else \
        2 * grad_bytes * (n_chips - 1) / n_chips / ICI_BW
    step = max(compute_s, memory_s) + coll_s
    return n_chips * tokens / step


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-1.5b")
    ap.add_argument("--per-chip-batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=4096)
    ap.add_argument("--measured", action="store_true",
                    help="host-CPU batch weak scaling via the paired-median "
                         "harness instead of the analytical chip sweep")
    ap.add_argument("--model", default="resnet-18")
    ap.add_argument("--image", type=int, default=112)
    args = ap.parse_args(argv)
    if args.measured:
        rows = run_measured(args.model, args.image)
        emit(rows)
        return rows
    cfg = ARCHS[args.arch]
    rows = []
    base = throughput(cfg, 1, args.per_chip_batch, args.seq)
    for n in CHIPS:
        tp = throughput(cfg, n, args.per_chip_batch, args.seq)
        eff = tp / (base * n)
        rows.append((f"figure4/{cfg.name}/chips={n}",
                     1e6 * n * args.per_chip_batch * args.seq / tp,
                     f"tokens_per_s={tp:.3e};efficiency={eff:.3f}"))
        print(f"# chips={n:4d} tokens/s={tp:.3e} efficiency={eff:.3f}",
              flush=True)
    emit(rows)
    return rows


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    main()
