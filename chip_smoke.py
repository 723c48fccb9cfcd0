#!/usr/bin/env python3
"""Chip smoke: the main serving path, once, on a TPU.

Default (one chip, one process):

1. device   — print platform, device kind and count, and whether the kind
               is in the peak table (``repro.core.peaks``); exit non-zero
               unless JAX's first device is a TPU.
2. resnet50 — ``compile`` ResNet-50 at (1, 3, 224, 224) up to its logits
               (the zoo graph without its softmax; roofline tuning),
               specialize batch 8, ``save`` -> ``load`` in this process,
               warm both buckets, serve single-image requests through
               ``AsyncServer`` with dynamic batching; zero schedule
               searches on load -> serve; served logits checked against
               the same plan and parameters on the host's CPU backend,
               and again at "highest" precision, bucket 1 called directly
               and the largest bucket through the server.
3. pallas   — the same model with ``use_pallas=True``: the Pallas conv
               kernel compiled (not interpreted), checked against phase 2
               and the CPU reference, and at "highest" precision against
               the CPU reference.
4. lm       — reduced ``qwen2-1.5b``: tokens streamed through
               ``AsyncServer.submit_stream`` equal ``LMSession.generate``.

``--four-chips`` runs only the multi-chip serving path: a batch-sharded
``compile(..., devices=4)`` artifact and ``AsyncServer(workers=4)`` with
one replica per chip serving at "highest" precision, each compared with
a one-chip run of the same artifact.

The last line of standard output is ``{"ok": true, "device": {...}}``; a
failed phase raises, so that line is never printed.  Weights and inputs
come from ``--seed``; the artifact is written under ``.chip_smoke/`` and
JAX's compile cache where ``repro.launch.cache`` says.

    python chip_smoke.py
    python chip_smoke.py --four-chips
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
SRC = ROOT / "src"
OUT = ROOT / ".chip_smoke"

# TPU default precision runs an fp32 matmul or conv as one pass over
# bf16-rounded operands (8-bit mantissa, ~2e-3 relative per operand); the
# CPU backend computes in full fp32.  Over ResNet-50's 53 convs that
# rounding compounds to about 1e-2 of the logits' scale, so logits agree
# within 5e-2 of max|logit|.  At "highest" precision the TPU computes
# fp32 products too, and only summation order differs: 1e-3.
DEFAULT_PRECISION_RTOL = 5e-2
HIGHEST_PRECISION_RTOL = 1e-3
# Random weights without normalisation grow ResNet-50's logits to ~1e4,
# mostly the same for every input: the logits of different inputs differ
# by ~2e-2 of their scale.  A default-precision comparison therefore checks
# numerics only: it cannot see a result that ignored its input, or a
# server that returned another request's row.  Every path is also compared
# at "highest" precision, whose tolerance can, as long as the reference's
# inputs differ by more than this many times it (``check_spread``).
MIN_SPREAD_OVER_RTOL = 4


def log(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise RuntimeError(msg)


def compare(phase: str, what: str, got, ref, rtol: float) -> None:
    """Logits ``got`` against ``ref``: max absolute error, that error
    relative to max|ref|, and top-1 agreement; fails past ``rtol``.  Within
    it, top-1 can flip only on a row whose top-2 gap is under twice the
    tolerated error, so the error bound also decides top-1."""
    import numpy as np

    got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    check(got.shape == ref.shape, f"{what}: shape {got.shape} != {ref.shape}")
    check(bool(np.isfinite(got).all()), f"{what}: non-finite logits")
    scale = float(np.abs(ref).max())
    err = float(np.abs(got - ref).max())
    rel = err / scale
    top2 = np.sort(ref, axis=-1)[:, -2:]
    close = int(((top2[:, 1] - top2[:, 0]) <= 2 * rtol * scale).sum())
    agree = int((got.argmax(-1) == ref.argmax(-1)).sum())
    log(phase, f"{what}: max abs err {err:.3e}, rel {rel:.3e} "
               f"(tol {rtol:g}), top-1 agree {agree}/{len(ref)} "
               f"({close} rows with top-2 gap inside the tolerance)")
    check(rel <= rtol, f"{what}: rel err {rel:.3e} > {rtol:g}")


def check_spread(phase: str, ref, rtol: float) -> None:
    """Fails unless the reference logits of different inputs differ by more
    than ``MIN_SPREAD_OVER_RTOL * rtol`` of their scale: a comparison at
    ``rtol`` then fails a result that ignores its input."""
    import numpy as np

    ref = np.asarray(ref, np.float64)
    spread = float(np.abs(ref - ref[:1]).max() / np.abs(ref).max())
    log(phase, f"reference logits: max|logit| {np.abs(ref).max():.4g}, "
               f"spread across inputs {spread:.3e} of it")
    check(spread > MIN_SPREAD_OVER_RTOL * rtol,
          f"reference spread {spread:.3e} <= {MIN_SPREAD_OVER_RTOL} x "
          f"{rtol:g}: the comparison could not fail")


def logits_model(model: str, image: int, batch: int = 1):
    """``model``'s zoo graph without its closing softmax, and its input
    shapes.  With random weights the logits reach ~1e4 and the softmax
    saturates to the same one-hot row for every input, so comparing its
    output could never fail; comparing the logits can."""
    from repro.core.graph import Graph
    from repro.models.cnn import build

    g, shapes = build(model, batch=batch, image=image)
    (out,) = g.outputs
    head = g.nodes[out]
    check(head.op == "softmax", f"{model} ends in {head.op}, not softmax")
    logits = Graph()
    for n in g.topo_order():
        if n.name != out:
            logits.add(n.name, n.op, n.inputs, **n.attrs)
    logits.mark_output(head.inputs[0])
    return logits, shapes


def check_device(chips: int):
    """The running device, or exit: this script never runs on the CPU."""
    import jax

    from repro.core.peaks import PEAKS

    devs = jax.devices()
    d = devs[0]
    log("device", f"platform={d.platform} device_kind={d.device_kind} "
                  f"count={len(devs)} "
                  f"in_peak_table={d.device_kind in PEAKS}")
    if d.platform != "tpu":
        sys.exit(f"chip_smoke.py: no TPU found (jax.devices()[0] is "
                 f"{d.platform}); this script runs only on a TPU")
    if len(devs) < chips:
        sys.exit(f"chip_smoke.py: needs {chips} TPU chips, found "
                 f"{len(devs)}")
    return devs


def inputs(seed: int, n: int, image: int):
    """``n`` single-image requests, from ``seed``."""
    import numpy as np

    rng = np.random.default_rng(seed)
    return [rng.normal(size=(1, 3, image, image)).astype(np.float32)
            for _ in range(n)]


def warm(sess, image: int, phase: str) -> None:
    """Compile every bucket of ``sess`` (first call = compile + one run)."""
    import jax
    import jax.numpy as jnp

    for b in sess.batch_sizes:
        t0 = time.perf_counter()
        jax.block_until_ready(sess.specialize(b).predict(
            jnp.zeros((b, 3, image, image), jnp.float32)))
        log(phase, f"bucket {b}: first call (compile + run) "
                   f"{time.perf_counter() - t0:.2f} s")


def serve(sess, xs, workers: int = 1, devices=None, bucket: int = None):
    """Serve ``xs`` one request each through ``AsyncServer`` with dynamic
    batching, every batch in ``bucket`` when it is given; returns the
    outputs and the server's stats."""
    import jax.numpy as jnp

    from repro.engine import AsyncServer, DynamicBatchPolicy

    policy = DynamicBatchPolicy(max_batch=max(sess.batch_sizes),
                                max_wait_ms=2.0, fixed_bucket=bucket)
    with AsyncServer(sess, policy, max_queue=4 * len(xs), workers=workers,
                     devices=devices) as srv:
        futs = [srv.submit(jnp.asarray(x)) for x in xs]
        outs = [f.result(timeout=600) for f in futs]
    return outs, srv.stats


@contextlib.contextmanager
def precision_highest():
    """"highest" matmul precision for the whole process: unlike the
    thread-local ``jax.default_matmul_precision``, the server's worker
    threads see it too."""
    import jax

    prev = jax.config.jax_default_matmul_precision
    jax.config.update("jax_default_matmul_precision", "highest")
    try:
        yield
    finally:
        jax.config.update("jax_default_matmul_precision", prev)


def serve_highest(phase: str, sess, xs, bucket: int, **kw):
    """``xs`` served at "highest" precision, every batch padded into
    ``bucket``; returns the rows in request order and the stats."""
    with precision_highest():
        outs, st = serve(sess, xs, bucket=bucket, **kw)
    check(st.n_completed == len(xs), f"served {st.n_completed}/{len(xs)}")
    check(st.rows_executed + st.rows_padded == bucket * st.n_batches,
          f"{st.n_batches} batches ran {st.rows_executed} + "
          f"{st.rows_padded} padded rows, not all in bucket {bucket}")
    log(phase, f"served {len(xs)} requests at precision=highest in "
               f"{st.n_batches} batches, all in bucket {bucket} "
               f"({st.rows_padded} padded rows)")
    return outs, st


def cpu_logits(model, xs):
    """``model``'s plan and parameters run on the host's CPU backend."""
    import jax
    import numpy as np

    cpu = jax.devices("cpu")[0]
    rep = model.replica(cpu)
    return np.concatenate([np.asarray(rep.predict(jax.device_put(x, cpu)))
                           for x in xs])


def highest(sess, xs):
    """``sess``'s logits for ``xs`` at "highest" matmul precision."""
    import jax.numpy as jnp
    import numpy as np

    with precision_highest():
        return np.concatenate([np.asarray(sess.predict(jnp.asarray(x)))
                               for x in xs])


def resnet_phase(model: str, image: int, seed: int, n_requests: int,
                 n_ref: int):
    """Phase 2.  Returns the reference inputs, their served logits and
    their CPU-reference logits, for the Pallas phase."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.local_search import search_calls
    from repro.engine import InferenceSession, compile

    phase = "resnet50"
    t0 = time.perf_counter()
    sess = compile(*logits_model(model, image), seed=seed)
    log(phase, f"{model} {image}x{image} (logits): bucket 1 planned + "
               f"bound in {time.perf_counter() - t0:.2f} s "
               f"(tuning={sess.tuning})")
    t0 = time.perf_counter()
    sess.specialize(8)
    log(phase, f"bucket 8 planned + bound in "
               f"{time.perf_counter() - t0:.2f} s")
    art = OUT / model
    shutil.rmtree(art, ignore_errors=True)
    sess.save(art)

    n_searches = search_calls()
    t0 = time.perf_counter()
    loaded = InferenceSession.load(art)
    log(phase, f"saved + loaded {art} in "
               f"{time.perf_counter() - t0:.2f} s, buckets "
               f"{loaded.batch_sizes}")
    warm(loaded, image, phase)
    xs = inputs(seed, n_requests, image)
    outs, st = serve(loaded, xs)
    check(search_calls() == n_searches,
          f"load -> serve ran {search_calls() - n_searches} schedule "
          "searches (want 0)")
    check(st.n_completed == n_requests,
          f"served {st.n_completed}/{n_requests}")
    log(phase, f"served {st.n_completed} requests in {st.n_batches} "
               f"batches (batch sizes {sorted(st.batch_hist.counts())}), "
               f"zero schedule searches on load -> serve")
    served = np.concatenate([np.asarray(o) for o in outs[:n_ref]])

    ref = cpu_logits(loaded.specialize(1), xs[:n_ref])
    check_spread(phase, ref, HIGHEST_PRECISION_RTOL)
    compare(phase, "served vs CPU reference", served, ref,
            DEFAULT_PRECISION_RTOL)
    # the tolerance that separates inputs, on both programs: bucket 1
    # called directly, and the largest bucket through the server, its
    # requests padded in beside each other
    compare(phase, "bucket 1 at precision=highest vs CPU reference",
            highest(loaded, xs[:n_ref]), ref, HIGHEST_PRECISION_RTOL)
    big = max(loaded.batch_sizes)
    outs, _ = serve_highest(phase, loaded, xs[:n_ref], big)
    compare(phase, f"served in bucket {big} at precision=highest vs CPU "
                   "reference", np.concatenate([np.asarray(o) for o in outs]),
            ref, HIGHEST_PRECISION_RTOL)
    return xs[:n_ref], served, ref


def pallas_phase(model: str, image: int, seed: int, xs, jnp_logits,
                 ref) -> None:
    """Phase 3: the Pallas conv path, compiled, against phase 2's served
    logits and, at "highest" precision, against its CPU reference."""
    import jax.numpy as jnp
    import numpy as np

    from repro.engine import compile
    from repro.kernels.pltpu_compat import resolve_interpret

    phase = "pallas"
    t0 = time.perf_counter()
    sess = compile(*logits_model(model, image), seed=seed, use_pallas=True)
    interpret = resolve_interpret(sess.interpret)
    log(phase, f"use_pallas=True planned + bound in "
               f"{time.perf_counter() - t0:.2f} s, interpret resolved to "
               f"{interpret}")
    check(interpret is False, "the Pallas kernel would run interpreted")
    warm(sess, image, phase)
    got = np.concatenate([np.asarray(sess.predict(jnp.asarray(x)))
                          for x in xs])
    compare(phase, "Pallas vs jnp templates (both on the chip)", got,
            jnp_logits, DEFAULT_PRECISION_RTOL)
    compare(phase, "Pallas vs CPU reference", got, ref,
            DEFAULT_PRECISION_RTOL)
    compare(phase, "Pallas at precision=highest vs CPU reference",
            highest(sess, xs), ref, HIGHEST_PRECISION_RTOL)


def lm_phase(seed: int, prompt_len: int = 12, gen: int = 8) -> None:
    """Phase 4: reduced qwen2-1.5b, streamed tokens == generate."""
    import numpy as np

    from repro.configs import ARCHS, reduced
    from repro.engine import AsyncServer, DynamicBatchPolicy, compile_lm

    phase = "lm"
    cfg = reduced(ARCHS["qwen2-1.5b"])
    t0 = time.perf_counter()
    sess = compile_lm(cfg, max_len=32, seed=seed, prewarm=True)
    log(phase, f"{cfg.name} (reduced: d_model={cfg.d_model}, "
               f"layers={cfg.n_layers}) compiled + prewarmed in "
               f"{time.perf_counter() - t0:.2f} s, seq buckets "
               f"{sess.seq_buckets}")
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg.vocab, size=(sess.batch, n))
               .astype(np.int32) for n in (prompt_len, prompt_len // 2 + 1)]
    want = [sess.generate(p, gen) for p in prompts]
    srv = AsyncServer(sess, DynamicBatchPolicy(max_batch=1, max_wait_ms=1.0))
    try:
        streams = [srv.submit_stream(p, gen) for p in prompts]
        got = [np.stack([np.asarray(t) for t in s], axis=1) for s in streams]
    finally:
        srv.close(drain=True)
    for i, (g, w) in enumerate(zip(got, want)):
        check(g.shape == w.shape and bool((g == w).all()),
              f"prompt {i}: streamed {g.tolist()} != generate {w.tolist()}")
    log(phase, f"streamed {len(prompts)} x {gen} tokens through "
               f"AsyncServer.submit_stream, equal to LMSession.generate")


def four_chip_phase(model: str, image: int, seed: int, devs) -> None:
    """``--four-chips``: the batch-sharded artifact and four one-chip
    replicas, each against a one-chip run of the same artifact."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.engine import InferenceSession, compile

    phase = "four-chips"
    chips = devs[:4]
    t0 = time.perf_counter()
    sess = compile(*logits_model(model, image, batch=8), seed=seed,
                   devices=4)
    log(phase, f"{model} {image}x{image} bucket 8 sharded over 4 chips "
               f"(2 rows each) planned + bound in "
               f"{time.perf_counter() - t0:.2f} s")
    art = OUT / f"{model}-4chips"
    shutil.rmtree(art, ignore_errors=True)
    sess.save(art)
    sharded = InferenceSession.load(art)
    single = InferenceSession.load(art, devices=1)
    check(sharded.devices == 4 and single.devices == 1,
          f"loaded devices {sharded.devices}, {single.devices}")

    xs = inputs(seed, 8, image)
    x8 = jnp.asarray(np.concatenate(xs))
    m = sharded.specialize(8)
    t0 = time.perf_counter()
    y = m.predict(x8)
    jax.block_until_ready(y)
    log(phase, f"sharded bucket 8: first call (compile + run) "
               f"{time.perf_counter() - t0:.2f} s")
    param_devs = {d for leaf in jax.tree.leaves(m.params)
                  for d in leaf.devices()}
    check(param_devs == set(chips) and y.sharding.device_set == set(chips),
          f"sharded params on {param_devs}, output on "
          f"{y.sharding.device_set}; want {set(chips)}")
    log(phase, f"sharded: parameters and output on "
               f"{sorted(d.id for d in y.sharding.device_set)}")
    # one chip, same artifact, the same per-chip program (2 rows)
    rows = [x8[i:i + 2] for i in range(0, 8, 2)]
    one = np.concatenate([np.asarray(single.predict(r)) for r in rows])
    log(phase, f"sharded vs one chip bit-identical: "
               f"{np.asarray(y).tobytes() == one.tobytes()}")
    compare(phase, "sharded vs one chip", y, one, DEFAULT_PRECISION_RTOL)
    one_hi = highest(single, rows)
    check_spread(phase, one_hi, HIGHEST_PRECISION_RTOL)
    compare(phase, "sharded vs one chip, precision=highest",
            highest(sharded, [x8]), one_hi, HIGHEST_PRECISION_RTOL)

    m1 = single.specialize(8)
    one8 = np.asarray(m1.predict(x8))
    for d in chips:
        rep = m1.replica(d)
        yd = rep.predict(x8)
        pd = {dd for leaf in jax.tree.leaves(rep.params)
              for dd in leaf.devices()}
        check(pd == {d} and yd.devices() == {d},
              f"replica for chip {d.id}: params on {pd}, output on "
              f"{yd.devices()}")
        # the same executable on a chip of the same kind
        log(phase, f"replica on chip {d.id} bit-identical to one chip: "
                   f"{np.asarray(yd).tobytes() == one8.tobytes()}")
        compare(phase, f"replica on chip {d.id} vs one chip", yd, one8,
                HIGHEST_PRECISION_RTOL)
    reqs = inputs(seed + 1, 64, image)
    outs, st = serve_highest(phase, single, reqs, 8, workers=4,
                             devices=chips)
    out_devs = {d for o in outs for d in o.devices()}
    log(phase, f"AsyncServer(workers=4): batches per worker "
               f"{dict(sorted(st.worker_batches.items()))}, outputs on "
               f"chips {sorted(d.id for d in out_devs)}")
    check(out_devs == set(chips), f"outputs on {out_devs}, want {chips}")
    # one chip, the same bucket-8 program, the same requests
    want = highest(m1, [np.concatenate(reqs[i:i + 8])
                        for i in range(0, len(reqs), 8)])
    check_spread(phase, want, HIGHEST_PRECISION_RTOL)
    got = np.concatenate([np.asarray(o) for o in outs])
    log(phase, f"AsyncServer(workers=4) bit-identical to one chip: "
               f"{got.tobytes() == want.tobytes()}")
    compare(phase, "AsyncServer(workers=4) vs one chip, precision=highest",
            got, want, HIGHEST_PRECISION_RTOL)


def result_line(devs) -> str:
    """The last line of a passing run: the device as JAX reports it."""
    d = devs[0]
    return json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind, "count": len(devs)}})


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawTextHelpFormatter)
    ap.add_argument("--seed", type=int, default=0,
                    help="weights and inputs (default 0)")
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip serving path")
    args = ap.parse_args(argv)
    if not (SRC / "repro").is_dir():
        sys.exit(f"chip_smoke.py: {SRC / 'repro'} not found; run it from a "
                 "checkout of the repository")
    sys.path.insert(0, str(SRC))
    # the CPU reference needs the host's CPU backend beside the TPU
    platforms = os.environ.get("JAX_PLATFORMS", "")
    if platforms and "cpu" not in platforms.split(","):
        os.environ["JAX_PLATFORMS"] = platforms + ",cpu"

    chips = 4 if args.four_chips else 1
    devs = check_device(chips)
    from repro.launch.cache import enable_compile_cache

    log("device", f"compile cache: {enable_compile_cache()}")
    model, image = "resnet-50", 224
    if args.four_chips:
        four_chip_phase(model, image, args.seed, devs)
    else:
        xs, logits, ref = resnet_phase(model, image, args.seed,
                                       n_requests=32, n_ref=4)
        pallas_phase(model, image, args.seed, xs, logits, ref)
        lm_phase(args.seed)
    print(result_line(devs), flush=True)


if __name__ == "__main__":
    main()
