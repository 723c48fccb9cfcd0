"""Serving driver: batched prefill + decode loop (the paper's kind of
workload — latency-focused inference).

Greedy-decodes a batch of synthetic prompts with a reduced config;
at production scale the same prefill/decode_step functions are what the
dry-run lowers onto the 256/512-chip meshes.

``--artifact <dir>`` instead serves from a saved artifact with zero
schedule search and zero weight transformation — the fast-cold-start
path.  The manifest routes the workload family: CNN ``InferenceSession``
artifacts go load -> predict through the dynamic-batching driver, LM
artifacts (manifest ``lm`` section, built with
``engine.compile(<LM config>, ...).save(dir)``) go load -> prewarm ->
``submit_stream`` with seq-bucketed prefill and streamed greedy decode.

Multi-device serving: ``--devices D`` serves on the first D of
``jax.devices()`` — the chips of a TPU host — and fails if the process
sees fewer; ``--workers N`` runs N driver workers over one queue, each
worker's replica on its own device of those D.  Unless ``JAX_PLATFORMS``
names a platform other than cpu, D host cores are first exposed as JAX
CPU devices (``launch.cpu.configure_cpu_devices``, before the backend
initializes), so a CPU-only host serves on them.
``--pin-workers`` gives each worker its own CPU affinity set.  The
allocator/threading env preset (``launch.cpu.apply_serving_env``:
tcmalloc LD_PRELOAD detection, log/alloc-report hygiene — warn, never
fail) is applied on every serve.

The compile cache lives where ``launch.cache.enable_compile_cache``
says (``JAX_COMPILATION_CACHE_DIR``, else ``<checkout>/.jax_cache``).

Examples:
    PYTHONPATH=src python -m repro.launch.serve --arch mamba2-130m \
        --batch 4 --prompt-len 32 --gen 16
    PYTHONPATH=src python -m repro.launch.serve --artifact artifact/ \
        --requests 50 --devices 4 --workers 4
"""
from __future__ import annotations

import argparse
import sys
import time

from repro.launch.cache import enable_compile_cache
from repro.launch.cpu import (apply_serving_env, configure_cpu_devices,
                              cpu_platform_allowed)

# on a CPU run, --devices host cores must exist before the first jax
# import below locks the backend: peek at argv here (only when this
# module IS the entrypoint — `python -m repro.launch.serve` executes it
# as __main__), full parsing stays in main().  A TPU host's chips exist
# already; serve_artifact selects them.  Unless JAX_PLATFORMS names
# another platform the host cores are exposed: the flag shapes only the
# CPU backend, which a TPU host's serving does not use.
if __name__ == "__main__" and cpu_platform_allowed():
    _early = argparse.ArgumentParser(add_help=False)
    _early.add_argument("--devices", type=int, default=None)
    _early_args, _ = _early.parse_known_args(sys.argv[1:])
    if _early_args.devices:
        configure_cpu_devices(_early_args.devices)

import jax                               # noqa: E402
import jax.numpy as jnp                  # noqa: E402
import numpy as np                       # noqa: E402

from repro.configs import ARCHS, reduced as make_reduced   # noqa: E402
from repro.models.lm import model                          # noqa: E402


def serve_artifact(path: str, n_requests: int, *, max_batch: int = 8,
                   max_wait_ms: float = 2.0, max_queue: int = 64,
                   deadline_ms: float = None, workers: int = 1,
                   devices: int = None, pin=None, shed: str = "newest",
                   retry_budget: int = 2, backoff_ms: float = 10.0,
                   watchdog_ms: float = None, show_health: bool = False,
                   dtype: str = None, trace: str = "uniform",
                   priority_default: str = "standard",
                   buckets: str = None, stats_interval: float = None):
    """Cold-start CNN serving through the async dynamic-batching driver:
    load the compiled session artifact, pump a stream of single-image
    requests through a bounded queue (client-side backpressure on
    ``QueueFullError``), and drain gracefully on shutdown.  The driver
    packs requests into the artifact's specialized batch sizes, so the
    whole run stays at zero schedule searches; ``workers > 1`` executes
    batches concurrently through per-device program replicas, on the
    first ``devices`` of ``jax.devices()`` when that is given (all of
    them otherwise).

    Fault-tolerance knobs map straight onto ``AsyncServer``: ``shed``
    picks the overload policy, ``retry_budget``/``backoff_ms`` configure
    crash-recovery retries, ``watchdog_ms`` arms the hung-batch watchdog
    (set it well above a worst-case batch — buckets are pre-warmed here,
    so JIT compilation cannot trip it).

    Traffic-aware knobs: ``trace`` replays a synthetic arrival shape
    (``engine.traffic.synth_trace`` kinds — "uniform" keeps the legacy
    back-to-back single-image stream), ``priority_default`` classes
    unlabeled requests, ``buckets="auto"`` re-saves the artifact after
    the run with the bucket set solved from the *measured* arrival
    histogram, and ``stats_interval`` prints live telemetry snapshots
    from a daemon thread while the stream is in flight."""
    apply_serving_env()
    from repro.core.local_search import search_calls
    from repro.engine import (AsyncServer, DynamicBatchPolicy,
                              InferenceSession, QueueFullError, RetryPolicy,
                              expected_padded_waste, synth_trace)

    if n_requests < 1:
        raise ValueError(f"--requests must be >= 1, got {n_requests}")
    devs = select_devices(devices)
    n_searches = search_calls()
    t0 = time.perf_counter()
    sess = InferenceSession.load(path)
    t_load = time.perf_counter() - t0
    if dtype is not None and sess.dtype != dtype:
        raise ValueError(
            f"--dtype {dtype} requested but artifact {path} was compiled "
            f"at {sess.dtype} precision; rebuild it with "
            f"engine.compile(..., dtype={dtype!r}).save(...)")
    (name,) = sess.input_spec
    shape = (1,) + sess.input_spec[name][1:]
    rng = np.random.default_rng(0)
    if trace == "uniform":
        reqs = [None] * n_requests           # legacy back-to-back stream
        xs = [jnp.asarray(rng.normal(size=shape).astype(np.float32))
              for _ in range(n_requests)]
    else:
        # replay a synthetic arrival process: sized requests, paced
        # submits, mixed priority classes (sizes clamped to what the
        # artifact can pack so frozen sessions never see a typed reject)
        max_rows = min(max_batch, max(sess.batch_sizes))
        reqs = synth_trace(trace, n=n_requests, seed=0, mean_rate=100.0,
                           max_rows=max_rows,
                           priorities=("interactive", "standard", "batch"))
        xs = [jnp.asarray(rng.normal(size=(r.rows,) + shape[1:])
                          .astype(np.float32)) for r in reqs]
    for b in sess.batch_sizes:       # server startup: compile every bucket
        jax.block_until_ready(sess.specialize(b).predict(
            jnp.zeros((b,) + shape[1:], jnp.float32)))

    policy = DynamicBatchPolicy(max_batch=max_batch,
                                max_wait_ms=max_wait_ms,
                                order="fifo" if trace == "uniform"
                                else "edf")
    server = AsyncServer(sess, policy, max_queue=max_queue,
                         workers=workers, devices=devs, pin=pin, shed=shed,
                         retry=RetryPolicy(budget=retry_budget,
                                           backoff_ms=backoff_ms),
                         watchdog_ms=watchdog_ms,
                         priority_default=priority_default)
    stop_stats = None
    if stats_interval is not None:
        import threading

        stop_stats = threading.Event()

        def _report():
            while not stop_stats.wait(stats_interval):
                s = server.stats
                print(f"[stats] queued={len(server)} "
                      f"completed={s.n_completed} batches={s.n_batches} "
                      f"p50={s.percentile_ms(50):.1f} "
                      f"p99={s.percentile_ms(99):.1f} ms")

        threading.Thread(target=_report, daemon=True,
                         name="serve-stats").start()
    t_serve0 = time.perf_counter()
    futures = []
    n_retries = 0
    try:
        for req, x in zip(reqs, xs):
            if req is not None and req.t > time.perf_counter() - t_serve0:
                time.sleep(req.t - (time.perf_counter() - t_serve0))
            while True:
                try:
                    futures.append(server.submit(
                        x, deadline_ms=deadline_ms,
                        priority=req.priority if req is not None
                        else None))
                    break
                except QueueFullError:
                    # backpressure: wait for the newest outstanding result
                    # (FIFO — once it lands the queue has drained) instead
                    # of growing the queue without bound
                    n_retries += 1
                    futures[-1].result()
        out = None
        for f in futures:
            out = f.result()
        if show_health:
            import json as _json
            print("health:", _json.dumps(server.health(), indent=2))
    finally:
        if stop_stats is not None:
            stop_stats.set()
        server.close(drain=True)                  # graceful shutdown
    t_serve = time.perf_counter() - t_serve0
    assert search_calls() == n_searches, \
        "artifact serving must not re-run any schedule search"
    st = server.stats
    print(f"artifact={path} model={sess.model_name or '?'} "
          f"dtype={sess.dtype} "
          f"load={t_load * 1e3:.0f} ms (zero search, zero re-binding) "
          f"buckets={sess.batch_sizes} devices={sess.devices} "
          f"workers={workers} on {len(devs)} {devs[0].platform} device(s)")
    print(f"served {st.n_completed}/{n_requests} requests in "
          f"{st.n_batches} batches "
          f"(mean {st.rows_executed / max(st.n_batches, 1):.1f} rows, "
          f"{st.rows_padded} padded rows, {n_retries} backpressure waits): "
          f"{n_requests / t_serve:.1f} req/s  "
          f"p50={st.percentile_ms(50):.1f} "
          f"p90={st.percentile_ms(90):.1f} "
          f"p99={st.percentile_ms(99):.1f} ms")
    if trace != "uniform":
        per_class = {cls: round(q.percentile(99) * 1e3, 1)
                     for cls, q in sorted(st.latency_by_class.items())}
        print(f"trace={trace} per-class p99 (ms): {per_class}")
    if buckets == "auto":
        # close the measured-traffic loop: re-save the artifact with the
        # bucket set solved from what this run actually observed
        from repro.engine import solve_buckets

        hist = st.arrival_hist.counts()
        old = sorted(sess.batch_sizes)
        try:
            learned = solve_buckets(hist, devices=sess.devices)
            sess.save(path, buckets="auto", traffic=st.arrival_hist)
        except RuntimeError as e:
            print(f"--buckets auto skipped: {e} (save the artifact with "
                  "include_source=True to make its bucket set learnable)")
        else:
            print(f"re-saved {path} with learned buckets {learned}: "
                  f"expected padded waste "
                  f"{expected_padded_waste(hist, learned)} rows vs "
                  f"{expected_padded_waste(hist, old)} with the previous "
                  f"set {old}, on the measured histogram "
                  f"{dict(sorted(hist.items()))}")
    return out


def select_devices(n: int = None):
    """The first ``n`` of ``jax.devices()`` (all of them for None); fails
    when the process sees fewer."""
    devs = jax.devices()
    if n is None:
        return devs
    if n < 1 or n > len(devs):
        raise ValueError(
            f"--devices {n} asked for, but this process sees {len(devs)} "
            f"{devs[0].platform} device(s)")
    return devs[:n]


def serve_lm_artifact(path: str, n_requests: int, *, gen: int = 8,
                      max_queue: int = 64, deadline_ms: float = None,
                      retry_budget: int = 2, backoff_ms: float = 10.0,
                      watchdog_ms: float = None, show_health: bool = False,
                      priority_default: str = "standard"):
    """Cold-start LM serving: load the seq-bucketed ``LMSession``
    artifact, prewarm every prefill bucket + the decode program, then
    stream ``n_requests`` greedy generations through ``submit_stream`` —
    each prompt prefills the largest bucket <= its length, catches up
    through decode, and its tokens arrive on a :class:`TokenStream` as
    the worker produces them.  The whole run is zero schedule searches
    (asserted), mirroring the CNN cold-start path."""
    apply_serving_env()
    from repro.core.local_search import search_calls
    from repro.engine import (AsyncServer, DynamicBatchPolicy, LMSession,
                              QueueFullError, RetryPolicy)

    if n_requests < 1:
        raise ValueError(f"--requests must be >= 1, got {n_requests}")
    n_searches = search_calls()
    t0 = time.perf_counter()
    sess = LMSession.load(path)
    t_load = time.perf_counter() - t0
    t0 = time.perf_counter()
    sess.prewarm()                      # compile every bucket + decode once
    t_warm = time.perf_counter() - t0
    max_prompt = sess.max_len - gen + 1
    if max_prompt < 1:
        raise ValueError(f"--gen {gen} does not fit the artifact's "
                         f"max_len={sess.max_len}; lower it")
    rng = np.random.default_rng(0)
    lens = rng.integers(1, max_prompt + 1, size=n_requests)
    prompts = [jnp.asarray(rng.integers(0, sess.cfg.vocab,
                                        size=(sess.batch, int(n))),
                           jnp.int32) for n in lens]
    # streams execute alone, so the packing knobs are moot — keep the
    # queue/deadline/retry/watchdog machinery identical to CNN serving
    server = AsyncServer(sess, DynamicBatchPolicy(max_batch=1,
                                                  max_wait_ms=1.0),
                         max_queue=max_queue,
                         retry=RetryPolicy(budget=retry_budget,
                                           backoff_ms=backoff_ms),
                         watchdog_ms=watchdog_ms,
                         priority_default=priority_default)
    t_serve0 = time.perf_counter()
    streams = []
    n_retries = 0
    n_tokens = 0
    t_first = None
    try:
        for x in prompts:
            while True:
                try:
                    streams.append(server.submit_stream(
                        x, gen, deadline_ms=deadline_ms))
                    break
                except QueueFullError:
                    n_retries += 1
                    for _ in streams[-1]:
                        pass
        for s in streams:
            for tok in s:                 # tokens arrive per decode step
                if t_first is None:
                    t_first = time.perf_counter() - t_serve0
                n_tokens += tok.shape[-1] if hasattr(tok, "shape") else 1
        if show_health:
            import json as _json
            print("health:", _json.dumps(server.health(), indent=2))
    finally:
        server.close(drain=True)
    t_serve = time.perf_counter() - t_serve0
    assert search_calls() == n_searches, \
        "LM artifact serving must not re-run any schedule search"
    st = server.stats
    print(f"artifact={path} model={sess.model_name or sess.cfg.name} "
          f"family={sess.cfg.family} load={t_load * 1e3:.0f} ms "
          f"prewarm={t_warm * 1e3:.0f} ms (zero search) "
          f"seq_buckets={sess.seq_buckets} max_len={sess.max_len} "
          f"batch={sess.batch}")
    print(f"streamed {st.n_completed}/{n_requests} generations "
          f"({n_tokens} decode steps, first token "
          f"{(t_first or 0) * 1e3:.0f} ms, {n_retries} backpressure "
          f"waits): {n_tokens / max(t_serve, 1e-9):.1f} tok/s  "
          f"p50={st.percentile_ms(50):.1f} "
          f"p99={st.percentile_ms(99):.1f} ms/generation")
    return st.n_completed


def _artifact_is_lm(path: str) -> bool:
    """Peek the manifest to route ``--artifact`` without deserializing
    anything: LM artifacts carry a populated ``lm`` section."""
    import json
    from pathlib import Path
    manifest = Path(path) / "manifest.json"
    if not manifest.is_file():
        return False
    try:
        return bool(json.loads(manifest.read_text()).get("lm"))
    except (OSError, ValueError):
        return False


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-1.5b", choices=sorted(ARCHS))
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--artifact", default=None,
                    help="serve a saved artifact through the async "
                         "driver (zero search): CNN InferenceSession "
                         "artifacts get dynamic batching, LM artifacts "
                         "(manifest 'lm' section) get seq-bucketed "
                         "prefill + streamed decode; routed "
                         "automatically from the manifest")
    ap.add_argument("--requests", type=int, default=20,
                    help="request count for --artifact serving")
    ap.add_argument("--max-batch", type=int, default=8,
                    help="driver packing limit (rows per executed batch)")
    ap.add_argument("--max-wait-ms", type=float, default=2.0,
                    help="flush a partial batch after this queue age")
    ap.add_argument("--max-queue", type=int, default=64,
                    help="bounded queue capacity (backpressure beyond it)")
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="per-request deadline; queued past it fails typed")
    ap.add_argument("--devices", type=int, default=None,
                    help="serve on the first this many of jax.devices() "
                         "(the chips of a TPU host); fails if there are "
                         "fewer.  Unless JAX_PLATFORMS names a platform "
                         "other than cpu, this many host cores are "
                         "exposed as CPU devices first")
    ap.add_argument("--workers", type=int, default=1,
                    help="driver worker threads (per-device program "
                         "replicas behind one queue)")
    ap.add_argument("--pin-workers", action="store_true",
                    help="pin each worker thread to its own CPU set")
    ap.add_argument("--shed", default="newest",
                    choices=("newest", "oldest", "deadline"),
                    help="overload policy when the queue is full: reject "
                         "the newcomer, shed the oldest queued request, or "
                         "shed the queued request closest to its deadline")
    ap.add_argument("--retry-budget", type=int, default=2,
                    help="re-executions a request may get after a worker "
                         "crash or failed batch (0 disables retries)")
    ap.add_argument("--backoff-ms", type=float, default=10.0,
                    help="initial retry backoff (doubles per attempt, "
                         "capped at 1 s)")
    ap.add_argument("--watchdog-ms", type=float, default=None,
                    help="hung-batch watchdog: a worker silent this long "
                         "while holding a batch is restarted and its "
                         "batch requeued (off by default)")
    ap.add_argument("--trace", default="uniform",
                    choices=("uniform", "bursty", "diurnal", "heavytail"),
                    help="arrival shape for --artifact serving: 'uniform' "
                         "is the legacy back-to-back single-image stream; "
                         "the others replay a paced synthetic trace with "
                         "mixed request sizes and priority classes "
                         "(EDF packing)")
    ap.add_argument("--priority-default", default="standard",
                    choices=("interactive", "standard", "batch"),
                    help="priority class for requests submitted without "
                         "an explicit one")
    ap.add_argument("--buckets", default=None, choices=("auto",),
                    help="'auto' re-saves the artifact after the run with "
                         "the bucket set solved from the measured arrival "
                         "histogram (needs a source-packed artifact)")
    ap.add_argument("--stats-interval", type=float, default=None,
                    help="print live telemetry snapshots every this many "
                         "seconds while the stream is in flight")
    ap.add_argument("--health", action="store_true",
                    help="print the server health() snapshot after the run "
                         "(includes the telemetry section: arrival "
                         "histogram, queue-depth peak, per-class latency)")
    ap.add_argument("--dtype", default=None, choices=("fp32", "int8"),
                    help="require the artifact to carry this weight "
                         "precision (int8 = W8 per-channel quantized); "
                         "fails fast on a mismatch instead of silently "
                         "serving the other precision")
    args = ap.parse_args(argv)

    if args.artifact and _artifact_is_lm(args.artifact):
        return serve_lm_artifact(args.artifact, args.requests,
                                 gen=args.gen,
                                 max_queue=args.max_queue,
                                 deadline_ms=args.deadline_ms,
                                 retry_budget=args.retry_budget,
                                 backoff_ms=args.backoff_ms,
                                 watchdog_ms=args.watchdog_ms,
                                 show_health=args.health,
                                 priority_default=args.priority_default)
    if args.artifact:
        return serve_artifact(args.artifact, args.requests,
                              max_batch=args.max_batch,
                              max_wait_ms=args.max_wait_ms,
                              max_queue=args.max_queue,
                              deadline_ms=args.deadline_ms,
                              workers=args.workers,
                              devices=args.devices,
                              pin="auto" if args.pin_workers else None,
                              shed=args.shed,
                              retry_budget=args.retry_budget,
                              backoff_ms=args.backoff_ms,
                              watchdog_ms=args.watchdog_ms,
                              show_health=args.health,
                              dtype=args.dtype,
                              trace=args.trace,
                              priority_default=args.priority_default,
                              buckets=args.buckets,
                              stats_interval=args.stats_interval)

    cfg = make_reduced(ARCHS[args.arch])
    params = model.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompts = jnp.asarray(rng.integers(
        0, cfg.vocab, size=(args.batch, args.prompt_len)), jnp.int32)
    max_len = args.prompt_len + args.gen + cfg.n_img_tokens

    extra = {}
    if cfg.family == "vlm":
        extra["img_embeds"] = jnp.asarray(rng.normal(
            size=(args.batch, cfg.n_img_tokens, cfg.d_model)), jnp.float32)
    if cfg.family == "encdec":
        extra["frames"] = jnp.asarray(rng.normal(
            size=(args.batch, cfg.enc_positions, cfg.d_model)), jnp.float32)

    prefill = jax.jit(lambda p, t, **kw: model.prefill(
        p, cfg, t, max_len=max_len, **kw))
    decode = jax.jit(lambda p, tok, cache, pos: model.decode_step(
        p, cfg, tok, cache, pos))

    t0 = time.time()
    cache, logits = prefill(params, prompts, **extra)
    jax.block_until_ready(logits)
    t_prefill = time.time() - t0

    tok = jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
    out_tokens = [tok]
    pos0 = args.prompt_len + (cfg.n_img_tokens if cfg.family == "vlm" else 0)
    t0 = time.time()
    for i in range(args.gen - 1):
        logits, cache = decode(params, tok, cache, jnp.int32(pos0 + i))
        tok = jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
        out_tokens.append(tok)
    jax.block_until_ready(tok)
    t_decode = time.time() - t0

    gen = jnp.concatenate(out_tokens, axis=1)
    print(f"arch={cfg.name} batch={args.batch}")
    print(f"prefill ({args.prompt_len} tok): {t_prefill * 1e3:.1f} ms")
    print(f"decode  ({args.gen - 1} steps): "
          f"{t_decode / max(args.gen - 1, 1) * 1e3:.2f} ms/tok")
    print(f"generated tokens[0]: {np.asarray(gen[0])[:12]}")
    return gen


if __name__ == "__main__":
    enable_compile_cache()
    main()
