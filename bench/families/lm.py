"""LM family: a decoder LM served through ``AsyncServer.submit_stream``.

One run, in the order the contract of ``bench/run.py`` gives:

1. set-up: check that the program can express the configuration (its
   ``LMConfig`` built from the published keys must be the program's own
   entry, else the run stops at once); make the bfloat16 weights from the
   seed on the device, one layer at a time; then the normal path:
   ``compile_lm`` with ``seq_buckets="auto"`` solved from the mix's
   prompt-length histogram, ``LMSession.save``, ``LMSession.load``, and
   an ``AsyncServer`` warmed on every prefill bucket and the catch-up
   and decode programs, so that nothing compiles in the window;
2. the window: a closed loop of ``clients`` (one) client sending the
   mix's (prompt, new tokens) pairs, drawn without replacement from its
   bag and refilled, each prompt's token ids uniform over the vocabulary;
   each request timed from submit to its future resolving on the host
   clock.  The bag in progress when the window closes is sent to its end,
   so that ``p50_ms`` is the median over whole bags: every run takes it
   over the same multiset of pairs, whatever the seed's order.  The
   requests the check samples are chosen from the seed before the window
   (``CHECK_REQUESTS`` of the first bag, and the one with the longest
   prompt) and keep their logits on the device.  With ``trace`` a
   profiler trace of whole requests in the middle of the window;
3. the check, after the window, with the server closed: each sampled
   request's served logits at every generated position against the
   plain reference (``bench/reference_lm.py``) run teacher-forced over
   the prompt and the served tokens, on weights drawn again from the
   seed, so that the check also covers the artifact's save and restore.
"""
from __future__ import annotations

import concurrent.futures
import dataclasses
import functools
import gc
import glob
import itertools
import shutil
import time
from typing import Dict, Iterator, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

import devtrace as T
import lm_spans
import peaks
import reference_lm as R
import traffic
from families.cnn import Compiles, quantile, seed_key

CHECK_REQUESTS = 4    # requests of the first bag compared with the reference
WAIT_AFTER_S = 60.0   # how long past the window's close the last bag may take
TRACE_S = 3.0         # whole requests are traced until this much has passed

# the program's LMConfig fields, from the published config.json keys
PROGRAM_KEYS = {"n_layers": "num_hidden_layers", "d_model": "hidden_size",
                "n_heads": "num_attention_heads",
                "n_kv": "num_key_value_heads", "d_ff": "intermediate_size",
                "vocab": "vocab_size", "norm_eps": "norm_epsilon",
                "rope_theta": "rope_theta", "local_window": "sliding_window",
                "qkv_bias": "use_bias", "proj_bias": "use_bias",
                "tie_embeddings": "tie_word_embeddings",
                "dtype": "torch_dtype"}


def program_config(cfg: Dict, rehearsal: bool = False):
    """The program's ``LMConfig`` for the configuration, checked against
    the program's own entry for it: a program that cannot express it, or
    whose entry differs, stops the run here, before any work."""
    from repro.configs import ARCHS
    from repro.models.lm import LMConfig

    if cfg["hidden_act"] != "gelu_pytorch_tanh" \
            or cfg["norm_type"] != "layer_norm":
        raise ValueError(f"{cfg['name']}: the family serves the dense "
                         "block with a tanh-GELU MLP and LayerNorm")
    kw = {k: cfg[v] for k, v in PROGRAM_KEYS.items()}
    try:
        want = LMConfig(name=cfg["arch"], family="dense", norm="layernorm",
                        mlp_gated=False,
                        head_dim=kw["d_model"] // kw["n_heads"], **kw)
    except TypeError as e:
        raise SystemExit(f"{cfg['name']}: the program's LMConfig cannot "
                         f"express this configuration ({e}); it needs the "
                         "dense family's sliding window and projection "
                         "biases") from e
    if rehearsal:
        return want
    have = ARCHS.get(cfg["arch"])
    if have is None:
        raise SystemExit(f"{cfg['name']}: the program has no entry "
                         f"{cfg['arch']!r}")
    diff = {f.name: (getattr(have, f.name), getattr(want, f.name))
            for f in dataclasses.fields(want)
            if getattr(have, f.name) != getattr(want, f.name)}
    if diff:
        raise SystemExit(f"{cfg['name']}: the program's {cfg['arch']!r} "
                         f"differs from the published configuration "
                         f"(program, published): {diff}")
    return have


def rehearsal_config(cfg: Dict) -> Dict:
    """A small configuration of the same block for a rehearsal on the
    CPU (``ctx.image`` set): 2 layers of width 64, a window of 64."""
    return dict(cfg, num_hidden_layers=2, hidden_size=64,
                num_attention_heads=4, num_key_value_heads=2,
                intermediate_size=128, vocab_size=512, sliding_window=64)


def _layer_shapes(cfg: Dict) -> Dict[str, Dict[str, Tuple[int, ...]]]:
    d = cfg["hidden_size"]
    hd = d // cfg["num_attention_heads"]
    kv = cfg["num_key_value_heads"] * hd
    ff = cfg["intermediate_size"]
    return {"ln1": {"w": (d,), "b": (d,)}, "ln2": {"w": (d,), "b": (d,)},
            "attn": {"wq": (d, d), "wk": (d, kv), "wv": (d, kv),
                     "wo": (d, d), "bq": (d,), "bk": (kv,), "bv": (kv,),
                     "bo": (d,)},
            "mlp": {"wu": (d, ff), "wd": (ff, d), "bu": (ff,), "bd": (d,)}}


def _draw(key, name: str, shape):
    """One leaf in float32: a weight N(0, 1/fan_in), a norm gain
    U(0.5, 1.5), a bias or a norm shift N(0, 0.02)."""
    if name == "w":
        return jax.random.uniform(key, shape, minval=0.5, maxval=1.5)
    if name.startswith("w"):
        return jax.random.normal(key, shape) / np.sqrt(shape[0])
    return 0.02 * jax.random.normal(key, shape)


@functools.lru_cache(maxsize=None)
def _layer_filler(shapes_key):
    shapes = dict(shapes_key)

    @functools.partial(jax.jit, donate_argnums=0)
    def fill(layers, key, i):
        # one layer's leaves in float32, written in place into the stack
        out = {}
        for g, (group, leaves) in enumerate(sorted(shapes.items())):
            out[group] = {}
            for j, (name, shape) in enumerate(sorted(dict(leaves).items())):
                k = jax.random.fold_in(jax.random.fold_in(key, g), j)
                x = _draw(k, name, shape).astype(jnp.bfloat16)
                out[group][name] = layers[group][name].at[i].set(x)
        return out

    return fill


def make_params(cfg: Dict, key):
    """The served weights in bfloat16, on the device, from ``key``, one
    layer at a time (the float32 draw never holds more than one layer):
    the program's tree, with every bias, norm gain and norm shift
    non-zero, and the embedding N(0, 0.02^2)."""
    n, d, v = (cfg["num_hidden_layers"], cfg["hidden_size"],
               cfg["vocab_size"])
    shapes = _layer_shapes(cfg)
    layers = {g: {k: jnp.zeros((n,) + s, jnp.bfloat16)
                  for k, s in leaves.items()}
              for g, leaves in shapes.items()}
    fill = _layer_filler(tuple((g, tuple(sorted(l.items())))
                               for g, l in sorted(shapes.items())))
    for i in range(n):
        layers = fill(layers, jax.random.fold_in(key, 100 + i),
                      jnp.int32(i))
    ke, kw, kb = jax.random.split(jax.random.fold_in(key, 1), 3)
    embed = (0.02 * jax.random.normal(ke, (v, d))).astype(jnp.bfloat16)
    final = {"w": _draw(kw, "w", (d,)).astype(jnp.bfloat16),
             "b": _draw(kb, "b", (d,)).astype(jnp.bfloat16)}
    return {"embed": embed, "final_norm": final, "layers": layers}


def pairs(mix: Dict, seed: int) -> Iterator[Tuple[int, int]]:
    """(prompt length, new tokens) of each request: the mix's bag drawn
    without replacement in an order from the seed, refilled when empty."""
    bag = [tuple(p) for p in mix["pairs"]]
    rng = traffic.rng_for(seed, 10)
    while True:
        for i in rng.permutation(len(bag)):
            yield bag[int(i)]


def prompt(vocab: int, seed: int, i: int, length: int) -> np.ndarray:
    """Request ``i``'s ``(1, length)`` token ids, uniform over the
    vocabulary, from the seed."""
    rng = traffic.rng_for(seed, 1000 + i)
    return rng.integers(0, vocab, size=(1, length), dtype=np.int32)


def checked(mix: Dict, seed: int) -> List[int]:
    """Requests the check compares, chosen before the window: ``CHECK_
    REQUESTS`` of the first bag drawn from the seed, and the first with
    the bag's longest prompt, whose positions reach past the window."""
    n = len(mix["pairs"])
    first = [p for p, _ in itertools.islice(pairs(mix, seed), n)]
    rng = traffic.rng_for(seed, 3)
    pick = set(int(i) for i in rng.choice(n, CHECK_REQUESTS, replace=False))
    pick.add(first.index(max(first)))
    return sorted(pick)


def peak_bytes(devs) -> int:
    """The most device memory any of ``devs`` has held so far."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs)


def warm(srv, buckets: List[int]) -> int:
    """Every program the window can reach, through the server: each
    prefill bucket, with one catch-up step, then decode steps and the
    argmax.  Returns the number of requests run."""
    for b in buckets:
        srv.submit_stream(np.zeros((1, b + 1), np.int32), 3,
                          keep_logits=True).result()
    return len(buckets)


def trace_reduce(trace_dir, cfg: Dict):
    """``lm_spans.traced`` of the trace under ``trace_dir``, or None."""
    files = glob.glob(str(trace_dir / "**" / "*.xplane.pb"), recursive=True)
    return lm_spans.traced(lm_spans.events(files[0]), cfg) if files else None


def whole_bags(sent: List[float], done: List[float], ok: List[int],
               bag: int) -> List[float]:
    """Submit -> resolved, ms, of every answered request of the whole bags
    sent; a bag cut short (its wait ran out) is left out."""
    whole = len(sent) - len(sent) % bag
    return [(done[i] - sent[i]) * 1e3 for i in ok if i < whole]


def judge(served: List[np.ndarray], refs: List[np.ndarray], failed: int,
          limit: float):
    """Whether a run is correct, and the numbers compared with their
    limits: the widest gap of a served logit over the reference's largest
    |logit| at its position, over every generated position of every
    sampled request, and the requests that failed or never came."""
    gap = max(float((np.abs(s.astype(np.float64) - r).max(-1)
                     / np.abs(r).max(-1)).max())
              for s, r in zip(served, refs)) if served else float("inf")
    checks = [("logit_gap", gap, limit),
              ("failed_requests", float(failed), 0.0)]
    return all(v <= lim for _, v, lim in checks), checks


def run(ctx) -> Dict:
    """One run of an LM cell; see the module's docstring.  ``ctx`` is
    ``bench/run.py``'s (``config``, ``mix``, ``chips``, ``seed``,
    ``seconds``, ``trace``, ``t0``, ``log``, ``cache``, ``trace_dir``);
    ``ctx.image`` set (``bench/readings.py --image``) rehearses a small
    configuration of the same block on the CPU, and ``ctx.control``
    judges the reference with float8 operands on the same sample."""
    from repro.core.local_search import search_calls
    from repro.engine import (AsyncServer, DynamicBatchPolicy, LMSession,
                              compile_lm)

    rehearsal = ctx.image is not None
    cfg = rehearsal_config(ctx.config) if rehearsal else ctx.config
    prog = program_config(cfg, rehearsal)
    mix, log = ctx.mix, ctx.log
    devs = jax.devices()[:ctx.chips]
    compiles = Compiles()
    phases = {"start": time.perf_counter() - ctx.t0}
    peaks_gb = {}                 # the device's peak after each phase

    params = jax.block_until_ready(make_params(cfg, seed_key(ctx.seed)))
    phases["weights"] = time.perf_counter() - ctx.t0
    peaks_gb["weights"] = peak_bytes(devs) / 1e9
    hist: Dict[int, int] = {}
    for p, _ in mix["pairs"]:
        hist[p] = hist.get(p, 0) + 1
    sess = compile_lm(prog, max_len=cfg["max_len"], seq_buckets="auto",
                      prompt_hist=hist, params=params)
    path = ctx.cache / "artifacts" / cfg["name"]
    shutil.rmtree(path, ignore_errors=True)    # the last run's 6 GB first
    sess.save(path)
    buckets = sess.seq_buckets
    del sess, params                   # one copy of the weights at a time
    gc.collect()
    phases["save"] = time.perf_counter() - ctx.t0
    peaks_gb["save"] = peak_bytes(devs) / 1e9
    searches = search_calls()
    sess = LMSession.load(path)
    phases["load"] = time.perf_counter() - ctx.t0
    peaks_gb["load"] = peak_bytes(devs) / 1e9
    srv = AsyncServer(sess, DynamicBatchPolicy(**cfg["policy"]),
                      max_queue=16)
    n_warm = warm(srv, buckets)
    setup_s = time.perf_counter() - ctx.t0
    peaks_gb["warm"] = peak_bytes(devs) / 1e9
    done_at = ", ".join(f"{k} done at {v:.2f}" for k, v in phases.items())
    log(f"set-up {setup_s:.3f} s ({done_at}): buckets {buckets}, "
        f"{n_warm} warm-up requests; device peak GB by phase {peaks_gb}")

    check = set(checked(mix, ctx.seed))
    it = pairs(mix, ctx.seed)
    bag = len(mix["pairs"])
    sent: List[float] = []
    done: List[float] = []
    futures: List[concurrent.futures.Future] = []
    reqs: List[Tuple[int, int]] = []
    before = sess.counters()
    tracing, traced_from = None, None
    compiles.on = True
    t0 = time.perf_counter()
    t_end = t0 + ctx.seconds
    trace_at = t0 + max(0.0, (ctx.seconds - TRACE_S) / 2)
    # the window, then the rest of the bag in progress at its close
    while (time.perf_counter() < t_end or len(reqs) % bag) \
            and time.perf_counter() < t_end + WAIT_AFTER_S:
        if ctx.trace and traced_from is None \
                and time.perf_counter() >= trace_at:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0     # Python calls: host cost
            jax.profiler.start_trace(str(ctx.trace_dir),
                                     profiler_options=opts)
            tracing = jax.profiler.TraceAnnotation(T.WINDOW)
            tracing.__enter__()
            traced_from = time.perf_counter()
        i = len(reqs)
        p, n = next(it)
        reqs.append((p, n))
        done.append(float("nan"))
        sent.append(time.perf_counter())
        try:
            stream = srv.submit_stream(prompt(cfg["vocab_size"], ctx.seed, i,
                                              p), n, keep_logits=i in check)
            fut = stream.future
        except Exception as e:            # noqa: BLE001 — counted failed
            fut = concurrent.futures.Future()
            fut.set_exception(e)
        fut.add_done_callback(
            lambda f, i=i: done.__setitem__(i, time.perf_counter()))
        futures.append(fut)
        concurrent.futures.wait([fut], timeout=max(
            0.0, t_end + WAIT_AFTER_S - time.perf_counter()))
        if tracing is not None and (time.perf_counter() - traced_from
                                    >= TRACE_S or time.perf_counter()
                                    >= t_end):
            tracing.__exit__(None, None, None)
            jax.profiler.stop_trace()
            tracing = None
    compiles.on = False
    after = sess.counters()
    peak = peak_bytes(devs)
    srv.close(drain=False)
    traced = trace_reduce(ctx.trace_dir, cfg) if ctx.trace else None

    ok = [i for i, f in enumerate(futures)
          if f.done() and f.exception() is None]
    failed = len(futures) - len(ok)
    lat_ms = whole_bags(sent, done, ok, bag)
    window = {k: after[k] - before[k] for k in after}
    metrics = {"setup_s": setup_s}
    if lat_ms:
        metrics["p50_ms"] = quantile(lat_ms, 0.50)
    diag = {"requests": len(futures), "timed": len(lat_ms),
            "sent_in_window": sum(t < t_end for t in sent),
            "last_done_after_window_s": max(
                (d for d in done if d == d), default=t_end) - t_end,
            "latency_ms_min": min(lat_ms) if lat_ms else None,
            "latency_ms_max": max(lat_ms) if lat_ms else None,
            "buckets": buckets, "counters": window}
    log(f"window: {len(futures)} requests, {len(lat_ms)} timed in whole "
        f"bags, {failed} failed; counters {window}; programs compiled or loaded "
        f"in the window: {compiles.n}")

    # the served logits come to the host once the window is over
    pick = [i for i in sorted(check) if i in ok]
    served = [np.stack([np.asarray(lg, np.float32)[0]
                        for lg in futures[i].result()[1]]) for i in pick]
    tokens = [futures[i].result()[0][0] for i in pick]
    del srv, sess, futures
    fut = stream = None                  # the last request's logits too
    gc.collect()
    # the reference's weights come from the seed again, not from the
    # artifact the program restored
    params = jax.block_until_ready(make_params(cfg, seed_key(ctx.seed)))

    def reference(control: bool) -> List[np.ndarray]:
        out = []
        for i, toks in zip(pick, tokens):
            p, n = reqs[i]
            seq = np.concatenate([prompt(cfg["vocab_size"], ctx.seed, i,
                                         p)[0], toks[:-1]])
            out.append(R.logits(cfg, params, seq, range(p - 1, p - 1 + n),
                                control=control, pad_to=cfg["max_len"]))
        return out

    refs = reference(False)
    limit = float(cfg["limits"]["logit_gap"])
    correct, checks = judge(served, refs, failed, limit)
    control = None
    if ctx.control:
        c_correct, c_checks = judge(reference(True), refs, failed, limit)
        control = {"correct": c_correct, "checks": c_checks}
    log(f"compared {len(pick)} requests ({sum(len(s) for s in served)} "
        "generated positions) with the reference")
    return {"correct": correct, "attempted": len(reqs), "failed": failed,
            "metrics": metrics, "checks": checks, "diag": diag,
            "memory_peak_bytes": peak,
            "window_compiles": compiles.n,
            "searches": search_calls() - searches, "control": control,
            "readings": {"window": window, "traced": traced,
                         "peak_hbm_bytes_per_s": None if rehearsal else
                         peaks.peak(devs[0].device_kind, "hbm_bytes_per_s"),
                         "chips": ctx.chips}}
