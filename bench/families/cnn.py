"""CNN family: a zoo network served through ``AsyncServer``.

One run, in the order the contract of ``bench/run.py`` gives:

1. set-up: load the configuration's artifact (planned on the first run in
   a checkout and kept under ``bench/.cache``), make the weights from the
   seed on the device in one jitted call and bind them to every bucket's
   plan, make the input images, and warm every program the mix can reach
   by pushing each batch shape it can form through the server;
2. the window: an open loop (requests at due times fixed in advance) or a
   closed loop (clients that each keep one request outstanding) against
   ``AsyncServer.submit``, every completion timed on the host clock; with
   ``trace`` a profiler trace of a few seconds in its middle;
3. the check, after the window: a sample of the answers, drawn from the
   seed, against the plain reference (``bench/reference.py``) at the
   configuration's precision, computed once the program's state is freed.
"""
from __future__ import annotations

import concurrent.futures
import contextlib
import functools
import gc
import itertools
import queue
import time
from pathlib import Path
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

import reference as R
import devtrace as T
import traffic

POOL = 64             # distinct input images; a request takes a run of them
CHECK_REQUESTS = 96   # answers compared with the reference in every run
REF_BLOCK = 16        # reference rows per call (one compiled shape)
WAIT_AFTER_S = 60.0   # how long past the window's close an answer may take
TRACE_S = 3.0         # length of the traced part of a --trace 1 window


def seed_key(seed: int):
    """A JAX key from any integer seed (up to 62 bits are kept)."""
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF),
                              (seed >> 31) & 0x7FFFFFFF)


@functools.lru_cache(maxsize=None)
def _param_maker(arch: str, classes: int):
    spec = R.param_spec(arch, classes)
    sizes = [[int(np.prod(s)) for s in shapes] for _, shapes in spec]

    @jax.jit
    def make(key):
        # one draw for all the weights and one for all the biases: a
        # random call per layer compiles for tens of seconds
        kw, kb = jax.random.split(key)
        w = jax.random.normal(kw, (sum(n[0] for n in sizes),))
        b = jax.random.uniform(kb, (sum(sum(n[1:]) for n in sizes),))
        out, i, j = [], 0, 0
        for (kind, shapes), n in zip(spec, sizes):
            first = w[i:i + n[0]].reshape(shapes[0])
            i += n[0]
            if kind == "conv":
                out.append((first * np.sqrt(2.0 / np.prod(shapes[0][1:])),))
                continue
            u = b[j:j + n[1]]
            j += n[1]
            if kind == "bn":
                # scale U(0.5, 1.5), shift N(0, 0.1)
                out.append((u + 0.5, 0.1 * first))
            else:
                out.append((first * np.sqrt(1.0 / shapes[0][0]),
                            0.02 * (u - 0.5)))
        return out

    return make


def make_params(arch: str, classes: int, key):
    """The network's float32 weights, on the device, from ``key``: He-normal
    convolutions, batch norms with scale U(0.5, 1.5) and shift N(0, 0.1), a
    dense layer N(0, 1/in) with bias U(-0.01, 0.01).  One list in the
    reference's order."""
    return _param_maker(arch, classes)(jax.random.fold_in(key, 1))


@functools.partial(jax.jit, static_argnums=(1, 2))
def _images(key, n: int, image: int):
    k1, k2, k3, k4 = jax.random.split(key, 4)
    offset = 0.5 * jax.random.normal(k1, (n, 3, 1, 1))
    contrast = jax.random.uniform(k2, (n, 1, 1, 1), minval=0.5, maxval=1.5)
    low = jax.image.resize(jax.random.normal(k3, (n, 3, 8, 8)),
                           (n, 3, image, image), "linear")
    return offset + contrast * low + 0.3 * jax.random.normal(
        k4, (n, 3, image, image))


def make_images(key, n: int, image: int) -> np.ndarray:
    """``n`` normalised RGB images on the host, as uploads arrive: a
    per-image colour offset and contrast over smooth random structure and
    fine noise, so that the network's answers differ from image to image
    (white noise alone gives every image nearly the same logits)."""
    return np.asarray(_images(jax.random.fold_in(key, 2), n, image))


def program_graph(model: str, image: int):
    """The program's zoo graph of ``model`` up to its logits (the softmax
    head of a random-weight network is one-hot for every input), with its
    shapes inferred."""
    from repro.core.graph import Graph
    from repro.models.cnn import build

    g, shapes = build(model, batch=1, image=image)
    (out,) = g.outputs
    head = g.nodes[out]
    logits = Graph()
    for n in g.topo_order():
        if n is not head or n.op != "softmax":
            logits.add(n.name, n.op, n.inputs, **n.attrs)
    logits.mark_output(head.inputs[0] if head.op == "softmax" else out)
    logits.infer_shapes(shapes)
    return logits, shapes


def to_program(graph, params) -> Dict[str, Dict[str, jnp.ndarray]]:
    """The reference's parameter list keyed by the program's node names:
    the graph's layers with parameters, in order, must be the reference's
    layers, kind for kind and shape for shape."""
    layers = [n for n in graph.topo_order()
              if n.op in ("conv2d", "batch_norm", "dense")]
    if len(layers) != len(params):
        raise ValueError(f"the program's graph has {len(layers)} layers "
                         f"with parameters, the reference {len(params)}")
    out = {}
    for node, p in zip(layers, params):
        a = node.attrs
        if node.op == "conv2d":
            want = (a["out_channels"], a["in_channels"] // a.get("groups", 1),
                    a["kh"], a["kw"])
            got, leaves = p[0].shape if len(p) == 1 else None, ("w",)
        elif node.op == "batch_norm":
            want, leaves = (node.shape[1],), ("scale", "shift")
            got = p[0].shape if len(p) == 2 and p[0].ndim == 1 else None
        else:
            want, leaves = (a["units"],), ("w", "b")
            got = p[1].shape if len(p) == 2 and p[0].ndim == 2 else None
        if got != want:
            raise ValueError(f"{node.name} ({node.op}) wants {want}, the "
                             f"reference's layer has {[x.shape for x in p]}")
        out[node.name] = dict(zip(leaves, p))
    return out


def artifact(cfg: Dict, image: int, cache: Path, params, log) -> Path:
    """The configuration's saved artifact, planned and saved on the first
    run in this checkout; later runs load it and bind their own weights."""
    from repro.engine import compile as compile_model

    path = cache / "artifacts" / (f"{cfg['name']}-{image}-b"
                                  + "-".join(map(str, cfg["buckets"])))
    if (path / "manifest.json").is_file():
        return path
    t = time.perf_counter()
    graph, shapes = program_graph(cfg["model"], image)
    sess = compile_model(graph, {k: (cfg["buckets"][0],) + v[1:]
                                 for k, v in shapes.items()},
                         params=to_program(graph, params))
    for b in cfg["buckets"]:
        sess.specialize(b)
    path.parent.mkdir(parents=True, exist_ok=True)
    sess.save(path, include_source=False)
    log(f"planned and saved {path.name} in {time.perf_counter() - t:.2f} s")
    return path


def batch_shapes(sizes: Sequence[int], cap: int) -> List[tuple]:
    """Every FIFO batch a queue of these request sizes can form: each
    sequence of sizes whose rows fit ``cap``."""
    out, frontier = [], [()]
    while frontier:
        nxt = []
        for seq in frontier:
            for s in sizes:
                if sum(seq) + s <= cap:
                    out.append(seq + (s,))
                    nxt.append(seq + (s,))
        frontier = nxt
    return out


def warm(sess, policy, pool: np.ndarray, sizes, devs, workers: int) -> int:
    """Runs every batch shape the mix can form through the server, on
    every worker's device, so that nothing compiles in the window: the
    server pumped by hand (``step``) with a clock that lets each batch
    flush at once.  Returns the number of batches run."""
    from repro.engine import AsyncServer

    shapes = batch_shapes(sorted(sizes), policy.max_batch)
    for w in range(workers):
        clock = itertools.count()
        srv = AsyncServer(sess, policy, max_queue=4 * policy.max_batch,
                          workers=workers,
                          devices=list(devs[w:]) + list(devs[:w]),
                          autostart=False, clock=lambda: float(next(clock)))
        for seq in shapes:
            futs = [srv.submit(pool[:rows]) for rows in seq]
            srv.step()
            for f in futs:
                f.result(timeout=0)
        srv.close()
    return len(shapes) * workers


class Window:
    """The requests of one measured window and their timings."""

    def __init__(self, span):
        self.span = span                # host span: a context manager
        self.due: List[float] = []      # open loop: due time, else sent
        self.sent: List[float] = []
        self.done: List[float] = []
        self.rows: List[int] = []
        self.start: List[int] = []      # first pool image of the request
        self.futures: List[concurrent.futures.Future] = []

    def add(self, due: float, rows: int, start: int) -> int:
        self.due.append(due)
        self.rows.append(rows)
        self.start.append(start)
        self.done.append(float("nan"))
        return len(self.due) - 1

    def submit(self, srv, pool, i: int, on_done=None) -> None:
        x = pool[self.start[i]:self.start[i] + self.rows[i]]
        self.sent.append(time.perf_counter())
        try:
            with self.span("bench.submit"):
                fut = srv.submit(x)
        except Exception:                   # noqa: BLE001 — counted failed
            fut = concurrent.futures.Future()
            fut.set_exception(RuntimeError("refused at submit"))
        self.futures.append(fut)

        def mark(f, i=i):
            self.done[i] = time.perf_counter()
            if on_done is not None:
                on_done(i)

        fut.add_done_callback(mark)


def open_loop(srv, pool, mix, seed: int, seconds: float, t0: float,
              starts, span) -> Window:
    win = Window(span)
    for (due, rows), s in zip(traffic.arrivals(mix, seed, seconds), starts):
        i = win.add(t0 + due, rows, int(s) % (POOL - rows + 1))
        wait = win.due[i] - time.perf_counter()
        if wait > 0:
            with span("bench.generator_waits"):
                time.sleep(wait)
        win.submit(srv, pool, i)
    return win


def closed_loop(srv, pool, mix, seed: int, seconds: float, t0: float,
                starts, span) -> Window:
    win = Window(span)
    its = traffic.client_sizes(mix, seed)
    owner: List[int] = []
    ready: "queue.SimpleQueue[int]" = queue.SimpleQueue()
    t_end = t0 + seconds

    def send(client: int) -> None:
        rows = next(its[client])
        i = win.add(time.perf_counter(), rows,
                    int(next(starts)) % (POOL - rows + 1))
        owner.append(client)
        win.submit(srv, pool, i, on_done=ready.put)

    for c in range(len(its)):
        send(c)
    while True:
        left = t_end - time.perf_counter()
        if left <= 0:
            break
        try:
            with span("bench.clients_wait"):
                i = ready.get(timeout=left)
        except queue.Empty:
            break
        if time.perf_counter() < t_end:
            send(owner[i])
    return win


def _no_span(name: str):
    return contextlib.nullcontext()


def _counters(stats) -> Dict:
    return {"n_batches": stats.n_batches,
            "rows_executed": stats.rows_executed,
            "rows_padded": stats.rows_padded,
            "worker_batches": dict(stats.worker_batches)}


def _delta(a: Dict, b: Dict) -> Dict:
    out = {k: b[k] - a[k] for k in ("n_batches", "rows_executed",
                                    "rows_padded")}
    out["worker_batches"] = {w: b["worker_batches"].get(w, 0)
                             - a["worker_batches"].get(w, 0)
                             for w in b["worker_batches"]}
    return out


def quantile(values, q: float) -> float:
    """The ``q`` quantile (0..1) of ``values``, exact: linear between the
    two closest order statistics."""
    return float(np.quantile(np.asarray(values, np.float64), q))


class Compiles:
    """Counts the programs JAX compiles or loads from its cache while
    ``on``: each goes through one backend-compile event."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.on = False
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event: str, duration: float, **kw) -> None:
        if self.on and event == self.EVENT:
            self.n += 1


def sample(ok: List[int], results, n: int, seed: int,
           devices) -> List[int]:
    """Requests to compare: ``n`` drawn from the seed among the answered
    ones, plus one from every device that served any and is not drawn."""
    rng = traffic.rng_for(seed, 3)
    picked = set(int(i) for i in rng.choice(ok, size=min(n, len(ok)),
                                            replace=False))
    by_dev: Dict = {}
    for i in ok:
        by_dev.setdefault(devices(results[i]), []).append(i)
    for dev, idx in sorted(by_dev.items(), key=lambda kv: str(kv[0])):
        if not picked & set(idx):
            picked.add(int(rng.choice(idx)))
    return sorted(picked)


@functools.lru_cache(maxsize=None)
def _reference(arch: str, classes: int, precision: str):
    return jax.jit(functools.partial(R.forward, arch, classes=classes,
                                     precision=precision))


def reference_logits(arch: str, classes: int, params, x: np.ndarray,
                     precision: str = "highest") -> np.ndarray:
    """The reference's logits for the rows of ``x``, ``REF_BLOCK`` rows a
    call."""
    fn = _reference(arch, classes, precision)
    out = []
    for a in range(0, len(x), REF_BLOCK):
        blk = x[a:a + REF_BLOCK]
        pad = np.zeros((REF_BLOCK - len(blk),) + x.shape[1:], x.dtype)
        y = fn(params, jnp.asarray(np.concatenate([blk, pad])))
        out.append(np.asarray(y)[:len(blk)])
    return np.concatenate(out)


def logit_gap(got: np.ndarray, ref: np.ndarray) -> np.ndarray:
    """Per row: the widest gap between an answer's logits and the
    reference's, over the reference row's largest magnitude."""
    got = np.asarray(got, np.float64)
    ref = np.asarray(ref, np.float64)
    return np.abs(got - ref).max(axis=1) / np.abs(ref).max(axis=1)


def judge(answers: np.ndarray, ref: np.ndarray, failed: int,
          limit: float):
    """Whether a run is correct, and the numbers compared with their
    limits: the widest logit gap of the answers against the reference,
    and the requests that failed or never came."""
    checks = [("logit_gap", float(logit_gap(answers, ref).max()), limit),
              ("failed_requests", float(failed), 0.0)]
    return all(v <= lim for _, v, lim in checks), checks


def swap_gap(ref: np.ndarray, image: Sequence[int]) -> float:
    """The smallest gap that one answer given for another image reads:
    each row's reference against the reference of the next row that
    holds another image."""
    out = []
    for i in range(len(ref)):
        j = next((j for j in range(i + 1, len(ref))
                  if image[j] != image[i]), None)
        if j is not None:
            out.append(logit_gap(ref[j:j + 1], ref[i:i + 1])[0])
    return float(min(out))


def run(ctx) -> Dict:
    """One run of a CNN cell at its configuration's matmul precision, set
    for the whole process so that the server's worker threads compute at
    it too; see ``_run``."""
    prev = jax.config.jax_default_matmul_precision
    jax.config.update("jax_default_matmul_precision",
                      ctx.config["matmul_precision"])
    try:
        return _run(ctx)
    finally:
        jax.config.update("jax_default_matmul_precision", prev)


def _run(ctx) -> Dict:
    """One run of a CNN cell; see the module's docstring.  ``ctx`` carries
    the cell (``config``, ``mix``, ``chips``, ``seed``, ``seconds``,
    ``trace``), where to keep caches and traces, the process's start time
    ``t0`` and a logger ``log``.  With ``ctx.control`` (never in a
    benchmark run) the control's answers, the reference at "high" precision, are
    judged in the program's place on the same sample (``control``)."""
    from repro.core.local_search import search_calls
    from repro.engine import (AsyncServer, DynamicBatchPolicy,
                              InferenceSession, bind_params)

    cfg, mix, log = ctx.config, ctx.mix, ctx.log
    arch, classes = cfg["reference"], cfg["classes"]
    image = ctx.image or cfg["image"]
    devs = jax.devices()[:ctx.chips]
    key = seed_key(ctx.seed)
    policy = DynamicBatchPolicy(**cfg["policy"])
    compiles = Compiles()

    phases = {"start": time.perf_counter() - ctx.t0}
    params = jax.block_until_ready(make_params(arch, classes, key))
    phases["weights"] = time.perf_counter() - ctx.t0
    path = artifact(cfg, image, ctx.cache, params, log)
    searches = search_calls()
    sess = InferenceSession.load(path)
    phases["load"] = time.perf_counter() - ctx.t0
    graph, _ = program_graph(cfg["model"], image)
    prog = to_program(graph, params)
    for b in cfg["buckets"]:
        m = sess.specialize(b)
        m.params = jax.block_until_ready(
            jax.jit(functools.partial(bind_params, m.plan))(prog))
    del prog
    phases["bind"] = time.perf_counter() - ctx.t0
    pool = make_images(key, POOL, image)
    n_warm = warm(sess, policy, pool, mix["rows"], devs, ctx.chips)
    setup_s = time.perf_counter() - ctx.t0
    done_at = ", ".join(f"{k} done at {v:.2f}" for k, v in phases.items())
    log(f"set-up {setup_s:.3f} s ({done_at}): artifact {path.name}, "
        f"buckets {sess.batch_sizes}, {n_warm} warm-up batches")

    srv = AsyncServer(sess, policy, max_queue=1 << 16, workers=ctx.chips,
                      devices=devs)
    before = _counters(srv.stats)
    tracer = T.Tracer(ctx.trace_dir, srv, _counters) if ctx.trace else None
    starts = traffic.rng_for(ctx.seed, 4).integers(0, 1 << 30, size=1 << 20)
    compiles.on = True
    t0 = time.perf_counter() + 0.01
    if tracer is not None:
        tracer.schedule(t0 + max(0.0, (ctx.seconds - TRACE_S) / 2),
                        min(TRACE_S, ctx.seconds))
    loop = open_loop if mix["loop"] == "open" else closed_loop
    span = jax.profiler.TraceAnnotation if ctx.trace else _no_span
    win = loop(srv, pool, mix, ctx.seed, ctx.seconds, t0, iter(starts), span)
    t_end = t0 + ctx.seconds
    concurrent.futures.wait(win.futures,
                            timeout=max(0.0, t_end + WAIT_AFTER_S
                                        - time.perf_counter()))
    compiles.on = False
    window = _delta(before, _counters(srv.stats))
    traced = tracer.finish() if tracer is not None else None
    peak_bytes = 0
    for d in devs:
        st = d.memory_stats() or {}
        peak_bytes = max(peak_bytes, int(st.get("peak_bytes_in_use", 0)))
    srv.close(drain=False)

    results: List = [None] * len(win.futures)
    ok, failed = [], 0
    for i, f in enumerate(win.futures):
        if f.done() and f.exception() is None:
            results[i] = f.result()
            ok.append(i)
        else:
            failed += 1
    due = np.asarray(win.due)
    done = np.asarray(win.done)
    rows = np.asarray(win.rows)
    lat_ms = (done[ok] - due[ok]) * 1e3
    late = (np.asarray(win.sent) - due) * 1e3
    metrics = {"setup_s": setup_s}
    if mix["loop"] == "open" and ok:
        metrics["p50_ms"] = quantile(lat_ms, 0.50)
        metrics["p95_ms"] = quantile(lat_ms, 0.95)
    n = len(due)
    quarter = ([i for i in ok if i < n // 4],
               [i for i in ok if i >= n - n // 4])
    diag = {"lateness_p99_ms": quantile(late, 0.99),
            "lateness_max_ms": float(late.max()),
            "p50_first_quarter_ms": quantile((done - due)[quarter[0]], 0.5)
            * 1e3 if quarter[0] else None,
            "p50_last_quarter_ms": quantile((done - due)[quarter[1]], 0.5)
            * 1e3 if quarter[1] else None,
            "requests": n, "mean_batch_rows": window["rows_executed"]
            / max(1, window["n_batches"])}
    in_window = np.isfinite(done) & (done <= t_end)
    in_window[[i for i in range(len(results)) if results[i] is None]] = False
    metrics["images_per_s"] = float(rows[in_window].sum() / ctx.seconds)
    log(f"window: {len(win.futures)} requests, {int(rows.sum())} images, "
        f"{failed} failed; generator lateness p99 "
        f"{quantile(late, 0.99):.3f} ms, max {late.max():.3f} ms; "
        f"{window['n_batches']} batches, {window['rows_padded']} padded "
        f"rows; programs compiled or loaded in the window: {compiles.n}; "
        f"schedule searches since load: {search_calls() - searches}")

    pick = sample(ok, results, CHECK_REQUESTS, ctx.seed,
                  lambda y: next(iter(y.devices())))
    served = np.concatenate([np.asarray(results[i]) for i in pick])
    rows_of = [np.arange(win.start[i], win.start[i] + win.rows[i])
               for i in pick]
    x = pool[np.concatenate(rows_of)]
    # the program's state goes before the reference runs
    del srv, results, win, sess, m
    gc.collect()
    ref = reference_logits(arch, classes, params, x)
    limit = float(cfg["limits"]["logit_gap"])
    correct, checks = judge(served, ref, failed, limit)
    control = None
    if ctx.control:
        ctl = reference_logits(arch, classes, params, x, precision="high")
        c_correct, c_checks = judge(ctl, ref, failed, limit)
        control = {"correct": c_correct, "checks": c_checks,
                   "swap_gap": swap_gap(ref, np.concatenate(rows_of))}
    log(f"compared {len(pick)} requests ({len(x)} rows) with the reference")

    out = {"correct": correct, "attempted": len(due), "failed": failed,
           "metrics": metrics, "checks": checks, "diag": diag,
           "memory_peak_bytes": peak_bytes,
           "window_compiles": compiles.n,
           "searches": search_calls() - searches, "control": control,
           "readings": {"window": window, "traced": traced,
                        "macs_per_image": R.count_macs(arch, image, classes),
                        "chips": ctx.chips}}
    return out
