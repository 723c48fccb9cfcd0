#!/usr/bin/env python3
"""The program's own spans and per-node device time in a profiler trace.

The program writes its work into the profiler's trace itself: the serving
worker's ``serving.*`` spans (``engine/serving.py``: ``serving.idle``,
``serving.batch`` with its args and its children ``serving.gather``,
``serving.dispatch``, ``serving.device_wait``, ``serving.scatter``), the
collector's ``runtime.gc`` spans (``engine/telemetry.py``), and the graph
node of every device operation, as the ``jax.named_scope`` path in its
HLO ``op_name`` (``engine/executor.py``).  ``events`` reads those, with
the event stats that carry them, from an xplane file; ``reduce`` turns a
window [lo, hi) of them into the numbers the per-layer readers
``batch_host_ms``, ``queue_wait_ms``, ``stem_roofline`` and
``worker_imbalance`` take, under ``readings["spans"]``.  A trace without
the program's spans reduces to None, and those readers then read
nothing.  ``gc_pause_share`` reads the program's collector counters over
the whole window instead (``gc_window``, ``readings["gc"]``).

    python3 bench/spans.py --workload <cell> <trace.xplane.pb[.gz]> ...

prints, for each kept trace of a run of ``<cell>`` (``--keep-trace`` of
``bench/readings.py``), the reduction of its traced window, the readers'
values and the trace's breakdown (``devtrace.reduce``).
"""
from __future__ import annotations

import gzip
import json
from typing import Dict, Iterable, List, Optional, Tuple

import devtrace as T

PROGRAM = ("serving.", "runtime.gc")   # the program's host spans
CHILDREN = ("serving.gather", "serving.dispatch", "serving.device_wait",
            "serving.scatter")

Event = Tuple[str, int, int, Dict]      # name, start_ns, end_ns, stats


def scope_of(op_name: str) -> Optional[str]:
    """The graph node an XLA op belongs to: the first component of its
    ``op_name`` path that is no transformation (``jit(forward)``,
    ``while``, ``body``...), ``jit(forward)/stem_conv/jit(conv)/dot:``
    giving ``stem_conv``; None for an op outside any node's scope."""
    parts = op_name.split("/")
    for p in parts[:-1]:
        if "(" not in p and p not in ("while", "body", "cond",
                                      "closed_call", "remat"):
            return p
    return None


def _varint(buf, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        c = buf[i]
        i += 1
        out |= (c & 0x7F) << shift
        if c < 0x80:
            return out, i
        shift += 7


def _fields(buf) -> Iterable[Tuple[int, object]]:
    """``(field number, value)`` of one protobuf message: an int for a
    varint, a memoryview of the bytes otherwise."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        else:
            size = {1: 8, 5: 4}[kind]
            value, i = buf[i:i + size], i + size
        yield key >> 3, value


def op_scopes(xspace: bytes) -> Dict[str, Optional[str]]:
    """The graph node of each device operation, by the operation's event
    name: the node in its ``tf_op`` stat, which is the HLO ``op_name``.
    ``ProfileData`` gives an event only its own stats; ``tf_op`` is a
    stat of the event's metadata, read here off the protobuf: ``XSpace``
    planes (1); ``XPlane`` name (2), event_metadata (4) and stat_metadata
    (5), maps whose entries hold the value in field 2; ``XEventMetadata``
    name (2) and stats (5); ``XStat`` metadata_id (1) and str_value (5)
    or ref_value (7); ``XStatMetadata`` id (1) and name (2)."""
    out: Dict[str, Optional[str]] = {}
    for field, plane in _fields(memoryview(xspace)):
        if field != 1:
            continue
        name, metas, stat_names = "", [], {}
        for f, v in _fields(plane):
            if f == 2:
                name = bytes(v).decode()
            elif f == 4:
                metas.append(v)
            elif f == 5:
                entry = dict(_fields(dict(_fields(v))[2]))
                stat_names[entry.get(1, 0)] = bytes(entry.get(2, b"")).decode()
        if not name.startswith("/device:") or name.startswith("/device:CPU"):
            continue
        tf_op = [k for k, v in stat_names.items() if v == "tf_op"]
        for m in metas:
            meta = list(_fields(dict(_fields(m))[2]))
            event = next((bytes(v).decode() for f, v in meta if f == 2), "")
            node = None
            for f, stat in meta:
                st = dict(_fields(stat)) if f == 5 else {}
                if tf_op and st.get(1) == tf_op[0]:
                    text = bytes(st[5]).decode() if 5 in st else \
                        stat_names.get(st.get(7), "")
                    node = scope_of(text)
            # one name in two programs with two nodes names neither
            out[event] = node if out.get(event, node) == node else None
    return out


def events(path: str) -> Dict[str, Dict[str, List[Event]]]:
    """``{plane: {line: [(name, start_ns, end_ns, stats)]}}`` of the
    program's spans and the device operations in the xplane file at
    ``path`` (gzipped where it ends in ``.gz``).  Host lines are keyed
    ``"<n>:<name>"`` by position, since every Python thread's line has
    the same name; a device op's stats hold only ``scope``, its node
    (``op_scopes``).  The window span of the benchmark's tracer is kept
    too."""
    import jax

    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        xspace = f.read()
    data = jax.profiler.ProfileData.from_serialized_xspace(xspace)
    scopes = op_scopes(xspace)
    out: Dict[str, Dict[str, List[Event]]] = {}
    for plane in data.planes:
        host = plane.name.startswith("/host:")
        device = plane.name.startswith("/device:") and \
            not plane.name.startswith("/device:CPU")
        if not (host or device):
            continue
        lines = out.setdefault(plane.name, {})
        for i, line in enumerate(plane.lines):
            if device and line.name not in ("XLA Ops", "XLA Modules"):
                continue
            evs: List[Event] = []
            for e in line.events:
                name = e.name
                if host and not (name.startswith(PROGRAM)
                                 or name == T.WINDOW):
                    continue
                start = int(e.start_ns)
                if line.name == "XLA Ops":
                    stats = {"scope": scopes.get(name)}
                elif device:
                    stats = {}
                else:
                    stats = dict(e.stats)
                evs.append((name, start, start + int(e.duration_ns), stats))
            if evs:
                lines[f"{i}:{line.name}" if host else line.name] = evs
    return out


def window(planes) -> Optional[Tuple[int, int]]:
    """The benchmark tracer's window span, or None."""
    for p, lines in planes.items():
        if p.startswith("/host:"):
            for evs in lines.values():
                for n, a, b, _ in evs:
                    if n == T.WINDOW:
                        return a, b
    return None


def _clipped(a: int, b: int, lo: int, hi: int) -> int:
    return max(0, min(b, hi) - max(a, lo))


def _inside(evs: Iterable[Event], a: int, b: int) -> List[Event]:
    """The events that lie within [a, b]."""
    return [e for e in evs if a <= e[1] and e[2] <= b]


def reduce(planes, lo: int, hi: int) -> Optional[Dict]:
    """The program's spans in [lo, hi), or None where it has none:

    - ``workers``: for each worker that a ``serving.batch`` or
      ``serving.idle`` span names, ``batches``, ``rows`` and ``requests``
      of the batches that ended in the window, and the seconds of the
      window it spent in ``serving.idle`` and in ``serving.device_wait``;
    - ``requests``, ``wait_us_sum``: requests of those batches and their
      summed queue wait (µs from submit to the batch's formation);
    - ``batch_split_s``: over those batches, the seconds of each child
      span and of the rest of ``serving.batch`` (``other``);
    - ``gc_pause_s``, ``gc_pauses``: collector pauses in the window;
    - ``node_device_s``: device seconds of each graph node's operations
      in the window, summed over the chips."""
    host = [evs for p, lines in planes.items() if p.startswith("/host:")
            for evs in lines.values()]           # one list a thread
    if not any(n.startswith("serving.") for evs in host for n, *_ in evs):
        return None
    workers: Dict[int, Dict] = {}
    split = {c: 0.0 for c in CHILDREN}
    split["other"] = 0.0
    requests = wait_us = 0
    gc_ns = gc_n = 0

    def worker(w: int) -> Dict:
        return workers.setdefault(int(w), {
            "batches": 0, "rows": 0, "requests": 0, "idle_s": 0.0,
            "device_wait_s": 0.0})

    for evs in host:
        # a worker's spans are all on its thread's line; a device wait is
        # counted even where its batch ends after the trace stops
        names = [st["worker"] for n, *_, st in evs
                 if n in ("serving.batch", "serving.idle")]
        owner = worker(names[0]) if names else None
        for n, a, b, st in evs:
            t = _clipped(a, b, lo, hi)
            if n == "serving.idle":
                worker(st["worker"])["idle_s"] += t / 1e9
            elif n == "serving.device_wait" and owner is not None:
                owner["device_wait_s"] += t / 1e9
            elif n == "runtime.gc" and t:
                gc_ns += t
                gc_n += 1
        kids = [e for e in evs if e[0] in CHILDREN]
        for n, a, b, st in evs:
            if n != "serving.batch" or not lo <= b < hi:
                continue
            w = worker(st["worker"])
            w["batches"] += 1
            w["rows"] += st["rows"]
            w["requests"] += st["requests"]
            requests += st["requests"]
            wait_us += st["wait_us_sum"]
            inner = 0
            for kid, ka, kb, _ in _inside(kids, a, b):
                split[kid] += (kb - ka) / 1e9
                inner += kb - ka
            split["other"] += (b - a - inner) / 1e9
    nodes: Dict[str, float] = {}
    for d in T.device_planes(planes):
        for _, a, b, st in planes[d]["XLA Ops"]:
            node = st.get("scope")
            t = _clipped(a, b, lo, hi)
            if node is not None and t:
                nodes[node] = nodes.get(node, 0.0) + t / 1e9
    return {"window_s": (hi - lo) / 1e9, "workers": workers,
            "requests": requests, "wait_us_sum": wait_us,
            "batch_split_s": split, "gc_pause_s": gc_ns / 1e9,
            "gc_pauses": gc_n, "node_device_s": nodes}


def gc_window(before: Dict, after: Dict, window_s: float) -> Dict:
    """``readings["gc"]`` from two readings of the program's collector
    counters (``repro.engine.telemetry.gc_pauses()``) at a window's two
    ends: the pauses and pause seconds between them, summed over the
    generations, and the window's seconds."""
    def total(snap, key):
        return sum(v[key] for v in snap.values())

    return {"window_s": window_s,
            "pauses": total(after, "pauses") - total(before, "pauses"),
            "pause_s": total(after, "pause_s") - total(before, "pause_s")}


def conv_macs(arch: str, image: int, classes: int) -> List[int]:
    """Multiply-accumulates of each convolution of the reference network
    ``arch`` for one ``image`` x ``image`` input, in the order it runs
    them (``reference.count_macs`` adds these and the dense layer)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import reference as R

    params = [tuple(jax.ShapeDtypeStruct(s, jnp.float32) for s in shapes)
              for _, shapes in R.param_spec(arch, classes)]
    x = jax.ShapeDtypeStruct((1, 3, image, image), jnp.float32)
    jaxpr = jax.make_jaxpr(lambda p, x: R.forward(arch, p, x,
                                                  classes=classes))(params, x)
    return [int(np.prod(e.outvars[0].aval.shape))
            * int(np.prod(e.invars[1].aval.shape[1:]))
            for e in jaxpr.jaxpr.eqns
            if e.primitive.name == "conv_general_dilated"]


def stem(cfg: Dict, image: Optional[int] = None) -> Tuple[str, int]:
    """The configuration's first convolution: its node in the program's
    graph and its multiply-accumulates for one image, from the
    reference."""
    from families import cnn

    image = image or cfg["image"]
    graph, _ = cnn.program_graph(cfg["model"], image)
    node = next(n.name for n in graph.topo_order() if n.op == "conv2d")
    return node, conv_macs(cfg["reference"], image, cfg["classes"])[0]


def main(argv=None) -> int:
    import argparse

    import peaks
    import run

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--device-kind", default="TPU v5 lite")
    ap.add_argument("traces", nargs="+")
    args = ap.parse_args(argv)
    cell, cfg, _, _, _ = run.resolve(run.ROOT, args.workload, False)
    node, macs = stem(cfg)
    names = ["batch_host_ms", "queue_wait_ms", "stem_roofline",
             "worker_imbalance"]
    for path in args.traces:
        planes = events(path)
        lo, hi = window(planes)
        readings = {"spans": reduce(planes, lo, hi), "stem_node": node,
                    "stem_macs": macs, "chips": cell["chips"],
                    "peak_flops_per_s": peaks.peak(args.device_kind)}
        values = {n: run.reader(run.BENCH / "metrics", n)(readings)
                  for n in names}
        # the breakdown of the window, its idle gaps named by the
        # program's spans alone
        flat = {p: {k: [e[:3] for e in evs] for k, evs in lines.items()}
                for p, lines in planes.items()}
        print(json.dumps({"trace": path, "metrics": values,
                          "spans": readings["spans"],
                          "devtrace": T.reduce(flat, lo, hi)}), flush=True)
    return 0


if __name__ == "__main__":
    import sys
    from pathlib import Path

    sys.path[:0] = [str(Path(__file__).resolve().parent),
                    str(Path(__file__).resolve().parents[1] / "src")]
    sys.exit(main())
