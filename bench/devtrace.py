"""From a profiler trace to the numbers per-layer metrics read.

``Tracer`` records a few seconds in the middle of a window, in a thread of
its own, with the server's counters read at both ends; the traced window is
its host span ``WINDOW``, so that it is on the trace's own clock.
``reduce`` turns the trace's events into:

- ``busy_s``: per chip, the union of the intervals in which an operation
  ran on it, averaged over the chips; ``window_s``: the traced window;
- ``forward_s``, ``forward_n``: the device time (union) and count of the
  forward programs, the XLA modules whose name starts with ``FORWARD``;
- ``breakdown``: the device operations that took the most time, by
  instruction and result type (``op_name``), and the longest idle gaps on
  the first chip, each named by the host activity that overlaps it most.

Events come from ``jax.profiler.ProfileData``: a device plane is named
``/device:<KIND>:<n>`` and holds the lines ``XLA Ops`` and ``XLA
Modules``; host threads are the lines of the ``/host:CPU`` plane.
"""
from __future__ import annotations

import glob
import threading
import time
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Tuple

FORWARD = "jit_forward"   # the jitted whole-graph forward of the executor
WINDOW = "bench.traced_window"
TOP = 10

Interval = Tuple[int, int]


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, disjoint cover of ``intervals`` (start, end) in ns."""
    out: List[List[int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(intervals: Iterable[Interval], lo: int, hi: int) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def total(intervals: Iterable[Interval]) -> int:
    return sum(b - a for a, b in intervals)


def events(path: str) -> Dict[str, Dict[str, List[Tuple[str, int, int]]]]:
    """``{plane: {line: [(name, start_ns, end_ns)]}}`` of an xplane file."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    out: Dict[str, Dict[str, List[Tuple[str, int, int]]]] = {}
    for plane in data.planes:
        lines = out.setdefault(plane.name, {})
        for line in plane.lines:
            evs = lines.setdefault(line.name, [])
            for e in line.events:
                start = int(e.start_ns)
                evs.append((e.name, start, start + int(e.duration_ns)))
    return out


def device_planes(planes) -> List[str]:
    return sorted(p for p in planes if p.startswith("/device:")
                  and not p.startswith("/device:CPU")
                  and "XLA Ops" in planes[p])


def reduce(planes, lo_ns: int, hi_ns: int) -> Optional[Dict]:
    """The numbers of the window [lo_ns, hi_ns) of ``planes`` (see
    ``events``), or None where the trace has no device operations."""
    devs = device_planes(planes)
    if not devs:
        return None
    busy, fwd_ns, fwd_n = [], 0, 0
    op_time: Dict[str, int] = {}
    first_busy: List[Interval] = []
    for d in devs:
        ops = [(n, a, b) for n, a, b in planes[d]["XLA Ops"]
               if b > lo_ns and a < hi_ns]
        cover = union(clip(((a, b) for _, a, b in ops), lo_ns, hi_ns))
        busy.append(total(cover))
        if d == devs[0]:
            first_busy = cover
        for n, a, b in ops:
            n = op_name(n)
            op_time[n] = op_time.get(n, 0) + min(b, hi_ns) - max(a, lo_ns)
        mods = [(a, b) for n, a, b in planes[d].get("XLA Modules", ())
                if n.startswith(FORWARD) and b > lo_ns and a < hi_ns]
        fwd_ns += total(union(clip(mods, lo_ns, hi_ns)))
        fwd_n += len(mods)
    if not any(busy):
        return None
    gaps = []
    edges = [(lo_ns, lo_ns)] + first_busy + [(hi_ns, hi_ns)]
    for (_, a), (b, _) in zip(edges, edges[1:]):
        if b > a:
            gaps.append((a, b))
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:TOP]
    host = [(n, a, b) for p, lines in planes.items()
            if p.startswith("/host:") for evs in lines.values()
            for n, a, b in evs if b > lo_ns and a < hi_ns]
    ops_top = sorted(op_time.items(), key=lambda kv: -kv[1])[:TOP]
    return {
        "busy_s": sum(busy) / len(busy) / 1e9,
        "window_s": (hi_ns - lo_ns) / 1e9,
        "forward_s": fwd_ns / len(devs) / 1e9,
        "forward_n": fwd_n,
        "chips_traced": len(devs),
        "breakdown": {
            "device_ops": [[n, t / 1e9] for n, t in ops_top],
            "idle_gaps": [[gap_name(host, a, b), (b - a) / 1e9]
                          for a, b in gaps]},
    }


def op_name(text: str) -> str:
    """An XLA op event's name without its HLO text: the instruction and
    its result type, ``%fusion.96 = f32[8,64]{1,0:T(8,128)} fusion(...)``
    giving ``fusion.96 f32[8,64]``; other names are kept as they are."""
    if " = " not in text:
        return text
    inst, rest = text.split(" = ", 1)
    return f"{inst.lstrip('%')} {rest.split('{', 1)[0].split(' ', 1)[0]}"


def gap_name(host, a: int, b: int) -> str:
    """What the host was doing in the gap [a, b): the shortest host event
    that covers at least half of it, else the one that covers most of it,
    by name; "no host event" where none overlaps.  The bench's own spans
    are named ``bench.*``."""
    over = [(min(e, b) - max(s, a), e - s, n) for n, s, e in host
            if n != WINDOW and min(e, b) > max(s, a)]
    if not over:
        return "no host event"
    half = [o for o in over if 2 * o[0] >= b - a]
    if half:
        return min(half, key=lambda o: o[1])[2]
    return max(over)[2]


class Tracer:
    """Traces ``length`` seconds from ``start_at`` (``time.perf_counter``)
    in its own thread, with ``counters(server.stats)`` read at both ends."""

    def __init__(self, trace_dir: Path, server, counters) -> None:
        self.dir = Path(trace_dir)
        self.server = server
        self.counters = counters
        self.thread: Optional[threading.Thread] = None
        self.marks: Dict = {}

    def schedule(self, start_at: float, length: float) -> None:
        import shutil

        shutil.rmtree(self.dir, ignore_errors=True)
        self.thread = threading.Thread(target=self._run,
                                       args=(start_at, length),
                                       name="bench-tracer", daemon=True)
        self.thread.start()

    def _run(self, start_at: float, length: float) -> None:
        import jax

        time.sleep(max(0.0, start_at - time.perf_counter()))
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0      # Python calls: host cost, no use
        jax.profiler.start_trace(str(self.dir), profiler_options=opts)
        # the window is this span, on the trace's own clock
        with jax.profiler.TraceAnnotation(WINDOW):
            self.marks["c0"] = self.counters(self.server.stats)
            time.sleep(length)
            self.marks["c1"] = self.counters(self.server.stats)
        jax.profiler.stop_trace()

    def finish(self) -> Optional[Dict]:
        """The reduced trace with the counters' change over it, or None
        where it holds no device operations."""
        self.thread.join()
        files = glob.glob(str(self.dir / "**" / "*.xplane.pb"),
                          recursive=True)
        if not files:
            return None
        planes = events(files[0])
        spans = [(a, b) for p, lines in planes.items()
                 if p.startswith("/host:") for evs in lines.values()
                 for n, a, b in evs if n == WINDOW]
        if not spans:
            return None
        out = reduce(planes, *spans[0])
        if out is not None:
            c0, c1 = self.marks["c0"], self.marks["c1"]
            out["rows_executed"] = c1["rows_executed"] - c0["rows_executed"]
            out["n_batches"] = c1["n_batches"] - c0["n_batches"]
        return out
