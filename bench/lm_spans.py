#!/usr/bin/env python3
"""The LM session's spans and programs in a profiler trace.

``LMSession.generate`` (``engine/lm_session.py``) writes each request's
phases into the profiler's trace as host spans: ``lm.prefill`` (args
``bucket``, 0 where the prompt is below every bucket, and
``prompt_len``), then ``lm.catchup`` and ``lm.decode`` (arg ``steps``:
decode-program calls).  Its two jitted programs are the XLA modules
``jit_lm_prefill`` and ``jit_lm_decode``, and every device op in them
carries the ``jax.named_scope`` of its phase (``lm.prefill``,
``lm.decode``) in its ``op_name``.  ``events`` reads those, with every
other host event (which names idle gaps), from an xplane file; ``reduce``
turns the window [lo, hi) of whole traced requests into the numbers the
LM readers take (``readings["traced"]["lm"]``).

    python3 bench/lm_spans.py --workload <cell> <trace.xplane.pb[.gz]> ...

prints, for each kept trace of a run of ``<cell>`` (``--keep-trace`` of
``bench/readings.py``), the reduction of its traced window and the
readers' values.
"""
from __future__ import annotations

import gzip
import json
from typing import Dict, List, Optional

import devtrace as T
import lm_cost
from spans import Event, _clipped, op_scopes, window

PHASES = ("lm.prefill", "lm.decode")         # scopes and programs
MODULES = {"jit_lm_prefill": "lm.prefill", "jit_lm_decode": "lm.decode"}


def events(path: str) -> Dict[str, Dict[str, List[Event]]]:
    """``{plane: {line: [(name, start_ns, end_ns, stats)]}}`` of the host
    threads and the devices' ``XLA Ops`` and ``XLA Modules`` in the
    xplane file at ``path`` (gzipped where it ends in ``.gz``).  Host
    lines are keyed ``"<n>:<name>"`` by position; only the ``lm.*``
    spans keep their stats; a device op's stats hold only ``scope``
    (``spans.op_scopes``)."""
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
                name, start = e.name, int(e.start_ns)
                if line.name == "XLA Ops":
                    stats = {"scope": scopes.get(name)}
                elif host and name.startswith("lm."):
                    stats = dict(e.stats)
                else:
                    stats = {}
                evs.append((name, start, start + int(e.duration_ns), stats))
            if evs:
                lines[f"{i}:{line.name}" if host else line.name] = evs
    return out


def _requests(evs: List[Event], lo: int, hi: int) -> List[Dict]:
    """The requests of one thread whose three phases lie in [lo, hi]."""
    out, cur = [], None
    for n, a, b, st in sorted(evs, key=lambda e: e[1]):
        if n == "lm.prefill":
            cur = {"start": a, "bucket": int(st["bucket"]),
                   "prompt_len": int(st["prompt_len"]), "phases": 1}
        elif n in ("lm.catchup", "lm.decode") and cur is not None:
            cur[n.split(".")[1] + "_steps"] = int(st["steps"])
            cur["phases"] += 1
            if n == "lm.decode":
                if cur["phases"] == 3 and lo <= cur["start"] and b <= hi:
                    out.append({k: v for k, v in cur.items()
                                if k not in ("start", "phases")})
                cur = None
    for r in out:
        r["steps"] = r["catchup_steps"] + r["decode_steps"]
    return out


def reduce(planes, lo: int, hi: int) -> Optional[Dict]:
    """The LM session's work in [lo, hi), or None where the trace has no
    ``lm.*`` span:

    - ``requests``: each request whose phases all lie in the window, with
      its ``bucket``, ``prompt_len``, ``catchup_steps``, ``decode_steps``
      and ``steps`` (their sum: decode-program calls);
    - ``prefill_tokens``, ``decode_steps``: their sums;
    - ``<phase>_device_s``, ``<phase>_n``: device seconds of the phase's
      program (its XLA module) in the window, summed over the chips, and
      its executions there, each counted by the share of it inside;
    - ``scope_device_s``: device seconds of the ops whose ``op_name``
      carries each phase's scope (a check that the scopes reach the
      device)."""
    host = [evs for p, lines in planes.items() if p.startswith("/host:")
            for evs in lines.values()]
    if not any(n.startswith("lm.") for evs in host for n, *_ in evs):
        return None
    requests = [r for evs in host for r in _requests(evs, lo, hi)]
    out: Dict = {"requests": requests,
                 "prefill_tokens": sum(r["bucket"] for r in requests),
                 "decode_steps": sum(r["steps"] for r in requests),
                 "scope_device_s": dict.fromkeys(PHASES, 0.0)}
    for ph in PHASES:
        key = ph.split(".")[1]
        out[f"{key}_device_s"] = 0.0
        out[f"{key}_n"] = 0.0
    for d in T.device_planes(planes):
        for n, a, b, _ in planes[d].get("XLA Modules", ()):
            ph = next((v for k, v in MODULES.items() if n.startswith(k)),
                      None)
            t = _clipped(a, b, lo, hi)
            if ph is None or not t:
                continue
            key = ph.split(".")[1]
            out[f"{key}_device_s"] += t / 1e9
            out[f"{key}_n"] += t / (b - a)
        for _, a, b, st in planes[d]["XLA Ops"]:
            if st.get("scope") in PHASES:
                out["scope_device_s"][st["scope"]] += \
                    _clipped(a, b, lo, hi) / 1e9
    return out


def traced(planes, cfg: Dict) -> Optional[Dict]:
    """``readings["traced"]`` of the benchmark tracer's window in
    ``planes``: the busy share and breakdown (``devtrace.reduce``), with
    ``lm``, the LM session's work there (``reduce``) and what it must
    cost (``lm_cost``: ``prefill_flops``, ``decode_bytes``); None where
    the trace has no window or no device operations."""
    span = window(planes)
    if span is None:
        return None
    flat = {p: {k: [e[:3] for e in evs] for k, evs in lines.items()}
            for p, lines in planes.items()}
    out = T.reduce(flat, *span)
    lm = reduce(planes, *span)
    if out is None or lm is None:
        return out
    work = [lm_cost.request_work(cfg, r["bucket"], r["steps"])
            for r in lm["requests"]]
    lm["prefill_flops"] = sum(w["prefill_flops"] for w in work)
    lm["decode_bytes"] = sum(w["decode_bytes"] for w in work)
    out["lm"] = lm
    return out


def main(argv=None) -> int:
    import argparse

    import peaks
    import run

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--device-kind", default="TPU v5 lite")
    ap.add_argument("traces", nargs="+")
    args = ap.parse_args(argv)
    _, cfg, _, _, metrics = run.resolve(run.ROOT, args.workload, True)
    for path in args.traces:
        readings = {"traced": traced(events(path), cfg),
                    "peak_flops_per_s": peaks.peak(args.device_kind),
                    "peak_hbm_bytes_per_s":
                    peaks.peak(args.device_kind, "hbm_bytes_per_s")}
        # catchup_share reads the run's counters, which a trace lacks
        values = {m["name"]: m["read"](readings) for m in metrics
                  if m["name"] != "catchup_share"}
        print(json.dumps({"trace": path, "metrics": values,
                          "traced": readings["traced"]}), flush=True)
    return 0


if __name__ == "__main__":
    import sys
    from pathlib import Path

    sys.path[:0] = [str(Path(__file__).resolve().parent),
                    str(Path(__file__).resolve().parents[1] / "src")]
    sys.exit(main())
