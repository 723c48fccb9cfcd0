"""The serving worker's spans, the graph nodes' scopes and the collector
hook, read back from a profiler trace of a small session on the host:
``jax.profiler.TraceAnnotation`` spans land in the trace's host planes
with their args as event stats, as on the chip."""
import gc
import glob
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.graph import Graph
from repro.engine import AsyncServer, DynamicBatchPolicy
from repro.engine import compile as compile_session
from repro.engine.telemetry import GC_PAUSES, GcPauses, gc_pauses

CHILDREN = ("serving.gather", "serving.dispatch", "serving.device_wait",
            "serving.scatter")


def _mini_net():
    g = Graph()
    g.add("in", "input")
    g.add("c1", "conv2d", ["in"], in_channels=3, out_channels=8, kh=3,
          kw=3, stride=2, pad=1)
    g.add("bn1", "batch_norm", ["c1"])
    g.add("r1", "relu", ["bn1"])
    g.add("c2", "conv2d", ["r1"], in_channels=8, out_channels=16, kh=3,
          kw=3, pad=1)
    g.add("gap", "global_avg_pool", ["c2"])
    g.add("fl", "flatten", ["gap"])
    g.add("fc", "dense", ["fl"], units=10)
    g.mark_output("fc")
    return g, {"in": (1, 3, 16, 16)}


@pytest.fixture(scope="module")
def session():
    g, shapes = _mini_net()
    sess = compile_session(g, shapes)
    sess.specialize(4)
    return sess


def _spans(trace_dir):
    """``[(name, start_ns, end_ns, line, stats)]`` of the host planes of
    the trace written under ``trace_dir``; ``line`` is the thread's line
    by position (Python threads' lines share one name)."""
    (path,) = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                start = int(e.start_ns)
                out.append((e.name, start, start + int(e.duration_ns),
                            (plane.name, i), dict(e.stats)))
    return out


def _x(rng, rows):
    return jnp.asarray(rng.normal(size=(rows, 3, 16, 16))
                       .astype(np.float32))


def _check_batches(spans, before, after):
    """Every ``serving.batch`` span has each child nested inside it on its
    own thread, and the spans' args add up to the counters' change."""
    batches = [s for s in spans if s[0] == "serving.batch"]
    assert len(batches) == after.n_batches - before.n_batches > 0
    for name in CHILDREN:
        kids = [s for s in spans if s[0] == name]
        assert len(kids) == len(batches), name
        for _, a, b, line, _ in kids:
            (parent,) = [p for p in batches
                         if p[3] == line and p[1] <= a and b <= p[2]]
    for *_, st in batches:
        assert st["wait_us_sum"] >= st["wait_us_max"] >= 0
        assert st["requests"] <= st["rows"] <= st["bucket"]
    assert sum(s[4]["rows"] for s in batches) == \
        after.rows_executed - before.rows_executed
    assert sum(s[4]["requests"] for s in batches) == \
        after.n_completed - before.n_completed
    return batches


def test_spans_of_a_pumped_server(session, rng, tmp_path):
    ticks = iter(range(10_000))
    srv = AsyncServer(session, DynamicBatchPolicy(max_batch=4,
                                                  max_wait_ms=0.0),
                      autostart=False, clock=lambda: float(next(ticks)))
    assert GC_PAUSES.installed
    pauses = gc_pauses().get(2, {}).get("pauses", 0)
    before = srv.stats
    with jax.profiler.trace(str(tmp_path)):
        futs = [srv.submit(_x(rng, rows)) for rows in (1, 2, 1, 3, 4, 1)]
        while srv.step():
            pass
        for f in futs:
            f.result(timeout=0)
        gc.collect()
    spans = _spans(tmp_path)
    batches = _check_batches(spans, before, srv.stats)
    assert [s[4]["seq"] for s in batches] == list(range(len(batches)))
    assert {s[4]["worker"] for s in batches} == {0}
    # the fake clock ticks once a call: every request waited a tick or more
    assert all(s[4]["wait_us_max"] >= 1_000_000 for s in batches)
    gcs = [s for s in spans if s[0] == "runtime.gc"]
    assert any(s[4]["generation"] == 2 for s in gcs)
    assert gc_pauses()[2]["pauses"] > pauses
    assert srv.health()["gc"][2]["pause_s"] > 0
    users = GC_PAUSES._users
    srv.close()
    srv.close()                          # idempotent: released once
    assert GC_PAUSES._users == users - 1
    if GC_PAUSES._users == 0:
        assert not GC_PAUSES.installed
        n = gc_pauses()[2]["pauses"]
        gc.collect()
        assert gc_pauses()[2]["pauses"] == n


def test_spans_of_two_workers(session, rng, tmp_path):
    xs = [_x(rng, rows) for rows in (1, 2, 3, 1, 1, 2, 4, 1) * 3]
    srv = AsyncServer(session, DynamicBatchPolicy(max_batch=4,
                                                  max_wait_ms=1.0),
                      workers=2)
    srv.predict(xs[0], timeout=60)       # compiled before the trace
    before = srv.stats

    def wave(part):
        futs = []
        threads = [threading.Thread(
            target=lambda half=half: futs.extend(srv.submit(x)
                                                 for x in half))
            for half in (part[0::2], part[1::2])]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
            assert not t.is_alive()
        for f in futs:
            f.result(timeout=60)

    # a span is written when it closes inside the trace: the workers'
    # waits between the two waves are
    with jax.profiler.trace(str(tmp_path)):
        wave(xs[:12])
        wave(xs[12:])
        after = srv.stats
    srv.close()
    spans = _spans(tmp_path)
    batches = _check_batches(spans, before, after)
    assert {s[4]["worker"] for s in batches} <= {0, 1}
    idle = [s for s in spans if s[0] == "serving.idle"]
    assert idle and {s[4]["worker"] for s in idle} <= {0, 1}
    # a worker is never idle inside one of its own batches
    for _, a, b, line, _ in idle:
        assert not [p for p in batches if p[3] == line
                    and p[1] < b and a < p[2]]


def test_gc_hook_is_reference_counted():
    hook = GcPauses()                    # not the process's own
    assert not hook.installed
    hook.acquire()
    hook.acquire()
    assert hook.installed
    gc.collect()
    (gen2,) = [v for g, v in hook.snapshot().items() if g == 2]
    assert gen2["pauses"] >= 1
    assert 0 < gen2["pause_max_s"] <= gen2["pause_s"]
    hook.release()
    assert hook.installed
    hook.release()
    assert not hook.installed
    gc.collect()                         # no longer counted
    assert hook.snapshot()[2] == gen2
    with pytest.raises(RuntimeError):
        hook.release()


@pytest.mark.parametrize("dispatch", ["whole", "op"])
def test_compiled_forward_names_every_node(dispatch):
    g, shapes = _mini_net()
    m = compile_session(g, shapes, dispatch=dispatch).specialize(1)
    topo = m.plan.planned.graph.topo_order()
    x = jnp.zeros((1, 3, 16, 16), jnp.float32)
    if dispatch == "whole":
        lowered = m._forward.lower(m.params, {m.input_name: x})
        # every node's operations carry its scope into the module XLA is
        # given
        hlo = lowered.as_text(dialect="hlo", debug_info=True)
        assert [n.name for n in topo if n.op != "input"
                and f"/{n.name}/" not in hlo] == []
    else:
        # per-node dispatch jits each node alone: lowered under one outer
        # jit, its programs are inlined with their scopes
        lowered = jax.jit(lambda p, x: m._forward(p, {m.input_name: x})
                          ).lower(m.params, x)
    # the convolutions keep their scope through XLA's passes, where a
    # device trace reads it (reshapes and layout changes may fold away)
    compiled = lowered.compile().as_text()
    convs = [n.name for n in topo if n.op.startswith("conv")]
    assert len(convs) == 2
    assert [n for n in convs if f"/{n}/" not in compiled] == []
