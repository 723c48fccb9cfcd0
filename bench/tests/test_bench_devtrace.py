"""The reduction from trace events to busy time, forward step times and
the breakdown: on a small hand-made trace, and on 40 ms of a trace
recorded on one TPU v5e chip (``data/tpu-v5e-resnet50-bulk-mixed.json.gz``:
the events of ``devtrace.events`` of a ``readings.py --trace --keep-trace``
run of ``resnet50.bulk-mixed``, from 2 ms before a forward program
starts inside the traced window, times shifted to start at 0, the window's
span clipped to the slice)."""
import gzip
import json
from pathlib import Path

import pytest

import devtrace as T
import run

METRICS = Path(__file__).resolve().parents[1] / "metrics"
RECORDED = Path(__file__).resolve().parent / "data" / \
    "tpu-v5e-resnet50-bulk-mixed.json.gz"


def _reader(name):
    return run.reader(METRICS, name)


def _planes():
    ms = 1_000_000
    ops = [("conv.1", 0 * ms, 4 * ms), ("conv.2", 3 * ms, 6 * ms),
           ("copy", 10 * ms, 11 * ms), ("conv.1", 20 * ms, 26 * ms),
           ("conv.2", 40 * ms, 45 * ms)]       # the last outside the window
    mods = [("jit_forward(7)", 0, 6 * ms), ("jit_concatenate(3)", 10 * ms,
                                             11 * ms),
            ("jit_forward(7)", 20 * ms, 26 * ms)]
    host = [(T.WINDOW, 1 * ms, 30 * ms), ("bench.generator_waits",
                                           6 * ms, 10 * ms),
            ("bench.submit", 11 * ms, 12 * ms),
            ("PjitFunction(forward)", 12 * ms, 20 * ms),
            ("worker", 11 * ms, 20 * ms)]
    return {"/device:TPU:0": {"XLA Ops": ops, "XLA Modules": mods},
            "/host:CPU": {"python3": host}, "/host:metadata": {}}


def test_union_and_clip():
    assert T.union([(5, 9), (0, 2), (1, 3), (9, 10)]) == [(0, 3), (5, 10)]
    assert T.clip([(0, 3), (5, 10)], 2, 6) == [(2, 3), (5, 6)]
    assert T.total([(2, 3), (5, 6)]) == 2


def test_reduce_a_window():
    ms = 1_000_000
    r = T.reduce(_planes(), 1 * ms, 30 * ms)
    assert r["window_s"] == pytest.approx(0.029)
    # busy: [1, 6) + [10, 11) + [20, 26) inside the window
    assert r["busy_s"] == pytest.approx(0.012)
    assert r["forward_n"] == 2
    assert r["forward_s"] == pytest.approx(0.011)      # 5 + 6 ms
    ops = dict(r["breakdown"]["device_ops"])
    assert ops == pytest.approx({"conv.1": 0.009, "conv.2": 0.003,
                                 "copy": 0.001})
    gaps = r["breakdown"]["idle_gaps"]
    # [11, 20) 9 ms, [6, 10) 4 ms, [26, 30) 4 ms, longest first
    assert [g[1] for g in gaps] == pytest.approx([0.009, 0.004, 0.004])
    assert gaps[0][0] == "PjitFunction(forward)"   # most overlap: 8 of 9 ms
    assert gaps[1][0] == "bench.generator_waits"
    assert gaps[2][0] == "no host event"


def test_readers_on_the_reduction():
    ms = 1_000_000
    r = T.reduce(_planes(), 1 * ms, 30 * ms)
    r.update(rows_executed=5, n_batches=2)
    readings = {"traced": r, "macs_per_image": 1e9,
                "peak_flops_per_s": 1e14, "chips": 1,
                "window": {"rows_executed": 30, "rows_padded": 10,
                           "n_batches": 5, "worker_batches": {0: 5}}}
    assert _reader("step_ms.latency")(readings) == pytest.approx(5.5)
    # 2 * 1e9 * 5 / (0.011 s * 1e14)
    assert _reader("mfu.latency")(readings) == pytest.approx(
        100 * 1e10 / (0.011 * 1e14))
    assert _reader("idle_share.throughput")(readings) == pytest.approx(
        100 * (1 - 12 / 29))
    assert _reader("padded_share")(readings) == pytest.approx(25.0)
    assert (_reader("mfu.throughput").__code__.co_filename
            == _reader("mfu.latency").__code__.co_filename
            == str(METRICS / "mfu.py"))


def test_nothing_to_read_gives_nothing():
    planes = {"/host:CPU": {"python3": [(T.WINDOW, 0, 10)]}}
    assert T.reduce(planes, 0, 10) is None
    empty = {"traced": None, "window": {"rows_executed": 0,
                                        "rows_padded": 0}}
    for name in ("step_ms.latency", "mfu.latency", "mfu.throughput",
                 "idle_share.throughput", "padded_share"):
        assert _reader(name)(empty) is None


def test_a_metric_that_reads_nothing_fails_the_run():
    metrics = [{"name": "mfu.throughput", "unit": "%",
                "read": _reader("mfu.throughput")}]
    with pytest.raises(LookupError):
        run.result_metrics({"readings": {"traced": None}}, metrics)


@pytest.mark.parametrize("text,name", [
    ("%fusion.96 = f32[8,1,112,112,64]{3,4,2,0,1:T(8,128)} fusion(f32[8]), "
     "kind=kOutput", "fusion.96 f32[8,1,112,112,64]"),
    ("%copy-done = f32[8,1000]{1,0:T(8,128)S(1)} copy-done((f32[8,1000]))",
     "copy-done f32[8,1000]"),
    ("conv.1", "conv.1")])
def test_op_names_drop_the_hlo_text(text, name):
    assert T.op_name(text) == name


def _recorded():
    with gzip.open(RECORDED, "rt") as f:
        planes = json.load(f)
    return {p: {line: [tuple(e) for e in evs] for line, evs in lines.items()}
            for p, lines in planes.items()}


def test_a_recorded_tpu_trace_has_what_the_reduction_looks_for():
    planes = _recorded()
    assert T.device_planes(planes) == ["/device:TPU:0"]
    assert {"XLA Ops", "XLA Modules"} <= set(planes["/device:TPU:0"])
    mods = {n.split("(")[0] for n, _, _ in planes["/device:TPU:0"]["XLA Modules"]}
    assert T.FORWARD in mods
    (lo, hi), = _window(planes)
    assert 0 <= lo < hi == 40_000_000 and hi - lo > 38_000_000


def _window(planes):
    return [(a, b) for p, lines in planes.items() if p.startswith("/host:")
            for evs in lines.values() for n, a, b in evs if n == T.WINDOW]


def test_reduce_a_recorded_tpu_trace():
    planes = _recorded()
    (lo, hi), = _window(planes)
    r = T.reduce(planes, lo, hi)
    fwd = [(a, b) for n, a, b in planes["/device:TPU:0"]["XLA Modules"]
           if n.startswith(T.FORWARD) and b > lo and a < hi]
    # the slice holds two bucket-8 forwards of about 11.5 ms, one after the
    # other, which the reduction must count and time as they are
    assert len(fwd) == r["forward_n"] == 2
    assert r["forward_s"] == pytest.approx(
        sum(min(b, hi) - max(a, lo) for a, b in fwd) / 1e9)
    assert 0.02 < r["forward_s"] <= r["busy_s"] + 1e-9
    assert r["busy_s"] < r["window_s"] == pytest.approx((hi - lo) / 1e9)
    ops = r["breakdown"]["device_ops"]
    assert len(ops) == T.TOP
    assert [t for _, t in ops] == sorted((t for _, t in ops), reverse=True)
    assert ops[0][0] == "concatenate.49 f32[8,1,49,112,112,3]"
    assert all(" = " not in n for n, _ in ops)
    gaps = r["breakdown"]["idle_gaps"]
    assert [t for _, t in gaps] == sorted((t for _, t in gaps), reverse=True)
    assert sum(t for _, t in gaps) <= r["window_s"] - r["busy_s"] + 1e-9
    r.update(rows_executed=16, n_batches=2)
    readings = {"traced": r, "macs_per_image": 4.09e9,
                "peak_flops_per_s": 197e12}
    for name in ("step_ms.latency", "mfu.throughput",
                 "idle_share.throughput"):
        assert 0 < _reader(name)(readings) < 100, name
