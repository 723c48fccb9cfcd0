"""The reduction of the program's own spans and per-node device time: on a
small hand-made trace, on a trace of a small server recorded here on the
host, and on a trace recorded on one TPU v5e chip."""
import gzip
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import devtrace as T
import reference as R
import run
import spans as S

METRICS = Path(__file__).resolve().parents[1] / "metrics"
RECORDED = Path(__file__).resolve().parent / "data" / \
    "tpu-v5e-resnet50-bulk-mixed-spans.json.gz"
OLD = Path(__file__).resolve().parent / "data" / \
    "tpu-v5e-resnet50-bulk-mixed.json.gz"
NEW = ("batch_host_ms.throughput", "queue_wait_ms.latency",
       "stem_roofline.latency", "stem_roofline.throughput",
       "gc_pause_share.latency", "worker_imbalance.throughput")
MS = 1_000_000


def _reader(name):
    return run.reader(METRICS, name)


def _batch(worker, seq, a, b, rows, requests, wait_us, dw):
    """A batch span from a to b (ms) with its children; the device wait
    is ``dw`` = (start, end) in ms."""
    return [("serving.batch", a * MS, b * MS,
             {"worker": worker, "seq": seq, "requests": requests,
              "rows": rows, "bucket": 8, "wait_us_sum": wait_us,
              "wait_us_max": wait_us}),
            ("serving.gather", a * MS, (a + 1) * MS, {}),
            ("serving.dispatch", (a + 1) * MS, dw[0] * MS, {}),
            ("serving.device_wait", dw[0] * MS, dw[1] * MS, {}),
            ("serving.scatter", dw[1] * MS, b * MS, {})]


def _planes():
    w0 = ([("serving.idle", 0, 2 * MS, {"worker": 0})]
          + _batch(0, 0, 2, 20, 5, 3, 3000, (4, 18))
          + [("serving.idle", 20 * MS, 22 * MS, {"worker": 0})]
          + _batch(0, 2, 22, 44, 8, 1, 500, (24, 42)))   # ends after 40
    w1 = ([("serving.idle", 0, 10 * MS, {"worker": 1})]
          + _batch(1, 1, 10, 30, 3, 2, 1000, (12, 28))
          + [("runtime.gc", 31 * MS, 33 * MS, {"generation": 2})])
    ops = [("%fusion.1 = f32[8]", 4 * MS, 10 * MS, {"scope": "stem_conv"}),
           ("%fusion.2 = f32[8]", 10 * MS, 18 * MS, {"scope": "s1_conv"}),
           ("%fusion.3 = f32[8]", 24 * MS, 30 * MS, {"scope": "stem_conv"}),
           ("%copy.4 = f32[8]", 30 * MS, 31 * MS, {"scope": None})]
    return {"/host:CPU": {"0:python": w0, "1:python": w1,
                          "2:python": [(T.WINDOW, 0, 40 * MS, {})]},
            "/device:TPU:0": {"XLA Ops": ops, "XLA Modules": []}}


def test_reduce_a_window():
    r = S.reduce(_planes(), 0, 40 * MS)
    assert r["window_s"] == pytest.approx(0.040)
    w0, w1 = r["workers"][0], r["workers"][1]
    # worker 0 ended one batch in the window; its second wait is cut at 40
    assert (w0["batches"], w0["rows"], w0["requests"]) == (1, 5, 3)
    assert w0["idle_s"] == pytest.approx(0.004)
    assert w0["device_wait_s"] == pytest.approx(0.014 + 0.016)
    assert (w1["batches"], w1["rows"], w1["requests"]) == (1, 3, 2)
    assert w1["idle_s"] == pytest.approx(0.010)
    assert w1["device_wait_s"] == pytest.approx(0.016)
    assert (r["requests"], r["wait_us_sum"]) == (5, 4000)
    split = r["batch_split_s"]
    assert split == pytest.approx({
        "serving.gather": 0.002, "serving.dispatch": 0.002,
        "serving.device_wait": 0.030, "serving.scatter": 0.004,
        "other": 0.0})
    assert (r["gc_pauses"], r["gc_pause_s"]) == (1, pytest.approx(0.002))
    assert r["node_device_s"] == pytest.approx({"stem_conv": 0.012,
                                                "s1_conv": 0.008})


def test_readers_on_the_reduction():
    readings = {"spans": S.reduce(_planes(), 0, 40 * MS),
                "stem_node": "stem_conv", "stem_macs": 1e9,
                "peak_flops_per_s": 1e14, "chips": 2}
    # worker 0: (40 - 4 - 30) ms over 1 batch; worker 1: (40 - 10 - 16)
    assert _reader("batch_host_ms.throughput")(readings) == \
        pytest.approx((6 + 14) / 2)
    assert _reader("queue_wait_ms.latency")(readings) == pytest.approx(0.8)
    # 2 * 1e9 * 8 rows / (0.012 s * 1e14)
    for name in ("stem_roofline.latency", "stem_roofline.throughput"):
        assert _reader(name)(readings) == pytest.approx(
            100 * 1.6e10 / 1.2e12)
    # the collector's counters over the whole window, not the spans
    assert _reader("gc_pause_share.latency")(readings) is None
    gc = S.gc_window({0: {"pauses": 4, "pause_s": 0.01, "pause_max_s": 0.004},
                      2: {"pauses": 1, "pause_s": 0.2, "pause_max_s": 0.2}},
                     {0: {"pauses": 9, "pause_s": 0.02, "pause_max_s": 0.004},
                      1: {"pauses": 1, "pause_s": 0.003,
                          "pause_max_s": 0.003},
                      2: {"pauses": 2, "pause_s": 0.7, "pause_max_s": 0.5}},
                     10.0)
    assert gc == {"window_s": 10.0, "pauses": 7,
                  "pause_s": pytest.approx(0.513)}
    assert _reader("gc_pause_share.latency")(dict(readings, gc=gc)) == \
        pytest.approx(5.13)
    # rows 5 and 3 over two workers: 5 / 4 - 1
    assert _reader("worker_imbalance.throughput")(readings) == \
        pytest.approx(25.0)
    # a worker that ended no batch in the window counts in the mean
    assert _reader("worker_imbalance.throughput")(
        dict(readings, chips=4)) == pytest.approx(150.0)


def test_a_trace_without_the_programs_spans_reads_nothing():
    with gzip.open(OLD, "rt") as f:
        old = {p: {k: [tuple(e) + ({},) for e in evs]
                   for k, evs in lines.items()}
               for p, lines in json.load(f).items()}
    lo, hi = S.window(old)
    assert S.reduce(old, lo, hi) is None
    for readings in ({"spans": None}, {}):
        for name in NEW:
            assert _reader(name)(dict(readings, stem_node="stem_conv",
                                      stem_macs=1, chips=1,
                                      peak_flops_per_s=1)) is None


@pytest.mark.parametrize("op_name,node", [
    ("jit(forward)/stem_conv/jit(conv2d_block_jnp)/conv_general_dilated",
     "stem_conv"),
    ("jit(forward)/jit(main)/s1u1_a_conv/while/body/dot_general",
     "s1u1_a_conv"),
    ("jit(forward)/transpose", None), ("fusion", None)])
def test_scope_of(op_name, node):
    assert S.scope_of(op_name) == node


@pytest.mark.parametrize("arch", ["resnet50", "densenet121"])
def test_conv_macs_add_up_to_count_macs(arch):
    convs = S.conv_macs(arch, 224, 1000)
    assert len(convs) == {"resnet50": 53, "densenet121": 120}[arch]
    # the stem: 112 x 112 x 64 outputs of 3 x 7 x 7 products each
    assert convs[0] == 112 * 112 * 64 * 3 * 49
    dense = [s for k, s in R.param_spec(arch, 1000) if k == "dense"]
    (w, _), = dense
    assert sum(convs) + w[0] * w[1] == R.count_macs(arch, 224, 1000)


def test_stem_of_a_configuration():
    cfg = json.loads((run.ROOT / "bench" / "configs" /
                      "resnet50-224.json").read_text())
    assert S.stem(cfg, 32) == ("stem_conv", 16 * 16 * 64 * 3 * 49)


def test_events_of_a_server_traced_on_the_host(tmp_path):
    from repro.core.graph import Graph
    from repro.engine import AsyncServer, DynamicBatchPolicy
    from repro.engine import compile as compile_session

    g = Graph()
    g.add("in", "input")
    g.add("c1", "conv2d", ["in"], in_channels=3, out_channels=8, kh=3,
          kw=3, pad=1)
    g.add("gap", "global_avg_pool", ["c1"])
    g.add("fl", "flatten", ["gap"])
    g.add("fc", "dense", ["fl"], units=4)
    g.mark_output("fc")
    sess = compile_session(g, {"in": (1, 3, 8, 8)})
    sess.specialize(4)
    x = jnp.asarray(np.ones((1, 3, 8, 8), np.float32))
    srv = AsyncServer(sess, DynamicBatchPolicy(max_batch=4,
                                               max_wait_ms=0.5), workers=2)
    srv.predict(x, timeout=60)
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation(T.WINDOW):
            c0 = srv.stats
            for _ in range(3):
                for f in [srv.submit(x) for _ in range(6)]:
                    f.result(timeout=60)
            c1 = srv.stats
    srv.close()
    (path,) = tmp_path.glob("**/*.xplane.pb")
    planes = S.events(str(path))
    lo, hi = S.window(planes)
    r = S.reduce(planes, lo, hi)
    assert sum(w["batches"] for w in r["workers"].values()) == \
        c1.n_batches - c0.n_batches
    assert sum(w["rows"] for w in r["workers"].values()) == \
        c1.rows_executed - c0.rows_executed == 18
    assert set(r["workers"]) <= {0, 1}
    assert r["requests"] == 18 and r["wait_us_sum"] >= 0
    assert r["node_device_s"] == {}          # the host has no device plane
    assert 0 < _reader("batch_host_ms.throughput")({"spans": r}) < \
        1e3 * r["window_s"]


def _recorded():
    """200 ms of a traced window of ``resnet50.bulk-mixed`` on one v5e
    chip (``spans.events`` of the kept trace, times shifted to start at
    the slice, the window's span clipped to it, host spans kept 30 ms
    beyond it so that batches on its edges keep their children, XLA op
    names cut to ``devtrace.op_name``)."""
    with gzip.open(RECORDED, "rt") as f:
        planes = json.load(f)
    return {p: {line: [tuple(e) for e in evs] for line, evs in lines.items()}
            for p, lines in planes.items()}


def test_reduce_a_recorded_tpu_trace():
    planes = _recorded()
    lo, hi = S.window(planes)
    assert (lo, hi) == (0, 200 * MS)
    r = S.reduce(planes, lo, hi)
    (w,) = r["workers"].values()
    # one worker, never idle (16 clients keep the queue full): bucket-8
    # forwards of about 11 ms, each ended batch with its four children
    assert w["idle_s"] == 0 and 10 <= w["batches"] <= 20
    assert w["requests"] == r["requests"] <= w["rows"] <= 8 * w["batches"]
    split = r["batch_split_s"]
    assert split["serving.device_wait"] > split["serving.scatter"] > \
        split["serving.dispatch"] > 0 and split["serving.gather"] > 0
    assert 0 <= split["other"] < split["serving.gather"]
    # the named scopes reach the device ops: the stem conv's tap-by-tap
    # gather takes most of the device time, and every op with a scope is
    # one of the graph's nodes
    nodes = r["node_device_s"]
    assert max(nodes, key=nodes.get) == "stem_conv"
    busy = T.reduce({p: {k: [e[:3] for e in evs] for k, evs in ls.items()}
                     for p, ls in planes.items()}, lo, hi)["busy_s"]
    assert 0.5 * busy < nodes["stem_conv"] < sum(nodes.values()) <= busy
    from families import cnn

    graph, _ = cnn.program_graph("resnet-50", 224)
    assert set(nodes) <= {n.name for n in graph.topo_order()}
    readings = {"spans": r, "stem_node": "stem_conv",
                "stem_macs": S.conv_macs("resnet50", 224, 1000)[0],
                "peak_flops_per_s": 197e12, "chips": 1}
    values = {n: _reader(n)(readings) for n in NEW}
    assert 3 < values["batch_host_ms.throughput"] < 8
    assert values["queue_wait_ms.latency"] > 0
    assert 0 < values["stem_roofline.throughput"] == \
        values["stem_roofline.latency"] < 1
    assert values["gc_pause_share.latency"] is None   # no counters here
    assert 0 <= r["gc_pause_s"] < 0.1 * r["window_s"]
    assert values["worker_imbalance.throughput"] == 0


def _pb(*fields):
    """A protobuf message of ``(field, value)``: ints as varints, text,
    bytes and messages length-delimited."""
    def varint(n):
        out = bytearray()
        while True:
            out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
            n >>= 7
            if not n:
                return bytes(out)
    out = b""
    for num, value in fields:
        if isinstance(value, int):
            out += varint(num << 3) + varint(value)
        else:
            value = value.encode() if isinstance(value, str) else value
            out += varint(num << 3 | 2) + varint(len(value)) + value
    return out


def test_op_scopes_read_the_event_metadata():
    def stat_meta(i, name):
        return (5, _pb((1, i), (2, _pb((1, i), (2, name)))))

    def event_meta(i, name, *stats):
        return (4, _pb((1, i), (2, _pb((1, i), (2, name),
                                       *[(5, _pb(*s)) for s in stats]))))

    device = _pb(
        (2, "/device:TPU:0"), stat_meta(7, "tf_op"),
        stat_meta(9, "jit(forward)/s1_conv/dot_general:"),
        event_meta(1, "%fusion.1 = f32[8]",
                   [(1, 7), (5, "jit(forward)/stem_conv/jit(c)/dot:")]),
        event_meta(2, "%fusion.2 = f32[8]", [(1, 7), (7, 9)]),
        event_meta(3, "%copy.3 = f32[8]"))
    host = _pb((2, "/host:CPU"), stat_meta(7, "tf_op"),
               event_meta(1, "host op", [(1, 7), (5, "jit(f)/x/y")]))
    assert S.op_scopes(_pb((1, device), (1, host))) == {
        "%fusion.1 = f32[8]": "stem_conv", "%fusion.2 = f32[8]": "s1_conv",
        "%copy.3 = f32[8]": None}


def test_the_four_chip_cells_traced_line_reads_every_metric():
    """``resnet50.bulk-mixed-4chips`` reports the per-layer metrics of the
    bulk cell: on a trace of four chips each reads a number, the device
    time summed over the chips."""
    cell, _, mix, _, metrics = run.resolve(run.ROOT,
                                           "resnet50.bulk-mixed-4chips",
                                           True)
    assert (cell["chips"], mix["clients"]) == (4, 64)
    assert {m["name"] for m in metrics} == {
        "mfu.throughput", "padded_share", "idle_share.throughput"}
    planes = {"/host:CPU": {"python3": [(T.WINDOW, 0, 40 * MS)]}}
    for chip in range(4):
        # chip c runs forwards of 10 ms from 2c ms, one every 20 ms
        runs = [((2 * chip + t) * MS, (2 * chip + t + 10) * MS)
                for t in (0, 20)]
        planes[f"/device:TPU:{chip}"] = {
            "XLA Ops": [("%fusion.1 = f32[8]", a, b) for a, b in runs],
            "XLA Modules": [("jit_forward(1)", a, b) for a, b in runs]}
    traced = T.reduce(planes, 0, 40 * MS)
    assert (traced["chips_traced"], traced["forward_n"]) == (4, 8)
    traced.update(rows_executed=56, n_batches=8)
    res = {"metrics": {}, "readings": {
        "traced": traced, "macs_per_image": 4.09e9, "chips": 4,
        "peak_flops_per_s": 197e12,
        "window": {"rows_executed": 56, "rows_padded": 8, "n_batches": 8,
                   "worker_batches": {w: 2 for w in range(4)}}}}
    out = run.result_metrics(res, metrics)
    # each chip busy 20 of 40 ms; 2 * 4.09e9 * 56 over 80 ms at the peak
    assert out["idle_share.throughput"]["value"] == pytest.approx(50.0)
    assert out["mfu.throughput"]["value"] == pytest.approx(
        100 * 2 * 4.09e9 * 56 / (0.080 * 197e12))
    assert out["padded_share"]["value"] == pytest.approx(12.5)
