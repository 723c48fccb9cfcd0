"""Multi-core stack suite: XLA_FLAGS merging, worker CPU partitioning,
bucket/device divisibility, multi-worker serving determinism, and (in a
subprocess, because ``conftest.py`` deliberately exposes only the single
real device) sharded-vs-single-device equivalence plus sharded-artifact
round trips under 2 forced host devices."""
import os
import subprocess
import sys
import textwrap
import threading
import warnings
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.graph import Graph
from repro.engine import AsyncServer, DynamicBatchPolicy, padded_predict
from repro.engine import compile as compile_session
from repro.launch.cpu import (DEVICE_COUNT_FLAG, configure_cpu_devices,
                              configured_device_count, maybe_pin,
                              merge_xla_flag, parse_xla_flag,
                              worker_cpu_sets)

SRC = str(Path(__file__).resolve().parents[1] / "src")


def _mini_net():
    g = Graph()
    g.add("in", "input")
    g.add("c1", "conv2d", ["in"], in_channels=3, out_channels=16, kh=3,
          kw=3, stride=2, pad=1)
    g.add("bn1", "batch_norm", ["c1"])
    g.add("r1", "relu", ["bn1"])
    g.add("gap", "global_avg_pool", ["r1"])
    g.add("fl", "flatten", ["gap"])
    g.add("fc", "dense", ["fl"], units=10)
    g.mark_output("fc")
    return g, {"in": (1, 3, 16, 16)}


# ---------------------------------------------------------------------------
# configure_cpu_devices: XLA_FLAGS merging semantics
# ---------------------------------------------------------------------------

def test_configure_sets_flag_in_empty_env():
    env = {}
    assert configure_cpu_devices(4, env=env, warn_oversubscribe=False) == 4
    assert env["XLA_FLAGS"] == f"{DEVICE_COUNT_FLAG}=4"
    assert configured_device_count(env) == 4


def test_configure_preserves_existing_user_flags():
    env = {"XLA_FLAGS": "--xla_cpu_enable_fast_math=true"}
    configure_cpu_devices(2, env=env, warn_oversubscribe=False)
    assert "--xla_cpu_enable_fast_math=true" in env["XLA_FLAGS"]
    assert configured_device_count(env) == 2


def test_configure_replaces_without_duplicating():
    env = {"XLA_FLAGS": f"--foo=1 {DEVICE_COUNT_FLAG}=512 --bar=2"}
    configure_cpu_devices(2, env=env, warn_oversubscribe=False)
    toks = env["XLA_FLAGS"].split()
    assert sum(t.startswith(DEVICE_COUNT_FLAG) for t in toks) == 1
    assert configured_device_count(env) == 2
    assert "--foo=1" in toks and "--bar=2" in toks


def test_configure_rejects_non_positive():
    with pytest.raises(ValueError, match=">= 1"):
        configure_cpu_devices(0, env={})


def test_configure_warns_on_oversubscription():
    n = (os.cpu_count() or 1) + 1
    with pytest.warns(RuntimeWarning, match="time-share"):
        configure_cpu_devices(n, env={})
    with warnings.catch_warnings():
        warnings.simplefilter("error")       # must stay silent
        configure_cpu_devices(n, env={}, warn_oversubscribe=False)


def test_merge_and_parse_round_trip():
    flags = merge_xla_flag("", "--a", 1)
    flags = merge_xla_flag(flags, "--b", "x")
    flags = merge_xla_flag(flags, "--a", 2)
    assert parse_xla_flag(flags, "--a") == "2"
    assert parse_xla_flag(flags, "--b") == "x"
    assert parse_xla_flag(flags, "--c") is None


# ---------------------------------------------------------------------------
# Worker CPU partitioning + pinning
# ---------------------------------------------------------------------------

def test_worker_cpu_sets_partition_when_enough_cores():
    sets = worker_cpu_sets(2, cpus=[0, 1, 2, 3, 4])
    assert sets == [(0, 2, 4), (1, 3)]
    flat = [c for s in sets for c in s]
    assert sorted(flat) == [0, 1, 2, 3, 4]       # disjoint, full coverage


def test_worker_cpu_sets_repeat_when_fewer_cores():
    sets = worker_cpu_sets(3, cpus=[0])
    assert sets == [(0,), (0,), (0,)]
    with pytest.raises(ValueError):
        worker_cpu_sets(0)


def test_maybe_pin_explicit_cpus_pins_calling_thread():
    got = []

    def run():
        got.append(maybe_pin((0,)))

    t = threading.Thread(target=run)
    t.start()
    t.join()
    # None only where the platform/container forbids affinity calls
    assert got[0] in (None, (0,))


# ---------------------------------------------------------------------------
# Bucket/device divisibility + missing-device diagnostics
# ---------------------------------------------------------------------------

def test_specialize_rejects_indivisible_bucket():
    g, shapes = _mini_net()
    sess = compile_session(g, shapes, devices=2, eager=False)
    with pytest.raises(ValueError, match="not divisible by devices"):
        sess.specialize(3)


def test_compile_eager_rejects_indivisible_base_batch():
    g, shapes = _mini_net()
    shapes = {"in": (3,) + shapes["in"][1:]}
    with pytest.raises(ValueError, match="not divisible by devices"):
        compile_session(g, shapes, devices=2)


def test_missing_devices_error_names_the_fix():
    import jax
    if len(jax.devices()) >= 2:
        pytest.skip("host already exposes multiple devices")
    g, shapes = _mini_net()
    sess = compile_session(g, shapes, devices=2, eager=False)
    with pytest.raises(RuntimeError, match=r"sees 1 \(cpu\); compile with "
                                           r"devices <= 1"):
        sess.specialize(2)


# ---------------------------------------------------------------------------
# Multi-worker AsyncServer (single device: shared program, N threads)
# ---------------------------------------------------------------------------

def test_server_rejects_bad_workers_and_pin():
    g, shapes = _mini_net()
    sess = compile_session(g, shapes)
    with pytest.raises(ValueError, match="workers"):
        AsyncServer(sess, workers=0, autostart=False)
    with pytest.raises(ValueError, match="pin"):
        AsyncServer(sess, workers=2, pin=[(0,)], autostart=False)


def test_multiworker_fifo_bit_identical(rng):
    """Two real worker threads over one queue: fixed-bucket packing stays
    FIFO, so every response bit-matches sequential padded_predict in
    submission order no matter which worker ran the batch."""
    g, shapes = _mini_net()
    sess = compile_session(g, shapes)
    sess.specialize(4)
    xs = [jnp.asarray(rng.normal(size=(1, 3, 16, 16)).astype(np.float32))
          for _ in range(12)]
    refs = [np.asarray(padded_predict(sess, x, bucket=4)) for x in xs]
    policy = DynamicBatchPolicy(max_batch=4, max_wait_ms=5.0,
                                fixed_bucket=4)
    with AsyncServer(sess, policy, max_queue=64, workers=2) as srv:
        futs = [srv.submit(x) for x in xs]
        got = [np.asarray(f.result(timeout=60)) for f in futs]
    for a, b in zip(got, refs):
        assert a.shape == b.shape and a.tobytes() == b.tobytes()
    st = srv.stats
    assert st.n_completed == 12
    assert sum(st.worker_batches.values()) == st.n_batches
    assert set(st.worker_batches) <= {0, 1}


def test_multiworker_specializes_once(monkeypatch, rng):
    """Workers racing on the same unseen bucket plan+compile it exactly
    once (the session lock) — the multi-worker double-compile guard."""
    g, shapes = _mini_net()
    sess = compile_session(g, shapes)
    calls = []
    real_run = type(sess.pipeline).run

    def counting_run(self, *a, **kw):
        calls.append(threading.get_ident())
        threading.Event().wait(0.05)         # widen the race window
        return real_run(self, *a, **kw)

    monkeypatch.setattr(type(sess.pipeline), "run", counting_run)
    xs = [jnp.asarray(rng.normal(size=(1, 3, 16, 16)).astype(np.float32))
          for _ in range(8)]
    policy = DynamicBatchPolicy(max_batch=4, max_wait_ms=1.0,
                                fixed_bucket=4)
    with AsyncServer(sess, policy, max_queue=16, workers=2) as srv:
        futs = [srv.submit(x) for x in xs]
        for f in futs:
            f.result(timeout=60)
    assert len(calls) == 1, "workers double-compiled the same bucket"
    assert 4 in sess.batch_sizes


def test_multiworker_fake_clock_manual_steps(rng):
    """autostart=False spawns no threads even with workers=2; manual
    step() retains the single-threaded deterministic schedule."""
    g, shapes = _mini_net()
    sess = compile_session(g, shapes)
    sess.specialize(4)
    clock_t = [100.0]
    policy = DynamicBatchPolicy(max_batch=4, max_wait_ms=10.0,
                                fixed_bucket=4)
    srv = AsyncServer(sess, policy, workers=2, autostart=False,
                      clock=lambda: clock_t[0])
    xs = [jnp.asarray(rng.normal(size=(1, 3, 16, 16)).astype(np.float32))
          for _ in range(4)]
    futs = [srv.submit(x) for x in xs]
    assert srv.step()                         # full bucket, no wait needed
    assert all(f.done() for f in futs)
    assert srv.stats.worker_batches == {0: 1}
    srv.close()


# ---------------------------------------------------------------------------
# Sharded execution: needs >1 host device -> subprocess with XLA_FLAGS
# ---------------------------------------------------------------------------

_SHARDED_SCRIPT = textwrap.dedent("""
    import numpy as np
    import jax, jax.numpy as jnp
    from repro.core.graph import Graph
    from repro.engine import InferenceSession
    from repro.engine import compile as compile_session

    assert len(jax.devices()) == 2, jax.devices()

    g = Graph()
    g.add("in", "input")
    g.add("c1", "conv2d", ["in"], in_channels=3, out_channels=16, kh=3,
          kw=3, stride=2, pad=1)
    g.add("bn1", "batch_norm", ["c1"])
    g.add("r1", "relu", ["bn1"])
    g.add("gap", "global_avg_pool", ["r1"])
    g.add("fl", "flatten", ["gap"])
    g.add("fc", "dense", ["fl"], units=10)
    g.mark_output("fc")
    shapes = {"in": (2, 3, 16, 16)}

    s1 = compile_session(g, shapes)
    s2 = compile_session(g, shapes, devices=2)
    rng = np.random.default_rng(0)
    for b in (2, 4):
        s1.specialize(b); s2.specialize(b)
        x = jnp.asarray(rng.normal(size=(b, 3, 16, 16)).astype(np.float32))
        y1, y2 = np.asarray(s1.predict(x)), np.asarray(s2.predict(x))
        assert y1.shape == y2.shape == (b, 10)
        assert np.allclose(y1, y2, rtol=1e-5, atol=1e-5), \\
            f"bucket {b}: sharded drifted {np.abs(y1 - y2).max()}"
        # sharded program is deterministic run-to-run
        assert np.asarray(s2.predict(x)).tobytes() == y2.tobytes()

    # artifact round trip keeps the device count and bit-exact execution
    import tempfile
    x = jnp.asarray(rng.normal(size=(4, 3, 16, 16)).astype(np.float32))
    ref = np.asarray(s2.predict(x))
    with tempfile.TemporaryDirectory() as d:
        s2.save(d + "/art")
        loaded = InferenceSession.load(d + "/art")
        assert loaded.devices == 2
        assert np.asarray(loaded.predict(x)).tobytes() == ref.tobytes()
        # retarget: same packed artifact, different device count
        single = InferenceSession.load(d + "/art", devices=1)
        assert single.devices == 1 and single.batch_sizes == []
        y = np.asarray(single.predict(x))
        assert np.allclose(y, ref, rtol=1e-5, atol=1e-5)
    print("SHARDED-OK")
""")


def test_sharded_equivalence_two_forced_devices():
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    env["XLA_FLAGS"] = merge_xla_flag(env.get("XLA_FLAGS", ""),
                                      DEVICE_COUNT_FLAG, 2)
    proc = subprocess.run([sys.executable, "-c", _SHARDED_SCRIPT],
                          env=env, capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "SHARDED-OK" in proc.stdout


_REPLICAS_SCRIPT = textwrap.dedent("""
    import itertools
    import numpy as np
    import jax, jax.numpy as jnp
    from repro.core.graph import Graph
    from repro.engine import AsyncServer, DynamicBatchPolicy
    from repro.engine import compile as compile_session

    devs = jax.devices()
    assert len(devs) == 4, devs
    g = Graph()
    g.add("in", "input")
    g.add("c1", "conv2d", ["in"], in_channels=3, out_channels=16, kh=3,
          kw=3, stride=2, pad=1)
    g.add("gap", "global_avg_pool", ["c1"])
    g.add("fl", "flatten", ["gap"])
    g.add("fc", "dense", ["fl"], units=10)
    g.mark_output("fc")
    sess = compile_session(g, {"in": (4, 3, 16, 16)})
    m = sess.specialize(4)
    x = jnp.asarray(np.random.default_rng(0).normal(size=(3, 3, 16, 16))
                    .astype(np.float32))
    ys = []
    # one pumped server a device, each with worker 0 on that device: the
    # first batch compiles the bucket on all four devices at once, and
    # the later servers compile no forward of their own
    for w in range(4):
        clock = itertools.count()
        srv = AsyncServer(sess, DynamicBatchPolicy(max_batch=4,
                                                   max_wait_ms=1.0),
                          workers=4, devices=devs[w:] + devs[:w],
                          autostart=False, clock=lambda: float(next(clock)))
        f = srv.submit(x)
        assert srv.step()
        ys.append(f.result(timeout=0))
        srv.close()
        assert m._forward._cache_size() == 4, (w, m._forward._cache_size())
    assert [next(iter(y.devices())) for y in ys] == devs
    assert all(np.asarray(y).tobytes() == np.asarray(ys[0]).tobytes()
               for y in ys)
    print("REPLICAS-OK")
""")


def test_first_batch_compiles_every_workers_replica_four_devices():
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    env["XLA_FLAGS"] = merge_xla_flag(env.get("XLA_FLAGS", ""),
                                      DEVICE_COUNT_FLAG, 4)
    proc = subprocess.run([sys.executable, "-c", _REPLICAS_SCRIPT],
                          env=env, capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "REPLICAS-OK" in proc.stdout


# ---------------------------------------------------------------------------
# Importing launch entry points must not configure devices (regression:
# launch.dryrun used to call configure_cpu_devices(512) at import time,
# oversubscription-warning every importer and locking the device count
# for the whole process — pytest collection included)
# ---------------------------------------------------------------------------

def test_importing_dryrun_has_no_device_side_effect():
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "-W", "error::RuntimeWarning", "-c", textwrap.dedent("""
            import os, jax
            n_before = jax.device_count()       # locks the backend
            import repro.launch.dryrun          # must be side-effect free
            assert jax.device_count() == n_before, "device count changed"
            assert "--xla_force_host_platform_device_count" \\
                not in os.environ.get("XLA_FLAGS", ""), \\
                "import mutated XLA_FLAGS"
            print("IMPORT-CLEAN")
        """)],
        env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "IMPORT-CLEAN" in proc.stdout
