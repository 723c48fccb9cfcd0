"""Deterministic serving-driver suite: packing/deadline scheduling on a
fake clock (no sleeps), bit-identical packed results, typed backpressure
errors, graceful drain, and the specialize() double-compile regression.

Kept on its own short-timeout CI lane — a hang here must fail fast, not
eat the tier-1 wall-clock budget."""
import threading

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.graph import Graph
from repro.engine import (AsyncServer, DeadlineExceededError,
                          DynamicBatchPolicy, QueueFullError,
                          ServerClosedError, compile_model, nearest_bucket,
                          padded_predict)
from repro.engine import compile as compile_session
from repro.engine.serving import pad_rows


def _mini_net():
    g = Graph()
    g.add("in", "input")
    g.add("c1", "conv2d", ["in"], in_channels=3, out_channels=16, kh=3,
          kw=3, stride=2, pad=1)
    g.add("bn1", "batch_norm", ["c1"])
    g.add("r1", "relu", ["bn1"])
    g.add("c2", "conv2d", ["r1"], in_channels=16, out_channels=32, kh=3,
          kw=3, pad=1)
    g.add("gap", "global_avg_pool", ["c2"])
    g.add("fl", "flatten", ["gap"])
    g.add("fc", "dense", ["fl"], units=10)
    g.mark_output("fc")
    return g, {"in": (1, 3, 16, 16)}


@pytest.fixture(scope="module")
def session():
    """One compiled session with serving buckets {1, 4} shared by the
    module (compilation dominates; the driver never mutates it beyond the
    specialization cache)."""
    g, shapes = _mini_net()
    sess = compile_session(g, shapes)
    sess.specialize(4)
    return sess


class FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t

    def advance_ms(self, ms):
        self.t += ms / 1e3


def _x(rng, rows, hw=16):
    return jnp.asarray(rng.normal(size=(rows, 3, hw, hw))
                       .astype(np.float32))


def _manual_server(session, **kw):
    clock = FakeClock()
    policy = kw.pop("policy", DynamicBatchPolicy(max_batch=4,
                                                 max_wait_ms=10.0))
    srv = AsyncServer(session, policy, clock=clock, autostart=False, **kw)
    return srv, clock


# ---------------------------------------------------------------------------
# Concurrency: packed results == sequential serving, bit for bit
# ---------------------------------------------------------------------------

def test_concurrent_submits_bit_identical_to_sequential(session, rng):
    xs = [_x(rng, 1) for _ in range(12)]
    refs = [np.asarray(padded_predict(session, x, bucket=4)) for x in xs]

    policy = DynamicBatchPolicy(max_batch=4, max_wait_ms=5.0,
                                fixed_bucket=4)
    with AsyncServer(session, policy, max_queue=64) as srv:
        futs = [None] * len(xs)

        def client(lo, hi):
            for i in range(lo, hi):
                futs[i] = srv.submit(xs[i])

        threads = [threading.Thread(target=client, args=(i * 4, i * 4 + 4))
                   for i in range(3)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        got = [np.asarray(f.result(timeout=60)) for f in futs]
    for g, r in zip(got, refs):
        assert g.shape == r.shape and g.tobytes() == r.tobytes(), \
            "packed result drifted from sequential serving"
    st = srv.stats
    assert st.n_completed == 12
    assert st.rows_executed == 12
    # every executed batch respected max_batch
    assert st.batch_hist.max_size <= 4


def test_padded_batch_slices_back_per_request(session, rng):
    """Mixed-size requests packed into one bucket come back with each
    request's own rows (and match the unpacked reference)."""
    xa, xb = _x(rng, 3), _x(rng, 1)
    srv, clock = _manual_server(session)
    fa, fb = srv.submit(xa), srv.submit(xb)
    assert srv.step()                       # 4 rows pending == max_batch
    ya, yb = np.asarray(fa.result(0)), np.asarray(fb.result(0))
    assert ya.shape[0] == 3 and yb.shape[0] == 1
    packed = np.asarray(session.specialize(4).predict(
        jnp.concatenate([xa, xb])))
    assert ya.tobytes() == packed[:3].tobytes()
    assert yb.tobytes() == packed[3:4].tobytes()
    assert srv.stats.rows_padded == 0       # 3+1 filled the bucket exactly
    srv.close()


# ---------------------------------------------------------------------------
# A batch's output reaches the host in one copy; requests get host views
# ---------------------------------------------------------------------------

def _two_output_net():
    g, shapes = _mini_net()
    g.mark_output("fl")                     # (N, 32) beside fc's (N, 10)
    return g, shapes


@pytest.fixture(scope="module")
def two_output_session():
    g, shapes = _two_output_net()
    sess = compile_session(g, shapes)
    sess.specialize(4)
    return sess


def _row_bytes(y):
    leaves = y if isinstance(y, tuple) else (y,)
    return sum(a[:1].nbytes for a in leaves)


@pytest.mark.parametrize("two_outputs", [False, True],
                         ids=["one_output", "tuple_of_outputs"])
def test_packed_batch_resolves_host_rows_identical_to_padded_predict(
        session, two_output_session, rng, two_outputs):
    """Three requests of different sizes packed into bucket 4: each future
    resolves to host arrays holding exactly its rows, bit-identical to
    (and of the same type as) ``padded_predict`` at the same bucket, and
    the batch's output is counted once in ``fetched_bytes``."""
    sess = two_output_session if two_outputs else session
    xs = [_x(rng, 1), _x(rng, 2), _x(rng, 1)]
    srv, _ = _manual_server(sess)
    futs = [srv.submit(x) for x in xs]
    assert srv.step()                       # 1+2+1 rows == max_batch
    for x, f in zip(xs, futs):
        got, ref = f.result(0), padded_predict(sess, x, bucket=4)
        assert type(got) is type(ref)
        assert isinstance(got, tuple) == two_outputs
        got_l = got if two_outputs else (got,)
        ref_l = ref if two_outputs else (ref,)
        assert len(got_l) == len(ref_l) == (2 if two_outputs else 1)
        for g, r in zip(got_l, ref_l):
            assert isinstance(g, np.ndarray)
            assert g.shape[0] == x.shape[0]
            assert g.dtype == r.dtype and g.shape == r.shape
            assert g.tobytes() == r.tobytes()
    st = srv.stats
    assert st.n_batches == 1 and st.rows_padded == 0
    assert st.fetched_bytes == st.n_batches * 4 * _row_bytes(ref)
    assert st.to_json()["fetched_bytes"] == st.fetched_bytes
    assert srv.health()["telemetry"]["fetched_bytes"] == st.fetched_bytes
    srv.close()


def test_fetched_bytes_counts_every_batch_at_its_bucket(session, rng):
    """Each batch copies its whole bucket, padded rows included: 4 rows
    at bucket 4, 3 rows padded to 4, then 1 row at bucket 1."""
    srv, clock = _manual_server(session)
    futs = []
    for rows in (4, 3, 1):
        futs.append(srv.submit(_x(rng, rows)))
        clock.advance_ms(20)                # past max_wait: flush it
        assert srv.step()
    row = futs[-1].result(0).nbytes
    st = srv.stats
    assert (st.n_batches, st.rows_padded) == (3, 1)
    assert st.fetched_bytes == (4 + 4 + 1) * row
    srv.close()


def test_scatter_makes_no_device_dispatch(session, rng, monkeypatch):
    """Once the batch's output is on the host, requests are resolved from
    host views: no device array is sliced during a step, so no
    ``dynamic_slice`` program is dispatched a request."""
    xs = [_x(rng, 1), _x(rng, 2), _x(rng, 1)]
    refs = [padded_predict(session, x, bucket=4) for x in xs]
    srv, _ = _manual_server(session)
    futs = [srv.submit(x) for x in xs]

    def no_device_slice(self, idx):
        raise AssertionError("a device array was sliced while serving")

    monkeypatch.setattr(type(jnp.zeros(1)), "__getitem__", no_device_slice)
    assert srv.step()
    monkeypatch.undo()
    for f, r in zip(futs, refs):
        assert f.exception(0) is None
        assert f.result(0).tobytes() == r.tobytes()
    assert srv.stats.n_completed == 3 and srv.stats.n_failed == 0
    srv.close()


def test_host_rows_name_the_device_that_computed_them(session, rng):
    """Served rows are host arrays that still answer ``devices()``, as
    the device array they were copied from did; views keep it."""
    x = _x(rng, 3)
    y = padded_predict(session, x, bucket=4)
    dev = session.specialize(4).predict(pad_rows(x, 4)).devices()
    assert y.devices() == dev
    assert y[1:2].devices() == dev
    assert type(np.asarray(y)) is np.ndarray


# ---------------------------------------------------------------------------
# Packing honors max_batch / max_wait_ms (fake clock, no sleeps)
# ---------------------------------------------------------------------------

def test_packing_respects_max_batch_and_max_wait(session, rng):
    srv, clock = _manual_server(session)
    # under max_batch and under max_wait: nothing runs
    f1 = srv.submit(_x(rng, 1))
    f2 = srv.submit(_x(rng, 1))
    assert not srv.step()
    assert not f1.done() and not f2.done()
    # oldest hits max_wait_ms -> partial flush of both
    clock.advance_ms(10.1)
    assert srv.step()
    assert f1.done() and f2.done()
    assert srv.stats.batch_hist.counts() == {2: 1}
    # a full batch flushes immediately, leftovers wait for their timeout
    futs = [srv.submit(_x(rng, 1)) for _ in range(5)]
    assert srv.step()
    assert srv.stats.batch_hist.counts() == {2: 1, 4: 1}
    assert [f.done() for f in futs] == [True] * 4 + [False]
    assert not srv.step()                     # 1 pending, clock unchanged
    clock.advance_ms(10.1)
    assert srv.step()
    assert futs[4].done()
    assert srv.stats.batch_hist.counts() == {1: 1, 2: 1, 4: 1}
    # padded waste accounting: flushed sizes 2, 4, 1 -> buckets 4, 4, 1
    assert srv.stats.rows_padded == (4 - 2) + 0 + 0
    srv.close()


def test_fifo_order_within_batches(session, rng):
    """Requests are packed strictly in submission order."""
    srv, clock = _manual_server(session)
    xs = [_x(rng, 2) for _ in range(4)]
    futs = [srv.submit(x) for x in xs]
    assert srv.step() and srv.step()
    got = [np.asarray(f.result(0)) for f in futs]
    refs = [np.asarray(padded_predict(session, x, bucket=4)) for x in xs]
    for g, r in zip(got, refs):
        assert g.tobytes() == r.tobytes()
    assert srv.stats.batch_hist.counts() == {4: 2}
    srv.close()


# ---------------------------------------------------------------------------
# Typed errors: queue-full backpressure, deadlines, oversize, closed
# ---------------------------------------------------------------------------

def test_queue_full_raises_typed_error(session, rng):
    srv, clock = _manual_server(session, max_queue=2)
    srv.submit(_x(rng, 1))
    srv.submit(_x(rng, 1))
    with pytest.raises(QueueFullError):
        srv.submit(_x(rng, 1))
    assert srv.stats.n_rejected_full == 1
    srv.close()


def test_deadline_exceeded_typed_error(session, rng):
    srv, clock = _manual_server(session)
    doomed = srv.submit(_x(rng, 1), deadline_ms=5.0)
    healthy = srv.submit(_x(rng, 1))
    clock.advance_ms(6.0)
    # past its deadline the request fails instead of executing late
    assert not srv.step()       # only 'healthy' left; max_wait not reached
    with pytest.raises(DeadlineExceededError):
        doomed.result(0)
    clock.advance_ms(5.0)
    assert srv.step()
    assert np.asarray(healthy.result(0)).shape[0] == 1
    assert srv.stats.n_deadline_expired == 1
    srv.close()


def test_oversize_and_malformed_requests_rejected(session, rng):
    srv, clock = _manual_server(session)
    with pytest.raises(ValueError, match="rows"):
        srv.submit(_x(rng, 5))              # > max_batch
    with pytest.raises(ValueError, match="rank"):
        srv.submit(jnp.zeros((3, 16, 16), jnp.float32))
    srv.close()


# ---------------------------------------------------------------------------
# Graceful drain
# ---------------------------------------------------------------------------

def test_graceful_drain_completes_inflight_rejects_new(session, rng):
    srv, clock = _manual_server(session)
    futs = [srv.submit(_x(rng, 1)) for _ in range(3)]
    srv.close(drain=True)                  # manual pump drains everything
    assert all(f.done() for f in futs)
    assert [np.asarray(f.result(0)).shape[0] for f in futs] == [1, 1, 1]
    with pytest.raises(ServerClosedError):
        srv.submit(_x(rng, 1))
    assert srv.closed


def test_close_without_drain_fails_pending(session, rng):
    srv, clock = _manual_server(session)
    fut = srv.submit(_x(rng, 1))
    srv.close(drain=False)
    with pytest.raises(ServerClosedError):
        fut.result(0)


def test_deadline_honored_without_policy_wakeup_hint(session, rng):
    """Deadlines are the *server's* promise: a custom policy that never
    becomes ready and gives no next_event hint must not leave a
    deadlined request blocked forever."""
    from repro.engine import BatchPolicy

    class Stubborn(BatchPolicy):
        max_batch = 4

        def ready(self, pending, now):
            return False

        def take(self, pending, cap):
            return 1

    srv = AsyncServer(session, Stubborn())
    fut = srv.submit(_x(rng, 1), deadline_ms=30.0)
    with pytest.raises(DeadlineExceededError):
        fut.result(timeout=30)
    srv.close(drain=False)


def test_drain_with_worker_thread(session, rng):
    """The async (real-thread) path: drain completes queued work."""
    policy = DynamicBatchPolicy(max_batch=4, max_wait_ms=1.0)
    srv = AsyncServer(session, policy, max_queue=32)
    futs = [srv.submit(_x(rng, 1)) for _ in range(6)]
    srv.close(drain=True, timeout=60)
    assert all(np.asarray(f.result(0)).shape[0] == 1 for f in futs)
    with pytest.raises(ServerClosedError):
        srv.submit(_x(rng, 1))


def test_cancelled_future_skipped_not_fatal(session, rng):
    """A client-cancelled request must neither kill the scheduling loop
    nor poison the results of co-batched neighbors."""
    srv, clock = _manual_server(session)
    doomed = srv.submit(_x(rng, 1))
    healthy = srv.submit(_x(rng, 1))
    assert doomed.cancel()                   # queued futures are cancelable
    clock.advance_ms(10.1)
    assert srv.step()
    assert np.asarray(healthy.result(0)).shape[0] == 1
    assert srv.stats.n_completed == 1
    # cancelled + deadline-expired: silently dropped, not double-failed
    gone = srv.submit(_x(rng, 1), deadline_ms=1.0)
    assert gone.cancel()
    clock.advance_ms(2.0)
    assert not srv.step()
    assert srv.stats.n_deadline_expired == 0
    srv.close()


def test_frozen_cap_flushes_full_bucket_immediately(session, tmp_path,
                                                    rng):
    """On a frozen session whose largest bucket is smaller than the
    policy's max_batch, a prefix that fills the executable cap flushes at
    once instead of idling on the max_wait timer."""
    from repro.engine import InferenceSession

    session.save(tmp_path / "art", include_source=False)
    frozen = InferenceSession.load(tmp_path / "art")
    assert frozen.frozen and max(frozen.batch_sizes) == 4
    policy = DynamicBatchPolicy(max_batch=8, max_wait_ms=1000.0)
    srv, clock = _manual_server(frozen, policy=policy)
    futs = [srv.submit(_x(rng, 1)) for _ in range(4)]
    assert srv.step()                        # no clock advance needed
    assert all(f.done() for f in futs)
    assert srv.stats.batch_hist.counts() == {4: 1}
    srv.close()


# ---------------------------------------------------------------------------
# Regression: concurrent specialize() must compile once
# ---------------------------------------------------------------------------

def test_concurrent_specialize_compiles_once(monkeypatch):
    """Two threads racing on the same unseen batch size plan+compile it
    exactly once (the session lock); the loser waits and reuses."""
    import repro.engine.session as session_mod

    g, shapes = _mini_net()
    sess = compile_session(g, shapes)

    calls = []
    in_run = threading.Event()
    real_run = type(sess.pipeline).run

    def slow_run(self, *a, **kw):
        calls.append(threading.get_ident())
        in_run.set()
        # widen the race window: the second thread submits while the
        # first is still planning
        threading.Event().wait(0.1)
        return real_run(self, *a, **kw)

    monkeypatch.setattr(type(sess.pipeline), "run", slow_run)
    results = []

    def worker():
        results.append(sess.specialize(2))

    t1 = threading.Thread(target=worker)
    t1.start()
    assert in_run.wait(10)
    t2 = threading.Thread(target=worker)
    t2.start()
    t1.join()
    t2.join()
    assert len(calls) == 1, "double-compiled the same batch size"
    assert results[0] is results[1]
    assert sess.batch_sizes == [1, 2]


# ---------------------------------------------------------------------------
# Bucket selection helpers
# ---------------------------------------------------------------------------

def test_nearest_bucket_picks_smallest_fit():
    assert nearest_bucket(3, [1, 4, 8]) == 4
    assert nearest_bucket(4, [1, 4, 8]) == 4
    assert nearest_bucket(5, [1, 4, 8]) == 8
    assert nearest_bucket(9, [1, 4, 8]) is None


def test_padded_predict_matches_direct_at_bucket(session, rng):
    x = _x(rng, 2)
    y = np.asarray(padded_predict(session, x, bucket=4))
    direct = np.asarray(session.specialize(4).predict(
        jnp.concatenate([x, jnp.zeros((2, 3, 16, 16), jnp.float32)])))[:2]
    assert y.tobytes() == direct.tobytes()
    with pytest.raises(ValueError, match="bucket"):
        padded_predict(session, _x(rng, 3), bucket=2)


# ---------------------------------------------------------------------------
# Snapshot atomicity: stats()/health() under real concurrent load
# ---------------------------------------------------------------------------

def test_stats_snapshots_consistent_under_threads(session, rng):
    """Hammer the server from several submitter threads while sampler
    threads read ``stats``/``health()`` concurrently: every snapshot must
    be internally consistent (no torn reads).  With no deadlines, faults,
    or cancels, every health snapshot satisfies

        submitted == completed + failed + shed + queue + in-flight

    and the stats copy's per-batch lists (appended in the same locked
    section) always agree in length."""
    n_threads, per_thread = 4, 20
    xs = [_x(rng, 1) for _ in range(4)]
    srv = AsyncServer(session,
                      DynamicBatchPolicy(max_batch=4, max_wait_ms=2.0),
                      workers=2, max_queue=256)
    futures, errors = [], []
    flock = threading.Lock()
    done = threading.Event()

    def submitter(i):
        for j in range(per_thread):
            f = srv.submit(xs[(i + j) % len(xs)])
            with flock:
                futures.append(f)

    def sampler():
        while not done.is_set():
            h = srv.health()
            c = h["counters"]
            lhs = c["n_submitted"]
            rhs = (c["n_completed"] + c["n_failed"] + c["n_shed"]
                   + c["n_cancelled"] + c["n_deadline_expired"]
                   + h["queue_depth"] + h["inflight_requests"])
            if lhs != rhs:
                errors.append(f"torn health snapshot: {lhs} != {rhs} ({c})")
            s = srv.stats
            if s.latency.count != s.n_completed:
                errors.append("torn stats copy: "
                              f"{s.latency.count} latencies vs "
                              f"{s.n_completed} completed")
            if s.batch_hist.n != s.n_batches:
                errors.append("torn stats copy: "
                              f"{s.batch_hist.n} batch_hist entries vs "
                              f"{s.n_batches} batches")
            if sum(s.worker_batches.values()) != s.batch_hist.n:
                errors.append("torn stats copy: worker_batches "
                              f"{s.worker_batches} vs "
                              f"{s.batch_hist.n} batches")

    threads = ([threading.Thread(target=submitter, args=(i,))
                for i in range(n_threads)]
               + [threading.Thread(target=sampler) for _ in range(2)])
    try:
        for t in threads:
            t.start()
        for t in threads[:n_threads]:
            t.join(timeout=60)
        for f in futures:
            f.result(timeout=60)
    finally:
        done.set()
        for t in threads[n_threads:]:
            t.join(timeout=10)
        srv.close()
    assert not errors, errors[:5]

    # quiescent: everything submitted was completed, nothing left over
    s = srv.stats
    assert s.n_submitted == n_threads * per_thread
    assert s.n_completed == s.n_submitted
    assert s.n_failed == s.n_shed == s.n_cancelled == 0
    assert s.batch_hist.rows == s.n_submitted      # 1 row per request
    assert s.arrival_hist.n == s.n_submitted
    h = srv.health()
    assert h["queue_depth"] == 0 and h["inflight_requests"] == 0


# ---------------------------------------------------------------------------
# Typed oversize rejection + bounded telemetry + priority packing
# ---------------------------------------------------------------------------

def test_oversize_reject_is_typed(session, rng):
    """A request larger than the packable maximum fails at submit() with
    RequestTooLargeError — a ServingError that still subclasses
    ValueError for pre-typed callers — and is counted, never queued."""
    from repro.engine import RequestTooLargeError, ServingError

    srv, clock = _manual_server(session)
    with pytest.raises(RequestTooLargeError):
        srv.submit(_x(rng, 5))
    assert issubclass(RequestTooLargeError, ServingError)
    assert issubclass(RequestTooLargeError, ValueError)
    st = srv.stats
    assert st.n_rejected_too_large == 1
    assert st.n_submitted == 0 and len(srv) == 0
    srv.close()


def test_oversize_reject_on_frozen_session(session, tmp_path, rng):
    """Frozen sessions clamp the cap to their largest specialized bucket:
    a request over it must reject at submit, not error late in a worker."""
    from repro.engine import InferenceSession, RequestTooLargeError

    session.save(tmp_path / "art_oversize", include_source=False)
    frozen = InferenceSession.load(tmp_path / "art_oversize")
    policy = DynamicBatchPolicy(max_batch=16, max_wait_ms=10.0)
    srv, clock = _manual_server(frozen, policy=policy)
    ok = srv.submit(_x(rng, 4))             # == largest bucket: fine
    with pytest.raises(RequestTooLargeError, match="rows"):
        srv.submit(_x(rng, 5))              # > largest bucket
    assert srv.step()
    assert np.asarray(ok.result(0)).shape[0] == 4
    srv.close()


def test_arrival_histogram_and_queue_depth_recorded(session, rng):
    srv, clock = _manual_server(session)
    for rows in (1, 1, 2, 3, 1):
        srv.submit(_x(rng, rows))
        clock.advance_ms(10.1)
        while srv.step():
            pass
    st = srv.stats
    assert st.arrival_hist.counts() == {1: 3, 2: 1, 3: 1}
    assert st.arrival_hist.rows == 8
    assert st.queue_depth_peak >= 1
    # the driver also feeds the session's own recorder (what
    # save(buckets="auto") solves from)
    assert session.traffic.n >= 5
    srv.close()


def test_edf_priority_packing_order(session, rng):
    """order='edf' packs by (deadline, priority class, arrival): a late-
    submitted interactive request with a tight deadline executes in the
    first flush while earlier deadline-free batch work waits — and every
    result still bit-matches the sequential fixed-bucket reference."""
    policy = DynamicBatchPolicy(max_batch=4, max_wait_ms=10.0,
                                fixed_bucket=4, order="edf")
    srv, clock = _manual_server(session, policy=policy)
    xs = [_x(rng, 2) for _ in range(4)]
    f_batch = [srv.submit(xs[0], priority="batch"),
               srv.submit(xs[1], priority="batch")]
    f_urgent = srv.submit(xs[2], deadline_ms=15.0, priority="interactive")
    f_std = srv.submit(xs[3], priority="standard")
    assert srv.step()                    # 4+ rows pending -> flush
    # EDF order: the deadlined request first, then deadline-free work by
    # priority rank — so the late-submitted urgent + standard pair jumped
    # the two earlier batch-class requests
    assert f_urgent.done() and f_std.done()
    assert not f_batch[0].done() and not f_batch[1].done()
    assert srv.step()                    # remaining 4 batch-class rows
    assert f_batch[0].done() and f_batch[1].done()
    refs = [np.asarray(padded_predict(session, x, bucket=4)) for x in xs]
    for f, r in zip(f_batch + [f_urgent, f_std], refs):
        assert np.asarray(f.result(0)).tobytes() == r.tobytes(), \
            "EDF reordering changed numerics"
    st = srv.stats
    assert st.latency_by_class["interactive"].count == 1
    assert st.latency_by_class["batch"].count == 2
    assert st.latency_by_class["standard"].count == 1
    srv.close()


def test_unknown_priority_rejected(session, rng):
    srv, clock = _manual_server(session)
    with pytest.raises(ValueError, match="priority"):
        srv.submit(_x(rng, 1), priority="platinum")
    assert srv.stats.n_submitted == 0
    srv.close()


def test_fifo_default_unchanged_by_priority_field(session, rng):
    """Without order='edf', priorities are recorded but never reorder."""
    srv, clock = _manual_server(session)
    f_batch = srv.submit(_x(rng, 2), priority="batch")
    f_inter = srv.submit(_x(rng, 2), priority="interactive")
    assert srv.step()
    assert f_batch.done() and f_inter.done()   # one FIFO batch of 4 rows
    assert srv.stats.batch_hist.counts() == {4: 1}
    srv.close()


# ---------------------------------------------------------------------------
# O(1)-memory telemetry under sustained load (the unbounded-lists bugfix)
# ---------------------------------------------------------------------------

class _FakeSession:
    """Executes instantly (no compilation): enough session surface for
    the driver, so the stress test can push thousands of requests."""

    def __init__(self, buckets=(1, 2, 4)):
        from repro.engine.telemetry import SizeHistogram

        self._buckets = sorted(buckets)
        self.traffic = SizeHistogram()

    @property
    def input_spec(self):
        return {"x": (1, 4)}

    @property
    def batch_sizes(self):
        return list(self._buckets)

    @property
    def frozen(self):
        return True

    def specialize(self, batch):
        class _M:
            devices = 1

            @staticmethod
            def predict(x):
                return x * 2.0
        return _M


def test_stats_memory_bounded_under_sustained_load(rng):
    """The pre-telemetry ServingStats kept every latency and batch size
    in unbounded lists; the rebuilt stats must hold O(1) state no matter
    how many requests flow through."""
    sess = _FakeSession()
    srv, clock = _manual_server(sess)
    sizes = [1, 2, 1, 3, 1, 4, 2, 1]

    def pump(n):
        for i in range(n):
            srv.submit(jnp.zeros((sizes[i % len(sizes)], 4), jnp.float32))
            clock.advance_ms(10.1)
            while srv.step():
                pass

    pump(500)
    st = srv.stats
    mid = (st.latency.state_size(), st.arrival_hist.state_size(),
           st.batch_hist.state_size(),
           st.latency_by_class["standard"].state_size())
    assert st.n_completed == 500
    pump(1500)
    st = srv.stats
    assert st.n_completed == 2000
    end = (st.latency.state_size(), st.arrival_hist.state_size(),
           st.batch_hist.state_size(),
           st.latency_by_class["standard"].state_size())
    assert end == mid, f"telemetry state grew under load: {mid} -> {end}"
    # the old unbounded fields are gone for good
    assert not hasattr(st, "latencies_s")
    assert not hasattr(st, "batch_rows")
    # and the summaries still answer
    assert st.latency.count == 2000
    assert np.isfinite(st.percentile_ms(99))
    assert st.arrival_hist.n == 2000
    srv.close()
