"""The traffic generator: deterministic per seed, on its rate and size
mix, every seed the same work in another order."""
import json
from pathlib import Path

import numpy as np
import pytest

import traffic

MIXES = Path(__file__).resolve().parents[1] / "traffic"


def _mix(**kw):
    return dict({"loop": "open", "rate_per_s": 200.0, "rows": {1: 1}}, **kw)


def test_every_mix_file_loads():
    names = sorted(p.stem for p in MIXES.glob("*.json"))
    assert names
    for n in names:
        traffic.load(MIXES / f"{n}.json")


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 5, 3 * 2**40])
def test_open_loop_is_deterministic_and_on_rate(seed):
    mix = _mix()
    a = traffic.arrivals(mix, seed, 10.0)
    assert a == traffic.arrivals(mix, seed, 10.0)
    assert a != traffic.arrivals(mix, seed + 1, 10.0)
    due = np.array([t for t, _ in a])
    assert len(due) == 2000                      # rate x seconds, every seed
    assert np.all(np.diff(due) >= 0) and due[0] >= 0 and due[-1] < 10.0
    # Poisson: gaps exponential with mean 1/rate (CV of an exponential 1)
    gaps = np.diff(due)
    assert abs(gaps.mean() * 200 - 1) < 0.02
    assert abs(gaps.std() / gaps.mean() - 1) < 0.1
    # and uniform over the window: each second holds about rate requests
    per_s = np.histogram(due, bins=10, range=(0, 10))[0]
    assert per_s.min() > 150 and per_s.max() < 250


def test_sizes_follow_the_multiset_in_another_order():
    mix = traffic.load(MIXES / "bulk-mixed-16c.json")
    assert traffic.mean_rows(mix) == pytest.approx(2.2)
    a = traffic.client_sizes(mix, 1)
    b = traffic.client_sizes(mix, 2)
    first = [next(a[0]) for _ in range(70)]
    other = [next(b[0]) for _ in range(70)]
    # one refill of the 70-size bag: 49 single images, three each of 2..8
    assert sorted(first) == sorted(other) != first
    assert first.count(1) == 49 and all(first.count(k) == 3
                                        for k in range(2, 9))
    again = traffic.client_sizes(mix, 1)
    assert [next(again[0]) for _ in range(70)] == first
    assert len(a) == 16


def test_on_off_arrivals_fall_in_the_on_phases():
    mix = _mix(on_off={"period_s": 1.0, "on_share": 0.2})
    due = np.array([t for t, _ in traffic.arrivals(mix, 3, 10.0)])
    assert len(due) == 2000
    assert np.all(due % 1.0 < 0.2 + 1e-9)
    assert np.histogram(due, bins=10, range=(0, 10))[0].min() > 150


@pytest.mark.parametrize("bad", [
    {"loop": "open", "rows": {"1": 1}},                     # no rate
    {"loop": "closed", "rows": {"1": 1}},                   # no clients
    {"loop": "open", "rate_per_s": 5, "rows": {"9": 1}},    # past bucket 8
    {"loop": "sideways", "rows": {"1": 1}},
])
def test_malformed_mixes_are_refused(tmp_path, bad):
    p = tmp_path / "m.json"
    p.write_text(json.dumps(bad))
    with pytest.raises((ValueError, KeyError)):
        traffic.load(p)


def test_warm_up_covers_every_batch_a_fifo_queue_can_form():
    from families import cnn

    assert cnn.batch_shapes([1], 8) == [(1,) * k for k in range(1, 9)]
    every = cnn.batch_shapes(list(range(1, 9)), 8)
    assert len(every) == 2**8 - 1           # compositions of 1..8
    assert len(set(every)) == len(every)
    assert all(sum(s) <= 8 for s in every)


def test_the_sample_holds_an_answer_from_every_chip():
    from families import cnn

    devices = {i: ("chip3" if i == 17 else "chip0") for i in range(200)}
    ok = list(range(200))
    for seed in range(5):
        pick = cnn.sample(ok, devices, 10, seed, lambda d: d)
        assert 17 in pick and len(pick) in (10, 11)
        assert pick == cnn.sample(ok, devices, 10, seed, lambda d: d)
