"""A whole run of the CNN family on the CPU at a small image, without the
harness's look for a chip: sound, it is correct and the control (the
reference at "high" precision, judged in the program's place) is not; with the served answers broken underneath
the timed path, it is not correct."""
import json
import time
import types
from pathlib import Path

import jax.numpy as jnp
import pytest

from families import cnn

BENCH = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def cache(tmp_path_factory):
    return tmp_path_factory.mktemp("bench-cache")


def _run(cache, mix, seed, control=False):
    cfg = json.loads((BENCH / "configs" / "resnet50-224.json").read_text())
    ctx = types.SimpleNamespace(
        config=cfg, mix=mix, chips=1, seed=seed, seconds=1.0, trace=False,
        t0=time.perf_counter(), log=lambda m: None, cache=cache,
        trace_dir=cache / "trace", image=32, control=control)
    return cnn.run(ctx)


OPEN = {"loop": "open", "rate_per_s": 40.0, "rows": {1: 1}}
CLOSED = {"loop": "closed", "clients": 4, "rows": {1: 2, 3: 1}}


def test_a_sound_run_is_correct_and_the_control_is_not(cache):
    res = _run(cache, OPEN, 2**31 + 77, control=True)
    checks = {n: (v, lim) for n, v, lim in res["checks"]}
    assert res["correct"] and res["failed"] == 0
    assert res["attempted"] == 40
    gap, limit = checks["logit_gap"]
    assert gap <= limit
    control = res["control"]
    assert not control["correct"]
    assert dict((n, v) for n, v, _ in control["checks"])["logit_gap"] > limit
    assert control["swap_gap"] > limit
    assert res["searches"] == 0 and res["window_compiles"] == 0
    assert {"setup_s", "p50_ms", "p95_ms"} <= set(res["metrics"])


def _rotate_rows(y):
    return jnp.roll(y, 1, axis=0)         # each row gets another's answer


def _alter_one_logit(y):
    big = jnp.abs(y).max(axis=1)
    return y.at[:, 0].add(1e-4 * big)


def _half_batch(y):
    half = (y.shape[0] + 1) // 2           # the rest get the first half's
    return jnp.concatenate([y[:half], y[:y.shape[0] - half]])


@pytest.mark.parametrize("fault", [_rotate_rows, _alter_one_logit,
                                   _half_batch])
def test_answers_broken_where_they_are_produced_are_not_correct(
        cache, monkeypatch, fault):
    from repro.engine.executor import CompiledModel

    predict = CompiledModel.predict
    monkeypatch.setattr(CompiledModel, "predict",
                        lambda self, x: fault(predict(self, x)))
    res = _run(cache, CLOSED, 5)
    assert res["failed"] == 0
    assert not res["correct"]
