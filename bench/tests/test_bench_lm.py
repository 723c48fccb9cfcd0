"""The LM cell's benchmark files: its mix, its harness entries, the costs
the readers divide by, the readers and the span reduction, and the plain
reference with its float8 control."""
import itertools
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lm_cost
import lm_spans
import reference_lm
import run
import traffic
from families import lm

BENCH = Path(__file__).resolve().parents[1]
CELL = "starcoder2-3b.completion-1c"
CFG = json.loads((BENCH / "configs" / "starcoder2-3b.json").read_text())
MIX = traffic.load(BENCH / "traffic" / "completion-1c.json")


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 5, -3, 3_000_000_019])
def test_bag_drawn_without_replacement_alike_for_every_seed(seed):
    assert MIX["loop"] == "closed" and MIX["clients"] == 1
    assert MIX["rows"] == {1: 1}
    bag = sorted(tuple(p) for p in MIX["pairs"])
    assert len(bag) == 16 and sum(n for _, n in bag) == 16 * 40
    assert [p for p, _ in bag][0] == 512 and max(bag)[0] == 4096
    drawn = list(itertools.islice(lm.pairs(MIX, seed), 48))
    for k in range(3):
        assert sorted(drawn[16 * k:16 * (k + 1)]) == bag
    again = list(itertools.islice(lm.pairs(MIX, seed), 48))
    assert drawn == again
    picked = lm.checked(MIX, seed)
    assert len(picked) in (lm.CHECK_REQUESTS, lm.CHECK_REQUESTS + 1)
    assert any(drawn[i][0] == 4096 for i in picked)
    assert all(0 <= i < 16 for i in picked)
    p = lm.prompt(CFG["vocab_size"], seed, 3, 700)
    assert p.shape == (1, 700) and p.dtype == np.int32
    assert 0 <= p.min() and p.max() < CFG["vocab_size"]
    np.testing.assert_array_equal(
        p, lm.prompt(CFG["vocab_size"], seed, 3, 700))


@pytest.mark.parametrize("seed", [0, 2**31 + 5])
def test_p50_is_taken_over_whole_bags(seed):
    # every run's median is over the same multiset of pairs, whatever the
    # seed's order: the latency a pair takes stands in for a served one
    ms = {tuple(p): 10.0 * p[0] + n for p, n in
          zip(MIX["pairs"], range(len(MIX["pairs"])))}
    drawn = list(itertools.islice(lm.pairs(MIX, seed), 40))
    sent = [float(i) for i in range(40)]
    done = [t + ms[p] / 1e3 for t, p in zip(sent, drawn)]
    got = lm.whole_bags(sent, done, list(range(40)), 16)
    assert len(got) == 32
    assert sorted(got) == pytest.approx(sorted(2 * list(ms.values())))
    # a failed request is left out, and so is a bag the wait cut short
    got = lm.whole_bags(sent[:20], done[:20], [i for i in range(20)
                                               if i != 3], 16)
    assert len(got) == 15 and ms[drawn[3]] not in got


def test_resolve_finds_the_family_and_the_four_readers():
    cell, cfg, mix, family, metrics = run.resolve(run.ROOT, CELL, True)
    assert cell["chips"] == 1 and cfg["family"] == "lm"
    assert Path(family.__file__) == BENCH / "families" / "lm.py"
    assert mix["pairs"] == MIX["pairs"]
    assert sorted(m["name"] for m in metrics) == [
        "catchup_share", "decode_hbm_share", "decode_step_ms",
        "prefill_mfu"]
    for m in metrics:
        assert Path(m["read"].__code__.co_filename) == \
            BENCH / "metrics" / f"{m['name']}.py"
    _, _, _, _, e2e = run.resolve(run.ROOT, CELL, False)
    assert sorted(m["name"] for m in e2e) == ["p50_ms", "setup_s"]


def test_the_program_matches_the_published_configuration():
    from repro.configs import ARCHS

    assert lm.program_config(CFG) is ARCHS["starcoder2-3b"]
    with pytest.raises(SystemExit, match="rope_theta"):
        lm.program_config(dict(CFG, rope_theta=1e5))
    small = lm.program_config(lm.rehearsal_config(CFG), rehearsal=True)
    assert (small.d_model, small.local_window, small.proj_bias) == \
        (64, 64, True)


def test_costs_from_the_published_widths():
    # every parameter: 3,030,371,328, two bytes each
    assert lm_cost.param_count(CFG) == 3_030_371_328
    assert lm_cost.param_bytes(CFG) == 6_060_742_656
    # the layers' matrices: 30 x (2 x 3072^2 + 2 x 3072 x 256
    # + 2 x 3072 x 12288)
    assert lm_cost.matrix_params(CFG) == 2_878_341_120
    # 30 layers x (key + value) x 2 heads x 128 x 2 bytes
    assert lm_cost.kv_bytes_per_position(CFG) == 30_720
    assert lm_cost.step_bytes(CFG, 100) == 6_060_742_656 + 3_072_000
    # past the window a step reads 4096 positions, no more
    assert lm_cost.step_bytes(CFG, 4160) == lm_cost.step_bytes(CFG, 4096) \
        == 6_060_742_656 + 30_720 * 4096
    assert lm_cost.attended_pairs(512, 4096) == 131_328
    assert lm_cost.attended_pairs(5000, 4096) == 8_390_656 + 904 * 4096
    # 512 tokens: 2 x 2,878,341,120 x 512, then 4 x 24 x 128 x 30 layers
    # x 131,328 pairs, then the head 2 x 3072 x 49152
    assert lm_cost.prefill_flops(CFG, 512) == (
        2_947_421_306_880 + 48_412_753_920 + 301_989_888)
    work = lm_cost.request_work(CFG, 512, 3)
    assert work == {"prefill_flops": lm_cost.prefill_flops(CFG, 512),
                    "decode_bytes": 3 * 6_060_742_656
                    + 30_720 * (513 + 514 + 515)}
    assert lm_cost.request_work(CFG, 0, 2) == {
        "prefill_flops": 0, "decode_bytes": 2 * 6_060_742_656 + 30_720 * 3}


def _read(name, readings):
    return run.reader(BENCH / "metrics", name)(readings)


def test_readers_on_hand_made_readings():
    lmr = {"decode_steps": 400, "decode_device_s": 3.6,
           "decode_bytes": 400 * 6.0e9, "prefill_device_s": 0.2,
           "prefill_flops": 1.97e13}
    r = {"traced": {"lm": lmr}, "peak_flops_per_s": 197e12,
         "peak_hbm_bytes_per_s": 819e9,
         "window": {"catchup_steps": 300, "decode_steps": 500}}
    assert _read("decode_step_ms", r) == pytest.approx(9.0)
    assert _read("decode_hbm_share", r) == pytest.approx(
        100 * 2.4e12 / (3.6 * 819e9))
    assert _read("prefill_mfu", r) == pytest.approx(50.0)
    assert _read("catchup_share", r) == pytest.approx(60.0)
    empty = {"traced": None, "window": {"decode_steps": 0}}
    for name in ("decode_step_ms", "decode_hbm_share", "prefill_mfu",
                 "catchup_share"):
        assert _read(name, empty) is None


def _hand_made_trace():
    """One thread's two requests inside the window and one after it, the
    two programs on the device, and one op in each scope."""
    ms = 1_000_000

    def span(name, a, b, **st):
        return (name, a * ms, b * ms, st)

    worker = [span("lm.prefill", 1, 2, bucket=512, prompt_len=563),
              span("lm.catchup", 2, 3, steps=51),
              span("lm.decode", 3, 10, steps=60),
              span("lm.prefill", 11, 12, bucket=0, prompt_len=5),
              span("lm.catchup", 12, 13, steps=5),
              span("lm.decode", 13, 20, steps=1),
              span("lm.prefill", 95, 96, bucket=512, prompt_len=512),
              span("lm.catchup", 96, 96, steps=0),
              span("lm.decode", 96, 110, steps=9)]
    main = [span("bench.traced_window", 0, 100)]
    mods = [span("jit_lm_prefill(3)", 2, 4), span("jit_lm_decode(4)", 4, 13),
            span("jit_lm_decode(4)", 98, 102), span("jit_argmax", 13, 14)]
    ops = [("%fusion.1 = bf16[1,512,3072]{}", 2 * ms, 3 * ms,
            {"scope": "lm.prefill"}),
           ("%fusion.2 = bf16[1,1,3072]{}", 5 * ms, 12 * ms,
            {"scope": "lm.decode"})]
    return {"/host:CPU": {"0:python": worker, "1:python": main},
            "/device:TPU:0": {"XLA Modules": mods, "XLA Ops": ops}}


def test_span_reduction_on_a_hand_made_trace():
    planes = _hand_made_trace()
    lo, hi = lm_spans.window(planes)
    got = lm_spans.reduce(planes, lo, hi)
    assert got["requests"] == [
        {"bucket": 512, "prompt_len": 563, "catchup_steps": 51,
         "decode_steps": 60, "steps": 111},
        {"bucket": 0, "prompt_len": 5, "catchup_steps": 5,
         "decode_steps": 1, "steps": 6}]
    assert (got["prefill_tokens"], got["decode_steps"]) == (512, 117)
    assert got["prefill_device_s"] == pytest.approx(0.002)
    # 9 ms, then 2 of the last execution's 4 ms inside the window
    assert got["decode_device_s"] == pytest.approx(0.011)
    assert (got["prefill_n"], got["decode_n"]) == (1.0, 1.5)
    assert got["scope_device_s"] == pytest.approx(
        {"lm.prefill": 0.001, "lm.decode": 0.007})
    traced = lm_spans.traced(planes, CFG)
    assert traced["window_s"] == pytest.approx(0.1)
    assert traced["lm"]["prefill_flops"] == lm_cost.prefill_flops(CFG, 512)
    assert traced["lm"]["decode_bytes"] == (
        lm_cost.decode_bytes(CFG, range(513, 624))
        + lm_cost.decode_bytes(CFG, range(1, 7)))
    assert lm_spans.reduce({"/host:CPU": {"0:python": []}}, lo, hi) is None


TINY = lm.rehearsal_config(CFG)


@pytest.fixture(scope="module")
def tiny_params():
    return lm.make_params(TINY, jax.random.key(3))


def test_make_params_draws_every_leaf_from_the_seed(tiny_params):
    layers = tiny_params["layers"]
    assert layers["attn"]["wq"].shape == (2, 64, 64)
    assert layers["mlp"]["wd"].shape == (2, 128, 64)
    for path, leaf in jax.tree_util.tree_flatten_with_path(tiny_params)[0]:
        assert leaf.dtype == jnp.bfloat16
        if path[-1].key in ("w", "b", "bq", "bk", "bv", "bo", "bu", "bd"):
            assert np.all(np.asarray(leaf, np.float32) != 0), path
    gains = np.asarray(layers["ln1"]["w"], np.float32)
    assert 0.5 <= gains.min() and gains.max() <= 1.5
    again = lm.make_params(TINY, jax.random.key(3))
    for a, b in zip(jax.tree.leaves(tiny_params), jax.tree.leaves(again)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_round_e4m3_is_the_float8_grid():
    x = np.concatenate([np.linspace(-440, 440, 2001),
                        np.geomspace(1e-4, 3.0, 2001),
                        [0.0, 2.0 ** -9, 1.5 * 2.0 ** -9, 0.0146484375,
                         1.9375, 2.0 ** -7 * 1.0625]]).astype(np.float32)
    got = np.asarray(reference_lm.round_e4m3(jnp.asarray(x)))
    want = np.asarray(jnp.asarray(x).astype(jnp.float8_e4m3fn)
                      .astype(jnp.float32))
    np.testing.assert_array_equal(got, want)


def test_reference_and_its_control_at_a_tiny_size(tiny_params):
    """The benchmark's reference against the program's own float32
    reference on the same bfloat16 weights, and the float8 control well
    outside the tolerance that holds them together."""
    from repro.models.lm import reference as program_ref

    cfg = lm.program_config(TINY, rehearsal=True)
    rng = np.random.default_rng(0)
    toks = rng.integers(0, TINY["vocab_size"], 100).astype(np.int32)
    at = list(range(60, 100))
    want = np.asarray(program_ref.forward(tiny_params, cfg, toks))[at]
    got = reference_lm.logits(TINY, tiny_params, toks, at)
    ctl = reference_lm.logits(TINY, tiny_params, toks, at, control=True)
    padded = reference_lm.logits(TINY, tiny_params, toks, at, pad_to=700)

    def gap(a, b):
        return float((np.abs(a - b).max(-1) / np.abs(b).max(-1)).max())

    # two float32 forwards, summed in other orders: ~1e-6 of the largest
    assert gap(got, want) < 1e-5
    assert gap(padded, want) < 1e-5
    assert gap(ctl, want) > 1e-2
    judged = lm.judge([ctl.astype(np.float32)], [got], 0, 1e-2)
    assert judged[0] is False and judged[1][0][0] == "logit_gap"
