"""Roofline machinery: jaxpr flop counter + HLO collective parser."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.flops import count_costs
from repro.analysis.roofline import (RooflineReport, model_flops,
                                     parse_collective_bytes)
from repro.configs import ARCHS


def test_flops_matmul_exact():
    a = jnp.ones((64, 128))
    b = jnp.ones((128, 32))
    c = count_costs(lambda a, b: a @ b, a, b)
    assert c["flops"] == 2 * 64 * 128 * 32


def test_flops_scan_multiplies_by_length():
    W = jnp.ones((8, 32, 32))
    x = jnp.ones((4, 32))

    def f(W, x):
        return jax.lax.scan(lambda c, w: (c @ w, None), x, W)[0]

    c = count_costs(f, W, x)
    ideal = 2 * 4 * 32 * 32 * 8
    assert abs(c["flops"] - ideal) / ideal < 0.01


def test_flops_grad_roughly_3x_forward():
    W = jnp.ones((64, 64))
    x = jnp.ones((8, 64))
    fwd = count_costs(lambda W: jnp.sum((x @ W) ** 2), W)["flops"]
    bwd = count_costs(jax.grad(lambda W: jnp.sum((x @ W) ** 2)), W)["flops"]
    assert 1.8 * fwd < bwd < 3.5 * fwd


def test_flops_remat_counts_recompute():
    """checkpointed VJP must count MORE flops than the plain VJP (the
    recompute is real work the useful-flops ratio should see)."""
    W1 = jnp.ones((64, 64))
    W2 = jnp.ones((64, 64))

    def f(W1, W2, x):
        h = jnp.tanh(x @ W1)
        return jnp.sum(jnp.tanh(h @ W2))

    x = jnp.ones((8, 64))
    plain = count_costs(jax.grad(f, argnums=(0, 1)), W1, W2, x)["flops"]
    ck = count_costs(jax.grad(
        lambda a, b, x: jax.checkpoint(f)(a, b, x),
        argnums=(0, 1)), W1, W2, x)["flops"]
    assert ck > plain


def test_flops_conv():
    x = jnp.ones((1, 8, 16, 16))
    w = jnp.ones((16, 8, 3, 3))
    c = count_costs(
        lambda x, w: jax.lax.conv_general_dilated(
            x, w, (1, 1), "SAME",
            dimension_numbers=("NCHW", "OIHW", "NCHW")), x, w)
    ideal = 2 * 16 * 16 * 16 * 8 * 9   # out_elems x 2 x cin x k x k
    assert abs(c["flops"] - ideal) / ideal < 0.01


HLO_SAMPLE = """
  %add.clone { ... }
  %all-reduce = f32[64,128]{1,0} all-reduce(%dot.1), replica_groups={}
  %ag = bf16[4,256]{1,0} all-gather(%p0), dimensions={0}
  %rs = f32[2,8]{1,0} reduce-scatter(%x), dimensions={0}
  %cp = f32[16]{0} collective-permute(%y), source_target_pairs={{0,1}}
  %a2a = f32[8,8]{1,0} all-to-all(%z), dimensions={0}
  %ard = f32[64,128]{1,0} all-reduce-done(%ars)
"""


def test_parse_collective_bytes():
    out = parse_collective_bytes(HLO_SAMPLE)
    assert out["all-reduce"] == 64 * 128 * 4
    assert out["all-gather"] == 4 * 256 * 2
    assert out["reduce-scatter"] == 2 * 8 * 4
    assert out["collective-permute"] == 16 * 4
    assert out["all-to-all"] == 8 * 8 * 4
    assert out["total"] == sum(out[k] for k in (
        "all-reduce", "all-gather", "reduce-scatter", "collective-permute",
        "all-to-all"))


def test_roofline_report_terms():
    r = RooflineReport(
        arch="x", shape="train_4k", mesh="16x16", chips=256,
        flops_per_device=197e12, bytes_per_device=819e9,
        collective_bytes_per_device=50e9, collectives={},
        model_flops_total=197e12 * 256)
    assert r.compute_s == pytest.approx(1.0)
    assert r.memory_s == pytest.approx(1.0)
    assert r.collective_s == pytest.approx(1.0)
    assert r.bottleneck in ("compute", "memory")
    assert r.step_time_s == pytest.approx(2.0)
    assert r.roofline_fraction == pytest.approx(0.5)


def test_model_flops_kinds():
    cfg = ARCHS["qwen2-1.5b"]
    n = cfg.active_param_count()
    assert model_flops(cfg, "train", 2, 10) == 6.0 * n * 20
    assert model_flops(cfg, "prefill", 2, 10) == 2.0 * n * 20
    assert model_flops(cfg, "decode", 2, 10) == 2.0 * n * 2


def test_peak_table_is_the_one_source():
    """One peak table keyed by ``device_kind``: the planner's cost model
    and the roofline read the same row, and an unlisted kind raises."""
    from repro.analysis import roofline
    from repro.core import cost
    from repro.core.peaks import PEAKS, PLAN_TARGET, peaks

    v5e = peaks("TPU v5 lite")
    assert (v5e.bf16_flops, v5e.hbm_bytes_per_s) == (197e12, 819e9)
    assert PLAN_TARGET in PEAKS
    target = peaks(PLAN_TARGET)
    assert cost.PEAK_FLOPS_BF16 == roofline.PEAK_FLOPS == target.bf16_flops
    assert cost.HBM_BW == roofline.HBM_BW == target.hbm_bytes_per_s
    assert cost.ICI_BW_PER_LINK == roofline.ICI_BW \
        == target.ici_bytes_per_s_per_link
    with pytest.raises(KeyError, match="no peaks for device kind 'cpu'"):
        peaks("cpu")
