"""Conv template-variant family vs the NCHW oracle.

Every lowering variant (per_tap / tap_stack / scan / patch_gemm / xla_conv)
must agree with ``conv2d_nchw_ref`` within fp32 tolerance across stride,
asymmetric padding, sub-sublane/sublane/super-sublane ic_bn, and with or
without the fused scale/shift/residual/ReLU epilogue — the acceptance
matrix of the variant axis."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:   # the deterministic acceptance grid must run even without hypothesis
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

from repro.core.epilogue import EpilogueSpec, PoolSpec
from repro.core.layout import from_nchwc, kernel_to_kcrs_ck, to_nchwc
from repro.core.schedule import (VARIANTS, ConvSchedule, ConvWorkload,
                                 candidate_schedules)
from repro.kernels.ops import conv2d_block_jnp, conv2d_nchwc_jnp
from repro.kernels.ref import conv2d_nchw_ref
from repro.nn.ops import max_pool


def _epilogue_ref(out, scale, shift, residual_nchw, relu):
    out = np.asarray(out, np.float32)
    if scale is not None:
        out = out * scale[None, :, None, None]
    if shift is not None:
        out = out + shift[None, :, None, None]
    if residual_nchw is not None:
        out = out + residual_nchw
    if relu:
        out = np.maximum(out, 0.0)
    return out


def _run_case(variant, ic_bn, stride, pad, epilogue, hw, seed, oc_bn=8):
    cin = ic_bn * 2 if ic_bn >= 8 else ic_bn      # ic_bn=3 -> cin=3 (stem)
    cout = oc_bn * 2
    kh, kw = 3, 3
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(2, cin, hw, hw)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(cout, cin, kh, kw)).astype(np.float32))
    xb = to_nchwc(x, ic_bn)
    wb = kernel_to_kcrs_ck(w, ic_bn, oc_bn)
    ref = conv2d_nchw_ref(x, w, stride=stride, pad=pad)

    if not epilogue:
        out = from_nchwc(conv2d_nchwc_jnp(xb, wb, stride=stride, pad=pad,
                                          variant=variant))
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-4, atol=1e-4)
        return

    scale = rng.normal(size=cout).astype(np.float32)
    shift = rng.normal(size=cout).astype(np.float32)
    res_nchw = rng.normal(size=ref.shape).astype(np.float32)
    out = conv2d_block_jnp(
        xb, wb,
        jnp.asarray(scale.reshape(cout // oc_bn, oc_bn)),
        jnp.asarray(shift.reshape(cout // oc_bn, oc_bn)),
        to_nchwc(jnp.asarray(res_nchw), oc_bn),
        stride=stride, pad=pad, relu=True, variant=variant)
    want = _epilogue_ref(ref, scale, shift, res_nchw, relu=True)
    np.testing.assert_allclose(np.asarray(from_nchwc(out)), want,
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("variant", VARIANTS)
@pytest.mark.parametrize("ic_bn", [3, 8, 16])
@pytest.mark.parametrize("stride", [1, 2])
@pytest.mark.parametrize("epilogue", [False, True],
                         ids=["plain", "fused-epilogue"])
def test_variant_matrix(variant, ic_bn, stride, epilogue):
    """The full acceptance grid with square padding."""
    _run_case(variant, ic_bn, stride, pad=1, epilogue=epilogue, hw=9, seed=0)


@pytest.mark.parametrize("variant", VARIANTS)
@pytest.mark.parametrize("pad", [(0, 2), (2, 0)], ids=["pad-w", "pad-h"])
def test_variant_asymmetric_pad(variant, pad):
    _run_case(variant, 8, 1, pad=pad, epilogue=True, hw=8, seed=1)


@pytest.mark.parametrize("ic_bn", [3, 1])
def test_rgb_stem_xla_conv(ic_bn):
    """The real RGB stem (7x7/2, pad 3, 3 -> 64) through xla_conv with
    shift, ReLU and the fused 3x3/2 max-pool, at "highest": the NHWC view
    (ic_bn 3) and the NCHW view (ic_bn 1) of the blocked input."""
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(2, 3, 32, 32)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(64, 3, 7, 7)).astype(np.float32))
    shift = rng.normal(size=64).astype(np.float32)
    spec = EpilogueSpec(relu=True, pool=PoolSpec("max", 3, 2, 1))
    with jax.default_matmul_precision("highest"):
        ref = conv2d_nchw_ref(x, w, stride=2, pad=3)
        out = conv2d_block_jnp(
            to_nchwc(x, ic_bn), kernel_to_kcrs_ck(w, ic_bn, 64), None,
            jnp.asarray(shift.reshape(1, 64)), stride=2, pad=3,
            epilogue=spec, variant="xla_conv")
    want = np.asarray(max_pool(
        jnp.maximum(ref + shift[None, :, None, None], 0.0), 3, 2, 1))
    assert out.shape == (2, 1, 8, 8, 64)
    np.testing.assert_allclose(np.asarray(from_nchwc(out)), want,
                               rtol=1e-5, atol=1e-5)


if HAVE_HYPOTHESIS:
    @settings(max_examples=20, deadline=None)
    @given(
        variant=st.sampled_from(VARIANTS),
        ic_bn=st.sampled_from([3, 8, 16]),
        stride=st.sampled_from([1, 2]),
        epilogue=st.booleans(),
        hw=st.integers(7, 12),
        seed=st.integers(0, 2**16),
    )
    def test_variant_hypothesis(variant, ic_bn, stride, epilogue, hw, seed):
        """Property: every variant == oracle on random workloads/params."""
        _run_case(variant, ic_bn, stride, pad=1, epilogue=epilogue, hw=hw,
                  seed=seed)


def test_auto_matches_explicit():
    """'auto' must be exactly the static heuristic's variant."""
    for ic_bn, expect in ((3, "tap_stack"), (8, "per_tap")):
        s = ConvSchedule(ic_bn, 8, 1, 1, False)
        assert s.resolved_variant() == expect
        s.validate(ConvWorkload(batch=1, in_channels=ic_bn, out_channels=8,
                                height=8, width=8, kh=3, kw=3, pad=1))


def test_bad_variant_rejected():
    wl = ConvWorkload(batch=1, in_channels=8, out_channels=8, height=8,
                      width=8, kh=3, kw=3, pad=1)
    with pytest.raises(ValueError):
        ConvSchedule(8, 8, 1, 1, False, "im2col").validate(wl)


def _stem_wl(cin):
    return ConvWorkload(batch=1, in_channels=cin, out_channels=64,
                        height=32, width=32, kh=7, kw=7, stride=2, pad=3,
                        fused_bn=True, fused_relu=True, fused_pool="max",
                        pool_k=3, pool_stride=2, pool_pad=1)


def test_lane_sparse_conv_enumerates_xla_conv_alone():
    """A 3-channel conv's jnp space is xla_conv over every (ic_bn, oc_bn)
    pair; the Pallas kernel's space stays per_tap; a 64-channel conv
    never enumerates xla_conv on either path."""
    rgb = _stem_wl(3)
    jnp_space = candidate_schedules(rgb)
    pallas_space = candidate_schedules(rgb, pallas=True)
    assert {s.variant for s in jnp_space} == {"xla_conv"}
    assert {s.variant for s in pallas_space} == {"per_tap"}
    assert {(s.ic_bn, s.oc_bn) for s in jnp_space} \
        == {(s.ic_bn, s.oc_bn) for s in pallas_space}
    for s in jnp_space:
        s.validate(rgb)
    wide = _stem_wl(64)
    for pallas in (False, True):
        assert "xla_conv" not in {
            s.variant for s in candidate_schedules(wide, pallas=pallas)}


def test_compile_gives_only_the_stem_xla_conv():
    from repro.engine import compile as compile_session

    sess = compile_session("resnet-50", (1, 3, 64, 64))
    schedules = sess.plan_for(1).planned.schedules
    assert len(schedules) == 53
    assert [n for n, s in schedules.items() if s.variant == "xla_conv"] \
        == ["stem_conv"]
