"""Jit'd public wrappers around the kernels.

Two execution paths per op, same template parameters:

* ``*_pallas`` — the Pallas TPU kernel: compiled on TPU, run by the Pallas
  interpreter on backends without a Pallas lowering (``interpret=None``);
* ``*_jnp``    — the identical loop nest expressed as strided slices + einsum,
  compiled by XLA for whatever backend runs it; the engine's default path
  (``use_pallas=False``).

Both consume the NCHW[x]c / KCRS[x]c[y]k tensors the planner produces.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.epilogue import EpilogueSpec, IDENTITY
from repro.core.layout import kernel_to_kcrs_ck, to_nchwc, from_nchwc
from repro.core.schedule import ConvSchedule
from repro.kernels.conv2d_nchwc import conv2d_nchwc_pallas
from repro.kernels.matmul_blocked import MatmulSchedule, matmul_padded


def _pad_hw(pad) -> tuple:
    """Normalize an int-or-(ph, pw) padding spec."""
    return (pad, pad) if isinstance(pad, int) else tuple(pad)


def pad_blocked(x_blocked: jnp.ndarray, pad) -> jnp.ndarray:
    ph, pw = _pad_hw(pad)
    if ph == 0 and pw == 0:
        return x_blocked
    return jnp.pad(x_blocked, ((0, 0), (0, 0), (ph, ph), (pw, pw), (0, 0)))


# ---------------------------------------------------------------------------
# Template variants: lowerings of the same blocked direct conv
# (ConvSchedule.variant — see core/schedule.py).  Each accumulator function
# maps padded-input + blocked-weight to the fp32 accumulator in the
# dot-natural (n, oh, ow, ko, oc) order — the einsum's M dims (n, h, w) stay
# adjacent to its N dims (k, o), so XLA emits the GEMM with no per-tap
# transpose; one transpose back to the blocked NCHW[x]c order happens after
# the last tap (1.3-2.3x on ResNet bodies).
# ---------------------------------------------------------------------------

def _acc_per_tap(xp, w_blocked, stride, oh, ow):
    """Unrolled tap loop, one (M=hw, K=ic, N=oc) micro-GEMM per tap; the
    accumulator materializes between the kh*kw partial sums."""
    n, ci, hp, wp, ic_bn = xp.shape
    ko, _, kh, kw, _, oc_bn = w_blocked.shape
    acc = jnp.zeros((n, oh, ow, ko, oc_bn), dtype=jnp.float32)
    for dh in range(kh):
        for dw in range(kw):
            patch = xp[:, :, dh:dh + oh * stride:stride,
                       dw:dw + ow * stride:stride, :]
            acc = acc + jnp.einsum(
                "nchwi,kcio->nhwko", patch.astype(jnp.float32),
                w_blocked[:, :, dh, dw].astype(jnp.float32),
                preferred_element_type=jnp.float32)
    return acc


def _acc_tap_stack(xp, w_blocked, stride, oh, ow):
    """All kh*kw taps stacked into one tensor, the full kh*kw*ic_bn
    reduction done as a single contraction.  Duplicates the input kh*kw
    times but grows the micro-GEMM's K dim from ic_bn to kh*kw*ic_bn —
    decisive for sub-sublane contractions (e.g. the RGB stem, ic_bn=3,
    ~40x over per_tap here)."""
    n, ci, hp, wp, ic_bn = xp.shape
    ko, ci_w, kh, kw, ic_w, oc_bn = w_blocked.shape
    taps = jnp.stack(
        [xp[:, :, dh:dh + oh * stride:stride,
            dw:dw + ow * stride:stride, :]
         for dh in range(kh) for dw in range(kw)],
        axis=2)                                      # (n, ci, t, oh, ow, ic)
    wt = w_blocked.reshape(ko, ci_w, kh * kw, ic_w, oc_bn)
    return jnp.einsum(
        "ncthwi,kctio->nhwko", taps.astype(jnp.float32),
        wt.astype(jnp.float32), preferred_element_type=jnp.float32)


def _acc_scan(xp, w_blocked, stride, oh, ow):
    """lax.scan over the taps with the fp32 accumulator as the carry: the
    partial sum stays loop-resident (XLA aliases the carry in place) instead
    of round-tripping through memory between kh*kw unrolled taps."""
    n, ci, hp, wp, ic_bn = xp.shape
    ko, ci_w, kh, kw, ic_w, oc_bn = w_blocked.shape
    # (t, ko, ci, ic, oc) so the scan streams one tap's weights per step
    wt = w_blocked.reshape(ko, ci_w, kh * kw, ic_w, oc_bn) \
                  .transpose(2, 0, 1, 3, 4).astype(jnp.float32)
    span_h = (oh - 1) * stride + 1
    span_w = (ow - 1) * stride + 1
    taps = jnp.arange(kh * kw, dtype=jnp.int32)

    def body(acc, tap):
        dh, dw = tap // kw, tap % kw
        window = jax.lax.dynamic_slice(
            xp, (0, 0, dh, dw, 0), (n, ci, span_h, span_w, ic_bn))
        patch = window[:, :, ::stride, ::stride, :]
        acc = acc + jnp.einsum(
            "nchwi,kcio->nhwko", patch.astype(jnp.float32), wt[tap],
            preferred_element_type=jnp.float32)
        return acc, None

    acc0 = jnp.zeros((n, oh, ow, ko, oc_bn), dtype=jnp.float32)
    acc, _ = jax.lax.scan(body, acc0, taps)
    return acc


def prelay_patch_gemm_weight(w_blocked: jnp.ndarray) -> jnp.ndarray:
    """Bind-time pre-layout for the patch_gemm lowering: materialize the
    KCRS[x]c[y]k weight in panel-major ``(Ci, kh, kw, ic_bn, Ko, oc_bn)``
    order — the transpose ``_acc_patch_gemm`` otherwise pays at run time.
    The kernel's remaining reshape to the ``(kh*kw*cin, cout)`` GEMM operand
    is a free bitcast on the contiguous pre-laid array (§3.2: parameter
    layout is invariant, so transform it during compilation)."""
    return jnp.asarray(w_blocked).transpose(1, 2, 3, 4, 0, 5)


def _patch_gemm(xp, w_panel_major, stride, oh, ow):
    """Shared tail of both patch_gemm entries: ``w_panel_major`` is the
    weight already in (Ci, kh, kw, ic_bn, Ko, oc_bn) order."""
    n, ci, hp, wp, ic_bn = xp.shape
    ci_w, kh, kw, ic_w, ko, oc_bn = w_panel_major.shape
    taps = jnp.stack(
        [xp[:, :, dh:dh + oh * stride:stride,
            dw:dw + ow * stride:stride, :]
         for dh in range(kh) for dw in range(kw)],
        axis=-2)                                     # (n, ci, oh, ow, t, ic)
    panel = taps.transpose(0, 2, 3, 1, 4, 5).reshape(
        n * oh * ow, ci * kh * kw * ic_bn)
    wmat = w_panel_major.reshape(ci_w * kh * kw * ic_w, ko * oc_bn)
    out = jnp.dot(panel.astype(jnp.float32), wmat.astype(jnp.float32),
                  preferred_element_type=jnp.float32)
    return out.reshape(n, oh, ow, ko, oc_bn)


def _acc_patch_gemm(xp, w_blocked, stride, oh, ow):
    """im2col lowering: strided patch panels flattened to a single plain
    (n*oh*ow, kh*kw*cin) @ (kh*kw*cin, cout) GEMM.  Pays an explicit panel
    transpose but hands the backend one contiguous full-reduction matmul —
    the measured winner on small-spatial deep layers (e.g. 7x7x512).  The
    weight-side transpose disappears when the engine pre-lays the panels at
    bind time (``prelay_patch_gemm_weight``)."""
    return _patch_gemm(xp, w_blocked.transpose(1, 2, 3, 4, 0, 5),
                       stride, oh, ow)


_ACC_FNS = {"per_tap": _acc_per_tap, "tap_stack": _acc_tap_stack,
            "scan": _acc_scan, "patch_gemm": _acc_patch_gemm}


def _acc_xla_conv(x_blocked, w_blocked, stride, pad):
    """The compiler's own convolution, padding passed to the conv, on a
    view of the unpadded blocked input: NCHW when ``ic_bn`` is 1, NHWC
    otherwise (exact for one channel chunk; several are merged), and an
    HWIO view of the KCRS[x]c[y]k weight.  No tap tensor is materialized:
    for a lane-sparse input (the RGB stem's 3 channels pad to 128 TPU
    lanes) every strided tap copy of the other variants moves ~40x its
    real bytes.  Its operations carry the ``xla_conv`` scope in their
    ``op_name``."""
    n, ci, h, w, ic_bn = x_blocked.shape
    ko, ci_w, kh, kw, ic_w, oc_bn = w_blocked.shape
    ph, pw = _pad_hw(pad)
    with jax.named_scope("xla_conv"):
        if ic_bn == 1:
            x, lhs = x_blocked.reshape(n, ci, h, w), "NCHW"
        else:
            x = x_blocked.transpose(0, 2, 3, 1, 4).reshape(
                n, h, w, ci * ic_bn)
            lhs = "NHWC"
        wt = w_blocked.transpose(2, 3, 1, 4, 0, 5).reshape(
            kh, kw, ci_w * ic_w, ko * oc_bn)
        out = jax.lax.conv_general_dilated(
            x.astype(jnp.float32), wt.astype(jnp.float32),
            (stride, stride), ((ph, ph), (pw, pw)),
            dimension_numbers=(lhs, "HWIO", "NHWC"),
            preferred_element_type=jnp.float32)
        oh, ow = out.shape[1:3]
        return out.reshape(n, oh, ow, ko, oc_bn)


# ---------------------------------------------------------------------------
# int8 instantiations (ConvSchedule.dtype == "int8", weight-only W8).
#
# The weight operand arrives as int8 *integer codes* (quantized per output
# channel at bind time — core/quantize.py); activations stay fp32.  The
# loop nests are identical to the fp32 variants: the integer codes are
# upcast at the MAC (XLA:CPU has no s8 GEMM kernels — on a VNNI/s8-dot
# backend this upcast is where the native s8 contraction slots in), and
# the per-channel dequantize scale is applied by the shared epilogue's
# ``scale`` operand, exactly like a folded BN scale.  What int8 buys on
# this backend is the 4x denser weight payload and traffic, not FLOPs.
# ---------------------------------------------------------------------------

def _require_int8_weight(w, variant: str):
    if w.dtype != jnp.int8:
        raise TypeError(
            f"dtype='int8' {variant} template expects an int8 weight "
            f"operand (quantized codes), got {w.dtype}")


def _acc_tap_stack_int8(xp, w_blocked, stride, oh, ow):
    """tap_stack over int8 weight codes: one contraction with the full
    kh*kw*ic_bn reduction, weight upcast at the MAC."""
    _require_int8_weight(w_blocked, "tap_stack")
    return _acc_tap_stack(xp, w_blocked, stride, oh, ow)


def _acc_patch_gemm_int8(xp, w_blocked, stride, oh, ow):
    """im2col lowering over int8 weight codes: the (kh*kw*cin, cout) GEMM
    operand is 4x denser in memory, upcast at the MAC."""
    _require_int8_weight(w_blocked, "patch_gemm")
    return _acc_patch_gemm(xp, w_blocked, stride, oh, ow)


_ACC_FNS_INT8 = {"tap_stack": _acc_tap_stack_int8,
                 "patch_gemm": _acc_patch_gemm_int8}


def apply_epilogue_fp32(acc: jnp.ndarray, scale, shift, residual,
                        spec: EpilogueSpec) -> jnp.ndarray:
    """The composable epilogue on the blocked fp32 accumulator
    ``(n, Ko, oh, ow, oc_bn)`` — shared by every template variant, so a
    new epilogue stage is written once and every lowering gets it.  Order is
    fixed (see ``core.epilogue``): affine -> residual -> ReLU -> pool."""
    if scale is not None:   # (Ko, oc_bn) per-channel affine
        acc = acc * scale.astype(jnp.float32)[None, :, None, None, :]
    if shift is not None:
        acc = acc + shift.astype(jnp.float32)[None, :, None, None, :]
    if residual is not None:
        acc = acc + residual.astype(jnp.float32)
    if spec.relu:
        acc = jnp.maximum(acc, 0.0)
    if spec.pool is not None:
        acc = spec.pool.apply(acc)
    return acc


def _conv2d_block_core(x_blocked, w_blocked, scale, shift, residual, out_buf,
                       stride: int, pad, spec: EpilogueSpec,
                       variant: str = "auto",
                       w_prelaid: bool = False,
                       dtype: str = "fp32") -> jnp.ndarray:
    """Blocked direct conv + composable fused epilogue as XLA ops — the
    template's jnp instantiation, dispatched over the lowering ``variant``
    (one of ``core.schedule.VARIANTS``, or ``"auto"`` for the static
    heuristic: tap_stack below sublane ic_bn, per_tap otherwise).

    out[n,ko,oh,ow,oc] = sum_{ci,kh,kw,ic} x[n,ci,oh*s+kh,ow*s+kw,ic]
                                           * w[ko,ci,kh,kw,ic,oc]

    then (fused, still in the fp32 accumulator — XLA folds these into the
    final accumulation pass instead of separate full-tensor round trips):
    ``out = pool(relu(out * scale + shift + residual))``, optionally stored
    at a channel offset into the shared concat buffer ``out_buf``.

    ``w_prelaid`` marks a weight that arrived panel-major from
    ``prelay_patch_gemm_weight`` (legal only for variant ``patch_gemm``).

    ``dtype="int8"`` selects the weight-quantized instantiation of the
    variant (tap_stack / patch_gemm only): ``w_blocked`` holds int8
    quantization codes and the caller passes the per-channel dequantize
    scale through ``scale`` — the shared epilogue applies it like a BN
    scale.
    """
    if variant in ("auto", None):
        variant = "tap_stack" if x_blocked.shape[-1] < 8 else "per_tap"
    if w_prelaid:
        assert variant == "patch_gemm", \
            f"pre-laid panel weight requires patch_gemm, got {variant!r}"
    if dtype == "int8":
        if variant not in _ACC_FNS_INT8:
            raise ValueError(
                f"dtype 'int8' has no {variant!r} instantiation; int8 "
                f"variants are {tuple(_ACC_FNS_INT8)}")
        if scale is None:
            raise ValueError(
                "dtype 'int8' requires the per-channel dequantize scale "
                "in the epilogue's scale operand")
        if w_prelaid:
            _require_int8_weight(w_blocked, variant)
    oc_bn = w_blocked.shape[-1]
    if variant == "xla_conv":
        acc = _acc_xla_conv(x_blocked, w_blocked, stride, pad)
    else:
        xp = pad_blocked(x_blocked, pad)
        kh, kw = w_blocked.shape[1:3] if w_prelaid else w_blocked.shape[2:4]
        oh = (xp.shape[2] - kh) // stride + 1
        ow = (xp.shape[3] - kw) // stride + 1
        if w_prelaid:
            acc = _patch_gemm(xp, w_blocked, stride, oh, ow)
        else:
            acc_fns = _ACC_FNS_INT8 if dtype == "int8" else _ACC_FNS
            acc = acc_fns[variant](xp, w_blocked, stride, oh, ow)
    acc = acc.transpose(0, 3, 1, 2, 4)               # -> (n, ko, oh, ow, oc)
    acc = apply_epilogue_fp32(acc, scale, shift, residual, spec)
    out = acc.astype(x_blocked.dtype)
    if spec.writes_concat:
        # §3.1 concat-aware placement: store this block's channels at its
        # offset in the shared buffer (under jit XLA updates in place)
        assert out_buf is not None, "concat-write epilogue needs out_buf"
        assert spec.concat_offset % oc_bn == 0, (spec.concat_offset, oc_bn)
        out = jax.lax.dynamic_update_slice(
            out_buf, out.astype(out_buf.dtype),
            (0, spec.concat_offset // oc_bn, 0, 0, 0))
    return out


@functools.partial(jax.jit,
                   static_argnames=("stride", "pad", "variant", "w_prelaid",
                                    "dtype"))
def conv2d_nchwc_jnp(x_blocked: jnp.ndarray, w_blocked: jnp.ndarray,
                     stride: int = 1, pad=0,
                     variant: str = "auto",
                     w_prelaid: bool = False,
                     dtype: str = "fp32") -> jnp.ndarray:
    """Plain blocked conv (no epilogue) — see ``_conv2d_block_core``.
    (``dtype="int8"`` is rejected here: the quantized template needs the
    dequantize scale, which only the epilogue entry carries.)"""
    return _conv2d_block_core(x_blocked, w_blocked, None, None, None, None,
                              stride, pad, IDENTITY, variant, w_prelaid,
                              dtype)


@functools.partial(jax.jit,
                   static_argnames=("stride", "pad", "relu", "variant",
                                    "epilogue", "w_prelaid", "dtype"))
def conv2d_block_jnp(x_blocked: jnp.ndarray, w_blocked: jnp.ndarray,
                     scale: jnp.ndarray | None = None,
                     shift: jnp.ndarray | None = None,
                     residual: jnp.ndarray | None = None,
                     out_buf: jnp.ndarray | None = None,
                     stride: int = 1, pad=0,
                     relu: bool = False, variant: str = "auto",
                     epilogue: EpilogueSpec | None = None,
                     w_prelaid: bool = False,
                     dtype: str = "fp32") -> jnp.ndarray:
    """Fused CONV + composable epilogue block — see ``_conv2d_block_core``.
    ``relu`` is kept as a shorthand for the PR-1 call sites; it merges into
    ``epilogue`` (the full spec: ReLU, fused pooling, concat-offset store)."""
    spec = (epilogue or IDENTITY).with_relu(relu)
    return _conv2d_block_core(x_blocked, w_blocked, scale, shift, residual,
                              out_buf, stride, pad, spec, variant, w_prelaid,
                              dtype)


def _schedule_variant(schedule: ConvSchedule | None) -> str:
    return schedule.variant if schedule is not None else "auto"


def _schedule_dtype(schedule: ConvSchedule | None) -> str:
    return getattr(schedule, "dtype", "fp32") if schedule is not None \
        else "fp32"


def conv2d_blocked(x_blocked: jnp.ndarray, w_blocked: jnp.ndarray, *,
                   stride: int = 1, pad=0,
                   schedule: ConvSchedule | None = None,
                   use_pallas: bool = False,
                   interpret: bool | None = None,
                   w_prelaid: bool = False) -> jnp.ndarray:
    """Planner-facing entry point on blocked tensors.  On the jnp path the
    schedule's ``variant`` picks the lowering; the Pallas kernel has one
    loop nest (its accumulator is VMEM-resident by construction) and ignores
    the variant axis."""
    if use_pallas:
        assert schedule is not None
        assert not w_prelaid, "Pallas kernel consumes KCRS[x]c[y]k weights"
        assert _schedule_dtype(schedule) == "fp32", \
            "the Pallas kernel has no int8 instantiation yet"
        xp = pad_blocked(x_blocked, pad)
        return conv2d_nchwc_pallas(xp, w_blocked, stride=stride,
                                   schedule=schedule, interpret=interpret)
    return conv2d_nchwc_jnp(x_blocked, w_blocked, stride=stride, pad=pad,
                            variant=_schedule_variant(schedule),
                            w_prelaid=w_prelaid,
                            dtype=_schedule_dtype(schedule))


def conv2d_block_blocked(x_blocked: jnp.ndarray, w_blocked: jnp.ndarray,
                         scale: jnp.ndarray | None = None,
                         shift: jnp.ndarray | None = None,
                         residual: jnp.ndarray | None = None,
                         out_buf: jnp.ndarray | None = None, *,
                         stride: int = 1, pad=0, relu: bool = False,
                         epilogue: EpilogueSpec | None = None,
                         schedule: ConvSchedule | None = None,
                         use_pallas: bool = False,
                         interpret: bool | None = None,
                         w_prelaid: bool = False) -> jnp.ndarray:
    """Fused conv_block entry on blocked tensors (engine-facing).  ``scale``
    and ``shift`` are per-channel vectors pre-blocked to ``(Ko, oc_bn)``;
    ``residual`` arrives in the conv's own NCHW[oc_bn]c output layout, and
    ``out_buf`` (concat fusion) is the shared blocked buffer the epilogue
    spec's channel-offset store writes into."""
    spec = (epilogue or IDENTITY).with_relu(relu)
    if use_pallas:
        assert schedule is not None
        assert not w_prelaid, "Pallas kernel consumes KCRS[x]c[y]k weights"
        assert _schedule_dtype(schedule) == "fp32", \
            "the Pallas kernel has no int8 instantiation yet"
        xp = pad_blocked(x_blocked, pad)
        return conv2d_nchwc_pallas(xp, w_blocked, scale, shift, residual,
                                   out_buf, stride=stride, schedule=schedule,
                                   epilogue=spec, interpret=interpret)
    return conv2d_block_jnp(x_blocked, w_blocked, scale, shift, residual,
                            out_buf, stride=stride, pad=pad,
                            epilogue=spec,
                            variant=_schedule_variant(schedule),
                            w_prelaid=w_prelaid,
                            dtype=_schedule_dtype(schedule))


def conv2d(x_nchw: jnp.ndarray, w_kcrs: jnp.ndarray, *, stride: int = 1,
           pad=0, schedule: ConvSchedule,
           use_pallas: bool = False,
           interpret: bool | None = None) -> jnp.ndarray:
    """Convenience NCHW->NCHW entry: blocks inputs, runs the template,
    unblocks.  The engine never uses this (it keeps tensors blocked); tests
    and the quickstart do."""
    xb = to_nchwc(x_nchw, schedule.ic_bn)
    wb = kernel_to_kcrs_ck(w_kcrs, schedule.ic_bn, schedule.oc_bn)
    ob = conv2d_blocked(xb, wb, stride=stride, pad=pad, schedule=schedule,
                        use_pallas=use_pallas, interpret=interpret)
    return from_nchwc(ob)


# ---------------------------------------------------------------------------
# LM-side fused matmul tails: the dense->softmax and attention-score
# instantiations of the blocked-GEMM template.  Both route through the one
# shared epilogue body (core.epilogue.apply_matmul_epilogue) applied while
# the logits block is accumulator-resident, so the probabilities never
# round-trip through HBM as raw logits.
# ---------------------------------------------------------------------------

def dense_softmax(x: jnp.ndarray, w: jnp.ndarray, *,
                  schedule: MatmulSchedule | None = None,
                  interpret: bool | None = None) -> jnp.ndarray:
    """``softmax(x @ w, axis=-1)`` with the row-softmax fused into the GEMM
    epilogue — the LM-head / router instantiation.  Arbitrary (M, K, N):
    padding is handled by ``matmul_padded`` (padded vocab columns are
    masked out of the exp-sum via ``n_valid``)."""
    return matmul_padded(x, w, schedule=schedule or MatmulSchedule(),
                         epilogue=EpilogueSpec(softmax=True),
                         interpret=interpret)


def attention_probs(q: jnp.ndarray, k: jnp.ndarray, *,
                    causal: bool = True, scale: float | None = None,
                    schedule: MatmulSchedule | None = None,
                    interpret: bool | None = None) -> jnp.ndarray:
    """One head's attention probabilities ``softmax(mask(q @ k.T * scale))``
    with the whole ``scale -> mask -> softmax`` tail fused into the GEMM
    epilogue.  ``q``/``k`` are (S, D); vmap over batch/head axes upstream.
    ``scale`` defaults to ``1/sqrt(D)``."""
    s, d = q.shape
    spec = EpilogueSpec(scale=scale if scale is not None else d ** -0.5,
                        mask="causal" if causal else "none", softmax=True)
    return matmul_padded(q, k.T, schedule=schedule or MatmulSchedule(),
                         epilogue=spec, interpret=interpret)
