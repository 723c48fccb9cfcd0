"""Roofline cost model for schedules, transforms, and collectives.

NeoCPU's local search *measures* wall time on the target.  The default
scoring signal here is an analytical roofline model of the planner's
target chip (``core.peaks.PLAN_TARGET``), built from that chip's row of
the peak table (``core.peaks``); ``local_search.measured_runner`` can
replace it with wall-clock measurement.

The model is intentionally coarse — it only has to *rank* schedules the way a
real measurement would.  It is a prediction, not a measurement.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Tuple

from repro.core.layout import Layout, transform_bytes
from repro.core.peaks import PLAN_TARGET, VMEM_BUDGET, peaks
from repro.core.schedule import ConvSchedule, ConvWorkload

_TARGET = peaks(PLAN_TARGET)
PEAK_FLOPS_BF16 = _TARGET.bf16_flops
# model assumption, not a published peak: fp32 operands run the MXU at
# half its bf16 rate
PEAK_FLOPS_FP32 = PEAK_FLOPS_BF16 / 2
HBM_BW = _TARGET.hbm_bytes_per_s
ICI_BW_PER_LINK = _TARGET.ici_bytes_per_s_per_link
MXU_DIM = 128
SUBLANE = 8


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


@dataclasses.dataclass(frozen=True)
class CostBreakdown:
    compute_s: float
    memory_s: float
    collective_s: float = 0.0

    @property
    def total_s(self) -> float:
        # compute and memory overlap on TPU (async copies); collectives may
        # overlap too but we charge them serially as the conservative bound.
        return max(self.compute_s, self.memory_s) + self.collective_s

    @property
    def bottleneck(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)


# ---------------------------------------------------------------------------
# Conv schedule cost (feeds the local search)
# ---------------------------------------------------------------------------

def mxu_utilization(m: int, k: int, n: int) -> float:
    """Fraction of MXU work that is useful for an (m,k)@(k,n) micro-GEMM.
    Dims pad to (sublane, lane) = (8, 128) tiles; K pads to 8."""
    um = m / _round_up(m, SUBLANE)
    uk = k / _round_up(k, SUBLANE)
    un = n / _round_up(n, MXU_DIM)
    return um * uk * un


def _tile_bytes(*dims: int, dtype_bytes: int = 4) -> int:
    """VMEM bytes of one block: the minor dim pads to the 128 lanes, the
    second-minor to the 8 sublanes."""
    *lead, sub, lane = dims
    n = _round_up(sub, SUBLANE) * _round_up(lane, MXU_DIM) * dtype_bytes
    for d in lead:
        n *= d
    return n


def pool_lanes(oc_bn: int) -> int:
    """Lane width of one chunk of the Pallas kernel's pooling scratch: a
    strided read on the TPU takes a minor dim of at most 128 lanes, so a
    wider ``oc_bn`` plane is kept as ``oc_bn // lanes`` chunks."""
    return max(d for d in range(1, min(oc_bn, MXU_DIM) + 1) if oc_bn % d == 0)


def conv_vmem_bytes(wl: ConvWorkload, s: ConvSchedule) -> int:
    """Tiled VMEM footprint of one grid step of the Pallas kernel
    (``kernels/conv2d_nchwc.py``): the (H_pad, stride, W_pad/stride, ic_bn)
    phase-split input slab (single-buffered when it is the only channel
    chunk, as the kernel stages it), the (kh, kw, ic_bn, oc_bn) weight block, the fp32
    (oh_bn, OW, oc_bn) output block, the residual / concat-buffer blocks,
    and the fused-pooling scratch plane.  Pipelined blocks count twice
    (double buffering); every block pads to the (8, 128) tile."""
    oh, ow = wl.out_hw
    h_pad = wl.height + 2 * wl.pad
    w_pad = wl.width + 2 * wl.pw
    cin = wl.in_channels // wl.groups
    b = wl.dtype_bytes
    inp = _tile_bytes(h_pad, wl.stride, -(-w_pad // wl.stride), s.ic_bn,
                      dtype_bytes=b) \
        * (1 if cin == s.ic_bn else 2)
    ker = 2 * _tile_bytes(wl.kh, wl.kw, s.ic_bn, s.oc_bn, dtype_bytes=b)
    vecs = 2 * 2 * _tile_bytes(1, s.oc_bn)          # scale + shift rows
    spec = wl.epilogue_spec()
    if spec.pool is not None:
        out_h, out_w = spec.pool.out_hw(oh, ow)
        outp = 2 * _tile_bytes(out_h, out_w, s.oc_bn)
        lanes = pool_lanes(s.oc_bn)
        scratch = (s.oc_bn // lanes) * _tile_bytes(
            *spec.pool.padded_hw(oh, ow), lanes)
        rows = oh
    else:
        outp = 2 * _tile_bytes(s.oh_bn, ow, s.oc_bn)
        scratch = 0
        rows = s.oh_bn
    res = 2 * _tile_bytes(rows, ow, s.oc_bn, dtype_bytes=b) \
        if wl.fused_residual else 0
    buf = outp if wl.concat_total else 0
    return inp + ker + vecs + outp + scratch + res + buf


# per-step working set past which conv_schedule_cost charges a spill
SPILL_BYTES = 16 * 1024 * 1024


def _working_set_bytes(wl: ConvWorkload, s: ConvSchedule) -> int:
    """Untiled bytes one step of the blocked loop nest touches: the
    (H_pad, W_pad, ic_bn) input slab, the weight block and the fp32
    (oh_bn, OW, oc_bn) output rows."""
    _, ow = wl.out_hw
    b = wl.dtype_bytes
    inp = (wl.height + 2 * wl.pad) * (wl.width + 2 * wl.pw) * s.ic_bn * b
    ker = wl.kh * wl.kw * s.ic_bn * s.oc_bn * (1 if s.dtype == "int8" else b)
    return inp + ker + s.oh_bn * ow * s.oc_bn * 4


def fits_vmem(wl: ConvWorkload, s: ConvSchedule) -> bool:
    """Whether the Pallas kernel can stage schedule ``s`` within the VMEM
    budget it asks the compiler for; a local search for the kernel
    (``local_search(..., pallas=True)``) never ranks a schedule that does
    not."""
    return conv_vmem_bytes(wl, s) <= VMEM_BUDGET


def conv_schedule_cost(wl: ConvWorkload, s: ConvSchedule,
                       dtype_peak: float = PEAK_FLOPS_FP32) -> CostBreakdown:
    """Roofline estimate for one CONV executed under schedule ``s``.

    The lowering ``variant`` changes both terms:

    * compute — the stacked variants (tap_stack, patch_gemm) contract the
      full ``kh*kw*ic_bn`` reduction in one GEMM, so their K dim pads much
      better than per-tap micro-GEMMs when ``ic_bn`` is sub-sublane;
      patch_gemm additionally flattens M to ``n*oh*ow`` (no ow_bn padding).
    * memory — per_tap round-trips the fp32 accumulator between taps;
      tap_stack/patch_gemm materialize the input ``kh*kw`` times (write +
      GEMM read); scan carries the accumulator in the loop but copies a
      strided window per tap.

    The Pallas kernel's schedules (``candidate_schedules(pallas=True)``)
    are per_tap with ``ow_bn`` = OW, so compute is priced at the kernel's
    own (OW × ic_bn) @ (ic_bn × oc_bn) tap GEMM.  Its accumulator stays in
    VMEM, so per_tap's round-trip term overstates its memory time by the
    same bytes for every schedule of a workload.  No device time has
    checked this ranking yet (ROADMAP 3.1).

    The workload's fused-epilogue flags add the §3.1 epilogue traffic here,
    so the local search ranks schedules *with* their epilogue included
    (fused: only the residual read survives — everything else happens while
    the accumulator is still register/VMEM-resident).
    """
    oh, ow = wl.out_hw
    cin = wl.in_channels // wl.groups
    khkw = wl.kh * wl.kw
    variant = s.resolved_variant()
    if variant == "xla_conv":
        return _xla_conv_cost(wl, s, dtype_peak)
    if variant in ("tap_stack", "patch_gemm"):
        # one contraction over the stacked kh*kw*ic reduction
        util = mxu_utilization(
            wl.batch * oh * ow if variant == "patch_gemm" else s.ow_bn,
            khkw * s.ic_bn, s.oc_bn)
    else:
        util = mxu_utilization(s.ow_bn, s.ic_bn, s.oc_bn)
    # unrolling the (kh, kw) loops trims scalar-loop overhead; model it as a
    # small utilization bonus that decays for large kernels (paper: "in some
    # scenarios unrolling may increase the performance").  scan keeps the
    # tap loop rolled, so it forfeits the bonus.
    if s.unroll_ker and variant != "scan":
        util = min(1.0, util * (1.0 + 0.05 / max(1, khkw / 9)))
    compute_s = wl.flops / (dtype_peak * max(util, 1e-3))

    b = wl.dtype_bytes
    # HBM traffic under the kernel's loop nest (n, oc_chunk, oh_blk, ic_chunk):
    # the input slab is re-read once per output-channel chunk; weights are
    # re-read once per batch element; the output is written once (+1 read per
    # extra input-channel pass for accumulation).
    oc_chunks = wl.out_channels // s.oc_bn
    ic_chunks = cin // s.ic_bn
    input_once = wl.batch * cin * wl.height * wl.width * b
    input_bytes = input_once * oc_chunks
    # dtype="int8" stores the weight as 1-byte quantization codes — 4x
    # denser weight traffic (the accumulator stays 4 bytes either way:
    # int32 and fp32 are the same width, so acc_bytes below is unchanged);
    # the per-channel dequant multiply rides the fused epilogue pass for
    # free, like a BN scale.
    wb = 1 if s.dtype == "int8" else b
    weight_bytes = (wl.out_channels * cin * wl.kh * wl.kw * wb) * wl.batch
    # stored output: the fused pooling reduction shrinks the final store to
    # the pooled tiling (the conv-resolution tensor never reaches HBM); the
    # extra input-channel accumulation passes still run at conv resolution
    poh, pow_ = wl.pooled_out_hw
    output_bytes = (wl.batch * wl.out_channels * poh * pow_ * b
                    + wl.batch * wl.out_channels * oh * ow * b
                    * max(0, ic_chunks - 1))
    # variant-specific traffic (fp32 accumulator is 4 bytes/elem); one tap's
    # strided patch holds oh*ow spatial positions — input_once/stride^2 on
    # downsample convs, not the full-resolution slab
    acc_bytes = wl.batch * wl.out_channels * oh * ow * 4
    tap_once = wl.batch * cin * oh * ow * b
    if variant == "per_tap":
        # the accumulator materializes between taps: one read + one write
        # per extra tap
        variant_bytes = 2 * max(0, khkw - 1) * acc_bytes
    elif variant == "scan":
        # accumulator is loop-carried (aliased in place); each tap copies a
        # strided window of the input slab out of the padded tensor
        variant_bytes = 2 * khkw * tap_once
    elif variant == "tap_stack":
        # the stacked tap tensor is written once and read once by the GEMM
        variant_bytes = 2 * khkw * tap_once
    else:  # patch_gemm
        # stacked taps + the explicit panel transpose pass
        variant_bytes = 3 * khkw * tap_once
    epi_bytes = epilogue_bytes(
        (wl.batch, wl.out_channels, oh, ow), bn=wl.fused_bn,
        relu=wl.fused_relu, residual=wl.fused_residual, fused=True,
        dtype_bytes=b)
    memory_s = (input_bytes + weight_bytes + output_bytes + variant_bytes
                + epi_bytes) / HBM_BW
    # schedules whose working set spills pay a heavy penalty (they would
    # thrash HBM); the Pallas kernel's hard VMEM limit is fits_vmem
    if _working_set_bytes(wl, s) > SPILL_BYTES:
        memory_s *= 8.0
    return CostBreakdown(compute_s=compute_s, memory_s=memory_s)


def _xla_conv_cost(wl: ConvWorkload, s: ConvSchedule,
                   dtype_peak: float) -> CostBreakdown:
    """The compiler's own conv (variant ``xla_conv``): tiled by the
    compiler, it contracts one tap's input channels at a time over every
    output channel; input, weight and output cross HBM once, and no tap
    tensor exists.  Splitting the output into ``oc_bn`` chunks is the
    transpose every variant pays, fused into the epilogue."""
    oh, ow = wl.out_hw
    cin = wl.in_channels // wl.groups
    b = wl.dtype_bytes
    util = mxu_utilization(wl.batch * oh * ow, cin, wl.out_channels)
    compute_s = wl.flops / (dtype_peak * max(util, 1e-3))
    poh, pow_ = wl.pooled_out_hw
    input_bytes = wl.batch * cin * wl.height * wl.width * b
    weight_bytes = wl.out_channels * cin * wl.kh * wl.kw * b
    output_bytes = wl.batch * wl.out_channels * poh * pow_ * b
    # the NCHW (ic_bn 1) and NHWC (ic_bn = cin) views are free; any other
    # blocking merges the input's chunks in one more pass
    merge_bytes = 2 * input_bytes if 1 < s.ic_bn < cin else 0
    epi_bytes = epilogue_bytes(
        (wl.batch, wl.out_channels, oh, ow), bn=wl.fused_bn,
        relu=wl.fused_relu, residual=wl.fused_residual, fused=True,
        dtype_bytes=b)
    return CostBreakdown(
        compute_s=compute_s,
        memory_s=(input_bytes + weight_bytes + output_bytes + merge_bytes
                  + epi_bytes) / HBM_BW)


# ---------------------------------------------------------------------------
# Epilogue cost (§3.1 operation fusion)
# ---------------------------------------------------------------------------

def epilogue_bytes(nchw_shape: Tuple[int, ...], *, bn: bool = False,
                   relu: bool = False, residual: bool = False,
                   pool_stride: int = 0, concat: bool = False,
                   scale: bool = False, mask: bool = False,
                   softmax: bool = False,
                   fused: bool = False, dtype_bytes: int = 4) -> int:
    """HBM traffic for a conv's elementwise/shallow epilogue.

    Unfused graphs dispatch BN / residual-add / ReLU as separate nodes, each
    round-tripping the full conv output through memory (read + write; the
    add also reads the residual operand); a standalone pooling node reads
    the conv output and writes the (stride²-smaller) pooled tensor, and a
    standalone concat copies this conv's slice into the concat buffer (read
    + write).  A fused ``conv_block`` applies the affine/ReLU while the
    output block is still register/VMEM-resident, pools the fp32 tile
    before the store, and writes straight into the concat buffer — the only
    epilogue traffic left is the single residual read.  (The *smaller
    pooled store itself* is credited in ``conv_schedule_cost``'s output
    term, not here.)

    The matmul-tail stages price the same way (``nchw_shape`` is then the
    logical (M, N) logits shape, trailing dims 1): an unfused ``scale`` or
    ``mask`` is one elementwise pass (read + write), and an unfused row
    ``softmax`` is three passes over the logits (max-reduce read, exp read
    + write, normalize read + write ≈ 3x tensor — the reductions' scalar
    outputs are noise).  Fused, all three run on the accumulator-resident
    block and add zero HBM traffic, which is exactly why the fused
    attention tail wins: the (S, S) logits tensor never materializes.

    Caveat on the fused concat credit: it models the in-place offset store
    (what XLA emits for the jnp path under jit, and what a TPU backend gets
    from ``input_output_aliases``).  The interpret-mode Pallas kernel
    instead copies non-owned buffer chunks through its grid, so on that
    path the realized win is smaller than predicted — compare measured
    columns, not predicted ones, for concat-fusion claims.
    """
    elems = 1
    for d in nchw_shape:
        elems *= int(d)
    tensor = elems * dtype_bytes
    if fused:
        return tensor if residual else 0
    total = 0
    if bn:
        total += 2 * tensor
    if residual:
        total += 3 * tensor
    if relu:
        total += 2 * tensor
    if pool_stride:
        total += tensor + tensor // (pool_stride * pool_stride)
    if concat:
        total += 2 * tensor
    if scale:
        total += 2 * tensor
    if mask:
        total += 2 * tensor
    if softmax:
        total += 3 * tensor
    return total


def epilogue_cost_s(nchw_shape: Tuple[int, ...], *, bn: bool = False,
                    relu: bool = False, residual: bool = False,
                    pool_stride: int = 0, concat: bool = False,
                    scale: bool = False, mask: bool = False,
                    softmax: bool = False,
                    fused: bool = False, dtype_bytes: int = 4) -> float:
    return epilogue_bytes(nchw_shape, bn=bn, relu=relu, residual=residual,
                          pool_stride=pool_stride, concat=concat,
                          scale=scale, mask=mask, softmax=softmax,
                          fused=fused, dtype_bytes=dtype_bytes) / HBM_BW


# ---------------------------------------------------------------------------
# Layout-transform cost (graph-edge cost in the global search)
# ---------------------------------------------------------------------------

def transform_cost_s(nchw_shape: Tuple[int, ...], src: Layout, dst: Layout,
                     dtype_bytes: int = 4) -> float:
    return transform_bytes(nchw_shape, src, dst, dtype_bytes) / HBM_BW


# ---------------------------------------------------------------------------
# Collective costs (sharding-as-layout tier; also used by the roofline report)
# ---------------------------------------------------------------------------

def all_gather_s(bytes_per_device: int, axis_size: int,
                 links: int = 1) -> float:
    """Ring all-gather: each device sends (axis-1)/axis of the gathered array."""
    if axis_size <= 1:
        return 0.0
    return bytes_per_device * (axis_size - 1) / (ICI_BW_PER_LINK * links)


def reduce_scatter_s(bytes_per_device: int, axis_size: int,
                     links: int = 1) -> float:
    if axis_size <= 1:
        return 0.0
    return bytes_per_device * (axis_size - 1) / axis_size / (
        ICI_BW_PER_LINK * links)


def all_reduce_s(bytes_per_device: int, axis_size: int, links: int = 1) -> float:
    # ring all-reduce = reduce-scatter + all-gather
    return (reduce_scatter_s(bytes_per_device, axis_size, links)
            + all_gather_s(bytes_per_device // max(1, axis_size), axis_size,
                           links))


def all_to_all_s(bytes_per_device: int, axis_size: int, links: int = 1) -> float:
    if axis_size <= 1:
        return 0.0
    return bytes_per_device * (axis_size - 1) / axis_size / (
        ICI_BW_PER_LINK * links)
