"""Pallas TPU direct-convolution kernel in NCHW[x]c layout (NeoCPU Alg. 1).

The paper's AVX-512 template keeps one ZMM register of kernel values resident
and FMA-accumulates it against ``reg_n`` feature-map vectors.  The TPU-native
translation keeps a ``(kh, kw, ic_bn, oc_bn)`` weight block resident in VMEM
and, for every kernel tap, issues an ``(OW × ic_bn) @ (ic_bn × oc_bn)`` MXU
GEMM over one whole output row — ``oc_bn`` maps to the 128-lane N dimension
and ``ic_bn`` is the contraction the paper calls the sub-channel block.  The
row is the M-tile: ``ow_bn`` (reg_n) is a register-blocking axis of the jnp
lowerings only, like the ``variant`` axis.

Grid: ``(N, OC_chunks, OH_blocks, IC_chunks)`` — the input-channel dimension
is innermost so each output block is revisited and accumulated across the
reduction (index_map of the output ignores it), the standard Pallas reduction
pattern.  BlockSpecs stage, per step:

    input :  (1, 1, H_pad, stride, W_pad/stride, ic_bn) — one channel-chunk
                                                   slab, columns split into
                                                   ``stride`` phases
    weight:  (1, 1, KH, KW, ic_bn, oc_bn)       — one (oc, ic) weight block
    output:  (1, 1, oh_bn, OW, oc_bn)           — fp32 accumulator rows

Every tap reads its operands straight from the VMEM refs: the input row
``oh * stride + dy``, the contiguous window ``pl.ds(dx // stride, OW)`` of
column phase ``dx % stride``, and the weight tap ``w_ref[0, 0, dy, dx]``.
The tiled footprint of these blocks (sublane/lane padding, double
buffering) is what ``core.cost.conv_vmem_bytes`` costs, and the kernel asks
the compiler for exactly the planner's VMEM budget (``core.peaks``).

The composable epilogue (``core.epilogue.EpilogueSpec``) runs on the last
reduction step, while the fp32 block is still VMEM-resident:

* affine / residual / ReLU;
* **fused pooling** — the conv accumulates into a whole-plane VMEM scratch
  that carries the pooling window's padding as a border of the reduction's
  identity (-inf for max, 0 for avg), so each pooled row is a strided read
  of that scratch (kept in chunks of at most 128 lanes, the widest minor
  dim a strided read takes); the output BlockSpec carries the *pooled*
  block and the conv-resolution tensor never reaches HBM;
* **concat-offset store** — the grid's OC dimension runs over the *shared
  concat buffer's* chunks; chunks inside this block's channel range
  accumulate the conv, chunks outside copy the incoming buffer through, so
  the kernel returns the buffer with the block's slice written in place of
  a standalone concat copy.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.cost import pool_lanes
from repro.core.epilogue import EpilogueSpec, IDENTITY, PoolSpec
from repro.core.peaks import VMEM_BUDGET
from repro.core.schedule import ConvSchedule
from repro.kernels.pltpu_compat import resolve_interpret


def _conv_kernel(x_ref, w_ref, *rest, stride: int, kh: int, kw: int,
                 oh_bn: int, ow: int, unroll_ker: bool,
                 has_scale: bool, has_shift: bool, has_residual: bool,
                 relu: bool, pool: PoolSpec | None, has_buf: bool,
                 off_chunks: int, own_chunks: int):
    refs = list(rest)
    acc_scr = refs.pop() if pool is not None else None  # padded plane
    o_ref = refs.pop()
    scale_ref = refs.pop(0) if has_scale else None
    shift_ref = refs.pop(0) if has_shift else None
    res_ref = refs.pop(0) if has_residual else None
    buf_ref = refs.pop(0) if has_buf else None
    ci = pl.program_id(3)
    ohb = pl.program_id(2)
    co = pl.program_id(1)
    last_ci = ci == pl.num_programs(3) - 1
    # the conv plane sits at the pooling pad's offset inside the scratch,
    # split over lane chunks: (chunks, H_s, W_s, lanes)
    p0 = pool.pad if pool is not None else 0
    n_lc, lanes = (acc_scr.shape[0], acc_scr.shape[-1]) \
        if pool is not None else (0, 0)
    # concat fusion: the OC grid covers the whole shared buffer; only chunks
    # in [off, off + own) belong to this conv — the rest copy through
    inside = ((co >= off_chunks) & (co < off_chunks + own_chunks)) \
        if has_buf else (ci >= 0)

    def load_row(dh):
        """fp32 accumulator row ``dh`` of the block: (OW, oc_bn)."""
        if pool is None:
            return o_ref[0, 0, dh]
        parts = [acc_scr[j, p0 + dh, pl.ds(p0, ow)] for j in range(n_lc)]
        return parts[0] if n_lc == 1 else jnp.concatenate(parts, axis=-1)

    def store_row(dh, acc):
        if pool is None:
            o_ref[0, 0, dh] = acc
            return
        for j in range(n_lc):
            acc_scr[j, p0 + dh, pl.ds(p0, ow)] = acc[:, j * lanes:
                                                     (j + 1) * lanes]

    if has_buf:
        @pl.when(~inside & (ci == 0))
        def _copy_through():
            o_ref[...] = buf_ref[...].astype(o_ref.dtype)

    @pl.when(inside & (ci == 0))
    def _init():
        if pool is not None:
            # the border holds the pooling reduction's identity
            fill = -jnp.inf if pool.kind == "max" else 0.0
            acc_scr[...] = jnp.full(acc_scr.shape, fill, jnp.float32)

            def zero_row(dh, carry):
                store_row(dh, jnp.zeros((ow, n_lc * lanes), jnp.float32))
                return carry

            jax.lax.fori_loop(0, oh_bn, zero_row, 0)
        else:
            o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(inside)
    def _accumulate():
        def tap_row(row, dy, acc):
            # all kw taps of kernel row dy: input window x weight tap.  The
            # input arrives split into ``stride`` column phases, so tap dx
            # reads phase dx % stride contiguously from column dx // stride
            for dx in range(kw):
                patch = x_ref[0, 0, row + dy, dx % stride,
                              pl.ds(dx // stride, ow)]
                acc = acc + jnp.dot(patch.astype(jnp.float32),
                                    w_ref[0, 0, dy, dx].astype(jnp.float32),
                                    preferred_element_type=jnp.float32)
            return acc

        def one_row(dh, carry):
            row = (ohb * oh_bn + dh) * stride
            acc = load_row(dh)
            if unroll_ker:  # Alg. 1 line 12: "(opt) unroll"
                for dy in range(kh):
                    acc = tap_row(row, dy, acc)
            else:
                acc = jax.lax.fori_loop(
                    0, kh, lambda dy, a: tap_row(row, dy, a), acc)
            store_row(dh, acc)
            return carry

        jax.lax.fori_loop(0, oh_bn, one_row, 0)

    if has_scale or has_shift or has_residual or relu or pool is not None:
        # §3.1 fused epilogue: on the last reduction step — while the output
        # block is still VMEM-resident — apply the per-channel affine, the
        # residual add, ReLU, and the pooling reduction before the block is
        # ever stored to HBM
        def affine(acc, dh):
            if has_scale:
                acc = acc * scale_ref[0]           # (1, oc_bn) broadcasts
            if has_shift:
                acc = acc + shift_ref[0]
            if has_residual:
                acc = acc + res_ref[0, 0, dh].astype(jnp.float32)
            if relu:
                acc = jnp.maximum(acc, 0.0)
            return acc

        @pl.when(inside & last_ci)
        def _epilogue():
            def one_row(dh, carry):
                store_row(dh, affine(load_row(dh), dh))
                return carry

            jax.lax.fori_loop(0, oh_bn, one_row, 0)
            if pool is not None:
                out_h, out_w = o_ref.shape[2], o_ref.shape[3]
                s = pool.stride
                cols = [pl.ds(dx, out_w, stride=s) if s > 1
                        else pl.ds(dx, out_w) for dx in range(pool.k)]

                def pooled_row(r, carry):
                    for j in range(n_lc):
                        acc = None
                        for dy in range(pool.k):
                            for dx in range(pool.k):
                                v = acc_scr[j, r * s + dy, cols[dx]]
                                acc = v if acc is None else (
                                    jnp.maximum(acc, v) if pool.kind == "max"
                                    else acc + v)
                        if pool.kind == "avg":
                            acc = acc / (pool.k * pool.k)
                        o_ref[0, 0, r, :, pl.ds(j * lanes, lanes)] = acc
                    return carry

                jax.lax.fori_loop(0, out_h, pooled_row, 0)


def conv2d_nchwc_pallas(x_blocked: jnp.ndarray, w_blocked: jnp.ndarray,
                        scale: jnp.ndarray | None = None,
                        shift: jnp.ndarray | None = None,
                        residual: jnp.ndarray | None = None,
                        out_buf: jnp.ndarray | None = None,
                        *, stride: int = 1,
                        schedule: ConvSchedule,
                        epilogue: EpilogueSpec | None = None,
                        interpret: bool | None = None) -> jnp.ndarray:
    """Blocked conv via pallas_call.  ``x_blocked`` must already be padded:
    (N, C_in//ic_bn, H_pad, W_pad, ic_bn); weights (Ko, Ci, KH, KW, ic, oc).

    The composable fused epilogue (``core.epilogue.EpilogueSpec``) applies
    ``out * scale + shift`` (per-channel vectors pre-blocked to
    ``(Ko, oc_bn)``), adds a ``residual`` in the conv's own blocked layout,
    clamps with ReLU, runs the fused pooling reduction, and stores at the
    spec's channel offset into ``out_buf`` (the shared concat buffer) — all
    on the last reduction step, before the fp32 accumulator leaves VMEM.

    ``interpret=None`` compiles the kernel where the backend has a Pallas
    lowering (TPU) and interprets it elsewhere; an explicit bool wins.
    """
    return _conv2d_nchwc_jit(x_blocked, w_blocked, scale, shift, residual,
                             out_buf, stride=stride, schedule=schedule,
                             epilogue=epilogue,
                             interpret=resolve_interpret(interpret))


@functools.partial(
    jax.jit,
    static_argnames=("stride", "schedule", "epilogue", "interpret"))
def _conv2d_nchwc_jit(x_blocked, w_blocked, scale, shift, residual, out_buf,
                      *, stride: int, schedule: ConvSchedule,
                      epilogue: EpilogueSpec | None,
                      interpret: bool) -> jnp.ndarray:
    spec = epilogue or IDENTITY
    pool = spec.pool
    n, ci_chunks, h_pad, w_pad, ic_bn = x_blocked.shape
    ko_chunks, ci_chunks_w, kh, kw, ic_bn_w, oc_bn = w_blocked.shape
    assert (ci_chunks, ic_bn) == (ci_chunks_w, ic_bn_w), "layout mismatch"
    assert ic_bn == schedule.ic_bn and oc_bn == schedule.oc_bn
    oh = (h_pad - kh) // stride + 1
    ow = (w_pad - kw) // stride + 1
    if pool is not None:
        # pooled output tiling: the conv plane accumulates in a whole-plane
        # VMEM scratch, so the OH grid collapses and oh_bn covers the plane
        oh_bn = oh
        out_h, out_w = pool.out_hw(oh, ow)
    else:
        oh_bn = schedule.oh_bn
        out_h, out_w = oh, ow
    assert oh % oh_bn == 0, (oh, schedule)

    has_buf = spec.writes_concat
    if has_buf:
        assert out_buf is not None, "concat-write epilogue needs out_buf"
        assert spec.concat_offset % oc_bn == 0, (spec.concat_offset, oc_bn)
        assert spec.concat_total % oc_bn == 0, (spec.concat_total, oc_bn)
        off_chunks = spec.concat_offset // oc_bn
        grid_oc = spec.concat_total // oc_bn
        assert out_buf.shape == (n, grid_oc, out_h, out_w, oc_bn), \
            (out_buf.shape, (n, grid_oc, out_h, out_w, oc_bn))
    else:
        off_chunks = 0
        grid_oc = ko_chunks

    def _wi(k):
        # map an output-buffer chunk index to this conv's weight chunk
        # (clamped for the copy-through chunks, whose weights are unused)
        return jnp.clip(k - off_chunks, 0, ko_chunks - 1) if has_buf else k

    grid = (n, grid_oc, oh // oh_bn, ci_chunks)
    kernel = functools.partial(
        _conv_kernel, stride=stride, kh=kh, kw=kw, oh_bn=oh_bn, ow=ow,
        unroll_ker=schedule.unroll_ker,
        has_scale=scale is not None, has_shift=shift is not None,
        has_residual=residual is not None, relu=spec.relu, pool=pool,
        has_buf=has_buf, off_chunks=off_chunks, own_chunks=ko_chunks)
    # split the padded columns into ``stride`` phases (column w -> phase
    # w % stride, slot w // stride) so every tap reads a contiguous window
    w_ph = -(-w_pad // stride)
    x_ph = jnp.pad(x_blocked, ((0, 0),) * 3 + ((0, w_ph * stride - w_pad),
                                                (0, 0)))
    x_ph = x_ph.reshape(n, ci_chunks, h_pad, w_ph, stride, ic_bn) \
        .transpose(0, 1, 2, 4, 3, 5)
    in_specs = [
        # with one channel chunk the slab changes only per image: a single
        # buffer halves the largest block (the RGB stem's lane-padded plane)
        pl.BlockSpec((1, 1, h_pad, stride, w_ph, ic_bn),
                     lambda b, k, o, c: (b, c, 0, 0, 0, 0),
                     **({"pipeline_mode": pl.Buffered(1)}
                        if ci_chunks == 1 and not interpret else {})),
        pl.BlockSpec((1, 1, kh, kw, ic_bn, oc_bn),
                     lambda b, k, o, c: (_wi(k), c, 0, 0, 0, 0)),
    ]
    operands = [x_ph, w_blocked]
    for vec in (scale, shift):
        if vec is not None:
            assert vec.shape == (ko_chunks, oc_bn), (vec.shape,
                                                     w_blocked.shape)
            # (Ko, 1, oc_bn): a (1, oc_bn) block spans the array's last
            # two dims, as the TPU tiling requires
            in_specs.append(pl.BlockSpec((1, 1, oc_bn),
                                         lambda b, k, o, c: (_wi(k), 0, 0)))
            operands.append(vec.astype(jnp.float32)[:, None])
    if residual is not None:
        # consumed at conv resolution, before the pooling reduction
        assert residual.shape == (n, ko_chunks, oh, ow, oc_bn), residual.shape
        in_specs.append(pl.BlockSpec((1, 1, oh_bn, ow, oc_bn),
                                     lambda b, k, o, c: (b, _wi(k), o, 0, 0)))
        operands.append(residual)
    if has_buf:
        # the buffer is staged with exactly the output's block tiling (the
        # copy-through chunks move one block per grid step)
        in_specs.append(pl.BlockSpec(
            (1, 1, out_h if pool is not None else oh_bn, out_w, oc_bn),
            lambda b, k, o, c: (b, k, o, 0, 0)))
        operands.append(out_buf)
    scratch = []
    if pool is not None:
        lanes = pool_lanes(oc_bn)
        scratch.append(pltpu.VMEM(
            (oc_bn // lanes,) + pool.padded_hw(oh, ow) + (lanes,),
            jnp.float32))
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, out_h if pool is not None else oh_bn,
                                out_w, oc_bn),
                               lambda b, k, o, c: (b, k, o, 0, 0)),
        out_shape=jax.ShapeDtypeStruct(
            (n, grid_oc, out_h, out_w, oc_bn), jnp.float32),
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(
                "parallel", "parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_BUDGET),
        interpret=interpret,
    )(*operands)
    return out.astype(x_blocked.dtype)
