"""Composable conv_block epilogue spec (NeoCPU §3.1, extended).

PR 1 hardcoded the fused epilogue as ``scale/shift -> residual -> ReLU``.
This module turns it into a small *spec* every template variant (and the
Pallas kernel) accepts, so the epilogue is a planned, costed, searched axis
rather than a fixed tail.  Two additions beyond the PR-1 sequence:

* **fused pooling** — a ``conv_block -> max_pool/avg_pool`` chain collapses:
  the pooling reduction runs over the fp32 accumulator tile *before* it is
  stored, so the stem ``conv7x7 -> bn -> relu -> max_pool3x3s2`` becomes one
  kernel and the conv-resolution tensor never round-trips through HBM
  (the fused-downsampling-epilogue win of Georganas et al., 1808.05567).
* **concat-aware output placement** — DenseNet's ``concat(conv outs)`` fuses
  by giving each producing conv_block a channel-offset write into the shared
  concat buffer, eliminating the copy the standalone concat would do.

The spec is a frozen (hashable) dataclass so it can ride through ``jax.jit``
as a static argument.  The *presence* of the affine/residual operands is
conveyed by the tensors themselves (None or not); the spec carries only the
structural knobs the kernels must specialize on.

Epilogue application order is fixed:

    acc = conv(x)                      # fp32 accumulator
    acc = acc * scale + shift          # absorbed BN (folded at bind time)
    acc = acc + residual               # ResNet tail, conv resolution
    acc = relu(acc)                    # before pooling, as in the zoo graphs
    acc = pool(acc)                    # spatial reduction on the fp32 tile
    out[.., off:off+C, ..] = acc       # channel-offset store (concat fusion)

The per-channel ``scale`` operand has two producers, folded the same way
at bind time: the absorbed BN scale, and (``ConvSchedule.dtype="int8"``)
the weight-dequantize scale of the quantized template — the int8
accumulator holds integer-code contractions, so multiplying by the
quantization scale in the affine stage reconstructs the fp32 conv, and
every template variant gets the dequant epilogue for free from the one
shared implementation (:func:`fold_dequant_scale` composes the two when a
conv carries both).
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

NEG_INF = -1e30   # matches kernels.flash_attention.NEG_INF


def _pool_out_hw(h: int, w: int, k: int, stride: int, pad: int,
                 ceil_mode: bool) -> Tuple[int, int]:
    """The one copy of the pooled output-size arithmetic (floor/ceil)."""
    if ceil_mode:
        oh = -(-(h + 2 * pad - k) // stride) + 1
        ow = -(-(w + 2 * pad - k) // stride) + 1
    else:
        oh = (h + 2 * pad - k) // stride + 1
        ow = (w + 2 * pad - k) // stride + 1
    return oh, ow


def pool2d(x: jnp.ndarray, k: int, stride: int, pad: int = 0,
           ceil_mode: bool = False, reducer: str = "max") -> jnp.ndarray:
    """Window pooling over axes (2, 3) of an arbitrary-rank tensor — THE
    pooling implementation: logical NCHW, blocked NCHW[x]c, the 5-D fp32
    accumulator of the fused jnp epilogue, and (via ``PoolSpec.apply``) the
    VMEM plane inside the Pallas kernel all reduce through this one body,
    so fused and standalone pooling cannot drift apart."""
    h, w = x.shape[2], x.shape[3]
    oh, ow = _pool_out_hw(h, w, k, stride, pad, ceil_mode)
    if ceil_mode:
        eh = (oh - 1) * stride + k - h - pad
        ew = (ow - 1) * stride + k - w - pad
    else:
        eh, ew = pad, pad
    fill = -jnp.inf if reducer == "max" else 0.0
    widths = [(0, 0)] * x.ndim
    widths[2] = (pad, max(eh, pad))
    widths[3] = (pad, max(ew, pad))
    xp = jnp.pad(x, widths, constant_values=fill)
    acc = None
    for dh in range(k):
        for dw in range(k):
            sl = [slice(None)] * x.ndim
            sl[2] = slice(dh, dh + oh * stride, stride)
            sl[3] = slice(dw, dw + ow * stride, stride)
            patch = xp[tuple(sl)]
            if acc is None:
                acc = patch
            elif reducer == "max":
                acc = jnp.maximum(acc, patch)
            else:
                acc = acc + patch
    if reducer == "avg":
        acc = acc / (k * k)
    return acc


@dataclasses.dataclass(frozen=True)
class PoolSpec:
    """A pooling reduction fused into the conv epilogue."""

    kind: str                 # "max" | "avg"
    k: int
    stride: int
    pad: int = 0
    ceil_mode: bool = False

    def __post_init__(self):
        if self.kind not in ("max", "avg"):
            raise ValueError(f"pool kind {self.kind!r} not in ('max', 'avg')")

    def out_hw(self, h: int, w: int) -> Tuple[int, int]:
        """Pooled spatial dims (matches ``pool2d``'s output)."""
        return _pool_out_hw(h, w, self.k, self.stride, self.pad,
                            self.ceil_mode)

    def padded_hw(self, h: int, w: int) -> Tuple[int, int]:
        """Extent of the ``(h, w)`` plane once ``pool2d``'s padding is laid
        around it (top/left ``pad``, bottom/right to the last window)."""
        oh, ow = self.out_hw(h, w)
        return (max(self.pad + h, (oh - 1) * self.stride + self.k),
                max(self.pad + w, (ow - 1) * self.stride + self.k))

    def apply(self, x: jnp.ndarray) -> jnp.ndarray:
        """Run this pooling reduction over axes (2, 3) of ``x``."""
        return pool2d(x, self.k, self.stride, self.pad, self.ceil_mode,
                      self.kind)


@dataclasses.dataclass(frozen=True)
class EpilogueSpec:
    """Static structure of a conv_block's fused epilogue.

    ``concat_total`` > 0 means the block stores into a shared concat buffer
    of that many channels, at channel offset ``concat_offset`` — the kernel
    then receives the buffer and returns it with the block's slice written.

    The LM extension adds the matmul-tail stages, applied while the logits
    block is still accumulator-resident (order fixed, after the conv-side
    affine/residual stages and instead of pooling):

        acc = acc * scale              # e.g. 1/sqrt(head_dim)
        acc = mask(acc)                # "causal": NEG_INF above the diagonal
        acc = softmax(acc, axis=-1)    # row softmax over the full N extent

    ``softmax=True`` requires the kernel to hold a full output row in one
    block (the matmul template enforces a single N-block, the same way
    concat fusion constrains ``oc_bn``).  The matmul stages are mutually
    exclusive with pooling/concat — those are conv-side spatial stages.
    """

    relu: bool = False
    pool: Optional[PoolSpec] = None
    concat_offset: int = 0
    concat_total: int = 0
    scale: Optional[float] = None
    mask: str = "none"        # "none" | "causal"
    softmax: bool = False

    def __post_init__(self):
        if self.mask not in ("none", "causal"):
            raise ValueError(f"mask {self.mask!r} not in ('none', 'causal')")
        if self.has_matmul_tail and (self.pool is not None
                                     or self.concat_total > 0):
            raise ValueError(
                "matmul-tail stages (scale/mask/softmax) cannot combine "
                "with conv-side pooling or concat placement")
        if self.softmax and self.relu:
            raise ValueError("softmax and relu are mutually exclusive "
                             "epilogue tails")

    @property
    def has_matmul_tail(self) -> bool:
        return (self.scale is not None or self.mask != "none"
                or self.softmax)

    @property
    def writes_concat(self) -> bool:
        return self.concat_total > 0

    def with_relu(self, relu: bool) -> "EpilogueSpec":
        if relu and not self.relu:
            return dataclasses.replace(self, relu=True)
        return self

    def out_hw(self, oh: int, ow: int) -> Tuple[int, int]:
        """Stored spatial dims for a conv-resolution (oh, ow)."""
        return self.pool.out_hw(oh, ow) if self.pool is not None else (oh, ow)

    def out_channels(self, conv_channels: int) -> int:
        """Stored channel count (the concat buffer's, if fused)."""
        return self.concat_total if self.writes_concat else conv_channels


IDENTITY = EpilogueSpec()


def apply_matmul_epilogue(acc: jnp.ndarray, spec: EpilogueSpec, *,
                          row0=0, col0=0,
                          n_valid: Optional[int] = None) -> jnp.ndarray:
    """Apply a matmul-tail epilogue to an fp32 accumulator block.

    THE shared implementation: the jnp oracle, the Pallas blocked-GEMM
    kernel (on the VMEM accumulator at the last k-step), and any future
    template variant all run this one body, so fused and standalone
    epilogues cannot drift apart — the conv-side twin of
    ``kernels.ops.apply_epilogue_fp32``.

    ``row0``/``col0`` locate the block inside the logical (M, N) output
    (the causal mask needs absolute coordinates).  ``n_valid`` masks
    padded columns ``>= n_valid`` to NEG_INF before the softmax so the
    exp-sum of a padded row matches the unpadded computation exactly; it
    is ignored without softmax (padded columns are sliced away anyway).
    """
    bm, bn = acc.shape[-2], acc.shape[-1]
    if spec.scale is not None:
        acc = acc * jnp.float32(spec.scale)
    need_cols = (spec.mask == "causal"
                 or (spec.softmax and n_valid is not None and n_valid < bn))
    if need_cols:
        cols = col0 + jax.lax.broadcasted_iota(jnp.int32, acc.shape,
                                               acc.ndim - 1)
    if spec.mask == "causal":
        rows = row0 + jax.lax.broadcasted_iota(jnp.int32, acc.shape,
                                               acc.ndim - 2)
        acc = jnp.where(rows >= cols, acc, NEG_INF)
    if spec.softmax:
        if n_valid is not None and n_valid < bn:
            acc = jnp.where(cols < n_valid, acc, NEG_INF)
        m = jnp.max(acc, axis=-1, keepdims=True)
        p = jnp.exp(acc - m)
        acc = p / jnp.maximum(p.sum(axis=-1, keepdims=True), 1e-30)
    if spec.relu:
        acc = jnp.maximum(acc, 0.0)
    return acc


def fold_dequant_scale(scale, w_scale):
    """Fold a per-output-channel weight-dequantize scale into the epilogue's
    ``scale`` operand, exactly the way BN folding composes at bind time:
    scales multiply (the affine stage applies their product once), and an
    absent epilogue scale just becomes the dequant scale.  Shift is
    untouched — dequantization is purely multiplicative (symmetric
    quantization has no zero-point)."""
    if w_scale is None:
        return scale
    w_scale = jnp.asarray(w_scale, jnp.float32)
    return w_scale if scale is None else scale * w_scale
