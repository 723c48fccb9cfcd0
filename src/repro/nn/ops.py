"""Layout-aware layer implementations.

Every op here runs in whatever physical layout the planner assigned —
``NCHW`` or ``NCHW[x]c`` — without densifying back to the default layout.
Spatial dims sit at axes (2, 3) in both layouts, so pooling and padding
share code; channel-pointwise ops (batch-norm scale/shift) broadcast against
pre-blocked parameters the engine prepared at bind time (§3.2 weight
pre-transformation).
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from repro.core.epilogue import EpilogueSpec, pool2d
from repro.core.layout import Layout, relayout
from repro.core.schedule import ConvSchedule
from repro.kernels.ops import conv2d_block_blocked, conv2d_blocked


# ---------------------------------------------------------------------------
# Convolution
# ---------------------------------------------------------------------------

def conv2d_nchw_direct(x: jnp.ndarray, w: jnp.ndarray, stride: int = 1,
                       pad=0, groups: int = 1) -> jnp.ndarray:
    """Unblocked direct conv — the Table 3 row-1 baseline template.  Same
    loop nest as the blocked kernel but over the raw NCHW layout."""
    n, c, h, wd = x.shape
    k, c_per_g, kh, kw = w.shape
    ph, pw = (pad, pad) if isinstance(pad, int) else tuple(pad)
    xp = jnp.pad(x, ((0, 0), (0, 0), (ph, ph), (pw, pw)))
    oh = (h + 2 * ph - kh) // stride + 1
    ow = (wd + 2 * pw - kw) // stride + 1
    kpg = k // groups
    outs = []
    for g in range(groups):
        xg = xp[:, g * c_per_g:(g + 1) * c_per_g]
        wg = w[g * kpg:(g + 1) * kpg]
        acc = jnp.zeros((n, kpg, oh, ow), dtype=jnp.float32)
        for dh in range(kh):
            for dw in range(kw):
                patch = xg[:, :, dh:dh + oh * stride:stride,
                           dw:dw + ow * stride:stride]
                acc = acc + jnp.einsum(
                    "nchw,kc->nkhw", patch.astype(jnp.float32),
                    wg[:, :, dh, dw].astype(jnp.float32),
                    preferred_element_type=jnp.float32)
        outs.append(acc)
    out = outs[0] if groups == 1 else jnp.concatenate(outs, axis=1)
    return out.astype(x.dtype)


def conv2d(x: jnp.ndarray, w: jnp.ndarray, b: Optional[jnp.ndarray],
           layout: Layout, *, stride: int = 1, pad=0,
           groups: int = 1, schedule: Optional[ConvSchedule] = None,
           use_pallas: bool = False, interpret: Optional[bool] = None,
           w_prelaid: bool = False) -> jnp.ndarray:
    """``w`` (and ``b``) arrive pre-transformed for ``layout``:
    KCRS for NCHW, KCRS[x]c[y]k for blocked (panel-major when the engine
    pre-laid a patch_gemm weight — ``w_prelaid``)."""
    if layout.is_blocked:
        assert groups == 1, "grouped convs run in NCHW"
        out = conv2d_blocked(x, w, stride=stride, pad=pad, schedule=schedule,
                             use_pallas=use_pallas, interpret=interpret,
                             w_prelaid=w_prelaid)
        if b is not None:   # b pre-shaped (Ko, 1, 1, oc_bn)
            out = out + b[None]
    else:
        out = conv2d_nchw_direct(x, w, stride=stride, pad=pad, groups=groups)
        if b is not None:   # b pre-shaped (K, 1, 1)
            out = out + b[None]
    return out


def conv_block(x: jnp.ndarray, w: jnp.ndarray,
               scale: Optional[jnp.ndarray], shift: Optional[jnp.ndarray],
               residual: Optional[jnp.ndarray], layout: Layout, *,
               stride: int = 1, pad=0, groups: int = 1, relu: bool = False,
               epilogue: Optional[EpilogueSpec] = None,
               out_buf: Optional[jnp.ndarray] = None,
               schedule: Optional[ConvSchedule] = None,
               use_pallas: bool = False,
               interpret: Optional[bool] = None,
               w_prelaid: bool = False) -> jnp.ndarray:
    """Fused CONV + composable epilogue (§3.1 operation fusion): per-channel
    affine (-> residual add) -> ReLU -> fused pooling, optionally stored at a
    channel offset into the shared concat buffer ``out_buf``.  ``w`` arrives
    pre-transformed for ``layout`` with BN scale usually pre-folded in (then
    ``scale`` is None); ``scale``/``shift`` are pre-blocked per-channel
    vectors — ``(Ko, oc_bn)`` blocked, ``(C, 1, 1)`` in NCHW — and
    ``residual`` is in the conv's own output layout (conv resolution,
    pre-pool)."""
    spec = (epilogue or EpilogueSpec()).with_relu(relu)
    if layout.is_blocked:
        assert groups == 1, "grouped convs run in NCHW"
        return conv2d_block_blocked(
            x, w, scale, shift, residual, out_buf, stride=stride, pad=pad,
            epilogue=spec, schedule=schedule, use_pallas=use_pallas,
            interpret=interpret, w_prelaid=w_prelaid)
    out = conv2d_nchw_direct(x, w, stride=stride, pad=pad,
                             groups=groups).astype(jnp.float32)
    if scale is not None:
        out = out * scale[None]
    if shift is not None:
        out = out + shift[None]
    if residual is not None:
        out = out + residual.astype(jnp.float32)
    if spec.relu:
        out = jnp.maximum(out, 0.0)
    if spec.pool is not None:
        out = spec.pool.apply(out)
    out = out.astype(x.dtype)
    if spec.writes_concat:
        assert out_buf is not None, "concat-write epilogue needs out_buf"
        out = jax.lax.dynamic_update_slice(
            out_buf, out.astype(out_buf.dtype),
            (0, spec.concat_offset, 0, 0))
    return out


# ---------------------------------------------------------------------------
# Normalization / activations (inference-simplified, as TVM's passes do)
# ---------------------------------------------------------------------------

def batch_norm(x: jnp.ndarray, scale: jnp.ndarray, shift: jnp.ndarray,
               layout: Layout) -> jnp.ndarray:
    """Inference BN folded to scale/shift; parameters pre-blocked:
    NCHW: (C, 1, 1);  NCHW[x]c: (C//x, 1, 1, x)."""
    return x * scale[None] + shift[None]


def relu(x: jnp.ndarray) -> jnp.ndarray:
    return jnp.maximum(x, 0)


def softmax(x: jnp.ndarray, layout: Layout) -> jnp.ndarray:
    if x.ndim == 2:
        return jax.nn.softmax(x, axis=-1)
    if layout.is_blocked:   # joint softmax over (C//x, x)
        m = x.max(axis=(1, 4), keepdims=True)
        e = jnp.exp(x - m)
        return e / e.sum(axis=(1, 4), keepdims=True)
    m = x.max(axis=1, keepdims=True)
    e = jnp.exp(x - m)
    return e / e.sum(axis=1, keepdims=True)


def l2_normalize(x: jnp.ndarray, layout: Layout, eps: float = 1e-12
                 ) -> jnp.ndarray:
    if layout.is_blocked:
        sq = (x * x).sum(axis=(1, 4), keepdims=True)
    else:
        sq = (x * x).sum(axis=1, keepdims=True)
    return x * jax.lax.rsqrt(sq + eps)


# ---------------------------------------------------------------------------
# Pooling — spatial axes are (2, 3) in both layouts
# ---------------------------------------------------------------------------

def max_pool(x, k, stride=None, pad=0, ceil_mode=False):
    return pool2d(x, k, stride or k, pad, ceil_mode, "max")


def avg_pool(x, k, stride=None, pad=0, ceil_mode=False):
    return pool2d(x, k, stride or k, pad, ceil_mode, "avg")


def global_avg_pool(x: jnp.ndarray) -> jnp.ndarray:
    return x.mean(axis=(2, 3), keepdims=True)


# ---------------------------------------------------------------------------
# Structure ops
# ---------------------------------------------------------------------------

def add(*xs: jnp.ndarray) -> jnp.ndarray:
    out = xs[0]
    for x in xs[1:]:
        out = out + x
    return out


def concat(xs: Sequence[jnp.ndarray], layout: Layout) -> jnp.ndarray:
    # channel concat: super-channel axis is 1 in NCHW, blocked, and 2-D
    return jnp.concatenate(xs, axis=1)


def concat_alloc(xs: Sequence[jnp.ndarray], offsets: Sequence[int],
                 total_channels: int, layout: Layout) -> jnp.ndarray:
    """Seed the shared concat buffer for concat-aware fusion: allocate the
    full ``total_channels`` buffer and place the *pass-through* operands (the
    ones whose producers could not take a fused channel-offset write) at
    their channel offsets.  The fused conv_block producers then write their
    own slices directly into this buffer."""
    ref = xs[0]
    if layout.is_blocked:
        x = layout.block
        assert total_channels % x == 0, (total_channels, layout)
        shape = (ref.shape[0], total_channels // x) + ref.shape[2:]
    else:
        shape = (ref.shape[0], total_channels) + ref.shape[2:]
    buf = jnp.zeros(shape, dtype=ref.dtype)
    for arr, off in zip(xs, offsets):
        if layout.is_blocked:
            assert off % layout.block == 0, (off, layout)
            off = off // layout.block
        idx = (0, off) + (0,) * (buf.ndim - 2)
        buf = jax.lax.dynamic_update_slice(buf, arr.astype(buf.dtype), idx)
    return buf


def flatten(x: jnp.ndarray) -> jnp.ndarray:
    return x.reshape(x.shape[0], -1)


def dense(x: jnp.ndarray, w: jnp.ndarray, b: Optional[jnp.ndarray]
          ) -> jnp.ndarray:
    out = x @ w
    return out + b[None] if b is not None else out


def layout_transform(x: jnp.ndarray, src: Layout, dst: Layout) -> jnp.ndarray:
    if x.ndim == 2:   # flattened tensors carry the default layout tag only
        return x
    return relayout(x, src, dst)
