"""Pallas TPU kernel for the SSD intra-chunk quadratic block (Mamba-2).

The hot spot of `models/lm/ssm.ssd_chunked` is the per-chunk masked
quadratic form

    y[i] = sum_{j<=i} exp(acum_i - acum_j) * (c_i . b_j) * x_j

which the XLA path materializes as a (B, C, Q, Q, H) decay tensor.  The
kernel keeps the (Q, Q) score/decay tile resident in VMEM per (batch-chunk,
head) grid step and fuses mask, decay and both matmuls — the same
working-set discipline as the paper's conv template (the (Q, N)/(Q, P)
blocks are the NCHW[x]c analogue, Q the reg_n analogue).

Grid: (B*n_chunks, H).  b/c blocks are shared across heads (single SSD
group), selected by the first grid axis only.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.pltpu_compat import resolve_interpret


def _ssd_intra_kernel(cc_ref, bc_ref, acum_ref, x_ref, o_ref):
    q = cc_ref.shape[1]
    cc = cc_ref[0].astype(jnp.float32)              # (Q, N)
    bc = bc_ref[0].astype(jnp.float32)              # (Q, N)
    acum = acum_ref[0, 0].astype(jnp.float32)       # (Q,)
    xd = x_ref[0, 0].astype(jnp.float32)            # (Q, P)

    scores = jnp.dot(cc, bc.T, preferred_element_type=jnp.float32)
    diff = acum[:, None] - acum[None, :]            # (Q, Q)
    rows = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    ell = jnp.where(rows >= cols, jnp.exp(diff), 0.0)
    o_ref[0, 0] = jnp.dot(scores * ell, xd,
                          preferred_element_type=jnp.float32
                          ).astype(o_ref.dtype)


def ssd_intra_pallas(cc: jnp.ndarray, bc: jnp.ndarray, acum: jnp.ndarray,
                     xd: jnp.ndarray, *, interpret: bool | None = None
                     ) -> jnp.ndarray:
    """cc, bc: (BC, Q, N) — per-(batch x chunk) C/B blocks (shared across
    heads); acum: (BC, H, Q) cumulative decay logs; xd: (BC, H, Q, P)
    dt-weighted inputs.  Returns y_diag: (BC, H, Q, P).  ``interpret=None``
    compiles on TPU and interprets elsewhere."""
    return _ssd_intra_jit(cc, bc, acum, xd,
                          interpret=resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("interpret",))
def _ssd_intra_jit(cc, bc, acum, xd, *, interpret: bool) -> jnp.ndarray:
    bcn, q, n = cc.shape
    _, h, _, p = xd.shape
    grid = (bcn, h)
    return pl.pallas_call(
        _ssd_intra_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, q, n), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, q, n), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, 1, q), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, 1, q, p), lambda i, j: (i, j, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, q, p), lambda i, j: (i, j, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((bcn, h, q, p), xd.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(cc, bc, acum, xd)


def ssd_intra_ref(cc, bc, acum, xd):
    """Pure-jnp oracle (same contraction as ssm.ssd_chunked's y_diag)."""
    scores = jnp.einsum("gin,gjn->gij", cc.astype(jnp.float32),
                        bc.astype(jnp.float32))
    diff = acum[..., :, None] - acum[..., None, :]    # (BC, H, Q, Q)
    q = acum.shape[-1]
    mask = jnp.tril(jnp.ones((q, q), bool))
    ell = jnp.where(mask, jnp.exp(diff), 0.0)         # (BC, H, Q, Q)
    return jnp.einsum("gij,ghij,ghjp->ghip", scores, ell,
                      xd.astype(jnp.float32)).astype(xd.dtype)
