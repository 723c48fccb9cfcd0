"""Pallas TPU fused attention (GQA, causal, optional local window).

The LM serving path's compute hot-spot.  Online-softmax flash attention
blocked for VMEM: the grid walks (batch, q-head, q-block) in parallel and
the kv-block axis as the innermost reduction; running max/denominator and
the fp32 accumulator live in VMEM scratch.  GQA is expressed in the k/v
BlockSpec index maps (q-head h reads kv-head h // group), so no repeated
K/V materialization — the kernel-level analogue of the paper's rule that
the template, not the graph, decides the data movement.

Local windows (RecurrentGemma's 1:2 attention layers) reuse the same kernel
with an extra band mask.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.pltpu_compat import resolve_interpret

NEG_INF = -1e30


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                 bq: int, bkv: int, seq: int, scale: float, causal: bool,
                 window: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    n_kv = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = qi * bq
    k_start = ki * bkv

    # skip kv blocks that are entirely masked (above the causal diagonal or
    # left of the local window)
    run = jnp.bool_(True)
    if causal:
        run = jnp.logical_and(run, k_start <= q_start + bq - 1)
    if window > 0:
        run = jnp.logical_and(run, k_start + bkv - 1 >= q_start - window + 1)

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale          # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)                  # (bkv, d)
        v = v_ref[0, 0].astype(jnp.float32)                  # (bkv, d)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)

        rows = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0)
        cols = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
        mask = jnp.ones((bq, bkv), dtype=bool)
        if causal:
            mask &= rows >= cols
        if window > 0:
            mask &= rows - cols < window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]                                   # (bq, 1)
        m_cur = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_cur)                                # (bq, bkv)
        alpha = jnp.exp(m_prev - m_cur)                       # (bq, 1)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        m_ref[...] = m_cur

    @pl.when(ki == n_kv - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def flash_attention_pallas(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                           *, causal: bool = True, window: int = 0,
                           bq: int = 128, bkv: int = 128,
                           interpret: bool | None = None) -> jnp.ndarray:
    """q: (B, Hq, S, D); k, v: (B, Hkv, S, D); Hq % Hkv == 0.
    S must be divisible by bq and bkv (pad upstream if not).

    ``interpret=None`` (default) resolves platform-aware: compiled on
    TPU, interpreter elsewhere (``pltpu_compat.resolve_interpret``) —
    resolved *here*, outside the jit, because the backend query is a
    Python-side decision the trace must not capture.
    """
    return _flash_attention_jit(q, k, v, causal=causal, window=window,
                                bq=bq, bkv=bkv,
                                interpret=resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "bq", "bkv", "interpret"))
def _flash_attention_jit(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                         *, causal: bool, window: int,
                         bq: int, bkv: int,
                         interpret: bool) -> jnp.ndarray:
    b, hq, s, d = q.shape
    _, hkv, sk, _ = k.shape
    assert s == sk and hq % hkv == 0, (q.shape, k.shape)
    bq = min(bq, s)
    bkv = min(bkv, s)
    assert s % bq == 0 and s % bkv == 0, (s, bq, bkv)
    group = hq // hkv
    scale = 1.0 / (d ** 0.5)

    grid = (b, hq, s // bq, s // bkv)
    kernel = functools.partial(
        _attn_kernel, bq=bq, bkv=bkv, seq=s, scale=scale, causal=causal,
        window=window)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda bb, h, qi, ki: (bb, h, qi, 0)),
            pl.BlockSpec((1, 1, bkv, d),
                         lambda bb, h, qi, ki: (bb, h // group, ki, 0)),
            pl.BlockSpec((1, 1, bkv, d),
                         lambda bb, h, qi, ki: (bb, h // group, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d),
                               lambda bb, h, qi, ki: (bb, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),   # running max
            pltpu.VMEM((bq, 1), jnp.float32),   # running denominator
            pltpu.VMEM((bq, d), jnp.float32),   # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)
