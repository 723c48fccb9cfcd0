"""Pallas TPU blocked-GEMM template.

The LM-side instantiation of the paper's operation template (§3.1): the
schedule is the (bm, bk, bn) VMEM block triple — bm plays reg_n's role as
the M-tile, bn maps to the 128-lane MXU dimension (oc_bn's analogue), bk is
the contraction block (ic_bn's analogue).  The same template serves dense
projections, MoE expert FFNs, and the LM head; the local search ranks block
triples with the same roofline model used for convs.

Grid ``(M/bm, N/bn, K/bk)`` with the contraction innermost; the output block
is revisited across k-steps and accumulated in fp32 (standard Pallas
reduction pattern — the out index_map ignores the k axis).
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.epilogue import (EpilogueSpec, IDENTITY,
                                 apply_matmul_epilogue)
from repro.kernels.pltpu_compat import resolve_interpret


@dataclasses.dataclass(frozen=True, order=True)
class MatmulSchedule:
    """VMEM block triple; defaults are MXU-aligned (128-lane, 8-sublane)."""

    bm: int = 128
    bk: int = 128
    bn: int = 128

    def validate(self, m: int, k: int, n: int) -> None:
        if m % self.bm or k % self.bk or n % self.bn:
            raise ValueError(f"{(m, k, n)} not divisible by {self}")

    @property
    def vmem_bytes(self) -> int:
        # a block + b block (bf16-or-fp32 ~4B worst case) + fp32 accumulator
        return 4 * (self.bm * self.bk + self.bk * self.bn
                    + self.bm * self.bn)


def _mm_kernel(a_ref, b_ref, o_ref, *, nk: int, bm: int, bn: int,
               epilogue: EpilogueSpec, n_valid):
    i, j, k = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += jnp.dot(a_ref[...].astype(jnp.float32),
                          b_ref[...].astype(jnp.float32),
                          preferred_element_type=jnp.float32)

    if epilogue != IDENTITY:
        # fused tail: applied on the fp32 accumulator block at the last
        # k-step, while it is still VMEM-resident — the matmul analogue of
        # the conv epilogue running before the NCHW[x]c store
        @pl.when(k == nk - 1)
        def _tail():
            o_ref[...] = apply_matmul_epilogue(
                o_ref[...], epilogue, row0=i * bm, col0=j * bn,
                n_valid=n_valid)


def matmul_pallas(a: jnp.ndarray, b: jnp.ndarray, *,
                  schedule: MatmulSchedule = MatmulSchedule(),
                  out_dtype=None, interpret: bool | None = None,
                  epilogue: EpilogueSpec = IDENTITY,
                  n_valid: int = None) -> jnp.ndarray:
    """(M, K) @ (K, N) under the blocked template.

    ``epilogue`` fuses a matmul-tail spec (scale/causal-mask/row-softmax,
    see ``core.epilogue``) into the last k-step.  A softmax tail needs the
    whole output row in one block: ``bn`` must cover N (single N-block),
    exactly the way concat fusion constrains ``oc_bn``.  ``n_valid`` marks
    the first ``n_valid`` columns as real when N carries padding, so the
    fused softmax normalizes over real columns only.

    ``interpret=None`` resolves platform-aware (compiled on TPU,
    interpreter elsewhere); an explicit bool always wins.
    """
    return _matmul_jit(a, b, schedule=schedule, out_dtype=out_dtype,
                       interpret=resolve_interpret(interpret),
                       epilogue=epilogue, n_valid=n_valid)


@functools.partial(jax.jit, static_argnames=("schedule", "interpret",
                                             "out_dtype", "epilogue",
                                             "n_valid"))
def _matmul_jit(a: jnp.ndarray, b: jnp.ndarray, *,
                schedule: MatmulSchedule, out_dtype, interpret: bool,
                epilogue: EpilogueSpec, n_valid) -> jnp.ndarray:
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    s = schedule
    s.validate(m, k, n)
    if epilogue.softmax and s.bn != n:
        raise ValueError(
            f"fused softmax needs the full row in one N-block: bn={s.bn} "
            f"!= n={n} (use matmul_padded, which widens bn to cover N)")
    grid = (m // s.bm, n // s.bn, k // s.bk)
    kernel = functools.partial(_mm_kernel, nk=grid[2], bm=s.bm, bn=s.bn,
                               epilogue=epilogue, n_valid=n_valid)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((s.bm, s.bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((s.bk, s.bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((s.bm, s.bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(a, b)
    return out.astype(out_dtype or a.dtype)


def matmul_padded(a: jnp.ndarray, b: jnp.ndarray, *,
                  schedule: MatmulSchedule = MatmulSchedule(),
                  interpret: bool | None = None,
                  epilogue: EpilogueSpec = IDENTITY) -> jnp.ndarray:
    """Pads M/K/N up to block multiples, runs the template, slices back —
    the wrapper the LM stack calls for arbitrary projection shapes.

    With a softmax epilogue the N-block is widened to cover the whole
    padded row (single N-block) and ``n_valid`` masks the padded columns
    out of the exp-sum, so ``dense -> softmax`` over an arbitrary vocab
    width fuses without a separate normalization pass.
    """
    m, k = a.shape
    _, n = b.shape
    s = schedule
    pm, pk, pn = (-m) % s.bm, (-k) % s.bk, (-n) % s.bn
    if epilogue.softmax:
        s = dataclasses.replace(s, bn=n + pn)      # one N-block, aligned
    ap = jnp.pad(a, ((0, pm), (0, pk)))
    bp = jnp.pad(b, ((0, pk), (0, pn)))
    out = matmul_pallas(ap, bp, schedule=s, interpret=interpret,
                        epilogue=epilogue,
                        n_valid=n if (epilogue.softmax and pn) else None)
    return out[:m, :n]
