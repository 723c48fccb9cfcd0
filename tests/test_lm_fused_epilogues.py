"""Oracle-backed matmul-tail epilogue tests (the LM side of ISSUE 10).

The ``EpilogueSpec`` matmul-tail stages — ``scale``, causal ``mask``, row
``softmax`` — fuse into the blocked GEMM's last k-step while the fp32
accumulator block is still VMEM-resident.  The oracle is deliberately
independent of the fused kernel: an fp32 jnp matmul with the same stages
applied as standalone ops, exactly what an unfused graph would execute.

Covers ``dense -> softmax`` (the LM head) and the attention tail
``scale -> causal-mask -> softmax`` (logits never materialize), the padded
path (``n_valid`` keeping padded columns out of the exp-sum), spec
validation/hashability (jit-static), the single-N-block constraint, and
the cost model's unfused-vs-fused pricing of the new stages.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.cost import epilogue_bytes
from repro.core.epilogue import (EpilogueSpec, IDENTITY, NEG_INF,
                                 apply_matmul_epilogue)
from repro.kernels.matmul_blocked import (MatmulSchedule, matmul_padded,
                                          matmul_pallas)
from repro.kernels.ops import attention_probs, dense_softmax
from repro.models.lm.layers import flash_attention_xla

TOL = dict(rtol=1e-5, atol=1e-5)
KEY = jax.random.PRNGKey(0)


def _oracle(a, b, spec: EpilogueSpec):
    """Standalone-op reference: unfused matmul + separate tail stages."""
    out = jnp.asarray(a, jnp.float32) @ jnp.asarray(b, jnp.float32)
    if spec.scale is not None:
        out = out * spec.scale
    if spec.mask == "causal":
        m, n = out.shape
        rows = jnp.arange(m)[:, None]
        cols = jnp.arange(n)[None, :]
        out = jnp.where(rows >= cols, out, NEG_INF)
    if spec.softmax:
        out = jax.nn.softmax(out, axis=-1)
    if spec.relu:
        out = jnp.maximum(out, 0.0)
    return out


def _tail_tol(k: int, ref: np.ndarray) -> dict:
    """Tolerance of the fused tail against the oracle.  The kernel sums the
    length-``k`` contraction in ``bk``-wide blocks and the oracle in one
    matmul; the two fp32 summation orders round apart by about
    ``eps * sqrt(k)`` times the output's magnitude, so ``atol`` scales with
    both (4x margin).  Outputs of order one (the softmax tails) stay at the
    1e-5 floor."""
    finite = np.abs(ref) < 1e30                  # masked entries excluded
    rms = float(np.sqrt(np.mean(ref[finite] ** 2)))
    eps = float(np.finfo(np.float32).eps)
    return dict(rtol=TOL["rtol"],
                atol=max(TOL["atol"], 4 * eps * np.sqrt(k) * rms))


def _ab(m, k, n, seed=0):
    ka, kb = jax.random.split(jax.random.PRNGKey(seed))
    return (jax.random.normal(ka, (m, k), jnp.float32),
            jax.random.normal(kb, (k, n), jnp.float32))


# ---------------------------------------------------------------------------
# fused tail vs standalone-op oracle
# ---------------------------------------------------------------------------

SPECS = {
    "softmax":            EpilogueSpec(softmax=True),
    "scale_softmax":      EpilogueSpec(scale=0.125, softmax=True),
    "causal_softmax":     EpilogueSpec(mask="causal", softmax=True),
    "attention_tail":     EpilogueSpec(scale=0.25, mask="causal",
                                       softmax=True),
    "scale_only":         EpilogueSpec(scale=2.0),
    "causal_only":        EpilogueSpec(mask="causal"),
    "scale_relu":         EpilogueSpec(scale=0.5, relu=True),
}


@pytest.mark.parametrize("name", sorted(SPECS))
@pytest.mark.parametrize("shape", [(128, 128, 128), (96, 64, 80),
                                   (40, 32, 200)])
def test_fused_tail_matches_oracle(name, shape):
    """matmul_padded with a fused tail == unfused oracle, including the
    non-block-multiple shapes where n_valid must keep the padded columns
    out of the softmax exp-sum."""
    m, k, n = shape
    a, b = _ab(m, k, n)
    spec = SPECS[name]
    got = matmul_padded(a, b, schedule=MatmulSchedule(bm=32, bk=32, bn=32),
                        epilogue=spec, interpret=True)
    ref = np.asarray(_oracle(a, b, spec))
    np.testing.assert_allclose(np.asarray(got), ref, **_tail_tol(k, ref))
    if spec.softmax:
        np.testing.assert_allclose(np.asarray(got).sum(-1),
                                   np.ones(m), **TOL)


def test_dense_softmax_entry_point():
    """dense -> softmax as one fused call (the LM-head pattern)."""
    x, w = _ab(8, 32, 50)       # vocab 50: forces the padded path
    got = dense_softmax(x, w, interpret=True)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(jax.nn.softmax(x @ w, -1)), **TOL)


def test_attention_probs_vs_flash_kernel():
    """Fused attention tail composed with @v equals the flash kernel —
    the (S, S) probability matrix from the fused path is the one flash
    never materializes."""
    s, d = 48, 16
    q = jax.random.normal(jax.random.PRNGKey(1), (s, d), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(2), (s, d), jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(3), (s, d), jnp.float32)
    probs = attention_probs(q, k, causal=True, interpret=True)
    ref = flash_attention_xla(q[None, None], k[None, None], v[None, None],
                              causal=True)[0, 0]
    np.testing.assert_allclose(np.asarray(probs @ v), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_attention_probs_noncausal_scale_default():
    s, d = 32, 16
    q, kk = _ab(s, d, d, seed=5)[0], jax.random.normal(
        jax.random.PRNGKey(6), (s, d), jnp.float32)
    got = attention_probs(q, kk, causal=False, interpret=True)
    ref = jax.nn.softmax((q @ kk.T) * d ** -0.5, -1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), **TOL)


def test_apply_matmul_epilogue_block_offsets():
    """row0/col0 place the causal mask correctly for an interior block."""
    acc = jnp.zeros((4, 4), jnp.float32)
    spec = EpilogueSpec(mask="causal")
    # block at rows 8..11, cols 8..11: diagonal crosses it
    out = apply_matmul_epilogue(acc, spec, row0=8, col0=8)
    want = jnp.where(jnp.arange(4)[:, None] >= jnp.arange(4)[None, :],
                     0.0, NEG_INF)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))
    # block fully below the diagonal: untouched
    out = apply_matmul_epilogue(acc, spec, row0=64, col0=0)
    np.testing.assert_array_equal(np.asarray(out), np.zeros((4, 4)))


# ---------------------------------------------------------------------------
# spec validation + jit-staticness + kernel constraint
# ---------------------------------------------------------------------------

def test_spec_validation():
    with pytest.raises(ValueError):
        EpilogueSpec(mask="sliding")               # unknown mask kind
    with pytest.raises(ValueError):
        EpilogueSpec(softmax=True, relu=True)      # softmax then relu: no-op
    with pytest.raises(ValueError):
        EpilogueSpec(softmax=True, concat_offset=0, concat_total=64)


def test_spec_is_hashable_jit_static():
    a = EpilogueSpec(scale=0.25, mask="causal", softmax=True)
    b = EpilogueSpec(scale=0.25, mask="causal", softmax=True)
    assert a == b and hash(a) == hash(b)
    assert a != IDENTITY
    assert a.has_matmul_tail and not IDENTITY.has_matmul_tail


def test_softmax_needs_single_n_block():
    a, b = _ab(32, 32, 64)
    with pytest.raises(ValueError, match="one N-block"):
        matmul_pallas(a, b, schedule=MatmulSchedule(bm=32, bk=32, bn=32),
                      epilogue=EpilogueSpec(softmax=True), interpret=True)


# ---------------------------------------------------------------------------
# cost-model pricing of the new stages
# ---------------------------------------------------------------------------

def test_epilogue_bytes_prices_matmul_tail():
    shape = (64, 128)           # logical (M, N) logits
    tensor = 64 * 128 * 4
    base = epilogue_bytes(shape)
    assert epilogue_bytes(shape, scale=True) - base == 2 * tensor
    assert epilogue_bytes(shape, mask=True) - base == 2 * tensor
    assert epilogue_bytes(shape, softmax=True) - base == 3 * tensor
    # full attention tail, unfused: 2 + 2 + 3 passes over the logits
    assert (epilogue_bytes(shape, scale=True, mask=True, softmax=True)
            - base == 7 * tensor)
    # fused: the tail runs on the accumulator-resident block — zero bytes
    assert epilogue_bytes(shape, scale=True, mask=True, softmax=True,
                          fused=True) == epilogue_bytes(shape, fused=True)
