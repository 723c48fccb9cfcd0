"""StarCoder2's dense block through the served path, against the plain
float32 reference (``models/lm/reference.py``).

At the reduced preset (2 layers, width 64, a sliding window of 8, biases
on every projection, LayerNorm at eps 1e-5, tied embeddings) with seeded
random weights whose biases, gains and shifts are all non-zero, and
sequences of 20-30 tokens, so that positions past the window exist:
prefill at a bucket, catch-up and decode through the cache, directly and
through ``AsyncServer.submit_stream``, compared on logits at every
generated position; the window's mask, one key at a time; and the
published entry's parameter count.
"""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, reduced
from repro.engine import AsyncServer, DynamicBatchPolicy, LMSession, compile_lm
from repro.models.lm import forward, init_params
from repro.models.lm import layers as L
from repro.models.lm import reference as R

PUBLISHED = ARCHS["starcoder2-3b"]
CFG = reduced(PUBLISHED)
MAX_LEN = 32
BUCKETS = [8, 16]
# The program and the reference both compute in float32 here; they differ
# only in the order of their sums (the program's online softmax over key
# blocks, its cache and its separate prefill and decode programs).  Over
# 2 layers of width 64 that moves a logit by about 1e-6 of the largest;
# one key too many or too few in a window of 8 moves it by 1e-2 or more.
LOGIT_TOL = 1e-4


def seeded_params(cfg, seed: int):
    """The program's tree with every bias and norm shift N(0, 0.02) and
    every norm gain U(0.5, 1.5), so that the comparison exercises them."""
    params = init_params(cfg, jax.random.PRNGKey(seed))
    leaves, tree = jax.tree_util.tree_flatten_with_path(params)
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(leaves))
    out = []
    for (path, leaf), k in zip(leaves, keys):
        name = path[-1].key
        parent = path[-2].key if len(path) > 1 else ""
        if parent.startswith("ln") or parent == "final_norm":
            if name == "w":
                leaf = jax.random.uniform(k, leaf.shape, leaf.dtype, 0.5, 1.5)
            else:
                leaf = 0.02 * jax.random.normal(k, leaf.shape, leaf.dtype)
        elif name.startswith("b"):
            leaf = 0.02 * jax.random.normal(k, leaf.shape, leaf.dtype)
        out.append(leaf)
    return jax.tree_util.tree_unflatten(tree, out)


PARAMS = seeded_params(CFG, 0)


def _prompt(n: int, seed: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    return rng.integers(0, CFG.vocab, size=(1, n)).astype(np.int32)


def logit_gap(served, ref) -> float:
    """The widest gap of a served logit over the reference's largest
    |logit|, over the positions compared."""
    served = np.asarray(served, np.float64)
    ref = np.asarray(ref, np.float64)
    return float((np.abs(served - ref).max(-1)
                  / np.abs(ref).max(-1)).max())


def _teacher_forced(prompt, tokens):
    """The reference's logits at the positions whose logits gave each
    generated token: the last prompt position and every generated one
    but the last."""
    seq = np.concatenate([prompt[0], tokens[0, :-1]])
    logits = R.forward(PARAMS, CFG, seq)
    return np.asarray(logits)[prompt.shape[1] - 1:]


@pytest.fixture(scope="module")
def session():
    return compile_lm(CFG, max_len=MAX_LEN, seq_buckets=BUCKETS,
                      params=PARAMS)


@pytest.mark.parametrize("prompt_len,new", [
    (20, 8),    # prefill bucket 16, 4 catch-up steps, decode past W
    (16, 10),   # exactly a bucket, decode to position 25
    (6, 20),    # below every bucket: the whole prompt through decode
    (27, 4),    # bucket 16, 11 catch-up steps, all past the window
])
def test_generate_matches_reference(session, prompt_len, new):
    prompt = _prompt(prompt_len, seed=prompt_len)
    tokens, logits = session.generate(prompt, new, keep_logits=True)
    assert len(logits) == new
    assert all(isinstance(lg, jax.Array) for lg in logits)
    served = np.stack([np.asarray(lg)[0] for lg in logits])
    gap = logit_gap(served, _teacher_forced(prompt, tokens))
    assert gap < LOGIT_TOL, gap
    # the tokens are the served logits' argmax
    np.testing.assert_array_equal(tokens[0], served.argmax(-1))


def test_submit_stream_keeps_logits(session):
    prompt = _prompt(23, seed=5)
    srv = AsyncServer(session, DynamicBatchPolicy(max_batch=1))
    try:
        before = srv.health()["lm"]
        plain = srv.submit_stream(prompt, 6)
        kept = srv.submit_stream(prompt, 6, keep_logits=True)
        streamed = [int(t[0]) for t in kept]
        tokens, logits = kept.result(timeout=120)
        np.testing.assert_array_equal(plain.result(timeout=120), tokens)
        after = srv.health()["lm"]
    finally:
        srv.close()
    assert streamed == list(tokens[0])
    served = np.stack([np.asarray(lg)[0] for lg in logits])
    assert logit_gap(served, _teacher_forced(prompt, tokens)) < LOGIT_TOL
    # two requests: bucket 16, 7 catch-up steps and 5 decode steps each
    assert {k: after[k] - before[k] for k in after} == {
        "requests": 2, "prompt_tokens": 46, "prefill_tokens": 32,
        "catchup_steps": 14, "decode_steps": 24, "generated_tokens": 12}


def test_window_masks_exactly_the_keys_outside_it():
    """One key at a time: with every score equal, each query averages the
    values of exactly the keys it may read, and a value that is the
    one-hot of its key's position shows which those are:
    ``p - W < k <= p``, in prefill (blocks of 4 across the window of 8)
    and in decode against a longer cache."""
    w, s, d = CFG.local_window, 24, 32
    q = jnp.zeros((1, 1, s, d))
    k = jnp.zeros((1, 1, s, d))
    v = jnp.eye(s, d)[None, None]
    want = np.asarray(R.mask(s, w), np.float64)
    want /= want.sum(1, keepdims=True)
    got = L.flash_attention_xla(q, k, v, window=w, q_chunk=4, kv_chunk=4)
    np.testing.assert_allclose(np.asarray(got)[0, 0, :, :s], want,
                               atol=1e-6)
    cache_k = jnp.zeros((1, 1, MAX_LEN, d))
    cache_v = jnp.eye(MAX_LEN, d)[None, None]
    for p in range(s):
        row = L.decode_attention(q[:, :, p:p + 1], cache_k, cache_v, p + 1,
                                 window=w)
        np.testing.assert_allclose(np.asarray(row)[0, 0, 0, :s], want[p],
                                   atol=1e-6, err_msg=f"position {p}")
    # and both forwards apply it: the last position of a stack of n
    # layers reads n (W - 1) positions back, and no further
    toks = _prompt(s, seed=9)
    far = s - 2 - CFG.n_layers * (w - 1)
    other = toks.copy()
    other[0, far] = (other[0, far] + 1) % CFG.vocab
    for fwd in (lambda t: R.forward(PARAMS, CFG, t[0]),
                lambda t: forward(PARAMS, CFG, t)[0][0]):
        a, b = (np.asarray(fwd(t))[-1] for t in (toks, other))
        np.testing.assert_array_equal(a, b)
        other[0, far + 1] = (other[0, far + 1] + 1) % CFG.vocab
        assert not np.array_equal(a, np.asarray(fwd(other))[-1])
        other[0, far + 1] = toks[0, far + 1]


def test_published_param_count():
    """3,030,371,328 parameters, from the published widths: per layer
    q and o 3072^2 each, k and v 3072 x 256 each, the MLP 2 x 3072 x
    12288, their biases (3072 + 256 + 256 + 3072 + 12288 + 3072) and two
    LayerNorms (2 x 2 x 3072); then the tied 49152 x 3072 embedding and
    the final LayerNorm."""
    d, ff, kv = 3072, 12288, 256
    per_layer = (2 * d * d + 2 * d * kv + 2 * d * ff
                 + (d + 2 * kv + d + ff + d) + 4 * d)
    want = 30 * per_layer + 49152 * d + 2 * d
    assert want == 3_030_371_328
    assert PUBLISHED.param_count() == want
    assert (PUBLISHED.local_window, PUBLISHED.norm_eps) == (4096, 1e-5)
    assert PUBLISHED.tie_embeddings and PUBLISHED.proj_bias
    assert math.isclose(PUBLISHED.rope_theta, 999999.4420358813)


def test_loaded_session_logits_equal_saved(tmp_path):
    """bfloat16 weights through save and load: the restore template is
    shapes only, the leaves come back as bfloat16 on the device, and the
    loaded session's logits are the saved session's, bit for bit."""
    cfg = dataclasses.replace(CFG, dtype="bfloat16")
    params = jax.tree.map(lambda a: a.astype(jnp.bfloat16), PARAMS)
    sess = compile_lm(cfg, max_len=MAX_LEN, seq_buckets=BUCKETS,
                      params=params)
    prompt = _prompt(21, seed=2)
    want, want_logits = sess.generate(prompt, 5, keep_logits=True)
    loaded = LMSession.load(sess.save(tmp_path / "lm"))
    leaves = jax.tree.leaves(loaded.params)
    assert all(isinstance(a, jax.Array) and a.dtype == jnp.bfloat16
               for a in leaves)
    got, got_logits = loaded.generate(prompt, 5, keep_logits=True)
    np.testing.assert_array_equal(got, want)
    for a, b in zip(got_logits, want_logits):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
