"""Plain reference of the benchmark's LM configuration (StarCoder2-3B).

Written from the published description (Lozhkov et al. 2024,
arXiv:2402.19173) and its ``config.json``, whose key names the
configuration file keeps; nothing here comes from the program under test.
For each of ``num_hidden_layers`` layers, on the positions ``0..S-1`` of
one sequence:

    h  = LayerNorm1(x)                       (gain and shift, eps
                                              ``norm_epsilon``)
    q, k, v = h Wq + bq, h Wk + bk, h Wv + bv
    q, k = rope(q), rope(k)                  rotate-half, frequencies
                                             theta^(-2i/head_dim)
    a_p = softmax_k(q_p . k_k / sqrt(head_dim))   over p - W < k <= p
          (W = ``sliding_window``; query head j reads key/value head
          j // (heads / kv_heads))
    x  = x + (sum_k a_pk v_k) Wo + bo
    x  = x + gelu_tanh(LayerNorm2(x) Wu + bu) Wd + bd

then ``logits = LayerNorm_f(x) E^T`` with the embedding ``E`` tied.

``logits(cfg, params, tokens, at)`` computes in float32, every product
at "highest" precision: the reference.  It takes bfloat16 parameters
of the program's tree (the family draws them from the seed again) and
upcasts them one layer at a time inside the jitted layer, so that it
fits beside them on the chip, computes attention in blocks of
``QUERY_BLOCK`` queries against the explicit causal-and-window mask, and
applies the head only at the positions ``at``.  Sequences are padded at
the end to a multiple of ``QUERY_BLOCK`` (causal: the padding changes no
earlier position), or to ``pad_to``, and ``at`` to a multiple of
``HEAD_ROWS``, so that few shapes compile.

With ``control=True`` every matrix product's operands are first rounded
to ``float8_e4m3fn`` (``round_e4m3``: round to nearest even on its grid,
written out in float32 so that it computes the same on any backend):
the same forward one precision below the configuration's bfloat16, the
control that the correctness limit has to fail.

Departures from the published description: dropout (0.1 in the config)
is off, as at inference; the embeddings are tied (assumed, see the
configuration file).  The parameters are a tree of arrays read by name:
``embed``, ``final_norm`` {``w``, ``b``} and ``layers``, whose leaves
(``ln1``/``ln2`` {``w``, ``b``}, ``attn`` {``wq``, ``wk``, ``wv``,
``wo``, ``bq``, ``bk``, ``bv``, ``bo``}, ``mlp`` {``wu``, ``wd``, ``bu``,
``bd``}) carry a leading layer axis; a weight is ``(in, out)``.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HIGHEST = lax.Precision.HIGHEST
QUERY_BLOCK = 512
HEAD_ROWS = 64      # the head's positions are padded to a multiple of this
E4M3_MAX = 448.0


def round_e4m3(x: jnp.ndarray) -> jnp.ndarray:
    """``x`` (float32) rounded to the nearest ``float8_e4m3fn`` value, ties
    to even: 3 mantissa bits, exponents down to -6 and subnormal steps of
    2^-9 below, magnitudes clipped at 448 (no value here comes near)."""
    a = jnp.abs(x)
    _, e = jnp.frexp(a)                       # a = m 2^e, 0.5 <= m < 1
    step = jnp.ldexp(jnp.float32(1), jnp.maximum(e - 1, -6) - 3)
    r = jnp.minimum(jnp.round(a / step) * step, E4M3_MAX)
    return jnp.where(a > 0, jnp.sign(x) * r, 0.0).astype(jnp.float32)


def _matmul(a, b, control: bool):
    if control:
        a, b = round_e4m3(a), round_e4m3(b)
    return jnp.matmul(a, b, precision=HIGHEST)


def _einsum(spec, a, b, control: bool):
    if control:
        a, b = round_e4m3(a), round_e4m3(b)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def _layernorm(x, w, b, eps):
    mu = x.mean(-1, keepdims=True)
    var = jnp.square(x - mu).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * w + b


def _rope(x, theta):
    """x: (S, H, D) at positions 0..S-1."""
    s, _, d = x.shape
    half = d // 2
    inv = 1.0 / theta ** (np.arange(0, d, 2, dtype=np.float64) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] \
        * jnp.asarray(inv, jnp.float32)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


@functools.partial(jax.jit, static_argnames=("cfg", "control"))
def _layer(x, layers, i, *, cfg, control):
    c = dict(cfg)
    s, d = x.shape
    heads = c["num_attention_heads"]
    kvh = c["num_key_value_heads"]
    hd = d // heads
    w = c["sliding_window"]
    eps = c["norm_epsilon"]
    lp = _f32(jax.tree.map(lambda a: a[i], layers))   # this layer only
    at, mlp = lp["attn"], lp["mlp"]
    h = _layernorm(x, lp["ln1"]["w"], lp["ln1"]["b"], eps)
    q = (_matmul(h, at["wq"], control) + at["bq"]).reshape(s, heads, hd)
    k = (_matmul(h, at["wk"], control) + at["bk"]).reshape(s, kvh, hd)
    v = (_matmul(h, at["wv"], control) + at["bv"]).reshape(s, kvh, hd)
    q, k = _rope(q, c["rope_theta"]), _rope(k, c["rope_theta"])
    k = jnp.repeat(k, heads // kvh, axis=1)
    v = jnp.repeat(v, heads // kvh, axis=1)
    key_pos = jnp.arange(s)[None, :]
    out = []
    for a in range(0, s, QUERY_BLOCK):
        p = a + jnp.arange(QUERY_BLOCK)[:, None]
        ok = (key_pos <= p) & (key_pos > p - w)
        sc = _einsum("phd,khd->hpk", q[a:a + QUERY_BLOCK], k, control)
        sc = jnp.where(ok[None], sc / math.sqrt(hd), -jnp.inf)
        out.append(_einsum("hpk,khd->phd", jax.nn.softmax(sc, axis=-1), v,
                           control))
    att = jnp.concatenate(out).reshape(s, heads * hd)
    x = x + _matmul(att, at["wo"], control) + at["bo"]
    h = _layernorm(x, lp["ln2"]["w"], lp["ln2"]["b"], eps)
    y = jax.nn.gelu(_matmul(h, mlp["wu"], control) + mlp["bu"],
                    approximate=True)
    return x + _matmul(y, mlp["wd"], control) + mlp["bd"]


@functools.partial(jax.jit, static_argnames=("cfg", "control"))
def _head(x, at, final_norm, embed, *, cfg, control):
    fn = _f32(final_norm)
    h = _layernorm(x[at], fn["w"], fn["b"], dict(cfg)["norm_epsilon"])
    return _matmul(h, embed.astype(jnp.float32).T, control)


def logits(cfg: Dict, params, tokens: Sequence[int], at: Sequence[int],
           control: bool = False, pad_to: int = 0) -> np.ndarray:
    """Float32 logits ``(len(at), vocab)`` at the positions ``at`` of the
    sequence ``tokens``, padded at the end to at least ``pad_to``
    positions (one compiled shape for every sequence up to it); see the
    module's docstring."""
    keys = ("num_hidden_layers", "num_attention_heads",
            "num_key_value_heads", "sliding_window", "norm_epsilon",
            "rope_theta")
    static = tuple((k, cfg[k]) for k in keys)
    n = len(tokens)
    padded = -(-max(n, pad_to) // QUERY_BLOCK) * QUERY_BLOCK
    ids = np.zeros(padded, np.int32)
    ids[:n] = tokens
    x = params["embed"][jnp.asarray(ids)].astype(jnp.float32)
    for i in range(cfg["num_hidden_layers"]):
        x = _layer(x, params["layers"], jnp.int32(i), cfg=static,
                   control=control)
    rows = np.zeros(-(-len(at) // HEAD_ROWS) * HEAD_ROWS, np.int32)
    rows[:len(at)] = at
    out = _head(x, jnp.asarray(rows), params["final_norm"], params["embed"],
                cfg=static, control=control)
    return np.asarray(out)[:len(at)]
