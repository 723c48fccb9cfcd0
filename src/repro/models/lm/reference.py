"""Plain float32 reference of the dense decoder family, for the tests.

One sequence, no cache, no buckets, no flash blocking, no batching: the
forward pass as the published descriptions write it (StarCoder2,
arXiv:2402.19173, and its ``config.json``; the same block serves the
family's other members), in straightforward ``jax.numpy`` at float32
under ``jax.default_matmul_precision("highest")``.  For each layer:

    h  = norm1(x)
    q, k, v = h Wq + bq, h Wk + bk, h Wv + bv      (biases where configured)
    q, k = rope(q), rope(k)      rotate-half, frequencies theta^(-2i/d)
    a_p = softmax_k(q_p . k_k / sqrt(d))  over  p - W < k <= p
          (causal; W the sliding window, none where it is 0; each query
          head reads its group's key/value head)
    x  = x + (sum_k a_pk v_k) Wo + bo
    h  = norm2(x)
    x  = x + gelu_tanh(h Wu + bu) Wd + bd    or   (silu(h Wg) * h Wu) Wd

then ``logits = norm_f(x) E^T`` (tied) or ``norm_f(x) W_head``.  The norm
is LayerNorm with gain and shift, or RMSNorm with a gain, at the
configured epsilon.  The mask is built explicitly, one (S, S) array.

Departures from the published description: none in the mathematics.
Dropout (0.1 on embeddings, residuals and attention in StarCoder2's
config) is off, as it is at inference.  The parameters are the program's
tree (``models.lm.init_params``' layout, one leading layer axis), read
here by name only.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def _norm(x, p, cfg):
    if cfg.norm == "layernorm":
        mu = x.mean(-1, keepdims=True)
        var = jnp.square(x - mu).mean(-1, keepdims=True)
        return (x - mu) / jnp.sqrt(var + cfg.norm_eps) * p["w"] + p["b"]
    return x / jnp.sqrt(jnp.square(x).mean(-1, keepdims=True)
                        + cfg.norm_eps) * p["w"]


def _rope(x, theta):
    """x: (S, H, D), positions 0..S-1."""
    s, _, d = x.shape
    half = d // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def mask(s: int, window: int) -> jnp.ndarray:
    """(S, S) booleans: query ``p`` may read key ``k`` iff
    ``p - window < k <= p`` (causal alone where ``window`` is 0)."""
    p = jnp.arange(s)[:, None]
    k = jnp.arange(s)[None, :]
    ok = k <= p
    if window > 0:
        ok = ok & (k > p - window)
    return ok


def _linear(h, p, name, bias):
    y = h @ p[f"w{name}"]
    return y + p[f"b{name}"] if bias else y


def _layer(x, lp, cfg):
    s = x.shape[0]
    h_, kv, hd = cfg.n_heads, cfg.n_kv, cfg.head_dim
    a = lp["attn"]
    h = _norm(x, lp["ln1"], cfg)
    q = _linear(h, a, "q", cfg.qkv_bias).reshape(s, h_, hd)
    k = _linear(h, a, "k", cfg.qkv_bias).reshape(s, kv, hd)
    v = _linear(h, a, "v", cfg.qkv_bias).reshape(s, kv, hd)
    q, k = _rope(q, cfg.rope_theta), _rope(k, cfg.rope_theta)
    group = h_ // kv
    k = jnp.repeat(k, group, axis=1)          # query head j reads j // g
    v = jnp.repeat(v, group, axis=1)
    scores = jnp.einsum("phd,khd->hpk", q, k) / math.sqrt(hd)
    scores = jnp.where(mask(s, cfg.local_window)[None], scores, -jnp.inf)
    att = jnp.einsum("hpk,khd->phd", jax.nn.softmax(scores, axis=-1), v)
    x = x + _linear(att.reshape(s, h_ * hd), a, "o", cfg.proj_bias)
    m = lp["mlp"]
    h = _norm(x, lp["ln2"], cfg)
    if cfg.mlp_gated:
        y = jax.nn.silu(_linear(h, m, "g", cfg.proj_bias)) \
            * _linear(h, m, "u", cfg.proj_bias)
    else:
        y = jax.nn.gelu(_linear(h, m, "u", cfg.proj_bias), approximate=True)
    return x + _linear(y, m, "d", cfg.proj_bias)


def forward(params, cfg, tokens) -> jnp.ndarray:
    """Logits ``(S, vocab)`` of the 1-D token sequence ``tokens``, every
    parameter upcast to float32, every product at "highest"."""
    if cfg.family != "dense":
        raise ValueError(f"the reference covers the dense family, not "
                         f"{cfg.family!r}")
    f32 = jax.tree.map(lambda t: jnp.asarray(t, jnp.float32), params)
    with jax.default_matmul_precision("highest"):
        x = f32["embed"][jnp.asarray(tokens)]
        for i in range(cfg.n_layers):
            x = _layer(x, jax.tree.map(lambda t: t[i], f32["layers"]), cfg)
        x = _norm(x, f32["final_norm"], cfg)
        head = f32["embed"].T if cfg.tie_embeddings else f32["lm_head"]
        return x @ head
