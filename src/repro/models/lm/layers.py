"""Transformer building blocks: norms, RoPE, attention, MLP, MoE.

Attention is memory-bounded *flash attention written in JAX*: nested
``lax.scan`` over query and key/value chunks with an online softmax, so the
compiled HLO for a 32k-token prefill never materializes an (S, S) logits
tensor.  (The Pallas kernel in ``kernels/flash_attention.py`` is the
TPU-native instantiation of the same loop; the XLA path below is what the
dry-run lowers, shard-able by GSPMD.)

MoE uses the standard capacity-dropping formulation: tokens are ranked
within their chosen expert (sort-based, no (T, E, C) one-hot), scattered
into an (E, capacity, d) buffer, run through batched expert GEMMs sharded
on the expert axis, and combined with their top-k gates.
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.lm.config import LMConfig
from repro.models.lm.sharding import BATCH, shard_attn_q, shard_hint

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def rmsnorm(x: jnp.ndarray, w: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps)).astype(x.dtype) * w


def layernorm(x: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray,
              eps: float = 1e-6) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    mu = xf.mean(axis=-1, keepdims=True)
    var = jnp.square(xf - mu).mean(axis=-1, keepdims=True)
    return ((xf - mu) * jax.lax.rsqrt(var + eps)).astype(x.dtype) * w + b


def apply_norm(x: jnp.ndarray, p: Dict, cfg: LMConfig) -> jnp.ndarray:
    if cfg.norm == "layernorm":
        return layernorm(x, p["w"], p["b"], cfg.norm_eps)
    return rmsnorm(x, p["w"], cfg.norm_eps)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope(x: jnp.ndarray, positions: jnp.ndarray,
         theta: float = 10000.0) -> jnp.ndarray:
    """x: (B, S, H, D); positions: (B, S) or (S,)."""
    d = x.shape[-1]
    half = d // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    if positions.ndim == 1:
        positions = positions[None, :]
    ang = positions[..., None].astype(jnp.float32) * freqs   # (B, S, half)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Flash attention in XLA (nested-scan online softmax)
# ---------------------------------------------------------------------------

def flash_attention_xla(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                        causal: bool = True, window: int = 0,
                        q_chunk: int = 1024,
                        kv_chunk: int = 1024) -> jnp.ndarray:
    """q: (B, Hq, S, D); k, v: (B, Hkv, S, D).  Memory O(S·chunk), not O(S²)."""
    b, hq, s, d = q.shape
    hkv, sk = k.shape[1], k.shape[2]
    g = hq // hkv
    scale = 1.0 / math.sqrt(d)
    cq = min(q_chunk, s)
    ckv = min(kv_chunk, sk)
    pad_q = (-s) % cq
    pad_k = (-sk) % ckv
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    nq, nk = (s + pad_q) // cq, (sk + pad_k) // ckv

    # (n, B, Hkv, G|1, chunk, D) with the chunk index leading for scan
    qs = qp.reshape(b, hkv, g, nq, cq, d).transpose(3, 0, 1, 2, 4, 5)
    ks = kp.reshape(b, hkv, nk, ckv, d).transpose(2, 0, 1, 3, 4)
    vs = vp.reshape(b, hkv, nk, ckv, d).transpose(2, 0, 1, 3, 4)

    def q_step(_, qi_blk):
        qi, qblk = qi_blk                      # qblk: (B, Hkv, G, cq, D)
        q_pos = qi * cq + jnp.arange(cq)

        def kv_step(carry, ki_blk):
            m, l, acc = carry
            ki, kblk, vblk = ki_blk
            k_pos = ki * ckv + jnp.arange(ckv)
            logits = jnp.einsum(
                "bhgqd,bhkd->bhgqk", qblk.astype(jnp.float32),
                kblk.astype(jnp.float32),
                preferred_element_type=jnp.float32) * scale
            mask = (k_pos < sk)[None, :]
            if causal:
                mask = mask & (q_pos[:, None] >= k_pos[None, :])
            if window > 0:
                mask = mask & (q_pos[:, None] - k_pos[None, :] < window)
            logits = jnp.where(mask[None, None, None], logits, NEG_INF)
            m_new = jnp.maximum(m, logits.max(axis=-1))
            p = jnp.exp(logits - m_new[..., None])
            alpha = jnp.exp(m - m_new)
            l_new = l * alpha + p.sum(axis=-1)
            acc_new = acc * alpha[..., None] + jnp.einsum(
                "bhgqk,bhkd->bhgqd", p, vblk.astype(jnp.float32),
                preferred_element_type=jnp.float32)
            return (m_new, l_new, acc_new), None

        init = (jnp.full((b, hkv, g, cq), NEG_INF, jnp.float32),
                jnp.zeros((b, hkv, g, cq), jnp.float32),
                jnp.zeros((b, hkv, g, cq, d), jnp.float32))
        (m, l, acc), _ = jax.lax.scan(
            kv_step, init, (jnp.arange(nk), ks, vs))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return None, out.astype(q.dtype)

    _, outs = jax.lax.scan(q_step, None, (jnp.arange(nq), qs))
    # outs: (nq, B, Hkv, G, cq, D) -> (B, Hq, S, D)
    out = outs.transpose(1, 2, 3, 0, 4, 5).reshape(b, hq, s + pad_q, d)
    return out[:, :, :s]


def decode_attention(q: jnp.ndarray, k_cache: jnp.ndarray,
                     v_cache: jnp.ndarray, cache_len,
                     window: int = 0) -> jnp.ndarray:
    """Single-position attention against a (B, Hkv, S_max, D) cache.
    ``cache_len`` masks positions >= the currently valid length; a
    ``window`` > 0 also masks those < ``cache_len - window``, so the
    query at position ``p = cache_len - 1`` sees ``p - window < k <= p``
    (the rule ``flash_attention_xla`` applies in prefill)."""
    b, hq, one, d = q.shape
    hkv, smax = k_cache.shape[1], k_cache.shape[2]
    g = hq // hkv
    qg = q.reshape(b, hkv, g, d)
    logits = jnp.einsum("bhgd,bhkd->bhgk", qg.astype(jnp.float32),
                        k_cache.astype(jnp.float32)) / math.sqrt(d)
    k_pos = jnp.arange(smax)[None]
    cache_len = jnp.asarray(cache_len).reshape(-1, 1)
    valid = k_pos < cache_len
    if window > 0:
        valid = valid & (k_pos >= cache_len - window)
    logits = jnp.where(valid[:, None, None], logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgk,bhkd->bhgd", p, v_cache.astype(jnp.float32))
    return out.reshape(b, hq, 1, d).astype(q.dtype)


# ---------------------------------------------------------------------------
# Attention block (projections + rope + flash / cache paths)
# ---------------------------------------------------------------------------

def attention(x: jnp.ndarray, p: Dict, cfg: LMConfig, *,
              positions: jnp.ndarray, causal: bool = True, window: int = 0,
              kv_cache: Optional[Tuple] = None, cache_len=None,
              cross_kv: Optional[Tuple] = None,
              use_rope: bool = True):
    """x: (B, S, d).  Modes:
    * train/prefill: kv_cache None -> flash attention over x itself;
      returns (out, (k, v)) so prefill can seed a cache.
    * decode: kv_cache=(k, v) pre-updated with this token -> cache attention.
    * cross: cross_kv=(k, v) from the encoder (whisper) -> full attention,
      no causal mask."""
    b, s, dm = x.shape
    h, kv, hd = cfg.n_heads, cfg.n_kv, cfg.head_dim

    def proj(name, heads):
        y = x @ p[f"w{name}"]
        if cfg.qkv_bias and f"b{name}" in p:
            y = y + p[f"b{name}"]
        return y.reshape(b, s, heads, hd)

    q = proj("q", h)
    if cross_kv is None:
        key = proj("k", kv)
        val = proj("v", kv)
    else:
        key = val = None

    if use_rope and cross_kv is None:
        q = rope(q, positions, cfg.rope_theta)
        key = rope(key, positions, cfg.rope_theta)

    qt = q.transpose(0, 2, 1, 3)                       # (B, H, S, hd)
    if cross_kv is not None:
        ck, cvv = cross_kv                              # (B, Hkv, Senc, hd)
        out = flash_attention_xla(qt, ck, cvv, causal=False)
        new_kv = None
    elif kv_cache is not None:
        k_cache, v_cache = kv_cache
        out = decode_attention(qt, k_cache, v_cache, cache_len)
        new_kv = (key.transpose(0, 2, 1, 3), val.transpose(0, 2, 1, 3))
    else:
        kt = key.transpose(0, 2, 1, 3)
        vt = val.transpose(0, 2, 1, 3)
        # per-op activation-layout choice: heads on the model axis when
        # divisible, else sequence-parallel q (kv gathered; cheap for GQA)
        qt = shard_attn_q(qt, h)
        kt = shard_attn_q(kt, kv)
        vt = shard_attn_q(vt, kv)
        out = flash_attention_xla(qt, kt, vt, causal=causal, window=window,
                                  q_chunk=cfg.attn_q_chunk,
                                  kv_chunk=cfg.attn_kv_chunk)
        new_kv = (kt, vt)
    out = out.transpose(0, 2, 1, 3).reshape(b, s, h * hd) @ p["wo"]
    if cfg.proj_bias:
        out = out + p["bo"]
    return out, new_kv


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------

def mlp(x: jnp.ndarray, p: Dict, cfg: LMConfig) -> jnp.ndarray:
    def lin(name, h):
        y = h @ p[f"w{name}"]
        return y + p[f"b{name}"] if cfg.proj_bias else y

    if cfg.mlp_gated:
        return lin("d", jax.nn.silu(lin("g", x)) * lin("u", x))
    return lin("d", jax.nn.gelu(lin("u", x), approximate=True))


# ---------------------------------------------------------------------------
# Mixture of experts (capacity-dropping, sort-based dispatch)
# ---------------------------------------------------------------------------

def moe_capacity(n_tokens: int, cfg: LMConfig) -> int:
    cap = math.ceil(n_tokens * cfg.top_k / cfg.n_experts
                    * cfg.capacity_factor)
    # tiny token counts (decode steps) run dropless — the buffer is small
    # and drops would make serving non-deterministic vs prefill
    floor = n_tokens * cfg.top_k if n_tokens * cfg.top_k <= 64 else 1
    return max(floor, min(cap, n_tokens * cfg.top_k))


def moe_ffn(x: jnp.ndarray, p: Dict, cfg: LMConfig
            ) -> Tuple[jnp.ndarray, Dict]:
    """x: (T, d) token-major.  Returns (out, aux) where aux carries the
    load-balance loss term (Shazeer-style f·P) and router stats."""
    t, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    cap = moe_capacity(t, cfg)

    logits = x.astype(jnp.float32) @ p["router"].astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)                 # (T, E)
    gates, eids = jax.lax.top_k(probs, k)                   # (T, K)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)

    flat_e = eids.reshape(-1)                               # (T*K,)
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    starts = jnp.searchsorted(sorted_e, jnp.arange(e), side="left")
    pos_sorted = jnp.arange(t * k) - starts[sorted_e]
    pos = jnp.zeros_like(pos_sorted).at[order].set(pos_sorted)
    keep = pos < cap
    safe_pos = jnp.where(keep, pos, cap - 1)
    tok = jnp.arange(t * k) // k

    buf = jnp.zeros((e, cap, d), x.dtype)
    contrib = jnp.where(keep[:, None], x[tok], 0)
    buf = buf.at[flat_e, safe_pos].add(contrib)
    # expert-parallel buffer layout: E on the model axis, capacity rows on
    # the DP axes (the dispatch becomes the EP all-to-all)
    buf = shard_hint(buf, "model", BATCH, None)

    # batched expert GEMMs — sharded on the expert axis at the mesh level
    ex = p["experts"]
    if cfg.mlp_gated:
        hdn = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, ex["wg"])) \
            * jnp.einsum("ecd,edf->ecf", buf, ex["wu"])
    else:
        hdn = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", buf, ex["wu"]),
                          approximate=True)
    out_buf = jnp.einsum("ecf,efd->ecd", hdn, ex["wd"])

    y_tok = out_buf[flat_e, safe_pos] * keep[:, None]       # (T*K, d)
    y = (y_tok.reshape(t, k, d)
         * gates[..., None].astype(x.dtype)).sum(axis=1)

    # load-balance loss: E * sum_e fraction_routed(e) * mean_prob(e)
    f = jnp.zeros((e,), jnp.float32).at[flat_e].add(
        keep.astype(jnp.float32)) / (t * k)
    pbar = probs.mean(axis=0)
    aux = {"lb_loss": e * jnp.sum(f * pbar),
           "dropped_frac": 1.0 - keep.mean()}
    return y, aux
