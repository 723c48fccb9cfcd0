"""LM model: parameter init, forward, loss, prefill, decode — all families.

Design points (production-shaped):

* Homogeneous stacks (dense / moe / ssm / vlm) hold parameters stacked with
  a leading layer axis and run under ``lax.scan`` — compact HLO, fast
  compiles even for the 61-layer / 1T-param config, optional per-layer
  remat (``cfg.remat``).
* Heterogeneous stacks (hybrid's 1:2 recurrent:attention pattern, whisper's
  encoder-decoder) run as Python loops over per-layer parameter lists.
* Every activation passes through ``sharding.shard_hint`` so one model
  definition serves the single-host smoke tests (hints no-op) and the
  512-chip dry-run (hints become GSPMD constraints).
* Decode paths carry explicit caches (KV / SSM state / LRU state / ring
  buffers for local attention) updated functionally.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.lm import layers as L
from repro.models.lm import rglru, ssm
from repro.models.lm.config import LMConfig
from repro.models.lm.sharding import BATCH, shard_hint

MODEL = "model"


def _dt(cfg: LMConfig):
    return jnp.dtype(cfg.dtype)


# ===========================================================================
# Parameter initialization
# ===========================================================================

def _mat(key, shape, cfg, scale=None):
    scale = scale if scale is not None else 1.0 / math.sqrt(shape[0])
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(
        _dt(cfg))


def _norm_p(cfg: LMConfig, d: int) -> Dict:
    p = {"w": jnp.ones((d,), _dt(cfg))}
    if cfg.norm == "layernorm":
        p["b"] = jnp.zeros((d,), _dt(cfg))
    return p


def _attn_p(key, cfg: LMConfig, cross: bool = False) -> Dict:
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv, cfg.head_dim
    ks = jax.random.split(key, 4)
    p = {"wq": _mat(ks[0], (d, h * hd), cfg),
         "wk": _mat(ks[1], (d, kv * hd), cfg),
         "wv": _mat(ks[2], (d, kv * hd), cfg),
         "wo": _mat(ks[3], (h * hd, d), cfg)}
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((h * hd,), _dt(cfg))
        p["bk"] = jnp.zeros((kv * hd,), _dt(cfg))
        p["bv"] = jnp.zeros((kv * hd,), _dt(cfg))
    if cfg.proj_bias:
        p["bo"] = jnp.zeros((d,), _dt(cfg))
    return p


def _mlp_p(key, cfg: LMConfig, d_ff: int) -> Dict:
    d = cfg.d_model
    ks = jax.random.split(key, 3)
    if cfg.mlp_gated:
        p = {"wg": _mat(ks[0], (d, d_ff), cfg),
             "wu": _mat(ks[1], (d, d_ff), cfg),
             "wd": _mat(ks[2], (d_ff, d), cfg)}
    else:
        p = {"wu": _mat(ks[0], (d, d_ff), cfg),
             "wd": _mat(ks[1], (d_ff, d), cfg)}
    if cfg.proj_bias:
        p.update({f"b{k[1]}": jnp.zeros((v.shape[1],), _dt(cfg))
                  for k, v in p.items()})
    return p


def _moe_p(key, cfg: LMConfig) -> Dict:
    d, e, f = cfg.d_model, cfg.n_experts, cfg.moe_d_ff
    ks = jax.random.split(key, 5)
    p = {"router": _mat(ks[0], (d, e), cfg, scale=0.02)}
    experts = {"wu": _mat(ks[1], (e, d, f), cfg),
               "wd": _mat(ks[2], (e, f, d), cfg, scale=1 / math.sqrt(f))}
    if cfg.mlp_gated:
        experts["wg"] = _mat(ks[3], (e, d, f), cfg)
    p["experts"] = experts
    if cfg.n_shared_experts:
        p["shared"] = _mlp_p(ks[4], cfg,
                             cfg.moe_d_ff * cfg.n_shared_experts)
    if cfg.dense_residual:
        p["dense"] = _mlp_p(jax.random.fold_in(key, 7), cfg, cfg.d_ff)
    return p


def _dense_layer_p(key, cfg: LMConfig) -> Dict:
    ks = jax.random.split(key, 2)
    p = {"ln1": _norm_p(cfg, cfg.d_model), "attn": _attn_p(ks[0], cfg),
         "ln2": _norm_p(cfg, cfg.d_model)}
    if cfg.family == "moe":
        p["moe"] = _moe_p(ks[1], cfg)
    else:
        p["mlp"] = _mlp_p(ks[1], cfg, cfg.d_ff)
    return p


def _ssm_layer_p(key, cfg: LMConfig) -> Dict:
    d, di, n, nh = cfg.d_model, cfg.d_inner, cfg.ssm_state, cfg.ssm_heads
    ks = jax.random.split(key, 3)
    return {
        "norm": _norm_p(cfg, d),
        "in_proj": _mat(ks[0], (d, 2 * di + 2 * n + nh), cfg),
        "conv_w": _mat(ks[1], (cfg.conv_kernel, di + 2 * n), cfg, scale=0.5),
        "dt_bias": jnp.zeros((nh,), jnp.float32),
        "a_log": jnp.zeros((nh,), jnp.float32),     # A = -1
        "d_skip": jnp.ones((nh,), jnp.float32),
        "norm_w": jnp.ones((di,), _dt(cfg)),
        "out_proj": _mat(ks[2], (di, d), cfg),
    }


def _rec_layer_p(key, cfg: LMConfig) -> Dict:
    d, w = cfg.d_model, cfg.lru_width
    ks = jax.random.split(key, 5)
    p = {
        "wx": _mat(ks[0], (d, w), cfg),
        "wy": _mat(ks[1], (d, w), cfg),
        "conv_w": _mat(ks[2], (cfg.conv_kernel, w), cfg, scale=0.5),
        # init so a ~ U(0.9, 0.999)^(1/c) region (Griffin's Λ init)
        "lam": jnp.asarray(
            jnp.linspace(0.5, 2.0, w), jnp.float32),
        "wo": _mat(jax.random.fold_in(key, 9), (w, d), cfg),
    }
    if cfg.fused_gates:
        p["w_gates"] = _mat(ks[3], (w, 2 * w), cfg)
        p["b_gates"] = jnp.zeros((2 * w,), _dt(cfg))
    else:
        p["w_in_gate"] = _mat(ks[3], (w, w), cfg)
        p["b_in_gate"] = jnp.zeros((w,), _dt(cfg))
        p["w_rec_gate"] = _mat(ks[4], (w, w), cfg)
        p["b_rec_gate"] = jnp.zeros((w,), _dt(cfg))
    return p


def _hybrid_layer_p(key, cfg: LMConfig, kind: str) -> Dict:
    # NOTE: layer kind is a config property (cfg.layer_kind(i)), never a
    # param leaf — params stay a pure array pytree for optimizers/checkpoint.
    ks = jax.random.split(key, 2)
    p = {"ln1": _norm_p(cfg, cfg.d_model), "ln2": _norm_p(cfg, cfg.d_model),
         "mlp": _mlp_p(ks[1], cfg, cfg.d_ff)}
    if kind == "attn":
        p["attn"] = _attn_p(ks[0], cfg)
    else:
        p["rec"] = _rec_layer_p(ks[0], cfg)
    return p


def _encdec_layer_p(key, cfg: LMConfig, cross: bool) -> Dict:
    ks = jax.random.split(key, 3)
    p = {"ln1": _norm_p(cfg, cfg.d_model), "attn": _attn_p(ks[0], cfg),
         "ln2": _norm_p(cfg, cfg.d_model),
         "mlp": _mlp_p(ks[1], cfg, cfg.d_ff)}
    if cross:
        p["ln_x"] = _norm_p(cfg, cfg.d_model)
        p["xattn"] = _attn_p(ks[2], cfg)
    return p


def init_params(cfg: LMConfig, key) -> Dict:
    keys = jax.random.split(key, 8)
    p: Dict[str, Any] = {
        "embed": _mat(keys[0], (cfg.vocab, cfg.d_model), cfg, scale=0.02),
        "final_norm": _norm_p(cfg, cfg.d_model),
    }
    if not cfg.tie_embeddings:
        p["lm_head"] = _mat(keys[1], (cfg.d_model, cfg.vocab), cfg)

    if cfg.family in ("dense", "moe", "vlm"):
        lkeys = jax.random.split(keys[2], cfg.n_layers)
        p["layers"] = jax.vmap(lambda k: _dense_layer_p(k, cfg))(lkeys)
    elif cfg.family == "ssm":
        lkeys = jax.random.split(keys[2], cfg.n_layers)
        p["layers"] = jax.vmap(lambda k: _ssm_layer_p(k, cfg))(lkeys)
    elif cfg.family == "hybrid":
        lkeys = jax.random.split(keys[2], cfg.n_layers)
        p["layers_list"] = [
            _hybrid_layer_p(lkeys[i], cfg, cfg.layer_kind(i))
            for i in range(cfg.n_layers)]
        # (kind per index comes from cfg.layer_kind; params stay array-only)
    elif cfg.family == "encdec":
        ekeys = jax.random.split(keys[2], cfg.enc_layers)
        dkeys = jax.random.split(keys[3], cfg.n_layers)
        p["enc_layers"] = [_encdec_layer_p(k, cfg, cross=False)
                           for k in ekeys]
        p["dec_layers"] = [_encdec_layer_p(k, cfg, cross=True)
                           for k in dkeys]
        p["enc_pos"] = _mat(keys[4], (cfg.enc_positions, cfg.d_model), cfg,
                            scale=0.02)
        p["enc_norm"] = _norm_p(cfg, cfg.d_model)
    return p


# ===========================================================================
# Forward passes
# ===========================================================================

def _dense_layer_fwd(x, lp, cfg: LMConfig, positions):
    h = L.apply_norm(x, lp["ln1"], cfg)
    h = shard_hint(h, BATCH, None, None)
    attn_out, _ = L.attention(h, lp["attn"], cfg, positions=positions,
                              window=cfg.local_window)
    x = x + attn_out
    h = L.apply_norm(x, lp["ln2"], cfg)
    aux = jnp.zeros((), jnp.float32)
    if cfg.family == "moe":
        b, s, d = h.shape
        flat = h.reshape(b * s, d)
        y, moe_aux = L.moe_ffn(flat, lp["moe"], cfg)
        if "shared" in lp:
            y = y + L.mlp(flat, lp["shared"], cfg)
        if "dense" in lp:
            y = y + L.mlp(flat, lp["dense"], cfg)
        y = y.reshape(b, s, d)
        aux = moe_aux["lb_loss"]
    else:
        y = L.mlp(h, lp["mlp"], cfg)
    x = x + y
    return shard_hint(x, BATCH, None, None), aux


def _run_stacked(params, cfg: LMConfig, x, positions, collect_kv=False):
    """lax.scan over the stacked layer params."""

    def body(carry, lp):
        if cfg.family == "ssm":
            normed = L.apply_norm(carry, lp["norm"], cfg)
            out, _ = ssm.mamba2_layer(normed, lp, cfg)
            return carry + out, jnp.zeros((), jnp.float32)
        return _dense_layer_fwd(carry, lp, cfg, positions)

    def body_kv(carry, lp):
        # dense-family prefill: also emit this layer's rope'd K/V
        h = L.apply_norm(carry, lp["ln1"], cfg)
        attn_out, kv = L.attention(h, lp["attn"], cfg, positions=positions,
                                   window=cfg.local_window)
        x2 = carry + attn_out
        h2 = L.apply_norm(x2, lp["ln2"], cfg)
        if cfg.family == "moe":
            b, s, d = h2.shape
            y, _ = L.moe_ffn(h2.reshape(b * s, d), lp["moe"], cfg)
            if "shared" in lp:
                y = y + L.mlp(h2.reshape(b * s, d), lp["shared"], cfg)
            if "dense" in lp:
                y = y + L.mlp(h2.reshape(b * s, d), lp["dense"], cfg)
            y = y.reshape(b, s, d)
        else:
            y = L.mlp(h2, lp["mlp"], cfg)
        return x2 + y, kv

    fn = body_kv if collect_kv else body
    if cfg.remat:
        if cfg.remat_policy == "dots":
            # save matmul outputs: backward re-does only elementwise work,
            # so the forward's TP collectives are not re-issued (§Perf)
            fn = jax.checkpoint(
                fn, policy=jax.checkpoint_policies
                .dots_with_no_batch_dims_saveable)
        else:
            fn = jax.checkpoint(fn)
    unroll = cfg.n_layers if cfg.unroll_layers else 1
    x, ys = jax.lax.scan(fn, x, params["layers"], unroll=unroll)
    return x, ys


def _hybrid_fwd(params, cfg: LMConfig, x, positions):
    def one_layer(kind, x, lp):
        h = L.apply_norm(x, lp["ln1"], cfg)
        if kind == "attn":
            out, _ = L.attention(h, lp["attn"], cfg, positions=positions,
                                 window=cfg.local_window)
        else:
            out, _ = rglru.recurrent_block(h, lp["rec"], cfg)
        x = x + out
        h = L.apply_norm(x, lp["ln2"], cfg)
        return x + L.mlp(h, lp["mlp"], cfg)

    for i, lp in enumerate(params["layers_list"]):
        fn = functools.partial(one_layer, cfg.layer_kind(i))
        if cfg.remat:
            if cfg.remat_policy == "dots":
                fn = jax.checkpoint(
                    fn, policy=jax.checkpoint_policies
                    .dots_with_no_batch_dims_saveable)
            else:
                fn = jax.checkpoint(fn)
        x = fn(x, lp)
    return x


def _encode(params, cfg: LMConfig, frames):
    """Whisper encoder over (stub-frontend) frame embeddings."""
    s = frames.shape[1]
    x = frames.astype(_dt(cfg)) + params["enc_pos"][None, :s]
    pos = jnp.arange(s)
    for lp in params["enc_layers"]:
        h = L.apply_norm(x, lp["ln1"], cfg)
        out, _ = L.attention(h, lp["attn"], cfg, positions=pos,
                             causal=False, use_rope=False)
        x = x + out
        h = L.apply_norm(x, lp["ln2"], cfg)
        x = x + L.mlp(h, lp["mlp"], cfg)
    return L.apply_norm(x, params["enc_norm"], cfg)


def _cross_kv(lp, cfg: LMConfig, enc_out):
    b, s, _ = enc_out.shape
    kv, hd = cfg.n_kv, cfg.head_dim
    k = (enc_out @ lp["xattn"]["wk"]).reshape(b, s, kv, hd)
    v = (enc_out @ lp["xattn"]["wv"]).reshape(b, s, kv, hd)
    return k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)


def _decoder_fwd(params, cfg: LMConfig, x, positions, enc_out):
    for lp in params["dec_layers"]:
        h = L.apply_norm(x, lp["ln1"], cfg)
        out, _ = L.attention(h, lp["attn"], cfg, positions=positions)
        x = x + out
        h = L.apply_norm(x, lp["ln_x"], cfg)
        out, _ = L.attention(h, lp["xattn"], cfg, positions=positions,
                             cross_kv=_cross_kv(lp, cfg, enc_out))
        x = x + out
        h = L.apply_norm(x, lp["ln2"], cfg)
        x = x + L.mlp(h, lp["mlp"], cfg)
    return x


def _logits(params, cfg: LMConfig, x):
    x = L.apply_norm(x, params["final_norm"], cfg)
    if cfg.tie_embeddings:
        out = x @ params["embed"].T
    else:
        out = x @ params["lm_head"]
    return shard_hint(out, BATCH, None, MODEL)


def forward(params, cfg: LMConfig, tokens, *, img_embeds=None, frames=None):
    """tokens: (B, S_text) int32.  Returns logits (B, S_total, V) and the
    scalar MoE aux loss."""
    x = params["embed"][tokens]
    if cfg.family == "vlm":
        assert img_embeds is not None
        x = jnp.concatenate([img_embeds.astype(_dt(cfg)), x], axis=1)
    x = shard_hint(x, BATCH, None, None)
    positions = jnp.arange(x.shape[1])
    aux = jnp.zeros((), jnp.float32)
    if cfg.family in ("dense", "moe", "vlm", "ssm"):
        x, auxs = _run_stacked(params, cfg, x, positions)
        aux = auxs.sum() if cfg.family == "moe" else aux
    elif cfg.family == "hybrid":
        x = _hybrid_fwd(params, cfg, x, positions)
    elif cfg.family == "encdec":
        assert frames is not None
        enc_out = _encode(params, cfg, frames)
        x = _decoder_fwd(params, cfg, x, positions, enc_out)
    return _logits(params, cfg, x), aux


# ===========================================================================
# Loss
# ===========================================================================

def loss_fn(params, cfg: LMConfig, batch) -> Tuple[jnp.ndarray, Dict]:
    logits, aux = forward(
        params, cfg, batch["tokens"],
        img_embeds=batch.get("img_embeds"), frames=batch.get("frames"))
    targets = batch["targets"]
    if cfg.family == "vlm":           # loss on text positions only
        logits = logits[:, -targets.shape[1]:]
    logits = logits.astype(jnp.float32)
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None],
                               axis=-1)[..., 0]
    mask = (targets >= 0).astype(jnp.float32)
    ce = ((logz - gold) * mask).sum() / jnp.maximum(mask.sum(), 1.0)
    total = ce + 0.01 * aux
    return total, {"ce": ce, "aux": aux}


# ===========================================================================
# Serving: cache init / prefill / decode
# ===========================================================================

def init_cache(cfg: LMConfig, batch: int, max_len: int) -> Dict:
    dt = _dt(cfg)
    if cfg.family in ("dense", "moe", "vlm"):
        shape = (cfg.n_layers, batch, cfg.n_kv, max_len, cfg.head_dim)
        return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}
    if cfg.family == "ssm":
        return {
            "ssm": jnp.zeros((cfg.n_layers, batch, cfg.ssm_heads,
                              cfg.ssm_head_dim, cfg.ssm_state), jnp.float32),
            "conv": jnp.zeros((cfg.n_layers, batch, cfg.conv_kernel - 1,
                               cfg.d_inner + 2 * cfg.ssm_state), dt)}
    if cfg.family == "hybrid":
        cache = []
        for i in range(cfg.n_layers):
            if cfg.layer_kind(i) == "attn":
                w = min(cfg.local_window, max_len)
                cache.append({
                    "k": jnp.zeros((batch, cfg.n_kv, w, cfg.head_dim), dt),
                    "v": jnp.zeros((batch, cfg.n_kv, w, cfg.head_dim), dt)})
            else:
                cache.append({
                    "lru": jnp.zeros((batch, cfg.lru_width), jnp.float32),
                    "conv": jnp.zeros((batch, cfg.conv_kernel - 1,
                                       cfg.lru_width), dt)})
        return {"layers": cache}
    if cfg.family == "encdec":
        shape = (batch, cfg.n_kv, max_len, cfg.head_dim)
        xshape = (batch, cfg.n_kv, cfg.enc_positions, cfg.head_dim)
        return {"self": [{"k": jnp.zeros(shape, dt),
                          "v": jnp.zeros(shape, dt)}
                         for _ in range(cfg.n_layers)],
                "cross": [{"k": jnp.zeros(xshape, dt),
                           "v": jnp.zeros(xshape, dt)}
                          for _ in range(cfg.n_layers)]}
    raise NotImplementedError(cfg.family)


def _write_kv(kc, vc, new_kv, pos):
    k_t, v_t = new_kv                       # (B, Hkv, S_new, hd)
    kc = jax.lax.dynamic_update_slice(kc, k_t.astype(kc.dtype),
                                      (0, 0, pos, 0))
    vc = jax.lax.dynamic_update_slice(vc, v_t.astype(vc.dtype),
                                      (0, 0, pos, 0))
    return kc, vc


def prefill(params, cfg: LMConfig, tokens, *, max_len: int,
            img_embeds=None, frames=None):
    """Full forward that also populates a fresh cache of size ``max_len``.
    Returns (cache, last-position logits)."""
    bsz = tokens.shape[0]
    cache = init_cache(cfg, bsz, max_len)
    x = params["embed"][tokens]
    if cfg.family == "vlm" and img_embeds is not None:
        x = jnp.concatenate([img_embeds.astype(_dt(cfg)), x], axis=1)
    x = shard_hint(x, BATCH, None, None)
    s = x.shape[1]
    positions = jnp.arange(s)

    if cfg.family in ("dense", "moe", "vlm"):
        x, kv = _run_stacked(params, cfg, x, positions, collect_kv=True)
        ks, vs = kv                               # (L, B, Hkv, S, hd)
        cache["k"] = jax.lax.dynamic_update_slice(
            cache["k"], ks.astype(cache["k"].dtype), (0, 0, 0, 0, 0))
        cache["v"] = jax.lax.dynamic_update_slice(
            cache["v"], vs.astype(cache["v"].dtype), (0, 0, 0, 0, 0))
    elif cfg.family == "ssm":
        ssm_states, conv_states = [], []
        for i in range(cfg.n_layers):
            lp = jax.tree.map(lambda a: a[i], params["layers"])
            normed = L.apply_norm(x, lp["norm"], cfg)
            out, (s_new, c_new) = ssm.mamba2_layer(normed, lp, cfg)
            x = x + out
            ssm_states.append(s_new)
            conv_states.append(c_new)
        cache["ssm"] = jnp.stack(ssm_states)
        cache["conv"] = jnp.stack(conv_states)
    elif cfg.family == "hybrid":
        w = min(cfg.local_window, max_len)
        for i, lp in enumerate(params["layers_list"]):
            h = L.apply_norm(x, lp["ln1"], cfg)
            if cfg.layer_kind(i) == "attn":
                out, kv = L.attention(h, lp["attn"], cfg,
                                      positions=positions,
                                      window=cfg.local_window)
                kt, vt = kv
                if s >= w:
                    # ring-buffer layout: position p lives at slot p % w
                    roll = s % w
                    cache["layers"][i]["k"] = jnp.roll(
                        kt[:, :, -w:], roll, axis=2).astype(_dt(cfg))
                    cache["layers"][i]["v"] = jnp.roll(
                        vt[:, :, -w:], roll, axis=2).astype(_dt(cfg))
                else:
                    cache["layers"][i]["k"], cache["layers"][i]["v"] = \
                        _write_kv(cache["layers"][i]["k"],
                                  cache["layers"][i]["v"], kv, 0)
            else:
                out, (lru, conv) = rglru.recurrent_block(h, lp["rec"], cfg)
                cache["layers"][i]["lru"] = lru
                cache["layers"][i]["conv"] = conv
            x = x + out
            h = L.apply_norm(x, lp["ln2"], cfg)
            x = x + L.mlp(h, lp["mlp"], cfg)
    elif cfg.family == "encdec":
        enc_out = _encode(params, cfg, frames)
        for i, lp in enumerate(params["dec_layers"]):
            h = L.apply_norm(x, lp["ln1"], cfg)
            out, kv = L.attention(h, lp["attn"], cfg, positions=positions)
            cache["self"][i]["k"], cache["self"][i]["v"] = _write_kv(
                cache["self"][i]["k"], cache["self"][i]["v"], kv, 0)
            x = x + out
            ck, cv = _cross_kv(lp, cfg, enc_out)
            cache["cross"][i]["k"] = ck.astype(_dt(cfg))
            cache["cross"][i]["v"] = cv.astype(_dt(cfg))
            h = L.apply_norm(x, lp["ln_x"], cfg)
            out, _ = L.attention(h, lp["xattn"], cfg, positions=positions,
                                 cross_kv=(ck, cv))
            x = x + out
            h = L.apply_norm(x, lp["ln2"], cfg)
            x = x + L.mlp(h, lp["mlp"], cfg)

    # the head at the last position only: the norm is per position
    return cache, _logits(params, cfg, x[:, -1:])[:, 0]


def _token_attn_decode(h, lp_attn, cfg, kc, vc, pos, cache_len, ring=0,
                       window=0):
    """One-token attention against (and updating) a cache: a ring of
    ``ring`` slots (position ``p`` at slot ``p % ring``), or a contiguous
    cache whose keys outside a sliding ``window`` are masked."""
    b = h.shape[0]
    kv, hd, hq = cfg.n_kv, cfg.head_dim, cfg.n_heads
    q = (h @ lp_attn["wq"])
    k = (h @ lp_attn["wk"])
    v = (h @ lp_attn["wv"])
    if cfg.qkv_bias and "bq" in lp_attn:
        q, k, v = q + lp_attn["bq"], k + lp_attn["bk"], v + lp_attn["bv"]
    q = q.reshape(b, 1, hq, hd)
    k = k.reshape(b, 1, kv, hd)
    v = v.reshape(b, 1, kv, hd)
    posv = jnp.full((b, 1), pos)
    q = L.rope(q, posv, cfg.rope_theta)
    k = L.rope(k, posv, cfg.rope_theta)
    write_at = pos % ring if ring else pos
    kc, vc = _write_kv(kc, vc, (k.transpose(0, 2, 1, 3),
                                v.transpose(0, 2, 1, 3)), write_at)
    out = L.decode_attention(q.transpose(0, 2, 1, 3), kc, vc, cache_len,
                             window=window)
    out = out.transpose(0, 2, 1, 3).reshape(b, 1, hq * hd) @ lp_attn["wo"]
    if cfg.proj_bias:
        out = out + lp_attn["bo"]
    return out, kc, vc


def decode_step(params, cfg: LMConfig, token, cache, pos):
    """token: (B, 1) int32; pos: scalar int32 (current position index).
    Returns (logits (B, V), new cache)."""
    x = params["embed"][token]
    x = shard_hint(x, BATCH, None, None)
    cache_len = pos + 1

    if cfg.family in ("dense", "moe", "vlm"):
        def body(carry, lp_kc_vc):
            h_in = carry
            lp, kc, vc = lp_kc_vc
            h = L.apply_norm(h_in, lp["ln1"], cfg)
            out, kc, vc = _token_attn_decode(h, lp["attn"], cfg, kc, vc,
                                             pos, cache_len,
                                             window=cfg.local_window)
            x2 = h_in + out
            h = L.apply_norm(x2, lp["ln2"], cfg)
            if cfg.family == "moe":
                b, s, d = h.shape
                y, _ = L.moe_ffn(h.reshape(b * s, d), lp["moe"], cfg)
                if "shared" in lp:
                    y = y + L.mlp(h.reshape(b * s, d), lp["shared"], cfg)
                if "dense" in lp:
                    y = y + L.mlp(h.reshape(b * s, d), lp["dense"], cfg)
                y = y.reshape(b, s, d)
            else:
                y = L.mlp(h, lp["mlp"], cfg)
            return x2 + y, (kc, vc)

        x, (new_k, new_v) = jax.lax.scan(
            body, x, (params["layers"], cache["k"], cache["v"]))
        cache = {"k": new_k, "v": new_v}
    elif cfg.family == "ssm":
        def body(carry, lp_states):
            lp, s_st, c_st = lp_states
            normed = L.apply_norm(carry, lp["norm"], cfg)
            out, (s_new, c_new) = ssm.mamba2_layer(
                normed, lp, cfg, ssm_state=s_st, conv_state=c_st,
                decode=True)
            return carry + out, (s_new, c_new)

        x, (new_s, new_c) = jax.lax.scan(
            body, x, (params["layers"], cache["ssm"], cache["conv"]))
        cache = {"ssm": new_s, "conv": new_c}
    elif cfg.family == "hybrid":
        new_layers = []
        for i, lp in enumerate(params["layers_list"]):
            cl = cache["layers"][i]
            h = L.apply_norm(x, lp["ln1"], cfg)
            if cfg.layer_kind(i) == "attn":
                w = cl["k"].shape[2]
                clen = jnp.minimum(cache_len, w)
                out, kc, vc = _token_attn_decode(
                    h, lp["attn"], cfg, cl["k"], cl["v"], pos, clen,
                    ring=w)
                new_layers.append({"k": kc, "v": vc})
            else:
                out, (lru, conv) = rglru.recurrent_block(
                    h, lp["rec"], cfg, lru_state=cl["lru"],
                    conv_state=cl["conv"], decode=True)
                new_layers.append({"lru": lru, "conv": conv})
            x = x + out
            h = L.apply_norm(x, lp["ln2"], cfg)
            x = x + L.mlp(h, lp["mlp"], cfg)
        cache = {"layers": new_layers}
    elif cfg.family == "encdec":
        new_self = []
        pos_v = jnp.arange(1) + pos
        for i, lp in enumerate(params["dec_layers"]):
            cl = cache["self"][i]
            h = L.apply_norm(x, lp["ln1"], cfg)
            out, kc, vc = _token_attn_decode(h, lp["attn"], cfg,
                                             cl["k"], cl["v"], pos,
                                             cache_len)
            new_self.append({"k": kc, "v": vc})
            x = x + out
            h = L.apply_norm(x, lp["ln_x"], cfg)
            out, _ = L.attention(
                h, lp["xattn"], cfg, positions=pos_v,
                cross_kv=(cache["cross"][i]["k"], cache["cross"][i]["v"]))
            x = x + out
            h = L.apply_norm(x, lp["ln2"], cfg)
            x = x + L.mlp(h, lp["mlp"], cfg)
        cache = {"self": new_self, "cross": cache["cross"]}
    else:
        raise NotImplementedError(cfg.family)

    logits = _logits(params, cfg, x)
    return logits[:, -1], cache
