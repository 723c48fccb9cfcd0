"""What the LM cell's work must cost: operations and bytes computed from
the published widths, never from the program.

The widths are the configuration file's keys, as the model's own
``config.json`` names them (``hidden_size``, ``num_hidden_layers``,
``num_attention_heads``, ``num_key_value_heads``, ``intermediate_size``,
``vocab_size``, ``sliding_window``), for the dense block with biases on
every projection, a non-gated MLP, LayerNorm and tied embeddings, held in
bfloat16 (2 bytes a parameter and a cached key or value).

- ``param_bytes``: every parameter once, which one decode step must read.
- ``kv_bytes_per_position``: the keys and values of one position, over
  the layers.
- ``step_bytes(cfg, cache_len)``: one decode step at ``cache_len`` valid
  positions (the new one included): every parameter, and the keys and
  values it attends to (``min(cache_len, window)`` positions).
- ``prefill_flops(cfg, tokens)``: a prefill of ``tokens`` positions:
  2 x the layers' matrix parameters a token; windowed causal attention,
  4 x heads x head size for each (query, key) pair it reads (the scores
  and the weighted values); and the tied head at the last position only.
"""
from __future__ import annotations

from typing import Dict, Iterable

BYTES = 2   # bfloat16


def _dims(cfg: Dict):
    d = cfg["hidden_size"]
    heads = cfg["num_attention_heads"]
    hd = d // heads
    kv = cfg["num_key_value_heads"] * hd
    return d, heads, hd, kv, cfg["intermediate_size"]


def matrix_params(cfg: Dict) -> int:
    """The layers' matrix parameters: q, k, v, o and the two MLP
    projections (no embedding, bias or norm)."""
    d, _, _, kv, ff = _dims(cfg)
    return cfg["num_hidden_layers"] * (2 * d * d + 2 * d * kv + 2 * d * ff)


def param_count(cfg: Dict) -> int:
    """Every parameter: the matrices, their biases, two LayerNorms a
    layer, the tied embedding and the final LayerNorm."""
    d, _, _, kv, ff = _dims(cfg)
    biases = d + 2 * kv + d + ff + d
    return (matrix_params(cfg) + cfg["num_hidden_layers"] * (biases + 4 * d)
            + cfg["vocab_size"] * d + 2 * d)


def param_bytes(cfg: Dict) -> int:
    return BYTES * param_count(cfg)


def kv_bytes_per_position(cfg: Dict) -> int:
    _, _, _, kv, _ = _dims(cfg)
    return BYTES * cfg["num_hidden_layers"] * 2 * kv


def _window(cfg: Dict) -> int:
    return cfg.get("sliding_window") or 0


def step_bytes(cfg: Dict, cache_len: int) -> int:
    w = _window(cfg)
    seen = min(cache_len, w) if w else cache_len
    return param_bytes(cfg) + kv_bytes_per_position(cfg) * seen


def decode_bytes(cfg: Dict, cache_lens: Iterable[int]) -> int:
    """The bytes of decode steps at these valid lengths."""
    return sum(step_bytes(cfg, n) for n in cache_lens)


def attended_pairs(tokens: int, window: int) -> int:
    """(query, key) pairs a causal prefill of ``tokens`` reads: position
    ``p`` reads ``min(p + 1, window)`` keys (all ``p + 1`` without one)."""
    if not window or tokens <= window:
        return tokens * (tokens + 1) // 2
    return window * (window + 1) // 2 + (tokens - window) * window


def prefill_flops(cfg: Dict, tokens: int) -> int:
    d, heads, hd, _, _ = _dims(cfg)
    return (2 * matrix_params(cfg) * tokens
            + 4 * heads * hd * cfg["num_hidden_layers"]
            * attended_pairs(tokens, _window(cfg))
            + 2 * d * cfg["vocab_size"])


def request_work(cfg: Dict, bucket: int, steps: int) -> Dict[str, int]:
    """One request's must-do work: its prefill of ``bucket`` tokens (none
    where 0) and its ``steps`` decode steps, which run at the valid
    lengths ``bucket + 1 .. bucket + steps``."""
    return {"prefill_flops": prefill_flops(cfg, bucket) if bucket else 0,
            "decode_bytes": decode_bytes(
                cfg, range(bucket + 1, bucket + steps + 1))}
