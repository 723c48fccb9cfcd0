"""Roofline analysis from compiled dry-run artifacts, in the terms of the
planner's target chip (``core.peaks``):

    compute term    = HLO_FLOPs / (chips x peak bf16 FLOP/s)
    memory term     = HLO_bytes / (chips x HBM bytes/s)
    collective term = collective_bytes / (chips x ICI bytes/s per link)

``compiled.cost_analysis()`` supplies FLOPs/bytes of the *per-device*
partitioned module; collective bytes are parsed from the optimized HLO text
(sum of result-buffer sizes of all-gather / all-reduce / reduce-scatter /
all-to-all / collective-permute, including their -start forms).
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, Optional, Tuple

from repro.core.peaks import PLAN_TARGET, peaks

_TARGET = peaks(PLAN_TARGET)
PEAK_FLOPS = _TARGET.bf16_flops               # FLOP/s / chip
HBM_BW = _TARGET.hbm_bytes_per_s              # bytes/s / chip
ICI_BW = _TARGET.ici_bytes_per_s_per_link     # bytes/s / link

_DTYPE_BYTES = {
    "f64": 8, "s64": 8, "u64": 8, "c64": 8,
    "f32": 4, "s32": 4, "u32": 4,
    "bf16": 2, "f16": 2, "s16": 2, "u16": 2,
    "f8e4m3fn": 1, "f8e5m2": 1, "s8": 1, "u8": 1, "pred": 1,
}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def parse_collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Sum result-buffer sizes per collective op kind.

    HLO line shape: ``%name = f32[64,128]{1,0} all-reduce(%dot), ...`` —
    the result shape(s) sit between '=' and the op token.  ``-start`` ops
    are counted (tuple results halved: they alias operand+result buffers);
    ``-done`` twins are skipped."""
    out: Dict[str, int] = {c: 0 for c in _COLLECTIVES}
    for line in hlo_text.splitlines():
        if "=" not in line:
            continue
        rhs = line.partition("=")[2]
        for coll in _COLLECTIVES:
            is_start = f" {coll}-start(" in rhs
            if not is_start and f" {coll}(" not in rhs:
                continue
            op_tok = f" {coll}-start(" if is_start else f" {coll}("
            result_part = rhs.split(op_tok)[0]
            shapes = [_shape_bytes(d, s)
                      for d, s in _SHAPE_RE.findall(result_part)
                      if d in _DTYPE_BYTES]
            total = sum(shapes)
            if is_start and len(shapes) >= 2 and len(shapes) % 2 == 0:
                total //= 2
            out[coll] += total
            break
    out["total"] = sum(out[c] for c in _COLLECTIVES)
    return out


def count_ops(hlo_text: str, names: Tuple[str, ...]) -> Dict[str, int]:
    out = {n: 0 for n in names}
    for line in hlo_text.splitlines():
        rhs = line.partition("=")[2]
        for n in names:
            if f" {n}(" in rhs or f" {n}-start(" in rhs:
                out[n] += 1
                break
    return out


@dataclasses.dataclass
class RooflineReport:
    """Primary FLOP/byte source is the jaxpr walker (analysis/flops.py) —
    exact under scan — divided by chips for the per-device terms.
    ``ca_*`` carry compiled.cost_analysis() for reference; XLA:CPU counts
    while-loop bodies once, so ca_flops underreads scan-over-layer programs
    by ~n_layers (documented in EXPERIMENTS.md §Dry-run methodology)."""

    arch: str
    shape: str
    mesh: str
    chips: int
    flops_per_device: float            # jaxpr_total / chips
    bytes_per_device: float            # jaxpr heavy bytes / chips
    collective_bytes_per_device: float
    collectives: Dict[str, int]
    model_flops_total: float           # 6·N·D (train) / 2·N·D (inference)
    ca_flops_per_device: float = 0.0   # cost_analysis (while-body-once)
    ca_bytes_per_device: float = 0.0
    model_bytes_total: float = 0.0     # algorithmic minimum HBM traffic

    @property
    def compute_s(self) -> float:
        return self.flops_per_device / PEAK_FLOPS

    @property
    def memory_s(self) -> float:
        return self.bytes_per_device / HBM_BW

    @property
    def collective_s(self) -> float:
        return self.collective_bytes_per_device / ICI_BW

    @property
    def bottleneck(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def useful_flops_ratio(self) -> float:
        """MODEL_FLOPS / (HLO_FLOPs x chips) — catches remat/redundancy."""
        hw = self.flops_per_device * self.chips
        return self.model_flops_total / hw if hw else 0.0

    @property
    def step_time_s(self) -> float:
        """Roofline-optimal step time: overlapped compute/memory plus the
        collective term charged serially (conservative)."""
        return max(self.compute_s, self.memory_s) + self.collective_s

    @property
    def ideal_step_s(self) -> float:
        """The algorithmic lower bound: the larger of the compute roofline
        on MODEL_FLOPS and the memory roofline on MODEL_BYTES (for decode
        the latter dominates — params+cache must stream once per token)."""
        c = self.model_flops_total / (self.chips * PEAK_FLOPS)
        m = self.model_bytes_total / (self.chips * HBM_BW)
        return max(c, m)

    @property
    def roofline_fraction(self) -> float:
        """ideal_step / achieved step — 1.0 means sitting on the roofline
        that binds this workload (compute for train, memory for decode)."""
        return self.ideal_step_s / self.step_time_s if self.step_time_s \
            else 0.0

    def to_dict(self) -> Dict:
        return {
            "arch": self.arch, "shape": self.shape, "mesh": self.mesh,
            "chips": self.chips,
            "flops_per_device": self.flops_per_device,
            "bytes_per_device": self.bytes_per_device,
            "ca_flops_per_device": self.ca_flops_per_device,
            "ca_bytes_per_device": self.ca_bytes_per_device,
            "collective_bytes_per_device": self.collective_bytes_per_device,
            "collectives": self.collectives,
            "model_flops_total": self.model_flops_total,
            "compute_s": self.compute_s, "memory_s": self.memory_s,
            "collective_s": self.collective_s,
            "bottleneck": self.bottleneck,
            "useful_flops_ratio": self.useful_flops_ratio,
            "model_bytes_total": self.model_bytes_total,
            "ideal_step_s": self.ideal_step_s,
            "step_time_s": self.step_time_s,
            "roofline_fraction": self.roofline_fraction,
        }


def model_flops(cfg, kind: str, batch: int, seq: int) -> float:
    """6·N·D for training, 2·N·D for inference (N = active params)."""
    n = cfg.active_param_count()
    if kind == "train":
        return 6.0 * n * batch * seq
    if kind == "prefill":
        return 2.0 * n * batch * seq
    return 2.0 * n * batch          # decode: one token per sequence


def _param_bytes(cfg) -> float:
    return cfg.param_count() * (2 if cfg.dtype == "bfloat16" else 4)


def _cache_bytes(cfg, batch: int, seq: int) -> float:
    el = 2 if cfg.dtype == "bfloat16" else 4
    if cfg.family in ("dense", "moe", "vlm", "encdec"):
        return (cfg.n_layers * batch * cfg.n_kv * seq * cfg.head_dim
                * 2 * el)
    if cfg.family == "ssm":
        return (cfg.n_layers * batch * cfg.ssm_heads * cfg.ssm_head_dim
                * cfg.ssm_state * 4)
    if cfg.family == "hybrid":
        n_attn = sum(1 for i in range(cfg.n_layers)
                     if cfg.layer_kind(i) == "attn")
        w = min(cfg.local_window, seq)
        kv = n_attn * batch * cfg.n_kv * w * cfg.head_dim * 2 * el
        lru = (cfg.n_layers - n_attn) * batch * cfg.lru_width * 4
        return kv + lru
    return 0.0


def model_bytes(cfg, kind: str, batch: int, seq: int) -> float:
    """Algorithmic minimum HBM traffic per step:
    train — params read (fwd+bwd) + grads written + Adam moments r/w +
    activations floor (one residual-stream r/w per layer);
    decode — params (all experts resident stream for MoE routing is NOT
    needed: only active experts' weights are read) + the KV/state cache;
    prefill — params + activations floor + cache write."""
    pb = _param_bytes(cfg)
    act_el = 2 if cfg.dtype == "bfloat16" else 4
    layer_io = batch * seq * cfg.d_model * act_el * cfg.n_layers * 2
    if kind == "train":
        # fwd read + bwd read + grad write (bf16) + 2 fp32 moments r/w +
        # fp32 master update ≈ 3·pb + 16·N
        n = cfg.param_count()
        return 3 * pb + 16 * n + 2 * layer_io
    if kind == "prefill":
        return pb + layer_io + _cache_bytes(cfg, batch, seq)
    # decode: active params stream once + full cache read + tiny writes
    active_pb = cfg.active_param_count() * (2 if cfg.dtype == "bfloat16"
                                            else 4)
    return active_pb + _cache_bytes(cfg, batch, seq)
