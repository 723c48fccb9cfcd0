"""Pallas TPU kernels (compiled on TPU, interpreted elsewhere) + jnp templates.

conv2d_nchwc — the paper's CONV template (Algorithm 1) blocked for the MXU;
matmul_blocked — the LM-side GEMM instantiation of the same template;
flash_attention — fused GQA attention for the serving path.
ops.py carries the jit'd wrappers, ref.py the pure-jnp oracles.
"""
from repro.kernels.conv2d_nchwc import conv2d_nchwc_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.matmul_blocked import MatmulSchedule, matmul_pallas
from repro.kernels.ssd_chunk import ssd_intra_pallas

__all__ = ["conv2d_nchwc_pallas", "flash_attention_pallas",
           "MatmulSchedule", "matmul_pallas", "ssd_intra_pallas"]
