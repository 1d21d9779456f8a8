"""Jit'd public wrappers over the Pallas kernels.

Every kernel runs compiled on TPU and in interpret mode on CPU, chosen by
the platform its caller is lowered for (:mod:`repro.kernels.dispatch`) -- so
the same call sites run everywhere and tests exercise the kernel bodies on
the CPU.
"""

from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.block_matmul import block_matmul
from repro.kernels.cad_score import cad_scores, cad_scores_tile
from repro.kernels.edge_projection import edge_projection
from repro.kernels.flash_attention import flash_attention
from repro.kernels.stream_gemm import fused_panel_matvec, stream_gemm
from repro.kernels.wkv import wkv

__all__ = [
    "block_matmul",
    "cad_scores",
    "cad_scores_tile",
    "edge_projection",
    "flash_attention",
    "fused_panel_matvec",
    "stream_gemm",
    "wkv",
]
