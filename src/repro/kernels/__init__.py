"""Pallas TPU kernels for the perf-critical hot spots.

stream_stats    — fused one-HBM-pass windowed raw moments (S1..S4/stream)
                  + cross-product matrix X·Xᵀ (dependence estimation, §III-A).
polyfit         — fused Vandermonde accumulations (Σuᵐ, Σy·uᵐ) for the compact
                  conditional-expectation models (§IV-B).
flash_attention — online-softmax attention forward (causal/sliding-window,
                  GQA): removes the materialized (B,H,S,T) score traffic that
                  dominates the dense-arch roofline (EXPERIMENTS.md §Perf A4).

Each kernel ships kernel.py (pl.pallas_call + BlockSpec), ops.py (jit'd
wrapper: padding, and kernel-or-reference through dispatch.py — the
compiled kernel on TPU, the reference or interpret mode elsewhere), ref.py
(pure-jnp oracle).
"""
from repro.kernels.stream_stats.ops import (fleet_window_moments_xxt,
                                            window_moments_xxt)
from repro.kernels.polyfit.ops import vandermonde_moments
from repro.kernels.flash_attention.ops import flash_attention

__all__ = ["window_moments_xxt", "fleet_window_moments_xxt",
           "vandermonde_moments", "flash_attention"]
