"""Pallas TPU kernels for the perf-critical hot spots.

stream_stats — fused one-HBM-pass windowed raw moments (S1..S4/stream)
               + cross-product matrix X·Xᵀ (dependence estimation, §III-A).
polyfit      — fused Vandermonde accumulations (Σuᵐ, Σy·uᵐ) for the compact
               conditional-expectation models (§IV-B).

Each kernel ships kernel.py (pl.pallas_call + BlockSpec), ops.py (jit'd
wrapper: padding, and kernel-or-reference through dispatch.py — the
compiled kernel on TPU, the reference or interpret mode elsewhere), ref.py
(pure-jnp oracle).
"""
from repro.kernels.stream_stats.ops import (fleet_window_moments_xxt,
                                            window_moments_xxt)
from repro.kernels.polyfit.ops import vandermonde_moments

__all__ = ["window_moments_xxt", "fleet_window_moments_xxt",
           "vandermonde_moments"]
