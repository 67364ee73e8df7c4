"""Fused windowed stream statistics, TPU Pallas.

One HBM read of X (k, N) produces:
  * raw power sums  S_m = sum_t x^m, m = 1..4   -> (k, 4)
  * cross products  G = X @ X^T                 -> (k, k)

The paper's edge loop needs variances (S1, S2), fourth moments for the eq.-8
epsilon policy (S3, S4) and the dependence matrix (G) every tumbling window;
a naive implementation reads X three times (moments, covariance, model fit).
Here the window is tiled (TK, TN) into VMEM once: the MXU computes the
(TK x TN)·(TN x TK) cross-product tile while the VPU accumulates the power
sums from the same resident tile.

Grid: (k/TK, k/TK, N/TN) — c (the window chunk axis) innermost so output
tiles stay VMEM-resident across the accumulation;
moments are accumulated only on the j == 0 column of the grid.
Callers pad k and N (zero padding is exact for sums/products).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_TK = 8
DEFAULT_TN = 512
# full-f32 MXU passes: the statistics subtract XX^T/n from mean products, so
# a single bf16 pass would leave them with ~3 significant digits
_HIGHEST = jax.lax.Precision.HIGHEST


def _kernel(xi_ref, xj_ref, xxt_ref, mom_ref):
    c = pl.program_id(2)
    j = pl.program_id(1)

    xi = xi_ref[...].astype(jnp.float32)          # (TK, TN)
    xj = xj_ref[...].astype(jnp.float32)

    @pl.when(c == 0)
    def _init_xxt():
        xxt_ref[...] = jnp.zeros_like(xxt_ref)

    xxt_ref[...] += jax.lax.dot_general(
        xi, xj, (((1,), (1,)), ((), ())), precision=_HIGHEST,
        preferred_element_type=jnp.float32)       # MXU tile

    @pl.when(j == 0)
    def _moments():
        @pl.when(c == 0)
        def _init_mom():
            mom_ref[...] = jnp.zeros_like(mom_ref)
        x2 = xi * xi
        s1 = jnp.sum(xi, axis=1)
        s2 = jnp.sum(x2, axis=1)
        s3 = jnp.sum(x2 * xi, axis=1)
        s4 = jnp.sum(x2 * x2, axis=1)
        mom_ref[...] += jnp.stack([s1, s2, s3, s4], axis=1)


def _fleet_kernel(x_ref, xxt_ref, mom_ref):
    c = pl.program_id(1)

    x = x_ref[...].astype(jnp.float32)            # (KP, TN) — one whole site

    @pl.when(c == 0)
    def _init():
        xxt_ref[...] = jnp.zeros_like(xxt_ref)
        mom_ref[...] = jnp.zeros_like(mom_ref)

    xxt_ref[...] += jax.lax.dot_general(
        x, x, (((1,), (1,)), ((), ())), precision=_HIGHEST,
        preferred_element_type=jnp.float32)       # MXU diagonal tile
    x2 = x * x
    mom_ref[...] += jnp.stack([jnp.sum(x, axis=1), jnp.sum(x2, axis=1),
                               jnp.sum(x2 * x, axis=1),
                               jnp.sum(x2 * x2, axis=1)], axis=1)


@functools.partial(jax.jit, static_argnames=("kp", "tn", "interpret"))
def stream_stats_fleet_pallas(x: jax.Array, kp: int, tn: int = DEFAULT_TN,
                              interpret: bool = False):
    """Fleet (block-diagonal) layout: x is E sites flattened to (E·kp, N).

    Cross-site products are never needed for planning — each site's
    dependence matrix is the kp×kp diagonal block — so instead of the full
    (E·kp)² grid of :func:`stream_stats_pallas` the grid is just (E, N/tn)
    and only the diagonal tiles are computed: O(E) MXU work, not O(E²).
    kp is the per-site stream tile (multiple of 8; caller pads k up to it).

    Returns (moments (E·kp, 4) f32, xxt (E·kp, kp) f32) where xxt row-block
    e holds site e's diagonal tile.
    """
    ek, n = x.shape
    assert ek % kp == 0 and n % tn == 0 and kp % 8 == 0, (ek, n, kp, tn)
    grid = (ek // kp, n // tn)
    xxt, mom = pl.pallas_call(
        _fleet_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((kp, tn), lambda e, c: (e, c))],
        out_specs=[
            pl.BlockSpec((kp, kp), lambda e, c: (e, 0)),
            pl.BlockSpec((kp, 4), lambda e, c: (e, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((ek, kp), jnp.float32),
            jax.ShapeDtypeStruct((ek, 4), jnp.float32),
        ],
        interpret=interpret,
        name="stream_stats_fleet",
    )(x)
    return mom, xxt


@functools.partial(jax.jit, static_argnames=("tk", "tn", "interpret"))
def stream_stats_pallas(x: jax.Array, tk: int = DEFAULT_TK,
                        tn: int = DEFAULT_TN, interpret: bool = False):
    """x: (k, N) with k % tk == 0 and N % tn == 0 (caller pads).

    Returns (moments (k, 4) f32, xxt (k, k) f32).
    """
    k, n = x.shape
    assert k % tk == 0 and n % tn == 0, (k, n, tk, tn)
    grid = (k // tk, k // tk, n // tn)
    xxt, mom = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tk, tn), lambda i, j, c: (i, c)),
            pl.BlockSpec((tk, tn), lambda i, j, c: (j, c)),
        ],
        out_specs=[
            pl.BlockSpec((tk, tk), lambda i, j, c: (i, j)),
            pl.BlockSpec((tk, 4), lambda i, j, c: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((k, k), jnp.float32),
            jax.ShapeDtypeStruct((k, 4), jnp.float32),
        ],
        interpret=interpret,
        name="stream_stats",
    )(x, x)
    return mom, xxt
