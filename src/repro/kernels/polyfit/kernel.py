"""Fused Vandermonde moment accumulation for the compact models (§IV-B).

For each stream i (target y_i, standardized predictor u_i) the degree-3
normal equations need
  pu_m  = sum_t u^m            m = 0..6   (the 4x4 Hankel Gram matrix)
  py_m  = sum_t y * u^m        m = 0..3   (the RHS)
One pass over (Y, U) tiles resident in VMEM; pure VPU accumulation; the
4x4 solve happens outside (ops.py) — it is O(k) and tiny.

Grid: (k/TK, N/TN), chunk axis innermost; outputs (TK, 7) and (TK, 4)
accumulate in VMEM across chunks.  Callers zero-pad (exact for sums; the
m=0 row is fixed up with the true N outside).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_TK = 8
DEFAULT_TN = 512


def _kernel(y_ref, u_ref, pu_ref, py_ref):
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _init():
        pu_ref[...] = jnp.zeros_like(pu_ref)
        py_ref[...] = jnp.zeros_like(py_ref)

    y = y_ref[...].astype(jnp.float32)          # (TK, TN)
    u = u_ref[...].astype(jnp.float32)
    u2 = u * u
    u3 = u2 * u
    ones = jnp.ones_like(u)
    pu_ref[...] += jnp.stack(
        [jnp.sum(ones, 1), jnp.sum(u, 1), jnp.sum(u2, 1), jnp.sum(u3, 1),
         jnp.sum(u2 * u2, 1), jnp.sum(u2 * u3, 1), jnp.sum(u3 * u3, 1)],
        axis=1)
    py_ref[...] += jnp.stack(
        [jnp.sum(y, 1), jnp.sum(y * u, 1), jnp.sum(y * u2, 1),
         jnp.sum(y * u3, 1)], axis=1)


@functools.partial(jax.jit, static_argnames=("tk", "tn", "interpret"))
def polyfit_pallas(y: jax.Array, u: jax.Array, tk: int = DEFAULT_TK,
                   tn: int = DEFAULT_TN, interpret: bool = False):
    """y, u: (k, N), k % tk == 0, N % tn == 0. Returns (pu (k,7), py (k,4))."""
    k, n = y.shape
    assert y.shape == u.shape and k % tk == 0 and n % tn == 0
    grid = (k // tk, n // tn)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tk, tn), lambda i, c: (i, c)),
            pl.BlockSpec((tk, tn), lambda i, c: (i, c)),
        ],
        out_specs=[
            pl.BlockSpec((tk, 7), lambda i, c: (i, 0)),
            pl.BlockSpec((tk, 4), lambda i, c: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((k, 7), jnp.float32),
            jax.ShapeDtypeStruct((k, 4), jnp.float32),
        ],
        interpret=interpret,
        name="polyfit",
    )(y, u)
