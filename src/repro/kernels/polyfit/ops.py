"""jit'd wrapper for polyfit: padding, dispatch, normal-equation assembly."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.dispatch import resolve_use_kernel
from repro.kernels.polyfit.kernel import (DEFAULT_TK, DEFAULT_TN,
                                          polyfit_pallas)
from repro.kernels.polyfit.ref import polyfit_ref


@functools.partial(jax.jit, static_argnames=("use_kernel", "interpret",
                                             "degree"))
def vandermonde_moments(y: jax.Array, u: jax.Array, use_kernel: bool = True,
                        interpret: bool = False, degree: int = 3,
                        counts=None):
    """Vandermonde power sums for E[y|u] polynomial fits.

    Zero padding is exact for every sum except m=0 (the count), which is
    fixed up with the true N — or, when ``counts`` (k,) is given, with the
    caller's per-row valid count.  That is what makes *masked* fits work
    through this kernel: with y and u pre-multiplied by a 0/1 mask w,
    ``(u*w)**m == (u**m)*w`` for every m >= 1, so all higher moments are
    the masked sums already and only the m=0 row needs the true count.
    """
    k, n = y.shape
    if not resolve_use_kernel(use_kernel, interpret):
        pu, py = polyfit_ref(y, u)
    else:
        tk = min(DEFAULT_TK, max(1, k))
        tn = min(DEFAULT_TN, max(128, 1 << int(np.ceil(np.log2(max(n, 1))))))
        kp = int(np.ceil(k / tk) * tk)
        np_ = int(np.ceil(n / tn) * tn)
        yp = jnp.pad(y, ((0, kp - k), (0, np_ - n)))
        up = jnp.pad(u, ((0, kp - k), (0, np_ - n)))
        pu, py = polyfit_pallas(yp, up, tk=tk, tn=tn, interpret=interpret)
        pu, py = pu[:k], py[:k]
    if counts is None:
        pu = pu.at[:, 0].set(float(n))  # zero-padding fixup for the count
    else:
        pu = pu.at[:, 0].set(counts.astype(pu.dtype))
    return pu, py


@functools.partial(jax.jit, static_argnames=("degree", "ridge"))
def solve_normal_equations(pu: jax.Array, py: jax.Array, degree: int = 3,
                           ridge: float = 1e-6):
    """(k,7),(k,4) -> coeffs (k,4) for c0 + c1 u + c2 u^2 + c3 u^3 (degrees
    above ``degree`` forced to zero by masking the Gram matrix)."""
    k = pu.shape[0]
    idx = jnp.arange(4)
    gram = pu[:, idx[:, None] + idx[None, :]]          # (k, 4, 4) Hankel
    keep = (idx <= degree).astype(pu.dtype)
    mask = keep[:, None] * keep[None, :]
    eye = jnp.eye(4, dtype=pu.dtype)
    gram = gram * mask + (1.0 - mask) * eye * jnp.maximum(pu[:, 0:1, None], 1.0)
    gram = gram + ridge * eye
    rhs = py * keep[None, :]
    return jnp.linalg.solve(gram, rhs[..., None])[..., 0]
