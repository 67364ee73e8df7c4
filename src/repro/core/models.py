"""Compact conditional-expectation models E[X_i | X_{p_i}] (§IV-B).

Two families per the paper:
  * Pearson dependence  -> linear model.
  * Spearman dependence -> cubic polynomial (fits a wide class of monotone maps).
Mean imputation is the degenerate model with explained variance exactly 0.

Fitting is plain least squares on standardized predictor features via 4x4
normal equations, vmapped over the k streams; the Pallas ``polyfit`` kernel
computes the same XtX / Xty accumulations fused (see repro.kernels.polyfit).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.types import Array, CompactModel
from repro.kernels.dispatch import resolve_use_kernel

_RIDGE = 1e-6
# full-f32 contractions: a TPU runs f32 matmuls as one bf16 pass by default,
# and the explained variance is a difference of two large sums
_HIGHEST = jax.lax.Precision.HIGHEST
_mm = functools.partial(jnp.matmul, precision=_HIGHEST)


def _features(u: Array, degree: int) -> Array:
    """(N,) -> (N, 4) Vandermonde; degrees above ``degree`` zeroed."""
    feats = jnp.stack([jnp.ones_like(u), u, u**2, u**3], axis=-1)
    keep = (jnp.arange(4) <= degree).astype(u.dtype)
    return feats * keep[None, :]


def _fit_one(y: Array, x_pred: Array, pair_mask: Array, degree: int):
    """LSQ fit of y ~ poly(x_pred) over co-valid positions. Returns
    (coeffs(4,), loc, scale, explained_var)."""
    w = pair_mask
    n = jnp.maximum(jnp.sum(w), 1.0)
    loc = jnp.sum(x_pred * w) / n
    var_p = jnp.sum(((x_pred - loc) ** 2) * w) / n
    scale = jnp.sqrt(jnp.maximum(var_p, 1e-12))
    u = (x_pred - loc) / scale
    f = _features(u, degree) * w[:, None]
    xtx = _mm(f.T, f) + _RIDGE * jnp.eye(4, dtype=f.dtype)
    xty = _mm(f.T, y * w)
    coeffs = jnp.linalg.solve(xtx, xty)
    fitted = _mm(f, coeffs)
    mean_fit = jnp.sum(fitted * w) / n
    # Var[E[X|Xp]] — unbiased over co-valid samples (the V_i of eqs. 3/7/11)
    ev = jnp.sum(((fitted - mean_fit) ** 2) * w) / jnp.maximum(n - 1.0, 1.0)
    return coeffs, loc, scale, ev


@functools.partial(jax.jit, static_argnames=("degree", "use_kernel",
                                             "interpret"))
def fit_models(values: Array, counts: Array, predictor: Array,
               degree: int = 3, use_kernel=None,
               interpret: bool = False) -> CompactModel:
    """Fit E[X_i | X_{p_i}] for every stream i in one vmapped pass.

    With the kernel on (``use_kernel=True``, or None on a TPU — resolved by
    :func:`repro.kernels.dispatch.resolve_use_kernel`, like the
    stream-statistics pass) the normal-equation accumulations run through
    the fused Pallas ``vandermonde_moments`` kernel (one pass over the
    window instead of materializing the (N, 4) feature matrix); otherwise
    the reference least-squares path runs.  Both solve the same ridge
    system, so they agree to f32 association noise (pinned in
    tests/test_models_fit.py).
    """
    n_max = values.shape[-1]
    idx = jnp.arange(n_max)[None, :]
    mask = (idx < counts[:, None]).astype(values.dtype)
    xp = values[predictor]          # (k, N)
    mp = mask[predictor]            # predictor validity
    pair = mask * mp
    if resolve_use_kernel(use_kernel, interpret):
        coeffs, loc, scale, ev = _fit_fused(values, xp, pair, degree,
                                            interpret)
    else:
        def one(y, x, w):
            return _fit_one(y, x, w, degree)

        coeffs, loc, scale, ev = jax.vmap(one)(values, xp, pair)
    return CompactModel(coeffs=coeffs, loc=loc, scale=scale,
                        explained_var=ev, predictor=predictor)


def _fit_fused(values: Array, xp: Array, pair: Array, degree: int,
               interpret: bool):
    """The `_fit_one` system assembled from fused Vandermonde moments.

    With the 0/1 pair mask w folded into the standardized predictor,
    ``(u*w)**m == (u**m)*w`` for m >= 1, so one kernel pass over
    ``(y*w, u*w)`` yields every masked power sum the 4x4 normal equations
    and the explained-variance identity ``(sum f^2 w - (sum f w)^2/n)``
    need; only the m=0 count is fed in explicitly.
    """
    from repro.kernels.polyfit.ops import (solve_normal_equations,
                                           vandermonde_moments)
    pair_n = jnp.sum(pair, axis=-1)                  # (k,) true pair counts
    n = jnp.maximum(pair_n, 1.0)
    loc = jnp.sum(xp * pair, axis=-1) / n
    var_p = jnp.sum(((xp - loc[:, None]) ** 2) * pair, axis=-1) / n
    scale = jnp.sqrt(jnp.maximum(var_p, 1e-12))
    uw = ((xp - loc[:, None]) / scale[:, None]) * pair
    pu, py = vandermonde_moments(values * pair, uw, use_kernel=True,
                                 interpret=interpret, counts=pair_n)
    coeffs = solve_normal_equations(pu, py, degree=degree, ridge=_RIDGE)
    idx4 = jnp.arange(4)
    keep = (idx4 <= degree).astype(pu.dtype)
    c = coeffs * keep[None, :]
    gram = pu[:, idx4[:, None] + idx4[None, :]]      # (k, 4, 4) Hankel
    s = jnp.einsum("km,km->k", c, pu[:, :4],
                   precision=_HIGHEST)                       # sum fitted*w
    ss = jnp.einsum("ki,kij,kj->k", c, gram, c,
                    precision=_HIGHEST)                      # sum fitted^2*w
    ev = jnp.maximum(ss - s * s / n, 0.0) / jnp.maximum(n - 1.0, 1.0)
    return coeffs, loc, scale, ev


def mean_model(values: Array, counts: Array, predictor: Array) -> CompactModel:
    """Mean imputation: E[X_i|X_p] := mu_i, explained variance exactly zero
    (paper §III-B2: 'Var[E[X_i|X_{p_i}]] is exactly zero')."""
    n_max = values.shape[-1]
    idx = jnp.arange(n_max)[None, :]
    mask = (idx < counts[:, None]).astype(values.dtype)
    n = jnp.maximum(counts.astype(values.dtype), 1.0)
    mean = jnp.sum(values * mask, axis=-1) / n
    k = values.shape[0]
    coeffs = jnp.zeros((k, 4), values.dtype).at[:, 0].set(mean)
    return CompactModel(coeffs=coeffs,
                        loc=jnp.zeros((k,), values.dtype),
                        scale=jnp.ones((k,), values.dtype),
                        explained_var=jnp.zeros((k,), values.dtype),
                        predictor=predictor)


@jax.jit
def evaluate_model(model: CompactModel, x_pred: Array) -> Array:
    """Impute values for every stream from its predictor's observations.

    x_pred: (k, M) — per stream, M observations of that stream's predictor.
    Returns (k, M) imputed values.
    """
    u = (x_pred - model.loc[:, None]) / model.scale[:, None]
    c = model.coeffs
    return (c[:, 0:1] + c[:, 1:2] * u + c[:, 2:3] * u**2 + c[:, 3:4] * u**3)


# ---------------------------------------------------------------------------
# Beyond-paper (§V-G of the paper): TWO predictor streams per target.
# E[X_i | X_p, X_q] ~ c0 + c1 u + c2 w + c3 uw — still 4 coefficients, so the
# WAN footprint matches the cubic single-predictor model (+4 bytes for the
# second index); constraint 1d becomes n_s,i <= min(n_r,p, n_r,q).
# ---------------------------------------------------------------------------

def _fit_one_multi(y: Array, xp: Array, xq: Array, pair_mask: Array):
    w_ = pair_mask
    n = jnp.maximum(jnp.sum(w_), 1.0)

    def std(v):
        loc = jnp.sum(v * w_) / n
        var = jnp.sum(((v - loc) ** 2) * w_) / n
        scale = jnp.sqrt(jnp.maximum(var, 1e-12))
        return (v - loc) / scale, loc, scale

    u, loc_u, sc_u = std(xp)
    v, loc_v, sc_v = std(xq)
    f = jnp.stack([jnp.ones_like(u), u, v, u * v], axis=-1) * w_[:, None]
    xtx = _mm(f.T, f) + _RIDGE * jnp.eye(4, dtype=f.dtype)
    xty = _mm(f.T, y * w_)
    coeffs = jnp.linalg.solve(xtx, xty)
    fitted = _mm(f, coeffs)
    mean_fit = jnp.sum(fitted * w_) / n
    ev = jnp.sum(((fitted - mean_fit) ** 2) * w_) / jnp.maximum(n - 1.0, 1.0)
    return coeffs, jnp.stack([loc_u, loc_v]), jnp.stack([sc_u, sc_v]), ev


@jax.jit
def fit_models_multi(values: Array, counts: Array, predictors: Array):
    """predictors: (k, 2) int — two predictor streams per target.

    Returns a dict model {coeffs (k,4), loc (k,2), scale (k,2),
    explained_var (k,), predictor (k,2)} (duck-types CompactModel where the
    planner needs it)."""
    n_max = values.shape[-1]
    idx = jnp.arange(n_max)[None, :]
    mask = (idx < counts[:, None]).astype(values.dtype)
    xp = values[predictors[:, 0]]
    xq = values[predictors[:, 1]]
    pair = mask * mask[predictors[:, 0]] * mask[predictors[:, 1]]
    coeffs, loc, scale, ev = jax.vmap(_fit_one_multi)(values, xp, xq, pair)
    return {"coeffs": coeffs, "loc": loc, "scale": scale,
            "explained_var": ev, "predictor": predictors}


def evaluate_model_multi(model: dict, xp: Array, xq: Array) -> Array:
    """(k, M) predictor observations x2 -> (k, M) imputed values."""
    u = (xp - model["loc"][:, 0:1]) / model["scale"][:, 0:1]
    v = (xq - model["loc"][:, 1:2]) / model["scale"][:, 1:2]
    c = model["coeffs"]
    return c[:, 0:1] + c[:, 1:2] * u + c[:, 2:3] * v + c[:, 3:4] * u * v
