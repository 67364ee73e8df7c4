"""Windowed stream statistics (§II-B, §IV-C of the paper).

Everything here is masked (per-stream valid counts), pure-jnp and jit-able.
The Pallas `stream_stats` kernel in ``repro.kernels`` computes the same
quantities fused in one HBM pass; ``repro.kernels.stream_stats.ref`` delegates
to these functions as the oracle.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.api.registry import DEPENDENCE
from repro.core.types import Array, StreamStats, WindowBatch

_EPS = 1e-12
# full-f32 contractions: a TPU runs f32 matmuls as one bf16 pass by default,
# too coarse for moments that are differences of large products
_HIGHEST = jax.lax.Precision.HIGHEST


def _mask(values: Array, counts: Array) -> Array:
    n_max = values.shape[-1]
    idx = jnp.arange(n_max)[None, :]
    return (idx < counts[:, None]).astype(values.dtype)


def masked_mean(values: Array, counts: Array) -> Array:
    m = _mask(values, counts)
    n = jnp.maximum(counts.astype(values.dtype), 1.0)
    return jnp.sum(values * m, axis=-1) / n


def masked_central_moments(values: Array, counts: Array):
    """Returns (mean, var_unbiased, m2_biased, m4) per stream."""
    m = _mask(values, counts)
    n = jnp.maximum(counts.astype(values.dtype), 1.0)
    mean = jnp.sum(values * m, axis=-1) / n
    d = (values - mean[:, None]) * m
    m2 = jnp.sum(d * d, axis=-1) / n
    m4 = jnp.sum(d**4, axis=-1) / n
    var = m2 * n / jnp.maximum(n - 1.0, 1.0)
    return mean, var, m2, m4


def var_of_var_estimator(var: Array, m4: Array, counts: Array) -> Array:
    """eq. 8:  Var[sigma_hat^2] = (mu4 - (N-3)/(N-1) sigma^4) / N.

    Plug-in with the sample fourth central moment; clipped at 0 (the plug-in
    can go slightly negative for tiny N / near-degenerate streams).
    """
    n = jnp.maximum(counts.astype(var.dtype), 2.0)
    out = (m4 - (n - 3.0) / (n - 1.0) * var**2) / n
    return jnp.maximum(out, 0.0)


def masked_cov(values: Array, counts: Array) -> Array:
    """Pairwise (k,k) covariance over positions valid in *both* streams.

    Streams are time-aligned within the window, so pairing by position is the
    natural estimator.  Unbiased (n_pair - 1) normalization.
    """
    m = _mask(values, counts)
    mm = functools.partial(jnp.matmul, precision=_HIGHEST)
    n_pair = mm(m, m.T)  # (k,k) number of co-valid positions
    n_pair_c = jnp.maximum(n_pair, 1.0)
    s1 = mm(values * m, m.T)  # sum_i over co-valid with j
    # pairwise means differ per (i,j); compute E[xy] - E[x]E[y] over co-valid set
    sxy = mm(values * m, (values * m).T)
    mean_i = s1 / n_pair_c
    mean_j = mean_i.T
    cov = sxy / n_pair_c - mean_i * mean_j
    cov = cov * n_pair_c / jnp.maximum(n_pair_c - 1.0, 1.0)
    return cov


def pearson_corr(values: Array, counts: Array) -> Array:
    cov = masked_cov(values, counts)
    d = jnp.sqrt(jnp.maximum(jnp.diagonal(cov), _EPS))
    corr = cov / (d[:, None] * d[None, :])
    corr = jnp.clip(corr, -1.0, 1.0)
    return corr


# XLA:CPU lowers sorts to a serial per-row loop, so at fleet scale the rank
# transform (and the sampler's shuffle) dominates the whole window step.
# Below this length we rank by counting pairwise comparisons instead: an
# O(N^2) form that vectorizes across the full (..., N, N) batch and is
# bitwise the stable double-argsort (ties resolved by position).  Above it
# the quadratic memory stops paying for itself and we fall back to sorting.
COUNTING_RANK_MAX_N = 512


def ordinal_ranks(keys: Array) -> Array:
    """Stable-sort ranks along the last axis, sort-free.

    Bitwise ``jnp.argsort(jnp.argsort(keys, axis=-1), axis=-1)``: element
    i's rank counts the j with ``keys[j] < keys[i]`` plus the earlier j
    tied with it (stable tie-break by position).
    """
    n = keys.shape[-1]
    lt = (keys[..., :, None] > keys[..., None, :]).sum(-1)
    tri = jnp.arange(n)[:, None] > jnp.arange(n)[None, :]       # j < i
    ties = ((keys[..., :, None] == keys[..., None, :]) & tri).sum(-1)
    return lt + ties


def rank_transform(values: Array, counts: Array) -> Array:
    """Per-stream ranks of the valid prefix (invalid slots pushed to the end).

    Continuous-data ranks (no tie averaging); ranks are 0..N_i-1 scaled to
    [0, 1] so downstream masked stats remain well-conditioned.
    """
    n_max = values.shape[-1]
    big = jnp.finfo(values.dtype).max
    m = _mask(values, counts)
    masked = jnp.where(m > 0, values, big)
    if n_max <= COUNTING_RANK_MAX_N:
        ranks = ordinal_ranks(masked).astype(values.dtype)
    else:
        order = jnp.argsort(masked, axis=-1)
        ranks = jnp.argsort(order, axis=-1).astype(values.dtype)
    denom = jnp.maximum(counts.astype(values.dtype) - 1.0, 1.0)[:, None]
    return jnp.where(m > 0, ranks / denom, 0.0)


def spearman_corr(values: Array, counts: Array) -> Array:
    return pearson_corr(rank_transform(values, counts), counts)


DEPENDENCE.register("pearson", pearson_corr)
DEPENDENCE.register("spearman", spearman_corr)


@functools.partial(jax.jit, static_argnames=("dependence",))
def window_stats(values: Array, counts: Array, dependence: str = "pearson") -> StreamStats:
    mean, var, _m2, m4 = masked_central_moments(values, counts)
    vov = var_of_var_estimator(var, m4, counts)
    cov = masked_cov(values, counts)
    # static under jit: the registry lookup happens once per trace
    corr = DEPENDENCE.get(dependence)(values, counts)
    return StreamStats(count=counts, mean=mean, var=var, m4=m4,
                       var_of_var=vov, cov=cov, corr=corr)


def window_stats_batch(batch: WindowBatch, dependence: str = "pearson") -> StreamStats:
    return window_stats(batch.values, batch.counts, dependence=dependence)


# ---------------------------------------------------------------------------
# Batched (fleet) entry points: derive the same statistics from raw power
# sums S1..S4 and the cross-product matrix X·Xᵀ of *zero-masked* values —
# exactly what one pass of the ``stream_stats`` kernel produces for a whole
# fleet in the flattened (E·k, N) layout.  All formulas broadcast over any
# leading batch dims.
#
# Exactness: identical to the masked estimators above whenever every count
# is 0 or N (full windows plus whole-stream stragglers — the fleet runtime's
# regime).  For partially-filled streams the pairwise covariances use each
# stream's *global* mean instead of the per-pair co-valid mean (the raw-sum
# layout cannot recover per-pair means); the diagonal is always exact.
# ---------------------------------------------------------------------------

def _cov_corr_from_sums(mom: Array, xxt: Array, counts: Array):
    """Shared pairwise (unbiased) covariance + clipped correlation."""
    c = counts.astype(mom.dtype)
    n = jnp.maximum(c, 1.0)
    mean = mom[..., 0] / n
    n_pair = jnp.minimum(c[..., :, None], c[..., None, :])
    n_pair_c = jnp.maximum(n_pair, 1.0)
    cov = xxt / n_pair_c - mean[..., :, None] * mean[..., None, :]
    cov = cov * n_pair_c / jnp.maximum(n_pair_c - 1.0, 1.0)
    d = jnp.sqrt(jnp.maximum(jnp.diagonal(cov, axis1=-2, axis2=-1), _EPS))
    corr = jnp.clip(cov / (d[..., :, None] * d[..., None, :]), -1.0, 1.0)
    return cov, corr


def corr_from_sums(mom: Array, xxt: Array, counts: Array) -> Array:
    """(..., k, 4) sums + (..., k, k) cross products -> (..., k, k) Pearson.

    Feed rank-transformed sums (see :func:`rank_transform`) for Spearman.
    """
    return _cov_corr_from_sums(mom, xxt, counts)[1]


def stats_from_sums(mom: Array, xxt: Array, counts: Array,
                    shift: Optional[Array] = None) -> StreamStats:
    """Raw sums of zero-masked values -> :class:`StreamStats`, batched.

    mom: (..., k, 4) holding S1..S4; xxt: (..., k, k); counts: (..., k).
    ``shift`` (..., k): the sums are of ``x - shift`` (callers centre the
    window so the one-pass formulas keep their f32 digits); every moment
    but the mean is shift-invariant, and the mean adds it back.
    The returned ``corr`` is Pearson; Spearman callers substitute via
    :func:`corr_from_sums` on rank sums (dataclasses.replace).
    """
    c = counts.astype(mom.dtype)
    n = jnp.maximum(c, 1.0)
    s1, s2, s3, s4 = (mom[..., i] for i in range(4))
    mu = s1 / n
    m2 = s2 / n - mu**2
    var = m2 * n / jnp.maximum(n - 1.0, 1.0)
    m4 = (s4 - 4.0 * mu * s3 + 6.0 * mu**2 * s2 - 3.0 * mu**4 * n) / n
    m4 = jnp.maximum(m4, 0.0)
    vov = var_of_var_estimator(var, m4, counts)
    cov, corr = _cov_corr_from_sums(mom, xxt, counts)
    mean = mu if shift is None else mu + shift
    return StreamStats(count=counts, mean=mean, var=var, m4=m4,
                       var_of_var=vov, cov=cov, corr=corr)


def autocovariance(x: Array, n_valid: Array, max_lag: int) -> Array:
    """Autocovariances gamma_1..gamma_max_lag of a single stream (masked).

    Used for the m-dependence penalty (eq. 9) and the PACF (§V-F).
    """
    n_max = x.shape[-1]
    idx = jnp.arange(n_max)
    m = (idx < n_valid).astype(x.dtype)
    n = jnp.maximum(jnp.sum(m), 1.0)
    mean = jnp.sum(x * m) / n
    d = (x - mean) * m

    def gamma(lag):
        a = d[: n_max - lag]
        b = d[lag:]
        pair = m[: n_max - lag] * m[lag:]
        return jnp.sum(a * b * pair) / n

    return jnp.stack([gamma(l) for l in range(1, max_lag + 1)])


def pacf(x: Array, n_valid: Array, max_lag: int) -> Array:
    """Partial autocorrelations via Durbin–Levinson on sample autocovariances."""
    n_max = x.shape[-1]
    idx = jnp.arange(n_max)
    m = (idx < n_valid).astype(x.dtype)
    n = jnp.maximum(jnp.sum(m), 1.0)
    mean = jnp.sum(x * m) / n
    d = (x - mean) * m
    gamma0 = jnp.sum(d * d) / n
    gammas = jnp.concatenate([gamma0[None], autocovariance(x, n_valid, max_lag)])

    # Durbin–Levinson (host-friendly small loop; max_lag is static & small)
    phi_prev = jnp.zeros((max_lag,))
    pacfs = []
    v = gamma0
    for kk in range(1, max_lag + 1):
        num = gammas[kk] - jnp.sum(phi_prev[: kk - 1] * gammas[1:kk][::-1])
        phi_kk = num / jnp.maximum(v, _EPS)
        pacfs.append(phi_kk)
        if kk > 1:
            upd = phi_prev[: kk - 1] - phi_kk * phi_prev[: kk - 1][::-1]
            phi_prev = phi_prev.at[: kk - 1].set(upd)
        phi_prev = phi_prev.at[kk - 1].set(phi_kk)
        v = v * (1.0 - phi_kk**2)
    return jnp.stack(pacfs)
