"""jit'd wrapper: padding, backend dispatch, derived statistics."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.dispatch import resolve_use_kernel
from repro.kernels.stream_stats.kernel import (DEFAULT_TK, DEFAULT_TN,
                                               stream_stats_fleet_pallas,
                                               stream_stats_pallas)
from repro.kernels.stream_stats.ref import stream_stats_ref


@functools.partial(jax.jit, static_argnames=("use_kernel", "interpret"))
def window_moments_xxt(x: jax.Array, use_kernel: bool = True,
                       interpret: bool = False):
    """Raw power sums + cross products of a full window (k, N).

    Zero-pads to tile multiples (exact for sums/products) and runs the
    Pallas kernel, or the jnp oracle when ``use_kernel`` is False
    (None = auto, :func:`repro.kernels.dispatch.resolve_use_kernel`).
    """
    k, n = x.shape
    if not resolve_use_kernel(use_kernel, interpret):
        return stream_stats_ref(x)
    tk = min(DEFAULT_TK, max(1, k))
    tn = min(DEFAULT_TN, max(128, 1 << int(np.ceil(np.log2(max(n, 1))))))
    kp = int(np.ceil(k / tk) * tk)
    np_ = int(np.ceil(n / tn) * tn)
    xp = jnp.pad(x, ((0, kp - k), (0, np_ - n)))
    mom, xxt = stream_stats_pallas(xp, tk=tk, tn=tn, interpret=interpret)
    return mom[:k], xxt[:k, :k]


@functools.partial(jax.jit, static_argnames=("use_kernel", "interpret"))
def fleet_window_moments_xxt(x: jax.Array, use_kernel=None,
                             interpret: bool = False):
    """Raw power sums + per-site cross products for a whole fleet (E, k, N).

    Flattens the fleet to the (E·kp, N) layout (per-site k zero-padded up to
    a sublane multiple) and runs the block-diagonal ``stream_stats`` pass —
    one kernel launch for all E sites, computing only the E diagonal
    (kp, kp) tiles.  Off-kernel the vmapped jnp oracle is used.
    use_kernel=None means auto: the Pallas kernel on TPU (or under
    ``interpret``), the oracle elsewhere
    (:func:`repro.kernels.dispatch.resolve_use_kernel`).

    Returns (moments (E, k, 4), xxt (E, k, k)), both f32.
    """
    e, k, n = x.shape
    if not resolve_use_kernel(use_kernel, interpret):
        return jax.vmap(stream_stats_ref)(x)
    kp = int(np.ceil(k / 8) * 8)
    tn = min(DEFAULT_TN, max(128, 1 << int(np.ceil(np.log2(max(n, 1))))))
    np_ = int(np.ceil(n / tn) * tn)
    xp = jnp.pad(x, ((0, 0), (0, kp - k), (0, np_ - n))).reshape(e * kp, np_)
    mom, xxt = stream_stats_fleet_pallas(xp, kp=kp, tn=tn, interpret=interpret)
    mom = mom.reshape(e, kp, 4)[:, :k]
    xxt = xxt.reshape(e, kp, kp)[:, :k, :k]
    return mom, xxt


def derived_stats(mom: jax.Array, xxt: jax.Array, n: int):
    """(S1..S4, XXt, N) -> mean, var(unbiased), m4, cov(unbiased).

    Matches repro.core.stats for full (unmasked) windows.
    """
    nf = jnp.asarray(float(n), jnp.float32)
    s1, s2, s3, s4 = mom[:, 0], mom[:, 1], mom[:, 2], mom[:, 3]
    mean = s1 / nf
    m2 = s2 / nf - mean**2
    var = m2 * nf / jnp.maximum(nf - 1.0, 1.0)
    m4 = (s4 - 4 * mean * s3 + 6 * mean**2 * s2 - 3 * mean**4 * nf) / nf
    cov = (xxt / nf - mean[:, None] * mean[None, :]) \
        * nf / jnp.maximum(nf - 1.0, 1.0)
    return mean, var, jnp.maximum(m4, 0.0), cov
