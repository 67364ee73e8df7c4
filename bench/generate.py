"""The benchmark's traffic: fleet windows made from a seed, on the device.

A vectorised copy of the model of ``repro.data.streams.fleet_like``.  Each
region has a latent driver (a diurnal cycle plus AR(1) weather), each site
adds its own AR(1) identity to its region's driver, and stream ``j`` of a
site mixes that site driver with its own AR(1) with weight ``w``:

    x_j = offset_j + scale_j * (w * B_site + sqrt(1 - w^2) * eta_j) + noise

so the within-site pairwise correlation is about ``w_i * w_j``.  The weight
is per region (``"mixing": {"by": "region"}``, as ``fleet_like`` mixes) or
per stream (``"by": "stream"``, the turbine channel coupling of
``turbine_like``).  Departures from ``fleet_like``, which loops in Python
over every element: every AR(1) starts at its stationary law, and the site
driver and stream terms are scaled by their stationary deviations instead
of the deviations of one drawn series.

Windows are made one at a time by one jitted call that carries the AR(1)
states, so the device holds a single window of temporaries, and are then
copied to the host: the runtime under test takes host windows.
"""
from __future__ import annotations

import functools
import json
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent

DRIVER_PHI, DRIVER_SIGMA, DRIVER_GAIN = 0.97, 0.2, 0.5
SITE_PHI, SITE_SIGMA, SITE_GAIN = 0.9, 0.3, 0.4
LOCAL_PHI, LOCAL_SIGMA = 0.9, 0.4
NOISE = 0.15                                  # sensor noise, x scale


def _ar_var(phi: float, sigma: float) -> float:
    return sigma * sigma / (1.0 - phi * phi)


# stationary deviation of the site driver: sin over whole periods (1/2),
# the region weather and the site identity
BASE_STD = float(np.sqrt(0.5 + DRIVER_GAIN ** 2 * _ar_var(DRIVER_PHI,
                                                          DRIVER_SIGMA)
                         + SITE_GAIN ** 2 * _ar_var(SITE_PHI, SITE_SIGMA)))
LOCAL_STD = float(np.sqrt(_ar_var(LOCAL_PHI, LOCAL_SIGMA)))


def load(kind: str, name: str) -> dict:
    """The data file ``bench/<kind>/<name>.json`` (``configs``, ``traffic``)."""
    path = HERE / kind / f"{name}.json"
    if not path.is_file():
        known = sorted(p.stem for p in (HERE / kind).glob("*.json"))
        raise FileNotFoundError(f"no {kind} file {path.name}; have {known}")
    with open(path) as f:
        return json.load(f)


def site_regions(cfg: dict) -> np.ndarray:
    """(E,) region of each site: contiguous blocks, as the topology has."""
    e, r = int(cfg["sites"]), int(cfg["regions"])
    return np.arange(e) // (e // r)


def mixing_weights(cfg: dict) -> np.ndarray:
    """(E, k) weight of the site driver in each stream."""
    e, k = int(cfg["sites"]), int(cfg["streams_per_site"])
    mix = cfg["mixing"]
    w = np.asarray(mix["weights"], np.float64)
    if mix["by"] == "region":
        return np.repeat(w[site_regions(cfg)][:, None], k, axis=1)
    if mix["by"] == "stream":
        if w.shape != (k,):
            raise ValueError(f"{k} streams need {k} weights, got {w.shape}")
        return np.broadcast_to(w, (e, k)).copy()
    raise ValueError(f"mixing by {mix['by']!r}: use 'region' or 'stream'")


def _seed_words(seed: int) -> np.ndarray:
    return np.random.SeedSequence(int(seed)).generate_state(4, np.uint32)


@functools.lru_cache(maxsize=1)
def _window_fn():
    """The jitted window maker; its shapes are its only constants, so every
    seed reuses one compiled program."""
    import jax
    import jax.numpy as jnp

    def ar_scan(states, innov):
        def step(c, z):
            c = tuple(phi * ci + zi for phi, ci, zi in
                      zip((DRIVER_PHI, SITE_PHI, LOCAL_PHI), c, z))
            return c, c
        return jax.lax.scan(step, states, innov)

    @functools.partial(jax.jit, static_argnames="n")
    def window(key_data, wid, period, states, offset, scale, wb, wl, region,
               n):
        (r,), (e, k) = states[0].shape, offset.shape
        key = jax.random.fold_in(jax.random.wrap_key_data(key_data), wid)
        kd, ks, kl, km = jax.random.split(key, 4)
        innov = (DRIVER_SIGMA * jax.random.normal(kd, (n, r)),
                 SITE_SIGMA * jax.random.normal(ks, (n, e)),
                 LOCAL_SIGMA * jax.random.normal(kl, (n, e, k)))
        states, (drv, site, loc) = ar_scan(states, innov)
        t = (wid * n + jnp.arange(n)).astype(jnp.float32)
        driver = (jnp.sin(2.0 * np.pi * t / period)[:, None]
                  + DRIVER_GAIN * drv)
        base = (driver[:, region] + SITE_GAIN * site) / BASE_STD
        x = wb * base[:, :, None] + wl * (loc / LOCAL_STD)
        noise = NOISE * jax.random.normal(km, (n, e, k))
        vals = offset + scale * (x + noise)
        return jnp.transpose(vals, (1, 2, 0)), states

    return window


def fleet_windows(cfg: dict, n_windows: int, seed: int) -> np.ndarray:
    """(n_windows, E, k, N) float32 host array of consecutive windows."""
    import jax

    e, r = int(cfg["sites"]), int(cfg["regions"])
    k, n = int(cfg["streams_per_site"]), int(cfg["window"])
    words = _seed_words(seed)
    rng = np.random.default_rng(words)
    offset = rng.uniform(20.0, 80.0, (e, k)).astype(np.float32)
    scale = rng.uniform(2.0, 6.0, (e, k)).astype(np.float32)
    w = mixing_weights(cfg)
    params = (offset, scale, w.astype(np.float32),
              np.sqrt(np.maximum(1.0 - w * w, 0.0)).astype(np.float32),
              site_regions(cfg).astype(np.int32))
    k0 = jax.random.wrap_key_data(np.asarray(words[2:], np.uint32))
    kd, ks, kl = jax.random.split(k0, 3)
    states = (np.sqrt(_ar_var(DRIVER_PHI, DRIVER_SIGMA))
              * jax.random.normal(kd, (r,)),
              np.sqrt(_ar_var(SITE_PHI, SITE_SIGMA))
              * jax.random.normal(ks, (e,)),
              LOCAL_STD * jax.random.normal(kl, (e, k)))
    window = _window_fn()
    key_data = np.asarray(words[:2], np.uint32)
    period = np.float32(cfg["diurnal_period"])
    out = np.empty((n_windows, e, k, n), np.float32)
    for i in range(n_windows):
        vals, states = window(key_data, np.int32(i), period, states, *params,
                              n=n)
        out[i] = np.asarray(vals)
    return out
