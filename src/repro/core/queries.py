"""Cloud-side aggregate queries and error metrics (§V-A4).

Queries run over the *reconstructed* window (real + imputed samples).  The
error metric is NRMSE (eq. 10), normalized by the mean of the true aggregate
per stream across windows.
"""
from __future__ import annotations

import numpy as np

from repro.api.registry import QUERIES


@QUERIES.register("AVG")
def avg(x: np.ndarray) -> float:
    return float(np.mean(x)) if len(x) else float("nan")


@QUERIES.register("VAR")
def var(x: np.ndarray) -> float:
    return float(np.var(x, ddof=1)) if len(x) > 1 else float("nan")


@QUERIES.register("MIN")
def vmin(x: np.ndarray) -> float:
    return float(np.min(x)) if len(x) else float("nan")


@QUERIES.register("MAX")
def vmax(x: np.ndarray) -> float:
    return float(np.max(x)) if len(x) else float("nan")


@QUERIES.register("MEDIAN")
def median(x: np.ndarray) -> float:
    return float(np.median(x)) if len(x) else float("nan")


def quantile(x: np.ndarray, q: float) -> float:
    return float(np.quantile(x, q)) if len(x) else float("nan")


# QUERIES is the global query registry (repro.api.registry): dict-style
# access (QUERIES["AVG"], "AVG" in QUERIES) keeps working; unknown names
# raise with the registered alternatives listed.


def nrmse(estimates: np.ndarray, truth: np.ndarray) -> float:
    """eq. 10 for one stream: RMSE over windows / mean |true aggregate|.

    estimates/truth: (T,) per-window aggregate values.
    """
    est = np.asarray(estimates, np.float64)
    tru = np.asarray(truth, np.float64)
    ok = np.isfinite(est) & np.isfinite(tru)
    if not ok.any():
        return float("nan")
    rmse = np.sqrt(np.mean((est[ok] - tru[ok]) ** 2))
    denom = max(abs(np.mean(tru[ok])), 1e-9)
    return float(rmse / denom)


def nrmse_rows(estimates: np.ndarray,
               truth: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Batched :func:`nrmse` over the last axis: (..., k, T) x (..., k, T)
    -> ((..., k) NRMSE, (..., k) bool mask of the rows taken in one pass).

    A row whose T estimates and T truths are all finite is computed with
    numpy reductions over the last axis of a C-contiguous float64 copy, which
    sum each row pairwise as the 1-D ``np.mean`` in :func:`nrmse` does, so
    every value equals :func:`nrmse` bit for bit.  A row with a NaN or an
    inf anywhere goes through :func:`nrmse` itself.
    """
    est = np.ascontiguousarray(estimates, np.float64)
    tru = np.ascontiguousarray(truth, np.float64)
    fast = np.isfinite(est).all(axis=-1) & np.isfinite(tru).all(axis=-1)
    e, t = est[fast], tru[fast]
    out = np.empty(fast.shape)
    out[fast] = (np.sqrt(np.mean((e - t) ** 2, axis=-1))
                 / np.maximum(np.abs(np.mean(t, axis=-1)), 1e-9))
    for row in zip(*np.nonzero(~fast)):
        out[row] = nrmse(est[row], tru[row])
    return out, fast


def nrmse_table(estimates: np.ndarray, truth: np.ndarray) -> np.ndarray:
    """(..., k, T) x (..., k, T) -> (..., k) per-stream NRMSE."""
    return nrmse_rows(estimates, truth)[0]
