import numpy as np
import pytest

from repro.core import queries as Q


def test_aggregates(rng):
    x = rng.normal(3, 1, 100)
    assert abs(Q.avg(x) - x.mean()) < 1e-9
    assert abs(Q.var(x) - x.var(ddof=1)) < 1e-9
    assert Q.vmin(x) == x.min() and Q.vmax(x) == x.max()
    assert Q.median(x) == np.median(x)


def test_nrmse_zero_for_exact():
    t = np.array([1.0, 2.0, 3.0])
    assert Q.nrmse(t, t) == 0.0


def test_nrmse_normalization():
    t = np.array([10.0, 10.0])
    e = np.array([11.0, 9.0])
    assert abs(Q.nrmse(e, t) - 0.1) < 1e-9


def test_nrmse_ignores_nan():
    t = np.array([10.0, 10.0, 10.0])
    e = np.array([11.0, np.nan, 9.0])
    assert abs(Q.nrmse(e, t) - 0.1) < 1e-9


def _planted(T, E=6, k=4):
    """(E, k, T) estimate/truth views with the rows the per-row path takes:
    a NaN or an inf in the estimate or the truth, an all-NaN estimate row,
    plus a row whose mean truth is 0 (the 1e-9 floor)."""
    rng = np.random.default_rng(T)
    tru = rng.normal(50, 10, (T, E, k))
    est = tru + rng.normal(0, 1, (T, E, k))
    est[0, 0, 0] = np.nan
    tru[-1, 1, 2] = np.nan
    est[T // 2, 2, 1] = np.inf
    tru[0, 3, 3] = -np.inf
    est[:, 4, 0] = np.nan
    tru[:, 5, 1] = 0.0
    return est.transpose(1, 2, 0), tru.transpose(1, 2, 0)


@pytest.mark.parametrize("layout", ["view", "contiguous"])
@pytest.mark.parametrize("T", [1, 7, 8, 9, 16, 33])
def test_nrmse_table_equals_per_row_nrmse_bitwise(T, layout):
    est, tru = _planted(T)
    if layout == "contiguous":
        est, tru = np.ascontiguousarray(est), np.ascontiguousarray(tru)
    E, k = tru.shape[:2]
    want = np.array([[Q.nrmse(est[s, i], tru[s, i]) for i in range(k)]
                     for s in range(E)])
    got, fast = Q.nrmse_rows(est, tru)
    assert got.shape == (E, k)
    assert np.array_equal(got, want, equal_nan=True)
    assert np.array_equal(Q.nrmse_table(est, tru), want, equal_nan=True)
    assert np.array_equal(Q.nrmse_table(est[5], tru[5]), want[5])
    planted = {(0, 0), (1, 2), (2, 1), (3, 3), (4, 0)}
    assert {tuple(r) for r in np.argwhere(~fast)} == planted
    assert np.isnan(got[4, 0])
    assert got[5, 1] == np.sqrt(np.mean(est[5, 1] ** 2)) / 1e-9
