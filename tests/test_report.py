"""The shared fleet roll-up (``repro.runtime.report.aggregate_fleet``)."""
import numpy as np

from repro.fleet.topology import make_topology
from repro.runtime.report import aggregate_fleet

QNAMES = ("AVG", "VAR", "MIN", "MAX")


def _equal(a, b):
    """Same keys, types and bits, recursively (NaN equal to NaN)."""
    if isinstance(a, dict):
        return a.keys() == b.keys() and all(_equal(a[q], b[q]) for q in a)
    if isinstance(a, np.ndarray):
        return (a.dtype == b.dtype and a.shape == b.shape
                and a.tobytes() == b.tobytes())
    if isinstance(a, float):
        return type(b) is float and (a == b or (a != a and b != b))
    return type(a) is type(b) and a == b


def test_est_q_is_est_reports_as_an_equal_copy():
    T, k = 9, 3
    topo = make_topology(2, 4, k)
    E = topo.n_sites
    rng = np.random.default_rng(0)
    tru = {q: rng.normal(20, 5, (T, E, k)) for q in QNAMES}
    est = {q: tru[q] + rng.normal(0, 1, (T, E, k)) for q in QNAMES}
    est["AVG"][3, 1, 2] = np.nan
    est["VAR"][:, 2, 0] = np.nan
    kw = dict(topology=topo, qnames=QNAMES, tru=tru, ages=np.zeros((T, E)),
              bytes_per_site=np.arange(E), cost_per_site=np.arange(E) * 0.5,
              gaps=0, revisions=0, late_drops=0, duplicates=0,
              arrival_lag_ms=np.zeros(E), plan_seconds=0.0, plan_windows=T,
              budget_history=np.ones((T, E)), total_tuples=T * E * k * 16)
    same = aggregate_fleet(est=est, est_q=est, **kw)
    copy = aggregate_fleet(est=est, est_q={q: v.copy() for q, v in
                                           est.items()}, **kw)
    assert _equal(same, copy)
    assert same["fleet_nrmse"] == same["fleet_nrmse_at_query"]
    assert np.isnan(same["site_nrmse"]["VAR"][2, 0])
