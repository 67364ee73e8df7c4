"""Fleet subsystem: topology, batched stats/planning parity, closed-form
solver, budget controller, and the E>=64 end-to-end run."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import stats as S
from repro.core.types import PlannerConfig
from repro.data import fleet_like, fleet_windows
from repro.api.experiment import FleetRuntime
from repro.fleet import (BudgetController, fleet_plan, host_loop_plan,
                         make_topology, water_fill)
from repro.kernels.stream_stats.ops import fleet_window_moments_xxt
from repro.kernels.stream_stats.ref import stream_stats_ref


# ---------------------------------------------------------------- topology

def test_topology_shape_and_regions():
    topo = make_topology(n_regions=3, sites_per_region=4, k=5, seed=0)
    assert topo.n_sites == 12 and topo.k == 5
    assert topo.region_names == ("region0", "region1", "region2")
    reg = topo.region_of()
    assert reg.shape == (12,) and set(reg) == {0, 1, 2}
    # dense site ids in order
    assert [s.site_id for s in topo.sites] == list(range(12))


def test_topology_rejects_ragged_k():
    from repro.fleet.topology import (FleetTopology, LinkSpec, RegionSpec,
                                      SiteSpec)
    sites = (SiteSpec(0, "r", 3, LinkSpec()), SiteSpec(1, "r", 4, LinkSpec()))
    with pytest.raises(ValueError):
        FleetTopology(regions=(RegionSpec("r", sites),))


# ------------------------------------------------- batched stats and kernel

def test_fleet_kernel_matches_vmapped_ref():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(2.0, 1.5, (5, 6, 200)), jnp.float32)
    mom_k, xxt_k = fleet_window_moments_xxt(x, use_kernel=True, interpret=True)
    mom_r, xxt_r = jax.vmap(stream_stats_ref)(x)
    np.testing.assert_allclose(mom_k, mom_r, rtol=2e-5, atol=1e-2)
    np.testing.assert_allclose(xxt_k, xxt_r, rtol=2e-5, atol=1e-2)


@pytest.mark.parametrize("dependence", ["pearson", "spearman"])
def test_stats_from_sums_matches_window_stats(dependence):
    """Exact agreement regime: every count is 0 or N (full windows plus
    whole-stream stragglers — what the fleet runtime produces)."""
    rng = np.random.default_rng(1)
    k, n = 6, 256
    x = rng.normal(10.0, 3.0, (k, n)).astype(np.float32)
    x[1] = 0.8 * x[0] + 0.2 * x[1]
    for counts in (np.full(k, n, np.int32),
                   np.array([n, n, 0, n, 0, n], np.int32)):
        cj = jnp.asarray(counts)
        mask = (jnp.arange(n)[None, :] < cj[:, None]).astype(jnp.float32)
        vals = jnp.asarray(x)
        mom, xxt = stream_stats_ref(vals * mask)
        got = S.stats_from_sums(mom, xxt, cj)
        if dependence == "spearman":
            rmom, rxxt = stream_stats_ref(S.rank_transform(vals, cj) * mask)
            got_corr = S.corr_from_sums(rmom, rxxt, cj)
        else:
            got_corr = got.corr
        ref = S.window_stats(vals, cj, dependence=dependence)
        for field in ("mean", "var", "m4", "var_of_var", "cov"):
            np.testing.assert_allclose(np.asarray(getattr(got, field)),
                                       np.asarray(getattr(ref, field)),
                                       rtol=3e-4, atol=3e-3, err_msg=field)
        np.testing.assert_allclose(np.asarray(got_corr), np.asarray(ref.corr),
                                   rtol=1e-3, atol=2e-3)


# --------------------------------------------------------- closed-form solver

def test_closed_form_respects_constraints():
    from repro.core import solver as solver_mod
    rng = np.random.default_rng(2)
    k = 8
    n_obs = jnp.asarray(rng.integers(20, 200, k), jnp.float32)
    sigma2 = jnp.asarray(rng.uniform(0.5, 4.0, k), jnp.float32)
    v = sigma2 * jnp.asarray(rng.uniform(0.0, 0.9, k), jnp.float32)
    eps = 0.1 * sigma2
    q = jnp.asarray(rng.uniform(0.5, 2.0, k), jnp.float32)
    pred = jnp.asarray((np.arange(k) + 1) % k, jnp.int32)
    budget = jnp.asarray(150.0)
    nr, ns, obj = solver_mod.closed_form_alloc(
        q, jnp.ones(k), n_obs, sigma2, v, eps, budget, pred)
    nr, ns = np.asarray(nr), np.asarray(ns)
    assert (nr >= 0).all() and (nr <= np.asarray(n_obs)).all()      # 1c
    assert nr.sum() <= 150 + 1e-6                                   # 1f
    assert (ns <= nr[np.asarray(pred)]).all()                       # 1d
    assert (nr + ns >= 1).all()                                     # 1e
    # eq. 11 at the integer point
    lhs = ns * np.asarray(sigma2) - (ns - 1) * np.asarray(v)
    rhs = (nr + ns - 1) * np.asarray(eps)
    ok = (ns == 0) | (lhs <= rhs + 1e-4)
    assert ok.all()
    assert float(obj) > 0


def test_closed_form_through_plan_window():
    """cfg.solver='closed_form' flows through the Algorithm-1 planner and
    spends the (net) budget like the IPM does."""
    from repro.core.planner import plan_window
    from repro.data import turbine_like
    from repro.data.streams import windows_from_matrix
    vals, _ = turbine_like(512, seed=0, k=6)
    w = windows_from_matrix(vals, 256)[0]
    p_cf, d_cf = plan_window(w, 300.0, PlannerConfig(solver="closed_form"))
    p_ipm, d_ipm = plan_window(w, 300.0, PlannerConfig(solver="ipm"))
    assert p_cf.n_real.sum() == p_ipm.n_real.sum()          # same net budget
    # the closed form is a relaxation: objective within a factor of the IPM's
    assert float(d_cf.allocation.objective) <= \
        2.0 * float(d_ipm.allocation.objective)


# ------------------------------------------------------------ batched parity

def test_batched_planner_matches_host_loop():
    """Acceptance: fleet_plan allocations match E independent plan_window
    calls (same closed-form solver, same seeds) within rounding tolerance."""
    E, k, W = 16, 6, 128
    vals, _ = fleet_like(E, 4, k, n_points=256, seed=3)
    w = fleet_windows(vals, W)[0]
    counts = np.full((E, k), W, np.int64)
    budgets = np.full(E, 0.25 * k * W)
    plan = fleet_plan(jnp.asarray(w), jnp.asarray(counts, jnp.int32),
                      jnp.asarray(budgets, jnp.float32), 1.0)
    nr_h, ns_h, p_h = host_loop_plan(w, counts, budgets,
                                     PlannerConfig(solver="closed_form"))
    nr_b = np.asarray(plan.n_real)
    ns_b = np.asarray(plan.n_imputed)
    p_b = np.asarray(plan.predictor)
    assert (p_b == p_h).mean() >= 0.95          # argmax ties may flip
    assert np.abs(nr_b - nr_h).max() <= 1
    assert (nr_b == nr_h).mean() >= 0.9
    assert np.abs(ns_b - ns_h).max() <= 2
    assert (ns_b == ns_h).mean() >= 0.9


def test_batched_planner_straggler_stream():
    """A count-0 stream gets no real samples but >=1 imputed one (1e)."""
    E, k, W = 4, 4, 128
    vals, _ = fleet_like(E, 2, k, n_points=128, seed=4,
                         region_strength=[0.9, 0.8])
    w = fleet_windows(vals, W)[0]
    counts = np.full((E, k), W, np.int64)
    counts[1, 2] = 0
    plan = fleet_plan(jnp.asarray(w), jnp.asarray(counts, jnp.int32),
                      jnp.full((E,), 100.0, jnp.float32), 1.0)
    nr = np.asarray(plan.n_real)
    ns = np.asarray(plan.n_imputed)
    assert nr[1, 2] == 0
    assert ns[1, 2] >= 1


# ----------------------------------------------------------------- controller

def test_water_fill_conserves_and_clips():
    d = np.array([1.0, 1.0, 8.0, 10.0])
    b = water_fill(d, 100.0, lo=np.full(4, 10.0), hi=np.full(4, 40.0))
    assert abs(b.sum() - 100.0) < 1e-6
    assert (b >= 10.0 - 1e-9).all() and (b <= 40.0 + 1e-9).all()
    assert b[3] > b[0]            # more demand, more budget


def test_controller_shifts_budget_to_weak_sites():
    ctrl = BudgetController(total_budget=400.0, n_sites=4)
    assert np.allclose(ctrl.budgets(), 100.0)       # first window: equal
    # site 0 strongly correlated + low error; site 3 weak + high error
    ctrl.update(obs_err=np.array([0.01, 0.05, 0.1, 0.3]),
                r2=np.array([0.95, 0.6, 0.3, 0.05]))
    b = ctrl.budgets()
    assert abs(b.sum() - 400.0) < 1e-6
    assert b[0] < 100.0 < b[3]
    assert b[0] >= 0.3 * 100.0 - 1e-9               # floor respected
    ctrl_static = BudgetController(total_budget=400.0, n_sites=4,
                                   mode="static")
    ctrl_static.update(obs_err=np.array([0.01, 0.05, 0.1, 0.3]),
                       r2=np.array([0.95, 0.6, 0.3, 0.05]))
    assert np.allclose(ctrl_static.budgets(), 100.0)


# ---------------------------------------------------------------- end to end

def test_fleet_experiment_e64_end_to_end():
    """Acceptance: E >= 64 sites run end-to-end through batched planning."""
    E, R, k, W = 64, 4, 4, 64
    vals, _ = fleet_like(E, R, k, n_points=128, seed=0)
    topo = make_topology(R, E // R, k, seed=0)
    ctrl = BudgetController(total_budget=0.25 * E * k * W, n_sites=E)
    exp = FleetRuntime(topology=topo, controller=ctrl,
                          cfg=PlannerConfig(solver="closed_form"))
    r = exp.run(fleet_windows(vals, W))
    assert r["plan_windows"] == 2
    assert np.isfinite(r["fleet_nrmse"]["AVG"])
    assert r["wan_bytes"] < r["full_bytes"]
    assert r["gaps"] == 0
    assert len(r["region_nrmse"]) == R
    assert r["budget_history"].shape == (2, E)


def test_fleet_experiment_with_faults():
    """WAN drops and a straggler site flow through the fleet runtime with
    the single-edge fault semantics (stale serving; imputation cover)."""
    E, R, k, W = 8, 2, 4, 64
    vals, _ = fleet_like(E, R, k, n_points=256, seed=1)
    topo = make_topology(R, E // R, k, seed=1, drop_prob=0.5)
    ctrl = BudgetController(total_budget=0.3 * E * k * W, n_sites=E)
    exp = FleetRuntime(topology=topo, controller=ctrl,
                          cfg=PlannerConfig(solver="closed_form"),
                          straggler_drop=lambda wid, s, i: (s == 2 and i == 1))
    r = exp.run(fleet_windows(vals, W))
    assert r["gaps"] > 0                    # drops happened and were recorded
    assert np.isfinite(r["fleet_nrmse"]["AVG"])


def test_fleet_experiment_with_interpret_kernels():
    """The whole fleet experiment (event loop, rebalancing, freshness) with
    both Pallas kernels in interpret mode in place of the jnp reference."""
    from repro.api import (ControllerSpec, DataSpec, Experiment,
                           ScenarioConfig, TopologySpec)
    E, R, K, W = 6, 2, 4, 128
    scenario = ScenarioConfig(
        data=DataSpec(dataset="fleet", n_points=2 * W, window=W, seed=0,
                      options={"k": K}),
        budget_fraction=0.25, planner=PlannerConfig(solver="closed_form"),
        topology=TopologySpec(n_regions=R, sites_per_region=E // R, seed=0),
        controller=ControllerSpec(), queries=("AVG", "VAR"))
    res = Experiment.from_scenario(scenario, use_kernel=True,
                                   interpret=True).run()
    assert np.isfinite(res.nrmse["AVG"])
    assert res.wan_bytes < res.full_bytes
    assert np.isfinite(res.freshness_ms["p99_ms"])


def test_fleet_kernel_path_interpret():
    """The Pallas block-diagonal kernel path, interpret mode (CI smoke)."""
    E, R, k, W = 4, 2, 4, 128
    vals, _ = fleet_like(E, R, k, n_points=128, seed=2)
    w = fleet_windows(vals, W)[0]
    counts = np.full((E, k), W, np.int64)
    budgets = np.full(E, 100.0)
    plan_k = fleet_plan(jnp.asarray(w), jnp.asarray(counts, jnp.int32),
                        jnp.asarray(budgets, jnp.float32), 1.0,
                        use_kernel=True, interpret=True)
    plan_r = fleet_plan(jnp.asarray(w), jnp.asarray(counts, jnp.int32),
                        jnp.asarray(budgets, jnp.float32), 1.0,
                        use_kernel=False)
    assert np.abs(np.asarray(plan_k.n_real)
                  - np.asarray(plan_r.n_real)).max() <= 1
