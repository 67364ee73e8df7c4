"""The repro.planning engine layer (ISSUE 5).

  * ENGINES registry surface: "host" (alias "host_loop"), "batched",
    "sharded"; unknown names fail with alternatives listed.
  * Batched-vs-host-oracle parity across the FULL model x epsilon-policy
    grid — including "mean", "multi" and "exact_mse", which used to fall
    back to E round trips of the host loop — plus a hypothesis property
    over random (E, k, N) shapes.
  * The closed-form exact-MSE shrink equals the per-stream Python while
    loop it replaced.
  * Sharded-vs-batched equality: every allocation output bitwise, model
    floats to a few ULP (XLA's batch-size-dependent matmul reduction order
    in the normal-equations fit; see docs/planning.md).  CI re-runs this
    module under XLA_FLAGS=--xla_force_host_platform_device_count=8; the
    subprocess test below forces that layout from inside the tier-1 run.
  * plan_window routes through the engine as the degenerate E=1 case, and
    unsupported configs fail fast (UnsupportedPlanConfig) instead of
    silently drifting to another code path.
"""
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

# conftest installs the hypothesis fallback stub on bare containers — it
# must import before `from hypothesis import ...` when this module is
# imported outside pytest (the forced-device subprocess below)
from conftest import subprocess_env
from hypothesis import given, settings
from hypothesis import strategies as st
from repro.api import ScenarioConfig, DataSpec, TopologySpec, ControllerSpec
from repro.api.registry import (DEMAND_SIGNALS, ENGINES, IID_MODES,
                                UnknownComponentError)
from repro.core import epsilon as eps_mod
from repro.core.planner import plan_window
from repro.core.types import PlannerConfig, WindowBatch
from repro.data import fleet_like, fleet_windows
from repro.fleet import BudgetController, host_loop_plan
from repro.planning import UnsupportedPlanConfig

MODELS_GRID = ("linear", "cubic", "mean", "multi")
POLICIES_GRID = ("k_se", "alpha", "exact_mse")

# every allocation-relevant output; the remaining FleetPlan fields are the
# fitted-model floats (coeffs/loc/scale/explained_var/r2)
ALLOC_FIELDS = ("n_real", "n_imputed", "predictor", "eps", "objective",
                "mean", "var")


def _fleet_case(E=4, k=5, W=64, seed=7, frac=0.3):
    vals, _ = fleet_like(E, min(E, 2), k, n_points=2 * W, seed=seed)
    w = fleet_windows(vals, W)[0]
    counts = np.full((E, k), W, np.int64)
    budgets = np.full(E, frac * k * W)
    return w, counts, budgets


# ----------------------------------------------------------- registry surface

def test_engine_registry_names_and_aliases():
    assert ENGINES.names() == ("batched", "host", "host_loop", "sharded")
    assert ENGINES.get("host") is ENGINES.get("host_loop")
    with pytest.raises(UnknownComponentError, match="'sharded'"):
        ENGINES.get("warp")


def test_iid_mode_registry_and_scenario_validation():
    for name in ("none", "iid", "thinning", "m_dependence"):
        assert name in IID_MODES
    assert IID_MODES.get("none") is IID_MODES.get("iid")   # historical alias
    with pytest.raises(UnknownComponentError, match="iid mode"):
        ScenarioConfig(planner=PlannerConfig(iid_mode="weekly"))
    # the registered modes pass construction-time validation
    ScenarioConfig(planner=PlannerConfig(iid_mode="thinning"))
    ScenarioConfig(planner=PlannerConfig(iid_mode="m_dependence", m_lags=2))


def test_demand_signal_registry_and_controller():
    assert DEMAND_SIGNALS.names() == ("max_err", "obs_err", "pred_err")
    with pytest.raises(UnknownComponentError, match="demand signal"):
        ControllerSpec(demand_signal="vibes")
    obs = np.array([0.2, np.nan, 0.0])
    pred = np.array([0.1, 0.3, 0.4])
    np.testing.assert_array_equal(
        DEMAND_SIGNALS.get("obs_err")(obs, pred), [0.2, 0.3, 0.4])
    np.testing.assert_array_equal(
        DEMAND_SIGNALS.get("pred_err")(obs, pred), pred)
    np.testing.assert_array_equal(
        DEMAND_SIGNALS.get("max_err")(obs, pred), [0.2, 0.3, 0.4])
    # default signal is bit-for-bit the pre-registry controller: same
    # budgets from the same observations
    a = BudgetController(total_budget=400.0, n_sites=4)
    b = BudgetController(total_budget=400.0, n_sites=4,
                         demand_signal="obs_err")
    for c in (a, b):
        c.budgets()
        c.update(np.array([0.3, 0.1, 0.2, 0.05]), np.zeros(4),
                 objective=np.array([0.1, 0.1, 0.1, 0.1]))
    np.testing.assert_array_equal(a.budgets(), b.budgets())


def test_engine_field_validates_and_round_trips():
    cfg = ScenarioConfig(
        data=DataSpec(dataset="fleet", n_points=256, window=128, seed=0,
                      options={"k": 4}),
        planner=PlannerConfig(solver="closed_form", engine="sharded"),
        topology=TopologySpec(n_regions=2, sites_per_region=2, seed=0),
        queries=("AVG",))
    assert ScenarioConfig.from_json(cfg.to_json()) == cfg
    with pytest.raises(UnknownComponentError, match="plan engine"):
        ScenarioConfig(planner=PlannerConfig(engine="warp"))
    # engine-unsupported combos fail at construction, not deep in a run
    with pytest.raises(UnsupportedPlanConfig, match="'ipm'"):
        ScenarioConfig(planner=PlannerConfig(engine="batched"))
    with pytest.raises(UnsupportedPlanConfig, match="thinning"):
        ScenarioConfig(planner=PlannerConfig(solver="closed_form",
                                             engine="batched",
                                             iid_mode="thinning"))
    # a fleet scenario with engine=None resolves to the batched default, so
    # a host-only solver (the PlannerConfig default, "ipm") must be caught
    # here too — not at the first planned window
    fleet_kw = dict(
        data=DataSpec(dataset="fleet", n_points=256, window=128, seed=0,
                      options={"k": 4}),
        topology=TopologySpec(n_regions=2, sites_per_region=2, seed=0))
    with pytest.raises(UnsupportedPlanConfig, match="'ipm'"):
        ScenarioConfig(planner=PlannerConfig(), **fleet_kw)
    ScenarioConfig(planner=PlannerConfig(engine="host"), **fleet_kw)
    # direct runtime construction fails equally early
    from repro.api.experiment import FleetRuntime
    from repro.fleet import BudgetController, make_topology
    with pytest.raises(UnsupportedPlanConfig, match="'ipm'"):
        FleetRuntime(topology=make_topology(2, 2, 4, seed=0),
                     controller=BudgetController(total_budget=400.0,
                                                 n_sites=4))


# --------------------------------------------- batched vs host-oracle parity

@pytest.mark.parametrize("model", MODELS_GRID)
@pytest.mark.parametrize("policy", POLICIES_GRID)
def test_batched_matches_host_oracle_full_grid(model, policy):
    """Acceptance: mean / multi / exact_mse run through the jitted batched
    engine (no host-loop fallback) and match the host oracle within
    rounding tolerance."""
    w, counts, budgets = _fleet_case()
    cfg = PlannerConfig(solver="closed_form", model=model,
                        epsilon_policy=policy,
                        epsilon_scale=0.5 if policy == "alpha" else 1.0)
    plan = ENGINES.get("batched").plan_fleet(w, counts, budgets, cfg)
    assert "payloads" not in plan            # genuinely the array engine
    nr_h, ns_h, p_h = host_loop_plan(w, counts, budgets, cfg)
    assert (plan["predictor"] == p_h).mean() >= 0.95   # argmax ties may flip
    assert np.abs(plan["n_real"] - nr_h).max() <= 1
    assert (plan["n_real"] == nr_h).mean() >= 0.9
    assert np.abs(plan["n_imputed"] - ns_h).max() <= 2
    assert (plan["n_imputed"] == ns_h).mean() >= 0.9
    if model == "mean":
        # mean imputation has exactly zero explained variance (§III-B2)
        assert np.all(plan["explained_var"] == 0.0)
        assert np.all(plan["r2"] == 0.0)
    if model == "multi":
        assert plan["predictor"].shape == counts.shape + (2,)


def test_batched_exact_mse_only_shrinks_imputation():
    w, counts, budgets = _fleet_case(seed=11)
    base = PlannerConfig(solver="closed_form", epsilon_policy="k_se")
    capped = PlannerConfig(solver="closed_form", epsilon_policy="exact_mse")
    p_base = ENGINES.get("batched").plan_fleet(w, counts, budgets, base)
    p_mse = ENGINES.get("batched").plan_fleet(w, counts, budgets, capped)
    np.testing.assert_array_equal(p_base["n_real"], p_mse["n_real"])
    assert np.all(p_mse["n_imputed"] <= p_base["n_imputed"])


def test_batched_straggler_stream_gets_imputed():
    """A count-0 stream gets no real samples but >=1 imputed one (1e),
    for every batched model family."""
    w, counts, budgets = _fleet_case(E=4, k=4, seed=4)
    counts[1, 2] = 0
    for model in MODELS_GRID:
        cfg = PlannerConfig(solver="closed_form", model=model)
        plan = ENGINES.get("batched").plan_fleet(w, counts, budgets, cfg)
        assert plan["n_real"][1, 2] == 0, model
        assert plan["n_imputed"][1, 2] >= 1, model


@settings(max_examples=12, deadline=None)
@given(
    model=st.sampled_from(MODELS_GRID),
    policy=st.sampled_from(POLICIES_GRID),
    e=st.sampled_from([1, 2, 4]),
    k=st.sampled_from([3, 5]),
    n=st.sampled_from([32, 64]),
    seed=st.integers(0, 2**16),
    frac=st.sampled_from([0.15, 0.3, 0.6]),
)
def test_batched_parity_property(model, policy, e, k, n, seed, frac):
    """Property: random (E, k, N) shapes, seeds and budgets — batched
    allocations stay within rounding tolerance of the host oracle."""
    rng = np.random.default_rng(seed)
    w = rng.normal(10.0, 3.0, (e, k, n)).astype(np.float32)
    w[:, 1] = 0.7 * w[:, 0] + 0.3 * w[:, 1]    # give predictors something
    counts = np.full((e, k), n, np.int64)
    budgets = np.maximum(rng.uniform(0.5, 1.5, e) * frac * k * n, 4.0)
    cfg = PlannerConfig(solver="closed_form", model=model,
                        epsilon_policy=policy)
    plan = ENGINES.get("batched").plan_fleet(w, counts, budgets, cfg)
    nr_h, ns_h, _ = host_loop_plan(w, counts, budgets, cfg)
    assert np.abs(plan["n_real"] - nr_h).max() <= 1
    assert np.abs(plan["n_imputed"] - ns_h).max() <= 2


# ------------------------------------------------- the closed-form shrink

def _shrink_reference(nr, ns, sigma2, v, cap, tol=1e-12):
    """The per-stream Python while loop exact_mse_shrink replaced."""
    out = ns.copy()
    for i in range(len(ns)):
        while out[i] > 0:
            tot = nr[i] + out[i] - 1.0
            if tot <= 0:
                break
            bias = (out[i] * sigma2[i] - (out[i] - 1.0) * v[i]) / tot
            if bias <= cap[i] + tol:
                break
            out[i] -= 1
    return out


def _shrink_f64(nr, ns, sigma2, v, cap):
    """Run the jnp shrink in f64 so the IEEE arithmetic matches the f64
    reference loop exactly (the production path runs it in the planner's
    f32; the grid tests above cover that end to end)."""
    with jax.enable_x64(True):
        return np.asarray(eps_mod.exact_mse_shrink(
            jnp.asarray(nr, jnp.float64), jnp.asarray(ns, jnp.float64),
            jnp.asarray(sigma2, jnp.float64), jnp.asarray(v, jnp.float64),
            jnp.asarray(cap, jnp.float64)))


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_exact_mse_shrink_equals_while_loop(seed):
    rng = np.random.default_rng(seed)
    k = 256
    nr = rng.integers(0, 40, k).astype(np.float64)
    ns = rng.integers(0, 40, k).astype(np.float64)
    sigma2 = rng.uniform(0.1, 4.0, k)
    v = sigma2 * rng.uniform(0.0, 1.0, k)
    cap = rng.uniform(0.0, 1.0, k)
    got = _shrink_f64(nr, ns, sigma2, v, cap)
    ref = _shrink_reference(nr, ns, sigma2, v, cap)
    np.testing.assert_array_equal(got, ref)


@settings(max_examples=50, deadline=None)
@given(
    nr=st.integers(0, 30), ns=st.integers(0, 30),
    sigma2=st.floats(1e-3, 10.0), v_frac=st.floats(0.0, 1.0),
    cap=st.floats(0.0, 5.0),
)
def test_exact_mse_shrink_property(nr, ns, sigma2, v_frac, cap):
    args = (np.array([float(nr)]), np.array([float(ns)]),
            np.array([sigma2]), np.array([sigma2 * v_frac]),
            np.array([cap]))
    np.testing.assert_array_equal(_shrink_f64(*args),
                                  _shrink_reference(*args))


# --------------------------------------------------- E=1 plan_window routing

def test_plan_window_routes_through_batched_engine():
    w, counts, _ = _fleet_case(E=1, k=5)
    batch = WindowBatch.from_numpy(w[0], counts[0], 3)
    p_b, d_b = plan_window(batch, 90.0, PlannerConfig(
        solver="closed_form", engine="batched"))
    p_h, d_h = plan_window(batch, 90.0, PlannerConfig(solver="closed_form"))
    assert np.abs(p_b.n_real - p_h.n_real).max() <= 1
    assert p_b.n_real.sum() == p_h.n_real.sum()        # same net budget
    assert np.abs(p_b.n_imputed - p_h.n_imputed).max() <= 2
    assert d_b.solver_feasible
    # payload respects constraint 1d against what actually shipped
    for i in range(len(p_b.n_imputed)):
        assert p_b.n_imputed[i] <= len(
            p_b.real_values[int(p_b.predictor[i])])


def test_plan_window_unsupported_config_fails_fast():
    w, counts, _ = _fleet_case(E=1, k=4)
    batch = WindowBatch.from_numpy(w[0], counts[0], 0)
    with pytest.raises(UnsupportedPlanConfig, match="host-only"):
        plan_window(batch, 60.0, PlannerConfig(engine="batched"))  # ipm
    with pytest.raises(UnsupportedPlanConfig, match="cost_per_sample"):
        plan_window(batch, 60.0, PlannerConfig(
            solver="closed_form", engine="batched",
            cost_per_sample=np.ones(4)))


def test_plan_window_host_engine_name_is_default_path():
    w, counts, _ = _fleet_case(E=1, k=4)
    batch = WindowBatch.from_numpy(w[0], counts[0], 1)
    p_none, _ = plan_window(batch, 70.0, PlannerConfig(seed=3))
    p_host, _ = plan_window(batch, 70.0, PlannerConfig(seed=3,
                                                       engine="host"))
    np.testing.assert_array_equal(p_none.n_real, p_host.n_real)
    np.testing.assert_array_equal(p_none.n_imputed, p_host.n_imputed)


# ------------------------------------------------------- sharded engine

def _assert_sharded_matches_batched(E=12, k=4, W=64, seed=1):
    vals, _ = fleet_like(E, 3, k, n_points=2 * W, seed=seed)
    w = fleet_windows(vals, W)[0]
    counts = np.full((E, k), W, np.int64)
    counts[min(2, E - 1), 1] = 0                       # straggler survives pad
    budgets = np.full(E, 0.25 * k * W)
    cfg = PlannerConfig(solver="closed_form")
    b = ENGINES.get("batched").plan_fleet(w, counts, budgets, cfg)
    s = ENGINES.get("sharded").plan_fleet(w, counts, budgets, cfg)
    for f in ALLOC_FIELDS:
        np.testing.assert_array_equal(b[f], s[f], err_msg=f)
    for f in ("coeffs", "loc", "scale", "explained_var", "r2"):
        np.testing.assert_allclose(b[f], s[f], rtol=1e-4, atol=1e-4,
                                   err_msg=f)


def test_sharded_matches_batched():
    """Every allocation output bitwise-equal; fitted-model floats to a few
    ULP.  E=12 is deliberately not a multiple of the forced 8-device CI
    layout, so the empty-site padding path is exercised too."""
    _assert_sharded_matches_batched()


def test_sharded_through_experiment():
    from repro.api import Experiment
    scenario = ScenarioConfig(
        data=DataSpec(dataset="fleet", n_points=128, window=64, seed=2,
                      options={"k": 4}),
        budget_fraction=0.25,
        planner=PlannerConfig(solver="closed_form", engine="sharded"),
        topology=TopologySpec(n_regions=2, sites_per_region=3, seed=2),
        controller=ControllerSpec(demand_signal="pred_err"),
        queries=("AVG",))
    exp = Experiment.from_scenario(scenario)
    assert exp.runtime.engine.name == "sharded"
    r = exp.run()
    assert np.isfinite(r.nrmse["AVG"])
    assert r.wan_bytes < r.full_bytes


@pytest.mark.slow
def test_sharded_bitwise_parity_under_forced_devices():
    """The multi-device layout CI forces, reproduced from inside tier-1:
    8 host devices, sharded allocations bitwise-equal to batched."""
    prog = textwrap.dedent("""
        import jax, numpy as np
        assert len(jax.devices()) == 8, jax.devices()
        import test_planning_engine as t
        t._assert_sharded_matches_batched()
        print("OK", len(jax.devices()))
    """)
    out = subprocess.run(
        [sys.executable, "-c", prog], env=subprocess_env(8),
        cwd=__file__.rsplit("/", 1)[0], capture_output=True, text=True,
        timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "OK 8" in out.stdout
