"""Parity and validation tests for the scan runtime (repro.runtime).

The contract under test (docs/runtime.md): under zero-latency transport and
the shared RNG streams, a ``runtime="scan"`` run reproduces the event loop's
RunReport aggregates bit-for-bit, and ``runtime="scan_steps"`` is bit-for-bit
a scan run.  Also covered here: the sampler/rank identities the throughput
work leans on, scenario validation (what the scan runtime must refuse),
the bandwidth serialization-delay satellite and the per-query controller
split, plus the 8-device sharded-in-scan pin.
"""
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import subprocess_env

from repro.api import (ControllerSpec, DataSpec, Experiment, ScenarioConfig,
                       TopologySpec, TransportSpec)
from repro.core.samplers import draw_samples
from repro.core.stats import COUNTING_RANK_MAX_N, ordinal_ranks, rank_transform
from repro.core.types import EdgePayload, PlannerConfig
from repro.runtime.step import draw_fleet_samples, sample_fleet

K = 3
WINDOW = 24


def _fleet_scenario(E, runtime, *, n_windows=4, mode="static", planner=None,
                    controller=None):
    return ScenarioConfig(
        name=f"scan-test/E{E}",
        data=DataSpec(dataset="fleet", n_points=n_windows * WINDOW,
                      window=WINDOW, seed=1, options={"k": K}),
        planner=planner or PlannerConfig(solver="closed_form", seed=3),
        topology=TopologySpec(n_regions=2, sites_per_region=E // 2, seed=0,
                              latency_scale=0.0),
        controller=controller or ControllerSpec(mode=mode),
        queries=("AVG", "VAR", "MIN", "MAX"),
        runtime=runtime)


# ==========================================================================
# scan vs event: the bitwise parity guarantee
# ==========================================================================

class _InjectedPlans:
    """An ENGINES stand-in serving the scan's own per-window plan arrays to
    the event loop — the semantics-oracle harness.  Given identical plans,
    the RNG streams are integer-exact and every downstream byte/estimate
    goes through the event path's host code, so the reports must be
    bit-for-bit equal; any drift is a runtime-harness bug, not float noise.
    """

    name = "injected"

    def __init__(self, ys):
        self.fields = ("r2", "objective") + tuple(
            f for f in ys if f in ("n_real", "n_imputed", "predictor",
                                   "coeffs", "loc", "scale", "explained_var",
                                   "mean", "var"))
        self.ys = ys

    def check(self, cfg):
        pass

    def plan_fleet(self, values, counts, budgets, cfg, *, window_id, **kw):
        return {f: np.asarray(self.ys[f][window_id]) for f in self.fields}


def _scan_run_with_plans(scenario, windows):
    """Run the scan and capture the raw per-window ys tables it collected."""
    exp = Experiment.from_scenario(scenario)
    stash = {}
    replay = exp.runtime._replay

    def spy(ys, host, T, wins, w0=0, live_tbl=None):
        stash["ys"] = ys
        return replay(ys, host, T, wins, w0=w0, live_tbl=live_tbl)

    exp.runtime._replay = spy
    return exp.run(windows), stash["ys"]


def test_event_loop_reproduces_scan_report_given_same_plans():
    """The bitwise half of the parity contract: feed the scan's plans to
    the event loop (zero-latency links, device sampling, static budgets)
    and the full RunReport must match exactly."""
    windows = Experiment.from_scenario(_fleet_scenario(4, "scan")
                                       ).make_windows()
    rep_s, ys = _scan_run_with_plans(_fleet_scenario(4, "scan"), windows)

    exp_e = Experiment.from_scenario(_fleet_scenario(4, "event"))
    exp_e.runtime.sampling = "device"    # the scan-parity RNG path
    exp_e.runtime.engine = _InjectedPlans(ys)
    rep_e = exp_e.run(windows)

    assert rep_e.wan_bytes == rep_s.wan_bytes
    assert rep_e.wan_cost == rep_s.wan_cost
    for q in ("AVG", "VAR", "MIN", "MAX"):
        np.testing.assert_array_equal(rep_e.nrmse_per_stream[q],
                                      rep_s.nrmse_per_stream[q])
    np.testing.assert_array_equal(rep_e.raw["budget_history"],
                                  rep_s.raw["budget_history"])


def test_fleet_scan_tracks_event_loop():
    """The tolerance half: end-to-end, with each side compiling its own
    planner, reports agree to f32-association noise (XLA fuses reductions
    differently inside the scan's while-loop body, which can move a
    marginal allocation by one sample)."""
    exp_e = Experiment.from_scenario(_fleet_scenario(4, "event"))
    exp_e.runtime.sampling = "device"
    windows = exp_e.make_windows()
    rep_e = exp_e.run(windows)
    rep_s = Experiment.from_scenario(_fleet_scenario(4, "scan")).run(windows)

    assert abs(rep_s.wan_bytes - rep_e.wan_bytes) <= 0.05 * rep_e.wan_bytes
    for q in ("AVG", "VAR", "MIN", "MAX"):
        np.testing.assert_allclose(rep_s.nrmse[q], rep_e.nrmse[q],
                                   rtol=0.08, atol=0.02)
    np.testing.assert_array_equal(rep_s.raw["budget_history"],
                                  rep_e.raw["budget_history"])


def test_single_edge_scan_matches_event_bitwise():
    """E=1 replicates plan_one's key chain and sampler: single-edge scan
    runs agree with the event loop through the batched engine bitwise."""
    def scenario(runtime):
        return ScenarioConfig(
            name="scan-test/E1",
            data=DataSpec(dataset="home", n_points=4 * WINDOW, window=WINDOW,
                          seed=2),
            planner=PlannerConfig(solver="closed_form", engine="batched",
                                  seed=5),
            queries=("AVG", "VAR", "MIN", "MAX"),
            runtime=runtime)

    exp_e = Experiment.from_scenario(scenario("event"))
    windows = exp_e.make_windows()
    rep_e = exp_e.run(windows)
    rep_s = Experiment.from_scenario(scenario("scan")).run(windows)

    assert rep_s.wan_bytes == rep_e.wan_bytes
    for q in ("AVG", "VAR", "MIN", "MAX"):
        np.testing.assert_array_equal(rep_s.nrmse_per_stream[q],
                                      rep_e.nrmse_per_stream[q])


@pytest.mark.parametrize("model,policy", [("cubic", "k_se"),
                                          ("mean", "exact_mse"),
                                          ("multi", "alpha")])
def test_scan_steps_matches_scan_run(model, policy):
    """runtime='scan_steps' drives the same compiled step one window at a
    time — including the device-resident rebalance controller state.  The
    discrete trajectory (budgets, WAN bytes) must match exactly; float
    tables agree to f32 association (XLA unrolls the trip-count-1 loop,
    which re-fuses the body's reductions)."""
    planner = PlannerConfig(solver="closed_form", model=model,
                            epsilon_policy=policy, seed=7)
    sc = _fleet_scenario(4, "scan", mode="rebalance", planner=planner)
    sc_steps = _fleet_scenario(4, "scan_steps", mode="rebalance",
                               planner=planner)
    windows = Experiment.from_scenario(sc).make_windows()
    rep_a = Experiment.from_scenario(sc).run(windows)
    rep_b = Experiment.from_scenario(sc_steps).run(windows)

    assert rep_a.wan_bytes == rep_b.wan_bytes
    np.testing.assert_array_equal(rep_a.raw["budget_history"],
                                  rep_b.raw["budget_history"])
    for f in ("budgets", "obs_err", "r2", "objective"):
        np.testing.assert_allclose(rep_a.raw["plan_raw"][f],
                                   rep_b.raw["plan_raw"][f],
                                   rtol=1e-4, atol=1e-6)
    for q in ("AVG", "VAR", "MIN", "MAX"):
        np.testing.assert_allclose(rep_a.nrmse_per_stream[q],
                                   rep_b.nrmse_per_stream[q],
                                   rtol=1e-3, atol=1e-5)


# ==========================================================================
# sampler and rank identities behind the throughput numbers
# ==========================================================================

def test_sample_fleet_e1_matches_host_draw_samples():
    """The E=1 device sampler walks draw_samples' exact split chain."""
    rng = np.random.default_rng(0)
    seed, wid, n = 7, 3, 40
    values = rng.normal(size=(1, K, n)).astype(np.float32)
    n_real = np.array([[11, 0, 40]], np.int32)

    host = draw_samples(jax.random.PRNGKey(seed ^ wid), values[0],
                        np.full(K, n), n_real[0])
    dev = np.asarray(sample_fleet(seed, jnp.int32(wid),
                                  jnp.asarray(values), jnp.asarray(n_real)))
    for i in range(K):
        np.testing.assert_array_equal(dev[0, i, :n_real[0, i]], host[i])
        assert not dev[0, i, n_real[0, i]:].any()


@pytest.mark.parametrize("E,k,n", [(5, K, 17), (4, 3, 288), (4, 5, 288),
                                   (3, 2, 300)])
def test_fleet_sampler_is_deterministic_srs(E, k, n):
    """E>1 keyed-sort path: SRS without replacement per (site, stream),
    deterministic in (seed, wid), zero past n_real; at the cells' N=288
    and past 256."""
    rng = np.random.default_rng(1)
    values = (rng.permutation(E * k * n).reshape(E, k, n) + 1).astype(
        np.float32)
    n_real = rng.integers(0, n + 1, size=(E, k)).astype(np.int32)
    n_real[0, 0] = n

    out = draw_fleet_samples(9, 2, values, n_real)
    np.testing.assert_array_equal(out, draw_fleet_samples(9, 2, values,
                                                          n_real))
    assert not np.array_equal(out, draw_fleet_samples(9, 3, values, n_real))
    for s in range(E):
        for i in range(k):
            prefix = out[s, i, :n_real[s, i]]
            assert len(np.unique(prefix)) == n_real[s, i]   # no replacement
            assert np.isin(prefix, values[s, i]).all()      # from the row
            assert not out[s, i, n_real[s, i]:].any()


def _chi2_p(observed, expected, scale=1.0):
    """Survival of the pooled Pearson statistic (divided by ``scale``) on
    ``observed.size - rows`` degrees of freedom; one row per leading
    index."""
    from scipy.stats import chi2
    obs = np.asarray(observed, np.float64).reshape(-1, observed.shape[-1])
    exp = np.asarray(expected, np.float64).reshape(-1, 1)
    stat = (((obs - exp) ** 2 / exp).sum(-1) / np.ravel(scale)).sum()
    return chi2.sf(stat, obs.size - obs.shape[0])


@pytest.mark.parametrize("what", ["inclusion", "first"])
def test_fleet_sampler_is_uniform(what):
    """Over 2,000 window ids at E=4, k=2, N=12, every position is sampled
    equally often and comes first equally often (chi-square, p > 1e-3)."""
    E, k, n, T = 4, 2, 12, 2000
    values = np.broadcast_to(np.arange(1, n + 1, dtype=np.float32),
                             (E, k, n))
    n_real = np.array([[1, 2], [3, 5], [6, 8], [9, 11]], np.int32)
    draw = jax.jit(jax.vmap(lambda w: sample_fleet(
        11, w, jnp.asarray(values), jnp.asarray(n_real))))
    out = np.asarray(draw(jnp.arange(T, dtype=jnp.int32)))    # (T, E, k, n)
    pos = out.astype(np.int64) - 1                           # -1 = unsampled
    if what == "inclusion":
        counts = np.stack([(pos == j).sum(0).sum(-1) for j in range(n)], -1)
        np.testing.assert_array_equal(counts.sum(-1), T * n_real)
        # inclusion counts of an n_real-of-n SRS: the Pearson statistic is
        # (n - n_real)/(n - 1) times a chi-square on n - 1 freedoms
        p = _chi2_p(counts, T * n_real / n, (n - n_real) / (n - 1))
    else:
        first = pos[..., 0]
        counts = np.stack([(first == j).sum(0) for j in range(n)], -1)
        p = _chi2_p(counts, np.full((E, k), T / n))
    assert p > 1e-3, p


def test_ordinal_ranks_matches_stable_double_argsort():
    rng = np.random.default_rng(2)
    for shape in [(7, 33), (2, 3, 17)]:
        x = rng.integers(0, 5, size=shape).astype(np.float32)  # heavy ties
        ref = jnp.argsort(jnp.argsort(x, axis=-1), axis=-1)
        np.testing.assert_array_equal(np.asarray(ordinal_ranks(jnp.asarray(x))),
                                      np.asarray(ref))


def test_rank_transform_counting_path_matches_sort_path():
    rng = np.random.default_rng(3)
    n = 31
    assert n <= COUNTING_RANK_MAX_N        # the counting path is live
    values = rng.integers(0, 6, size=(K, n)).astype(np.float32)
    counts = np.array([31, 12, 0], np.int32)

    got = np.asarray(rank_transform(jnp.asarray(values), jnp.asarray(counts)))

    # the sort-based fallback, replicated with numpy's stable argsort
    big = np.finfo(np.float32).max
    m = np.arange(n)[None, :] < counts[:, None]
    masked = np.where(m, values, big)
    order = np.argsort(masked, axis=-1, kind="stable")
    ranks = np.argsort(order, axis=-1, kind="stable").astype(np.float32)
    denom = np.maximum(counts.astype(np.float32) - 1.0, 1.0)[:, None]
    np.testing.assert_array_equal(got, np.where(m, ranks / denom, 0.0))


# ==========================================================================
# scenario validation: what runtime='scan' must refuse
# ==========================================================================

_CF = dict(solver="closed_form")


@pytest.mark.parametrize("match,build", [
    ("zero-latency", lambda: ScenarioConfig(
        data=DataSpec(dataset="fleet", n_points=96, window=24, seed=1,
                      options={"k": K}),
        planner=PlannerConfig(**_CF),
        topology=TopologySpec(n_regions=2, sites_per_region=2,
                              latency_scale=1.0),
        runtime="scan")),
    ("bandwidth", lambda: ScenarioConfig(
        data=DataSpec(dataset="fleet", n_points=96, window=24, seed=1,
                      options={"k": K}),
        planner=PlannerConfig(**_CF),
        topology=TopologySpec(n_regions=2, sites_per_region=2,
                              latency_scale=0.0,
                              bandwidth_bytes_per_ms=64.0),
        runtime="scan")),
    ("zero-latency", lambda: ScenarioConfig(
        planner=PlannerConfig(**_CF),
        transport=TransportSpec(latency_ms=5.0), runtime="scan")),
    ("serialization", lambda: ScenarioConfig(
        planner=PlannerConfig(**_CF),
        transport=TransportSpec(bandwidth_bytes_per_ms=32.0),
        runtime="scan")),
    ("late payloads", lambda: ScenarioConfig(
        planner=PlannerConfig(**_CF),
        transport=TransportSpec(staleness_deadline_ms=10.0),
        runtime="scan")),
    ("on-device mirror", lambda: ScenarioConfig(
        planner=PlannerConfig(**_CF), queries=("AVG", "MEDIAN"),
        runtime="scan")),
    ("baseline method", lambda: ScenarioConfig(
        planner=PlannerConfig(**_CF), method="srs", runtime="scan")),
    ("plan engine", lambda: ScenarioConfig(
        data=DataSpec(dataset="fleet", n_points=96, window=24, seed=1,
                      options={"k": K}),
        planner=PlannerConfig(engine="host", **_CF),
        topology=TopologySpec(n_regions=2, sites_per_region=2,
                              latency_scale=0.0),
        runtime="scan")),
    ("per-query", lambda: ScenarioConfig(
        data=DataSpec(dataset="fleet", n_points=96, window=24, seed=1,
                      options={"k": K}),
        planner=PlannerConfig(**_CF),
        topology=TopologySpec(n_regions=2, sites_per_region=2,
                              latency_scale=0.0),
        controller=ControllerSpec(query_split=0.3),
        runtime="scan")),
])
def test_scan_scenario_rejections(match, build):
    with pytest.raises(ValueError, match=match):
        build()


# ==========================================================================
# satellite: bandwidth serialization delay on the event transport
# ==========================================================================

def _payload(n_samples=4):
    return EdgePayload(window_id=0,
                       n_real=np.array([n_samples], np.int32),
                       n_imputed=np.array([0], np.int32),
                       real_values=[np.zeros(n_samples, np.float32)],
                       model=None, mean_imputation=True,
                       predictor=np.array([0]), stats_digest={})


def test_bandwidth_serialization_delay():
    from repro.streaming.events import AsyncTransport
    p = _payload()                       # 4*4 data + 10 header = 26 bytes
    assert p.wan_bytes() == 26

    t = AsyncTransport(latency_ms=5.0, bandwidth_bytes_per_ms=2.0)
    t.send(p, now_ms=0.0)                # delay = 5 + 26/2 = 18 ms
    assert t.drain(17.9) == []
    assert len(t.drain(18.0)) == 1

    # None keeps transmission instantaneous: bit-for-bit the old schedule
    t0 = AsyncTransport(latency_ms=5.0)
    t0.send(p, now_ms=0.0)
    ev = t0.drain(5.0)
    assert len(ev) == 1 and ev[0].at_ms == 5.0


def test_topology_bandwidth_reaches_links():
    topo = TopologySpec(n_regions=2, sites_per_region=2, seed=0,
                        bandwidth_bytes_per_ms=64.0).build(K)
    assert all(s.link.bandwidth_bytes_per_ms == 64.0 for s in topo.sites)
    none = TopologySpec(n_regions=2, sites_per_region=2, seed=0).build(K)
    assert all(s.link.bandwidth_bytes_per_ms is None for s in none.sites)


# ==========================================================================
# satellite: per-query controller split
# ==========================================================================

def test_query_split_conserves_total_and_reduces_to_single_tranche():
    from repro.fleet.controller import BudgetController
    E, total = 4, 96.0
    rng = np.random.default_rng(4)
    obs = rng.uniform(0.1, 1.0, size=E)
    r2 = rng.uniform(0.0, 1.0, size=(E, K))

    plain = BudgetController(total_budget=total, n_sites=E, mode="rebalance",
                             demand_signal="obs_err")
    plain.update(obs, r2)
    split = BudgetController(total_budget=total, n_sites=E, mode="rebalance",
                             demand_signal="obs_err", query_split=0.4,
                             tail_demand_signal="obs_err")
    split.update(obs, r2, obs_err_tail=obs)   # tail demand == primary demand
    b_plain, b_split = plain.budgets(), split.budgets()
    # each tranche water-fills a scaled copy of the same box: identical sum
    # and (same demand both tranches) identical allocation
    np.testing.assert_allclose(b_split, b_plain, rtol=1e-9, atol=1e-9)
    np.testing.assert_allclose(b_split.sum(), total, rtol=1e-9)

    hot_tail = obs.copy()
    hot_tail[0] *= 8.0                   # site 0's tail queries hurt more
    split2 = BudgetController(total_budget=total, n_sites=E,
                              mode="rebalance", demand_signal="obs_err",
                              query_split=0.4, tail_demand_signal="obs_err")
    split2.update(obs, r2, obs_err_tail=hot_tail)
    b2 = split2.budgets()
    np.testing.assert_allclose(b2.sum(), total, rtol=1e-9)
    assert b2[0] > b_split[0]            # the tail tranche shifted toward it


def test_query_split_event_run_end_to_end():
    sc = _fleet_scenario(4, "event", mode="rebalance",
                         controller=ControllerSpec(mode="rebalance",
                                                   query_split=0.3))
    rep = Experiment.from_scenario(sc).run()
    assert rep.wan_bytes > 0
    assert np.isfinite(rep.nrmse["AVG"])


# ==========================================================================
# sharded engine inside the scan, pinned under 8 forced host devices
# ==========================================================================

def _assert_sharded_scan_matches_batched(E=8, n_windows=4):
    """Static budgets -> identical plan inputs every window; the sharded
    pass is the batched pass under shard_map.  Sharding (like the scan's
    while-loop body) re-fuses the f32 reductions, so the comparison is
    the tolerance contract: identical budget trajectory, WAN bytes within
    an allocation-jitter margin, fleet error aggregates close."""
    planner_b = PlannerConfig(solver="closed_form", seed=3)
    planner_s = PlannerConfig(solver="closed_form", seed=3, engine="sharded")
    sc_b = _fleet_scenario(E, "scan", n_windows=n_windows, planner=planner_b)
    sc_s = _fleet_scenario(E, "scan", n_windows=n_windows, planner=planner_s)
    windows = Experiment.from_scenario(sc_b).make_windows()
    rep_b = Experiment.from_scenario(sc_b).run(windows)
    rep_s = Experiment.from_scenario(sc_s).run(windows)
    assert abs(rep_s.wan_bytes - rep_b.wan_bytes) <= 0.05 * rep_b.wan_bytes
    np.testing.assert_array_equal(rep_s.raw["budget_history"],
                                  rep_b.raw["budget_history"])
    for q in ("AVG", "VAR", "MIN", "MAX"):
        np.testing.assert_allclose(rep_s.nrmse[q], rep_b.nrmse[q],
                                   rtol=0.08, atol=0.02)


@pytest.mark.slow
def test_sharded_scan_parity_under_forced_devices():
    """Run the sharded-vs-batched scan comparison in a subprocess with 8
    forced host devices so shard_map actually spreads the site axis."""
    prog = textwrap.dedent("""
        import jax
        assert len(jax.devices()) == 8, jax.devices()
        import test_scan_runtime as t
        t._assert_sharded_scan_matches_batched()
        print("OK", len(jax.devices()))
    """)
    out = subprocess.run([sys.executable, "-c", prog],
                         env=subprocess_env(8),
                         cwd=Path(__file__).parent,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    assert "OK 8" in out.stdout


# ==========================================================================
# ShardedScanRuntime: the whole window step under shard_map over sites
# ==========================================================================

import dataclasses

from hypothesis import given, settings
from hypothesis import strategies as st

from repro.adaptive import AdaptiveSpec
from repro.chaos import ChaosSpec
from repro.runtime.sharded import ShardedScanRuntime

_SHARDED_E = 6


def _sharded_scenario(runtime, mode="rebalance", chaos=None, adaptive=None,
                      n_windows=6):
    kw = {}
    if chaos is not None:
        kw["chaos"] = chaos
    if adaptive is not None:
        kw["adaptive"] = adaptive
    return ScenarioConfig(
        name=f"sharded-test/{runtime}/{mode}",
        data=DataSpec(dataset="fleet", n_points=n_windows * WINDOW,
                      window=WINDOW, seed=1, options={"k": K}),
        planner=PlannerConfig(solver="closed_form", seed=3),
        topology=TopologySpec(n_regions=2, sites_per_region=_SHARDED_E // 2,
                              seed=0, latency_scale=0.0),
        controller=ControllerSpec(mode=mode),
        queries=("AVG", "VAR", "MIN", "MAX"), budget_fraction=0.25,
        runtime=runtime, **kw)


def _assert_sharded_report_matches(rb, rs, *, bitwise_budgets):
    """The ISSUE-10 parity contract: integer counters, WAN bytes and byte
    histories bitwise; budgets bitwise under static mode (host-f64
    constants) and f32-class under rebalance (psum reassociation); every
    carry float at f32 association noise with NaN masks aligned."""
    for f in ("wan_bytes", "full_bytes", "duplicates", "gaps"):
        assert rb[f] == rs[f], (f, rb[f], rs[f])
    np.testing.assert_array_equal(np.asarray(rb["bytes_history"]),
                                  np.asarray(rs["bytes_history"]))
    if bitwise_budgets:
        np.testing.assert_array_equal(np.asarray(rb["budget_history"]),
                                      np.asarray(rs["budget_history"]))
    else:
        np.testing.assert_allclose(np.asarray(rs["budget_history"]),
                                   np.asarray(rb["budget_history"]),
                                   rtol=2e-5, atol=1e-4)
    sb, ss = rb["final_state"], rs["final_state"]
    assert jax.tree.structure(sb) == jax.tree.structure(ss)
    flat_b = jax.tree_util.tree_flatten_with_path(sb)[0]
    flat_s = jax.tree_util.tree_leaves(ss)
    for (path, xb), xs in zip(flat_b, flat_s):
        a, b = np.asarray(xb), np.asarray(xs)
        label = jax.tree_util.keystr(path)
        if a.dtype.kind in "iub":
            np.testing.assert_array_equal(a, b, err_msg=label)
        else:
            np.testing.assert_allclose(b, a, rtol=2e-4, atol=2e-4,
                                       equal_nan=True, err_msg=label)


def _run_sharded_pair(mode="rebalance", chaos=None, adaptive=None,
                      n_windows=6):
    sc_b = _sharded_scenario("scan", mode, chaos, adaptive, n_windows)
    sc_s = _sharded_scenario("scan_sharded", mode, chaos, adaptive,
                             n_windows)
    eb = Experiment.from_scenario(sc_b)
    windows = eb.make_windows()
    rb = eb.runtime.run(windows)
    rs = Experiment.from_scenario(sc_s).runtime.run(windows)
    return rb, rs


def _assert_sharded_runtime_static_parity():
    rb, rs = _run_sharded_pair(mode="static")
    _assert_sharded_report_matches(rb, rs, bitwise_budgets=True)


def _assert_sharded_runtime_rebalance_parity():
    rb, rs = _run_sharded_pair(mode="rebalance")
    _assert_sharded_report_matches(rb, rs, bitwise_budgets=False)


def _assert_sharded_runtime_chaos_parity():
    spec = ChaosSpec(flaps=((1, 1, "down"), (3, 1, "up")),
                     outages=((2, 1, 0),))
    rb, rs = _run_sharded_pair(chaos=spec)
    _assert_sharded_report_matches(rb, rs, bitwise_budgets=False)
    np.testing.assert_array_equal(np.asarray(rb["liveness"]),
                                  np.asarray(rs["liveness"]))


def _assert_sharded_runtime_adaptive_parity():
    spec = AdaptiveSpec(detector="page_hinkley", ph_delta=0.01,
                        ph_lambda=0.05)
    rb, rs = _run_sharded_pair(adaptive=spec)
    _assert_sharded_report_matches(rb, rs, bitwise_budgets=False)
    # the pmax'd gate must fire on exactly the same windows
    assert rb["planner_invocations"] == rs["planner_invocations"]
    assert rb["plans_reused"] == rs["plans_reused"]


def _assert_sharded_ckpt_interchange(cut=3, n_windows=6):
    """Sharded and batched carries are interchangeable in both directions:
    a run killed after `cut` windows resumes on the other runtime and
    replays the remaining byte trajectory bitwise."""
    sc_b = _sharded_scenario("scan", "rebalance", n_windows=n_windows)
    sc_s = _sharded_scenario("scan_sharded", "rebalance",
                             n_windows=n_windows)
    exp = Experiment.from_scenario(sc_b)
    windows = exp.make_windows()
    full = exp.runtime.run(windows)
    for head_sc, tail_sc in ((sc_s, sc_b), (sc_b, sc_s)):
        head = Experiment.from_scenario(head_sc).runtime.run(
            windows, n_windows=cut)
        tail = Experiment.from_scenario(tail_sc).runtime.run(
            windows, n_windows=n_windows - cut, state=head["final_state"])
        assert head["wan_bytes"] + tail["wan_bytes"] == full["wan_bytes"]
        np.testing.assert_array_equal(
            np.asarray(tail["bytes_history"]),
            np.asarray(full["bytes_history"])[cut:])
        assert int(np.asarray(tail["final_state"].window_id)) == n_windows


def _assert_sharded_runtime_all_parity():
    _assert_sharded_runtime_static_parity()
    _assert_sharded_runtime_rebalance_parity()
    _assert_sharded_runtime_chaos_parity()
    _assert_sharded_runtime_adaptive_parity()
    _assert_sharded_ckpt_interchange()


def test_sharded_runtime_static_parity():
    _assert_sharded_runtime_static_parity()


def test_sharded_runtime_rebalance_parity():
    _assert_sharded_runtime_rebalance_parity()


def test_sharded_runtime_chaos_parity():
    _assert_sharded_runtime_chaos_parity()


def test_sharded_runtime_adaptive_parity():
    _assert_sharded_runtime_adaptive_parity()


def test_sharded_ckpt_interchange():
    _assert_sharded_ckpt_interchange()


@pytest.mark.slow
def test_sharded_runtime_parity_under_forced_devices():
    """The tentpole pin: under 8 forced host devices the sharded runtime
    reproduces the batched scan's RunReport on the static, rebalance,
    chaos and adaptive scenarios, checkpoints interchange with the
    batched runtime in both directions, and padding E=6 to 16 rows
    instead of 8 changes nothing."""
    prog = textwrap.dedent("""
        import jax
        assert len(jax.devices()) == 8, jax.devices()
        import test_scan_runtime as t
        t._assert_sharded_runtime_all_parity()
        t._assert_sharded_padding_invariant(1)
        print("OK", len(jax.devices()))
    """)
    out = subprocess.run([sys.executable, "-c", prog],
                         env=subprocess_env(8),
                         cwd=Path(__file__).parent,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    assert "OK 8" in out.stdout


def _assert_sharded_padding_invariant(extra):
    """Scan results must not depend on how far E is padded: padded rows
    are permanently dead sites, so any pad >= E that the mesh accepts
    (extra whole rows per device) yields the same counters bitwise and
    the same floats to f32 noise."""
    sc_s = _sharded_scenario("scan_sharded", "rebalance")
    exp = Experiment.from_scenario(sc_s)
    windows = exp.make_windows()
    base = exp.runtime.run(windows)
    rt0 = Experiment.from_scenario(sc_s).runtime
    d = int(rt0._mesh.shape["sites"])
    rt = dataclasses.replace(rt0, pad_sites=rt0._run_sites + extra * d)
    padded = rt.run(windows)
    _assert_sharded_report_matches(base, padded, bitwise_budgets=False)


@pytest.mark.parametrize("extra", [1, 3])
def test_sharded_padding_invariance(extra):
    _assert_sharded_padding_invariant(extra)


@given(st.integers(min_value=0, max_value=6))
@settings(max_examples=6, deadline=None)
def test_sharded_padding_invariance_property(extra):
    _assert_sharded_padding_invariant(extra)


def test_sharded_runtime_construction_rejections():
    # a single edge has no site axis to shard: refused at scenario
    # construction, before any compilation
    with pytest.raises(ValueError, match="nothing to shard"):
        ScenarioConfig(
            data=DataSpec(dataset="mvn", n_points=96, window=24, seed=1),
            planner=PlannerConfig(solver="closed_form"),
            runtime="scan_sharded")
    # pad_sites below E or off the device multiple: refused up front
    rt = Experiment.from_scenario(
        _sharded_scenario("scan_sharded")).runtime
    with pytest.raises(ValueError, match="pad_sites"):
        dataclasses.replace(rt, pad_sites=_SHARDED_E - 2)


@pytest.mark.parametrize("n", [1, 5, 288, 1024])
def test_ordered_sum_is_one_fixed_pairwise_tree(n):
    """The controller's sums: a pairwise tree over the zero-padded axis,
    bit for bit, and the same bits for a row alone or inside a batch (the
    property that keeps budgets equal across fleet shapes and meshes)."""
    from repro.runtime.controller import ordered_sum
    x = np.random.default_rng(n).normal(50.0, 4.0, (16, n)).astype(
        np.float32)
    tree = np.concatenate(
        [x, np.zeros((16, (1 << (n - 1).bit_length()) - n), np.float32)], 1)
    while tree.shape[1] > 1:
        tree = tree[:, 0::2] + tree[:, 1::2]
    batched = np.asarray(jax.jit(ordered_sum)(jnp.asarray(x)))
    np.testing.assert_array_equal(batched, tree[:, 0])
    for i in (0, 7, 15):
        np.testing.assert_array_equal(
            np.asarray(ordered_sum(jnp.asarray(x[i:i + 1]))), batched[i:i + 1])
    np.testing.assert_allclose(batched, x.astype(np.float64).sum(1),
                               rtol=1e-6)
