"""Fleet chaos engine (repro.chaos): faults, liveness, recovery metrics.

  * ChaosSpec validation / JSON round trip / topology checks, and the
    full ``FAULTS`` registry surface ("flap", "join", "outage",
    "random" — ``tests/test_repo_hygiene.py`` looks for these
    literals).
  * ``liveness_table`` semantics: flap toggles, join masks the prefix,
    outage darkens a region, down always wins; slice-stability
    (``liveness_table(spec, T)[a:b] == liveness_table(spec, b - a,
    first_window=a)``) — the property that makes chaos runs resume-safe,
    including the random-flap process (hypothesis over schedules).
  * Parity pins: an *empty* ChaosSpec is bit-for-bit ``chaos=None`` in
    BOTH runtimes (``is_trivial`` routes to the legacy code path), and a
    chaos=None report keeps its legacy raw/golden key set.
  * Dead-site invariants: a site that is down ships zero WAN bytes and
    ingests zero windows (event: the transport/cloud counters; scan: the
    ``bytes_history`` table), while its queries are gap-served from the
    last live reconstruction.  Hypothesis drives random flap schedules
    through the scan runtime — dead cells are all-zero-byte, live cells
    respect the payload byte model bound.
  * BudgetController under membership: all-dead windows return zero
    budgets (no NaN poisoning), redistribution conserves the fleet total
    over the survivors, dead sites' demand/r2 EWMAs stay frozen, and
    ``water_fill`` survives zero/NaN demand.
  * ``recovery_windows`` unit semantics on a synthetic history, and the
    committed acceptance golden's bounds: outage NRMSE <= 2x steady via
    gap-serving, budget reconvergence within the pinned window count.
  * Scan chaos runs kill-and-resume bitwise (``ChaosCarry`` lives in the
    checkpointed state; the liveness table is slice-stable).
"""
import json
from pathlib import Path

import jax
import numpy as np
import pytest

from conftest import run_matrix  # noqa: F401  (imports conftest stub first)
from hypothesis import given, settings
from hypothesis import strategies as st
from repro.api import (ControllerSpec, DataSpec, Experiment, ScenarioConfig,
                       TopologySpec)
from repro.api.registry import FAULTS
from repro.chaos import (ChaosSpec, liveness_table, masked_nrmse,
                         recovery_windows)
from repro.core.types import PlannerConfig
from repro.fleet.controller import BudgetController, water_fill
from repro.sweep.report import serialize_report

GOLDEN_DIR = Path(__file__).parent / "goldens" / "reports"


def _scenario(chaos=None, runtime="event", seed=21, windows=8,
              latency_scale=0.0):
    return ScenarioConfig(
        data=DataSpec(dataset="fleet", n_points=windows * 64, window=64,
                      seed=seed, options={"k": 4}),
        planner=PlannerConfig(solver="closed_form", seed=seed),
        topology=TopologySpec(n_regions=2, sites_per_region=3, seed=seed,
                              latency_scale=latency_scale),
        controller=ControllerSpec(),
        queries=("AVG", "VAR"), runtime=runtime, chaos=chaos)


REGION_OF = np.array([0, 0, 0, 1, 1, 1])


# ------------------------------------------------------------- spec surface

def test_faults_registry_surface():
    assert set(FAULTS.names()) >= {"flap", "join", "outage", "random"}
    with pytest.raises(Exception, match="flap"):
        FAULTS.get("flapp")           # typo fails with alternatives listed


def test_chaos_spec_validation():
    with pytest.raises(ValueError, match="up.*or.*down"):
        ChaosSpec(flaps=((0, 1, "offline"),))
    with pytest.raises(ValueError, match=">= 0"):
        ChaosSpec(flaps=((-1, 0, "down"),))
    with pytest.raises(ValueError, match="n_windows"):
        ChaosSpec(outages=((3, 0, 0),))
    with pytest.raises(ValueError, match=">= 0"):
        ChaosSpec(joins=((2, -1),))
    with pytest.raises(ValueError, match="flap_prob"):
        ChaosSpec(flap_prob=1.0)
    with pytest.raises(ValueError, match="flap_len"):
        ChaosSpec(flap_prob=0.1, flap_len=0)


def test_chaos_spec_topology_validation():
    ChaosSpec(flaps=((0, 5, "down"),)).validate_topology(6, 2)
    with pytest.raises(ValueError, match="site 6"):
        ChaosSpec(flaps=((0, 6, "down"),)).validate_topology(6, 2)
    with pytest.raises(ValueError, match="region 2"):
        ChaosSpec(outages=((0, 2, 2),)).validate_topology(6, 2)
    with pytest.raises(ValueError, match="site 9"):
        ChaosSpec(joins=((1, 9),)).validate_topology(6, 2)


def test_chaos_spec_round_trip():
    spec = ChaosSpec(flaps=((2, 1, "down"), (4, 1, "up")),
                     outages=((3, 2, 0),), joins=((1, 5),),
                     flap_prob=0.05, flap_len=2, seed=7)
    back = ChaosSpec.from_dict(json.loads(json.dumps(spec.to_dict())))
    assert back == spec
    with pytest.raises(ValueError, match="unknown"):
        ChaosSpec.from_dict({"outage": [[0, 1, 0]]})


def test_scenario_round_trip_and_rejections():
    sc = _scenario(chaos=ChaosSpec(outages=((3, 2, 1),)))
    back = ScenarioConfig.from_dict(json.loads(json.dumps(sc.to_dict())))
    assert back.chaos == sc.chaos
    with pytest.raises(ValueError, match="fleet"):
        ScenarioConfig(
            data=DataSpec(dataset="mvn", n_points=512, window=64, seed=1,
                          options={"k": 4}),
            chaos=ChaosSpec(flaps=((0, 0, "down"),)))
    with pytest.raises(ValueError, match="region 5"):
        _scenario(chaos=ChaosSpec(outages=((0, 1, 5),)))


def test_empty_spec_is_trivial():
    assert ChaosSpec().is_trivial
    assert not ChaosSpec(flaps=((0, 0, "down"),)).is_trivial
    assert not ChaosSpec(flap_prob=0.1).is_trivial


# ---------------------------------------------------------- liveness table

def test_liveness_table_semantics():
    spec = ChaosSpec(flaps=((2, 1, "down"), (5, 1, "up")),
                     joins=((3, 4),), outages=((4, 2, 0),))
    live = liveness_table(spec, 8, 6, REGION_OF)
    # flap: site 1 down on [2, 5), back up from 5 — except the outage
    assert live[:2, 1].all() and not live[2:5, 1].any()
    # join: site 4 dark before window 3
    assert not live[:3, 4].any() and live[3:, 4].all()
    # outage darkens all of region 0 on [4, 6) — down wins over flap-up
    assert not live[4:6, :3].any() and live[6:, :3].all()
    # untouched site stays up throughout
    assert live[:, 5].all()


@settings(max_examples=25, deadline=None)
@given(st.integers(0, 2**31 - 1), st.floats(0.0, 0.5), st.integers(1, 3),
       st.integers(0, 10), st.integers(1, 12))
def test_liveness_table_slice_stable(seed, prob, flap_len, a, span):
    """Any slice of the table reproduces bitwise from ``first_window`` —
    the property that makes chaos runs checkpoint/resume-safe."""
    spec = ChaosSpec(flaps=((2, 1, "down"),), joins=((4, 3),),
                     outages=((6, 3, 1),), flap_prob=prob,
                     flap_len=flap_len, seed=seed)
    full = liveness_table(spec, 24, 6, REGION_OF)
    part = liveness_table(spec, span, 6, REGION_OF, first_window=a)
    np.testing.assert_array_equal(full[a:a + span], part)


# ------------------------------------------------------- recovery semantics

def test_recovery_windows_unit():
    # membership change at t=2; budgets reach the steady profile at t=4
    live = np.ones((8, 2), bool)
    live[2:, 1] = False
    hist = np.array([[10.0, 10.0]] * 2 + [[15.0, 5.0]] * 2
                    + [[20.0, 0.0]] * 4)
    rec = recovery_windows(live, hist, equal_share=10.0)
    assert rec == 3.0                  # windows 2,3 transient; 4 settles
    # never changes -> NaN
    assert np.isnan(recovery_windows(np.ones((4, 2), bool), hist[:4], 10.0))
    # never settles -> full epoch length
    drift = np.array([[10.0, 10.0]] * 2
                     + [[100.0 + 10 * t, 0.0] for t in range(6)])
    assert recovery_windows(live, drift, equal_share=10.0) == 6.0


def test_recovery_windows_region_grouping():
    """Per-site noise that cancels within a region must not mask
    convergence: the grouped metric settles, the ungrouped one never."""
    live = np.ones((6, 4), bool)
    live[2:, 3] = False
    region_of = np.array([0, 0, 1, 1])
    hist = np.full((6, 4), 10.0)
    hist[2:, 3] = 0.0
    hist[2:, 2] = 20.0                # region 1 total is steady at 20
    hist[2:, 0] = [15, 4, 16, 7]      # noise that cancels within region 0
    hist[2:, 1] = [5, 16, 4, 13]
    assert recovery_windows(live, hist, 10.0, region_of=region_of) == 1.0
    assert recovery_windows(live, hist, 10.0) == 4.0


def test_masked_nrmse_selects_cells():
    tru = np.ones((4, 2, 3))
    est = np.ones((4, 2, 3))
    est[2:] = 2.0                     # error only in the last two windows
    early = np.zeros((4, 2), bool)
    early[:2] = True
    late = ~early
    assert masked_nrmse(est, tru, early) == 0.0
    assert masked_nrmse(est, tru, late) == pytest.approx(1.0)
    assert np.isnan(masked_nrmse(est, tru, np.zeros((4, 2), bool)))


# ------------------------------------------------------------- parity pins

@pytest.mark.parametrize("runtime", ["event", "scan"])
def test_empty_chaos_spec_is_bitwise_none(runtime):
    legacy = Experiment.from_scenario(_scenario(runtime=runtime)).run()
    trivial = Experiment.from_scenario(
        _scenario(chaos=ChaosSpec(), runtime=runtime)).run()
    assert trivial.nrmse == legacy.nrmse
    assert trivial.wan_bytes == legacy.wan_bytes
    for q in legacy.nrmse_per_stream:
        np.testing.assert_array_equal(trivial.nrmse_per_stream[q],
                                      legacy.nrmse_per_stream[q])
    np.testing.assert_array_equal(trivial.raw["budget_history"],
                                  legacy.raw["budget_history"])
    assert set(trivial.raw) == set(legacy.raw)


def test_default_off_is_legacy_shape():
    rep = Experiment.from_scenario(_scenario()).run()
    assert rep.down_site_windows is None
    assert rep.recovery_windows is None
    for key in ("liveness", "down_site_windows", "gap_served_cells",
                "availability_by_region", "outage_nrmse", "steady_nrmse",
                "recovery_windows"):
        assert key not in rep.raw
        assert key not in rep.to_dict()


def test_chaos_refuses_adaptive():
    from repro.adaptive import AdaptiveSpec
    with pytest.raises(ValueError, match="adaptive"):
        ScenarioConfig(
            data=DataSpec(dataset="fleet", n_points=512, window=64, seed=1,
                          options={"k": 4}),
            topology=TopologySpec(n_regions=2, sites_per_region=3, seed=1),
            planner=PlannerConfig(solver="closed_form"),
            adaptive=AdaptiveSpec(detector="always"),
            chaos=ChaosSpec(flaps=((0, 0, "down"),)))


# -------------------------------------------------------- dead-site physics

def test_event_dead_site_ships_nothing_and_is_gap_served():
    # site 1 dark from window 3 onward; the fleet keeps running
    exp = Experiment.from_scenario(
        _scenario(chaos=ChaosSpec(flaps=((3, 1, "down"),))))
    rep = exp.run()
    rt = exp.runtime
    # a permanently-darkened site stops transmitting: its byte counter
    # freezes at the pre-outage level while live peers keep growing
    assert rt.transports[1].bytes_sent < rt.transports[0].bytes_sent
    assert rt.clouds[1].windows_seen == 3      # windows 0..2 only
    assert rep.down_site_windows == 5
    # its queries after window 3 are answered from window 2 (gap-serving)
    assert rt.clouds[1].stale_serves == 5
    assert rep.raw["gap_served_cells"] == 5
    assert rep.raw["liveness"].shape == (8, 6)


def test_event_join_site_silent_before_join():
    exp = Experiment.from_scenario(
        _scenario(chaos=ChaosSpec(joins=((5, 2),))))
    rep = exp.run()
    rt = exp.runtime
    assert rt.clouds[2].windows_seen == 3      # windows 5..7
    assert rep.down_site_windows == 5
    # nothing to gap-serve before the first live window
    assert rep.raw["gap_served_cells"] == 0


def test_scan_dead_cells_ship_zero_bytes():
    exp = Experiment.from_scenario(
        _scenario(chaos=ChaosSpec(outages=((3, 2, 1),)), runtime="scan"))
    res = exp.runtime.run(exp.make_windows())
    live = np.asarray(res["liveness"], bool)
    nbytes = np.asarray(res["bytes_history"])
    assert not live[3:5, 3:].any()
    assert (nbytes[~live] == 0).all()
    assert (nbytes[live] > 0).all()
    # budgets of dead sites are zero, never redistributed back to them
    budgets = np.asarray(res["budget_history"])
    assert (budgets[~live] == 0).all()


@settings(max_examples=10, deadline=None)
@given(st.lists(st.tuples(st.integers(0, 7), st.integers(0, 5),
                          st.sampled_from(["up", "down"])),
                min_size=1, max_size=6))
def test_scan_bytes_respect_budget_under_flaps(flaps):
    """Property: under ANY flap schedule, dead cells ship zero bytes and
    live cells respect the payload byte model (4 bytes/sample + header
    + per-stream model coefficients).  The liveness table is a runtime
    input, so hypothesis examples reuse one compiled scan."""
    spec = ChaosSpec(flaps=tuple(flaps))
    exp = Experiment.from_scenario(_scenario(chaos=spec, runtime="scan"))
    if exp.runtime._chaos_active is False:     # all-up schedule: legacy path
        return
    res = exp.runtime.run(exp.make_windows())
    live = np.asarray(res["liveness"], bool)
    np.testing.assert_array_equal(
        live, liveness_table(spec, 8, 6, REGION_OF))
    nbytes = np.asarray(res["bytes_history"])
    budgets = np.asarray(res["budget_history"])
    k = 4
    assert (nbytes[~live] == 0).all()
    bound = 4 * (budgets + k) + (8 + 2 * k) + 40 * k
    assert (nbytes[live] <= bound[live]).all()


# -------------------------------------------------- controller under chaos

def _controller(**kw):
    return BudgetController(total_budget=60.0, n_sites=6, **kw)


def test_controller_all_dead_returns_zeros():
    c = _controller()
    b = c.budgets(live=np.zeros(6, bool))
    np.testing.assert_array_equal(b, np.zeros(6))
    assert np.isfinite(b).all()


def test_controller_all_live_mask_is_bitwise_none():
    c, d = _controller(), _controller()
    c.update(np.full(6, 0.1), np.full(6, 0.5))
    d.update(np.full(6, 0.1), np.full(6, 0.5), live=np.ones(6, bool))
    np.testing.assert_array_equal(c.budgets(),
                                  d.budgets(live=np.ones(6, bool)))


def test_controller_masked_redistribution_conserves_total():
    c = _controller()
    c.update(np.array([0.5, 0.1, 0.3, 0.2, 0.4, 0.05]), np.full(6, 0.5))
    live = np.array([True, True, False, True, False, True])
    b = c.budgets(live=live)
    assert (b[~live] == 0).all()
    assert b.sum() == pytest.approx(60.0)
    # static mode never redistributes: survivors keep their static share
    s = _controller(mode="static")
    bs = s.budgets(live=live)
    assert (bs[~live] == 0).all()
    np.testing.assert_array_equal(bs[live], s.budgets()[live])


def test_controller_freezes_dead_site_ewmas():
    c = _controller()
    c.update(np.full(6, 0.2), np.full(6, 0.5))
    demand_before = c._demand.copy()
    live = np.array([True, True, True, False, False, False])
    # dead sites report NaN (no payloads) — their EWMAs must not move
    obs = np.where(live, 0.9, np.nan)
    c.update(obs, np.where(live, 0.8, np.nan), live=live)
    np.testing.assert_array_equal(c._demand[3:], demand_before[3:])
    assert (c._demand[:3] != demand_before[:3]).all()
    np.testing.assert_array_equal(c._r2[3:], np.full(3, 0.5))


def test_water_fill_zero_and_nan_demand():
    lo, hi = np.full(4, 2.0), np.full(4, 30.0)
    # zero demand -> uniform split, not NaN
    b = water_fill(np.zeros(4), 40.0, lo, hi)
    np.testing.assert_allclose(b, np.full(4, 10.0))
    # NaN demand entries are treated as no-demand, never poison the rest
    b = water_fill(np.array([1.0, np.nan, 1.0, np.nan]), 40.0, lo, hi)
    assert np.isfinite(b).all()
    assert b.sum() == pytest.approx(40.0)


# ------------------------------------------------------------ resume + CI

def test_scan_chaos_resumes_bitwise(tmp_path):
    """Kill-and-restore mid-outage: the ChaosCarry (liveness + gap-served
    memory) rides in the checkpoint and the liveness table is slice-
    stable, so the tail replays bit-for-bit."""
    from repro.ckpt import latest_step, restore, save
    scenario = _scenario(chaos=ChaosSpec(outages=((2, 4, 0),),
                                         flap_prob=0.05, seed=9),
                         runtime="scan")
    exp = Experiment.from_scenario(scenario)
    windows = exp.make_windows()
    T, cut = 8, 4                      # cut lands inside the outage
    full = exp.runtime.run(windows)

    rt1 = Experiment.from_scenario(scenario).runtime
    head = rt1.run(windows, n_windows=cut)
    save(head["final_state"], cut, tmp_path)

    rt2 = Experiment.from_scenario(scenario).runtime
    st_ = restore(tmp_path, latest_step(tmp_path),
                  jax.eval_shape(lambda: head["final_state"]))
    tail = rt2.run(windows, n_windows=T - cut, state=st_)

    assert head["wan_bytes"] + tail["wan_bytes"] == full["wan_bytes"]
    np.testing.assert_array_equal(tail["budget_history"],
                                  full["budget_history"][cut:])
    np.testing.assert_array_equal(tail["liveness"], full["liveness"][cut:])
    for a, b in zip(jax.tree.leaves(full["final_state"]),
                    jax.tree.leaves(tail["final_state"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_serializer_emits_chaos_keys_only_when_present():
    legacy = serialize_report(Experiment.from_scenario(_scenario()).run(),
                              name="t", tolerance="ulp")
    for key in ("down_site_windows", "gap_served_cells"):
        assert key not in legacy["counters"]
    assert "recovery_windows" not in legacy["floats"]
    assert "liveness" not in legacy["streams"]
    chaos = serialize_report(
        Experiment.from_scenario(
            _scenario(chaos=ChaosSpec(flaps=((3, 1, "down"),)))).run(),
        name="t", tolerance="ulp")
    assert chaos["counters"]["down_site_windows"] == 5
    assert chaos["counters"]["gap_served_cells"] == 5
    assert chaos["floats"]["recovery_windows"] is not None
    assert chaos["streams"]["liveness"]["shape"] == [8, 6]


def test_acceptance_golden_bounds():
    """The committed region-outage golden (E=64, one region dark for 20
    windows) holds the PR's acceptance claims: gap-serving keeps outage
    NRMSE within 2x steady state, budgets reconverge within the pinned
    recovery window, and every dark cell was still answered."""
    g = json.loads((GOLDEN_DIR / "fleet_scan_chaos_region.json").read_text())
    f, c = g["floats"], g["counters"]
    assert f["outage_nrmse/AVG"] <= 2.0 * f["steady_nrmse/AVG"]
    assert f["recovery_windows"] <= 2.0
    assert c["gap_served_cells"] == c["down_site_windows"] == 320
    assert f["availability/region1"] == pytest.approx(1.0 - 20 / 48)


def test_event_outage_and_join_end_to_end():
    """Region 1 dark for windows 3-4 and site 0 joining at window 2, through
    the event runtime under the rebalance controller: the down-cell count
    is exact, answers stay finite and the budgets reconverge."""
    E, W = 6, 64
    scenario = ScenarioConfig(
        data=DataSpec(dataset="fleet", n_points=8 * W, window=W, seed=5,
                      options={"k": 4}),
        budget_fraction=0.25, planner=PlannerConfig(solver="closed_form"),
        topology=TopologySpec(n_regions=2, sites_per_region=E // 2, seed=5,
                              latency_scale=0.0),
        controller=ControllerSpec(mode="rebalance"), queries=("AVG", "VAR"),
        chaos=ChaosSpec(outages=((3, 2, 1),), joins=((2, 0),)))
    rep = Experiment.from_scenario(scenario).run()
    # site 0 before its join (2 windows) + region 1's 3 sites for 2 windows
    assert rep.down_site_windows == 2 + 2 * 3
    assert np.isfinite(rep.nrmse["AVG"])
    assert np.isfinite(rep.recovery_windows)
    assert rep.raw["liveness"].shape == (8, E)
