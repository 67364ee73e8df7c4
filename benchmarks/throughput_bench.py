"""On-device streaming throughput: scan runtime vs event loop.

Measures end-to-end windows/sec of the ``repro.runtime`` scan engine at
fleet sizes E in {16, 64, 256} over 1000 windows, against the event-driven
``FleetRuntime`` on the identical scenario (zero-latency links, rebalance
controller, batched closed-form planning), plus the shard_map-over-sites
``scan_sharded`` runtime at E in {64, 256, 1024}.  Both paths run the same jitted
fleet planner; the delta is the runtime harness — the scan engine keeps the
whole loop (controller EWMAs, per-site budgets, sampling, query tables) on
device under one ``lax.scan`` with a donated carry, while the event loop
crosses the host boundary every window and walks sites in Python.

Results land in ``BENCH_throughput.json`` at the repo root (schema in
benchmarks/common.py: one row per (scenario, engine) with windows/sec,
streams/sec, WAN bytes and mean AVG-NRMSE) — the tracked perf trajectory.

Usage::

    PYTHONPATH=src python benchmarks/throughput_bench.py            # refresh
    PYTHONPATH=src python benchmarks/throughput_bench.py --smoke    # CI gate

``--smoke`` never rewrites the artifact: it validates the committed JSON
against the schema and runs a miniature E=4 scan to prove the path executes.
"""
from __future__ import annotations

import sys
import time
from pathlib import Path

import numpy as np

if __package__ in (None, ""):                       # `python benchmarks/...`
    sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from benchmarks.common import (REPO_ROOT, fmt, read_bench_json, timed,
                               write_bench_json)
from repro.api import (AdaptiveSpec, ChaosSpec, ControllerSpec, DataSpec,
                       Experiment, ScenarioConfig, TopologySpec)
from repro.core.types import PlannerConfig

BENCH_PATH = REPO_ROOT / "BENCH_throughput.json"

K = 4                    # streams per site
WINDOW = 128             # tuples per stream per window
POOL = 8                 # distinct generated windows; the scan cycles them
FLEET_SIZES = (16, 64, 256)
SCAN_WINDOWS = 1000
# the event loop is host-bound: a handful of windows gives a stable
# per-window cost without minutes of wall time at E=256
EVENT_WINDOWS = {16: 16, 64: 8, 256: 4}
# sharded scan runtime (repro.runtime.sharded): the whole window step under
# shard_map over the site mesh.  On the single-device bench box this rides
# the same executables as the scan rows (the mesh is 1-wide), so the rows
# track harness overhead; multi-device speedups are pinned functionally in
# tests/test_scan_runtime.py under 8 forced host devices.  E=1024 gets
# fewer windows to bound wall time at the largest fleet.
SHARDED_FLEET_SIZES = (64, 256, 1024)
SHARDED_WINDOWS = {64: 1000, 256: 500, 1024: 125}

# adaptive re-planning payoff (repro.adaptive): a drifting E=64 fleet where
# the per-region coupling to the shared signal is re-shuffled three times;
# the detector-gated run must cover the drift with few planner invocations.
# The window spans one full diurnal cycle of the fleet generator so that
# between drifts the per-window statistics are phase-stationary — the
# benchmark then measures staleness from *correlation* drift, not from a
# window length that aliases the daily cycle
ADAPTIVE_E = 64
ADAPTIVE_WINDOW = 288
ADAPTIVE_WINDOWS = 48
ADAPTIVE_SCHEDULE = [[0, [0.9, 0.7, 0.3, 0.1]],
                     [12, [0.1, 0.9, 0.7, 0.3]],
                     [24, [0.3, 0.1, 0.9, 0.7]],
                     [36, [0.7, 0.3, 0.1, 0.9]]]
# payoff bars pinned by run() and re-checked against the committed artifact
# by run_smoke(): planner runs on <=25% of windows, accuracy within 10%
# relative of plan-every-window
ADAPTIVE_MAX_INVOCATION_FRAC = 0.25
ADAPTIVE_MAX_REL_NRMSE = 0.10

# chaos recovery (repro.chaos): the acceptance scenario of docs/chaos.md —
# an E=64 fleet whose region 1 goes dark for 20 windows mid-run.  The row
# must show the rebalancing controller re-spreading the freed budget within
# CHAOS_MAX_RECOVERY_WINDOWS and gap-serving holding the outage NRMSE within
# CHAOS_MAX_OUTAGE_RATIO x steady state, with every dark cell still answered
CHAOS_E = 64
CHAOS_WINDOW = 288
CHAOS_WINDOWS = 48
CHAOS_OUTAGE = (10, 20, 1)       # (start, n_windows, region)
CHAOS_BUDGET_FRACTION = 0.08
CHAOS_MAX_RECOVERY_WINDOWS = 2.0
CHAOS_MAX_OUTAGE_RATIO = 2.0


def _scenario(E: int, runtime: str) -> ScenarioConfig:
    return ScenarioConfig(
        name=f"throughput/E{E}",
        data=DataSpec(dataset="fleet", n_points=POOL * WINDOW, window=WINDOW,
                      seed=0, options={"k": K}),
        planner=PlannerConfig(solver="closed_form", dependence="pearson",
                              seed=0),
        topology=TopologySpec(n_regions=4, sites_per_region=E // 4, seed=0,
                              latency_scale=0.0),
        controller=ControllerSpec(mode="rebalance"),
        queries=("AVG", "VAR"),
        runtime=runtime)


def _measure_scan(E: int, n_windows: int, runtime: str = "scan") -> dict:
    exp = Experiment.from_scenario(_scenario(E, runtime))
    exp.runtime.collect = "estimates"    # device-only tables; no host replay
    windows = exp.make_windows()
    exp.runtime.run(windows, n_windows=n_windows)        # compile + warm
    r = exp.runtime.run(windows, n_windows=n_windows)    # steady-state
    return {"scenario": f"throughput/E{E}", "engine": runtime,
            "n_sites": E, "n_windows": n_windows,
            "windows_per_sec": float(r["windows_per_sec"]),
            "streams_per_sec": float(r["windows_per_sec"]) * E * K,
            "wan_bytes": int(r["wan_bytes"]),
            "nrmse_avg": float(r["fleet_nrmse"]["AVG"])}


def _measure_event(E: int, n_windows: int) -> dict:
    sc = _scenario(E, "event")
    windows = Experiment.from_scenario(sc).make_windows()[:n_windows]
    Experiment.from_scenario(sc).run(windows[:2])        # warm the planner
    exp = Experiment.from_scenario(sc)                   # fresh state
    t0 = time.perf_counter()
    rep = exp.run(windows)
    wall = time.perf_counter() - t0
    wps = n_windows / max(wall, 1e-9)
    return {"scenario": f"throughput/E{E}", "engine": "event",
            "n_sites": E, "n_windows": n_windows,
            "windows_per_sec": wps, "streams_per_sec": wps * E * K,
            "wan_bytes": int(rep.wan_bytes),
            "nrmse_avg": float(rep.nrmse["AVG"])}


def _adaptive_scenario(spec: AdaptiveSpec) -> ScenarioConfig:
    return ScenarioConfig(
        name=f"adaptive/E{ADAPTIVE_E}",
        data=DataSpec(dataset="fleet",
                      n_points=ADAPTIVE_WINDOWS * ADAPTIVE_WINDOW,
                      window=ADAPTIVE_WINDOW, seed=7,
                      options={"k": K,
                               "strength_schedule": ADAPTIVE_SCHEDULE}),
        planner=PlannerConfig(solver="closed_form", dependence="pearson",
                              seed=7),
        topology=TopologySpec(n_regions=4,
                              sites_per_region=ADAPTIVE_E // 4, seed=7,
                              latency_scale=0.0),
        # static budgets: with per-window rebalancing every cached plan is
        # stale by construction, which would measure the controller, not
        # the drift detector (both rows share this, the comparison is fair)
        controller=ControllerSpec(),
        queries=("AVG", "VAR"),
        runtime="scan",
        adaptive=spec)


def _measure_adaptive(label: str, spec: AdaptiveSpec) -> dict:
    exp = Experiment.from_scenario(_adaptive_scenario(spec))
    exp.runtime.collect = "estimates"
    windows = exp.make_windows()
    exp.runtime.run(windows, n_windows=ADAPTIVE_WINDOWS)      # compile + warm
    r = exp.runtime.run(windows, n_windows=ADAPTIVE_WINDOWS)  # steady-state
    return {"scenario": f"adaptive/E{ADAPTIVE_E}/{label}", "engine": "scan",
            "n_sites": ADAPTIVE_E, "n_windows": ADAPTIVE_WINDOWS,
            "windows_per_sec": float(r["windows_per_sec"]),
            "streams_per_sec": float(r["windows_per_sec"]) * ADAPTIVE_E * K,
            "wan_bytes": int(r["wan_bytes"]),
            "nrmse_avg": float(r["fleet_nrmse"]["AVG"]),
            "planner_invocations": int(r["planner_invocations"]),
            "plans_reused": int(r["plans_reused"])}


def _chaos_scenario(E: int = CHAOS_E, windows: int = CHAOS_WINDOWS,
                    window: int = CHAOS_WINDOW,
                    outage: tuple = CHAOS_OUTAGE) -> ScenarioConfig:
    return ScenarioConfig(
        name=f"chaos/E{E}",
        data=DataSpec(dataset="fleet", n_points=windows * window,
                      window=window, seed=29, options={"k": K}),
        planner=PlannerConfig(solver="closed_form", dependence="pearson",
                              seed=29),
        topology=TopologySpec(n_regions=4, sites_per_region=E // 4, seed=29,
                              latency_scale=0.0),
        controller=ControllerSpec(mode="rebalance"),
        queries=("AVG", "VAR"),
        budget_fraction=CHAOS_BUDGET_FRACTION,
        runtime="scan",
        chaos=ChaosSpec(outages=(outage,)))


def _measure_chaos() -> dict:
    exp = Experiment.from_scenario(_chaos_scenario())
    exp.runtime.collect = "estimates"
    windows = exp.make_windows()
    exp.runtime.run(windows, n_windows=CHAOS_WINDOWS)      # compile + warm
    r = exp.runtime.run(windows, n_windows=CHAOS_WINDOWS)  # steady-state
    return {"scenario": f"chaos/E{CHAOS_E}/outage", "engine": "scan",
            "n_sites": CHAOS_E, "n_windows": CHAOS_WINDOWS,
            "windows_per_sec": float(r["windows_per_sec"]),
            "streams_per_sec": float(r["windows_per_sec"]) * CHAOS_E * K,
            "wan_bytes": int(r["wan_bytes"]),
            "nrmse_avg": float(r["fleet_nrmse"]["AVG"]),
            "recovery_windows": float(r["recovery_windows"]),
            "outage_nrmse_avg": float(r["outage_nrmse"]["AVG"]),
            "steady_nrmse_avg": float(r["steady_nrmse"]["AVG"]),
            "down_site_windows": int(r["down_site_windows"]),
            "gap_served_cells": int(r["gap_served_cells"])}


def _check_chaos_recovery(row: dict) -> None:
    """The bars the chaos row must clear (fresh or committed)."""
    assert row["recovery_windows"] <= CHAOS_MAX_RECOVERY_WINDOWS, (
        f"budgets must reconverge within {CHAOS_MAX_RECOVERY_WINDOWS:g} "
        f"windows of a membership change, took "
        f"{row['recovery_windows']:g}")
    ratio = row["outage_nrmse_avg"] / row["steady_nrmse_avg"]
    assert ratio <= CHAOS_MAX_OUTAGE_RATIO, (
        f"gap-served outage NRMSE {row['outage_nrmse_avg']:.4g} is "
        f"{ratio:.2f}x steady-state {row['steady_nrmse_avg']:.4g} "
        f"(> {CHAOS_MAX_OUTAGE_RATIO:g}x)")
    assert row["gap_served_cells"] == row["down_site_windows"], (
        f"every dark (window, site) cell must still be answered from the "
        f"site's last live window: served {row['gap_served_cells']} of "
        f"{row['down_site_windows']}")


def _check_adaptive_payoff(gated: dict, always: dict) -> None:
    """The bars the adaptive rows must clear (fresh or committed)."""
    budget = ADAPTIVE_MAX_INVOCATION_FRAC * gated["n_windows"]
    assert gated["planner_invocations"] <= budget, (
        f"detector-gated run must plan on <={budget:g} of "
        f"{gated['n_windows']} windows, planned on "
        f"{gated['planner_invocations']}")
    assert always["planner_invocations"] == always["n_windows"], always
    rel = (gated["nrmse_avg"] - always["nrmse_avg"]) / always["nrmse_avg"]
    assert rel <= ADAPTIVE_MAX_REL_NRMSE, (
        f"gated NRMSE {gated['nrmse_avg']:.4g} exceeds plan-every-window "
        f"{always['nrmse_avg']:.4g} by {rel:.1%} "
        f"(> {ADAPTIVE_MAX_REL_NRMSE:.0%})")


def run() -> list[tuple[str, float, str]]:
    """Full bench: measure, refresh BENCH_throughput.json, return CSV rows."""
    csv_rows, bench_rows, speedups = [], [], {}
    for E in FLEET_SIZES:
        scan, t_scan = timed(_measure_scan, E, SCAN_WINDOWS)
        event, t_event = timed(_measure_event, E, EVENT_WINDOWS[E])
        speedups[E] = scan["windows_per_sec"] / event["windows_per_sec"]
        bench_rows += [scan, event]
        csv_rows.append((f"throughput/E{E}/scan", t_scan,
                         f"{fmt(scan['windows_per_sec'])} win/s "
                         f"({fmt(speedups[E])}x event)"))
        csv_rows.append((f"throughput/E{E}/event", t_event,
                         f"{fmt(event['windows_per_sec'])} win/s"))
    for E in SHARDED_FLEET_SIZES:
        sharded, t_sharded = timed(_measure_scan, E, SHARDED_WINDOWS[E],
                                   "scan_sharded")
        bench_rows.append(sharded)
        csv_rows.append((f"throughput/E{E}/scan_sharded", t_sharded,
                         f"{fmt(sharded['windows_per_sec'])} win/s"))
    gated, t_gated = timed(
        _measure_adaptive, "gated",
        AdaptiveSpec(detector="threshold", halflife=12.0, threshold=0.25,
                     min_replan_interval=2))
    always, t_always = timed(_measure_adaptive, "always",
                             AdaptiveSpec(detector="always"))
    _check_adaptive_payoff(gated, always)
    bench_rows += [gated, always]
    csv_rows.append((f"adaptive/E{ADAPTIVE_E}/gated", t_gated,
                     f"{gated['planner_invocations']}/{ADAPTIVE_WINDOWS} "
                     f"plans, nrmse {fmt(gated['nrmse_avg'])} "
                     f"({fmt(gated['windows_per_sec'])} win/s)"))
    csv_rows.append((f"adaptive/E{ADAPTIVE_E}/always", t_always,
                     f"{always['planner_invocations']}/{ADAPTIVE_WINDOWS} "
                     f"plans, nrmse {fmt(always['nrmse_avg'])} "
                     f"({fmt(always['windows_per_sec'])} win/s)"))
    chaos, t_chaos = timed(_measure_chaos)
    _check_chaos_recovery(chaos)
    bench_rows.append(chaos)
    csv_rows.append((f"chaos/E{CHAOS_E}/outage", t_chaos,
                     f"recovery {fmt(chaos['recovery_windows'])} win, "
                     f"outage/steady "
                     f"{chaos['outage_nrmse_avg'] / chaos['steady_nrmse_avg']:.2f}x "
                     f"({fmt(chaos['windows_per_sec'])} win/s)"))
    write_bench_json(BENCH_PATH, bench_rows)
    best = max(speedups.values())
    assert best >= 10.0, (
        f"scan runtime must reach >=10x the event loop at some fleet size; "
        f"got {sorted(speedups.items())}")
    return csv_rows


def run_smoke() -> list[tuple[str, float, str]]:
    """CI gate: schema-validate the committed artifact + a tiny live scan."""
    payload = read_bench_json(BENCH_PATH)
    engines = {r["engine"] for r in payload["rows"]}
    assert engines == {"scan", "event", "scan_sharded"}, engines
    rows = {r["scenario"]: r for r in payload["rows"]}
    _check_adaptive_payoff(rows[f"adaptive/E{ADAPTIVE_E}/gated"],
                           rows[f"adaptive/E{ADAPTIVE_E}/always"])
    _check_chaos_recovery(rows[f"chaos/E{CHAOS_E}/outage"])
    mini, us = timed(_measure_scan, 4, 32)
    assert np.isfinite(mini["nrmse_avg"]), mini
    assert mini["wan_bytes"] > 0, mini
    # the sharded runtime must execute too, and on one device it carries
    # the batched scan's bitwise byte contract
    mini_sh, _ = timed(_measure_scan, 4, 32, "scan_sharded")
    assert mini_sh["wan_bytes"] == mini["wan_bytes"], (mini, mini_sh)
    # miniature chaos run: a 2-window outage on a 4-site fleet must ship
    # zero bytes from dark cells and still answer every query
    exp = Experiment.from_scenario(_chaos_scenario(
        E=4, windows=8, window=WINDOW, outage=(3, 2, 1)))
    exp.runtime.collect = "estimates"
    r = exp.runtime.run(exp.make_windows(), n_windows=8)
    live = np.asarray(r["liveness"], bool)
    assert (np.asarray(r["bytes_history"])[~live] == 0).all()
    assert np.isfinite(r["fleet_nrmse"]["AVG"])
    return [("throughput/smoke", us,
             f"artifact ok ({len(payload['rows'])} rows), "
             f"E=4 scan {fmt(mini['windows_per_sec'])} win/s, "
             f"chaos E=4 recovery {fmt(r['recovery_windows'])} win")]


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    from repro.compile_cache import setup_compile_cache
    setup_compile_cache()
    rows = run_smoke() if "--smoke" in argv else run()
    print("name,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
