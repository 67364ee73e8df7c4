"""Shared scenario-sweep driver for the figure benchmarks.

Every benchmark is a *scenario table*: a list of
:class:`repro.api.ScenarioConfig` (or (method, budget) grids over one
:class:`DataSpec`) fed to the shared driver here — no hand-rolled
experiment loops in the fig modules.  ``SMOKE_SCENARIOS`` is the compact
table ``python -m benchmarks.run --smoke`` executes; it is constructed to
exercise every registered component name at least once (the CI
registry-coverage check keys off these files).
"""
from __future__ import annotations

import json
import time
from pathlib import Path

import numpy as np

from repro.api import (AdaptiveSpec, ControllerSpec, DataSpec, Experiment,
                       RunReport, ScenarioConfig, TopologySpec,
                       TransportSpec)
from repro.core.types import PlannerConfig

REPO_ROOT = Path(__file__).resolve().parent.parent


def timed(fn, *args, **kw):
    t0 = time.perf_counter()
    out = fn(*args, **kw)
    return out, (time.perf_counter() - t0) * 1e6


def run_scenario(cfg: ScenarioConfig, **build_kw) -> RunReport:
    """Build + run one scenario (deterministic given the config)."""
    return Experiment.from_scenario(cfg, **build_kw).run()


def method_grid(data: DataSpec, methods, fracs, planner=None,
                queries=("AVG",), transport=None) -> list[ScenarioConfig]:
    """The standard figure sweep: methods x budget fractions on one dataset."""
    planner = planner or PlannerConfig()
    transport = transport or TransportSpec()
    return [ScenarioConfig(data=data, method=m, budget_fraction=f,
                           planner=planner, transport=transport,
                           queries=tuple(queries), name=f"{m}@{f:g}")
            for m in methods for f in fracs]


def sweep_methods(data: DataSpec, fracs, methods, planner=None,
                  queries=("AVG",)):
    """{(method, frac): ({query: mean NRMSE}, wan_bytes)} — the shape the
    fig modules' derived headlines (bytes_to_reach etc.) consume."""
    out = {}
    for s in method_grid(data, methods, fracs, planner=planner,
                         queries=queries):
        r = run_scenario(s)
        out[(s.method, s.budget_fraction)] = (dict(r.nrmse), r.wan_bytes)
    return out


def bytes_to_reach(curve, target_err, query="AVG"):
    """Smallest wan_bytes among budget points whose error <= target."""
    best = None
    for (m, f), (errs, bts) in curve.items():
        if errs[query] <= target_err and (best is None or bts < best):
            best = bts
    return best


def fmt(v):
    if v is None:
        return "n/a"
    if isinstance(v, float):
        return f"{v:.4g}"
    return str(v)


# --------------------------------------------------------------------------
# tracked perf artifacts (BENCH_*.json at the repo root)
# --------------------------------------------------------------------------
# One stable, diffable schema so the perf trajectory is reviewable across
# PRs.  ``rows`` is a flat list of per-configuration measurements; the
# required keys below are the contract ``validate_bench_json`` checks on
# every write and read (``throughput_bench.py --smoke`` reads the committed
# BENCH_throughput.json through it).

BENCH_SCHEMA_VERSION = 1

BENCH_TOP_FIELDS = ("schema_version", "benchmark", "device", "rows")
BENCH_ROW_FIELDS = ("scenario", "engine", "n_sites", "n_windows",
                    "windows_per_sec", "streams_per_sec", "wan_bytes",
                    "nrmse_avg")


def validate_bench_json(payload: dict) -> None:
    """Raise ValueError if ``payload`` violates the bench artifact schema."""
    for f in BENCH_TOP_FIELDS:
        if f not in payload:
            raise ValueError(f"bench artifact missing top-level field {f!r}")
    if payload["schema_version"] != BENCH_SCHEMA_VERSION:
        raise ValueError(
            f"bench artifact schema_version {payload['schema_version']!r} "
            f"!= {BENCH_SCHEMA_VERSION}")
    if not isinstance(payload["rows"], list) or not payload["rows"]:
        raise ValueError("bench artifact needs a non-empty 'rows' list")
    for i, row in enumerate(payload["rows"]):
        for f in BENCH_ROW_FIELDS:
            if f not in row:
                raise ValueError(f"bench row {i} missing field {f!r}")
        for f in ("n_sites", "n_windows", "windows_per_sec",
                  "streams_per_sec", "wan_bytes", "nrmse_avg"):
            if not isinstance(row[f], (int, float)) or not np.isfinite(row[f]):
                raise ValueError(
                    f"bench row {i} field {f!r} must be finite numeric, "
                    f"got {row[f]!r}")


def write_bench_json(path, rows: list[dict],
                     benchmark: str = "throughput") -> dict:
    """Validate and write one BENCH_*.json perf artifact (sorted, indented
    — stable text for clean diffs).  Returns the payload written."""
    import jax
    payload = {
        "schema_version": BENCH_SCHEMA_VERSION,
        "benchmark": benchmark,
        "device": jax.devices()[0].platform,
        "rows": rows,
    }
    validate_bench_json(payload)
    Path(path).write_text(json.dumps(payload, indent=2, sort_keys=True)
                          + "\n")
    return payload


def read_bench_json(path) -> dict:
    """Load + schema-validate an existing bench artifact."""
    payload = json.loads(Path(path).read_text())
    validate_bench_json(payload)
    return payload


# --------------------------------------------------------------------------
# the --smoke table: one small scenario per component family so every
# registered name (models, baselines, solvers, epsilon policies, dependence
# measures, datasets, queries) is exercised through the Scenario API
# --------------------------------------------------------------------------

_SMALL = DataSpec(dataset="smartcity", n_points=512, window=128, seed=0)

SMOKE_SCENARIOS: list[ScenarioConfig] = [
    # imputation-model families (cubic via the default planner config)
    ScenarioConfig(name="smoke/model_cubic", data=_SMALL, method="model",
                   queries=("AVG", "VAR", "MIN", "MAX", "MEDIAN")),
    ScenarioConfig(name="smoke/linear_pearson", data=_SMALL, method="linear",
                   planner=PlannerConfig(model="linear", dependence="pearson",
                                         epsilon_policy="alpha",
                                         epsilon_scale=0.05)),
    ScenarioConfig(name="smoke/mean", data=_SMALL, method="mean"),
    ScenarioConfig(name="smoke/multi",
                   data=DataSpec(dataset="turbine", n_points=512, window=128,
                                 seed=0, options={"k": 5}),
                   method="multi"),
    # baseline planners
    ScenarioConfig(name="smoke/srs", data=_SMALL, method="srs"),
    ScenarioConfig(name="smoke/approx_iot", data=_SMALL, method="approx_iot"),
    ScenarioConfig(name="smoke/s_voila", data=_SMALL, method="s_voila"),
    ScenarioConfig(name="smoke/neyman_cost", data=_SMALL, method="neyman_cost",
                   planner=PlannerConfig(cost_per_sample=(1.0, 2.0, 0.5, 1.5,
                                                          1.0))),  # k=5
    # solvers + epsilon policies (ipm/k_se are the defaults above)
    ScenarioConfig(name="smoke/slsqp_exact_mse",
                   data=DataSpec(dataset="home", n_points=512, window=128,
                                 seed=0),
                   planner=PlannerConfig(solver="slsqp",
                                         epsilon_policy="exact_mse")),
    ScenarioConfig(name="smoke/mvn_closed_form",
                   data=DataSpec(dataset="mvn", n_points=512, window=128,
                                 seed=0, options={"rho": 0.8}),
                   planner=PlannerConfig(solver="closed_form",
                                         model="linear",
                                         dependence="pearson")),
    # async WAN path
    ScenarioConfig(name="smoke/wan_latency", data=_SMALL,
                   transport=TransportSpec(latency_ms=1500.0,
                                           staleness_deadline_ms=4000.0)),
    # fleet: batched planning + rebalancing + cost-aware water-filling
    ScenarioConfig(name="smoke/fleet_rebalance",
                   data=DataSpec(dataset="fleet", n_points=256, window=128,
                                 seed=0, options={"k": 4}),
                   planner=PlannerConfig(solver="closed_form"),
                   topology=TopologySpec(n_regions=2, sites_per_region=3,
                                         seed=0),
                   controller=ControllerSpec(mode="rebalance",
                                             link_cost_aware=True),
                   queries=("AVG", "VAR")),
    # plan engines selected declaratively (repro.planning.ENGINES): the
    # batched engine covering a former host-only family (mean imputation),
    # and the shard_map engine splitting the site axis over the local
    # devices — coverage regressions in either fail the CI smoke
    ScenarioConfig(name="smoke/fleet_engine_batched_mean",
                   data=DataSpec(dataset="fleet", n_points=256, window=128,
                                 seed=1, options={"k": 4}),
                   planner=PlannerConfig(solver="closed_form", model="mean",
                                         engine="batched"),
                   topology=TopologySpec(n_regions=2, sites_per_region=3,
                                         seed=1),
                   queries=("AVG",)),
    ScenarioConfig(name="smoke/fleet_engine_sharded",
                   data=DataSpec(dataset="fleet", n_points=256, window=128,
                                 seed=1, options={"k": 4}),
                   planner=PlannerConfig(solver="closed_form",
                                         epsilon_policy="exact_mse",
                                         engine="sharded"),
                   topology=TopologySpec(n_regions=2, sites_per_region=3,
                                         seed=1),
                   controller=ControllerSpec(demand_signal="max_err"),
                   queries=("AVG",)),
    # adaptive re-planning (repro.adaptive): a "threshold"-gated event run
    # over a mid-run correlation shift, and a "page_hinkley"-gated scan run
    # — both detectors exercised by name for the registry-coverage check
    # ("always"/"never" are covered by the parity tests in
    # tests/test_adaptive.py)
    ScenarioConfig(name="smoke/adaptive_threshold_event",
                   data=DataSpec(dataset="fleet", n_points=512, window=64,
                                 seed=2,
                                 options={"k": 4,
                                          "strength_schedule":
                                              [[0, [0.9, 0.2]],
                                               [4, [0.2, 0.9]]]}),
                   planner=PlannerConfig(solver="closed_form", seed=2),
                   topology=TopologySpec(n_regions=2, sites_per_region=3,
                                         seed=2, latency_scale=0.0),
                   controller=ControllerSpec(),
                   queries=("AVG", "VAR"),
                   adaptive=AdaptiveSpec(detector="threshold",
                                         halflife=16.0, threshold=0.3)),
    ScenarioConfig(name="smoke/adaptive_ph_scan",
                   data=DataSpec(dataset="fleet", n_points=512, window=64,
                                 seed=3,
                                 options={"k": 4,
                                          "strength_schedule":
                                              [[0, [0.9, 0.2]],
                                               [4, [0.2, 0.9]]]}),
                   planner=PlannerConfig(solver="closed_form", seed=3),
                   topology=TopologySpec(n_regions=2, sites_per_region=3,
                                         seed=3, latency_scale=0.0),
                   controller=ControllerSpec(),
                   queries=("AVG", "VAR"), runtime="scan",
                   adaptive=AdaptiveSpec(detector="page_hinkley",
                                         halflife=12.0, ph_delta=0.02,
                                         ph_lambda=0.3,
                                         min_replan_interval=2)),
]


def run_smoke() -> list[tuple[str, float, str]]:
    """Execute the smoke table; returns benchmark-style rows."""
    rows = []
    for s in SMOKE_SCENARIOS:
        r, us = timed(run_scenario, s)
        assert all(np.isfinite(v) for v in r.nrmse.values()), s.name
        assert r.wan_bytes <= r.full_bytes, s.name
        rows.append((s.name, us, r.summary()))
    return rows
