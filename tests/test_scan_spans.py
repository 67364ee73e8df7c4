"""The scan runtime's host spans and the window step's stage scopes.

``ScanRuntime.run`` writes one ``TraceAnnotation`` per host phase, each
with its call's number and window count, and ``make_window_step`` opens one
``named_scope`` per stage, which reaches the compiled program only as op
metadata (docs/runtime.md, "Tracing a serving process")."""
import glob
import os
import re

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.adaptive import AdaptiveSpec
from repro.api import (ControllerSpec, DataSpec, Experiment, ScenarioConfig,
                       TopologySpec)
from repro.chaos import ChaosSpec
from repro.core.types import PlannerConfig

E, K, N = 8, 3, 32
PHASES = ["scan.prepare", "scan.place", "scan.execute", "scan.readback",
          "scan.report"]
STAGES = {"step.budgets", "step.plan", "step.sample", "step.impute",
          "step.queries", "step.truth", "step.update"}


def _runtime(mode="rebalance", adaptive=None, collect="estimates",
             chaos=None, budget_fraction=0.25):
    scenario = ScenarioConfig(
        name="spans",
        data=DataSpec(dataset="fleet", n_points=2 * N, window=N, seed=1,
                      options={"k": K}),
        planner=PlannerConfig(solver="closed_form", seed=3),
        topology=TopologySpec(n_regions=2, sites_per_region=E // 2, seed=0,
                              latency_scale=0.0),
        controller=ControllerSpec(mode=mode),
        queries=("AVG", "VAR", "MIN", "MAX"), adaptive=adaptive,
        chaos=chaos, budget_fraction=budget_fraction, runtime="scan")
    rt = Experiment.from_scenario(scenario).runtime
    rt.collect = collect
    return rt


def _windows(T):
    rng = np.random.default_rng(0)
    return [rng.normal(size=(E, K, N)).astype(np.float32) for _ in range(T)]


def _spans(log_dir, prefixes=("scan.",)):
    (path,) = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                        recursive=True)
    return sorted(((ev.name, ev.start_ns, ev.end_ns, dict(ev.stats))
                   for plane in ProfileData.from_file(path).planes
                   for line in plane.lines for ev in line.events
                   if ev.name.startswith(prefixes)), key=lambda s: s[1])


@pytest.mark.parametrize("collect", ["estimates", "payloads"])
def test_run_writes_five_phase_spans_a_call(tmp_path, collect):
    rt = _runtime(collect=collect)
    w = _windows(2)
    first = rt.run(w, n_windows=2)                # compiles, untraced
    with jax.profiler.trace(str(tmp_path)):
        res = rt.run(w, n_windows=2, state=first["final_state"])
        rt.run(w[:1], n_windows=1, state=res["final_state"])
    spans = _spans(tmp_path)
    assert [s[0] for s in spans] == PHASES * 2
    assert all(a[2] <= b[1] for a, b in zip(spans, spans[1:]))
    assert all(s[1] <= s[2] for s in spans)
    assert [{a: s[3][a] for a in ("call", "windows")} for s in spans] == (
        [{"call": 2, "windows": 2}] * 5 + [{"call": 3, "windows": 1}] * 5)
    # scan.place adds its devices and bytes; the other four carry no more
    assert [set(s[3]) - {"call", "windows"} for s in spans] == (
        [set(), {"devices", "bytes"}, set(), set(), set()] * 2)
    assert res["scan_seconds"] * 1e9 <= spans[2][2] - spans[2][1]
    assert res["windows_per_sec"] == 2 / res["scan_seconds"]
    assert not hasattr(rt, "plan_seconds")


@pytest.mark.parametrize("chaos", [
    None, ChaosSpec(flaps=((0, 1, "down"),))])
def test_report_writes_one_nrmse_span_inside_scan_report(tmp_path, chaos):
    # a static budget of one window's tuples: every stream draws enough
    # samples for a VAR answer, so only the down site leaves non-finite
    # rows (a rebalancing controller may starve a stream to one sample)
    rt = _runtime(mode="static", chaos=chaos, budget_fraction=1.0)
    tables = []
    report_fn = rt._result_fleet

    def keep(est, tru, *a, **kw):
        tables.append((est, tru))
        return report_fn(est, tru, *a, **kw)
    rt._result_fleet = keep
    w = _windows(2)
    first = rt.run(w, n_windows=2)
    with jax.profiler.trace(str(tmp_path)):
        rt.run(w, n_windows=2, state=first["final_state"])
    report, nrmse = _spans(tmp_path, ("scan.report", "report."))
    assert (report[0], nrmse[0]) == ("scan.report", "report.nrmse")
    assert report[1] <= nrmse[1] <= nrmse[2] <= report[2]
    est, tru = tables[-1]
    masked = sum(int((~np.isfinite(est[q]) | ~np.isfinite(tru[q]))
                     .any(axis=0).sum()) for q in tru)
    assert (masked > 0) == (chaos is not None)
    assert nrmse[3] == {"rows": 4 * E * K, "masked_rows": masked}


@pytest.mark.parametrize("mode,adaptive,stages", [
    ("rebalance", None, STAGES),
    ("static", None, STAGES - {"step.budgets"}),
    ("rebalance", AdaptiveSpec(detector="threshold"), STAGES | {"step.gate"}),
])
def test_the_compiled_step_names_every_stage(mode, adaptive, stages):
    rt = _runtime(mode=mode, adaptive=adaptive)
    text = rt.lower(_windows(2), 2).compile().as_text()
    named = set(re.findall(r'op_name="[^"]*?(step\.[a-z]+)/', text))
    assert named == stages


def test_place_span_carries_its_devices_and_bytes(tmp_path):
    """``scan.place`` names the devices the call's arguments went to and
    the bytes they hold there: the pool, the carry and the window ids."""
    rt = _runtime()
    w = _windows(2)
    first = rt.run(w, n_windows=2)
    carry = first["final_state"]
    with jax.profiler.trace(str(tmp_path)):
        rt.run(w, n_windows=2, state=carry)
    (place,) = [s for s in _spans(tmp_path) if s[0] == "scan.place"]
    pool = np.stack(w)
    carry_bytes = sum(np.asarray(x).nbytes for x in jax.tree.leaves(carry))
    wids = np.arange(2, dtype=np.int32)
    assert place[3] == {"call": 2, "windows": 2, "devices": 1,
                        "bytes": pool.nbytes + carry_bytes + wids.nbytes}
    assert first["exchange_all_gathers"] == 0
    assert first["exchange_all_reduces"] == 0
    assert first["exchange_gather_bytes"] == 0
