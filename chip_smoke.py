#!/usr/bin/env python3
"""Chip smoke test: the fleet scan runtime, end to end, on a TPU.

Drives the system's main path once at deployment size —
``ScenarioConfig(runtime="scan" | "scan_sharded")`` ->
``Experiment.from_scenario`` -> ``runtime.run(..., collect="estimates")``
— and checks what comes out:

  default (one chip)
    A fleet of E=1024 sites in 4 regions, k=4 streams per site, tumbling
    windows of N=288 tuples (one day of 5-minute readings, the fleet
    generator's diurnal period), rebalance controller, closed-form solver,
    AVG/VAR/MIN/MAX.  An 8-window pool cycled over 64 windows, through
    ``scan`` and through ``scan_sharded`` on the one-chip mesh.
      * both Pallas kernels (``stream_stats_fleet``, ``polyfit``) are
        compiled into each scan program, none in interpret mode;
      * ``scan_sharded`` equals ``scan`` bitwise on counters, WAN bytes
        and the per-window byte history (budgets to f32 noise);
      * both agree with the event-loop oracle (``FleetRuntime``,
        ``sampling="device"``) run on the host CPU device over the first
        4 windows: WAN bytes within 5%, NRMSE within rtol 0.08 / atol 0.02;
      * bytes in (0, full_bytes), every (window, site) shipped and was
        answered, every NRMSE finite.

  ``--chips 4``
    Only ``scan_sharded`` at E=4096 over the four-chip mesh, against
    ``scan`` on one chip for the same scenario: counters, WAN bytes and
    byte history bitwise.

Without a TPU it exits nonzero before running anything.  Any failed check
raises, so the process exits nonzero and never prints ``"ok": true``.
Windows/s lines are smoke readings of one short run, not benchmarks.

Usage::

    python chip_smoke.py            # one chip
    python chip_smoke.py --chips 4  # the four-chip path only

The last line of standard output is one JSON object:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

import numpy as np

SRC = Path(__file__).resolve().parent / "src"

K = 4                    # streams per site
WINDOW = 288             # tuples per window: one day of 5-minute readings
POOL = 8                 # distinct generated windows, cycled by the scan
N_WINDOWS = 64           # windows per scan run
ORACLE_WINDOWS = 4       # event-loop oracle prefix (host CPU)
REGIONS = 4
SEED = 0
QUERIES = ("AVG", "VAR", "MIN", "MAX")
E_ONE_CHIP = 1024
E_FOUR_CHIPS = 4096
KERNELS = ("stream_stats_fleet", "polyfit")
COUNTERS = ("wan_bytes", "full_bytes", "gaps", "revisions", "late_drops",
            "duplicates", "retransmits", "plan_windows")


def scenario(E: int, runtime: str):
    """The smoke fleet: E sites in 4 regions, zero-latency links."""
    from repro.api import (ControllerSpec, DataSpec, ScenarioConfig,
                           TopologySpec)
    from repro.core.types import PlannerConfig
    return ScenarioConfig(
        name=f"chip_smoke/E{E}",
        data=DataSpec(dataset="fleet", n_points=POOL * WINDOW, window=WINDOW,
                      seed=SEED, options={"k": K}),
        planner=PlannerConfig(solver="closed_form", dependence="pearson",
                              seed=SEED),
        topology=TopologySpec(n_regions=REGIONS,
                              sites_per_region=E // REGIONS, seed=SEED,
                              latency_scale=0.0),
        controller=ControllerSpec(mode="rebalance"),
        queries=QUERIES,
        runtime=runtime)


def _scan_experiment(E: int, runtime: str):
    from repro.api import Experiment
    exp = Experiment.from_scenario(scenario(E, runtime))
    exp.runtime.collect = "estimates"      # device-side query tables only
    return exp


def make_windows(E: int) -> list:
    """The pool of generated windows, the same for every runtime (the
    data depends only on the scenario's data and fleet shape)."""
    from repro.api import Experiment
    return Experiment.from_scenario(scenario(E, "scan")).make_windows()


def run_fleet(E: int, runtime: str, windows,
              n_windows: int = N_WINDOWS) -> dict:
    """Compile the runtime's scan ahead of time (timed), then run it twice:
    the first run dispatches through ``jit``, the second is the smoke
    reading of windows/s."""
    rt = _scan_experiment(E, runtime).runtime
    t0 = time.perf_counter()
    compiled = rt.lower(windows, n_windows).compile()
    compile_s = time.perf_counter() - t0
    rt.run(windows, n_windows=n_windows)
    result = rt.run(windows, n_windows=n_windows)
    mesh = getattr(rt, "_mesh", None)      # the site mesh of scan_sharded
    return {"runtime": runtime, "E": E, "n_windows": n_windows,
            "compile_s": compile_s, "hlo": compiled.as_text(),
            "interpret": rt.interpret, "result": result,
            "mesh_devices": 1 if mesh is None else int(mesh.size)}


def run_prefix(E: int, runtime: str, windows,
               n_windows: int = ORACLE_WINDOWS) -> dict:
    """The runtime over the first ``n_windows`` pool windows."""
    return _scan_experiment(E, runtime).runtime.run(windows,
                                                    n_windows=n_windows)


def oracle_report(E: int, windows, n_windows: int = ORACLE_WINDOWS):
    """The event-loop oracle on the host CPU device: ``FleetRuntime`` with
    the scan's sampler (``sampling="device"``) and the jnp reference
    statistics and fits."""
    import jax
    from repro.api import Experiment
    with jax.default_device(jax.devices("cpu")[0]):
        exp = Experiment.from_scenario(scenario(E, "event"),
                                       use_kernel=False)
        exp.runtime.sampling = "device"
        return exp.run(windows[:n_windows])


def kernel_calls(hlo: str) -> dict:
    """{kernel name: count} of the Pallas kernels compiled into ``hlo``."""
    calls = [line for line in hlo.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    counts = {name: sum(f"/{name}/pallas_call" in c for c in calls)
              for name in KERNELS}
    counts["total"] = len(calls)
    return counts


def check(ok, what: str) -> None:
    """Fail the smoke (an ``assert`` would vanish under ``python -O``)."""
    if not ok:
        raise AssertionError(what)


def check_counts(result: dict, E: int, n_windows: int) -> None:
    """What holds on any device: bytes in (0, full), every (window, site)
    shipped a payload and was answered, every NRMSE finite."""
    check(0 < result["wan_bytes"] < result["full_bytes"],
          f"wan_bytes {result['wan_bytes']} of {result['full_bytes']}")
    check(result["full_bytes"] == n_windows * E * K * WINDOW * 4,
          f"full_bytes {result['full_bytes']}")
    hist = np.asarray(result["bytes_history"])
    check(hist.shape == (n_windows, E), f"byte history {hist.shape}")
    check((hist > 0).all(), "a (window, site) cell shipped nothing")
    check(result["gaps"] == 0, f"{result['gaps']} gaps")
    for q in QUERIES:
        check(np.isfinite(result["fleet_nrmse"][q]), f"{q} fleet NRMSE")
        check(np.isfinite(np.asarray(result["site_nrmse"][q])).all(),
              f"{q} site NRMSE")


def check_sharded_matches_scan(scan: dict, sharded: dict) -> None:
    """The scan_sharded contract: integer counters, WAN bytes and the byte
    history bitwise; rebalance budgets to f32 association noise (psum)."""
    for f in COUNTERS:
        check(scan[f] == sharded[f], f"{f}: {scan[f]} != {sharded[f]}")
    check(scan["wan_bytes_by_region"] == sharded["wan_bytes_by_region"],
          "wan_bytes_by_region")
    np.testing.assert_array_equal(np.asarray(sharded["bytes_history"]),
                                  np.asarray(scan["bytes_history"]))
    np.testing.assert_allclose(np.asarray(sharded["budget_history"]),
                               np.asarray(scan["budget_history"]),
                               rtol=2e-5, atol=1e-4)


def oracle_agreement(result: dict, oracle) -> dict:
    """Relative WAN-byte gap and per-query NRMSE pairs vs the oracle."""
    return {"bytes_rel": abs(result["wan_bytes"] - oracle.wan_bytes)
            / oracle.wan_bytes,
            "nrmse": {q: (float(result["fleet_nrmse"][q]),
                          float(oracle.nrmse[q])) for q in QUERIES}}


def check_against_oracle(result: dict, oracle) -> None:
    """tests/test_scan_runtime.py's scan-vs-event tolerance contract."""
    check(abs(result["wan_bytes"] - oracle.wan_bytes)
          <= 0.05 * oracle.wan_bytes,
          f"wan_bytes {result['wan_bytes']} vs oracle {oracle.wan_bytes}")
    for q in QUERIES:
        np.testing.assert_allclose(result["fleet_nrmse"][q], oracle.nrmse[q],
                                   rtol=0.08, atol=0.02, err_msg=q)


# --------------------------------------------------------------------- main

def _say(msg: str) -> None:
    print(f"chip_smoke: {msg}", flush=True)


def _report_run(run: dict) -> None:
    r = run["result"]
    calls = kernel_calls(run["hlo"])
    _say(f"{run['runtime']} E={run['E']} on {run['mesh_devices']} "
         f"device(s): compile {run['compile_s']:.2f} s, "
         f"{r['windows_per_sec']:.1f} windows/s over {run['n_windows']} "
         f"windows (smoke reading, not a benchmark), wan_bytes "
         f"{r['wan_bytes']} of {r['full_bytes']}, fleet NRMSE "
         + " ".join(f"{q}={r['fleet_nrmse'][q]:.6g}" for q in QUERIES))
    _say(f"{run['runtime']} Pallas kernels in the compiled scan: {calls}")


def _check_kernels(run: dict) -> None:
    check(run["interpret"] is False, "interpret mode on the chip path")
    calls = kernel_calls(run["hlo"])
    missing = [k for k in KERNELS if calls[k] < 1]
    check(not missing, f"{run['runtime']}: kernels not compiled: {missing}")


def _one_chip() -> None:
    E = E_ONE_CHIP
    windows = make_windows(E)
    runs = [run_fleet(E, rt, windows) for rt in ("scan", "scan_sharded")]
    for run in runs:
        _report_run(run)
        _check_kernels(run)
        check_counts(run["result"], E, N_WINDOWS)
    check_sharded_matches_scan(runs[0]["result"], runs[1]["result"])
    _say("scan_sharded == scan: counters, WAN bytes, byte history bitwise")

    t0 = time.perf_counter()
    oracle = oracle_report(E, windows)
    _say(f"event-loop oracle on the host CPU, {ORACLE_WINDOWS} windows: "
         f"{time.perf_counter() - t0:.1f} s, wan_bytes {oracle.wan_bytes}")
    for rt in ("scan", "scan_sharded"):
        prefix = run_prefix(E, rt, windows)
        check_counts(prefix, E, ORACLE_WINDOWS)
        _say(f"{rt} vs oracle: {oracle_agreement(prefix, oracle)}")
        check_against_oracle(prefix, oracle)


def _four_chips() -> None:
    E = E_FOUR_CHIPS
    windows = make_windows(E)
    runs = [run_fleet(E, rt, windows) for rt in ("scan", "scan_sharded")]
    check(runs[1]["mesh_devices"] == 4, f"mesh of {runs[1]['mesh_devices']}")
    for run in runs:
        _report_run(run)
        _check_kernels(run)
        check_counts(run["result"], E, N_WINDOWS)
    check_sharded_matches_scan(runs[0]["result"], runs[1]["result"])
    _say("scan_sharded on 4 chips == scan on 1 chip: counters, WAN bytes, "
         "byte history bitwise")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the four-chip scan_sharded path")
    args = ap.parse_args(argv)

    if str(SRC) not in sys.path:
        sys.path.insert(0, str(SRC))
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform!r} devices); "
              f"nothing was run", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"chips, JAX found {len(devices)}", file=sys.stderr)
        return 1
    from repro.compile_cache import setup_compile_cache
    cache_dir = setup_compile_cache()
    _say(f"device {dev.device_kind} x{len(devices)}, JAX {jax.__version__}, "
         f"compile cache {cache_dir}")

    if args.chips == 4:
        _four_chips()
    else:
        _one_chip()

    stats = dev.memory_stats() or {}
    entries = (len(os.listdir(cache_dir)) if os.path.isdir(cache_dir)
               else 0)
    _say(f"peak_bytes_in_use {stats.get('peak_bytes_in_use')} on device 0; "
         f"compile cache {cache_dir} holds {entries} entries")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
