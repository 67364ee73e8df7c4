"""Benchmark harness — one module per paper figure/table.

``python -m benchmarks.run [--only fig4,fig5] [--skip kernel_bench]``
prints ``name,us_per_call,derived`` CSV rows.

``python -m benchmarks.run --smoke`` runs the compact Scenario-API smoke
table instead (benchmarks.common.SMOKE_SCENARIOS): one small scenario per
registered component family, through ``Experiment.from_scenario``
(``tests/test_smoke_table.py`` runs the same table, one case a scenario).
"""
from __future__ import annotations

import argparse
import importlib
import sys
import traceback

MODULES = [
    "fig3_heuristic", "fig4_turbine", "fig5_smartcity", "fig6_latency",
    "fig7_bias", "fig8_correlation", "fig9_iid", "fig10_models",
    "fig11_costs", "fig12_multi_predictor", "kernel_bench",
    "fleet_bench", "throughput_bench",
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="")
    ap.add_argument("--skip", default="")
    ap.add_argument("--smoke", action="store_true",
                    help="run the small Scenario-API smoke table only")
    args = ap.parse_args()
    only = [m.strip() for m in args.only.split(",") if m.strip()]
    skip = [m.strip() for m in args.skip.split(",") if m.strip()]
    from repro.compile_cache import setup_compile_cache
    setup_compile_cache()

    print("name,us_per_call,derived")
    failures = 0
    if args.smoke:
        from benchmarks.common import run_smoke
        try:
            for row_name, us, derived in run_smoke():
                print(f"{row_name},{us:.1f},{derived}")
        except Exception:
            failures += 1
            print(f"smoke,0.0,ERROR: {traceback.format_exc(limit=2)!r}")
        sys.exit(1 if failures else 0)

    for name in MODULES:
        if only and name not in only:
            continue
        if name in skip:
            continue
        try:
            mod = importlib.import_module(f"benchmarks.{name}")
            for row_name, us, derived in mod.run():
                print(f"{row_name},{us:.1f},{derived}")
            sys.stdout.flush()
        except Exception:
            failures += 1
            print(f"{name},0.0,ERROR: {traceback.format_exc(limit=2)!r}")
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
