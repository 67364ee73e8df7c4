#!/usr/bin/env python3
"""Readings that set a cell's limits: the program's, the control's and a
planted fault's.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds <s>
        [--matmul-precision bfloat16]

In one process, for each seed: the cell's windows are served as a run
serves them (warm-up, then a closed loop for ``--seconds``), and every call
is compared with the float64 reference (the program's reading); the
reference computed in bfloat16 and put in the program's place is compared
the same way (the control's reading); and so are the served calls with an
answer altered (the fault's reading).  The benchmark's own runs never run
this.  The lower reading of a number is the largest the program gives over
the seeds, the upper the smallest the control or the fault gives; the limit
in ``bench/limits/<cell>.json`` lies between them.  ``--matmul-precision``
sets JAX's default contraction precision for the program, to read what a
lower precision in the program itself does.  Prints one JSON line a seed
and a last line with the readings.
"""
import argparse
import json
import sys
import time

import run as R


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--matmul-precision", default=None)
    args = ap.parse_args(argv)
    bench = R.load_benchmark()
    cell, cfg, traffic = R.find_cell(bench, args.workload)
    R.tpu_devices(int(cell["chips"]))
    import jax
    if args.matmul_precision:
        jax.config.update("jax_default_matmul_precision",
                          args.matmul_precision)
    limits = R.limits_of(cell["name"])
    per_call = int(traffic["windows_per_call"])
    distinct = int(traffic["distinct_windows"])
    rt = R.build_runtime(cfg, int(cell["chips"]))
    ref = R.reference
    readings = {"program": [], "control": [], "fault": []}
    for seed in (int(s) for s in args.seeds.split(",")):
        windows = R.generate.fleet_windows(cfg, distinct, seed)
        srv = R.Server(rt, windows, per_call, R.first_window(seed, distinct))
        for _ in range(R.WARMUP_CALLS):
            srv.call()
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < args.seconds:
            srv.call()
        srv.close()
        table = ref.Reference.of(windows)
        got = {
            "program": ref.compare(srv.calls, windows, cfg, table),
            "control": ref.compare(ref.control_calls(srv.calls, windows,
                                                     cfg), windows, cfg,
                                   table),
            "fault": ref.compare(ref.altered_calls(srv.calls), windows, cfg,
                                 table)}
        for k, v in got.items():
            readings[k].append(v)
        print(json.dumps({"seed": seed, "calls": len(srv.calls), **got,
                          "correct": all(got["program"][k] <= limits[k]
                                         for k in limits)}), flush=True)
    print(json.dumps({
        "workload": args.workload, "seeds": len(readings["program"]),
        "matmul_precision": args.matmul_precision,
        "lower": {k: max(p[k] for p in readings["program"]) for k in limits},
        "upper_control": {k: min(c[k] for c in readings["control"])
                          for k in limits},
        "upper_fault": {k: min(c[k] for c in readings["fault"])
                        for k in limits},
        "limits": limits}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
