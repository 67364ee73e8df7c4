#!/usr/bin/env python3
"""Where a call's time goes: the program's host phases, its idle gaps and
its stages on the device, and what tracing costs.

    python3 bench/phases.py --workload <cell> --seed <n> [--calls 10]
                            [--keep <dir>]

One process on the chip, set up as ``bench/run.py`` sets up a cell: it
serves ``--calls`` calls untraced, ``--calls`` under the profiler, and
``--calls`` untraced again, all on the same windows, and prints the call
latency (median, p95) of the traced calls against the untraced ones.  From
the trace it prints, per call, the harness's ``run`` span, the device busy
time inside it and the self time of each of ``ScanRuntime.run``'s spans
(``scan.prepare``, ``scan.place``, ``scan.execute``, ``scan.readback``,
``scan.report``); the longest idle gaps of the busiest chip, each named by
the innermost span that covers most of it; and the device time of every
stage of the window step (``bench/scopes.py``).  The last line of standard
output is one JSON object.  ``--keep`` keeps the trace and the compiled
program's text there.  The benchmark's runs never run this.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import shutil
import sys
import tempfile
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
sys.path[:0] = [p for p in (str(HERE), str(HERE.parent / "src"))
                if p not in sys.path]

import generate  # noqa: E402
import readings  # noqa: E402
import run as R  # noqa: E402
import scopes  # noqa: E402
import tracefile  # noqa: E402

PHASES = ("scan.prepare", "scan.place", "scan.execute", "scan.readback",
          "scan.report")
MARSHAL = ("scan.prepare", "scan.place", "scan.readback")


@dataclasses.dataclass
class Span:
    name: str
    start: float              # seconds, on the device ops' clock
    end: float
    args: dict


def load_spans(path: str) -> list:
    """The harness's ``run`` spans and the program's ``scan.*`` spans, with
    their args, sorted by start."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != tracefile.HOST_PLANE:
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == "run" or ev.name in PHASES:
                    out.append(Span(ev.name, ev.start_ns * 1e-9,
                                    ev.end_ns * 1e-9, dict(ev.stats)))
    return sorted(out, key=lambda s: s.start)


def calls(spans: list) -> list:
    """[(run span, {phase: span})] of each ``run`` span, with the program
    spans that lie inside it."""
    out = []
    for r in (s for s in spans if s.name == "run"):
        inner = {s.name: s for s in spans if s.name in PHASES
                 and r.start <= s.start and s.end <= r.end}
        out.append((r, inner))
    return out


def idle_gaps(ops: list, spans: list, lo: float, hi: float,
              n: int = 10) -> list:
    """[[span, seconds]]: the longest idle stretches of a device in
    [lo, hi], each named by the innermost span that covers the largest
    part of it (``outside_spans`` where none does)."""
    gaps, t = [], lo
    for s, e in tracefile.busy_intervals(ops, lo, hi):
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    gaps.sort(key=lambda g: g[0] - g[1])
    return [[gap_name(spans, s, e), e - s] for s, e in gaps[:n]]


def gap_name(spans: list, lo: float, hi: float) -> str:
    """The span that is innermost over the largest part of [lo, hi]."""
    cuts = sorted({lo, hi} | {x for s in spans for x in (s.start, s.end)
                              if lo < x < hi})
    held = {}
    for a, b in zip(cuts, cuts[1:]):
        open_ = [s for s in spans if s.start <= a and b <= s.end]
        if open_:
            inner = min(open_, key=lambda s: s.end - s.start)
            held[inner.name] = held.get(inner.name, 0.0) + (b - a)
    return max(held, key=held.get) if held else "outside_spans"


def phase_table(tr, spans: list, device: int) -> dict:
    """Mean ms a call of the run span, the device busy time in it, each
    program phase, and what the phases leave of the host time."""
    rows = []
    for r, inner in calls(spans):
        busy = tracefile.busy_seconds(tr.ops[device], r.start, r.end)
        row = {"run": r.end - r.start, "busy": busy,
               "host": r.end - r.start - busy}
        row.update({p: s.end - s.start for p, s in inner.items()})
        row["marshal"] = sum(row.get(p, 0.0) for p in MARSHAL)
        row["uncovered"] = (row["host"] - row["marshal"]
                            - row.get("scan.report", 0.0))
        ex = inner.get("scan.execute")
        if ex is not None:
            row["execute_not_busy"] = (ex.end - ex.start) - \
                tracefile.busy_seconds(tr.ops[device], ex.start, ex.end)
        rows.append(row)
    keys = sorted({k for row in rows for k in row})
    return {k: 1e3 * float(np.mean([row.get(k, 0.0) for row in rows]))
            for k in keys}


def latency(xs: list) -> dict:
    ms = np.asarray(xs) * 1e3
    return {"calls": int(ms.size), "median_ms": float(np.median(ms)),
            "p95_ms": float(np.percentile(ms, 95))}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--calls", type=int, default=10)
    ap.add_argument("--keep", default=None)
    args = ap.parse_args(argv)
    try:
        bench = R.load_benchmark()
        cell, cfg, traffic = R.find_cell(bench, args.workload)
        devices = R.tpu_devices(int(cell["chips"]))
    except R.Refused as e:
        R.say(f"refused: {e}; nothing was measured")
        return 2
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    from repro.compile_cache import setup_compile_cache
    setup_compile_cache()
    chips = int(cell["chips"])
    distinct = int(traffic["distinct_windows"])
    per_call = int(traffic["windows_per_call"])
    windows = generate.fleet_windows(cfg, distinct, args.seed)
    server = R.Server(R.build_runtime(cfg, chips), windows, per_call,
                      R.first_window(args.seed, distinct))
    server.call()                                  # warm-up: compiles

    def serve(n):
        out = []
        for _ in range(n):
            with R.span("run"):
                s, e = server.call()
            out.append(e - s)
        return out

    before = serve(args.calls)
    trace_dir = args.keep or tempfile.mkdtemp(prefix="phases_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    traced = serve(args.calls)
    jax.profiler.stop_trace()
    after = serve(args.calls)
    server.close()
    try:
        path = tracefile.xplane_path(trace_dir)
        tr, spans = tracefile.load(path), load_spans(path)
    finally:
        if not args.keep:
            shutil.rmtree(trace_dir, ignore_errors=True)

    run = R.Run(cell=cell, cfg=cfg, traffic=traffic, chips=chips,
                device_kind=devices[0].device_kind, setup_s=0.0,
                timed=[(0.0, 1.0)], trace=tr)
    text = scopes.compiled_text(run)
    if args.keep:
        Path(args.keep, "compiled.txt").write_text(text)
    table = scopes.hlo_scopes(text)
    lo, hi = run.trace_window()
    dev = readings.busiest(run)
    ops = [o for o in tr.ops[dev] if lo <= o.start < hi]
    found = sum(scopes.instruction(o) in table for o in ops)
    result = {
        "workload": args.workload, "seed": args.seed,
        "device": devices[0].device_kind,
        "latency": {"untraced": latency(before + after),
                    "traced": latency(traced),
                    "untraced_before": latency(before),
                    "untraced_after": latency(after)},
        "phases_ms_per_call": phase_table(tr, spans, dev),
        "idle_gaps": idle_gaps(tr.ops[dev], spans, lo, hi),
        "stage_ms_per_window": {s or "no stage": v for s, v in
                                scopes.by_stage(run, table).items()},
        "busy_ms_per_window": 1e3 * tracefile.busy_seconds(
            tr.ops[dev], lo, hi) / run.traced_windows(),
        "ops_named_in_program": [found, len(ops)],
        "program_spans": sorted({s.name for s in spans} - {"run"}),
        "span_args": sorted({k for s in spans for k in s.args}),
    }
    for k, v in result.items():
        R.say(f"{k}: {v}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
