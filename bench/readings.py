"""Arithmetic the metric readers share: host-clock and trace readings of a
run, per call, per fleet window and per kernel."""
from __future__ import annotations

import numpy as np

import roofline
import tracefile


def latencies_ms(run) -> np.ndarray:
    return np.asarray([e - s for s, e in run.timed]) * 1e3


def busiest(run) -> int:
    """The chip of the cell with the most busy time in the traced window."""
    lo, hi = run.trace_window()
    devs = run.trace.devices[:run.chips]
    return max(devs, key=lambda d: tracefile.busy_seconds(
        run.trace.ops[d], lo, hi))


def host_ms_per_call(run):
    """Mean over traced calls of the call's span less the device busy time
    inside it, on the busiest chip."""
    if run.trace is None:
        return None
    ops = run.trace.ops[busiest(run)]
    spans = [(s, e) for n, s, e in run.trace.spans if n == "run"]
    host = [(e - s) - tracefile.busy_seconds(ops, s, e) for s, e in spans]
    return 1e3 * float(np.mean(host))


def step_ms_per_window(run):
    """Device busy time per fleet window, on the busiest chip."""
    if run.trace is None:
        return None
    lo, hi = run.trace_window()
    busy = tracefile.busy_seconds(run.trace.ops[busiest(run)], lo, hi)
    return 1e3 * busy / run.traced_windows()


def kernel_roofline(run, kernel: str):
    """Least time of the kernel at the chip's unpadded share of the fleet
    over its summed device time, as a percentage; None where the trace
    holds no such kernel."""
    if run.trace is None:
        return None
    lo, hi = run.trace_window()
    match = tracefile.kernel_match(kernel)
    per_chip = [tracefile.op_seconds(run.trace.ops[d], lo, hi, match)
                for d in run.trace.devices[:run.chips]]
    spent = max(per_chip)
    if spent <= 0:
        return None
    c = run.cfg
    least = run.traced_windows() * roofline.least_seconds(
        kernel, int(c["sites"]) // run.chips, int(c["streams_per_site"]),
        int(c["window"]), run.device_kind)
    return 100.0 * least / spent

