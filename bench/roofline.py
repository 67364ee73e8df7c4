"""Least time of each Pallas kernel at the cell's unpadded shapes.

Work is counted at (E, k, N) as the algorithm needs it, not at the padded
layout a kernel runs (k to 8 sublanes, N to 128-lane tiles), so a layout
change shows up as a change in kernel time and not in the count.  The least
time is the larger of bytes over the peak bandwidth and MXU operations
over the peak rate of f32 at HIGHEST precision (the kernels' precision).
The VPU's f32 rate is not in the published table, so VPU work sets no
bound.  A share of the roofline is that least time over the kernel's
summed device time in the trace.
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"
F32 = 4


def peaks(device_kind: str) -> dict:
    with open(PEAKS) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"the table has {sorted(table)}")
    return table[device_kind]


def stream_stats(e: int, k: int, n: int) -> dict:
    """One window: read X (E, k, N); write per stream S1..S4 and each
    site's k x k cross products; XX^T is 2 k^2 N MXU operations a site."""
    return {"bytes": F32 * (e * k * n + e * k * (4 + k)),
            "mxu_ops": 2 * e * k * k * n}


def polyfit(e: int, k: int, n: int) -> dict:
    """One window: read the target and the standardized predictor
    (E, k, N) each; write 7 + 4 Vandermonde sums per stream."""
    return {"bytes": F32 * (2 * e * k * n + e * k * 11), "mxu_ops": 0}


KERNELS = {"stream_stats_fleet": stream_stats, "polyfit": polyfit}


def least_seconds(kernel: str, e: int, k: int, n: int,
                  device_kind: str) -> float:
    p = peaks(device_kind)
    w = KERNELS[kernel](e, k, n)
    return max(w["bytes"] / p["hbm_bytes_per_s"],
               w["mxu_ops"] / p["f32_highest_flops_per_s"])
