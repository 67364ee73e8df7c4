"""Reduce a JAX profiler trace to device busy and idle time, op and kernel
time, and idle gaps attributed to the harness's host spans.

The trace is the ``.xplane.pb`` that ``jax.profiler.start_trace`` writes.
Each TPU is a plane named ``/device:TPU:<n>``; its ``XLA Ops`` line holds
one event per executed HLO op.  The harness's own spans
(``jax.profiler.TraceAnnotation``) are events on a line of the host plane,
on the same clock.  Busy time is the union of a device's op intervals.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
SPANS = ("generate", "warmup", "run", "between_calls")
# ops that only hold others: their events span their bodies' ops
CONTAINERS = ("while", "conditional", "call")


@dataclasses.dataclass
class Op:
    name: str             # the op's HLO text: ``%fusion.12 = f32[...] ...``
    start: float          # seconds from the trace's start
    end: float

    @property
    def kind(self) -> str:
        """The HLO opcode: ``fusion``, ``while``, ``custom-call``, ..."""
        rhs = self.name.partition(" = ")[2]
        if rhs.startswith("("):            # a tuple type: skip to its end
            depth = 0
            for i, ch in enumerate(rhs):
                depth += (ch == "(") - (ch == ")")
                if depth == 0:
                    rhs = rhs[i + 1:]
                    break
        else:
            rhs = rhs.partition(" ")[2]
        return rhs.strip().partition("(")[0]

    @property
    def short(self) -> str:
        """``fusion.12 fusion f32[4096]``: name, opcode and result type."""
        lhs, _, rhs = self.name.partition(" = ")
        typ = "tuple" if rhs.startswith("(") else rhs.partition(" ")[0]
        return f"{lhs.lstrip('%')} {self.kind} {typ.partition('{')[0]}"


@dataclasses.dataclass
class Trace:
    ops: dict             # {device index: [Op] sorted by start}
    spans: list           # [(name, start, end)] of the harness's spans

    @property
    def devices(self) -> list:
        return sorted(self.ops)

    def window(self, span: str = "run") -> tuple:
        """(start, end) from the first to the last span of that name."""
        sel = [(s, e) for n, s, e in self.spans if n == span]
        if not sel:
            raise ValueError(f"no {span!r} span in the trace")
        return min(s for s, _ in sel), max(e for _, e in sel)


def xplane_path(log_dir: str) -> str:
    found = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise FileNotFoundError(f"{len(found)} xplane files in {log_dir}")
    return found[0]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    ops, spans = {}, []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            ops[int(m.group(1))] = sorted(
                (Op(ev.name, ev.start_ns * 1e-9, ev.end_ns * 1e-9)
                 for line in plane.lines if line.name == OPS_LINE
                 for ev in line.events), key=lambda o: o.start)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in SPANS:
                        spans.append((ev.name, ev.start_ns * 1e-9,
                                      ev.end_ns * 1e-9))
    if not ops:
        raise ValueError(f"no TPU device plane in {path}")
    spans.sort(key=lambda s: s[1])
    return Trace(ops=ops, spans=spans)


def busy_intervals(ops: list, lo: float, hi: float) -> list:
    """Union of the op intervals, clipped to [lo, hi], as sorted pairs."""
    out = []
    for o in ops:
        s, e = max(o.start, lo), min(o.end, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def busy_seconds(ops: list, lo: float, hi: float) -> float:
    return sum(e - s for s, e in busy_intervals(ops, lo, hi))


def op_seconds(ops: list, lo: float, hi: float, match) -> float:
    """Summed device time, inside [lo, hi], of the ops ``match`` accepts."""
    return sum(max(0.0, min(o.end, hi) - max(o.start, lo))
               for o in ops if match(o))


def kernel_match(kernel: str):
    """Ops of the Pallas kernel named ``kernel``: the custom call takes the
    ``pallas_call``'s name, as in ``%stream_stats_fleet.2 = ...``."""
    pat = re.compile(rf"^%{re.escape(kernel)}(\.\d+)? = ")
    return lambda o: bool(pat.match(o.name))


def top_ops(ops: list, lo: float, hi: float, n: int = 10) -> list:
    """[[op, seconds]] of the ops that took most device time, loops and
    other containers left out."""
    acc = {}
    for o in ops:
        d = min(o.end, hi) - max(o.start, lo)
        if d > 0 and o.kind not in CONTAINERS:
            acc[o.short] = acc.get(o.short, 0.0) + d
    return [[k, v] for k, v in sorted(acc.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(tr: Trace, device: int, lo: float, hi: float,
              n: int = 10) -> list:
    """[[span, seconds]]: the longest idle stretches of a device in
    [lo, hi], each named by the harness span open when it began."""
    busy = busy_intervals(tr.ops[device], lo, hi)
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))

    def span_at(x):
        name = "outside_spans"
        for sn, s, e in tr.spans:
            if s <= x < e:
                name = sn
        return name

    gaps.sort(key=lambda g: g[0] - g[1])
    return [[span_at(s), e - s] for s, e in gaps[:n]]
