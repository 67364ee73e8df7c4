"""Device time of each stage of the window step, from the trace's op names
and the compiled program's op metadata.

``make_window_step`` opens one ``jax.named_scope`` per stage (``step.plan``,
``step.sample``, ...), so the compiled program's instructions carry the
stage in their ``metadata={op_name="jit(fn)/while/body/.../step.sample/..."}``.
A trace's ``XLA Ops`` events are named by the instruction's HLO text,
without that metadata, so the stage of each op is looked up in the
compiled text of the cell's program: its own ``op_name``, or, where the
compiler made the instruction and gave it none (the sort a scatter becomes,
a copy inside a loop), the stage of the loop, conditional or call whose
body holds it.  Instruction names are unique in a module, and the program
compiled again from the same lowering names them alike.
"""
from __future__ import annotations

import re
import sys

import numpy as np

import readings
import tracefile

SCOPE = re.compile(r"(?:^|/)(step\.[a-z]+)(?:/|$)")
COMPUTATION = re.compile(r"^(?:ENTRY )?%([\w.\-]+) .*\{$")
INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = (.*)$")
CALLED = re.compile(r"\b(?:body|condition|calls|to_apply|true_computation|"
                    r"false_computation)=%([\w.\-]+)")
BRANCHES = re.compile(r"\bbranch_computations=\{([^}]*)\}")
OP_NAME = re.compile(r'\bop_name="([^"]*)"')


def hlo_scopes(text: str) -> dict:
    """{instruction name: ``step.*`` scope or None} of a compiled module's
    text, an instruction without a scope of its own taking its caller's."""
    comp, home, caller, own = None, {}, {}, {}
    for line in text.splitlines():
        m = COMPUTATION.match(line)
        if m:
            comp = m.group(1)
            continue
        m = INSTRUCTION.match(line)
        if not m or comp is None:
            continue
        name, rhs = m.groups()
        home[name] = comp
        op = OP_NAME.search(rhs)
        s = SCOPE.search(op.group(1)) if op else None
        own[name] = s.group(1) if s else None
        called = CALLED.findall(rhs)
        for b in BRANCHES.findall(rhs):
            called += re.findall(r"%([\w.\-]+)", b)
        for c in called:
            caller[c] = name

    def scope(name, seen=()):
        if own.get(name) or name in seen:
            return own.get(name)
        up = caller.get(home.get(name))
        return None if up is None else scope(up, seen + (name,))

    return {name: scope(name) for name in home}


def compiled_text(run) -> str:
    """The compiled text of the cell's program, built and lowered as the
    harness serves it (a fresh ``run`` of ``windows_per_call`` windows);
    the persistent compile cache hands back the executable that ran."""
    import run as harness
    cfg = run.cfg
    rt = harness.build_runtime(cfg, run.chips)
    shape = (int(cfg["sites"]), int(cfg["streams_per_site"]),
             int(cfg["window"]))
    windows = [np.zeros(shape, np.float32)] * run.windows_per_call
    return rt.lower(windows, run.windows_per_call).compile().as_text()


def instruction(op: tracefile.Op) -> str:
    """The HLO instruction an op event ran: ``fusion.12``."""
    return op.name.partition(" = ")[0].lstrip("%")


def scope_of(op: tracefile.Op, scopes: dict):
    return scopes.get(instruction(op))


def scope_seconds(ops: list, lo: float, hi: float, scopes: dict,
                  scope) -> float:
    """Summed device time, inside [lo, hi], of the ops of ``scope`` (None:
    the ops of no stage), loops and other containers left out."""
    return tracefile.op_seconds(
        ops, lo, hi, lambda o: (o.kind not in tracefile.CONTAINERS
                                and scope_of(o, scopes) == scope))


def by_stage(run, scopes: dict) -> dict:
    """{stage: device ms per traced fleet window} on the busiest chip, the
    ops of no stage under None; empty for a program without stages."""
    names = sorted(set(scopes.values()) - {None})
    if not names:
        return {}
    lo, hi = run.trace_window()
    ops = run.trace.ops[readings.busiest(run)]
    per = 1e3 / run.traced_windows()
    return {s: per * scope_seconds(ops, lo, hi, scopes, s)
            for s in names + [None]}


def ms_per_window(run, stage: str):
    """Device time of one stage per traced fleet window, busiest chip; None
    where the program names no such stage.  The stages are worked out once
    a run, for every reader, and logged on standard error with the busy
    time a window."""
    if run.trace is None:
        return None
    if getattr(run, "stages_ms", None) is None:
        run.stages_ms = by_stage(run, hlo_scopes(compiled_text(run)))
        if run.stages_ms:
            lo, hi = run.trace_window()
            busy = 1e3 * tracefile.busy_seconds(
                run.trace.ops[readings.busiest(run)], lo, hi) / \
                run.traced_windows()
            parts = ", ".join(f"{s or 'no stage'} {v:.3f}"
                              for s, v in run.stages_ms.items())
            print(f"bench: device ms per window by stage: {parts}; busy "
                  f"{busy:.3f}", file=sys.stderr, flush=True)
    return run.stages_ms.get(stage)
