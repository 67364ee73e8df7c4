"""Device time of the cross-chip exchange per fleet window, busiest chip:
the collectives of the site mesh's water-fill, which run under the
``exchange`` scope (``repro.parallel.sharding``), from the trace and the
compiled program's text.

An op is the exchange's where its instruction's ``op_name`` lies under
``exchange``, or where it is a collective with no ``op_name`` at all: the
TPU compiler rewrites a tiled all-gather whose shard it cannot tile as a
dynamic-update-slice and an all-reduce, and that all-reduce keeps no
metadata.  A program without the scope reads None.  The compiled text is
made once a run, and ``scopes.ms_per_window`` logs the per-stage split
from the same text."""
import re

import readings
import scopes
import tracefile

EXCHANGE = re.compile(r"(?:^|/)exchange(?:/|$)")
COLLECTIVES = ("all-gather", "all-reduce", "all-to-all",
               "collective-permute", "reduce-scatter")


def is_collective(kind: str) -> bool:
    return kind.removesuffix("-start").removesuffix("-done") in COLLECTIVES


def exchange_instructions(text: str) -> set:
    """Names of a compiled module's instructions that belong to the
    exchange; empty where no instruction lies under the scope."""
    scoped, bare = set(), set()
    for line in text.splitlines():
        m = scopes.INSTRUCTION.match(line)
        if not m:
            continue
        name, rhs = m.groups()
        op = scopes.OP_NAME.search(rhs)
        if op is not None:
            if EXCHANGE.search(op.group(1)):
                scoped.add(name)
        elif is_collective(tracefile.Op(f"%{name} = {rhs}", 0, 0).kind):
            bare.add(name)
    return scoped | bare if scoped else set()


def read(run):
    if run.trace is None:
        return None
    text = scopes.compiled_text(run)
    compile_text = scopes.compiled_text
    scopes.compiled_text = lambda _run: text     # the same program, once
    try:
        scopes.ms_per_window(run, "step.budgets")
    finally:
        scopes.compiled_text = compile_text
    names = exchange_instructions(text)
    if not names:
        return None
    lo, hi = run.trace_window()
    seconds = tracefile.op_seconds(
        run.trace.ops[readings.busiest(run)], lo, hi,
        lambda o: scopes.instruction(o) in names)
    return 1e3 * seconds / run.traced_windows()
