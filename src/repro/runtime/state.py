"""Carry pytrees for the scan streaming runtime.

One :class:`RuntimeState` travels through ``lax.scan`` across windows; it
is the *entire* mutable state of the streaming system, so a window step is
a pure function ``(state, window_id) -> (state, outputs)`` and the whole
run compiles to one XLA while-loop with donated carry buffers:

  * ``controller`` — the on-device mirror of the fleet budget controller's
    EWMAs (:mod:`repro.fleet.controller`): demand, correlation strength,
    arrival-lag telemetry, the previous raw budgets and the seen flags.
  * ``totals`` — running per-site/per-stream moment sums (count, sum,
    sum-of-squares) over everything ingested, the ``stream_stats``
    long-horizon digest surfaced as end-of-run diagnostics.
  * ``window_id`` — the RNG cursor: sampler keys are derived per window as
    ``PRNGKey(seed ^ wid)`` (+ ``fold_in(site)`` for fleets), exactly the
    streams the event-loop path consumes, so parity needs no key state
    beyond the window counter itself.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import numpy as np

from repro.core.types import Array


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class ControllerState:
    """Device mirror of ``BudgetController``'s mutable fields (f32)."""

    demand: Array        # (E,) EWMA sqrt(err * budget)
    r2: Array            # (E,) EWMA explained-variance fraction
    lag: Array           # (E,) EWMA WAN arrival lag (ms); 0 at zero latency
    lag_seen: Array      # (E,) bool — per-site lag EWMA seeded
    seen: Array          # () bool — any observation yet
    last_budgets: Array  # (E,) raw (un-floored) budgets of the last window


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class StreamTotals:
    """Running per-stream moment sums across every ingested window."""

    count: Array         # (E, k) f32 tuples seen
    s1: Array            # (E, k) f32 running sum
    s2: Array            # (E, k) f32 running sum of squares


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class RuntimeState:
    """Everything the streaming engine carries window to window."""

    window_id: Array     # () i32 — next window to ingest (RNG cursor)
    controller: ControllerState
    totals: StreamTotals
    # adaptive re-planning carry (repro.adaptive.AdaptiveCarry: the EW gate
    # + the cached FleetPlan) — None when the scenario plans every window.
    # As a pytree, None is an empty subtree, so legacy states/checkpoints
    # flatten to the same leaves as before this field existed.
    adaptive: Optional[Any] = None
    # chaos carry (repro.chaos.ChaosCarry: last liveness mask + the
    # gap-serving estimate memory) — None outside chaos runs, same
    # empty-subtree contract as ``adaptive``.
    chaos: Optional[Any] = None


def init_state(n_sites: int, k: int, equal_share: float) -> RuntimeState:
    """Fresh state matching ``BudgetController.__post_init__`` semantics.

    Host (numpy) leaves: the runtime places them itself — on one device,
    or shard by shard across the site mesh."""
    e = n_sites
    return RuntimeState(
        window_id=np.asarray(0, np.int32),
        controller=ControllerState(
            demand=np.ones((e,), np.float32),
            r2=np.zeros((e,), np.float32),
            lag=np.zeros((e,), np.float32),
            lag_seen=np.zeros((e,), bool),
            seen=np.asarray(False),
            last_budgets=np.full((e,), equal_share, np.float32)),
        totals=StreamTotals(
            count=np.zeros((e, k), np.float32),
            s1=np.zeros((e, k), np.float32),
            s2=np.zeros((e, k), np.float32)))
