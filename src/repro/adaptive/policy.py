"""Re-plan policy: cache the last plan, reuse it until drift fires.

Glue between the EW estimator (:mod:`repro.adaptive.stats`) and the
detectors (:mod:`repro.adaptive.drift`):

* :class:`AdaptiveSpec` — the scenario-level knob block.  Registry-
  validated at construction, JSON-round-trippable, and hashable so jitted
  code can close over it statically.
* :class:`GateState` — everything the policy carries between windows:
  the EW sums, the correlation snapshot the cached plan assumed, detector
  scalars, the cooldown clock, and the replans/reuses/fires/lag counters
  that surface in ``RunReport``.
* :func:`gate_update` — ONE pure-jnp step shared by both runtimes.  The
  event loop wraps it in ``jax.jit`` (via :class:`AdaptivePolicy`) and the
  ``lax.scan`` runtime inlines it into the window step, so a fire decision
  can never diverge between the semantics oracle and the compiled path.

Decision rule per window (after folding the window into the EW sums)::

    dev    = max off-diagonal |ew_corr - assumed_corr|   over all E sites
    fire   = detector(dev)  AND  at least one plan exists already
    cool   = windows_since_replan + 1 >= min_replan_interval
    replan = first_window  OR  (fire AND cool)

``min_replan_interval=1`` therefore allows a re-plan every window, which
is exactly how the ``always`` detector reproduces the legacy
plan-every-window runtimes (pinned bit-for-bit for the event loop in
tests/test_adaptive.py).  The first window always plans — there is
nothing to reuse — and never counts as a drift fire.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.adaptive import drift as drift_mod
from repro.adaptive import stats as ew_mod
from repro.api.registry import DRIFT_DETECTORS
from repro.core.types import Array
from repro.parallel.sharding import exchange_pmax


@dataclasses.dataclass(frozen=True)
class AdaptiveSpec:
    """Adaptive re-planning knobs (``ScenarioConfig.adaptive``).

    Absence of this block (``adaptive=None``) is the legacy
    plan-every-window behaviour, bit-for-bit.  Fields beyond ``detector``
    only matter to the detectors that read them.
    """

    detector: str = "threshold"          # DRIFT_DETECTORS name
    halflife: Optional[float] = 8.0      # EW halflife in windows; None = no decay
    threshold: float = 0.1               # max |corr dev| bound ('threshold')
    ph_delta: float = 0.01               # drift allowance ('page_hinkley')
    ph_lambda: float = 0.25              # evidence bound ('page_hinkley')
    min_replan_interval: int = 1         # cooldown: windows between re-plans

    def __post_init__(self):
        DRIFT_DETECTORS.get(self.detector)      # fail fast with alternatives
        if self.halflife is not None and not float(self.halflife) > 0.0:
            raise ValueError(f"halflife must be > 0 or None, "
                             f"got {self.halflife!r}")
        if not self.threshold > 0.0:
            raise ValueError(f"threshold must be > 0, got {self.threshold!r}")
        if self.ph_delta < 0.0:
            raise ValueError(f"ph_delta must be >= 0, got {self.ph_delta!r}")
        if not self.ph_lambda > 0.0:
            raise ValueError(f"ph_lambda must be > 0, got {self.ph_lambda!r}")
        if int(self.min_replan_interval) < 1:
            raise ValueError(f"min_replan_interval must be >= 1, "
                             f"got {self.min_replan_interval!r}")

    # ------------------------------------------------------- serialization
    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: dict) -> "AdaptiveSpec":
        known = {f.name for f in dataclasses.fields(cls)}
        extra = set(d) - known
        if extra:
            raise ValueError(f"unknown AdaptiveSpec fields: {sorted(extra)}")
        return cls(**d)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class GateState:
    """Per-run adaptive carry (everything but the cached plan itself)."""

    ew: ew_mod.EWStats         # decayed stream sums, all E sites
    assumed_corr: Array        # (E, k, k) f32 corr snapshot behind the plan
    det_accum: Array           # () f32 detector accumulator
    det_age: Array             # () i32 detector elevated-age
    windows_since: Array       # () i32 windows since the last re-plan
    replans: Array             # () i32 planner invocations
    reuses: Array              # () i32 windows served from the cached plan
    fires: Array               # () i32 detector fires (post-cooldown or not)
    lag_sum: Array             # () i32 summed detection lag over fires
    lag_events: Array          # () i32 fires with a measurable lag


def gate_init(n_sites: int, k: int) -> GateState:
    # distinct buffers per field (donated-carry runs refuse aliasing)
    i0 = lambda: jnp.zeros((), jnp.int32)     # noqa: E731
    return GateState(ew=ew_mod.ew_init(n_sites, k),
                     assumed_corr=jnp.zeros((n_sites, k, k), jnp.float32),
                     det_accum=jnp.zeros((), jnp.float32),
                     det_age=i0(), windows_since=i0(), replans=i0(),
                     reuses=i0(), fires=i0(), lag_sum=i0(), lag_events=i0())


def gate_update(spec: AdaptiveSpec, gate: GateState, values: Array,
                counts: Array, *, use_kernel=None, interpret: bool = False,
                axis_name: Optional[str] = None
                ) -> Tuple[GateState, Array]:
    """One window of the re-plan policy; returns ``(gate', replan () bool)``.

    Pure jnp — both runtimes call exactly this function so the fire/replan
    decision is shared, not re-implemented.  The caller is responsible for
    actually producing a plan when ``replan`` is true and snapshotting it.
    """
    ew = ew_mod.ew_update(gate.ew, values, counts,
                          ew_mod.ew_decay(spec.halflife),
                          use_kernel=use_kernel, interpret=interpret)
    corr = ew_mod.ew_corr(ew)
    k = corr.shape[-1]
    off = ~jnp.eye(k, dtype=bool)
    dev = jnp.max(jnp.abs(corr - gate.assumed_corr) * off).astype(jnp.float32)
    if axis_name is not None:
        # sharded scan: close the max over the site mesh.  Max is exact
        # under reassociation, so the fire/replan decision (and every
        # replicated detector scalar downstream) is bitwise the
        # single-device gate's; padded sites (zero values, zero assumed
        # corr) contribute dev = 0.
        dev = exchange_pmax(dev, axis_name)

    det_state, fire, lag = drift_mod.detector_update(
        spec.detector, {"accum": gate.det_accum, "age": gate.det_age},
        dev, spec)
    first = gate.replans < 1
    fire = fire & ~first        # no plan yet -> nothing to be stale
    cool = (gate.windows_since + 1) >= int(spec.min_replan_interval)
    replan = first | (fire & cool)

    fired = fire.astype(jnp.int32)
    lagged = (lag > 0).astype(jnp.int32)
    return GateState(
        ew=ew,
        assumed_corr=jnp.where(replan, corr, gate.assumed_corr),
        det_accum=jnp.where(replan, 0.0,
                            det_state["accum"]).astype(jnp.float32),
        det_age=jnp.where(replan, 0, det_state["age"]).astype(jnp.int32),
        windows_since=jnp.where(replan, 0,
                                gate.windows_since + 1).astype(jnp.int32),
        replans=gate.replans + replan.astype(jnp.int32),
        reuses=gate.reuses + (~replan).astype(jnp.int32),
        fires=gate.fires + fired,
        lag_sum=gate.lag_sum + lag,
        lag_events=gate.lag_events + lagged,
    ), replan


def gate_counters(gate: GateState) -> dict:
    """Host-side report fields from a (possibly device-resident) gate."""
    lag_events = int(gate.lag_events)
    return {
        "planner_invocations": int(gate.replans),
        "plans_reused": int(gate.reuses),
        "drift_fires": int(gate.fires),
        "detection_lag_windows": (float(int(gate.lag_sum)) / lag_events
                                  if lag_events else 0.0),
    }


# --------------------------------------------------------------- scan carry

@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class AdaptiveCarry:
    """Scan-runtime carry: the gate plus the cached plan pytree.

    ``plan`` is whatever the plan function returns (a ``FleetPlan``); kept
    generic so this module never imports the planning layer.
    """

    gate: GateState
    plan: Any


def make_adaptive_carry(n_sites: int, k: int, plan_like) -> AdaptiveCarry:
    """Initial carry with a zero-filled plan of exactly ``plan_like``'s
    structure/shapes/dtypes (built from ``jax.eval_shape`` output so the
    ``lax.cond`` branches agree before the first real plan exists)."""
    zero_plan = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), plan_like)
    return AdaptiveCarry(gate=gate_init(n_sites, k), plan=zero_plan)


# ---------------------------------------------------------- host-side policy

class AdaptivePolicy:
    """Event-loop wrapper around :func:`gate_update` with plan caching.

    The gate step runs jitted on device (identical math to the scan
    runtime); the plan cache and the planner callback stay on the host so
    the event loop's RNG/ordering semantics are untouched on re-plan
    windows — an ``always`` detector replays the legacy runtime's exact
    call sequence.
    """

    def __init__(self, spec: AdaptiveSpec, *, use_kernel=None,
                 interpret: bool = False):
        self.spec = spec
        self._step = jax.jit(functools.partial(
            gate_update, spec, use_kernel=use_kernel, interpret=interpret))
        self._gate: Optional[GateState] = None
        self._cached = None

    def step(self, values: Array, counts: Array, plan_cb):
        """Advance one window; call ``plan_cb()`` only when re-planning.

        Returns ``(plan, replanned bool)`` where ``plan`` is the fresh
        result or the cached one.
        """
        if self._gate is None:
            e, k = values.shape[0], values.shape[1]
            self._gate = gate_init(e, k)
        self._gate, replan = self._step(self._gate, jnp.asarray(values),
                                        jnp.asarray(counts))
        if bool(replan) or self._cached is None:
            self._cached = plan_cb()
        return self._cached, bool(replan)

    def counters(self) -> dict:
        if self._gate is None:
            return {"planner_invocations": 0, "plans_reused": 0,
                    "drift_fires": 0, "detection_lag_windows": 0.0}
        return gate_counters(self._gate)
