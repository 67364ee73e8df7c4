"""On-device (f32, jit-able) mirror of the fleet budget controller.

``repro.fleet.controller.BudgetController`` is host numpy (f64) and mutates
itself between windows — exactly the per-window host round-trip the scan
runtime eliminates.  This module re-states the same math as pure functions
over :class:`~repro.runtime.state.ControllerState` so the budgets() /
update() cycle runs inside the jitted window step:

  * :func:`water_fill` — the clip-and-redistribute allocator, with the
    host version's early ``break`` expressed as a ``where`` guard (once the
    excess is inside tolerance every further iteration is the identity).
  * :func:`controller_budgets` / :func:`controller_update` — the
    budgets()/update() pair, including the demand-signal variants from the
    ``DEMAND_SIGNALS`` registry ("obs_err" | "pred_err" | "max_err") as
    static routing, cost-aware demand discounting and the first-observation
    EWMA seeding.

Same formulas, f32 instead of f64: a scan run and a steps run agree
bit-for-bit (both use this code); agreement with the host controller is
within float tolerance (pinned in tests/test_scan_runtime.py).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.parallel.sharding import exchange_all_gather, exchange_pmax
from repro.runtime.state import ControllerState


@dataclasses.dataclass(frozen=True)
class CtrlParams:
    """Static controller configuration baked into the compiled step."""

    total_budget: float
    n_sites: int
    mode: str = "rebalance"          # "rebalance" | "static"
    floor_mult: float = 0.3
    ceil_mult: float = 3.0
    ewma: float = 0.5
    demand_signal: str = "obs_err"   # DEMAND_SIGNALS name, routed statically
    cost_discount: Optional[tuple] = None   # sqrt-normalized link cost, or None

    @property
    def equal_share(self) -> float:
        return self.total_budget / self.n_sites

    @staticmethod
    def make_cost_discount(link_cost) -> tuple:
        """Host-side mirror of the cost-aware discount normalization."""
        c = np.asarray(link_cost, np.float64)
        c = np.maximum(c / max(float(c.mean()), 1e-12), 1e-6)
        return tuple(np.sqrt(c).tolist())


def ordered_sum(x):
    """Sum over the last axis in one fixed order: a pairwise tree over the
    axis zero-padded to a power of two, written as elementwise adds.

    ``jnp.sum`` leaves the association to XLA, which picks it per program:
    the same rows summed inside an E=1024 and an E=4096 program can differ
    in the last bit on a TPU.  Where such a bit reaches a ``floor`` (the
    controller's budgets), per-site results would depend on the fleet's
    shape and on how it is sharded; elementwise adds of fixed slices
    cannot be re-associated, so this sum is the same in every program.
    """
    n = x.shape[-1]
    p = 1 << max(n - 1, 0).bit_length()
    if p != n:
        x = jnp.concatenate(
            [x, jnp.zeros(x.shape[:-1] + (p - n,), x.dtype)], axis=-1)
    while x.shape[-1] > 1:
        x = x[..., 0::2] + x[..., 1::2]
    return x[..., 0]


def water_fill(demand, total: float, lo, hi, iters: int = 8,
               axis_name: Optional[str] = None):
    """jnp mirror of ``repro.fleet.controller.water_fill`` (unrolled).

    Every fleet sum is an :func:`ordered_sum`.  ``axis_name`` (sharded
    scan runtime): the arrays are the local site shard and every reduction
    becomes global over the mesh axis — the only cross-device traffic in
    the whole window step.  Each device gathers the whole (E,) vector and
    sums it in the single-device program's order, so sharded budgets are
    bitwise those of one device.  (A ``psum`` of per-device partial sums
    re-associates the fleet sum; on four TPU chips that moved budgets by
    an ULP and flipped ``floor(budget)`` for some sites.)  The
    collectives run under the ``exchange`` scope and are counted
    (:func:`~repro.parallel.sharding.count_exchange`).
    """
    if axis_name is None:
        gsum = ordered_sum
        def gany(x):                            # noqa: E306
            return jnp.any(x)
    else:
        def gsum(x):
            return ordered_sum(exchange_all_gather(x, axis_name))

        def gany(x):
            return exchange_pmax(jnp.any(x).astype(jnp.int32),
                                 axis_name) > 0
    d = jnp.where(jnp.isfinite(demand), demand, 0.0)
    # no usable signal (all zero/non-finite, e.g. every site dark):
    # uniform in the box instead of NaN-poisoning the carry
    d = jnp.where(gany(d > 0), d, jnp.ones_like(d))
    d = jnp.maximum(d, 1e-12)
    b = jnp.clip(total * d / gsum(d), lo, hi)
    for _ in range(iters):
        excess = total - gsum(b)
        movable = jnp.where(excess > 0, b < hi, b > lo)
        w = d * movable
        wsum = gsum(w)
        moved = jnp.clip(b + excess * w / jnp.where(wsum > 0, wsum, 1.0),
                         lo, hi)
        # host loop breaks on tiny excess / nothing movable; here those
        # iterations simply keep b unchanged
        b = jnp.where((jnp.abs(excess) >= 1e-9) & (wsum > 0), moved, b)
    return b


def controller_budgets(state: ControllerState, p: CtrlParams, live=None,
                       axis_name: Optional[str] = None):
    """(E,) raw per-window budgets — ``BudgetController.budgets(live=)``.

    ``live`` is a traced (E,) bool membership mask (chaos runs): dead
    sites' floor/ceiling/demand collapse to 0 so the water-fill
    redistributes their share over the live fleet.  ``None`` (static
    Python, decided at trace time) compiles the legacy mask-free graph —
    chaos-off scenarios keep their exact XLA program.

    ``axis_name`` (sharded scan runtime): ``state``/``live`` hold the local
    site shard — shapes come from the state, not ``p.n_sites`` (which stays
    the *global* count so ``equal_share`` and the water-fill total keep
    fleet-wide semantics) — and the water-fill sums over the whole mesh.
    """
    eq = p.equal_share
    e = state.demand.shape[0]        # local shard size under shard_map
    hi = jnp.full((e,), p.ceil_mult * eq, jnp.float32)
    static_b = jnp.minimum(jnp.full((e,), eq, jnp.float32), hi)
    if live is not None:
        livf = live.astype(jnp.float32)
        hi = hi * livf
        static_b = static_b * livf
    if p.mode == "static":
        return static_b
    lo = jnp.minimum(jnp.full((e,), p.floor_mult * eq, jnp.float32), hi)
    demand = state.demand
    if live is not None:
        demand = demand * livf
    if p.cost_discount is not None:
        demand = demand / jnp.asarray(p.cost_discount, jnp.float32)
    reb = water_fill(demand, p.total_budget, lo, hi, axis_name=axis_name)
    if live is not None:
        # all-dead window: the uniform fallback inside water_fill fills a
        # degenerate [0, 0] box, but keep the contract explicit — ship 0
        reb = reb * livf
    return jnp.where(state.seen, reb, static_b)


def _signal(name: str, obs, pred):
    # static routing over the DEMAND_SIGNALS entries (scan supports the
    # registry's stateless trio; anything else is rejected at build time)
    if name == "obs_err":
        return jnp.where(jnp.isfinite(obs) & (obs > 0), obs, pred)
    if name == "pred_err":
        return pred
    if name == "max_err":
        return jnp.maximum(jnp.where(jnp.isfinite(obs), obs, 0.0), pred)
    raise ValueError(f"demand signal {name!r} has no on-device mirror")


def controller_update(state: ControllerState, p: CtrlParams, raw_budgets,
                      obs_err, r2, objective,
                      arrival_lag=None, live=None) -> ControllerState:
    """``BudgetController.update`` with ``last_budgets = raw_budgets``.

    ``live`` (traced (E,) bool, or static None): dead sites' demand/r2
    EWMAs hold their pre-outage value, so a rejoining site resumes from
    its last known demand instead of the nan->1.0 default.
    """
    a = p.ewma
    if arrival_lag is None:          # zero-latency scan: every lag obs is 0
        lag_obs = jnp.zeros_like(state.lag)
    else:
        lag_obs = arrival_lag
    ok = jnp.isfinite(lag_obs)
    mixed = jnp.where(state.lag_seen,
                      (1 - a) * state.lag + a * jnp.where(ok, lag_obs, 0.0),
                      jnp.where(ok, lag_obs, 0.0))
    lag = jnp.where(ok, mixed, state.lag)
    lag_seen = state.lag_seen | ok

    b = jnp.maximum(raw_budgets, 1.0)
    pred_err = jnp.sqrt(jnp.maximum(objective, 0.0))
    err = jnp.nan_to_num(_signal(p.demand_signal, obs_err, pred_err),
                         nan=1.0)
    demand_new = jnp.sqrt(jnp.maximum(err, 1e-9) * b)
    r2_new = jnp.clip(jnp.nan_to_num(r2), 0.0, 1.0)
    demand = jnp.where(state.seen,
                       (1 - a) * state.demand + a * demand_new, demand_new)
    r2_mix = jnp.where(state.seen, (1 - a) * state.r2 + a * r2_new, r2_new)
    if live is not None:             # dead sites: hold pre-outage EWMAs
        demand = jnp.where(live, demand, state.demand)
        r2_mix = jnp.where(live, r2_mix, state.r2)
    return ControllerState(demand=demand, r2=r2_mix, lag=lag,
                           lag_seen=lag_seen, seen=jnp.asarray(True),
                           last_budgets=raw_budgets)
