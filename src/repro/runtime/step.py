"""The jitted window step: ingest -> plan -> sample -> impute -> serve.

One call of the function :func:`make_window_step` builds is everything the
event loop does per window — controller budgets, the batched/sharded
Algorithm-1 plan (``repro.planning``), SRS sampling, cloud-side imputation
and the aggregate queries — as a pure f32 computation suitable for
``lax.scan``.  No host round-trips: the only host work left in a run is
stacking the window pool once and reading the output tables at the end.

RNG parity (bit-for-bit with the event-loop paths):

  * E = 1 — the per-window key is ``PRNGKey(seed ^ wid)``, the exact key
    ``PlanEngine.plan_one`` hands ``samplers.draw_samples``; per-stream
    subkeys walk the same sequential ``jax.random.split`` chain and stream
    ``i`` draws ``perm = permutation(sub, N)[:n_i]`` — the identical index
    sequence, so single-edge scan runs agree with the host planner bitwise.
  * E > 1 — one keyed sort per window: a random u32 key per (site,
    stream, position) from ``fold_in(PRNGKey(seed ^ wid), 0x5A)``, and a
    stable sort along the window axis that carries the values, so each
    row's front is its sample.  The fleet runtime's
    ``sampling="device"`` mode draws through the same function
    (:func:`draw_fleet_samples`, one jitted call per window), so the event
    loop and the scan consume identical sample sets by construction
    (pinned in tests/test_scan_runtime.py).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.planning.batched import FleetPlan
from repro.runtime.controller import (CtrlParams, controller_budgets,
                                      controller_update, ordered_sum)
from repro.runtime.state import RuntimeState, StreamTotals

# per-stream model upload footprint, matching EdgePayload.wan_bytes():
# 4 B for the shipped mean (mean imputation), 40 B for the two-predictor
# dict model, CompactModel.param_bytes() == 28 B otherwise
_PER_MODEL_BYTES = {"mean": 4, "multi": 40, "single": 28}


# --------------------------------------------------------------------------
# sampling — the device replica of samplers.draw_samples
# --------------------------------------------------------------------------

def _stream_keys(base_key, k: int):
    """The sequential split chain draw_samples walks: one subkey/stream."""
    subs = []
    key = base_key
    for _ in range(k):
        key, sub = jax.random.split(key)
        subs.append(sub)
    return jnp.stack(subs)


def _site_keys(seed: int, wid, n_sites: int):
    base = jax.random.PRNGKey(
        jnp.bitwise_xor(jnp.asarray(seed, jnp.int32),
                        jnp.asarray(wid, jnp.int32)))
    if n_sites == 1:                 # plan_one uses the base key directly
        return base[None]
    return jax.vmap(lambda s: jax.random.fold_in(base, s))(
        jnp.arange(n_sites, dtype=jnp.int32))


def _keyed_sample(key, values, n_real, sample_slice=None):
    """SRS without replacement for every (site, stream) row by one keyed sort.

    One random u32 key per (site, stream, position); a stable sort along
    the window axis carries the values as its payload, so each row comes
    out in a uniformly random order (ties, ~N^2/2^33 a row, keep position
    order) and its first ``n_real`` entries are the sample.  No index
    permutation is built and nothing is gathered.

    ``sample_slice`` = ``(e_rng, e_pad, offset)`` (sharded scan runtime):
    ``values`` is the local shard of a fleet padded to ``e_pad`` sites, of
    which the first ``e_rng`` are real.  Threefry draws are NOT prefix-
    stable across shapes, so every device draws the keys at the *global
    unpadded* shape ``(e_rng, k, n)`` — the exact tensor the batched scan
    draws — zero-pads it to ``e_pad`` rows and slices its own rows at
    ``offset``.  Real rows therefore sort by bitwise the batched run's
    keys; padded rows are masked to zero by ``n_real = 0``.
    """
    e, k, n = values.shape
    if sample_slice is None:
        keys = jax.random.bits(key, (e, k, n), jnp.uint32)
    else:
        e_rng, e_pad, offset = sample_slice
        keys = jax.random.bits(key, (e_rng, k, n), jnp.uint32)
        if e_pad > e_rng:
            keys = jnp.concatenate(
                [keys, jnp.zeros((e_pad - e_rng, k, n), keys.dtype)])
        keys = jax.lax.dynamic_slice_in_dim(keys, offset, e, axis=0)
    _, shuffled = jax.lax.sort((keys, values), dimension=-1, is_stable=True,
                               num_keys=1)
    return jnp.where(jnp.arange(n)[None, None, :] < n_real[..., None],
                     shuffled, 0.0)


def sample_fleet(seed: int, wid, values, n_real, sample_slice=None):
    """SRS without replacement for every site/stream in one pass.

    values (E, k, N) f32, n_real (E, k) int -> (E, k, N) f32 where row
    ``[s, i]`` holds stream i's ``n_real[s, i]`` sampled tuples (in draw
    order) followed by zeros.  Requires full windows (counts == N), which
    the scan runtime validates at build time.

    E == 1 replicates the host planner's sampler exactly (the sequential
    ``draw_samples`` split chain and ``jax.random.permutation``), keeping
    single-edge scan runs bitwise against ``plan_one``.  Fleets sort every
    row by random keys instead (:func:`_keyed_sample`) — both the scan and
    the event loop's ``sampling="device"`` mode draw through this same
    function, so scan/event parity is preserved by construction.
    """
    e, k, n = values.shape
    iota = jnp.arange(n)
    if e == 1 and sample_slice is None:
        keys = _site_keys(seed, wid, e)
        skeys = jax.vmap(lambda b: _stream_keys(b, k))(keys)

        def one(sub, row, cnt):
            perm = jax.random.permutation(sub, n)
            return jnp.where(iota < cnt, row[perm], 0.0)

        return jax.vmap(jax.vmap(one))(skeys, values, n_real)
    base = jax.random.PRNGKey(
        jnp.bitwise_xor(jnp.asarray(seed, jnp.int32),
                        jnp.asarray(wid, jnp.int32)))
    return _keyed_sample(jax.random.fold_in(base, 0x5A), values, n_real,
                         sample_slice=sample_slice)


@functools.lru_cache(maxsize=8)
def _jitted_sampler(seed: int):
    return jax.jit(functools.partial(sample_fleet, seed))


def draw_fleet_samples(seed: int, wid: int, values: np.ndarray,
                       n_real: np.ndarray) -> np.ndarray:
    """Host entry point (FleetRuntime ``sampling="device"``): one jitted
    dispatch per window, bitwise the streams the scan runtime consumes."""
    out = _jitted_sampler(int(seed))(jnp.asarray(wid, jnp.int32),
                                     jnp.asarray(values, jnp.float32),
                                     jnp.asarray(n_real, jnp.int32))
    return np.asarray(out)


# --------------------------------------------------------------------------
# cloud-side imputation + queries, batched over (E, k)
# --------------------------------------------------------------------------

def _impute(plan: FleetPlan, samples, n_real, *, multi: bool, mean: bool):
    """(E, k, N) imputed values + the 1d-capped n_imputed, on device.

    Mirrors ``assemble_payload`` (cap at what actually shipped) +
    ``reconstruct_window`` (evaluate the compact model on the *front* of
    the predictor's real sample).
    """
    e, k, n = samples.shape
    iota = jnp.arange(n)[None, None, :]
    if multi:
        p0, p1 = plan.predictor[..., 0], plan.predictor[..., 1]
        ns = jnp.minimum(plan.n_imputed,
                         jnp.minimum(jnp.take_along_axis(n_real, p0, axis=1),
                                     jnp.take_along_axis(n_real, p1, axis=1)))
        xp = jnp.take_along_axis(samples, p0[..., None], axis=1)
        xq = jnp.take_along_axis(samples, p1[..., None], axis=1)
        u = (xp - plan.loc[..., 0:1]) / plan.scale[..., 0:1]
        v = (xq - plan.loc[..., 1:2]) / plan.scale[..., 1:2]
        c = plan.coeffs
        imp = (c[..., 0:1] + c[..., 1:2] * u + c[..., 2:3] * v
               + c[..., 3:4] * u * v)
    else:
        ns = jnp.minimum(plan.n_imputed,
                         jnp.take_along_axis(n_real, plan.predictor, axis=1))
        if mean:
            imp = jnp.broadcast_to(plan.mean[..., None], samples.shape)
        else:
            xp = jnp.take_along_axis(samples, plan.predictor[..., None],
                                     axis=1)
            u = (xp - plan.loc[..., None]) / plan.scale[..., None]
            c = plan.coeffs
            imp = (c[..., 0:1] + c[..., 1:2] * u + c[..., 2:3] * u**2
                   + c[..., 3:4] * u**3)
    mask = iota < ns[..., None]
    return jnp.where(mask, imp, 0.0), ns, mask


def _masked_queries(parts, qnames):
    """Aggregate queries over masked sample sets, numpy-NaN semantics.

    parts: list of (values (E, k, N), mask (E, k, N) bool) making up each
    stream's reconstruction (real ++ imputed).  AVG/VAR use the stable
    two-pass form; VAR is ddof=1; empty -> NaN, single sample VAR -> NaN.
    Sums over the window are :func:`ordered_sum`s, so a site's answers
    (and the controller's error signal built on AVG) do not depend on the
    fleet's size or sharding.
    """
    tot = sum(m.sum(-1) for _, m in parts).astype(jnp.float32)
    s1 = sum(ordered_sum(jnp.where(m, x, 0.0)) for x, m in parts)
    avg = jnp.where(tot > 0, s1 / jnp.maximum(tot, 1.0), jnp.nan)
    out = {}
    for q in qnames:
        if q == "AVG":
            out[q] = avg
        elif q == "VAR":
            ss = sum(ordered_sum(jnp.where(m, x - avg[..., None], 0.0) ** 2)
                     for x, m in parts)
            out[q] = jnp.where(tot > 1, ss / jnp.maximum(tot - 1.0, 1.0),
                               jnp.nan)
        elif q == "MIN":
            m_ = [jnp.where(m, x, jnp.inf).min(-1) for x, m in parts]
            best = functools.reduce(jnp.minimum, m_)
            out[q] = jnp.where(tot > 0, best, jnp.nan)
        elif q == "MAX":
            m_ = [jnp.where(m, x, -jnp.inf).max(-1) for x, m in parts]
            best = functools.reduce(jnp.maximum, m_)
            out[q] = jnp.where(tot > 0, best, jnp.nan)
        else:                        # validated away at build time
            raise ValueError(f"query {q!r} has no on-device mirror")
    return out


SCAN_QUERIES = ("AVG", "VAR", "MIN", "MAX")

# the FleetPlan fields the payload-replay path ships back to the host —
# everything assemble_payload reads (plus n_real for slicing the samples)
PAYLOAD_PLAN_FIELDS = ("n_real", "n_imputed", "predictor", "coeffs", "loc",
                       "scale", "explained_var", "mean", "var")


# --------------------------------------------------------------------------
# the step factory
# --------------------------------------------------------------------------

def make_window_step(pool, *, seed: int, plan_fn, qnames, multi: bool,
                     mean: bool, ctrl: CtrlParams,
                     static_exec_budgets: Optional[np.ndarray] = None,
                     collect: str = "estimates", adaptive=None,
                     use_kernel=None, interpret: bool = False,
                     chaos: bool = False, axis_name: Optional[str] = None,
                     sample_slice: Optional[tuple] = None):
    """Build ``step(state, xs) -> (state, outputs)`` for ``lax.scan``.

    pool: (P, E, k, N) f32 device array; window ``wid`` reads slot
    ``wid % P`` (P == T for materialized runs; a small cycled pool for
    long synthetic throughput runs).
    plan_fn: (values, counts, budgets) -> FleetPlan (batched or sharded).
    static_exec_budgets: host-computed executed budgets for static-mode
    parity with the f64 host controller (floor + >=2 clamp already done).
    adaptive: an ``AdaptiveSpec`` (with ``state.adaptive`` carrying the
    matching ``AdaptiveCarry``) gates the plan refresh behind the drift
    detector: ``lax.cond(replan, plan_fn, cached_plan)``, so reused
    windows skip the planning work entirely inside the while-loop body.
    ``use_kernel``/``interpret`` route the gate's stream_stats pass.

    chaos: when True, ``xs`` is ``(wid, live)`` — ``live`` the window's
    (E,) bool membership row — instead of a bare ``wid``, and the step
    masks dead sites end to end: zero budget (the controller water-fills
    their share over the live fleet), zero samples/bytes (the planner's
    >=1-sample floor is masked off), NaN raw estimates (which freeze the
    controller's demand EWMA exactly like the event loop's missing
    payloads), frozen ingest totals, and gap-served output estimates from
    the ``ChaosCarry`` memory.  When False the compiled graph is the
    legacy one — no mask ops are traced at all.

    axis_name / sample_slice (the sharded scan runtime,
    :mod:`repro.runtime.sharded`): the step body is being traced inside
    ``shard_map`` over a 1-D site mesh, so ``pool``/``state``/``live``
    hold only the local site shard.  ``axis_name`` routes the two
    fleet-global reductions — the water-fill sums (all-gather, then the
    single-device order) and the adaptive
    gate's deviation max (pmax) — across the mesh; everything else in the
    step is per-site and stays collective-free.  ``sample_slice``
    ``(e_rng, e_pad, offset)`` makes the sampler sort by the batched
    run's exact global keys (see :func:`_keyed_sample`).  Both
    default to None, which traces the unchanged single-device graph.

    Each stage runs under a ``jax.named_scope`` (``step.budgets``,
    ``step.gate``, ``step.plan``, ``step.sample``, ``step.impute``,
    ``step.queries``, ``step.truth``, ``step.update``): op metadata only,
    so a device trace can be split by stage and the computation is the
    same (docs/runtime.md, "Tracing a serving process").
    """
    p_, e, k, n = pool.shape
    counts = jnp.full((e, k), n, jnp.int32)
    full_mask = jnp.ones((e, k, n), bool)
    per_model = _PER_MODEL_BYTES["mean" if mean else
                                 ("multi" if multi else "single")]
    header = 8 + 2 * k
    if static_exec_budgets is not None:
        static_exec = jnp.asarray(static_exec_budgets, jnp.float32)

    def step(state: RuntimeState, xs):
        if chaos:
            wid, live = xs
            livf = live.astype(jnp.float32)
        else:
            wid, live = xs, None
        values = jax.lax.dynamic_index_in_dim(pool, jnp.mod(wid, p_),
                                              keepdims=False)
        with jax.named_scope("step.budgets"):
            raw_b = controller_budgets(state.controller, ctrl, live=live,
                                       axis_name=axis_name)
            if static_exec_budgets is not None:
                budgets = static_exec if live is None else static_exec * livf
            elif live is None:
                budgets = jnp.maximum(jnp.floor(raw_b), 2.0)
            else:
                # the >=2 clamp would resurrect dead sites' zero budgets
                budgets = jnp.where(live,
                                    jnp.maximum(jnp.floor(raw_b), 2.0), 0.0)

        if adaptive is None:
            with jax.named_scope("step.plan"):
                plan = plan_fn(values, counts, budgets)
            adaptive_carry = state.adaptive
        else:
            from repro.adaptive import AdaptiveCarry, gate_update
            with jax.named_scope("step.gate"):
                gate, replan = gate_update(adaptive, state.adaptive.gate,
                                           values, counts,
                                           use_kernel=use_kernel,
                                           interpret=interpret,
                                           axis_name=axis_name)
            with jax.named_scope("step.plan"):
                if (adaptive.detector == "always"
                        and int(adaptive.min_replan_interval) == 1):
                    # the cond is statically always-true; planning
                    # unwrapped keeps XLA's fusion of the plan reductions
                    # identical to the plan-every-window body (the bitwise
                    # parity pin)
                    plan = plan_fn(values, counts, budgets)
                else:
                    plan = jax.lax.cond(
                        replan,
                        lambda: plan_fn(values, counts, budgets),
                        lambda: state.adaptive.plan)
            adaptive_carry = AdaptiveCarry(gate=gate, plan=plan)
        if live is not None:
            # closed_form_alloc floors every stream at 1 sample even on a
            # zero budget; dead sites must truly ship nothing.  Masking
            # n_real leaves live rows' samples bitwise intact (every row
            # is sorted whatever its n_real).
            with jax.named_scope("step.plan"):
                plan = dataclasses.replace(
                    plan, n_real=plan.n_real * live[:, None].astype(
                        plan.n_real.dtype))
        with jax.named_scope("step.sample"):
            samples = sample_fleet(seed, wid, values, plan.n_real,
                                   sample_slice=sample_slice)
        with jax.named_scope("step.impute"):
            imputed, ns, mask_i = _impute(plan, samples, plan.n_real,
                                          multi=multi, mean=mean)
        with jax.named_scope("step.queries"):
            mask_r = jnp.arange(n)[None, None, :] < plan.n_real[..., None]
            est = _masked_queries([(samples, mask_r), (imputed, mask_i)],
                                  qnames)
        with jax.named_scope("step.truth"):
            tru = _masked_queries([(values, full_mask)], qnames)

        if live is None:
            served = est
            chaos_carry = state.chaos
        else:
            # gap-serving: dead rows answer from the freshest estimate
            # that ever arrived (ReorderCloudNode.serve semantics); live
            # rows refresh the memory
            with jax.named_scope("step.queries"):
                served = {q: jnp.where(live[:, None], est[q],
                                       state.chaos.served[q])
                          for q in qnames}
            from repro.chaos import ChaosCarry
            chaos_carry = ChaosCarry(live=live, served=served)

        with jax.named_scope("step.update"):
            # WAN accounting — EdgePayload.wan_bytes() per site
            nbytes = (4 * plan.n_real.sum(-1) + header
                      + per_model * (ns > 0).sum(-1)).astype(jnp.int32)
            if live is not None:
                # a dark site ships nothing, not even the header
                nbytes = jnp.where(live, nbytes, 0)

            # edge-local error proxy -> controller (FleetRuntime.run
            # semantics)
            e_avg = est.get("AVG")
            if e_avg is None:
                e_avg = _masked_queries(
                    [(samples, mask_r), (imputed, mask_i)], ("AVG",))["AVG"]
        t_avg = tru.get("AVG")
        if t_avg is None:
            with jax.named_scope("step.truth"):
                t_avg = _masked_queries([(values, full_mask)],
                                        ("AVG",))["AVG"]
        with jax.named_scope("step.update"):
            rel = jnp.abs(e_avg - t_avg) / jnp.maximum(jnp.abs(t_avg), 1e-6)
            seen = ~jnp.isnan(rel)       # nanmean over streams, fixed order
            n_seen = seen.sum(-1)
            obs_err = jnp.where(
                n_seen > 0, ordered_sum(jnp.where(seen, rel, 0.0))
                / jnp.maximum(n_seen, 1), jnp.nan)

            ctrl2 = controller_update(state.controller, ctrl, raw_b, obs_err,
                                      plan.r2, plan.objective, live=live)
            if live is None:
                totals = StreamTotals(
                    count=state.totals.count + n,
                    s1=state.totals.s1 + values.sum(-1),
                    s2=state.totals.s2 + (values * values).sum(-1))
            else:                    # dead sites ingest nothing
                lcol = livf[:, None]
                totals = StreamTotals(
                    count=state.totals.count + n * lcol,
                    s1=state.totals.s1 + values.sum(-1) * lcol,
                    s2=state.totals.s2 + (values * values).sum(-1) * lcol)
        new_state = RuntimeState(window_id=wid + 1, controller=ctrl2,
                                 totals=totals, adaptive=adaptive_carry,
                                 chaos=chaos_carry)

        out = {"est": served, "tru": tru, "bytes": nbytes,
               "budgets": budgets, "obs_err": obs_err, "r2": plan.r2,
               "objective": plan.objective}
        if live is not None:
            out["live"] = live
        if collect == "payloads":
            out["samples"] = samples
            for f in PAYLOAD_PLAN_FIELDS:
                out[f] = getattr(plan, f)
        return new_state, out

    return step
