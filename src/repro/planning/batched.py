"""Batched Algorithm-1 planning for a whole fleet in one jitted pass.

The host engine (``repro.planning.engine.HostEngine``) interleaves host
numpy with several separately-dispatched jitted pieces; driving E sites
means E full round trips per window.  Here the fleet's windows are stacked
into one ``(E, k, N)`` tensor and every stage runs batched:

  * window statistics — one block-diagonal ``stream_stats`` kernel pass over
    the flattened (E·kp, N) layout (``fleet_window_moments_xxt``) of the
    mean-centred window, with the per-site dependence matrices extracted
    from the diagonal tiles and derived moments via
    ``repro.core.stats.stats_from_sums``;
  * predictor selection, compact-model fitting and the epsilon policy —
    vmapped over sites, for *every* registered model family (linear / cubic
    polynomials, mean imputation, the two-predictor multi model) through
    the same ``ModelSpec`` registry entries the host planner uses;
  * the eq.-1 program — the closed-form water-filling solver
    (``repro.core.solver.closed_form_alloc``) vmapped across sites;
  * the appendix-B exact-MSE cap — the closed-form shrink
    (``repro.core.epsilon.exact_mse_shrink``) applied inside the jitted
    pass, replacing the host path's per-stream Python ``while`` loop.

``fleet_plan`` therefore produces, per window, everything the per-site
``plan_window(cfg.solver='closed_form')`` produces — same formulas (shared
through ``make_epsilon``, ``ModelSpec.budget_net`` and ``exact_mse_shrink``
rather than re-derived), same f32 arithmetic — so its allocations match the
host loop within rounding tolerance while planning throughput scales to
hundreds of sites.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

import repro.core.planner  # noqa: F401  — populates the MODELS registry
from repro.api.registry import ENGINES, EPSILON_POLICIES, MODELS
from repro.core import epsilon as eps_mod
from repro.core import models as models_mod
from repro.core import predictor as pred_mod
from repro.core import solver as solver_mod
from repro.core import stats as stats_mod
from repro.core.types import Array, PlannerConfig
from repro.kernels.stream_stats.ops import fleet_window_moments_xxt
from repro.planning.engine import PlanEngine, UnsupportedPlanConfig


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class FleetPlan:
    """One window's plan for all E sites (all arrays lead with E).

    Shapes are per model family: single-predictor families carry
    ``predictor (E, k)`` and ``loc``/``scale (E, k)``; the multi model
    carries ``predictor (E, k, 2)`` and ``loc``/``scale (E, k, 2)``.
    """

    n_real: Array          # (E, k) i32
    n_imputed: Array       # (E, k) i32
    predictor: Array       # (E, k[, 2]) i32
    coeffs: Array          # (E, k, 4) compact-model coefficients
    loc: Array             # (E, k[, 2])
    scale: Array           # (E, k[, 2])
    explained_var: Array   # (E, k) V_i
    mean: Array            # (E, k) stats digest
    var: Array             # (E, k)
    eps: Array             # (E, k) bias tolerance used
    objective: Array       # (E,) relaxed eq.-2 value at the allocation
    r2: Array              # (E,) mean V_i / sigma_i^2 — correlation strength


@functools.partial(jax.jit, static_argnames=("dependence", "model",
                                             "epsilon_policy", "use_kernel",
                                             "interpret"))
def fleet_plan(values: Array, counts: Array, budgets: Array,
               epsilon_scale: float = 1.0, *, dependence: str = "spearman",
               model: str = "cubic", epsilon_policy: str = "k_se",
               use_kernel=None, interpret: bool = False) -> FleetPlan:
    """values (E, k, N) f32, counts (E, k) i32, budgets (E,) — one pass."""
    spec = MODELS.get(model)
    EPSILON_POLICIES.get(epsilon_policy)
    e, k, n_max = values.shape
    cf = counts.astype(values.dtype)
    mask = (jnp.arange(n_max)[None, None, :] < cf[..., None]).astype(values.dtype)
    # centre every stream on its window mean before the one-pass sums:
    # fleet streams sit many spreads away from zero, where S2/n - mean^2,
    # the S1..S4 fourth moment and XX^T/n - mean mean^T would cancel most
    # of their f32 digits (the fourth moment entirely)
    shift = jnp.sum(values * mask, axis=-1) / jnp.maximum(cf, 1.0)
    xc = (values - shift[..., None]) * mask

    mom, xxt = fleet_window_moments_xxt(xc, use_kernel=use_kernel,
                                        interpret=interpret)
    stats = stats_mod.stats_from_sums(mom, xxt, counts, shift=shift)
    if dependence == "spearman":
        ranks = jax.vmap(stats_mod.rank_transform)(values, counts)
        rmom, rxxt = fleet_window_moments_xxt(ranks * mask,
                                              use_kernel=use_kernel,
                                              interpret=interpret)
        corr = stats_mod.corr_from_sums(rmom, rxxt, counts)
    else:
        corr = stats.corr

    # --- predictor selection + compact models, vmapped over sites, through
    # the same ModelSpec registry entries plan_window resolves (§IV-A/B) ---
    if spec.multi:
        predictor = jax.vmap(pred_mod.heuristic_predictors_multi)(corr)
        fitted = jax.vmap(models_mod.fit_models_multi)(values, counts,
                                                       predictor)
        coeffs, loc, scale = (fitted["coeffs"], fitted["loc"],
                              fitted["scale"])
        explained_var = fitted["explained_var"]
    else:
        predictor = jax.vmap(pred_mod.heuristic_predictors)(corr)
        if spec.mean:
            fitted = jax.vmap(models_mod.mean_model)(values, counts,
                                                     predictor)
        else:
            degree = 1 if model == "linear" else 3
            fitted = jax.vmap(
                lambda v, c, p: models_mod.fit_models(
                    v, c, p, degree=degree, use_kernel=use_kernel,
                    interpret=interpret)
            )(values, counts, predictor)
        coeffs, loc, scale = fitted.coeffs, fitted.loc, fitted.scale
        explained_var = fitted.explained_var

    # --- epsilon policy (§IV-C), shared with the host planner ---
    eps = eps_mod.make_epsilon(epsilon_policy, stats, epsilon_scale)

    weights = 1.0 / jnp.maximum(jnp.abs(stats.mean), 1e-6)
    sigma2 = jnp.maximum(stats.var, 1e-12)
    v_exp = jnp.clip(explained_var, 0.0, sigma2 * (1.0 - 1e-9))
    q = weights**2 * sigma2
    # constraint-1f accounting shared with plan_window via the ModelSpec
    budget_net = spec.budget_net(budgets, k).astype(values.dtype)
    cost = jnp.ones_like(q)

    if spec.multi:
        nr, ns, obj = jax.vmap(
            lambda q_, c_, n_, s_, v_, e_, b_, p1, p2:
            solver_mod.closed_form_alloc(q_, c_, n_, s_, v_, e_, b_, p1, p2)
        )(q, cost, cf, sigma2, v_exp, eps, budget_net,
          predictor[..., 0], predictor[..., 1])
    else:
        nr, ns, obj = jax.vmap(solver_mod.closed_form_alloc)(
            q, cost, cf, sigma2, v_exp, eps, budget_net, predictor)

    if epsilon_policy == "exact_mse":
        # appendix-B post-hoc cap, closed form (see epsilon.exact_mse_shrink)
        nrf, nsf = nr.astype(values.dtype), ns.astype(values.dtype)
        cap = eps_mod.exact_mse_cap(stats, nrf, nsf, nrf + nsf)
        ns = eps_mod.exact_mse_shrink(nrf, nsf, sigma2, v_exp,
                                      cap).astype(ns.dtype)

    return FleetPlan(n_real=nr, n_imputed=ns, predictor=predictor,
                     coeffs=coeffs, loc=loc, scale=scale,
                     explained_var=explained_var,
                     mean=stats.mean, var=stats.var, eps=eps,
                     objective=obj, r2=jnp.mean(v_exp / sigma2, axis=-1))


class BatchedEngine(PlanEngine):
    """One jitted (E, k, N) pass; the fleet production path."""

    name = "batched"

    def check(self, cfg: PlannerConfig) -> None:
        MODELS.get(cfg.model)
        EPSILON_POLICIES.get(cfg.epsilon_policy)
        if cfg.solver != "closed_form":
            raise UnsupportedPlanConfig(
                self.name, f"solver {cfg.solver!r} is host-only; the batched "
                f"pass implements 'closed_form' (set PlannerConfig.solver="
                f"'closed_form' or engine='host')")
        if cfg.iid_mode not in ("none", "iid"):
            raise UnsupportedPlanConfig(
                self.name, f"iid_mode {cfg.iid_mode!r} is host-only "
                f"(per-stream thinning / autocovariance scans)")
        if cfg.fixed_predictors is not None:
            raise UnsupportedPlanConfig(
                self.name, "fixed_predictors is host-only")
        if cfg.cost_per_sample is not None:
            raise UnsupportedPlanConfig(
                self.name, "heterogeneous cost_per_sample is host-only")

    def plan_fleet(self, values, counts, budgets, cfg, *, window_id=0,
                   use_kernel=None, interpret=False) -> dict:
        self.check(cfg)
        plan = self._run(jnp.asarray(values, jnp.float32),
                         jnp.asarray(counts, jnp.int32),
                         jnp.asarray(budgets, jnp.float32), cfg,
                         use_kernel=use_kernel, interpret=interpret)
        return {f.name: np.asarray(getattr(plan, f.name))
                for f in dataclasses.fields(plan)}

    def _run(self, values, counts, budgets, cfg, *, use_kernel, interpret):
        return fleet_plan(values, counts, budgets, cfg.epsilon_scale,
                          dependence=cfg.dependence, model=cfg.model,
                          epsilon_policy=cfg.epsilon_policy,
                          use_kernel=use_kernel, interpret=interpret)


ENGINES.register("batched", BatchedEngine())
