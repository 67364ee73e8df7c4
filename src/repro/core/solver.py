"""The eq.-1 sample-allocation program (§III-B) and its solvers.

Variables (paper notation): n = (n_r, n_s) in R^{2k}_{>=0}.

    minimize    f(n) = sum_i w_i^2 sigma_i^2 / (n_{r,i} + n_{s,i})          (eq. 2)
    subject to  0 <= n_{r,i} <= N_i                                         (1c)
                0 <= n_{s,i} <= n_{r,p_i}                                   (1d)
                n_{r,i} + n_{s,i} >= 1 + delta                              (1e)
                sum_i c_i(n_{r,i}, n_{s,i}) <= C                            (1f)
                n_{s,i} sigma_i^2 - (n_{s,i}-1) V_i <= (n_{r,i}+n_{s,i}-1) eps_i
                                                                    (1g -> eq. 11)

With p fixed the problem is convex (paper Theorem, §III-B3): the objective
Hessian is sum_i psi_i (z_i + z_{i+k})^2 >= 0 and every constraint is affine.

Three solvers behind one interface:
  * ``solve_ipm``   — jit-compiled log-barrier interior-point Newton method in
    pure JAX (runs on-accelerator; the single-edge production path).
  * ``solve_slsqp`` — scipy SLSQP, the solver the paper used (§V-E); kept as
    the faithfulness/parity oracle for tests.
  * ``solve_closed_form`` — one-shot water-filling KKT solution of a
    relaxation (see :func:`closed_form_alloc`); fully elementwise, so it
    vmaps across edge sites — the fleet batched-planning hot path.

Feasibility notes (documented deviations):
  * eq. 11 at n_s = 0 degenerates to  V_i <= (n_{r,i}-1) eps_i  — an artifact
    of the (n_s - 1) bookkeeping in eq. 5.  When the user's eps_i makes even
    n_s = 0 infeasible we *restore* eps_i to the smallest feasible value and
    flag it (``eps_used``), matching what a deployed system must do.
  * The model-upload cost is charged as a constant per imputing stream outside
    the program (an indicator term would break convexity); C passed here is
    already net of that overhead.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.api.registry import SOLVERS
from repro.core.types import Allocation, Array

_DELTA = 1e-2          # strict margin for constraint 1e
_RIDGE = 1e-9


@dataclasses.dataclass(frozen=True)
class ProblemData:
    """Numeric inputs of one eq.-1 instance (host-side, numpy)."""

    n_obs: np.ndarray          # (k,) N_i
    sigma2: np.ndarray         # (k,) unbiased window variance (bias constraint)
    sigma2_obj: np.ndarray     # (k,) objective variance (m-dependence adjusted)
    explained_var: np.ndarray  # (k,) V_i = Var[E[X_i|X_p]]
    weights: np.ndarray        # (k,) w_i
    predictor: np.ndarray      # (k,) p_i
    eps: np.ndarray            # (k,) bias tolerance
    cost_real: np.ndarray      # (k,) cost per real sample
    budget: float              # C
    predictor2: Optional[np.ndarray] = None  # (k,) second predictor (§V-G)

    @property
    def k(self) -> int:
        return int(self.n_obs.shape[0])


def build_problem(stats, model, eps, budget, weights=None, cost_real=None,
                  sigma2_obj=None) -> ProblemData:
    n_obs = np.asarray(stats.count, np.float64)
    sigma2 = np.maximum(np.asarray(stats.var, np.float64), 1e-12)
    ev = model["explained_var"] if isinstance(model, dict) else model.explained_var
    pred = model["predictor"] if isinstance(model, dict) else model.predictor
    V = np.asarray(ev, np.float64)
    V = np.clip(V, 0.0, sigma2 * (1.0 - 1e-9))
    k = n_obs.shape[0]
    if weights is None:
        mu = np.asarray(stats.mean, np.float64)
        weights = 1.0 / np.maximum(np.abs(mu), 1e-6)   # footnote 3: CoV weights
    if cost_real is None:
        cost_real = np.ones((k,))
    if sigma2_obj is None:
        sigma2_obj = sigma2
    pred = np.asarray(pred, np.int64)
    pred2 = None
    if pred.ndim == 2:                 # multi-predictor model (§V-G)
        pred, pred2 = pred[:, 0], pred[:, 1]
    return ProblemData(n_obs=n_obs, sigma2=sigma2,
                       sigma2_obj=np.maximum(np.asarray(sigma2_obj, np.float64), 1e-12),
                       explained_var=V,
                       weights=np.asarray(weights, np.float64),
                       predictor=pred, predictor2=pred2,
                       eps=np.asarray(eps, np.float64),
                       cost_real=np.asarray(cost_real, np.float64),
                       budget=float(budget))


# --------------------------------------------------------------------------
# constraint assembly:  A n <= b,  n = (n_r, n_s)
# --------------------------------------------------------------------------

def assemble_constraints(p: ProblemData, eps: np.ndarray):
    k = p.k
    rows, rhs = [], []
    eye = np.eye(k)

    # 1c upper:  n_r <= N
    rows.append(np.hstack([eye, np.zeros((k, k))])); rhs.append(p.n_obs)
    # nonneg:   -n_r <= 0, -n_s <= 0
    rows.append(np.hstack([-eye, np.zeros((k, k))])); rhs.append(np.zeros(k))
    rows.append(np.hstack([np.zeros((k, k)), -eye])); rhs.append(np.zeros(k))
    # 1d:  n_s,i - n_r,p_i <= 0   (and <= n_r of every extra predictor)
    P = np.zeros((k, k))
    P[np.arange(k), p.predictor] = -1.0
    rows.append(np.hstack([P, eye])); rhs.append(np.zeros(k))
    if p.predictor2 is not None:
        P2 = np.zeros((k, k))
        P2[np.arange(k), p.predictor2] = -1.0
        rows.append(np.hstack([P2, eye])); rhs.append(np.zeros(k))
    # 1e:  -(n_r + n_s) <= -(1 + delta)
    rows.append(np.hstack([-eye, -eye])); rhs.append(-np.full(k, 1.0 + _DELTA))
    # 1f:  c^T n_r <= C    (imputation is free on the wire)
    rows.append(np.hstack([p.cost_real[None, :], np.zeros((1, k))]))
    rhs.append(np.array([p.budget]))
    # 1g (eq. 11):  (sigma2 - V - eps) n_s - eps n_r <= -(V + eps)... careful:
    #   n_s sigma2 - (n_s-1)V - (n_r+n_s-1) eps <= 0
    #   => n_s (sigma2 - V - eps) - eps n_r <= -V - eps  ... RHS: -(V) - eps? expand:
    #   n_s sigma2 - n_s V + V - eps n_r - eps n_s + eps <= 0
    bias_r = -np.diag(eps)
    bias_s = np.diag(p.sigma2 - p.explained_var - eps)
    rows.append(np.hstack([bias_r, bias_s]))
    rhs.append(-(p.explained_var + eps))

    A = np.vstack(rows)
    b = np.concatenate(rhs)
    return A, b


def feasible_start(p: ProblemData):
    """Strictly feasible (n0, eps_used). Restores eps where eq. 11 admits no
    solution even at n_s = 0 (see module docstring)."""
    k = p.k
    prop = p.n_obs / max(p.n_obs.sum(), 1.0)
    nr = 0.9 * p.budget * prop / np.maximum(p.cost_real, 1e-9)
    nr = np.clip(nr, 1.0 + _DELTA + 1e-3, 0.98 * np.maximum(p.n_obs, 1.2))
    # rescale down if cost still exceeds 0.95 C (can happen after the lower clip)
    cost = float(p.cost_real @ nr)
    if cost > 0.95 * p.budget:
        scale = 0.95 * p.budget / cost
        nr = np.maximum(nr * scale, 1.0 + _DELTA + 1e-3)

    eps = p.eps.copy()
    # eq.-11 feasibility at n_s -> 0 requires eps >= V / (n_r - 1)
    min_eps = p.explained_var / np.maximum(nr - 1.0, 1e-3)
    restored = eps < min_eps * 1.05
    eps = np.where(restored, min_eps * 1.10 + 1e-12, eps)

    # headroom for n_s under eq. 11 at this n_r
    slope = p.sigma2 - p.explained_var - eps
    cap = np.where(slope > 0,
                   ((nr - 1.0) * eps - p.explained_var) / np.maximum(slope, 1e-12),
                   np.inf)
    nr_pred = nr[p.predictor]
    if p.predictor2 is not None:
        nr_pred = np.minimum(nr_pred, nr[p.predictor2])
    ns = np.minimum(0.25 * np.maximum(cap, 0.0), 0.5 * nr_pred)
    ns = np.clip(ns, 1e-3, None)
    # keep strict: shrink ns if the bias row is tight
    lhs = ns * p.sigma2 - (ns - 1.0) * p.explained_var
    rhs = (nr + ns - 1.0) * eps
    bad = lhs >= rhs
    ns = np.where(bad, 1e-3, ns)
    n0 = np.concatenate([nr, ns])
    return n0, eps, bool(restored.any())


# --------------------------------------------------------------------------
# JAX interior-point solver
# --------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("outer_iters", "inner_iters"))
def _ipm(q: Array, A: Array, b: Array, n0: Array,
         outer_iters: int = 12, inner_iters: int = 40,
         mu: float = 12.0, tau0: float = 1.0):
    """Log-barrier Newton.  q = w^2 sigma2_obj per stream; f = sum q/total."""
    m = A.shape[0]
    two_k = A.shape[1]
    k = two_k // 2

    def totals(n):
        return n[:k] + n[k:]

    def f(n):
        return jnp.sum(q / totals(n))

    def grad_f(n):
        g = -q / totals(n) ** 2
        return jnp.concatenate([g, g])

    def hess_f(n):
        psi = 2.0 * q / totals(n) ** 3
        H = jnp.zeros((two_k, two_k))
        idx = jnp.arange(k)
        H = H.at[idx, idx].set(psi)
        H = H.at[idx + k, idx + k].set(psi)
        H = H.at[idx, idx + k].set(psi)
        H = H.at[idx + k, idx].set(psi)
        return H

    def merit(n, tau):
        s = b - A @ n
        safe = jnp.all(s > 0) & jnp.all(totals(n) > 0)
        val = tau * f(n) - jnp.sum(jnp.log(jnp.where(safe, s, 1.0)))
        return jnp.where(safe, val, jnp.inf)

    def newton_step(n, tau):
        s = b - A @ n
        d = 1.0 / s
        g = tau * grad_f(n) + A.T @ d
        H = tau * hess_f(n) + (A.T * (d * d)) @ A
        H = H + _RIDGE * jnp.trace(H) / two_k * jnp.eye(two_k)
        delta = -jax.scipy.linalg.solve(H, g, assume_a="pos")
        lam2 = -g @ delta
        # fraction-to-boundary
        Ad = A @ delta
        ratios = jnp.where(Ad > 0, s / Ad, jnp.inf)
        alpha0 = jnp.minimum(1.0, 0.99 * jnp.min(ratios))
        m0 = merit(n, tau)

        def body(carry):
            alpha, _ = carry
            return alpha * 0.5, merit(n + alpha * 0.5 * delta, tau)

        def cond(carry):
            alpha, mval = carry
            return (mval > m0 + 1e-4 * alpha * (g @ delta)) & (alpha > 1e-12)

        alpha, _ = jax.lax.while_loop(cond, body, (alpha0, merit(n + alpha0 * delta, tau)))
        return n + alpha * delta, lam2

    def inner(n, tau):
        def body(carry):
            n, _, it = carry
            n, lam2 = newton_step(n, tau)
            return n, lam2, it + 1

        def cond(carry):
            _, lam2, it = carry
            return (lam2 * 0.5 > 1e-10) & (it < inner_iters)

        n, _, _ = jax.lax.while_loop(cond, body, (n, jnp.inf, 0))
        return n

    def outer_body(carry, _):
        n, tau = carry
        n = inner(n, tau)
        return (n, tau * mu), None

    (n, _), _ = jax.lax.scan(outer_body, (n0, jnp.asarray(tau0)), None, length=outer_iters)
    gap = m / (tau0 * mu ** (outer_iters - 1))
    viol = jnp.max(A @ n - b)
    return n, f(n), viol, jnp.asarray(gap)


def solve_ipm(p: ProblemData) -> tuple[np.ndarray, float, np.ndarray, bool]:
    n0, eps, _restored = feasible_start(p)
    A, b = assemble_constraints(p, eps)
    q = p.weights**2 * p.sigma2_obj
    # The barrier Hessian conditioning (1/slack^2 terms) needs f64; the solve
    # runs edge/host-side so this never touches the MXU fast path.
    with jax.enable_x64(True):
        n, fval, viol, _gap = _ipm(jnp.asarray(q, jnp.float64),
                                   jnp.asarray(A, jnp.float64),
                                   jnp.asarray(b, jnp.float64),
                                   jnp.asarray(n0, jnp.float64))
        n = np.asarray(n)
        fval = float(fval)
        ok = bool(viol <= 1e-6)
    if not np.all(np.isfinite(n)):       # last-ditch: fall back to the start
        n, ok = n0, False
    return n, fval, eps, ok


# --------------------------------------------------------------------------
# closed-form water-filling solver (vmappable; the fleet batched-planning path)
# --------------------------------------------------------------------------

def closed_form_alloc(q: Array, cost: Array, n_obs: Array, sigma2: Array,
                      explained_var: Array, eps: Array, budget: Array,
                      predictor: Array, predictor2: Optional[Array] = None,
                      bisect_iters: int = 48):
    """One-shot KKT solution of a relaxation of eq. 1, pure jnp.

    Splits the program: (a) n_r by water-filling the budget constraint 1f —
    stationarity of eq. 2 w.r.t. n_r alone gives n_r,i = t·sqrt(q_i/c_i)
    clipped to [1, N_i], with the water level t found by bisection on the
    budget; (b) n_s pushed to its eq.-11 bias cap (imputation is free on the
    wire, so the objective is monotone decreasing in n_s) and clipped by
    constraint 1d.  Deviations vs. the IPM: the n_r stationarity ignores the
    n_s contribution to the totals (so n_r is slightly over-provisioned on
    strongly-predicted streams), and the >=1-sample floor (1e) may overshoot
    C by at most k·max(c) when C < sum(c).  Every op is elementwise or a
    fixed-length reduction, so the whole thing jits and vmaps across sites —
    this is the fleet batched-planning path (repro.planning.batched).

    Inputs are (k,) arrays (budget scalar); returns (n_r (k,) i32,
    n_s (k,) i32, objective scalar).
    """
    dt = q.dtype
    cost = jnp.maximum(cost, 1e-9)
    lo = jnp.minimum(jnp.asarray(1.0, dt), n_obs)     # 1e: >=1 where any exist
    r = jnp.sqrt(jnp.maximum(q, 0.0) / cost)

    def clipped(t):
        return jnp.clip(t * r, lo, n_obs)

    # bisect the water level t (cost is nondecreasing in t)
    r_min = jnp.min(jnp.where(r > 0, r, jnp.inf))
    t_hi = (jnp.max(n_obs) + 1.0) / jnp.maximum(r_min, 1e-9)
    t_lo = jnp.asarray(0.0, dt)
    for _ in range(bisect_iters):
        mid = 0.5 * (t_lo + t_hi)
        over = jnp.sum(cost * clipped(mid)) > budget
        t_lo, t_hi = jnp.where(over, t_lo, mid), jnp.where(over, mid, t_hi)
    nr_f = clipped(t_lo)

    # integer rounding: floor, then largest-remainder top-up within the budget
    nr = jnp.minimum(jnp.floor(nr_f + 1e-4), n_obs)
    leftover = budget - jnp.sum(cost * nr)
    headroom = nr < n_obs
    order = jnp.argsort(-jnp.where(headroom, nr_f - nr, -jnp.inf))
    affordable = jnp.cumsum(jnp.where(headroom[order], cost[order], 0.0)) <= leftover
    take = (affordable & headroom[order]).astype(dt)
    nr = nr + jnp.zeros_like(nr).at[order].set(take)

    # n_s: eq.-11 bias cap, then 1d (n_s <= n_r of every predictor)
    nr_pred = nr[predictor]
    if predictor2 is not None:
        nr_pred = jnp.minimum(nr_pred, nr[predictor2])
    slope = sigma2 - explained_var - eps
    cap = jnp.where(slope > 0,
                    ((nr - 1.0) * eps - explained_var)
                    / jnp.maximum(slope, 1e-20),
                    jnp.inf)
    cap = jnp.maximum(cap, 0.0)
    ns = jnp.floor(jnp.minimum(cap, nr_pred) + 1e-4)
    # 1e for unobserved (straggler) streams: at least one imputed sample
    ns = jnp.where((nr < 0.5) & (nr_pred >= 1.0), jnp.maximum(ns, 1.0), ns)

    obj = jnp.sum(q / jnp.maximum(nr + ns, 1.0))
    return nr.astype(jnp.int32), ns.astype(jnp.int32), obj


@partial(jax.jit, static_argnames=())
def _closed_form_jit(q, cost, n_obs, sigma2, V, eps, budget, predictor):
    return closed_form_alloc(q, cost, n_obs, sigma2, V, eps, budget, predictor)


def solve_closed_form(p: ProblemData) -> Allocation:
    """Host entry: same math as the vmapped fleet path (f32 for bit parity)."""
    f32 = jnp.float32
    q = jnp.asarray(p.weights**2 * p.sigma2_obj, f32)
    args = (q, jnp.asarray(p.cost_real, f32), jnp.asarray(p.n_obs, f32),
            jnp.asarray(p.sigma2, f32), jnp.asarray(p.explained_var, f32),
            jnp.asarray(p.eps, f32), jnp.asarray(p.budget, f32),
            jnp.asarray(p.predictor, jnp.int32))
    if p.predictor2 is not None:
        nr, ns, obj = closed_form_alloc(*args,
                                        jnp.asarray(p.predictor2, jnp.int32))
    else:
        nr, ns, obj = _closed_form_jit(*args)
    # the >=1-sample floor (1e) can overshoot C when C < sum(cost) — report it
    spent = float(np.asarray(p.cost_real) @ np.asarray(nr))
    return Allocation(n_real=nr, n_imputed=ns,
                      objective=jnp.asarray(obj, jnp.float32),
                      feasible=jnp.asarray(spent <= p.budget + 1e-6),
                      eps_used=jnp.asarray(p.eps, jnp.float32))


# --------------------------------------------------------------------------
# scipy SLSQP parity oracle (the paper's solver)
# --------------------------------------------------------------------------

def solve_slsqp(p: ProblemData):
    from scipy.optimize import minimize

    n0, eps, _ = feasible_start(p)
    A, b = assemble_constraints(p, eps)
    q = p.weights**2 * p.sigma2_obj
    k = p.k

    def f(n):
        return float(np.sum(q / (n[:k] + n[k:])))

    def grad(n):
        g = -q / (n[:k] + n[k:]) ** 2
        return np.concatenate([g, g])

    cons = [{"type": "ineq", "fun": lambda n: b - A @ n, "jac": lambda n: -A}]
    res = minimize(f, n0, jac=grad, constraints=cons, method="SLSQP",
                   options={"maxiter": 300, "ftol": 1e-12})
    return np.asarray(res.x), float(res.fun), eps, bool(res.success)


# --------------------------------------------------------------------------
# integer rounding (host-side; conservative w.r.t. every constraint)
# --------------------------------------------------------------------------

def round_allocation(p: ProblemData, n: np.ndarray, eps: np.ndarray):
    k = p.k
    nr = np.floor(n[:k] + 1e-9).astype(np.int64)
    ns = np.floor(n[k:] + 1e-9).astype(np.int64)
    nr = np.clip(nr, 0, p.n_obs.astype(np.int64))

    def bias_ok(nr_i, ns_i, i):
        if ns_i == 0:
            return True          # no imputation => estimator unbiased
        lhs = ns_i * p.sigma2[i] - (ns_i - 1) * p.explained_var[i]
        return lhs <= (nr_i + ns_i - 1) * eps[i] + 1e-9

    # enforce 1d / 1g after flooring
    for i in range(k):
        ns[i] = min(ns[i], nr[p.predictor[i]])
        if p.predictor2 is not None:
            ns[i] = min(ns[i], nr[p.predictor2[i]])
        while ns[i] > 0 and not bias_ok(nr[i], ns[i], i):
            ns[i] -= 1

    # greedy top-up of n_r with leftover budget (largest marginal gain / cost)
    budget_left = p.budget - float(p.cost_real @ nr)
    q = p.weights**2 * p.sigma2_obj
    for _ in range(8 * k):
        tot = np.maximum(nr + ns, 1)
        gain = q / tot - q / (tot + 1)
        gain = np.where(nr < p.n_obs, gain / p.cost_real, -np.inf)
        j = int(np.argmax(gain))
        if gain[j] <= 0 or p.cost_real[j] > budget_left + 1e-12:
            break
        nr[j] += 1
        budget_left -= p.cost_real[j]

    # guarantee >=1 sample per stream (1e) wherever we still can
    for i in range(k):
        if nr[i] + ns[i] == 0:
            if budget_left >= p.cost_real[i] and p.n_obs[i] >= 1:
                nr[i] += 1
                budget_left -= p.cost_real[i]
            elif nr[p.predictor[i]] > 0 and bias_ok(0, 1, i):
                ns[i] = 1
    return nr, ns


def _rounded(p: ProblemData, n: np.ndarray, fval: float, eps: np.ndarray,
             ok: bool) -> Allocation:
    nr, ns = round_allocation(p, n, eps)
    return Allocation(n_real=jnp.asarray(nr, jnp.int32),
                      n_imputed=jnp.asarray(ns, jnp.int32),
                      objective=jnp.asarray(fval, jnp.float32),
                      feasible=jnp.asarray(ok),
                      eps_used=jnp.asarray(eps, jnp.float32))


@SOLVERS.register("ipm")
def _ipm_allocation(p: ProblemData) -> Allocation:
    return _rounded(p, *solve_ipm(p))


@SOLVERS.register("slsqp")
def _slsqp_allocation(p: ProblemData) -> Allocation:
    return _rounded(p, *solve_slsqp(p))


SOLVERS.register("closed_form", solve_closed_form)  # does its own rounding


def solve(p: ProblemData, method: str = "ipm") -> Allocation:
    """Solve one eq.-1 instance; ``method`` resolves through the solver
    registry (``repro.api.registry.SOLVERS``)."""
    return SOLVERS.get(method)(p)
