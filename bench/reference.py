"""The plain reference, and the comparison that decides ``correct``.

It imports nothing of the program.  What the served path returns per
``run`` call, and what this checks it against, every number in float64
from the windows the harness fed:

* ``totals`` — the carry's running per-(site, stream) count, sum and sum of
  squares of every tuple ingested.  Number: ``totals_gap``, the largest
  relative gap of a sum over every (call, site, stream); a count that
  differs is a gap of 1.
* ``truth`` — the truth tables: AVG, VAR (ddof 1), MIN and MAX of every
  (window, site, stream).  Number: ``truth_gap``, the largest gap over
  every cell and query, relative to the stream's standard deviation.
* ``r2`` — the planner's mean share of each stream's variance that its
  compact model explains: Pearson dependence, each stream's predictor the
  other stream of largest ``|corr|``, a cubic least-squares fit on it.
  Where two predictors lie within ``TIE`` in ``|corr|`` either may be
  chosen, so the reference gives the interval of both.  Number:
  ``r2_gap``, the largest distance of the program's value from that
  interval over every (window, site).
* ``estimates`` — the served AVG answers.  Which tuples are sampled is
  random and the sampler's stream is not the reference's to redraw, so an
  answer is judged by its error: ``est_err``, the largest
  ``|est - AVG| / sd`` over every (window, site, stream), against the
  reference's truth.  An answer that is missing counts as infinite.
* ``budgets`` — the rebalance controller: an EWMA of each site's demand
  ``sqrt(err * budget)``, water-filled over the fleet inside
  ``[floor_mult, ceil_mult]`` times the equal share, floored to whole
  samples, replayed from the fresh state through every window.  Its error
  signal ``err`` is the mean over streams of ``|est - AVG| / |AVG|``,
  worked out here from the served AVG answers and the reference's truth,
  as a served model's reference is handed the served tokens.  Number:
  ``budget_gap``, the largest distance, in samples, of the reference's raw
  budget from ``[b, b + 1)`` around the program's executed budget ``b``.
* ``objective`` — the planner's allocation of the site's budget to its
  streams, through its value: ``sum_i q_i / (n_r,i + n_s,i)`` with
  ``q_i = var_i / mean_i^2``.  The reference works the allocation out from
  the executed budget: ``n_r`` water-filled in proportion to ``sqrt(q)``
  inside ``[1, N]`` and rounded by largest remainder, ``n_s`` each stream's
  bias cap under its k-standard-error tolerance (eq. 8, 11), no more than
  its predictor's ``n_r``.  Number: ``alloc_off``, the share of (window,
  site) cells whose objective lies further than ``OBJ_TOL`` from the
  interval that the near-tied predictors allow.
* ``bytes`` — the WAN bytes per (window, site).  The planner spends its
  whole net budget on real samples, so a site ships
  ``4 (b - model_bytes k / 4) + (8 + 2 k) + model_bytes m`` bytes with
  ``m`` in ``0..k`` streams that upload a model.  Number: ``bytes_off``,
  the count of (window, site) cells that no ``m`` explains.

:func:`control_calls` computes the same reference in bfloat16, the
precision below the configurations' float32, and puts it in the program's
place: that is the control.
"""
from __future__ import annotations

import dataclasses

import numpy as np

# EdgePayload wire format per site: 4-byte samples, a header of 8 + 2k
# bytes and one compact model per imputing stream
SAMPLE_BYTES = 4
MODEL_BYTES = {"cubic": 28}
QUERIES = ("AVG", "VAR", "MIN", "MAX")
TIE = 1e-5                     # |corr| this close: either predictor may win
CUBIC = 3
OBJ_TOL = 1e-4                 # relative: float32 rounding stays far below,
#                                one sample moved in one stream lies above
ROUND = 1e-4                   # the allocation's guard against floor()
BISECT_ITERS = 60


def header_bytes(k: int) -> int:
    return 8 + 2 * k


def control_dtype() -> np.dtype:
    """The control's float type: bfloat16, the precision below the float32
    that the configurations state."""
    import ml_dtypes
    return np.dtype(ml_dtypes.bfloat16)


class Exact:
    """float64 arithmetic: ``r`` leaves a value as it is."""

    dt = np.dtype(np.float64)

    def r(self, x):
        return np.asarray(x, np.float64)


class Rounded:
    """The control's arithmetic: every stored value rounded to ``low``,
    products accumulated in float32, as a bfloat16 contraction on the
    chip accumulates them."""

    def __init__(self, low):
        self.low = np.dtype(low)
        self.dt = np.dtype(np.float32)

    def r(self, x):
        return np.asarray(x, np.float32).astype(self.low).astype(np.float32)


# ------------------------------------------------------------ windows

def truth_tables(windows: np.ndarray, ar=Exact()) -> dict:
    """{query: (W, E, k)} of each window: AVG, VAR (ddof 1), MIN, MAX."""
    x = ar.r(windows)
    n = x.shape[-1]
    avg = ar.r(x.sum(-1, dtype=ar.dt) / n)
    xc = ar.r(x - avg[..., None])
    var = ar.r((xc * xc).sum(-1, dtype=ar.dt) / (n - 1))
    return {"AVG": avg, "VAR": var, "MIN": x.min(-1), "MAX": x.max(-1)}


def window_sums(windows: np.ndarray, ar=Exact()):
    """(W, E, k) sums and sums of squares of each window."""
    x = ar.r(windows)
    return (ar.r(x.sum(-1, dtype=ar.dt)),
            ar.r((x * x).sum(-1, dtype=ar.dt)))


def explained_shares(window: np.ndarray, ar=Exact()):
    """(|corr| (E, k, k) with the diagonal at -inf, R^2 (E, k, k) of the
    cubic fit of stream i on stream j) of one (E, k, N) window."""
    x = ar.r(window)
    n = x.shape[-1]
    xc = ar.r(x - ar.r(x.sum(-1, dtype=ar.dt) / n)[..., None])
    ss = ar.r((xc * xc).sum(-1, dtype=ar.dt))                   # (E, k)
    xct = xc.swapaxes(-1, -2)                                   # (E, N, k)
    cov = ar.r(xc @ xct)
    corr = np.abs(cov / np.sqrt(np.maximum(
        ss[..., :, None] * ss[..., None, :], 1e-300)))
    k = x.shape[1]
    corr[:, np.arange(k), np.arange(k)] = -np.inf
    # the predictor standardized as the program's fit standardizes it
    u = ar.r(xc / np.sqrt(np.maximum(ss / n, 1e-12))[..., None])
    feats = ar.r(np.stack([u ** p for p in range(CUBIC + 1)], -2))
    gram = ar.r(feats @ feats.swapaxes(-1, -2))                 # (E, j, 4, 4)
    rhs = ar.r(feats @ xct[:, None])                            # (E, j, 4, i)
    coef = np.linalg.solve(gram, rhs)
    fit_ss = (rhs * coef).sum(-2).swapaxes(-1, -2)              # (E, i, j)
    var = np.maximum(ss / (n - 1), 1e-12)
    ev = np.clip(fit_ss / (n - 1), 0.0, var[..., None] * (1 - 1e-9))
    return corr, ar.r(ev / var[..., None])


def plan_inputs(window: np.ndarray, ar=Exact()):
    """What the planner allocates from, for one (E, k, N) window: ``q``,
    variance and bias tolerance ``eps`` (E, k), and |corr| and the cubic
    fit's explained variance (E, k, k) of stream i on stream j."""
    x = ar.r(window)
    n = x.shape[-1]
    mu = ar.r(x.sum(-1, dtype=ar.dt) / n)
    xc = ar.r(x - mu[..., None])
    var = ar.r((xc * xc).sum(-1, dtype=ar.dt) / (n - 1))
    m4 = ar.r((xc ** 4).sum(-1, dtype=ar.dt) / n)
    vov = np.maximum((m4 - (n - 3.0) / (n - 1.0) * var ** 2) / n, 0.0)
    eps = np.maximum(np.sqrt(vov), 1e-12)
    sigma2 = np.maximum(var, 1e-12)
    q = sigma2 / np.maximum(np.abs(mu), 1e-6) ** 2
    corr, share = explained_shares(window, ar)
    return q, sigma2, eps, corr, share * sigma2[..., :, None]


def real_samples(q: np.ndarray, n: float, net: np.ndarray) -> np.ndarray:
    """(E, k) real samples: ``clip(t sqrt(q), 1, n)`` at the water level
    ``t`` that spends the net budget, floored, then topped up one sample a
    stream in order of the largest remainder while the budget lasts."""
    r = np.sqrt(np.maximum(q, 0.0))
    lo = np.zeros(len(q))
    hi = (n + 1.0) / np.maximum(np.where(r > 0, r, np.inf).min(-1), 1e-9)
    for _ in range(BISECT_ITERS):
        mid = 0.5 * (lo + hi)
        over = np.clip(mid[:, None] * r, 1.0, n).sum(-1) > net
        lo, hi = np.where(over, lo, mid), np.where(over, mid, hi)
    frac = np.clip(lo[:, None] * r, 1.0, n)
    nr = np.minimum(np.floor(frac + ROUND), n)
    room = nr < n
    order = np.argsort(-np.where(room, frac - nr, -np.inf), -1, kind="stable")
    room_o = np.take_along_axis(room, order, -1)
    take = room_o & (np.cumsum(room_o, -1) <= (net - nr.sum(-1))[:, None])
    np.put_along_axis(nr, order, np.take_along_axis(nr, order, -1) + take, -1)
    return nr


def objective_terms(inputs, budgets: np.ndarray, k: int, n: int,
                    model_bytes: int) -> np.ndarray:
    """(E, i, j) stream i's share of the objective with predictor j."""
    q, sigma2, eps, _, vexp = inputs
    net = np.maximum(np.asarray(budgets, np.float64)
                     - model_bytes * k / SAMPLE_BYTES, 2.0)
    nr = real_samples(q, float(n), net)
    slope = sigma2[..., None] - vexp - eps[..., None]
    cap = np.where(slope > 0, ((nr - 1.0)[..., None] * eps[..., None] - vexp)
                   / np.maximum(slope, 1e-20), np.inf)
    ns = np.floor(np.minimum(np.maximum(cap, 0.0), nr[:, None, :]) + ROUND)
    return q[..., None] / np.maximum(nr[..., None] + ns, 1.0)


def objective_interval(inputs, budgets, k: int, n: int, model_bytes: int):
    """(E,) least and largest objective that a choice of predictors among
    the near-ties gives."""
    term = objective_terms(inputs, budgets, k, n, model_bytes)
    corr = inputs[3]
    cand = corr >= corr.max(-1, keepdims=True) - TIE
    return (np.where(cand, term, np.inf).min(-1).sum(-1),
            np.where(cand, term, -np.inf).max(-1).sum(-1))


def r2_interval(inputs):
    """(E,) least and largest mean R^2 that a choice of predictors among
    the near-ties gives, from one window's :func:`plan_inputs`."""
    _, sigma2, _, corr, vexp = inputs
    share = vexp / sigma2[..., :, None]
    cand = corr >= corr.max(-1, keepdims=True) - TIE
    return (np.where(cand, share, np.inf).min(-1).mean(-1),
            np.where(cand, share, -np.inf).max(-1).mean(-1))


def chosen(inputs, per_pair: np.ndarray) -> np.ndarray:
    """(E, k) ``per_pair`` (E, i, j) at each stream's argmax predictor."""
    p = inputs[3].argmax(-1)
    return np.take_along_axis(per_pair, p[..., None], -1)[..., 0]


# ---------------------------------------------------------- controller

@dataclasses.dataclass
class Controller:
    """The rebalance controller's chain, in the float type ``dt``."""

    total: float
    n_sites: int
    floor_mult: float
    ceil_mult: float
    ewma: float
    iters: int
    dt: np.dtype = np.dtype(np.float64)

    def __post_init__(self):
        c = self._c
        e = self.n_sites
        self.eq = c(self.total) / c(e)
        self.demand = np.ones(e, self.dt)
        self.seen = False

    def _c(self, x):
        return np.asarray(x, self.dt)

    def water_fill(self, demand):
        """Share ``total`` in proportion to demand inside [lo, hi]: clip,
        then ``iters`` rounds of handing the excess to the movable sites."""
        c = self._c
        total = c(self.total)
        lo = np.full(self.n_sites, c(self.floor_mult) * self.eq, self.dt)
        hi = np.full(self.n_sites, c(self.ceil_mult) * self.eq, self.dt)
        d = np.where(np.isfinite(demand), demand, c(0)).astype(self.dt)
        if not (d > 0).any():
            d = np.ones_like(d)
        d = np.maximum(d, c(1e-12))
        b = np.clip(total * d / d.sum(dtype=self.dt), lo, hi).astype(self.dt)
        for _ in range(self.iters):
            excess = total - b.sum(dtype=self.dt)
            w = d * ((b < hi) if excess > 0 else (b > lo))
            wsum = w.sum(dtype=self.dt)
            if abs(float(excess)) < 1e-9 or wsum <= 0:
                break
            b = np.clip(b + excess * w / wsum, lo, hi).astype(self.dt)
        return b

    def budgets(self):
        """Raw (unfloored) budgets of the next window."""
        if not self.seen:
            return np.full(self.n_sites, self.eq, self.dt)
        return self.water_fill(self.demand)

    def update(self, raw, obs_err, objective):
        c = self._c
        a = c(self.ewma)
        obs = c(obs_err)
        pred = np.sqrt(np.maximum(c(objective), c(0)))
        err = np.where(np.isfinite(obs) & (obs > 0), obs, pred)
        err = np.nan_to_num(err, nan=1.0).astype(self.dt)
        new = np.sqrt(np.maximum(err, c(1e-9)) * np.maximum(raw, c(1)))
        self.demand = (((c(1) - a) * self.demand + a * new) if self.seen
                       else new).astype(self.dt)
        self.seen = True


def controller_for(cfg: dict, dt) -> Controller:
    ctl = cfg["controller"]
    e, k, n = (int(cfg["sites"]), int(cfg["streams_per_site"]),
               int(cfg["window"]))
    return Controller(total=cfg["budget_fraction"] * e * k * n, n_sites=e,
                      floor_mult=ctl["floor_mult"],
                      ceil_mult=ctl["ceil_mult"], ewma=ctl["ewma"],
                      iters=ctl["water_fill_iters"], dt=np.dtype(dt))


def error_signal(est_avg, avg) -> np.ndarray:
    """(E,) the controller's error signal: the mean over streams of the
    served AVG's relative error, streams without an answer left out."""
    rel = (np.abs(np.asarray(est_avg, np.float64) - avg)
           / np.maximum(np.abs(avg), 1e-6))
    seen = ~np.isnan(rel)
    n_seen = seen.sum(-1)
    return np.where(n_seen > 0, np.where(seen, rel, 0.0).sum(-1)
                    / np.maximum(n_seen, 1), np.nan)


# ------------------------------------------------------------- numbers

def budget_gap(raw_ref: np.ndarray, executed: np.ndarray) -> float:
    """Largest distance of the reference's raw budget from [b, b + 1)
    (from [2, 3) down to minus infinity where the floor of 2 binds)."""
    b = np.asarray(executed, np.float64)
    r = np.asarray(raw_ref, np.float64)
    below = np.where(b > 2, b - r, 0.0)
    return float(np.max(np.maximum(0.0, np.maximum(below, r - (b + 1)))))


def bytes_off(nbytes: np.ndarray, executed: np.ndarray, cfg: dict) -> int:
    """(window, site) cells whose bytes no count of model uploads explains."""
    k = int(cfg["streams_per_site"])
    mb = MODEL_BYTES[cfg["model"]]
    net = np.maximum(np.asarray(executed, np.float64) - mb * k / SAMPLE_BYTES,
                     2.0)
    rest = (np.asarray(nbytes, np.float64) - header_bytes(k)
            - SAMPLE_BYTES * net)
    m = rest / mb
    ok = (m == np.round(m)) & (m >= 0) & (m <= k)
    return int((~ok).sum())


def alloc_off(objective: np.ndarray, lo: np.ndarray,
              hi: np.ndarray) -> np.ndarray:
    """(E,) whether a site's plan objective lies further than ``OBJ_TOL``
    (relative) from the reference's interval."""
    o = np.asarray(objective, np.float64)
    gap = np.maximum(lo - o, o - hi) / np.maximum(np.abs(hi), 1e-30)
    return ~(gap <= OBJ_TOL)


def truth_gap(tru: dict, ref: dict, sd: np.ndarray) -> float:
    """Largest gap of a truth table from the reference's, in standard
    deviations of its stream."""
    return max(_worst(np.abs(np.asarray(tru[q], np.float64) - ref[q]) / sd)
               for q in QUERIES)


def est_err(est_avg: np.ndarray, avg: np.ndarray, sd: np.ndarray) -> float:
    return _worst(np.abs(np.asarray(est_avg, np.float64) - avg) / sd)


def _worst(x) -> float:
    x = np.asarray(x, np.float64)
    return float(np.max(np.where(np.isnan(x), np.inf, x)))


def _rel_gap(got, ref) -> float:
    got = np.asarray(got, np.float64)
    return _worst(np.abs(got - ref) / np.maximum(np.abs(ref), 1e-30))


@dataclasses.dataclass
class Call:
    """What one ``run`` call returned that the comparison reads."""

    slots: np.ndarray        # (T,) index of each window among the distinct
    budgets: np.ndarray      # (T, E) executed budgets
    nbytes: np.ndarray       # (T, E) WAN bytes
    objective: np.ndarray    # (T, E) plan objective
    r2: np.ndarray           # (T, E) plan's mean explained share
    est_avg: np.ndarray      # (T, E, k) served AVG answers
    tru: dict                # {query: (T, E, k)} truth tables
    count: np.ndarray        # (E, k) carry totals after the call
    s1: np.ndarray
    s2: np.ndarray


@dataclasses.dataclass
class Reference:
    """What the reference works out once per distinct window."""

    truth: dict              # {query: (W, E, k)}
    sd: np.ndarray           # (W, E, k) standard deviation of each stream
    r2_lo: np.ndarray        # (W, E)
    r2_hi: np.ndarray
    s1: np.ndarray           # (W, E, k)
    s2: np.ndarray
    plan: list               # plan_inputs of each window

    @classmethod
    def of(cls, windows: np.ndarray) -> "Reference":
        truth = truth_tables(windows)
        s1, s2 = window_sums(windows)
        plan = [plan_inputs(w) for w in windows]
        lo, hi = map(np.stack, zip(*(r2_interval(p) for p in plan)))
        return cls(truth=truth, sd=np.sqrt(np.maximum(truth["VAR"], 1e-30)),
                   r2_lo=lo, r2_hi=hi, s1=s1, s2=s2, plan=plan)


def compare(calls: list, windows: np.ndarray, cfg: dict,
            ref: Reference | None = None) -> dict:
    """{number: value} over every call of a run, in call order from the
    fresh state, against the float64 reference."""
    per_call = compare_calls(calls, windows, cfg, ref)
    return {k: max(c[k] for c in per_call) for k in per_call[0]}


def compare_calls(calls: list, windows: np.ndarray, cfg: dict,
                  ref: Reference | None = None) -> list:
    """The numbers of each call: its windows' answers, plan, budgets and
    bytes, and the carry totals it returned."""
    ref = ref or Reference.of(windows)
    ctl = controller_for(cfg, np.float64)
    n, k = int(cfg["window"]), int(cfg["streams_per_site"])
    mb = MODEL_BYTES[cfg["model"]]
    count = np.zeros(ref.s1.shape[1:])
    ref1 = np.zeros(ref.s1.shape[1:])
    ref2 = np.zeros(ref.s1.shape[1:])
    out = []
    for call in calls:
        nums = {"totals_gap": 0.0, "truth_gap": 0.0, "r2_gap": 0.0,
                "est_err": 0.0, "budget_gap": 0.0}
        off = []
        for t, slot in enumerate(call.slots):
            avg, sd = ref.truth["AVG"][slot], ref.sd[slot]
            raw = ctl.budgets()
            nums["budget_gap"] = max(nums["budget_gap"],
                                     budget_gap(raw, call.budgets[t]))
            ctl.update(raw, error_signal(call.est_avg[t], avg),
                       call.objective[t])
            nums["truth_gap"] = max(nums["truth_gap"], truth_gap(
                {q: call.tru[q][t] for q in QUERIES},
                {q: ref.truth[q][slot] for q in QUERIES}, sd))
            r2 = np.asarray(call.r2[t], np.float64)
            nums["r2_gap"] = max(nums["r2_gap"], _worst(np.maximum(
                0.0, np.maximum(ref.r2_lo[slot] - r2, r2 - ref.r2_hi[slot]))))
            nums["est_err"] = max(nums["est_err"],
                                  est_err(call.est_avg[t], avg, sd))
            off.append(alloc_off(call.objective[t], *objective_interval(
                ref.plan[slot], call.budgets[t], k, n, mb)))
            count += n
            ref1 += ref.s1[slot]
            ref2 += ref.s2[slot]
        nums["totals_gap"] = max(
            _rel_gap(call.s1, ref1), _rel_gap(call.s2, ref2),
            float(np.any(np.asarray(call.count) != count)))
        nums["alloc_off"] = float(np.mean(off))
        nums["bytes_off"] = bytes_off(call.nbytes, call.budgets, cfg)
        out.append(nums)
    return out


def control_calls(calls: list, windows: np.ndarray, cfg: dict) -> list:
    """The control: the reference computed in bfloat16 and put in the
    program's place.  Its own truth tables, plan shares and objectives,
    totals, controller chain and bytes, fed the same served answers, in
    that float type."""
    dt = control_dtype()
    ar = Rounded(dt)
    ctl = controller_for(cfg, dt)
    k = int(cfg["streams_per_site"])
    mb = MODEL_BYTES[cfg["model"]]
    n = np.asarray(cfg["window"], dt)
    used = sorted({int(s) for c in calls for s in c.slots})
    sub = windows[used]
    at = {s: i for i, s in enumerate(used)}
    truth = truth_tables(sub, ar)
    plan = [plan_inputs(w, ar) for w in sub]
    r2 = np.stack([ar.r(chosen(p, p[4] / p[1][..., :, None]).mean(-1))
                   for p in plan])
    s1w, s2w = window_sums(sub, ar)
    count = np.zeros(s1w.shape[1:], dt)
    s1 = np.zeros(s1w.shape[1:], dt)
    s2 = np.zeros(s1w.shape[1:], dt)
    out = []
    for call in calls:
        budgets, nbytes, objective = [], [], []
        rows = [at[int(s)] for s in call.slots]
        for t, i in enumerate(rows):
            raw = ctl.budgets()
            b = np.maximum(np.floor(raw), np.asarray(2, dt)).astype(dt)
            obj = ar.r(chosen(plan[i], objective_terms(
                plan[i], b.astype(np.float64), k, int(n), mb)).sum(-1))
            ctl.update(raw, error_signal(call.est_avg[t], truth["AVG"][i]),
                       obj)
            net = np.maximum(b - np.asarray(mb * k / SAMPLE_BYTES, dt),
                             np.asarray(2, dt))
            m = np.round((np.asarray(call.nbytes[t], np.float64)
                          - header_bytes(k) - SAMPLE_BYTES * np.maximum(
                              call.budgets[t] - mb * k / SAMPLE_BYTES, 2.0))
                         / mb)
            nb = (np.asarray(SAMPLE_BYTES, dt) * net
                  + np.asarray(header_bytes(k), dt)
                  + np.asarray(mb, dt) * m.astype(dt)).astype(dt)
            budgets.append(b.astype(np.float64))
            nbytes.append(nb.astype(np.float64))
            objective.append(obj.astype(np.float64))
            count = (count + n).astype(dt)
            s1 = (s1 + s1w[i].astype(dt)).astype(dt)
            s2 = (s2 + s2w[i].astype(dt)).astype(dt)
        out.append(dataclasses.replace(
            call, budgets=np.stack(budgets), nbytes=np.stack(nbytes),
            objective=np.stack(objective), r2=r2[rows],
            tru={q: truth[q][rows] for q in QUERIES},
            count=count.astype(np.float64), s1=s1.astype(np.float64),
            s2=s2.astype(np.float64)))
    return out


def altered_calls(calls: list) -> list:
    """The served calls with an answer altered: site 0's AVG answers mixed
    up, each stream answered with the next stream's."""
    return [dataclasses.replace(
        c, est_avg=np.concatenate([np.roll(c.est_avg[:, :1], -1, axis=-1),
                                   c.est_avg[:, 1:]], axis=1))
        for c in calls]
