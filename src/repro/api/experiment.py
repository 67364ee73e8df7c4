"""The one experiment runtime behind the Scenario API.

``Experiment.from_scenario(cfg)`` builds everything a run needs from a
:class:`~repro.api.scenario.ScenarioConfig` — data windows, edge planner,
WAN transport(s), cloud(s), fleet controller — and ``run()`` returns a
structured :class:`RunReport` instead of a loose dict.

Two runtimes live here:

  * :class:`SingleEdgeRuntime` — one edge, one uplink, one cloud on the
    event-driven virtual clock.
  * :class:`FleetRuntime` — E edges, per-site uplinks/clouds, planning
    through the plan-engine registry (``repro.planning.ENGINES``) and the
    fleet budget controller.

``Experiment`` picks the runtime from the scenario: no topology (or a
one-site topology) is the E=1 degenerate fleet and runs single-edge with
the lone link's WAN character; anything larger runs the fleet runtime.
Both plan through the same engine layer — ``plan_window`` routes the E=1
case and ``FleetRuntime`` the (E, k, N) stack — selected declaratively via
``PlannerConfig.engine``.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Optional

import numpy as np

from repro.core import queries as Q
from repro.core.reconstruct import reconstruct_window
from repro.core.types import EdgePayload, PlannerConfig, WindowBatch
from repro.api.scenario import ControllerSpec, ScenarioConfig
from repro.parallel.sharding import EXCHANGE_COUNTERS


# ==========================================================================
# single-edge runtime (one edge, one uplink, one cloud)
# ==========================================================================

@dataclasses.dataclass
class SingleEdgeRuntime:
    """Event-driven edge->WAN->cloud run on a virtual clock.

    Window ``wid`` closes at the edge at ``wid * window_period_ms``; its
    query is answered one period later (``t_due``), from whatever has
    arrived by then.  Payloads landing after their due time but within
    ``staleness_deadline_ms`` revise the already-emitted result
    retroactively (``revisions`` count, ``nrmse`` reflects the revised
    table, ``nrmse_at_query`` what was actually served on time); payloads
    past the deadline fall back to stale serving and count as ``gaps``.

    With zero latency and an infinite deadline this reproduces the
    lock-step runtime bit-for-bit (tests/test_async_transport.py).
    """

    edge: "EdgeNode"
    cloud: "CloudNode"
    transport: "Transport"
    window_period_ms: float = 1000.0
    staleness_deadline_ms: Optional[float] = None

    def __post_init__(self):
        from repro.streaming.events import AsyncTransport, ReorderCloudNode
        if not isinstance(self.transport, AsyncTransport):
            self.transport = AsyncTransport.from_transport(self.transport)
        self._user_cloud = None
        if not isinstance(self.cloud, ReorderCloudNode):
            # upgrade a plain CloudNode; its counters are mirrored back
            # after run() so callers holding the original still see them
            self._user_cloud = self.cloud
            self.cloud = ReorderCloudNode(query_names=self.cloud.query_names)
        self.cloud.window_period_ms = self.window_period_ms
        if self.staleness_deadline_ms is not None:
            self.cloud.deadline_ms = self.staleness_deadline_ms

    def run(self, windows: list[WindowBatch]) -> dict:
        from repro.streaming.events import freshness_percentiles
        k = windows[0].k
        T = len(windows)
        qnames = self.cloud.query_names
        period = self.window_period_ms
        est = {q: np.full((T, k), np.nan) for q in qnames}       # revised
        est_q = {q: np.full((T, k), np.nan) for q in qnames}     # at query
        tru = {q: np.full((T, k), np.nan) for q in qnames}
        ages = np.full(T, np.nan)
        revised = np.zeros(T, bool)

        def _record(wid, rec, tables):
            res = self.cloud.query(rec)
            for q in qnames:
                row = res.get(q, [])
                vals = np.asarray(row) if len(row) == k else np.full(k, np.nan)
                for tbl in tables:
                    tbl[q][wid] = vals

        def _apply(outcome):
            if outcome.kind == "revised":
                _record(outcome.window_id, outcome.reconstruction, (est,))
                revised[outcome.window_id] = True

        for wid, w in enumerate(windows):
            now = wid * period
            q_time = now + period
            payload = self.edge.process_window(w)
            payload = dataclasses.replace(payload, sent_at_ms=now)
            self.transport.send(payload, now_ms=now)
            for ev in self.transport.drain(q_time):
                _apply(self.cloud.ingest_event(ev.payload, now_ms=ev.at_ms))
            rec, age, _ = self.cloud.serve(wid, q_time)
            _record(wid, rec, (est, est_q))
            ages[wid] = age
            full = [np.asarray(w.values[i, : int(w.counts[i])])
                    for i in range(k)]
            _record(wid, full, (tru,))

        # in-flight payloads may still land within the deadline and revise
        for ev in self.transport.drain(float("inf")):
            _apply(self.cloud.ingest_event(ev.payload, now_ms=ev.at_ms))
        self.cloud.finalize(T)
        if self._user_cloud is not None:
            self._user_cloud.gaps = self.cloud.gaps
            self._user_cloud.windows_seen = self.cloud.windows_seen
            self._user_cloud.last_reconstruction = self.cloud.last_reconstruction

        nrmse = {q: Q.nrmse_table(est[q].T, tru[q].T) for q in qnames}
        nrmse_q = {q: Q.nrmse_table(est_q[q].T, tru[q].T) for q in qnames}
        total_tuples = int(sum(int(np.sum(w.counts)) for w in windows))
        return {
            "nrmse": nrmse,
            "nrmse_at_query": nrmse_q,
            "wan_bytes": self.transport.bytes_sent,
            "wan_cost": float(self.transport.bytes_cost),
            "full_bytes": total_tuples * 4,
            "plan_seconds": self.edge.plan_seconds,
            "gaps": self.cloud.gaps,
            "revisions": self.cloud.revisions,
            "late_drops": self.cloud.late_drops,
            "duplicates": self.cloud.duplicates,
            "retransmits": getattr(self.transport, "retransmits", 0),
            "window_age_ms": ages,
            "revised_windows": revised,
            "freshness_ms": freshness_percentiles(ages),
        }


# ==========================================================================
# fleet runtime (E edges against per-site clouds)
# ==========================================================================

def _draw_real_np(rng: np.random.Generator, values: np.ndarray,
                  counts: np.ndarray, alloc: np.ndarray) -> list[np.ndarray]:
    """SRS without replacement per stream (host-side numpy; the jax-PRNG
    sampler in core.samplers costs one dispatch per stream — at fleet scale
    that is E*k dispatches per window, which would dwarf planning)."""
    out = []
    for i in range(len(alloc)):
        n_i = int(min(int(alloc[i]), int(counts[i])))
        if n_i <= 0:
            out.append(np.zeros((0,), np.float32))
            continue
        idx = rng.permutation(int(counts[i]))[:n_i]
        out.append(values[i, idx].astype(np.float32))
    return out


@dataclasses.dataclass
class FleetRuntime:
    """Simulates E edge sites against one cloud for a window sequence.

    Planning goes through the engine registry (``repro.planning.ENGINES``):
    ``planning`` overrides the engine name explicitly, otherwise
    ``cfg.engine`` decides, and a fleet defaults to ``"batched"``.
    """

    topology: "FleetTopology"
    controller: "BudgetController"
    cfg: PlannerConfig = dataclasses.field(default_factory=PlannerConfig)
    planning: Optional[str] = None     # ENGINES name; None = cfg.engine
    use_kernel: Optional[bool] = None  # None=auto: Pallas kernel on TPU only
    interpret: bool = False            # kernel interpret mode (CPU testing)
    straggler_drop: Optional[Callable[[int, int, int], bool]] = None
    query_names: tuple = ("AVG", "VAR")
    window_period_ms: float = 1000.0   # virtual tumbling-window cadence
    staleness_deadline_ms: float = float("inf")
    sampling: str = "host"             # "host" | "device" (scan-parity RNG)
    retransmit_timeout_ms: Optional[float] = None
    max_retries: int = 0
    adaptive: Optional["AdaptiveSpec"] = None   # None = plan every window
    chaos: Optional["ChaosSpec"] = None         # None = fixed membership

    def __post_init__(self):
        from repro.planning import ENGINES
        from repro.streaming.events import AsyncTransport, ReorderCloudNode
        if self.sampling not in ("host", "device"):
            raise ValueError(f"sampling must be 'host' or 'device', got "
                             f"{self.sampling!r}")
        sites = self.topology.sites
        engine_name = self.planning or self.cfg.engine or "batched"
        self.engine = ENGINES.get(engine_name)
        self.engine.check(self.cfg)      # fail at construction, not mid-run
        self._adaptive_policy = None
        if self.adaptive is not None:
            if engine_name in ("host", "host_loop"):
                raise ValueError(
                    "adaptive re-planning cannot reuse host-engine plans "
                    "(plan_window draws samples inside the plan); use the "
                    "batched or sharded engine")
            from repro.adaptive import AdaptivePolicy
            self._adaptive_policy = AdaptivePolicy(
                self.adaptive, use_kernel=self.use_kernel,
                interpret=self.interpret)
        # trivial spec == no faults: run the exact legacy loop
        self._chaos_active = (self.chaos is not None
                              and not self.chaos.is_trivial)
        if self._chaos_active:
            if self.adaptive is not None:
                raise ValueError(
                    "chaos and adaptive re-planning cannot be combined: "
                    "the drift gate's cached plan would replay allocations "
                    "for dead sites")
            self.chaos.validate_topology(
                self.topology.n_sites, len(self.topology.region_names))
        self.transports = [AsyncTransport(
            drop_prob=s.link.drop_prob,
            seed=self.cfg.seed + s.site_id,
            cost_per_byte=s.link.cost_per_byte,
            latency_ms=s.link.latency_ms,
            jitter_ms=s.link.jitter_ms,
            bandwidth_bytes_per_ms=s.link.bandwidth_bytes_per_ms,
            retransmit_timeout_ms=self.retransmit_timeout_ms,
            max_retries=self.max_retries)
            for s in sites]
        self.clouds = [ReorderCloudNode(query_names=self.query_names,
                                        window_period_ms=self.window_period_ms,
                                        deadline_ms=self.staleness_deadline_ms)
                       for _ in sites]
        self.plan_seconds = 0.0
        self.plan_windows = 0
        self._rng = np.random.default_rng(self.cfg.seed)

    # ---------------------------------------------------------------- plan
    def _plan(self, wid: int, values: np.ndarray, counts: np.ndarray,
              budgets: np.ndarray) -> dict:
        """(E,k,N) window -> host-side plan arrays (or per-site payloads)."""
        t0 = time.perf_counter()
        out = self.engine.plan_fleet(values, counts, budgets, self.cfg,
                                     window_id=wid,
                                     use_kernel=self.use_kernel,
                                     interpret=self.interpret)
        self.plan_seconds += time.perf_counter() - t0
        self.plan_windows += 1
        return out

    def _payload(self, plan: dict, s: int, wid: int, values: np.ndarray,
                 counts: np.ndarray,
                 samples: Optional[np.ndarray] = None) -> EdgePayload:
        if "payloads" in plan:           # the host engine drew them already
            return plan["payloads"][s]
        from repro.api.registry import MODELS
        from repro.planning import assemble_payload
        if samples is not None:          # device sampling (scan-parity RNG)
            real = [samples[i, :int(min(int(plan["n_real"][s][i]),
                                        int(counts[i])))]
                    for i in range(len(counts))]
        else:
            real = _draw_real_np(self._rng, values, counts,
                                 plan["n_real"][s])
        return assemble_payload(MODELS.get(self.cfg.model), plan, s, wid,
                                real)

    # ----------------------------------------------------------------- run
    def run(self, fleet_windows: list[np.ndarray]) -> dict:
        """fleet_windows: list over time of (E, k, N) float arrays.

        Event-driven on a virtual clock: window ``wid`` is planned and sent
        at ``wid * window_period_ms``, each site's query is answered one
        period later from whatever its uplink has delivered by then, and
        late-but-within-deadline arrivals revise their window's entry in the
        (revised) estimate table retroactively.  Heterogeneous per-site
        ``LinkSpec.latency_ms`` therefore shows up as per-site window age
        (``freshness_ms``, ``site_arrival_lag_ms``) instead of being a dead
        accounting field.
        """
        E, k, n = fleet_windows[0].shape
        T = len(fleet_windows)
        qnames = self.query_names
        period = self.window_period_ms
        est = {q: np.full((T, E, k), np.nan) for q in qnames}    # revised
        est_q = {q: np.full((T, E, k), np.nan) for q in qnames}  # at query
        tru = {q: np.full((T, E, k), np.nan) for q in qnames}
        ages = np.full((T, E), np.nan)
        budget_history = []
        chaos_live = None
        if self._chaos_active:
            from repro.chaos import liveness_table
            chaos_live = liveness_table(self.chaos, T, E,
                                        self.topology.region_of())

        def _row(res):
            return {q: (np.asarray(res[q]) if len(res.get(q, [])) == k
                        else np.full(k, np.nan)) for q in qnames}

        def _apply(s, outcome):
            if outcome.kind == "revised":
                res = _row(self.clouds[s].query(outcome.reconstruction))
                for q in qnames:
                    est[q][outcome.window_id, s] = res[q]

        for wid, w in enumerate(fleet_windows):
            now = wid * period
            q_time = now + period
            w = np.asarray(w, np.float32)
            counts = np.full((E, k), n, np.int64)
            if self.straggler_drop is not None:
                for s in range(E):
                    for i in range(k):
                        if self.straggler_drop(wid, s, i):
                            counts[s, i] = 0
            live = None if chaos_live is None else chaos_live[wid]
            if live is None:
                budgets = np.maximum(np.floor(self.controller.budgets()),
                                     2.0)
            else:
                # the >=2 clamp would resurrect dead sites' zero budgets
                budgets = np.where(
                    live,
                    np.maximum(np.floor(self.controller.budgets(live=live)),
                               2.0),
                    0.0)
            budget_history.append(budgets)
            if self._adaptive_policy is not None:
                # the gate decides whether this window pays for planning;
                # the planner callback runs only on a re-plan, so _plan's
                # invocation count (plan_windows) stays honest
                plan, _ = self._adaptive_policy.step(
                    w, counts,
                    lambda: self._plan(wid, w, counts, budgets))
            else:
                plan = self._plan(wid, w, counts, budgets)
            if live is not None and "n_real" in plan:
                # the planner floors every stream at 1 sample even on a
                # zero budget; dead sites must truly ship nothing (and the
                # masked n_real keeps device sampling bitwise with scan)
                plan = dict(plan)
                plan["n_real"] = np.asarray(plan["n_real"]) * live[:, None]

            fleet_samples = None
            if self.sampling == "device" and "payloads" not in plan:
                # one jitted dispatch for the whole fleet, drawing from the
                # exact RNG streams the scan runtime consumes
                from repro.runtime.step import draw_fleet_samples
                fleet_samples = draw_fleet_samples(self.cfg.seed, wid, w,
                                                   plan["n_real"])
            split_on = self.controller.query_split is not None
            obs_err = np.zeros(E)
            obs_err_tail = np.zeros(E) if split_on else None
            lag_obs = np.full(E, np.nan)
            for s in range(E):
                if live is not None and not live[s]:
                    # dark site: nothing is planned-for or sent, but
                    # in-flight payloads still land and the cloud keeps
                    # gap-serving its freshest reconstruction
                    for ev in self.transports[s].drain(q_time):
                        _apply(s, self.clouds[s].ingest_event(
                            ev.payload, now_ms=ev.at_ms))
                    rec, age, _ = self.clouds[s].serve(wid, q_time)
                    res = _row(self.clouds[s].query(rec))
                    res_true = _row(self.clouds[s].query(
                        [w[s, i] for i in range(k)]))
                    for q in qnames:
                        est[q][wid, s] = res[q]
                        est_q[q][wid, s] = res[q]
                        tru[q][wid, s] = res_true[q]
                    ages[wid, s] = age
                    # no payload => no edge-local error observation; the
                    # live-masked controller update freezes this site's
                    # demand EWMA at its pre-outage value
                    obs_err[s] = np.nan
                    if split_on:
                        obs_err_tail[s] = np.nan
                    continue
                payload = self._payload(
                    plan, s, wid, w[s], counts[s],
                    samples=(None if fleet_samples is None
                             else fleet_samples[s]))
                payload = dataclasses.replace(payload, sent_at_ms=now)
                self.transports[s].send(payload, now_ms=now)
                lags = []
                for ev in self.transports[s].drain(q_time):
                    lags.append(ev.at_ms - ev.payload.sent_at_ms)
                    _apply(s, self.clouds[s].ingest_event(ev.payload,
                                                          now_ms=ev.at_ms))
                if lags:
                    lag_obs[s] = float(np.mean(lags))
                rec, age, _ = self.clouds[s].serve(wid, q_time)
                res = _row(self.clouds[s].query(rec))
                res_true = _row(self.clouds[s].query([w[s, i]
                                                      for i in range(k)]))
                for q in qnames:
                    est[q][wid, s] = res[q]
                    est_q[q][wid, s] = res[q]
                    tru[q][wid, s] = res_true[q]
                ages[wid, s] = age
                # edge-local error proxy: the edge knows its true window and
                # its own payload, so it can score the reconstruction the
                # cloud *would* produce — feeds the controller for free
                edge_rec = reconstruct_window(payload)
                t_mean = np.asarray([np.mean(w[s, i]) for i in range(k)])
                e_mean = np.asarray([np.mean(r) if len(r) else np.nan
                                     for r in edge_rec])
                obs_err[s] = np.nanmean(np.abs(e_mean - t_mean)
                                        / np.maximum(np.abs(t_mean), 1e-6))
                if split_on:
                    # tail-query proxy (VAR/MAX) for the split tranche
                    errs = []
                    for qfn in (Q.QUERIES["VAR"], Q.QUERIES["MAX"]):
                        t_q = np.asarray([qfn(w[s, i]) for i in range(k)])
                        e_q = np.asarray([qfn(r) for r in edge_rec])
                        errs.append(np.abs(e_q - t_q)
                                    / np.maximum(np.abs(t_q), 1e-6))
                    obs_err_tail[s] = np.nanmean(np.concatenate(errs))
            self.controller.update(obs_err, plan["r2"],
                                   objective=plan.get("objective"),
                                   arrival_lag=lag_obs,
                                   obs_err_tail=obs_err_tail, live=live)

        # drain in-flight payloads: late revisions and gap accounting
        for s in range(E):
            for ev in self.transports[s].drain(float("inf")):
                _apply(s, self.clouds[s].ingest_event(ev.payload,
                                                      now_ms=ev.at_ms))
            self.clouds[s].finalize(T)

        chaos_info = None
        if chaos_live is not None:
            from repro.chaos import chaos_metrics
            chaos_info = chaos_metrics(
                chaos_live, np.asarray(budget_history, np.float64),
                self.controller.equal_share, est, tru, qnames,
                self.topology.region_of(), self.topology.region_names)

        # aggregate errors/bytes/freshness through the shared roll-up the
        # scan runtime also reports through (repro.runtime.report)
        from repro.runtime.report import aggregate_fleet
        return aggregate_fleet(
            topology=self.topology, qnames=qnames,
            est=est, est_q=est_q, tru=tru, ages=ages,
            bytes_per_site=np.asarray([t.bytes_sent
                                       for t in self.transports], np.int64),
            cost_per_site=np.asarray([t.bytes_cost
                                      for t in self.transports]),
            gaps=sum(c.gaps for c in self.clouds),
            revisions=sum(c.revisions for c in self.clouds),
            late_drops=sum(c.late_drops for c in self.clouds),
            duplicates=sum(c.duplicates for c in self.clouds),
            retransmits=sum(t.retransmits for t in self.transports),
            arrival_lag_ms=self.controller.arrival_lag_ms,
            plan_seconds=self.plan_seconds, plan_windows=self.plan_windows,
            budget_history=np.asarray(budget_history),
            total_tuples=T * E * k * n,
            adaptive=(None if self._adaptive_policy is None
                      else self._adaptive_policy.counters()),
            chaos=chaos_info)


# ==========================================================================
# RunReport: one structured result shape for both engines
# ==========================================================================

@dataclasses.dataclass(frozen=True)
class RunReport:
    """Structured result of one scenario run.

    ``nrmse``/``nrmse_at_query`` are per-query scalar summaries (fleet-wide
    nan-mean); ``nrmse_per_stream`` keeps the full table ((k,) single-edge,
    (E, k) fleet).  Single-edge runs report one region named ``"local"``.
    ``raw`` is the engine's native dict for anything not lifted here
    (window ages, budget history, revised-window flags, ...).
    """

    scenario: Optional[ScenarioConfig]
    n_sites: int
    nrmse: dict                    # {query: float}
    nrmse_at_query: dict           # {query: float}
    nrmse_per_stream: dict         # {query: np.ndarray}
    region_nrmse: dict             # {region: {query: float}}
    wan_bytes: int
    wan_cost: float
    full_bytes: int
    wan_bytes_by_region: dict
    wan_cost_by_region: dict
    gaps: int
    revisions: int
    late_drops: int
    duplicates: int
    retransmits: int
    freshness_ms: dict             # {"p50_ms": .., "p99_ms": ..}
    freshness_by_region: dict
    plan_seconds: float
    raw: dict
    # adaptive re-planning (repro.adaptive); None = plan-every-window run
    planner_invocations: Optional[int] = None
    plans_reused: Optional[int] = None
    detection_lag_windows: Optional[float] = None
    # chaos fault injection (repro.chaos); None = fixed-membership run
    recovery_windows: Optional[float] = None
    down_site_windows: Optional[int] = None
    availability_by_region: Optional[dict] = None
    outage_nrmse: Optional[dict] = None
    steady_nrmse: Optional[dict] = None
    # scan runtimes: the window step's cross-device collectives a window
    # (0 on one device); None = a runtime that does not count them
    exchange_all_gathers: Optional[int] = None
    exchange_all_reduces: Optional[int] = None
    exchange_gather_bytes: Optional[int] = None

    @property
    def wan_fraction(self) -> float:
        """WAN bytes as a fraction of shipping every tuple raw."""
        return self.wan_bytes / max(self.full_bytes, 1)

    def to_dict(self) -> dict:
        """JSON-friendly summary (drops the raw arrays)."""
        d = {
            "scenario": (None if self.scenario is None
                         else self.scenario.to_dict()),
            "n_sites": self.n_sites,
            "nrmse": dict(self.nrmse),
            "nrmse_at_query": dict(self.nrmse_at_query),
            "region_nrmse": {r: dict(qs)
                             for r, qs in self.region_nrmse.items()},
            "wan_bytes": self.wan_bytes,
            "wan_cost": self.wan_cost,
            "full_bytes": self.full_bytes,
            "wan_bytes_by_region": dict(self.wan_bytes_by_region),
            "wan_cost_by_region": dict(self.wan_cost_by_region),
            "gaps": self.gaps,
            "revisions": self.revisions,
            "late_drops": self.late_drops,
            "duplicates": self.duplicates,
            "retransmits": self.retransmits,
            "freshness_ms": dict(self.freshness_ms),
            "plan_seconds": self.plan_seconds,
        }
        if self.planner_invocations is not None:
            d["planner_invocations"] = self.planner_invocations
            d["plans_reused"] = self.plans_reused
            d["detection_lag_windows"] = self.detection_lag_windows
        if self.down_site_windows is not None:
            d["recovery_windows"] = self.recovery_windows
            d["down_site_windows"] = self.down_site_windows
            d["availability_by_region"] = dict(self.availability_by_region)
            d["outage_nrmse"] = dict(self.outage_nrmse)
            d["steady_nrmse"] = dict(self.steady_nrmse)
        for f in EXCHANGE_COUNTERS:
            if getattr(self, f) is not None:
                d[f] = getattr(self, f)
        return d

    def summary(self) -> str:
        errs = " ".join(f"{q}={v:.4f}" for q, v in self.nrmse.items())
        return (f"{errs} wan={self.wan_bytes}B ({self.wan_fraction:.0%} of "
                f"raw) cost={self.wan_cost:.0f} gaps={self.gaps} "
                f"age_p99={self.freshness_ms['p99_ms']:.0f}ms")


def _report_single(scenario, r: dict) -> RunReport:
    nrmse = {q: float(np.nanmean(v)) for q, v in r["nrmse"].items()}
    nrmse_q = {q: float(np.nanmean(v))
               for q, v in r["nrmse_at_query"].items()}
    return RunReport(
        scenario=scenario, n_sites=1,
        nrmse=nrmse, nrmse_at_query=nrmse_q,
        nrmse_per_stream={q: np.asarray(v) for q, v in r["nrmse"].items()},
        region_nrmse={"local": nrmse},
        wan_bytes=int(r["wan_bytes"]), wan_cost=float(r.get("wan_cost", 0.0)),
        full_bytes=int(r["full_bytes"]),
        wan_bytes_by_region={"local": int(r["wan_bytes"])},
        wan_cost_by_region={"local": float(r.get("wan_cost", 0.0))},
        gaps=int(r["gaps"]), revisions=int(r["revisions"]),
        late_drops=int(r["late_drops"]), duplicates=int(r["duplicates"]),
        retransmits=int(r.get("retransmits", 0)),
        freshness_ms=dict(r["freshness_ms"]),
        freshness_by_region={"local": dict(r["freshness_ms"])},
        plan_seconds=float(r["plan_seconds"]),
        raw=r, **_exchange_counters(r))


def _report_fleet(scenario, r: dict, n_sites: int) -> RunReport:
    return RunReport(
        scenario=scenario, n_sites=n_sites,
        nrmse=dict(r["fleet_nrmse"]),
        nrmse_at_query=dict(r["fleet_nrmse_at_query"]),
        nrmse_per_stream={q: np.asarray(v)
                          for q, v in r["site_nrmse"].items()},
        region_nrmse={reg: dict(qs)
                      for reg, qs in r["region_nrmse"].items()},
        wan_bytes=int(r["wan_bytes"]), wan_cost=float(r["wan_cost"]),
        full_bytes=int(r["full_bytes"]),
        wan_bytes_by_region=dict(r["wan_bytes_by_region"]),
        wan_cost_by_region=dict(r["wan_cost_by_region"]),
        gaps=int(r["gaps"]), revisions=int(r["revisions"]),
        late_drops=int(r["late_drops"]), duplicates=int(r["duplicates"]),
        retransmits=int(r.get("retransmits", 0)),
        freshness_ms=dict(r["freshness_ms"]),
        freshness_by_region={reg: dict(f)
                             for reg, f in r["freshness_by_region"].items()},
        plan_seconds=float(r["plan_seconds"]),
        raw=r,
        planner_invocations=(int(r["planner_invocations"])
                             if "planner_invocations" in r else None),
        plans_reused=(int(r["plans_reused"])
                      if "plans_reused" in r else None),
        detection_lag_windows=(float(r["detection_lag_windows"])
                               if "detection_lag_windows" in r else None),
        recovery_windows=(float(r["recovery_windows"])
                          if "recovery_windows" in r else None),
        down_site_windows=(int(r["down_site_windows"])
                           if "down_site_windows" in r else None),
        availability_by_region=(dict(r["availability_by_region"])
                                if "availability_by_region" in r else None),
        outage_nrmse=(dict(r["outage_nrmse"])
                      if "outage_nrmse" in r else None),
        steady_nrmse=(dict(r["steady_nrmse"])
                      if "steady_nrmse" in r else None),
        **_exchange_counters(r))


def _exchange_counters(r: dict) -> dict:
    return {f: int(r[f]) for f in EXCHANGE_COUNTERS if f in r}


# ==========================================================================
# Experiment: scenario in, report out
# ==========================================================================

@dataclasses.dataclass
class Experiment:
    """One runnable experiment, built declaratively from a scenario.

    ``straggler_drop`` is the only non-serializable knob: a callable
    ``(wid, stream) -> bool`` (single-edge) or ``(wid, site, stream) ->
    bool`` (fleet) injected at build time for fault studies.
    """

    scenario: ScenarioConfig
    runtime: object                    # SingleEdgeRuntime | FleetRuntime

    @classmethod
    def from_scenario(cls, scenario: ScenarioConfig,
                      straggler_drop: Optional[Callable] = None,
                      planning: Optional[str] = None,
                      use_kernel: Optional[bool] = None,
                      interpret: bool = False) -> "Experiment":
        from repro.streaming.events import AsyncTransport
        from repro.streaming.runtime import CloudNode, EdgeNode
        tspec = scenario.transport
        if scenario.runtime in ("scan", "scan_steps", "scan_sharded"):
            from repro.runtime.scan import ScanRuntime
            if straggler_drop is not None:
                raise ValueError("runtime='scan' plans full windows only; "
                                 "straggler_drop needs runtime='event'")
            if planning is not None:
                scenario = dataclasses.replace(
                    scenario, planner=dataclasses.replace(scenario.planner,
                                                          engine=planning))
            rt_cls = ScanRuntime
            if scenario.runtime == "scan_sharded":
                from repro.runtime.sharded import ShardedScanRuntime
                rt_cls = ShardedScanRuntime
            runtime = rt_cls.from_scenario(scenario,
                                           use_kernel=use_kernel,
                                           interpret=interpret)
            return cls(scenario=scenario, runtime=runtime)
        if scenario.is_fleet:
            topo = scenario.topology.build(cls._fleet_k(scenario))
            controller = cls._build_controller(scenario, topo)
            runtime = FleetRuntime(
                topology=topo, controller=controller, cfg=scenario.planner,
                planning=planning, use_kernel=use_kernel, interpret=interpret,
                straggler_drop=straggler_drop,
                query_names=tuple(scenario.queries),
                window_period_ms=tspec.window_period_ms,
                staleness_deadline_ms=(float("inf")
                                       if tspec.staleness_deadline_ms is None
                                       else tspec.staleness_deadline_ms),
                retransmit_timeout_ms=tspec.retransmit_timeout_ms,
                max_retries=tspec.max_retries,
                adaptive=scenario.adaptive,
                chaos=scenario.chaos)
            return cls(scenario=scenario, runtime=runtime)

        # single edge — the E=1 degenerate fleet.  A one-site topology
        # contributes its link's WAN character; otherwise TransportSpec
        # describes the uplink directly.
        drop, cost, lat, jit = (tspec.drop_prob, 1.0, tspec.latency_ms,
                                tspec.jitter_ms)
        bandwidth = tspec.bandwidth_bytes_per_ms
        if scenario.topology is not None:
            link = scenario.topology.build(1).sites[0].link
            drop, cost, lat, jit = (link.drop_prob, link.cost_per_byte,
                                    link.latency_ms, link.jitter_ms)
            bandwidth = link.bandwidth_bytes_per_ms
        runtime = SingleEdgeRuntime(
            edge=EdgeNode(cfg=scenario.planner,
                          budget_fraction=scenario.budget_fraction,
                          method=scenario.method,
                          straggler_drop=straggler_drop),
            cloud=CloudNode(query_names=tuple(scenario.queries)),
            transport=AsyncTransport(drop_prob=drop, seed=scenario.planner.seed,
                                     cost_per_byte=cost, latency_ms=lat,
                                     jitter_ms=jit,
                                     bandwidth_bytes_per_ms=bandwidth,
                                     retransmit_timeout_ms=(
                                         tspec.retransmit_timeout_ms),
                                     max_retries=tspec.max_retries),
            window_period_ms=tspec.window_period_ms,
            staleness_deadline_ms=tspec.staleness_deadline_ms)
        return cls(scenario=scenario, runtime=runtime)

    # ------------------------------------------------------------- helpers
    @staticmethod
    def _fleet_k(scenario: ScenarioConfig) -> int:
        return int(scenario.data.options.get("k", 6))

    @staticmethod
    def _build_controller(scenario: ScenarioConfig, topo) -> "BudgetController":
        from repro.fleet.controller import BudgetController
        spec = scenario.controller or ControllerSpec()
        E = topo.n_sites
        total = (scenario.budget_fraction * E * topo.k
                 * scenario.data.window)
        link_cost = np.asarray([s.link.cost_per_byte for s in topo.sites])
        return BudgetController(
            total_budget=total, n_sites=E, mode=spec.mode,
            floor_mult=spec.floor_mult, ceil_mult=spec.ceil_mult,
            ewma=spec.ewma,
            link_cost=link_cost if spec.link_cost_aware else None,
            cost_aware=spec.link_cost_aware,
            demand_signal=spec.demand_signal,
            query_split=spec.query_split,
            tail_demand_signal=spec.tail_demand_signal)

    def make_windows(self):
        """Materialize the scenario's window sequence (deterministic)."""
        from repro.api.registry import DATASETS
        data = self.scenario.data
        if self.scenario.is_fleet:
            from repro.data.streams import fleet_windows
            topo_spec = self.scenario.topology
            gen = DATASETS.get(data.dataset)
            vals, _ = gen(n_sites=topo_spec.n_sites,
                          n_regions=topo_spec.n_regions,
                          n_points=data.n_points, seed=data.seed,
                          window=data.window, **dict(data.options))
            return fleet_windows(vals, data.window)
        from repro.data.streams import windows_from_matrix
        vals, _ = data.generate()
        return windows_from_matrix(vals, data.window)

    # ----------------------------------------------------------------- run
    def run(self, windows=None) -> RunReport:
        if windows is None:
            windows = self.make_windows()
        r = self.runtime.run(windows)
        if isinstance(self.runtime, FleetRuntime):
            return _report_fleet(self.scenario, r,
                                 self.runtime.topology.n_sites)
        if getattr(self.runtime, "is_scan", False):
            if self.runtime.n_sites > 1:
                return _report_fleet(self.scenario, r, self.runtime.n_sites)
            return _report_single(self.scenario, r)
        return _report_single(self.scenario, r)
