"""ScanRuntime — the on-device streaming engine.

Where the event loop (``repro.api.experiment``) re-enters JAX once per
window, this runtime puts each window of the sequence straight onto its
device(s), stacks them there into one device pool and runs the whole
ingest → plan → sample → impute → serve cycle as a single
``lax.scan`` over window ids with a donated :class:`RuntimeState` carry —
E=256+ sites over thousands of windows execute as one XLA while-loop with
no per-window host round-trips.

Two execution modes share the compiled step:

  * ``mode="scan"`` — one ``lax.scan`` over all T windows (production).
  * ``mode="steps"`` — T length-1 scans of the *same* jitted function:
    the incremental (checkpointable) cadence.  XLA unrolls the
    trip-count-1 while loop, which re-fuses the body's reductions, so a
    steps run matches a scan run on the discrete trajectory (budgets,
    samples, WAN bytes) and tracks its float tables to f32 association
    (pinned in tests/test_scan_runtime.py).

Two result fidelities:

  * ``collect="payloads"`` — the scan additionally stacks each window's
    samples and plan arrays; the host then *replays* them through the
    event path's own ``assemble_payload`` / ``reconstruct_window`` /
    ``QUERIES`` code.  Sampling is integer-PRNG exact and the replay IS
    the event path's code, so given the same plans the event loop
    reproduces this report bit-for-bit (pinned by plan injection in
    tests/test_scan_runtime.py).  The compiled in-scan planner itself can
    differ from the standalone host executable by f32 association — XLA
    fuses reductions differently inside a while-loop body — which may
    flip an occasional allocation boundary; end-to-end scan-vs-event
    agreement is therefore pinned within tolerance, not bitwise.  Memory
    is O(T·E·k·N) — the parity/report mode for moderate T.
  * ``collect="estimates"`` — queries are answered on device in f32 and
    only (T, E, k) tables come back.  Approximate (device float order),
    O(T·E·k) memory — the throughput mode benchmarks use.

Construction mirrors ``Experiment.from_scenario``; scenarios opt in with
``runtime="scan"`` (or ``"scan_steps"``), validated by the RUNTIMES
registry entry in :mod:`repro.runtime`.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.api.registry import ENGINES, MODELS
from repro.core import queries as Q
from repro.parallel.sharding import EXCHANGE_COUNTERS
from repro.runtime.controller import CtrlParams
from repro.runtime.state import init_state
from repro.runtime.step import (PAYLOAD_PLAN_FIELDS, SCAN_QUERIES,
                                make_window_step)


def placed_bytes(tree) -> int:
    """Bytes a pytree of placed arrays holds on its devices, summed over
    every device's shard (a replicated array counts once per device)."""
    return sum(s.data.nbytes for x in jax.tree.leaves(tree)
               for s in x.addressable_shards)


class _Prepared(NamedTuple):
    """One run's compiled scan fn and its host arguments, not yet placed."""

    fn: Callable
    state: "RuntimeState"
    xs: object                         # wids, or (wids, live rows)
    host: list                         # (run sites, k, N) f32 host windows
    staged: int                        # bytes the host copied to make them
    live_tbl: Optional[np.ndarray]
    k: int
    n: int
    T: int
    w0: int


@dataclasses.dataclass
class ScanRuntime:
    """Scan-based fleet (or E=1) runtime; zero-latency WAN semantics."""

    cfg: "PlannerConfig"
    ctrl: CtrlParams
    topology: Optional["FleetTopology"] = None   # None => single edge
    query_names: tuple = ("AVG", "VAR")
    mode: str = "scan"                 # "scan" | "steps"
    collect: str = "payloads"          # "payloads" | "estimates"
    method: str = "model"              # single-edge: "model" | model name
    budget_fraction: float = 0.25      # single-edge per-window budget frac
    use_kernel: Optional[bool] = None
    interpret: bool = False
    adaptive: Optional["AdaptiveSpec"] = None   # None = plan every window
    chaos: Optional["ChaosSpec"] = None         # None = fixed membership
    is_scan = True                     # duck-typed runtime dispatch

    def __post_init__(self):
        if self.mode not in ("scan", "steps"):
            raise ValueError(f"mode must be 'scan' or 'steps', got "
                             f"{self.mode!r}")
        if self.collect not in ("payloads", "estimates"):
            raise ValueError(f"collect must be 'payloads' or 'estimates', "
                             f"got {self.collect!r}")
        for q in self.query_names:
            if q not in SCAN_QUERIES:
                raise ValueError(
                    f"query {q!r} has no on-device mirror; the scan runtime "
                    f"supports {SCAN_QUERIES}")
        cfg = self.cfg
        if self.method != "model":
            if self.method not in MODELS:
                raise ValueError(
                    f"method {self.method!r}: the scan runtime plans through "
                    f"the model families ('model' or {MODELS.names()}); "
                    f"baselines need runtime='event'")
            cfg = dataclasses.replace(cfg, model=self.method)
        self.cfg_eff = cfg
        from repro.planning.batched import BatchedEngine
        self.engine = ENGINES.get(cfg.engine or "batched")
        if not isinstance(self.engine, BatchedEngine):
            raise ValueError(
                f"engine {self.engine.name!r} cannot run inside lax.scan; "
                f"the scan runtime needs the 'batched' or 'sharded' engine")
        self.engine.check(cfg)
        if self.adaptive is not None and self.topology is None:
            raise ValueError("adaptive re-planning requires a fleet "
                             "topology (>1 site); single-edge scans plan "
                             "per window by construction")
        if self.chaos is not None and self.topology is None:
            raise ValueError("chaos fault injection requires a fleet "
                             "topology; a single edge has no membership "
                             "to vary")
        # trivial spec == no faults: compile the exact legacy graph
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
        self.spec = MODELS.get(cfg.model)
        self.n_sites = 1 if self.topology is None else self.topology.n_sites
        if self.topology is not None:
            self._cost = np.asarray([s.link.cost_per_byte
                                     for s in self.topology.sites])
        else:
            self._cost = np.ones(1)
        self._fns = {}                 # static_exec key -> jitted scan fn
        # the placed windows -> the (P, E, k, N) pool, on the device(s)
        # that hold them
        self._stack = jax.jit(jnp.stack)
        # site rows the compiled step carries: == n_sites here; the sharded
        # runtime overrides it with E padded to the device multiple
        self._run_sites = self.n_sites
        self._calls = 0                # run calls so far: the spans' `call`
        # the window step's cross-device collectives a window: none on one
        # device; the sharded runtime counts its own as it traces the step
        self._exchange = dict.fromkeys(EXCHANGE_COUNTERS, 0)

    @classmethod
    def from_scenario(cls, scenario, *, use_kernel=None, interpret=False,
                      collect: str = "payloads") -> "ScanRuntime":
        """Build from a ScenarioConfig with ``runtime="scan"|"scan_steps"``
        (the same geometry/budget wiring as ``Experiment.from_scenario``)."""
        from repro.api.scenario import ControllerSpec
        spec = scenario.controller or ControllerSpec()
        mode = "steps" if scenario.runtime == "scan_steps" else "scan"
        if scenario.is_fleet:
            k = int(scenario.data.options.get("k", 6))
            topo = scenario.topology.build(k)
            E = topo.n_sites
            total = (scenario.budget_fraction * E * topo.k
                     * scenario.data.window)
            discount = None
            if spec.link_cost_aware:
                discount = CtrlParams.make_cost_discount(
                    [s.link.cost_per_byte for s in topo.sites])
            ctrl = CtrlParams(total_budget=total, n_sites=E, mode=spec.mode,
                              floor_mult=spec.floor_mult,
                              ceil_mult=spec.ceil_mult, ewma=spec.ewma,
                              demand_signal=spec.demand_signal,
                              cost_discount=discount)
            return cls(cfg=scenario.planner, ctrl=ctrl, topology=topo,
                       query_names=tuple(scenario.queries), mode=mode,
                       collect=collect, use_kernel=use_kernel,
                       interpret=interpret, adaptive=scenario.adaptive,
                       chaos=scenario.chaos)
        # single edge: the controller is inert (one site, static budget)
        ctrl = CtrlParams(total_budget=1.0, n_sites=1, mode="static")
        topo = (scenario.topology.build(1)
                if scenario.topology is not None else None)
        rt = cls(cfg=scenario.planner, ctrl=ctrl, topology=None,
                 query_names=tuple(scenario.queries), mode=mode,
                 collect=collect, method=scenario.method,
                 budget_fraction=scenario.budget_fraction,
                 use_kernel=use_kernel, interpret=interpret)
        if topo is not None:
            rt._cost = np.asarray([topo.sites[0].link.cost_per_byte])
        return rt

    # ------------------------------------------------------------- compile
    def _plan_fn(self, values, counts, budgets):
        return self.engine._run(values, counts, budgets, self.cfg_eff,
                                use_kernel=self.use_kernel,
                                interpret=self.interpret)

    def _scan_fn(self, static_exec: Optional[tuple]):
        """Jitted (state, wids, pool) -> (state, ys); donated carry."""
        if static_exec not in self._fns:
            exec_arr = (None if static_exec is None
                        else np.asarray(static_exec, np.float32))

            def fn(state, xs, pool):
                # xs: wids, or (wids, live rows) on an active chaos run
                step = make_window_step(
                    pool, seed=self.cfg_eff.seed, plan_fn=self._plan_fn,
                    qnames=self.query_names, multi=self.spec.multi,
                    mean=self.spec.mean, ctrl=self.ctrl,
                    static_exec_budgets=exec_arr, collect=self.collect,
                    adaptive=self.adaptive, use_kernel=self.use_kernel,
                    interpret=self.interpret, chaos=self._chaos_active)
                return jax.lax.scan(step, state, xs)

            self._fns[static_exec] = jax.jit(fn, donate_argnums=0)
        return self._fns[static_exec]

    def _static_exec(self, k: int, n: int) -> Optional[tuple]:
        """Executed budgets when they are window-invariant, computed on the
        host in f64 exactly as the event loop computes them (so the f32
        device floor can never flip a boundary case)."""
        if self.topology is None:
            budget = max(int(self.budget_fraction * k * n), 2)
            return (float(budget),)
        if self.ctrl.mode == "static":
            eq = self.ctrl.equal_share
            b = np.minimum(np.full(self.n_sites, eq),
                           np.full(self.n_sites, self.ctrl.ceil_mult * eq))
            return tuple(np.maximum(np.floor(b), 2.0).tolist())
        return None                    # rebalance: budgets live on device

    # ------------------------------------------------- overridable plumbing
    # The sharded runtime (repro.runtime.sharded) reuses this run() driver
    # and specializes exactly four seams: how a resumed state enters the
    # run (padding), which liveness table the step consumes (padding
    # columns as permanently-dead sites), how state, inputs and windows
    # land on the device(s), and how results/state leave (slicing the
    # padding back off).  Windows are padded to the run's site rows in
    # _stage, which both runtimes share.

    def _adopt_state(self, state):
        """A checkpointed RuntimeState entering this run's site layout."""
        return state

    def _liveness_table(self, T: int, w0: int):
        """(T, run_sites) bool mask for the step, or None (all live)."""
        if not self._chaos_active:
            return None
        from repro.chaos import liveness_table
        return liveness_table(self.chaos, T, self.n_sites,
                              self.topology.region_of(), first_window=w0)

    def _place(self, state, xs, host):
        """Host (state, xs, windows) -> the scan fn's device arguments:
        each window goes to the device on its own and the pool is stacked
        there."""
        return (jax.tree.map(jnp.asarray, state),
                jax.tree.map(jnp.asarray, xs),
                self._stack(jax.device_put(host)))

    def _finalize(self, ys, state, live_tbl):
        """Host-side (ys, final_state, live_tbl) right after the scan."""
        return ys, state, live_tbl

    # ----------------------------------------------------------------- run
    def _stage(self, values) -> tuple:
        """One window as the device takes it: a C-contiguous float32
        (run sites, k, N) host array, ``values`` itself where it already is
        one, and the bytes the host copied to make it (0 where none)."""
        src = np.asarray(values)
        w = np.ascontiguousarray(src, np.float32)
        pad = self._run_sites - w.shape[0]
        if pad:
            w = np.concatenate([w, np.zeros((pad,) + w.shape[1:], w.dtype)])
        return w, 0 if np.may_share_memory(w, src) else w.nbytes

    def _prepare(self, windows, n_windows, state, first_window):
        """Stage the windows, build or adopt the carry and fetch the
        compiled scan fn: what ``run`` and ``lower`` share before
        ``_place``."""
        single = self.topology is None
        if single:
            k = int(windows[0].k)
            n = int(np.max(np.asarray(windows[0].counts)))
            for w in windows:
                if not np.all(np.asarray(w.counts) == n):
                    raise ValueError("the scan runtime requires full "
                                     "windows (uniform counts)")
            staged = [self._stage(np.asarray(w.values)[None])
                      for w in windows]
        else:
            staged = [self._stage(w) for w in windows]
            _, k, n = staged[0][0].shape
        host = [w for w, _ in staged]
        P = len(host)
        T = int(n_windows) if n_windows is not None else P

        static_exec = self._static_exec(k, n)
        eq = (static_exec[0] if single else self.ctrl.equal_share)
        if state is None:
            state = init_state(self._run_sites, k, float(eq))
            w0 = int(first_window) if first_window is not None else 0
        else:
            w0 = (int(first_window) if first_window is not None
                  else int(np.asarray(state.window_id)))
            state = self._adopt_state(state)
        if self.adaptive is not None and state.adaptive is None:
            # fresh (or pre-adaptive) carry: a zero-filled plan with the
            # exact structure/shapes/dtypes the live plan branch produces,
            # via eval_shape, so both lax.cond branches agree
            from repro.adaptive import make_adaptive_carry
            plan_shapes = jax.eval_shape(
                self._plan_fn,
                jax.ShapeDtypeStruct((self._run_sites, k, n), jnp.float32),
                jax.ShapeDtypeStruct((self._run_sites, k), jnp.int32),
                jax.ShapeDtypeStruct((self._run_sites,), jnp.float32))
            state = dataclasses.replace(
                state,
                adaptive=make_adaptive_carry(self._run_sites, k, plan_shapes))
        live_tbl = self._liveness_table(T, w0)
        if live_tbl is not None and state.chaos is None:
            # fresh run (or a legacy checkpoint resumed into chaos/padding):
            # empty gap-serving memory, everyone live
            from repro.chaos import make_chaos_carry
            state = dataclasses.replace(
                state, chaos=make_chaos_carry(self._run_sites, k,
                                              self.query_names))
        wids = np.arange(w0, w0 + T, dtype=np.int32)
        xs = wids if live_tbl is None else (wids, live_tbl)
        return _Prepared(fn=self._scan_fn(static_exec), state=state, xs=xs,
                         host=host, staged=sum(b for _, b in staged),
                         live_tbl=live_tbl, k=k, n=n, T=T, w0=w0)

    def lower(self, windows, n_windows: Optional[int] = None):
        """The ``jax.stages.Lowered`` scan program a fresh ``run`` over
        these windows executes, its arguments placed as ``run`` places
        them.  ``.compile().as_text()`` shows what runs on the device —
        e.g. one ``tpu_custom_call`` per Pallas kernel on a TPU."""
        prep = self._prepare(windows, n_windows, None, None)
        return prep.fn.lower(*self._place(prep.state, prep.xs, prep.host))

    def run(self, windows, n_windows: Optional[int] = None, *,
            state=None, first_window: Optional[int] = None) -> dict:
        """windows: list of (E, k, N) arrays (fleet) or WindowBatch (E=1).

        ``n_windows`` extends the run past the materialized pool by cycling
        it (window ``wid`` reads pool slot ``wid % P``) — the sustained-
        throughput configuration benchmarks use.

        ``state``/``first_window`` resume a run from a checkpointed
        :class:`~repro.runtime.state.RuntimeState` carry: window ids start
        at ``first_window`` (default ``state.window_id`` — the cursor a
        checkpoint froze) so RNG keys, pool slots and controller EWMAs
        continue exactly where the saved run stopped; the result dict's
        ``final_state`` holds the end-of-run carry for the next checkpoint.
        Resuming is bit-for-bit: a full run equals any split of it
        (tests/test_ckpt.py).

        Each call writes five host spans on the profiler's clock, in turn:
        ``scan.prepare``, ``scan.place``, ``scan.execute`` (dispatch and
        wait), ``scan.readback`` and ``scan.report``, each with the args
        ``call`` (this runtime's count of ``run`` calls) and ``windows``
        (T); see docs/runtime.md, "Tracing a serving process".
        ``scan.place`` also carries ``devices`` (the devices the arguments
        went to), ``bytes`` (what they hold there, replicas counted on
        every device) and ``staged_bytes`` (what the host copied to cast,
        make contiguous or pad the windows before they went; 0 when every
        window went as the caller gave it).  The result's ``exchange_*``
        counters are the window step's cross-device collectives a window
        (0 on one device).
        """
        single = self.topology is None
        self._calls += 1
        T = len(windows) if n_windows is None else int(n_windows)

        def span(name):
            return jax.profiler.TraceAnnotation(name, call=self._calls,
                                                windows=T)

        with span("scan.prepare"):
            fn, state, xs, host, staged, live_tbl, k, n, T, w0 = \
                self._prepare(windows, n_windows, state, first_window)
        with span("scan.place") as place:
            state, xs, pool = self._place(state, xs, host)
            if place.is_enabled():
                place.set_metadata(devices=len(pool.sharding.device_set),
                                   bytes=placed_bytes((state, xs, pool)),
                                   staged_bytes=staged)

        with span("scan.execute"):
            t0 = time.perf_counter()
            if self.mode == "scan":
                state, ys = fn(state, xs, pool)
            else:
                chunks = []
                for w in range(T):
                    state, y = fn(state,
                                  jax.tree.map(lambda a: a[w:w + 1], xs),
                                  pool)
                    chunks.append(y)
                ys = jax.tree.map(lambda *xs_: jnp.concatenate(xs_), *chunks)
            ys = jax.block_until_ready(ys)
            scan_seconds = time.perf_counter() - t0

        with span("scan.readback"):
            ys = jax.tree.map(np.asarray, ys)
            state = jax.tree.map(np.asarray, state)
            ys, state, live_tbl = self._finalize(ys, state, live_tbl)
            if self.collect == "payloads":
                est, tru, bytes_site, cost_site = self._replay(
                    ys, host, T, windows, w0=w0, live_tbl=live_tbl)
            else:
                est = {q: np.asarray(ys["est"][q], np.float64)
                       for q in self.query_names}
                tru = {q: np.asarray(ys["tru"][q], np.float64)
                       for q in self.query_names}
                bytes_site = ys["bytes"].astype(np.int64).sum(axis=0)
                cost_site = bytes_site * self._cost
                if single:
                    est = {q: v[:, 0] for q, v in est.items()}
                    tru = {q: v[:, 0] for q, v in tru.items()}

        extras = {
            "final_state": state,
            "scan_seconds": scan_seconds,
            "windows_per_sec": T / max(scan_seconds, 1e-9),
            "mode": self.mode,
            "collect": self.collect,
            "stream_totals": {"count": state.totals.count,
                              "s1": state.totals.s1, "s2": state.totals.s2},
            "controller_demand": state.controller.demand,
            "plan_raw": {f: ys[f] for f in
                         ("budgets", "obs_err", "r2", "objective")},
            "bytes_history": ys["bytes"],
            **self._exchange,
        }
        with span("scan.report"):
            if single:
                return self._result_single(est, tru, bytes_site, cost_site,
                                           T, k, n, scan_seconds, extras)
            return self._result_fleet(est, tru, bytes_site, cost_site, ys,
                                      state, T, k, n, scan_seconds, extras,
                                      live_tbl=live_tbl)

    # ------------------------------------------------------------- results
    def _replay(self, ys, host, T, windows, w0: int = 0, live_tbl=None):
        """Host replay of the collected payloads through the event path's
        own assemble/reconstruct/query code — the bitwise report mode.

        ``host`` holds the staged windows the pool was stacked from.
        ``w0`` is the first window id of a resumed run: output row ``t``
        holds window ``w0 + t``, which read pool slot ``(w0 + t) % P``.

        ``live_tbl`` (chaos runs): dead (window, site) cells skip payload
        assembly entirely — zero WAN bytes — and are gap-served from the
        site's last live reconstruction, mirroring
        ``ReorderCloudNode.serve`` (NaN before the first live window).
        """
        from repro.core.reconstruct import reconstruct_window
        from repro.planning.engine import assemble_payload
        E, k = self.n_sites, host[0].shape[1]
        P = len(host)
        qnames = self.query_names
        est = {q: np.full((T, E, k), np.nan) for q in qnames}
        tru = {q: np.full((T, E, k), np.nan) for q in qnames}
        bytes_site = np.zeros(E, np.int64)
        cost_site = np.zeros(E, np.float64)
        samples = ys["samples"]
        last_rec = [None] * E          # gap-serving memory (chaos only)
        for t in range(T):
            plan_t = {f: ys[f][t] for f in PAYLOAD_PLAN_FIELDS}
            vals = host[(w0 + t) % P]
            for s in range(E):
                if live_tbl is not None and not live_tbl[t, s]:
                    vals_true = [vals[s, i] for i in range(k)]
                    if last_rec[s] is not None:
                        for q in qnames:
                            fn = Q.QUERIES[q]
                            est[q][t, s] = [fn(r) for r in last_rec[s]]
                            tru[q][t, s] = [fn(r) for r in vals_true]
                    else:
                        for q in qnames:
                            fn = Q.QUERIES[q]
                            tru[q][t, s] = [fn(r) for r in vals_true]
                    continue
                real = [samples[t, s, i, :int(plan_t["n_real"][s, i])]
                        for i in range(k)]
                payload = assemble_payload(self.spec, plan_t, s, w0 + t,
                                           real)
                nb = payload.wan_bytes()
                bytes_site[s] += nb
                cost_site[s] += nb * self._cost[s]
                rec = reconstruct_window(payload)
                if live_tbl is not None:
                    last_rec[s] = rec
                if self.topology is None:
                    # event oracle computes truth from the original window
                    # values (possibly f64), not the f32 device pool
                    w = windows[(w0 + t) % P]
                    true_rows = [np.asarray(w.values[i, :int(w.counts[i])])
                                 for i in range(k)]
                else:
                    true_rows = [vals[s, i] for i in range(k)]
                for q in qnames:
                    fn = Q.QUERIES[q]
                    est[q][t, s] = [fn(r) for r in rec]
                    tru[q][t, s] = [fn(r) for r in true_rows]
        if self.topology is None:
            est = {q: v[:, 0] for q, v in est.items()}
            tru = {q: v[:, 0] for q, v in tru.items()}
        return est, tru, bytes_site, cost_site

    def _result_single(self, est, tru, bytes_site, cost_site, T, k, n,
                       scan_seconds, extras):
        from repro.streaming.events import freshness_percentiles
        ages = np.zeros(T)             # zero-latency: served the moment due
        nrmse = {q: Q.nrmse_table(est[q].T, tru[q].T)
                 for q in self.query_names}
        return {
            "nrmse": nrmse,
            "nrmse_at_query": dict(nrmse),
            "wan_bytes": int(bytes_site.sum()),
            "wan_cost": float(cost_site.sum()),
            "full_bytes": T * k * n * 4,
            "plan_seconds": scan_seconds,
            "gaps": 0, "revisions": 0, "late_drops": 0, "duplicates": 0,
            "retransmits": 0,
            "window_age_ms": ages,
            "revised_windows": np.zeros(T, bool),
            "freshness_ms": freshness_percentiles(ages),
            **extras,
        }

    def _result_fleet(self, est, tru, bytes_site, cost_site, ys, state, T,
                      k, n, scan_seconds, extras, live_tbl=None):
        from repro.runtime.report import aggregate_fleet
        ages = np.zeros((T, self.n_sites))
        ad = None
        plan_windows = T
        if self.adaptive is not None and state.adaptive is not None:
            from repro.adaptive import gate_counters
            ad = gate_counters(state.adaptive.gate)
            plan_windows = ad["planner_invocations"]
        gaps = 0
        chaos_info = None
        if live_tbl is not None:
            from repro.chaos import chaos_metrics
            gaps = int((~live_tbl).sum())
            chaos_info = chaos_metrics(
                live_tbl, np.asarray(ys["budgets"], np.float64),
                self.ctrl.equal_share, est, tru, self.query_names,
                self.topology.region_of(), self.topology.region_names)
        raw = aggregate_fleet(
            topology=self.topology, qnames=self.query_names,
            est=est, est_q=est, tru=tru, ages=ages,
            bytes_per_site=bytes_site, cost_per_site=cost_site,
            gaps=gaps, revisions=0, late_drops=0, duplicates=0,
            arrival_lag_ms=np.asarray(state.controller.lag, np.float64),
            plan_seconds=scan_seconds, plan_windows=plan_windows,
            budget_history=ys["budgets"],
            total_tuples=T * self.n_sites * k * n, adaptive=ad,
            chaos=chaos_info)
        raw.update(extras)
        return raw
