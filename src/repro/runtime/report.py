"""Shared fleet result aggregation.

Both runtimes — the event loop (``repro.api.experiment.FleetRuntime``) and
the scan engine (:mod:`repro.runtime.scan`) — end a run holding the same
raw material: per-window estimate/truth tables, per-site byte counters and
freshness ages.  :func:`aggregate_fleet` is the one place that turns that
into the fleet result dict (site/region NRMSE roll-ups, byte and cost
accounting, freshness percentiles), so the scan runtime's bit-for-bit
parity with the event loop covers the aggregation arithmetic by
construction rather than by duplication.
"""
from __future__ import annotations

import jax
import numpy as np

from repro.core import queries as Q


def aggregate_fleet(*, topology, qnames, est, est_q, tru, ages,
                    bytes_per_site, cost_per_site, gaps, revisions,
                    late_drops, duplicates, arrival_lag_ms, plan_seconds,
                    plan_windows, budget_history, total_tuples,
                    retransmits=0, adaptive=None, chaos=None) -> dict:
    """Roll per-window tables into the fleet result dict.

    est/est_q/tru: {query: (T, E, k)} float arrays (NaN where unanswered);
    ages: (T, E) window age at query time (ms); bytes/cost_per_site: (E,)
    totals over the run; budget_history: (T, E) executed budgets.

    ``adaptive``: counters dict from the re-plan policy
    (``repro.adaptive.gate_counters``) or None.  Keys are merged into the
    result only when present, so plan-every-window runs keep the exact
    legacy key set (the sweep goldens treat key presence as part of the
    contract).

    ``chaos``: the recovery/degradation metric dict from
    ``repro.chaos.chaos_metrics`` or None — merged under the same
    only-when-present contract.

    The per-site NRMSE tables are one :func:`repro.core.queries.nrmse_rows`
    pass a query, under the profiler span ``report.nrmse`` with the args
    ``rows`` (the (query, site, stream) rows computed) and ``masked_rows``
    (those with a non-finite entry, which took the per-row ``Q.nrmse``).
    """
    from repro.streaming.events import freshness_percentiles
    reg_idx = topology.region_of()
    bytes_per_site = np.asarray(bytes_per_site)
    cost_per_site = np.asarray(cost_per_site, np.float64)

    nrmse_site = {}                         # {q: (E, k)}
    tables = [(est, nrmse_site)]
    if est_q is est:            # the scan runtime: one table serves both
        nrmse_site_q = nrmse_site
    else:
        nrmse_site_q = {}
        tables.append((est_q, nrmse_site_q))
    with jax.profiler.TraceAnnotation("report.nrmse") as span:
        rows = masked = 0
        for q in qnames:
            t_arr = tru[q].transpose(1, 2, 0)   # (E, k, T)
            for src, dst in tables:
                dst[q], fast = Q.nrmse_rows(src[q].transpose(1, 2, 0), t_arr)
                rows += fast.size
                masked += fast.size - int(fast.sum())
        span.set_metadata(rows=rows, masked_rows=masked)

    region_nrmse = {name: {} for name in topology.region_names}
    for r, name in enumerate(topology.region_names):
        sel = reg_idx == r
        for q in qnames:
            region_nrmse[name][q] = float(np.nanmean(nrmse_site[q][sel]))

    bytes_by_region = {name: 0 for name in topology.region_names}
    cost_by_region = {name: 0.0 for name in topology.region_names}
    for s, site in enumerate(topology.sites):
        bytes_by_region[site.region] += int(bytes_per_site[s])
        cost_by_region[site.region] += float(cost_per_site[s])

    freshness_by_region = {
        name: freshness_percentiles(ages[:, reg_idx == r])
        for r, name in enumerate(topology.region_names)}

    return {
        "fleet_nrmse": {q: float(np.nanmean(nrmse_site[q]))
                        for q in qnames},
        "fleet_nrmse_at_query": {q: float(np.nanmean(nrmse_site_q[q]))
                                 for q in qnames},
        "region_nrmse": region_nrmse,
        "site_nrmse": nrmse_site,
        "wan_bytes": int(bytes_per_site.sum()),
        "wan_bytes_by_region": bytes_by_region,
        "wan_cost": float(cost_per_site.sum()),
        "wan_cost_by_region": cost_by_region,
        "full_bytes": int(total_tuples) * 4,
        "gaps": int(gaps),
        "revisions": int(revisions),
        "late_drops": int(late_drops),
        "duplicates": int(duplicates),
        "retransmits": int(retransmits),
        "freshness_ms": freshness_percentiles(ages),
        "freshness_by_region": freshness_by_region,
        "window_age_ms": ages,
        "site_arrival_lag_ms": arrival_lag_ms,
        "plan_seconds": float(plan_seconds),
        "plan_windows": int(plan_windows),
        "budget_history": np.asarray(budget_history),
        **({} if adaptive is None else {
            "planner_invocations": int(adaptive["planner_invocations"]),
            "plans_reused": int(adaptive["plans_reused"]),
            "drift_fires": int(adaptive["drift_fires"]),
            "detection_lag_windows":
                float(adaptive["detection_lag_windows"]),
        }),
        **({} if chaos is None else {
            "liveness": chaos["liveness"],
            "down_site_windows": int(chaos["down_site_windows"]),
            "gap_served_cells": int(chaos["gap_served_cells"]),
            "availability_by_region": chaos["availability_by_region"],
            "recovery_windows": float(chaos["recovery_windows"]),
            "outage_nrmse": chaos["outage_nrmse"],
            "steady_nrmse": chaos["steady_nrmse"],
        }),
    }
