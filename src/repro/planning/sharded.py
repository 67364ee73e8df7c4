"""Sharded fleet planning: the batched (E, k, N) pass split across devices.

The site axis is embarrassingly parallel — every per-site quantity
(statistics, model fit, epsilon, the closed-form allocation) depends only on
that site's window and budget — so the whole ``fleet_plan`` body runs under
``shard_map`` with E split over a 1-D ``("sites",)`` mesh
(``repro.parallel.sharding.site_mesh``) and *zero* cross-device collectives:
only the controller's (E,) demand/budget vectors cross hosts, as plain
sharded inputs.  Per-site arithmetic is identical to the batched engine's,
so the outputs agree bitwise (pinned in tests/test_planning_engine.py under
``XLA_FLAGS=--xla_force_host_platform_device_count=8``).

E is padded up to a multiple of the device count with empty sites
(counts 0, floor budget) and the padding is sliced off the result.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.api.registry import ENGINES
from repro.parallel.sharding import pad_site_axis, site_mesh, site_pad
from repro.planning.batched import BatchedEngine, fleet_plan


@functools.lru_cache(maxsize=64)
def _sharded_plan_fn(device_ids, epsilon_scale, dependence, model,
                     epsilon_policy, use_kernel, interpret):
    """Compiled shard_map(fleet_plan) per (mesh, static planner config).

    The wrapper is cached and jitted so repeated windows hit the XLA
    executable cache instead of re-tracing the shard_map every call.
    """
    mesh = site_mesh(len(device_ids))
    plan_shard = functools.partial(
        fleet_plan, epsilon_scale=epsilon_scale,
        dependence=dependence, model=model, epsilon_policy=epsilon_policy,
        use_kernel=use_kernel, interpret=interpret)
    return jax.jit(jax.shard_map(
        plan_shard, mesh=mesh,
        in_specs=(P("sites"), P("sites"), P("sites")),
        out_specs=P("sites"), axis_names={"sites"}, check_vma=False))


class ShardedEngine(BatchedEngine):
    """``shard_map`` wrapper over the batched pass (multi-device fleets)."""

    name = "sharded"

    def _run(self, values, counts, budgets, cfg, *, use_kernel, interpret):
        mesh = site_mesh()
        d = mesh.shape["sites"]
        e = values.shape[0]
        pad = site_pad(e, d)
        if pad:
            values = pad_site_axis(values, e + pad)
            counts = pad_site_axis(counts, e + pad)
            budgets = pad_site_axis(budgets, e + pad, fill=2.0)

        fn = _sharded_plan_fn(tuple(dev.id for dev in mesh.devices.flat),
                              float(cfg.epsilon_scale), cfg.dependence,
                              cfg.model, cfg.epsilon_policy, use_kernel,
                              interpret)
        plan = fn(values, counts, budgets)
        if pad:
            plan = jax.tree.map(lambda x: x[:e], plan)
        return plan


ENGINES.register("sharded", ShardedEngine())
