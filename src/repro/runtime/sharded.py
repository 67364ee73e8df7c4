"""ShardedScanRuntime — the whole per-window cycle on the site mesh.

:class:`~repro.runtime.scan.ScanRuntime` keeps the full window step —
controller budgets → Algorithm-1 plan → SRS sampling → imputation
→ queries → controller update — inside one ``lax.scan``, but on a single
device; only the *planning* stage could shard (PR 5's engine).  This
runtime wraps the scan itself in ``shard_map`` over the 1-D ``("sites",)``
mesh (``repro.parallel.sharding.site_mesh``), so the entire cycle scales
with devices: every per-site quantity lives as the local shard of a
site-sharded, donated :class:`~repro.runtime.state.RuntimeState` pytree
(including the ``AdaptiveCarry``/``ChaosCarry`` subtrees) and never leaves
its device between windows.

Mesh layout / padding
    E is rounded up to the device multiple
    (:func:`~repro.parallel.sharding.site_pad`); the extra rows are not
    a special case but ordinary *permanently dead* sites in the same
    liveness mask chaos faults use
    (:func:`~repro.chaos.padded_liveness_table`), so the step always runs
    its ``chaos=True`` body and every dead-site guarantee (zero budget,
    zero bytes, frozen EWMAs, no ingest) covers padding for free.  Windows,
    carry and liveness go from the host straight to their devices
    (``jax.device_put`` on ``NamedSharding``s), never staged on one chip:
    each window is split on its site rows, which are contiguous in the
    caller's array, and every chip stacks its own rows into its shard of
    the pool.

Collective inventory (per window, rebalance controller only)
    ``water_fill`` — 1 + 2·iters ``all_gather`` of the (E,) vectors it
    sums plus one ``pmax`` (the budget redistribution is the one
    genuinely fleet-global computation); every device sums the gathered
    vector in the single-device order
    (:func:`~repro.runtime.controller.ordered_sum`).  Adaptive runs add
    one ``pmax`` for the drift gate's deviation max.  Every collective
    runs under the ``exchange`` scope, and the result dict counts them a
    window (``exchange_all_gathers``, ``exchange_all_reduces``,
    ``exchange_gather_bytes``: each device's gathered bytes).
    Static-budget runs are collective-free: the whole window step is then
    embarrassingly parallel, like the sharded plan engine.

Parity contract (pinned in tests/test_scan_runtime.py under 8 forced
host devices)
    Counters, WAN bytes and sample sets match the batched scan *bitwise*
    on any number of devices — budgets are host-f64 (static) or
    water-filled with ordered sums of the gathered fleet (rebalance), the
    controller's error signal is an ordered sum too, n_real is integer,
    and the sampler consumes the batched run's exact global uniforms
    (each device draws the full unpadded-(E, k, N) tensor and slices its
    rows; threefry is not prefix-stable across shapes, so replicated
    generation is the price of bitwise RNG parity).  Other float tables
    (fit coefficients) carry the documented f32 class: XLA may associate
    a reduction differently in a program of another shape
    (docs/runtime.md).

Checkpoints stay *unpadded*: ``final_state`` is sliced back to E sites, so
sharded and batched checkpoints are interchangeable in both directions —
a kill-and-restore can land on a different device count.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.parallel.sharding import count_exchange, site_mesh, site_pad
from repro.runtime.scan import ScanRuntime
from repro.runtime.step import make_window_step

AXIS = "sites"


@dataclasses.dataclass
class ShardedScanRuntime(ScanRuntime):
    """Scan runtime with the window step under shard_map over sites.

    ``pad_sites`` overrides the padded site count (tests use it to check
    padding-invariance on a single device); None pads E to the local
    device multiple.
    """

    pad_sites: Optional[int] = None

    def __post_init__(self):
        super().__post_init__()
        if self.topology is None:
            raise ValueError(
                "runtime='scan_sharded' shards the fleet site axis; a "
                "single edge has nothing to shard (use runtime='scan')")
        if self.n_sites < 2:
            raise ValueError(
                "runtime='scan_sharded' needs a fleet of >= 2 sites "
                "(single-site fleets sample through the host-parity chain "
                "the sharded sampler does not replicate)")
        self._mesh = site_mesh()
        d = int(self._mesh.shape[AXIS])
        e = self.n_sites
        e_pad = (int(self.pad_sites) if self.pad_sites is not None
                 else e + site_pad(e, d))
        if e_pad < e or e_pad % d:
            raise ValueError(
                f"pad_sites ({e_pad}) must be >= n_sites ({e}) and a "
                f"multiple of the {d}-device site mesh")
        self._run_sites = e_pad
        self._stack = jax.jit(
            jnp.stack, out_shardings=NamedSharding(self._mesh, P(None, AXIS)))

    # ------------------------------------------------------------- compile
    def _plan_fn(self, values, counts, budgets):
        # called inside the shard_map body on the local site shard; route
        # straight through the batched pass even when the scenario names
        # engine='sharded' — this runtime IS the sharded engine, hoisted
        # around the whole step (nesting shard_map would deadlock the mesh)
        from repro.planning.batched import BatchedEngine
        return BatchedEngine._run(self.engine, values, counts, budgets,
                                  self.cfg_eff, use_kernel=self.use_kernel,
                                  interpret=self.interpret)

    def _state_specs(self, state):
        """PartitionSpec pytree: site-leading leaves shard, scalars
        replicate (every replicated leaf — window id, seen flag, gate
        detector scalars — is provably device-invariant: it is updated
        from replicated values and pmax'd reductions only)."""
        e_pad = self._run_sites

        def one(x):
            if getattr(x, "ndim", 0) >= 1 and x.shape[0] == e_pad:
                return P(AXIS)
            return P()

        return jax.tree.map(one, state)

    def _scan_fn(self, static_exec: Optional[tuple]):
        if static_exec not in self._fns:
            e, e_pad = self.n_sites, self._run_sites
            exec_arr = None
            if static_exec is not None:
                exec_arr = np.zeros(e_pad, np.float32)
                exec_arr[:e] = np.asarray(static_exec, np.float32)
            mesh = self._mesh

            def body(state, xs, pool):
                # local shard sizes; offset of this device's first site row
                lsites = state.controller.demand.shape[0]
                offset = jax.lax.axis_index(AXIS) * lsites
                exec_local = None
                if exec_arr is not None:
                    exec_local = jax.lax.dynamic_slice_in_dim(
                        jnp.asarray(exec_arr), offset, lsites)
                step = make_window_step(
                    pool, seed=self.cfg_eff.seed, plan_fn=self._plan_fn,
                    qnames=self.query_names, multi=self.spec.multi,
                    mean=self.spec.mean, ctrl=self.ctrl,
                    static_exec_budgets=exec_local, collect=self.collect,
                    adaptive=self.adaptive, use_kernel=self.use_kernel,
                    interpret=self.interpret, chaos=True, axis_name=AXIS,
                    sample_slice=(e, e_pad, offset))

                def counted(state, x):
                    # the collectives of one traced window step are the
                    # run's exchange a window
                    with count_exchange() as counts:
                        out = step(state, x)
                    self._exchange = counts
                    return out

                return jax.lax.scan(counted, state, xs)

            def fn(state, xs, pool):
                specs = self._state_specs(state)
                sm = jax.shard_map(
                    body, mesh=mesh,
                    in_specs=(specs, (P(), P(None, AXIS)), P(None, AXIS)),
                    out_specs=(specs, P(None, AXIS)), axis_names={AXIS},
                    check_vma=False)
                return sm(state, xs, pool)

            self._fns[static_exec] = jax.jit(fn, donate_argnums=0)
        return self._fns[static_exec]

    # ------------------------------------------------------------ plumbing
    def _adopt_state(self, state):
        """Resume: checkpoints are unpadded (E); pad the site-leading
        leaves with zeros — padded rows are permanently dead, so their
        carry content is never read by a live output."""
        e, e_pad = self.n_sites, self._run_sites

        def pad(x):
            x = np.asarray(x)
            if e_pad != e and x.ndim >= 1 and x.shape[0] == e:
                return np.concatenate(
                    [x, np.zeros((e_pad - e,) + x.shape[1:], x.dtype)])
            return x

        return jax.tree.map(pad, state)

    def _liveness_table(self, T: int, w0: int):
        from repro.chaos import padded_liveness_table
        spec = self.chaos if self._chaos_active else None
        return padded_liveness_table(spec, T, self.n_sites,
                                     self._run_sites,
                                     self.topology.region_of(),
                                     first_window=w0)

    def _place(self, state, xs, host):
        """Each device receives only its own site shard, straight from the
        host: every window and the liveness split on their site axis, the
        carry by :meth:`_state_specs`, window ids replicated; each device
        then stacks its window shards into its shard of the pool."""
        def put(x, spec):
            return jax.device_put(x, NamedSharding(self._mesh, spec))

        state = jax.tree.map(put, state, self._state_specs(state))
        wids, live = xs
        xs = (put(wids, P()), put(live, P(None, AXIS)))
        return state, xs, self._stack(put(host, P(AXIS)))

    def _finalize(self, ys, state, live_tbl):
        """Slice padding off every output; hand back a state a *batched*
        resume accepts (unpadded, chaos carry only under real chaos)."""
        e, e_pad = self.n_sites, self._run_sites
        if e_pad != e:
            ys = jax.tree.map(lambda x: x[:, :e], ys)
            state = jax.tree.map(
                lambda x: x[:e] if (getattr(x, "ndim", 0) >= 1
                                    and x.shape[0] == e_pad) else x, state)
        if not self._chaos_active:
            # the all-live mask exists only to mask padding; the report and
            # the checkpoint must look exactly like a batched run's
            ys.pop("live", None)
            state = dataclasses.replace(state, chaos=None)
            live_tbl = None
        else:
            live_tbl = live_tbl[:, :e]
        return ys, state, live_tbl
