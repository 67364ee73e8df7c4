"""Compile rehearsal for the TPU v5e, without a chip.

The TPU compiler is installed with JAX, and it compiles for a chip that is
described (``v5e:2x2``) rather than attached.  These tests compile, with
``use_kernel=True, interpret=False``, what ``chip_smoke.py`` runs:

  * the ``stream_stats`` fleet kernel at the E=1024, k->8 layout;
  * the ``polyfit`` kernel vmapped over 1024 sites (k=4, N=288);
  * the whole ``ScanRuntime`` scan at E=1024, k=4, N=288 on one chip,
    with both kernels inside it;
  * the ``ShardedScanRuntime`` scan at E=4096 over a four-chip mesh, whose
    only collectives are the water-fill's 2 + 2 * iters: one pmax and an
    all-gather per fleet sum.

The compiler refuses here, at no chip time, what interpret mode accepts
and a chip would refuse: unaligned tiles, kernels over their fast-memory
budget, programs that do not fit.  Nothing runs, so nothing is timed.

The topology is described inside a module fixture, never while a module
is imported: only the worker that runs this file loads the TPU library.
"""
import dataclasses
import inspect
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

import chip_smoke
from repro.api import Experiment
from repro.chaos import make_chaos_carry
from repro.kernels.polyfit.ops import vandermonde_moments
from repro.kernels.stream_stats.kernel import stream_stats_fleet_pallas
from repro.runtime.controller import water_fill
from repro.runtime.state import init_state

E, K, N, POOL, T = 1024, 4, 288, 8, 32


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                # no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _tpu_calls(text):
    return text.count('custom_call_target="tpu_custom_call"')


def test_stream_stats_fleet_kernel_compiles(one_chip):
    x = _spec((E * 8, 512), jnp.float32, one_chip)
    fn = jax.jit(lambda x: stream_stats_fleet_pallas(x, kp=8, tn=512,
                                                     interpret=False))
    assert _tpu_calls(fn.lower(x).compile().as_text()) == 1


def test_polyfit_kernel_compiles_vmapped_over_sites(one_chip):
    y = _spec((E, K, N), jnp.float32, one_chip)
    fn = jax.jit(jax.vmap(lambda y, u: vandermonde_moments(
        y, u, use_kernel=True, interpret=False)))
    assert _tpu_calls(fn.lower(y, y).compile().as_text()) == 1


def _runtime(E_, runtime):
    exp = Experiment.from_scenario(chip_smoke.scenario(E_, runtime),
                                   use_kernel=True, interpret=False)
    exp.runtime.collect = "estimates"
    return exp.runtime


def test_scan_compiles_with_both_kernels(one_chip):
    rt = _runtime(E, "scan")
    state = jax.tree.map(
        lambda x: _spec(np.shape(x), np.asarray(x).dtype, one_chip),
        init_state(E, K, rt.ctrl.equal_share))
    wids = _spec((T,), jnp.int32, one_chip)
    pool = _spec((POOL, E, K, N), jnp.float32, one_chip)
    compiled = rt._scan_fn(None).lower(state, wids, pool).compile()
    calls = chip_smoke.kernel_calls(compiled.as_text())
    assert calls == {"stream_stats_fleet": 1, "polyfit": 1, "total": 2}


def test_sharded_scan_compiles_on_four_chips(topo):
    E4 = 4 * E
    rt = _runtime(E4, "scan_sharded")
    mesh = Mesh(np.asarray(topo.devices), ("sites",))
    assert mesh.size == 4
    rt._mesh = mesh                   # the described chips, not this host's
    state = init_state(E4, K, rt.ctrl.equal_share)
    state = dataclasses.replace(
        state, chaos=make_chaos_carry(E4, K, rt.query_names))
    specs = rt._state_specs(state)
    state = jax.tree.map(
        lambda x, s: _spec(np.shape(x), np.asarray(x).dtype,
                           NamedSharding(mesh, s)), state, specs)
    xs = (_spec((T,), jnp.int32, NamedSharding(mesh, P())),
          _spec((T, E4), bool, NamedSharding(mesh, P(None, "sites"))))
    pool = _spec((POOL, E4, K, N), jnp.float32,
                 NamedSharding(mesh, P(None, "sites")))
    text = rt._scan_fn(None).lower(state, xs, pool).compile().as_text()
    assert chip_smoke.kernel_calls(text)["total"] == 2
    # the water-fill's collectives: one pmax, and one all-gather per sum
    iters = inspect.signature(water_fill).parameters["iters"].default
    all_reduces = re.findall(r"\ball-reduce(?:-start)?\(", text)
    all_gathers = re.findall(r"\ball-gather(?:-start)?\(", text)
    assert len(all_reduces) == 1
    assert len(all_gathers) == 1 + 2 * iters


def test_fleet_sampler_is_one_sort_without_loop_or_gather(one_chip):
    """``sample_fleet`` at ``city``'s (449, 5, 288) compiles to one sort
    along the window axis: no ``while`` loop, and no gather whose output
    holds all E*k*N values."""
    from repro.runtime.step import sample_fleet
    e, k, n = 449, 5, 288
    text = jax.jit(sample_fleet, static_argnums=0).lower(
        7, _spec((), jnp.int32, one_chip),
        _spec((e, k, n), jnp.float32, one_chip),
        _spec((e, k), jnp.int32, one_chip)).compile().as_text()
    sorts = re.findall(r"\bsort\((.*)", text)
    assert len(sorts) == 1 and "dimensions={2}" in sorts[0]
    assert not re.search(r"\bwhile\(", text)
    gathered = [np.prod([int(d) for d in dims.split(",") if d]) for dims in
                re.findall(r"= \w+\[([\d,]*)\]\S* gather\(", text)]
    assert e * k * n not in gathered, gathered


# ------------------------------------- the benchmark's programs, full size

def _bench():
    """The benchmark harness and the exchange reader, from ``bench/``."""
    import importlib.util
    import sys
    bench = os.path.join(os.path.dirname(__file__), "..", "bench")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    import run as harness
    spec = importlib.util.spec_from_file_location(
        "exchange_ms", os.path.join(bench, "metrics", "exchange_ms.py"))
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)
    return harness, reader


def _cell_runtime(harness, config, chips):
    """A cell's configuration and its served runtime as ``bench/run.py``
    builds it, with the kernels a chip runs."""
    cfg = harness.generate.load("configs", config)
    rt = harness.build_runtime(cfg, chips)
    rt.use_kernel, rt.interpret = True, False
    shape = (int(cfg["sites"]), int(cfg["streams_per_site"]),
             int(cfg["window"]))
    per_call = harness.generate.load("traffic", "bulk")["windows_per_call"]
    return rt, shape, int(per_call)


def _collectives(text, reader):
    """{instruction: opcode} of the compiled module's collectives."""
    from tracefile import Op
    out = {}
    for line in text.splitlines():
        m = re.match(r"^\s*(?:ROOT )?%([\w.\-]+) = (.*)$", line)
        if m:
            kind = Op(f"%{m.group(1)} = {m.group(2)}", 0, 0).kind
            if reader.is_collective(kind):
                out[m.group(1)] = kind
    return out


def test_pems_bulk4_program_compiles_with_every_collective_in_the_exchange(
        topo):
    """``pems_ca.bulk4`` at full size (E=8600, k=3, N=288, 16 windows a
    call) over four chips: 2,150 sites a chip, a shard the compiler cannot
    tile, so each of the water-fill's 17 all-gathers becomes a
    dynamic-update-slice and an all-reduce without metadata; with the
    pmax, 18 all-reduces a window, all of them the exchange's."""
    harness, reader = _bench()
    from repro.runtime.controller import water_fill
    rt, (e, k, n), t = _cell_runtime(harness, "pems_ca", 4)
    mesh = Mesh(np.asarray(topo.devices), ("sites",))
    rt._mesh = mesh
    state = init_state(e, k, rt.ctrl.equal_share)
    state = dataclasses.replace(
        state, chaos=make_chaos_carry(e, k, rt.query_names))
    state = jax.tree.map(
        lambda x, s: _spec(np.shape(x), np.asarray(x).dtype,
                           NamedSharding(mesh, s)),
        state, rt._state_specs(state))
    xs = (_spec((t,), jnp.int32, NamedSharding(mesh, P())),
          _spec((t, e), bool, NamedSharding(mesh, P(None, "sites"))))
    pool = _spec((t, e, k, n), jnp.float32,
                 NamedSharding(mesh, P(None, "sites")))
    text = rt._scan_fn(None).lower(state, xs, pool).compile().as_text()
    assert chip_smoke.kernel_calls(text)["total"] == 2
    iters = inspect.signature(water_fill).parameters["iters"].default
    assert rt._exchange == {"exchange_all_gathers": 1 + 2 * iters,
                            "exchange_all_reduces": 1,
                            "exchange_gather_bytes": (1 + 2 * iters) * 4 * e}
    coll = _collectives(text, reader)
    assert sorted(coll.values()) == ["all-reduce"] * (2 + 2 * iters)
    exchange = reader.exchange_instructions(text)
    assert set(coll) <= exchange
    # the scope nests inside step.budgets: the stage split keeps its meaning
    import scopes
    stages = scopes.hlo_scopes(text)
    assert {stages[x] for x in exchange if x in coll} <= {"step.budgets",
                                                         None}
    assert "step.budgets" in {stages[x] for x in coll}


def test_city_bulk_program_keeps_its_stages_and_has_no_exchange(one_chip):
    """``city.bulk`` on one chip: ``bench/scopes.hlo_scopes`` finds the
    window step's seven stages and nothing else, and the program holds no
    collective, so nothing of it lies under ``exchange``."""
    harness, reader = _bench()
    import scopes
    rt, (e, k, n), t = _cell_runtime(harness, "city", 1)
    state = jax.tree.map(
        lambda x: _spec(np.shape(x), np.asarray(x).dtype, one_chip),
        init_state(e, k, rt.ctrl.equal_share))
    text = rt._scan_fn(None).lower(
        state, _spec((t,), jnp.int32, one_chip),
        _spec((t, e, k, n), jnp.float32, one_chip)).compile().as_text()
    assert set(scopes.hlo_scopes(text).values()) == {
        "step.budgets", "step.plan", "step.sample", "step.impute",
        "step.queries", "step.truth", "step.update", None}
    assert _collectives(text, reader) == {}
    assert reader.exchange_instructions(text) == set()
    assert rt._exchange == dict.fromkeys(rt._exchange, 0)
