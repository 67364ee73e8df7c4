"""Checkpointing: round trip, atomicity, retention, restore-into-sharding."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np

from repro.ckpt import CheckpointManager, latest_step, restore, save


def _state(seed=0):
    """A training-state-shaped pytree: nested float32 arrays, two float32
    trees of zeros like them, and an int32 step counter."""
    k = jax.random.PRNGKey(seed)
    params = {"w": jax.random.normal(k, (8, 4)),
              "b": {"c": jnp.arange(5, dtype=jnp.float32)}}
    zeros = jax.tree.map(jnp.zeros_like, params)
    return {"params": params, "m": zeros, "v": zeros,
            "step": jnp.asarray(0, jnp.int32)}


def test_round_trip(tmp_path):
    st = _state()
    save(st, 7, tmp_path)
    assert latest_step(tmp_path) == 7
    ab = jax.eval_shape(lambda: st)
    out = restore(tmp_path, 7, ab)
    for a, b in zip(jax.tree.leaves(st), jax.tree.leaves(out)):
        np.testing.assert_allclose(a, b)


def test_torn_checkpoint_ignored(tmp_path):
    st = _state()
    save(st, 3, tmp_path)
    bad = tmp_path / "step_00000009"
    bad.mkdir()
    (bad / "manifest.json").write_text("{not json")
    assert latest_step(tmp_path) == 3


def test_manager_retention_and_async(tmp_path):
    mgr = CheckpointManager(tmp_path, keep=2, async_save=True)
    st = _state()
    for s in (1, 2, 3, 4):
        mgr.save(st, s)
    mgr.wait()
    names = sorted(d.name for d in tmp_path.iterdir())
    assert names == ["step_00000003", "step_00000004"]
    restored, step = mgr.restore(jax.eval_shape(lambda: st))
    assert step == 4


def test_restore_with_shardings(tmp_path):
    """Restore placing leaves with explicit (trivial single-device) shardings
    — the cross-mesh path."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    st = _state()
    save(st, 1, tmp_path)
    mesh = jax.make_mesh((1,), ("data",))
    ab = jax.eval_shape(lambda: st)
    sh = jax.tree.map(lambda _: NamedSharding(mesh, P()), ab)
    out = restore(tmp_path, 1, ab, sh)
    for a, b in zip(jax.tree.leaves(st), jax.tree.leaves(out)):
        np.testing.assert_allclose(a, b)


# ------------------------------------------- scan-runtime state round trip

def _resume_scenario():
    from repro.api import (ControllerSpec, DataSpec, ScenarioConfig,
                           TopologySpec)
    from repro.core.types import PlannerConfig
    return ScenarioConfig(
        data=DataSpec(dataset="fleet", n_points=512, window=64, seed=3,
                      options={"k": 4}),
        planner=PlannerConfig(solver="closed_form"),
        topology=TopologySpec(n_regions=2, sites_per_region=3, seed=3,
                              latency_scale=0.0),
        controller=ControllerSpec(mode="rebalance"),
        queries=("AVG", "VAR"), runtime="scan")


def test_runtime_state_round_trips_through_checkpoint(tmp_path):
    """A mid-run RuntimeState (controller EWMAs, stream totals, the RNG
    window cursor) survives save/restore bit-for-bit."""
    from repro.api import Experiment
    exp = Experiment.from_scenario(_resume_scenario())
    windows = exp.make_windows()
    r = exp.runtime.run(windows, n_windows=3)
    st = r["final_state"]
    save(st, 3, tmp_path)
    assert latest_step(tmp_path) == 3
    out = restore(tmp_path, 3, jax.eval_shape(lambda: st))
    for (pa, a), (pb, b) in zip(
            jax.tree_util.tree_flatten_with_path(st)[0],
            jax.tree_util.tree_flatten_with_path(out)[0]):
        assert pa == pb
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert int(np.asarray(out.window_id)) == 3


def test_restored_scan_runtime_resumes_bitwise(tmp_path):
    """Kill-and-restore: a scan runtime restarted from a checkpointed
    carry replays the remaining windows bit-for-bit against the unbroken
    run — controller trajectory, WAN bytes and query tables all identical."""
    from repro.api import Experiment
    scenario = _resume_scenario()
    exp = Experiment.from_scenario(scenario)
    windows = exp.make_windows()
    T, cut = len(windows), 3
    full = exp.runtime.run(windows)

    # first process dies after `cut` windows, checkpointing its carry
    rt1 = Experiment.from_scenario(scenario).runtime
    head = rt1.run(windows, n_windows=cut)
    save(head["final_state"], cut, tmp_path)

    # a fresh process restores and finishes the run
    rt2 = Experiment.from_scenario(scenario).runtime
    step = latest_step(tmp_path)
    st = restore(tmp_path, step, jax.eval_shape(lambda: head["final_state"]))
    tail = rt2.run(windows, n_windows=T - cut, state=st)

    for f in ("budgets", "obs_err", "r2", "objective"):
        np.testing.assert_array_equal(
            np.concatenate([head["plan_raw"][f], tail["plan_raw"][f]]),
            full["plan_raw"][f])
    assert head["wan_bytes"] + tail["wan_bytes"] == full["wan_bytes"]
    # remaining windows' executed-budget rows equal the unbroken run's tail
    np.testing.assert_array_equal(tail["budget_history"],
                                  full["budget_history"][cut:])
    for a, b in zip(jax.tree.leaves(full["final_state"]),
                    jax.tree.leaves(tail["final_state"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert int(np.asarray(tail["final_state"].window_id)) == T


# ------------------------------------------------ orphan staging-dir GC

def test_orphan_tmp_from_killed_writer_gcd_on_next_save(tmp_path):
    """A writer killed between ``tmp.mkdir()`` and the atomic rename leaks
    its staging dir; the next save() tears it down (its pid is dead)."""
    import subprocess
    import sys
    code = ("import os, sys; from pathlib import Path; "
            "d = Path(sys.argv[1]); "
            "tmp = d / f'.tmp-9-{os.getpid()}'; tmp.mkdir(parents=True); "
            "(tmp / 'data.npz').write_bytes(b'partial'); "
            "print(os.getpid())")
    out = subprocess.run([sys.executable, "-c", code, str(tmp_path)],
                         capture_output=True, text=True, check=True)
    pid = int(out.stdout)
    orphan = tmp_path / f".tmp-9-{pid}"
    assert orphan.exists()          # the "crash" left its staging dir
    save(_state(), 1, tmp_path)
    assert not orphan.exists()
    assert latest_step(tmp_path) == 1


def test_tmp_dirs_of_live_writers_survive_gc(tmp_path):
    """Our own staging dir and a live concurrent writer's (pid 1 always
    exists) are never mistaken for orphans; a pre-pid legacy name is."""
    own = tmp_path / f".tmp-3-{os.getpid()}"
    live = tmp_path / ".tmp-4-1"
    legacy = tmp_path / ".tmp-5"
    for d in (own, live, legacy):
        d.mkdir(parents=True)
    save(_state(), 2, tmp_path)
    assert own.exists() and live.exists()
    assert not legacy.exists()
    assert latest_step(tmp_path) == 2


def test_async_manager_save_gcs_orphans(tmp_path):
    """The async writer thread goes through the same save() path, so a
    leaked staging dir is collected by the next managed save too."""
    orphan = tmp_path / ".tmp-7-999999999"      # no such pid
    orphan.mkdir(parents=True)
    mgr = CheckpointManager(tmp_path, keep=2, async_save=True)
    mgr.save(_state(), 11)
    mgr.wait()
    assert not orphan.exists()
    assert latest_step(tmp_path) == 11
