"""chip_smoke.py on the CPU: its phases at E=8, its refusals, and the
compile-cache placement its entry point relies on.

The chip run itself (E=1024 on one TPU, E=4096 on four) is
``python chip_smoke.py [--chips 4]``; here the same phase functions run
at a tiny fleet against the same oracle and parity contracts, so a
broken check or a wrong path shows up without a chip.
"""
import shutil
import subprocess
import sys
from pathlib import Path

import pytest
from conftest import subprocess_env

import chip_smoke as cs

E = 8
ROOT = Path(__file__).resolve().parents[1]


def test_phases_at_small_fleet():
    """Both runtimes through the smoke's own checks: counts, sharded ==
    scan bitwise, and the host event-loop oracle contract."""
    n_windows = 12
    windows = cs.make_windows(E)
    scan = cs.run_fleet(E, "scan", windows, n_windows)
    sharded = cs.run_fleet(E, "scan_sharded", windows, n_windows)
    for run in (scan, sharded):
        assert run["interpret"] is False and run["compile_s"] > 0
        cs.check_counts(run["result"], E, n_windows)
    cs.check_sharded_matches_scan(scan["result"], sharded["result"])

    oracle = cs.oracle_report(E, windows)
    for runtime in ("scan", "scan_sharded"):
        prefix = cs.run_prefix(E, runtime, windows)
        cs.check_counts(prefix, E, cs.ORACLE_WINDOWS)
        cs.check_against_oracle(prefix, oracle)
        assert cs.oracle_agreement(prefix, oracle)["bytes_rel"] <= 0.05


def test_checks_catch_a_broken_run():
    """The checks are not vacuous: a byte history one cell off, or an
    oracle answer far from the run's, fails them."""
    scan = cs.run_fleet(E, "scan", cs.make_windows(E), 4)["result"]
    broken = dict(scan, bytes_history=scan["bytes_history"].copy())
    broken["bytes_history"][0, 0] += 4
    with pytest.raises(AssertionError):
        cs.check_sharded_matches_scan(scan, broken)
    starved = dict(scan, bytes_history=scan["bytes_history"].copy())
    starved["bytes_history"][1, 2] = 0
    with pytest.raises(AssertionError):
        cs.check_counts(starved, E, 4)

    class Far:
        wan_bytes = 2 * scan["wan_bytes"]
        nrmse = scan["fleet_nrmse"]
    with pytest.raises(AssertionError):
        cs.check_against_oracle(scan, Far)


def test_kernel_calls_reads_op_names():
    hlo = "\n".join([
        '%a = f32[8] custom-call(%x), custom_call_target="tpu_custom_call",'
        ' metadata={op_name="jit(fn)/jit(k)/stream_stats_fleet/pallas_call"}',
        '%b = f32[8] custom-call(%y), custom_call_target="tpu_custom_call",'
        ' metadata={op_name="jit(fn)/vmap(jit(f))/polyfit/pallas_call"}',
        '%c = f32[8] fusion(%a)'])
    assert cs.kernel_calls(hlo) == {"stream_stats_fleet": 1, "polyfit": 1,
                                    "total": 2}
    assert cs.kernel_calls("%c = f32[8] fusion(%a)")["total"] == 0


def test_main_refuses_without_tpu(capsys):
    """On the CPU the script exits nonzero and prints no result."""
    assert cs.main([]) == 1
    out = capsys.readouterr()
    assert '"ok"' not in out.out and "no TPU" in out.err


def test_script_alone_fails(tmp_path):
    """chip_smoke.py copied away from the repo exits nonzero, no result."""
    shutil.copy(ROOT / "chip_smoke.py", tmp_path / "chip_smoke.py")
    env = subprocess_env(1)
    env.pop("PYTHONPATH")
    out = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                         env=env, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


_CACHE_PROG = """
import jax, jax.numpy as jnp
from repro.compile_cache import setup_compile_cache
print("DIR", setup_compile_cache())
print("CFG", jax.config.jax_compilation_cache_dir)
jax.jit(lambda x: jnp.sin(x) * 2.0)(jnp.ones(8)).block_until_ready()
"""


def test_compile_cache_follows_environment(tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: entries land there and the helper
    changes nothing."""
    env = subprocess_env(1)
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cache")
    env["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    out = subprocess.run([sys.executable, "-c", _CACHE_PROG], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert f"DIR {tmp_path / 'cache'}" in out.stdout
    assert f"CFG {tmp_path / 'cache'}" in out.stdout
    assert any((tmp_path / "cache").iterdir())


def test_compile_cache_defaults_to_repo_dir():
    """Unset: the fixed <repo>/.jax_cache (no compile here, so the test
    writes nothing into the checkout)."""
    from repro.compile_cache import REPO_CACHE_DIR
    assert REPO_CACHE_DIR == ROOT / ".jax_cache"
    env = subprocess_env(1)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    prog = _CACHE_PROG.split("jax.jit")[0]
    out = subprocess.run([sys.executable, "-c", prog], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert f"DIR {REPO_CACHE_DIR}" in out.stdout
    assert f"CFG {REPO_CACHE_DIR}" in out.stdout
