"""Shared fixtures. NOTE: no XLA device-count overrides here — smoke tests
and benches must see the real single device; only subprocess tests (dry-run,
multi-pod trainer) force placeholder devices via their own environment."""
import os
import sys
import types

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
# the repo root: chip_smoke.py's phases are tested on the CPU
sys.path.insert(1, os.path.join(os.path.dirname(__file__), ".."))

# ---------------------------------------------------------------------------
# hypothesis fallback: several test modules import `hypothesis` at module
# scope; when it is not installed, collecting them used to abort the whole
# suite.  CI installs the real package (scripts/ci.sh) and sets
# REPRO_REQUIRE_HYPOTHESIS=1, which turns a missing install into a hard
# error — the property tests genuinely run there.  Only bare containers
# without the package fall back to the stub, whose @given replaces each
# property test with a runtime skip so the non-property tests in those
# modules still run.
# ---------------------------------------------------------------------------
try:
    import hypothesis  # noqa: F401
except ImportError:
    if os.environ.get("REPRO_REQUIRE_HYPOTHESIS"):
        raise RuntimeError(
            "REPRO_REQUIRE_HYPOTHESIS is set but `hypothesis` is not "
            "importable — the scripts/ci.sh install step failed; property "
            "tests must not be silently skipped in CI.")
    def _strategy(*_a, **_k):
        return None

    _st = types.ModuleType("hypothesis.strategies")
    for _name in ("integers", "floats", "booleans", "sampled_from", "lists",
                  "tuples", "just", "one_of"):
        setattr(_st, _name, _strategy)

    def _given(*_a, **_k):
        def deco(fn):
            def stub():
                pytest.skip("hypothesis not installed")
            stub.__name__ = fn.__name__
            stub.__doc__ = fn.__doc__
            return stub
        return deco

    def _settings(*_a, **_k):
        def deco(fn):
            return fn
        return deco

    _hyp = types.ModuleType("hypothesis")
    _hyp.given, _hyp.settings, _hyp.strategies = _given, _settings, _st
    sys.modules["hypothesis"] = _hyp
    sys.modules["hypothesis.strategies"] = _st


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)


def run_matrix(values, window, budget_fraction, method, cfg=None,
               drop_prob=0.0, straggler_drop=None,
               query_names=("AVG", "VAR", "MIN", "MAX"),
               latency_ms=0.0, jitter_ms=0.0, window_period_ms=1000.0,
               staleness_deadline_ms=None, retransmit_timeout_ms=None,
               max_retries=0):
    """One in-memory (k, T) matrix through the single-edge runtime.

    Test-local stand-in for the removed ``run_experiment`` shim: builds a
    ``SingleEdgeRuntime`` from the public primitives and returns the legacy
    result dict.  Scenario-driven code should use
    ``Experiment.from_scenario`` instead; this exists for tests that feed
    explicit value matrices.
    """
    from repro.api.experiment import SingleEdgeRuntime
    from repro.core.types import PlannerConfig
    from repro.data.streams import windows_from_matrix
    from repro.streaming import AsyncTransport, CloudNode, EdgeNode

    cfg = cfg or PlannerConfig()
    exp = SingleEdgeRuntime(
        edge=EdgeNode(cfg=cfg, budget_fraction=budget_fraction, method=method,
                      straggler_drop=straggler_drop),
        cloud=CloudNode(query_names=query_names),
        transport=AsyncTransport(drop_prob=drop_prob, seed=cfg.seed,
                                 latency_ms=latency_ms, jitter_ms=jitter_ms,
                                 retransmit_timeout_ms=retransmit_timeout_ms,
                                 max_retries=max_retries),
        window_period_ms=window_period_ms,
        staleness_deadline_ms=staleness_deadline_ms,
    )
    return exp.run(windows_from_matrix(values, window))


def subprocess_env(n_devices: int) -> dict:
    """Environment for a child interpreter on ``n_devices`` forced host CPU
    devices.  ``JAX_PLATFORMS=cpu`` keeps the child off any accelerator:
    the parent test process may already hold it."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    return env
