"""The ``python -m benchmarks.run --smoke`` table, one case a scenario.

``benchmarks/common.py`` ``SMOKE_SCENARIOS`` holds one small scenario per
registered component family, and ``tests/test_repo_hygiene.py`` counts the
names it quotes as exercised: running it here is what makes that count
true.
"""
import numpy as np
import pytest

from benchmarks.common import SMOKE_SCENARIOS, run_scenario


@pytest.mark.parametrize("scenario", SMOKE_SCENARIOS,
                         ids=[s.name for s in SMOKE_SCENARIOS])
def test_smoke_scenario_runs(scenario):
    report = run_scenario(scenario)
    assert all(np.isfinite(v) for v in report.nrmse.values()), report.nrmse
    assert report.wan_bytes <= report.full_bytes
