"""The statewide PeMS configuration's traffic: flow and occupancy rise
together and speed falls as occupancy rises, in the bands the file
states, the negative ones included."""
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [p for p in (str(BENCH), str(BENCH.parent / "src"))
                if p not in sys.path]

import numpy as np  # noqa: E402
import pytest  # noqa: E402

import generate  # noqa: E402

SEED = 2**33 + 23


@pytest.mark.parametrize("pair", [(0, 1), (0, 2), (1, 2)])
def test_pems_correlation_bands_hold(pair):
    cfg = dict(generate.load("configs", "pems_ca"), sites=64)
    x = generate.fleet_windows(cfg, 8, SEED)            # (W, E, k, N)
    series = np.concatenate(list(x), axis=-1)           # (E, k, W N)
    corr = np.mean([np.corrcoef(s) for s in series], axis=0)
    (band,) = [b for b in cfg["correlation_bands"]
               if [list(pair)] == b["pairs"]]
    lo, hi = band["range"]
    assert lo <= corr[pair] <= hi, (pair, corr[pair])
    assert (corr[pair] < 0) == (2 in pair)            # speed runs opposite


def test_pems_configuration_is_the_deployment_it_states():
    cfg = generate.load("configs", "pems_ca")
    assert (cfg["sites"], cfg["regions"], cfg["streams_per_site"],
            cfg["window"]) == (8600, 4, 3, 288)
    assert cfg["reduced"] == [] and len(cfg["source"]) <= 200
    assert generate.mixing_weights(cfg).shape == (8600, 3)
    assert np.bincount(generate.site_regions(cfg)).tolist() == [2150] * 4
