"""The traffic generator: correlation bands, determinism, speed."""
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [p for p in (str(BENCH), str(BENCH.parent / "src"))
                if p not in sys.path]

import time  # noqa: E402

import numpy as np  # noqa: E402
import pytest  # noqa: E402

import generate  # noqa: E402

SEED = 2**33 + 17           # above 32 bits, as a check's seeds may be


def _pairs(band, k):
    if band["pairs"] == "all":
        return [(i, j) for i in range(k) for j in range(i + 1, k)]
    return [tuple(p) for p in band["pairs"]]


@pytest.mark.parametrize("name", ["city"])
def test_within_site_correlations_fall_in_the_stated_bands(name):
    cfg = dict(generate.load("configs", name), sites=64)
    x = generate.fleet_windows(cfg, 8, SEED)            # (W, E, k, N)
    series = np.concatenate(list(x), axis=-1)           # (E, k, W N)
    corr = np.mean([np.corrcoef(s) for s in series], axis=0)
    for band in cfg["correlation_bands"]:
        lo, hi = band["range"]
        for i, j in _pairs(band, cfg["streams_per_site"]):
            assert lo <= corr[i, j] <= hi, (name, i, j, corr[i, j])


def test_same_seed_same_windows_and_seeds_differ():
    cfg = dict(generate.load("configs", "city"), sites=16)
    a = generate.fleet_windows(cfg, 3, SEED)
    assert a.dtype == np.float32 and a.shape == (3, 16, 5, 288)
    np.testing.assert_array_equal(a, generate.fleet_windows(cfg, 3, SEED))
    assert not np.array_equal(a, generate.fleet_windows(cfg, 3, SEED + 1))


def test_sixty_four_windows_of_the_city_take_seconds():
    cfg = generate.load("configs", "city")
    assert cfg["sites"] == 449
    t0 = time.perf_counter()
    x = generate.fleet_windows(cfg, 64, SEED)
    took = time.perf_counter() - t0
    assert x.shape == (64, 449, 5, 288)
    assert np.isfinite(x).all()
    assert took < 60.0, f"{took:.1f} s"


def test_unknown_file_is_an_error_that_names_what_exists():
    with pytest.raises(FileNotFoundError, match="city"):
        generate.load("configs", "no_such_config")
