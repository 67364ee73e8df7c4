"""The comparison that decides ``correct``, driven end to end on the CPU
at a small size: the chip look is skipped and ``run.measure`` does the
rest of a run.  Sound runs pass; the control (the reference computed in
bfloat16 in the program's place) and each fault planted in the timed path
fail."""
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [p for p in (str(BENCH), str(BENCH.parent / "src"))
                if p not in sys.path]

import jax  # noqa: E402
import pytest  # noqa: E402

import reference  # noqa: E402
import run as R  # noqa: E402

SEED = 2**31 + 5


CELLS = {"city.bulk": ("city", "bulk", 1),
         "city.live": ("city", "live", 1)}
NUMBERS = {"totals_gap", "truth_gap", "r2_gap", "est_err", "budget_gap",
           "alloc_off", "bytes_off"}


def _small(cell_name, sites=16):
    """A cell whose configuration and traffic files are in ``bench/``, cut
    to a size a test run holds."""
    config, mix, chips = CELLS[cell_name]
    bench = R.load_benchmark()
    cell = {"name": cell_name, "config": config, "traffic": mix,
            "chips": chips}
    cfg = dict(R.generate.load("configs", config), sites=sites)
    traffic = R.generate.load("traffic", mix)
    traffic = dict(traffic, windows_per_call=min(2, traffic[
        "windows_per_call"]), distinct_windows=4)
    return bench, cell, cfg, traffic


def _measure(cell_name="city.bulk", seconds=0.2, **kw):
    bench, cell, cfg, traffic = _small(cell_name, **kw)
    return R.measure(bench, cell, cfg, traffic, seed=SEED, seconds=seconds,
                     trace=False, devices=jax.devices(), log=lambda m: None)


def _served(cell_name, n_calls=3):
    """A short closed loop on the served runtime; its calls and windows."""
    _, cell, cfg, traffic = _small(cell_name)
    windows = R.generate.fleet_windows(cfg, traffic["distinct_windows"],
                                       SEED)
    srv = R.Server(R.build_runtime(cfg, 1), windows,
                   traffic["windows_per_call"],
                   R.first_window(SEED, traffic["distinct_windows"]))
    for _ in range(n_calls):
        srv.call()
    return srv.calls, windows, cfg, R.limits_of(cell["name"])


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_sound_run_is_correct_and_reports_its_numbers_last(cell):
    res = _measure(cell)
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] >= 1
    assert list(res)[-1] == "checks"
    assert set(res["checks"]) == NUMBERS
    for c in res["checks"].values():
        assert c["value"] <= c["limit"]
    bench = R.load_benchmark()
    assert set(res["metrics"]) == {
        m["name"] for m in R.metrics_of(bench, cell, "end_to_end")}
    assert "setup_s" in res["metrics"]


def test_control_in_bfloat16_is_not_correct():
    calls, windows, cfg, limits = _served("city.bulk")
    sound = reference.compare(calls, windows, cfg)
    assert all(sound[k] <= limits[k] for k in limits), sound
    ctl = reference.compare(
        reference.control_calls(calls, windows, cfg), windows,
        cfg)
    over = {k for k in limits if ctl[k] > limits[k]}
    assert {"totals_gap", "truth_gap", "r2_gap", "alloc_off"} <= over, ctl


# ------------------------------------------------------- planted faults

def _patch_step(monkeypatch, wrap):
    """Wrap every window step the runtime builds."""
    import repro.runtime.scan as scan_mod
    make = scan_mod.make_window_step

    def make_broken(*a, **kw):
        return wrap(make(*a, **kw))
    monkeypatch.setattr(scan_mod, "make_window_step", make_broken)


def _over(res):
    return {k for k, c in res["checks"].items() if c["value"] > c["limit"]}


def test_step_that_returns_its_state_unchanged_is_caught(monkeypatch):
    def wrap(step):
        def broken(state, xs):
            _, out = step(state, xs)
            return state, out
        return broken
    _patch_step(monkeypatch, wrap)
    res = _measure()
    assert res["correct"] is False
    assert res["checks"]["totals_gap"]["value"] > 0.1


def test_half_of_the_sites_left_out_is_caught(monkeypatch):
    """The second half of the fleet's windows never reach the sampler: its
    answers come from zeros."""
    import repro.runtime.step as step_mod
    sample = step_mod.sample_fleet

    def half(seed, wid, values, n_real, sample_slice=None):
        e = values.shape[0]
        values = values.at[e // 2:].set(0.0)
        return sample(seed, wid, values, n_real, sample_slice)
    monkeypatch.setattr(step_mod, "sample_fleet", half)
    res = _measure()
    assert res["correct"] is False
    assert "est_err" in _over(res)


def test_an_answer_altered_where_it_is_produced_is_caught(monkeypatch):
    """Site 0's AVG answers are mixed up, each stream answered with the
    next stream's, before the controller reads them: the program stays
    consistent with itself."""
    import jax.numpy as jnp
    import repro.runtime.step as step_mod
    queries = step_mod._masked_queries

    def altered(parts, qnames):
        out = queries(parts, qnames)
        if len(parts) == 2 and "AVG" in out:
            avg = out["AVG"]
            out = dict(out, AVG=avg.at[0].set(jnp.roll(avg[0], -1)))
        return out
    monkeypatch.setattr(step_mod, "_masked_queries", altered)
    res = _measure()
    assert res["correct"] is False
    assert "est_err" in _over(res)
    calls, windows, cfg, limits = _served("city.bulk")
    planted = reference.compare(reference.altered_calls(calls), windows, cfg)
    assert planted["est_err"] > limits["est_err"]


def test_plan_statistics_in_bfloat16_are_caught(monkeypatch):
    """The planner's window statistics contracted from bfloat16 inputs, as
    a TPU contracts float32 at its default precision."""
    import jax.numpy as jnp
    import repro.planning.batched as batched
    moments = batched.fleet_window_moments_xxt

    def low(x, **kw):
        return moments(x.astype(jnp.bfloat16).astype(x.dtype), **kw)
    monkeypatch.setattr(batched, "fleet_window_moments_xxt", low)
    batched.fleet_plan.clear_cache()
    try:
        res = _measure()
    finally:
        batched.fleet_plan.clear_cache()
    assert res["correct"] is False
    assert "r2_gap" in _over(res)


def test_an_allocation_past_the_bias_cap_is_caught(monkeypatch):
    """The planner's bias tolerance doubled: streams impute more samples
    than their cap allows, and the answers barely move."""
    import repro.planning.batched as batched
    make = batched.eps_mod.make_epsilon

    def loose(policy, stats, scale):
        return 2.0 * make(policy, stats, scale)
    monkeypatch.setattr(batched.eps_mod, "make_epsilon", loose)
    batched.fleet_plan.clear_cache()
    try:
        res = _measure()
    finally:
        batched.fleet_plan.clear_cache()
    assert res["correct"] is False
    assert "alloc_off" in _over(res)
