"""The statewide PeMS fleet (``bench/configs/pems_ca.json``: k=3 streams,
one coupled negatively, 4 regions, rebalance controller) on the four-chip
path, cut to E=64 and run on 4 forced host devices: ``scan_sharded``
against ``scan``, both against the plain reference within
``pems_ca.bulk4``'s limits, and the exchange counters that reach
``RunReport``; the bfloat16 control and a planted fault fail those
limits."""
import json
import subprocess
import sys
import textwrap
from pathlib import Path

from conftest import subprocess_env

ROOT = Path(__file__).resolve().parents[1]

CHILD = textwrap.dedent("""
    import json, sys
    import jax
    import numpy as np
    assert len(jax.devices()) == 4, jax.devices()
    sys.path[:0] = [sys.argv[1]]
    import reference
    import run as R
    from repro.api.experiment import _report_fleet

    E, CALLS, SEED = 64, 3, 2**33 + 29
    cfg = dict(R.generate.load("configs", "pems_ca"), sites=E)
    per_call, distinct = 2, 4
    windows = R.generate.fleet_windows(cfg, distinct, SEED)

    def served(chips):
        rt = R.build_runtime(cfg, chips)
        results, run = [], rt.run

        def keep(*a, **kw):
            results.append(run(*a, **kw))
            return results[-1]
        rt.run = keep
        srv = R.Server(rt, windows, per_call, R.first_window(SEED, distinct))
        for _ in range(CALLS):
            srv.call()
        return srv.calls, results

    calls_1, res_1 = served(1)
    calls_4, res_4 = served(4)
    same = all(
        a[f] == b[f] for a, b in zip(res_1, res_4)
        for f in ("wan_bytes", "full_bytes", "gaps", "duplicates"))
    same &= all(np.array_equal(a["bytes_history"], b["bytes_history"])
                and np.array_equal(a["budget_history"], b["budget_history"])
                for a, b in zip(res_1, res_4))
    counters = [[_report_fleet(None, r, E).to_dict()[f] for f in (
        "exchange_all_gathers", "exchange_all_reduces",
        "exchange_gather_bytes")] for r in (res_1[-1], res_4[-1])]
    ref = reference.Reference.of(windows)
    numbers, control, fault = (
        reference.compare(c, windows, cfg, ref) for c in (
            calls_4, reference.control_calls(calls_4, windows, cfg),
            reference.altered_calls(calls_4)))
    print(json.dumps({"same": bool(same), "counters": counters,
                      "numbers": numbers, "control": control,
                      "fault": fault,
                      "limits": R.limits_of("pems_ca.bulk4")}))
""")


def test_pems_fleet_sharded_matches_scan_and_the_reference():
    out = subprocess.run([sys.executable, "-c", CHILD, str(ROOT / "bench")],
                         env=subprocess_env(4), cwd=str(ROOT),
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    got = json.loads(out.stdout.strip().splitlines()[-1])
    assert got["same"]
    one, four = got["counters"]
    assert one == [0, 0, 0]
    # the water-fill's 1 + 2 * 8 gathers of the (E,) float32 vector, and
    # its pmax, every window
    assert four == [17, 1, 17 * 4 * 64]
    limits = got["limits"]
    assert set(got["numbers"]) == set(limits)
    for k, v in got["numbers"].items():
        assert v <= limits[k], (k, v, limits[k])
    # the reference in bfloat16 in the program's place, and a planted
    # fault, are not correct
    over = {k for k, v in got["control"].items() if v > limits[k]}
    assert {"totals_gap", "truth_gap", "r2_gap", "budget_gap", "alloc_off",
            "bytes_off"} <= over, got["control"]
    assert got["fault"]["est_err"] > limits["est_err"], got["fault"]
