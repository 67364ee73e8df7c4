"""Trace reduction and rooflines, on a small trace recorded on a TPU v5e:
two one-window calls of a 16-site fleet (k=5, N=32) through the served
scan runtime, with the harness's ``run`` and ``between_calls`` spans.  The
file keeps what the reducer reads: the chip's ``XLA Ops`` line and the
host's harness spans, each event with its name and times."""
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [p for p in (str(BENCH), str(BENCH.parent / "src"))
                if p not in sys.path]


import pytest  # noqa: E402

import roofline  # noqa: E402
import run as R  # noqa: E402
import tracefile  # noqa: E402

FIXTURE = Path(__file__).parent / "data" / "fixture.xplane.pb"


@pytest.fixture(scope="module")
def tr():
    return tracefile.load(str(FIXTURE))


@pytest.fixture(scope="module")
def run(tr):
    bench = R.load_benchmark()
    cell, cfg, traffic = R.find_cell(bench, "city.live")
    cfg = dict(cfg, sites=16, window=32, diurnal_period=32)
    return R.Run(cell=cell, cfg=cfg, traffic=traffic, chips=1,
                 device_kind="TPU v5 lite", setup_s=1.0, timed=[(0.0, 1.0)],
                 trace=tr)


def test_the_trace_holds_one_chip_and_the_harness_spans(tr):
    assert tr.devices == [0]
    assert [n for n, _, _ in tr.spans if n == "run"] == ["run", "run"]
    lo, hi = tr.window("run")
    assert 0 < hi - lo < 10
    with pytest.raises(ValueError):
        tr.window("generate")


def test_busy_time_is_a_union_inside_the_window(tr):
    lo, hi = tr.window("run")
    ops = tr.ops[0]
    busy = tracefile.busy_seconds(ops, lo, hi)
    assert 0 < busy <= hi - lo
    summed = tracefile.op_seconds(ops, lo, hi, lambda o: True)
    assert busy <= summed       # loops hold their bodies' ops
    iv = tracefile.busy_intervals(ops, lo, hi)
    assert all(a[1] < b[0] for a, b in zip(iv, iv[1:]))


def test_both_kernels_are_found_and_their_rooflines_are_shares(run):
    for kernel, metric in (("stream_stats_fleet", "stream_stats_roofline"),
                           ("polyfit", "polyfit_roofline")):
        lo, hi = run.trace_window()
        spent = tracefile.op_seconds(run.trace.ops[0], lo, hi,
                                     tracefile.kernel_match(kernel))
        assert spent > 0, kernel
        share = R.reader(metric)(run)
        assert 0 < share <= 100, (metric, share)


def test_per_layer_readers_on_one_chip(run):
    host = R.reader("host_ms.live")(run)
    step = R.reader("step_ms.live")(run)
    assert host > 0 and step > 0
    lo, hi = run.trace_window()
    assert step * run.traced_windows() <= 1e3 * (hi - lo)


def test_breakdown_names_ops_and_gaps(tr):
    lo, hi = tr.window("run")
    top = tracefile.top_ops(tr.ops[0], lo, hi)
    assert 0 < len(top) <= 10
    assert all(" while " not in name for name, _ in top)
    assert top == sorted(top, key=lambda x: -x[1])
    gaps = tracefile.idle_gaps(tr, 0, lo, hi)
    assert gaps and all(n in tracefile.SPANS or n == "outside_spans"
                        for n, _ in gaps)
    busy = tracefile.busy_seconds(tr.ops[0], lo, hi)
    assert sum(s for _, s in tracefile.idle_gaps(tr, 0, lo, hi, n=10**6)) \
        == pytest.approx(hi - lo - busy)


def test_op_names_parse():
    op = tracefile.Op("%all-gather.3 = f32[4096]{0} all-gather(f32[1024]{0}"
                      " %x), dimensions={0}", 0.0, 1.0)
    assert op.kind == "all-gather"
    assert op.short == "all-gather.3 all-gather f32[4096]"
    loop = tracefile.Op("%while.1 = (s32[], f32[8]{0}) while((s32[], "
                        "f32[8]{0}) %t), body=%b", 0.0, 1.0)
    assert loop.kind == "while" and loop.short == "while.1 while tuple"


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="TPU v5 lite"):
        roofline.peaks("TPU v9 imaginary")
    assert roofline.least_seconds("polyfit", 1796, 5, 288,
                                  "TPU v5 lite") > 0


def test_rooflines_count_unpadded_work():
    s = roofline.stream_stats(1796, 5, 288)
    assert s["bytes"] == 4 * (1796 * 5 * 288 + 1796 * 5 * 9)
    assert s["mxu_ops"] == 2 * 1796 * 25 * 288
    p = roofline.polyfit(1024, 8, 144)
    assert p["bytes"] == 4 * (2 * 1024 * 8 * 144 + 1024 * 8 * 11)
