"""The program's spans and stage scopes as the benchmark reads them.

``fixture.xplane.pb`` is the parent's recorded trace (harness spans only);
its four readers must read as they did.  ``fixture_program.xplane.pb`` was
recorded on a TPU v5e the same way, with this program: two one-window
calls of a 16-site fleet (k=5, N=32) through the served scan runtime, with
the harness's ``run`` spans and the program's ``scan.*`` spans and their
args.  ``fixture_program.hlo.txt.gz`` is that program's compiled text on
the chip, which names each op's stage.  The trace keeps what the readers
read: the chip's ``XLA Ops`` events with their names and times (and each
op's ``tf_op`` stat, its metadata path, which ``ProfileData`` does not
show), and the host spans with their args.
"""
import gzip
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [p for p in (str(BENCH), str(BENCH.parent / "src"))
                if p not in sys.path]

import pytest  # noqa: E402

import phases  # noqa: E402
import run as R  # noqa: E402
import scopes  # noqa: E402
import tracefile  # noqa: E402

DATA = Path(__file__).parent / "data"
OLD = DATA / "fixture.xplane.pb"
NEW = DATA / "fixture_program.xplane.pb"
HLO = DATA / "fixture_program.hlo.txt.gz"

# what the parent's readers read on the parent's fixture
PARENT_READS = {"host_ms.live": 27.263584000000023,
                "step_ms.live": 0.34042349999997834,
                "stream_stats_roofline": 0.9384613954071602,
                "polyfit_roofline": 1.7695669869591613}


def _run(trace):
    bench = R.load_benchmark()
    cell, cfg, traffic = R.find_cell(bench, "city.live")
    cfg = dict(cfg, sites=16, window=32, diurnal_period=32)
    return R.Run(cell=cell, cfg=cfg, traffic=traffic, chips=1,
                 device_kind="TPU v5 lite", setup_s=1.0,
                 timed=[(0.0, 1.0)], trace=trace)


@pytest.fixture
def new_run():
    return _run(tracefile.load(str(NEW)))


@pytest.fixture(scope="module")
def stage_of():
    with gzip.open(HLO, "rt") as f:
        return scopes.hlo_scopes(f.read())


@pytest.fixture
def program(monkeypatch):
    """The compiled text the readers look up, as recorded on the chip."""
    with gzip.open(HLO, "rt") as f:
        text = f.read()
    monkeypatch.setattr(scopes, "compiled_text", lambda run: text)


@pytest.mark.parametrize("metric", sorted(PARENT_READS))
def test_the_old_fixture_reads_the_parents_values(metric):
    run = _run(tracefile.load(str(OLD)))
    assert R.reader(metric)(run) == PARENT_READS[metric]


HLO_TEXT = """\
HloModule m

%body (p: (s32[], u16[8])) -> (s32[], u16[8]) {
  %p = (s32[], u16[8]) parameter(0)
  %sort.1 = u16[8]{0} sort(%x), dimensions={0}
  ROOT %fusion.2 = u16[8]{0} fusion(%sort.1), kind=kLoop, calls=%f, metadata={op_name="jit(fn)/while/body/step.sample/while/body/gather"}
}

%cond (p: (s32[], u16[8])) -> pred[] {
  ROOT %lt = pred[] compare(%a, %b), direction=LT
}

%br (q: f32[4]) -> f32[4] {
  ROOT %neg = f32[4]{0} negate(%q)
}

ENTRY %main (a: f32[4]) -> f32[4] {
  %while.3 = (s32[], u16[8]) while(%t), condition=%cond, body=%body, metadata={op_name="jit(fn)/while/body/step.sample/while"}
  %conditional.4 = f32[4]{0} conditional(%c, %a, %a), branch_computations={%br, %br}, metadata={op_name="jit(fn)/while/body/step.plan/cond"}
  %copy.5 = f32[4]{0} copy(%a)
  ROOT %add.6 = f32[4]{0} add(%a, %a), metadata={op_name="jit(fn)/while/body/step.update/add"}
}
"""


def test_an_op_takes_its_own_stage_or_that_of_the_loop_that_holds_it():
    assert scopes.hlo_scopes(HLO_TEXT) == {
        "p": "step.sample", "sort.1": "step.sample", "fusion.2": "step.sample",
        "lt": "step.sample", "neg": "step.plan", "while.3": "step.sample",
        "conditional.4": "step.plan", "copy.5": None,
        "add.6": "step.update"}


def test_a_gap_is_named_by_the_innermost_span_over_most_of_it():
    S = phases.Span
    spans = [S("run", 0.0, 10.0, {}), S("scan.execute", 1.0, 3.0, {}),
             S("scan.readback", 3.0, 4.0, {}), S("scan.report", 4.0, 9.0, {}),
             S("run", 11.0, 20.0, {}), S("scan.prepare", 11.5, 12.0, {})]
    assert phases.gap_name(spans, 2.5, 9.5) == "scan.report"
    assert phases.gap_name(spans, 9.0, 11.6) == "run"
    assert phases.gap_name(spans, 10.0, 11.0) == "outside_spans"


def test_the_program_spans_lie_in_order_inside_the_harness_runs():
    spans = phases.load_spans(str(NEW))
    calls = phases.calls(spans)
    assert len(calls) == 2
    ids = set()
    for r, inner in calls:
        assert list(inner) == list(phases.PHASES)
        seq = list(inner.values())
        assert all(a.end <= b.start for a, b in zip(seq, seq[1:]))
        assert all(s.args["windows"] == 1 for s in seq)
        assert len({s.args["call"] for s in seq}) == 1
        ids.add(seq[0].args["call"])
    assert len(ids) == 2 and max(ids) - min(ids) == 1
    inside = sum(len(inner) for _, inner in calls)
    assert inside == sum(s.name in phases.PHASES for s in spans)


def test_the_idle_gaps_are_named_by_program_spans(new_run):
    lo, hi = new_run.trace_window()
    spans = phases.load_spans(str(NEW))
    gaps = phases.idle_gaps(new_run.trace.ops[0], spans, lo, hi, n=3)
    assert all(name in phases.PHASES for name, _ in gaps), gaps


def test_every_op_of_the_trace_is_an_instruction_of_the_program(new_run,
                                                                 stage_of):
    lo, hi = new_run.trace_window()
    ops = [o for o in new_run.trace.ops[0] if lo <= o.start < hi]
    assert ops and all(scopes.instruction(o) in stage_of for o in ops)


def test_the_stage_readers_read_inside_the_step(new_run, program, stage_of):
    sample = R.reader("sample_ms.bulk")(new_run)
    plan = R.reader("plan_ms.bulk")(new_run)
    step = R.reader("step_ms.live")(new_run)
    assert sample > 0 and plan > 0
    assert sample + plan <= step
    lo, hi = new_run.trace_window()
    per = 1e3 / new_run.traced_windows()
    staged = sum(per * scopes.scope_seconds(new_run.trace.ops[0], lo, hi,
                                            stage_of, s)
                 for s in set(stage_of.values()) - {None})
    assert staged <= step


def test_a_program_without_stages_reads_nothing(monkeypatch):
    monkeypatch.setattr(scopes, "compiled_text", lambda run: HLO_TEXT.replace(
        "step.", "stage."))
    run = _run(tracefile.load(str(OLD)))
    assert R.reader("sample_ms.bulk")(run) is None
    assert R.reader("plan_ms.bulk")(run) is None
