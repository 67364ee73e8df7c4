"""``exchange_ms``: the device time of the site mesh's collectives a fleet
window, found through the compiled text's ``exchange`` scope; None on a
program without it, such as the parent's."""
import gzip
import importlib.util
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [p for p in (str(BENCH), str(BENCH.parent / "src"))
                if p not in sys.path]

import pytest  # noqa: E402

import run as R  # noqa: E402
import scopes  # noqa: E402
import tracefile  # noqa: E402

DATA = Path(__file__).parent / "data"
read = R.reader("exchange_ms.pems4")
_spec = importlib.util.spec_from_file_location(
    "exchange_ms", BENCH / "metrics" / "exchange_ms.py")
exchange_ms = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(exchange_ms)

# a window step over four chips as the TPU compiler leaves it: the pmax
# keeps its metadata, the all-gathers became all-reduces with none
PROGRAM = """\
HloModule m

%body (p: (s32[], f32[8])) -> (s32[], f32[8]) {
  %p = (s32[], f32[8]) parameter(0)
  %fusion.3 = f32[8]{0} fusion(%p), kind=kLoop, calls=%f, metadata={op_name="jit(fn)/shard_map/while/body/step.budgets/max"}
  %all-reduce.1 = f32[8]{0} all-reduce(%fusion.3), channel_id=1, to_apply=%add
  %pmax.7 = s32[] all-reduce(%x), channel_id=2, to_apply=%r, metadata={op_name="jit(fn)/shard_map/while/body/step.budgets/exchange/pmax"}
  %fusion.9 = f32[8]{0} fusion(%all-reduce.1), kind=kLoop, calls=%g, metadata={op_name="jit(fn)/shard_map/while/body/step.sample/add"}
  ROOT %t = (s32[], f32[8]) tuple(%pmax.7, %fusion.9)
}
"""


def _op(name, rhs, start, end):
    return tracefile.Op(f"%{name} = {rhs}", start, end)


def _run(ops_by_chip, windows_per_call=16):
    bench = R.load_benchmark()
    cell, cfg, traffic = R.find_cell(bench, "pems_ca.bulk4")
    trace = tracefile.Trace(ops=ops_by_chip, spans=[("run", 0.0, 1.0)])
    return R.Run(cell=cell, cfg=cfg,
                 traffic=dict(traffic, windows_per_call=windows_per_call),
                 chips=4, device_kind="TPU v5 lite", setup_s=1.0,
                 timed=[(0.0, 1.0)], trace=trace)


@pytest.fixture
def compiles(monkeypatch):
    """The compiled text the reader is handed, and how often it asked."""
    asked = []

    def text(run):
        asked.append(run)
        return text.program
    text.program = PROGRAM
    monkeypatch.setattr(scopes, "compiled_text", text)
    return text, asked


def test_exchange_sums_the_scoped_and_the_bare_collectives(compiles,
                                                           capsys):
    _, asked = compiles
    chip = [_op("fusion.3", "f32[8]{0} fusion(%p)", 0.10, 0.20),
            _op("all-reduce.1", "f32[8]{0} all-reduce(%fusion.3)",
                0.20, 0.23),
            _op("pmax.7", "s32[] all-reduce(%x)", 0.23, 0.24),
            _op("fusion.9", "f32[8]{0} fusion(%all-reduce.1)", 0.24, 0.50)]
    idle = [_op("fusion.3", "f32[8]{0} fusion(%p)", 0.10, 0.11)]
    run = _run({0: idle, 1: chip, 2: idle, 3: idle})
    assert read(run) == pytest.approx(1e3 * 0.04 / 16)
    assert len(asked) == 1                          # one compile a run
    assert "device ms per window by stage: step.budgets" in (
        capsys.readouterr().err)


def test_a_program_without_the_scope_reads_none(compiles):
    text, asked = compiles
    text.program = PROGRAM.replace("exchange/", "")
    chip = [_op("all-reduce.1", "f32[8]{0} all-reduce(%fusion.3)", 0.2,
                0.3)]
    assert read(_run({d: chip for d in range(4)})) is None
    assert len(asked) == 1


def test_the_recorded_one_chip_program_has_no_exchange(compiles):
    text, _ = compiles
    with gzip.open(DATA / "fixture_program.hlo.txt.gz", "rt") as f:
        text.program = f.read()
    assert exchange_ms.exchange_instructions(text.program) == set()
    run = _run({0: [], 1: [], 2: [], 3: []})
    run.trace.ops[0] = tracefile.load(
        str(DATA / "fixture_program.xplane.pb")).ops[0]
    assert read(run) is None


def test_an_untraced_run_reads_none():
    run = _run({})
    run.trace = None
    assert read(run) is None
