"""Discovery by name, refusal without a chip, and the names' character
set."""
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [p for p in (str(BENCH), str(BENCH.parent / "src"))
                if p not in sys.path]

import json  # noqa: E402
import os  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402

import pytest  # noqa: E402

import generate  # noqa: E402
import run as R  # noqa: E402

ROOT = BENCH.parent
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def _bench():
    with open(ROOT / "BENCHMARK.json") as f:
        return json.load(f)


def test_every_name_and_unit_keeps_to_the_allowed_characters():
    b = _bench()
    names = [x["name"] for kind in ("configs", "workloads", "end_to_end",
                                    "per_layer") for x in b[kind]]
    names += [w[f] for w in b["workloads"] for f in ("config", "traffic")]
    names += [k for c in b["configs"] for k in c["reduced"]]
    assert names and all(NAME.match(n) for n in names), names
    units = [m["unit"] for kind in ("end_to_end", "per_layer")
             for m in b[kind]]
    assert all(UNIT.match(u) for u in units), units
    assert len(set(m["name"] for kind in ("end_to_end", "per_layer")
                   for m in b[kind])) == sum(
        len(b[kind]) for kind in ("end_to_end", "per_layer"))


def test_every_cell_finds_its_files_by_name():
    b = _bench()
    for w in b["workloads"]:
        cell, cfg, traffic = R.find_cell(b, w["name"])
        assert cfg["name"] == w["config"] and traffic["name"] == w["traffic"]
        assert set(R.limits_of(w["name"])) == {
            "totals_gap", "truth_gap", "r2_gap", "est_err", "budget_gap",
            "alloc_off", "bytes_off"}
        for kind in ("end_to_end", "per_layer"):
            for m in R.metrics_of(b, w["name"], kind):
                assert callable(R.reader(m["name"]))
    for c in b["configs"]:
        with open(ROOT / c["file"]) as f:
            assert json.load(f)["reduced"] == c["reduced"]


def test_a_config_traffic_and_metric_added_as_files_are_found(tmp_path,
                                                              monkeypatch):
    copy = tmp_path / "bench"
    shutil.copytree(BENCH, copy, ignore=shutil.ignore_patterns(
        "__pycache__", "tests"))
    cfg = generate.load("configs", "city")
    (copy / "configs" / "town.json").write_text(
        json.dumps(dict(cfg, name="town", sites=8, regions=1)))
    (copy / "traffic" / "trickle.json").write_text(json.dumps(
        {"name": "trickle", "loop": "closed", "windows_per_call": 3,
         "distinct_windows": 6, "why": "three windows a call"}))
    (copy / "metrics" / "calls_per_s.py").write_text(
        "def read(run):\n    return len(run.timed) / run.window_s\n")
    shutil.copy(BENCH / "limits" / "city.bulk.json",
                copy / "limits" / "town.trickle.json")
    b = _bench()
    b["workloads"].append({"name": "town.trickle", "config": "town",
                           "traffic": "trickle", "chips": 1, "why": "test"})
    b["end_to_end"].append({"name": "calls_per_s", "unit": "1/s",
                            "better": "higher", "bound": 0.1,
                            "source": "host_clock",
                            "workloads": ["town.trickle"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))
    monkeypatch.setattr(generate, "HERE", copy)
    monkeypatch.setattr(R, "HERE", copy)
    monkeypatch.setattr(R, "ROOT", tmp_path)

    b = R.load_benchmark()
    cell, cfg, traffic = R.find_cell(b, "town.trickle")
    assert cfg["sites"] == 8 and traffic["windows_per_call"] == 3
    assert R.limits_of("town.trickle")["bytes_off"] == 0
    names = [m["name"] for m in R.metrics_of(b, "town.trickle",
                                             "end_to_end")]
    assert names == ["setup_s", "calls_per_s"]
    run = R.Run(cell=cell, cfg=cfg, traffic=traffic, chips=1,
                device_kind="TPU v5 lite", setup_s=1.0,
                timed=[(0.0, 1.0), (1.0, 2.0)])
    assert R.reader("calls_per_s")(run) == 1.0
    assert generate.fleet_windows(cfg, 2, 3).shape == (2, 8, 5, 288)


def _run_cli(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("PYTHONPATH", None)
    cell = _bench()["workloads"][0]["name"]
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", cell,
         "--seed", str(2**33 + 1), "--seconds", "1", "--trace", "0"],
        cwd=str(cwd), env=env, capture_output=True, text=True, timeout=300)


def test_without_a_tpu_it_exits_nonzero_and_prints_no_result():
    out = _run_cli(ROOT)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "no TPU" in out.stderr


def test_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = _run_cli(tmp_path)
    assert out.returncode != 0
    assert out.stdout.strip() == ""


def test_a_workload_that_is_not_there_is_refused():
    with pytest.raises(R.Refused, match="city.live"):
        R.find_cell(_bench(), "city.nope")
