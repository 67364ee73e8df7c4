#!/usr/bin/env python3
"""The chip benchmark of the fleet scan runtime.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell of ``BENCHMARK.json`` names a configuration (``bench/configs/``)
and a traffic mix (``bench/traffic/``).  The harness makes the cell's
distinct fleet windows from ``--seed`` on the device, builds the served
runtime as a user does (``Experiment.from_scenario(...).runtime`` with
``collect="estimates"``: ``scan`` on one chip, ``scan_sharded`` on four),
warms it up, and then serves windows in a closed loop for ``--seconds``:
each ``run`` call takes the traffic's windows per call and resumes the
carry the previous call returned.  Once the window has closed it checks
what every call returned against the plain reference (``reference.py``).

``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics from a profiler trace of the window.  Each metric is
read by ``bench/metrics/<name>.py``.  The last line of standard output is
one JSON object; the compared numbers and their limits end standard error.
Without a TPU, or with fewer chips than the cell asks for, it exits 2 and
prints no result.
"""
import time

PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
CACHE_DIR = ROOT / ".jax_cache"
sys.path.insert(0, str(HERE))
sys.path.insert(1, str(ROOT / "src"))

import generate  # noqa: E402
import reference  # noqa: E402

RUNTIME_BY_CHIPS = {1: "scan", 4: "scan_sharded"}
WARMUP_CALLS = 1            # compiles, or loads the compile cache
TRACE_SECONDS = 4.0         # a traced run traces at most this much window


class Refused(Exception):
    """The run cannot be made here; nothing is measured or printed."""


def say(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


# ------------------------------------------------------------ the cell

def load_benchmark() -> dict:
    with open(ROOT / "BENCHMARK.json") as f:
        return json.load(f)


def find_cell(bench: dict, name: str):
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise Refused(f"no workload {name!r}; have {sorted(cells)}")
    cell = cells[name]
    return (cell, generate.load("configs", cell["config"]),
            generate.load("traffic", cell["traffic"]))


def metrics_of(bench: dict, cell: str, kind: str) -> list:
    """The cell's metrics of ``kind`` (``end_to_end`` or ``per_layer``)."""
    return [m for m in bench[kind]
            if "workloads" not in m or cell in m["workloads"]]


def reader(name: str):
    """``read(run) -> float | None`` of ``bench/metrics/<name>.py``, or of
    ``<stem>.py`` where a metric ``<stem>.<part>`` has no file of its own
    (``host_ms.live`` and ``host_ms.bulk`` read alike)."""
    path = HERE / "metrics" / f"{name}.py"
    if not path.is_file():
        path = HERE / "metrics" / f"{name.partition('.')[0]}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def limits_of(cell: str) -> dict:
    with open(HERE / "limits" / f"{cell}.json") as f:
        return json.load(f)["limits"]


def tpu_devices(chips: int) -> list:
    """The TPUs, once JAX is set to keep its compile cache in the checkout
    and the TPU runtime its logs nowhere; refused without enough chips."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    try:
        devs = jax.devices("tpu")
    except RuntimeError as e:
        raise Refused(f"no TPU: {e}") from None
    if len(devs) < chips:
        raise Refused(f"the cell needs {chips} TPU chips, JAX found "
                      f"{len(devs)}")
    return devs


# --------------------------------------------------------- the program

def build_runtime(cfg: dict, chips: int):
    """The served runtime, built as a user builds it."""
    from repro.api import (ControllerSpec, DataSpec, Experiment,
                           ScenarioConfig, TopologySpec)
    from repro.core.types import PlannerConfig
    e, r = int(cfg["sites"]), int(cfg["regions"])
    k, n = int(cfg["streams_per_site"]), int(cfg["window"])
    ctl = cfg["controller"]
    scenario = ScenarioConfig(
        name=cfg["name"],
        data=DataSpec(dataset="fleet", n_points=n, window=n,
                      options={"k": k}),
        planner=PlannerConfig(solver=cfg["solver"],
                              dependence=cfg["dependence"],
                              model=cfg["model"], seed=cfg["planner_seed"]),
        topology=TopologySpec(n_regions=r, sites_per_region=e // r,
                              latency_scale=cfg["link_latency"]),
        controller=ControllerSpec(mode=ctl["mode"],
                                  floor_mult=ctl["floor_mult"],
                                  ceil_mult=ctl["ceil_mult"],
                                  ewma=ctl["ewma"]),
        queries=tuple(cfg["queries"]),
        budget_fraction=cfg["budget_fraction"],
        runtime=RUNTIME_BY_CHIPS[chips])
    rt = Experiment.from_scenario(scenario).runtime
    rt.collect = "estimates"
    return rt


def first_window(seed: int, distinct: int) -> int:
    """The seed's first window id: it keys the sampler's random stream, so
    seeds differ in it without a program of their own to compile."""
    return distinct * int(np.random.default_rng(seed).integers(0, 1 << 20))


class Server:
    """The closed-loop client: one ``run`` call at a time, each on the
    next ``per_call`` of the distinct windows, resuming the carry.

    The served answer and truth tables are the ones ``run`` hands to its
    fleet report; the server keeps a reference to them as they pass."""

    def __init__(self, rt, windows: np.ndarray, per_call: int, w0: int):
        self.rt, self.windows, self.per_call = rt, windows, per_call
        self.w0, self.state = w0, None
        self.calls, self.wan_bytes, self.nrmse = [], 0, []
        self.tables = None
        report = rt._result_fleet

        def keep(est, tru, *a, **kw):
            self.tables = est, tru
            return report(est, tru, *a, **kw)
        rt._result_fleet = keep

    def call(self) -> tuple:
        """Serve one request; (start, end) on the host clock."""
        n = len(self.calls)
        slots = (n * self.per_call + np.arange(self.per_call)) % len(
            self.windows)
        batch = [self.windows[s] for s in slots]
        t0 = time.perf_counter()
        res = self.rt.run(batch, n_windows=self.per_call, state=self.state,
                          first_window=self.w0 if self.state is None
                          else None)
        t1 = time.perf_counter()
        self.state = res["final_state"]
        tot = self.state.totals
        est, tru = self.tables
        plan = res["plan_raw"]
        self.calls.append(reference.Call(
            slots=slots, budgets=np.asarray(res["budget_history"]),
            nbytes=np.asarray(res["bytes_history"]),
            objective=np.asarray(plan["objective"]),
            r2=np.asarray(plan["r2"]),
            est_avg=np.asarray(est["AVG"], np.float32),
            tru={q: np.asarray(tru[q], np.float32)
                 for q in reference.QUERIES},
            count=np.asarray(tot.count), s1=np.asarray(tot.s1),
            s2=np.asarray(tot.s2)))
        self.wan_bytes += int(res["wan_bytes"])
        self.nrmse.append(res["fleet_nrmse"])
        return t0, t1

    def close(self) -> None:
        """Free the program's state before the reference runs."""
        if self.rt is not None:
            del self.rt._result_fleet
        self.rt = self.state = self.tables = None


def span(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


class CompileCounter:
    """Counts the compiles JAX reports while it is open."""

    def __init__(self):
        import jax
        self.events = 0
        self.open = False

        def listen(event, duration, **kw):
            if self.open and "backend_compile" in event:
                self.events += 1
        jax.monitoring.register_event_duration_secs_listener(listen)


# ------------------------------------------------------------- the run

@dataclasses.dataclass
class Run:
    """What the metric readers read."""

    cell: dict
    cfg: dict
    traffic: dict
    chips: int
    device_kind: str
    setup_s: float
    timed: list               # [(start, end)] of each timed call
    trace: object = None      # trace.Trace of the window, or None

    @property
    def windows_per_call(self) -> int:
        return int(self.traffic["windows_per_call"])

    @property
    def tuples_per_window(self) -> int:
        c = self.cfg
        return (int(c["sites"]) * int(c["streams_per_site"])
                * int(c["window"]))

    @property
    def window_s(self) -> float:
        return self.timed[-1][1] - self.timed[0][0]

    def trace_window(self) -> tuple:
        return self.trace.window("run")

    def traced_windows(self) -> int:
        """Fleet windows whose calls lie inside the traced window."""
        return self.windows_per_call * sum(
            1 for n, _, _ in self.trace.spans if n == "run")


def measure(bench: dict, cell: dict, cfg: dict, traffic: dict, *,
            seed: int, seconds: float, trace: bool, devices: list,
            log=say) -> dict:
    """Set up, serve for ``seconds``, check; the result line as a dict."""
    import jax
    chips = int(cell["chips"])
    device_kind = devices[0].device_kind
    per_call = int(traffic["windows_per_call"])
    distinct = int(traffic["distinct_windows"])
    stamps = [("start", PROCESS_START), ("jax", time.perf_counter())]
    with span("generate"):
        windows = generate.fleet_windows(cfg, distinct, seed)
    stamps.append(("generate", time.perf_counter()))
    server = Server(build_runtime(cfg, chips), windows, per_call,
                    first_window(seed, distinct))
    stamps.append(("build", time.perf_counter()))
    with span("warmup"):
        for _ in range(WARMUP_CALLS):
            server.call()
    stamps.append(("warmup", time.perf_counter()))
    log("set-up phases (s): " + ", ".join(
        f"{name} {b - a:.3f}" for (_, a), (name, b) in zip(stamps,
                                                          stamps[1:])))
    compiles = CompileCounter()
    window_s = min(seconds, TRACE_SECONDS) if trace else seconds
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    try:
        if trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0       # host spans, not every call
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        compiles.open = True
        t_start = time.perf_counter()
        setup_s = t_start - PROCESS_START
        timed = []
        while not timed or timed[-1][1] - t_start < window_s:
            with span("run"):
                timed.append(server.call())
        compiles.open = False
        if trace:
            jax.profiler.stop_trace()
        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devices[:chips])
        tr = None
        if trace:
            import tracefile as trace_mod
            tr = trace_mod.load(trace_mod.xplane_path(trace_dir))
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    calls = server.calls
    server.close()

    run = Run(cell=cell, cfg=cfg, traffic=traffic, chips=chips,
              device_kind=device_kind, setup_s=setup_s, timed=timed,
              trace=tr)
    log(f"{len(timed)} calls of {per_call} fleet windows in "
        f"{run.window_s:.3f} s after {WARMUP_CALLS} warm-up calls; "
        f"set-up {setup_s:.3f} s; compiles in the window: {compiles.events}")
    lat = np.asarray([e - s for s, e in timed]) * 1e3
    log(f"call latency over {lat.size} calls: median "
        f"{np.median(lat):.3f} ms, p95 {np.percentile(lat, 95):.3f} ms, "
        f"max {lat.max():.3f} ms")
    nrmse = {q: float(np.mean([r[q] for r in server.nrmse]))
             for q in cfg["queries"]}
    log(f"WAN bytes {server.wan_bytes} over {len(calls) * per_call} "
        f"windows ({server.wan_bytes / (len(calls) * per_call * 4 * run.tuples_per_window):.4f}"
        f" of the raw stream); mean fleet NRMSE per call {nrmse}")

    checks = check(calls, windows, cfg, limits_of(cell["name"]),
                   first_timed=WARMUP_CALLS)
    result = {"correct": checks["correct"], "attempted": len(timed),
              "failed": checks["failed"]}
    result["metrics"] = read_metrics(bench, run, trace)
    result["device"] = {"platform": devices[0].platform,
                        "kind": device_kind, "count": len(devices),
                        "memory_peak_bytes": int(peak)}
    if tr is not None:
        import tracefile as trace_mod
        lo, hi = run.trace_window()
        busy = [trace_mod.busy_seconds(tr.ops[d], lo, hi)
                for d in tr.devices[:chips]]
        result["device"]["busy_s"] = float(np.mean(busy))
        result["device"]["window_s"] = hi - lo
        dev = tr.devices[int(np.argmax(busy))]
        result["breakdown"] = {
            "device_ops": trace_mod.top_ops(tr.ops[dev], lo, hi),
            "idle_gaps": trace_mod.idle_gaps(tr, dev, lo, hi)}
        log(f"traced {hi - lo:.3f} s: busy {result['device']['busy_s']:.4f}"
            f" s a chip ({100 * result['device']['busy_s'] / (hi - lo):.2f}"
            f" %)")
    result["checks"] = checks["numbers"]
    return result


def read_metrics(bench: dict, run: Run, trace: bool) -> dict:
    kind = "per_layer" if trace else "end_to_end"
    out = {}
    for m in metrics_of(bench, run.cell["name"], kind):
        value = reader(m["name"])(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def check(calls: list, windows: np.ndarray, cfg: dict, limits: dict,
          first_timed: int = 0) -> dict:
    """Compare every call with the reference; a timed call fails when one
    of its own numbers passes its limit.  The numbers shown are the worst
    over every call, warm-up included."""
    per_call = reference.compare_calls(calls, windows, cfg)
    worst = {k: max(c[k] for c in per_call) for k in limits}
    failed = sum(
        1 for nums in per_call[first_timed:]
        if any(nums[k] > limits[k] for k in limits))
    numbers = {k: {"value": worst[k], "limit": limits[k]} for k in limits}
    correct = all(worst[k] <= limits[k] for k in limits)
    return {"correct": bool(correct), "failed": failed, "numbers": numbers}


def report(result: dict) -> None:
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        bench = load_benchmark()
        cell, cfg, traffic = find_cell(bench, args.workload)
        devices = tpu_devices(int(cell["chips"]))
    except Refused as e:
        say(f"refused: {e}; nothing was measured")
        return 2
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    from repro.compile_cache import setup_compile_cache
    say(f"{args.workload}: {devices[0].device_kind} x{len(jax.devices())}, "
        f"JAX {jax.__version__}, compile cache {setup_compile_cache()}")
    result = measure(bench, cell, cfg, traffic, seed=args.seed,
                     seconds=args.seconds, trace=bool(args.trace),
                     devices=devices)
    report(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
