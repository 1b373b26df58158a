"""The benchmark's general machinery, driven by data.

A cell of ``BENCHMARK.json`` names a configuration file (sizes and
settings of one deployment) and a traffic mix (a JSON file of
parameters under ``bench/traffic/``).  The mix names the runner that
generates it (``bench/runners/<runner>.py``); the runner sets the
program up, runs the measured window and checks what the window
produced against ``bench/reference.py``.  Per-layer metrics are small
readers under ``bench/metrics/<name>.py``, each found by its name.  A
later cell or metric is added by adding such files and entries.

Every run prints the numbers it compared, each beside its limit, as its
last lines on standard error, and one JSON result as the last line of
standard output.
"""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import math
import os
import shutil
import sys
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
WORK_DIR = os.path.join(BENCH_DIR, ".work")


def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return load_json(os.path.join(ROOT, "BENCHMARK.json"))


@dataclasses.dataclass
class Check:
    """One number compared with its limit: correct when value <= limit."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return bool(self.value <= self.limit) and not math.isnan(self.value)


@dataclasses.dataclass
class WindowResult:
    """What a runner's window measured."""
    metrics: Dict[str, float]           # end-to-end values, by name
    attempted: int
    failed: int
    counters: Dict[str, Any]            # read by the per-layer metrics
    notes: Dict[str, Any] = dataclasses.field(default_factory=dict)


class Context:
    """Everything a runner needs about its cell and run."""

    def __init__(self, cell: dict, config: dict, traffic: dict, seed: int,
                 chips: int, workdir: str, seconds: float):
        self.cell = cell
        self.name = cell["name"]
        self.config = config
        self.traffic = traffic
        self.seed = int(seed)
        # the program's own seeds go through 32-bit JAX keys
        self.seed32 = int(seed) % (2 ** 31 - 1)
        self.chips = int(chips)
        self.workdir = workdir
        self.seconds = float(seconds)      # the measured window

    def span(self, name: str):
        """A host span in the profiler's trace (a no-op when no trace is
        being taken)."""
        import jax
        return jax.profiler.TraceAnnotation(name)

    def fresh_dir(self, *parts: str) -> str:
        path = os.path.join(self.workdir, *parts)
        shutil.rmtree(path, ignore_errors=True)
        os.makedirs(path)
        return path


def find_cell(name: str, bench: Optional[dict] = None) -> dict:
    bench = benchmark() if bench is None else bench
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                   f"{[c['name'] for c in bench['workloads']]}")


def cell_files(cell: dict, bench: dict) -> Tuple[dict, dict]:
    """(configuration, traffic) of a cell, as their files hold them."""
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = load_json(os.path.join(ROOT, conf["file"]))
    traffic = load_json(os.path.join(BENCH_DIR, "traffic",
                                     cell["traffic"] + ".json"))
    return config, traffic


def runner(traffic: dict):
    return importlib.import_module(f"bench.runners.{traffic['runner']}")


def metric_reader(name: str) -> Callable:
    path = os.path.join(BENCH_DIR, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def cell_metrics(cell: dict, bench: dict, trace: bool) -> List[dict]:
    """The metrics this cell reports in a run with or without a trace."""
    name = cell["name"]
    pool = bench["per_layer"] if trace else bench["end_to_end"]
    out = []
    for m in pool:
        if "workloads" in m and name not in m["workloads"]:
            continue
        if trace and "workloads" not in m:
            moved = next(e for e in bench["end_to_end"]
                         if e["name"] == m["moves"])
            if "workloads" in moved and name not in moved["workloads"]:
                continue
        out.append(m)
    return out


class CompileCounter:
    """Counts the executables JAX builds (compiled or read from the
    persistent cache) while ``active``: none should be built inside a
    measured window."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.active = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if self.active and event == self.EVENT:
            self.count += 1


def device_info(chips: int) -> dict:
    import jax
    devs = jax.devices()[:chips]
    peak = 0
    for d in devs:
        try:
            stats = d.memory_stats() or {}
        except Exception:  # noqa: BLE001 — a backend without stats
            stats = {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def arms() -> dict:
    """The implementation each dispatched op ran with, from the
    program's own record (``perf.dispatch_report()``)."""
    from repro import perf
    out: dict = {}
    for key, impl in perf.dispatch_report()["choices"].items():
        out.setdefault(key.split("|")[0], set()).add(impl)
    return {op: sorted(v) for op, v in sorted(out.items())}


def arm_checks(chosen: dict, want: dict) -> list:
    """Hot ops that left their kernel arm (0 when every one stayed)."""
    off = sum(1 for op, impl in want.items() if chosen.get(op) != [impl])
    return [Check("ops_off_kernel_arm", off, 0)] if want else []


def report_checks(checks: List[Check]) -> dict:
    """The numbers compared, each with its limit (the result's last
    key), also printed as the last lines on standard error."""
    for c in checks:
        print(f"check {c.name} = {c.value!r} (limit {c.limit!r}) "
              f"{'ok' if c.ok else 'FAILED'}", file=sys.stderr, flush=True)
    return {c.name: {"value": c.value, "limit": c.limit} for c in checks}


def run_cell(cell_name: str, seed: int, seconds: float, trace: bool,
             t_start: float, bench: Optional[dict] = None) -> dict:
    """Sets up one cell, measures it, checks it; returns the result."""
    bench = benchmark() if bench is None else bench
    cell = find_cell(cell_name, bench)
    config, traffic = cell_files(cell, bench)
    ctx = Context(cell, config, traffic, seed, cell["chips"],
                  os.path.join(WORK_DIR, cell_name), seconds)
    os.makedirs(ctx.workdir, exist_ok=True)
    drv = runner(traffic)

    state = drv.setup(ctx)
    setup_s = time.perf_counter() - t_start
    log({"phase": "setup", "cell": cell_name, "setup_s": setup_s})

    compiles = CompileCounter()
    tracer = None
    if trace:
        from bench import trace as tr
        tracer = tr.Tracer(ctx.fresh_dir("trace"))
        tracer.start()
    compiles.active = True
    try:
        try:
            with ctx.span("bench.window"):
                out = drv.window(ctx, state, seconds)
        finally:
            compiles.active = False
            if tracer is not None:
                tracer.stop()
        device = device_info(ctx.chips)
    finally:
        drv.release(ctx, state)      # stops what set-up started
    out.counters["compiles_in_window"] = compiles.count
    checks = drv.check(ctx, state, out)
    log({"phase": "window", "cell": cell_name, "counters": out.counters,
         **out.notes})

    result: Dict[str, Any] = {
        "correct": all(c.ok for c in checks),
        "attempted": out.attempted, "failed": out.failed}
    metrics = {}
    if trace:
        summary = tracer.summary(ctx.chips)
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        read_ctx = {"trace": summary, "counters": out.counters,
                    "config": config, "traffic": traffic,
                    "device_kind": device["kind"], "chips": ctx.chips}
        for m in cell_metrics(cell, bench, trace=True):
            value = metric_reader(m["name"])(read_ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        result["breakdown"] = summary.breakdown()
    else:
        values = dict(out.metrics, setup_s=setup_s)
        for m in cell_metrics(cell, bench, trace=False):
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    result["metrics"] = metrics
    result["device"] = device
    result["checks"] = report_checks(checks)
    return result


def log(rec: dict) -> None:
    print(json.dumps(rec, default=str), flush=True)


def percentile(values, q: float) -> float:
    """The q-th percentile (0..100) by the nearest-rank rule: the
    smallest value with at least q% of the values at or below it."""
    vals = sorted(values)
    if not vals:
        return float("nan")
    rank = max(1, math.ceil(q / 100.0 * len(vals)))
    return float(vals[rank - 1])
