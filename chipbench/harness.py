"""The benchmark's harness: finds a cell's files by name, runs its driver, and
builds the result line.

Everything that belongs to one configuration, traffic mix, driver, generator
or per-layer metric sits in a file of its own, found by the name that
``BENCHMARK.json`` or a configuration file gives:

    chipbench/configs/<config>.json      sizes, settings, `driver`, `reference`
    chipbench/configs/<reference>        plain float32 reference of the model
    chipbench/traffic/<traffic>.json     `generator` and its parameters
    chipbench/generators/<generator>.py  makes the inputs from the seed
    chipbench/drivers/<driver>.py        set-up, measured window, check
    chipbench/metrics/<metric>.py        reads one per-layer metric
    chipbench/limits/<workload>.json     the limits the check compares against

A new cell is new files plus a new ``workloads`` entry; no file changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import sys
import time
from typing import Any, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))


def root_of(bench_dir: str = HERE) -> str:
    return os.path.dirname(bench_dir)


def load_json(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def load_module(path: str) -> Any:
    """Import a file by its path (names may hold '.' and '-')."""
    name = "chipbench_" + os.path.relpath(path, HERE).replace(os.sep, "_") \
        .replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def part(bench_dir: str, kind: str, name: str, ext: str) -> str:
    path = os.path.join(bench_dir, kind, name + ext)
    if not os.path.exists(path):
        raise FileNotFoundError(f"no {kind[:-1]} named {name!r} ({path})")
    return path


def log(msg: str) -> None:
    """A progress line on stderr (the check's lines come last)."""
    print(f"[chipbench] {msg}", file=sys.stderr, flush=True)


def seed_words(seed: int):
    """A seed of any size as two 32-bit words (jax keys hold 32 bits each)."""
    seed = int(seed)
    if seed < 0:
        raise ValueError("seeds are non-negative")
    return seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF


def jax_key(seed: int, stream: int = 0):
    import jax
    lo, hi = seed_words(seed)
    key = jax.random.fold_in(jax.random.PRNGKey(lo), hi)
    return jax.random.fold_in(key, stream)


# ---------------------------------------------------------------------------
# checks
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Check:
    """One number compared with its limit; it passes when value <= limit."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return bool(self.value == self.value and self.value <= self.limit)


def verdict(checks: List[Check]) -> bool:
    """``correct``: at least one number compared, and every one within."""
    return bool(checks) and all(c.ok for c in checks)


# ---------------------------------------------------------------------------
# tracing of the measured window
# ---------------------------------------------------------------------------

class Tracer:
    """Starts and stops the profiler around part of the window. A no-op when
    the run is not traced; ``window_s`` is the host-clock length traced."""

    def __init__(self, enabled: bool, directory: str):
        self.enabled = enabled
        self.directory = directory
        self.window_s = 0.0
        self.active = False
        self.done = False
        self._t0 = 0.0

    def start(self) -> None:
        if not self.enabled or self.active or self.done:
            return
        import jax
        os.makedirs(self.directory, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0    # spans only, not every Python call
        jax.profiler.start_trace(self.directory, profiler_options=opts)
        self.active = True
        self._t0 = time.perf_counter()

    def stop(self) -> None:
        if not self.active:
            return
        import jax
        self.window_s = time.perf_counter() - self._t0
        jax.profiler.stop_trace()
        self.active = False
        self.done = True


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Run:
    """What a driver gets: the cell's files, read, and the run's arguments.
    ``test`` holds overrides for CPU rehearsals (reduced model, smaller
    traffic, planted faults); the benchmark itself never sets it."""
    root: str
    bench_dir: str
    bench: Dict[str, Any]
    workload: Dict[str, Any]
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    limits: Dict[str, float]
    seed: int
    seconds: float
    trace: bool
    t_start: float
    tracer: Tracer
    test: Dict[str, Any] = dataclasses.field(default_factory=dict)

    def module(self, kind: str, name: str) -> Any:
        return load_module(part(self.bench_dir, kind, name, ".py"))

    def reference(self) -> Any:
        return load_module(os.path.join(self.bench_dir, "configs",
                                        self.config["reference"]))

    def generator(self) -> Any:
        return self.module("generators", self.traffic["generator"])


@dataclasses.dataclass
class Outcome:
    """What a driver returns. ``facts`` carries whatever the per-layer
    readers need (counts, host timestamps, shapes)."""
    setup_s: float
    metrics: Dict[str, float]
    checks: List[Check]
    attempted: int
    failed: int
    memory_peak_bytes: Optional[int]
    facts: Dict[str, Any] = dataclasses.field(default_factory=dict)


def open_run(workload_name: str, seed: int, seconds: float, trace: bool, *,
             bench_dir: str = HERE, t_start: Optional[float] = None,
             test: Optional[Dict[str, Any]] = None,
             bench: Optional[Dict[str, Any]] = None) -> Run:
    """Read the cell's files. ``bench`` stands in for BENCHMARK.json (tests
    of cells not yet in it)."""
    root = root_of(bench_dir)
    if bench is None:
        bench = load_json(os.path.join(root, "BENCHMARK.json"))
    by_name = {w["name"]: w for w in bench["workloads"]}
    if workload_name not in by_name:
        raise KeyError(f"no workload {workload_name!r}; known: "
                       f"{sorted(by_name)}")
    workload = by_name[workload_name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[workload["config"]]
    config = load_json(os.path.join(root, cfg_entry["file"]))
    traffic = load_json(part(bench_dir, "traffic", workload["traffic"],
                             ".json"))
    limits = load_json(part(bench_dir, "limits", workload_name,
                            ".json"))["limits"]
    test = dict(test or {})
    traffic.update(test.get("traffic", {}))
    limits.update(test.get("limits", {}))
    trace_dir = os.path.join(root, ".chipbench_trace", workload_name)
    return Run(root=root, bench_dir=bench_dir, bench=bench, workload=workload, config=config,
               traffic=traffic, limits=limits, seed=int(seed),
               seconds=float(seconds), trace=bool(trace),
               t_start=time.perf_counter() if t_start is None else t_start,
               tracer=Tracer(bool(trace), trace_dir), test=test)


def metric_names(bench: Dict[str, Any], workload: str, kind: str) -> List[str]:
    """The cell's end-to-end (kind='end_to_end') or per-layer metrics."""
    out = []
    for m in bench[kind]:
        if "workloads" not in m or workload in m["workloads"]:
            out.append(m["name"])
    return out


def units(bench: Dict[str, Any]) -> Dict[str, str]:
    return {m["name"]: m["unit"]
            for kind in ("end_to_end", "per_layer") for m in bench[kind]}


def execute(run: Run) -> Dict[str, Any]:
    """Drive the cell and build the result line (a dict)."""
    import jax
    driver = run.module("drivers", run.config["driver"])
    outcome: Outcome = driver.run(run)
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": outcome.memory_peak_bytes}
    unit = units(run.bench)
    result: Dict[str, Any] = {"correct": verdict(outcome.checks),
                              "attempted": outcome.attempted,
                              "failed": outcome.failed}
    metrics: Dict[str, Any] = {}
    if not run.trace:
        for name in metric_names(run.bench, run.workload["name"],
                                 "end_to_end"):
            value = (outcome.setup_s if name == "setup_s"
                     else outcome.metrics.get(name))
            if value is not None:
                metrics[name] = {"value": value, "unit": unit[name]}
    else:
        from chipbench import trace as tracemod
        t0 = time.perf_counter()
        summary = tracemod.summarize(run.tracer.directory)
        log(f"trace read in {time.perf_counter() - t0:.1f}s")
        outcome.facts["trace"] = summary
        outcome.facts["traced_window_s"] = run.tracer.window_s
        device["busy_s"] = summary.busy_s if summary else None
        device["window_s"] = run.tracer.window_s
        for name in metric_names(run.bench, run.workload["name"],
                                 "per_layer"):
            reader = run.module("metrics", name)
            value = reader.read(run, outcome)
            if value is not None:
                metrics[name] = {"value": value, "unit": unit[name]}
        if summary is not None:
            result["breakdown"] = summary.breakdown()
        tracemod.remove(run.tracer.directory)
    result["metrics"] = metrics
    result["device"] = device
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in outcome.checks}
    return result
