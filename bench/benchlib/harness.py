"""One run of one cell: set up, measure a window, check, print one line.

Everything a cell is made of is found by name from `BENCHMARK.json`:

  bench/workloads/<cell>.json    engine settings, check limits, trace slice
  bench/traffic/<traffic>.json   the traffic mix (benchlib/traffic.py)
  bench/configs/<config>.json    the configuration as it is run
  bench/configs/<config>.py      builds the served system from it, and holds
                                 the plain reference and the counts of
                                 operations and bytes
  bench/metrics/<family>.py      one reader per metric family: the part of
                                 a metric's name before the first dot

A new cell, configuration or metric is new files and new entries in
`BENCHMARK.json`; nothing here changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import pathlib
import shutil
import sys
import tempfile
import time
import types

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


class NoChip(RuntimeError):
    """JAX finds no accelerator, or fewer chips than the cell asks for."""


def load_json(path) -> dict:
    return json.loads(pathlib.Path(path).read_text())


def load_module(path: pathlib.Path, prefix: str) -> types.ModuleType:
    """Import a file of the benchmark by its path (names may hold '-' and
    '.')."""
    name = prefix + "".join(c if c.isalnum() else "_" for c in path.stem)
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict          # the configuration file's contents
    workload: dict        # bench/workloads/<cell>.json
    traffic: dict         # bench/traffic/<traffic>.json
    module: types.ModuleType
    metrics: list         # this run's metric entries of BENCHMARK.json


def resolve(name: str, trace: bool, overrides: dict) -> Cell:
    """The cell's files, with `overrides` ({"config" | "workload" |
    "traffic": {key: value}}) replacing keys of them."""
    bench = load_json(ROOT / "BENCHMARK.json")
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    conf = next(c for c in bench["configs"] if c["name"] == entry["config"])
    conf_file = ROOT / conf["file"]
    kind = "per_layer" if trace else "end_to_end"
    from benchlib import traffic
    files = {"config": load_json(conf_file),
             "workload": load_json(BENCH / "workloads" / f"{name}.json"),
             "traffic": traffic.load(BENCH / "traffic"
                                     / f"{entry['traffic']}.json")}
    for key, extra in overrides.items():
        files[key] = dict(files[key], **extra)
    return Cell(name=name, chips=int(entry["chips"]),
                module=load_module(conf_file.with_suffix(".py"), "bench_cfg_"),
                metrics=[m for m in bench[kind]
                         if name in m.get("workloads", [name])], **files)


def reader(family: str):
    return load_module(BENCH / "metrics" / f"{family}.py", "bench_metric_")


def devices_for(chips: int, require_chip: bool) -> list:
    import jax
    devs = jax.devices()
    if require_chip and devs[0].platform == "cpu":
        raise NoChip(f"JAX found no accelerator (platform "
                     f"{devs[0].platform!r})")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX sees "
                     f"{len(devs)}")
    return devs[:chips]


def peaks_for(device_kind: str) -> dict:
    table = load_json(BENCH / "peaks.json")["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json")
    return table[device_kind]


def memory_peak(devs) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devs]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def set_up_jax() -> str:
    """Compile cache in the checkout (or where JAX_COMPILATION_CACHE_DIR
    says), every program cached however fast it compiled, and the
    autotuner's default policy, which times nothing."""
    import jax
    from repro.core import backends, enable_persistent_cache
    path = enable_persistent_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    backends.set_autotune_policy("heuristic")
    return path


class Tracer:
    """Traces two slices of the window, each from the first step at or
    after its start: the device slice from `start_s` into the window for
    `seconds`, with the host tracer off, so the host path runs at its own
    speed (device busy time and the slice's length by the host clock); then
    the host slice for `host_seconds`, with the host spans of level 1 (the
    benchmark's own and JAX's dispatch), which only files idle gaps under
    host spans.  Neither runs the Python tracer."""

    def __init__(self, window_start_hint: float, start_s: float,
                 seconds: float, host_seconds: float, log_dir: str):
        self.log_dir = log_dir
        self.plan = [("device", start_s, seconds, 0),
                     ("host", 0.0, host_seconds, 1)]
        self.slices = {}        # name -> [t_start, t_stop, t_stopped]
        self._origin = window_start_hint
        self._i = 0
        self._on = None

    def warm(self) -> None:
        """Start and stop the profiler once before the window: the first
        trace of a process is the slow one to stop."""
        import jax
        jax.profiler.start_trace(self.dir("warm"))
        jax.profiler.stop_trace()

    def spans(self) -> list:
        """(start, end) of each slice by the host clock, stopping included."""
        return [(a, c) for a, _, c in self.slices.values() if c is not None]

    def dir(self, name: str) -> str:
        return f"{self.log_dir}/{name}"

    def before_step(self, now: float) -> None:
        import jax
        if self._on is not None:
            name, _, seconds, _ = self.plan[self._i]
            t = self.slices[name]
            if now - t[0] < seconds and math.isfinite(now):
                return
            t[1] = time.perf_counter()
            jax.profiler.stop_trace()
            t[2] = time.perf_counter()
            self._on, self._i, self._origin = None, self._i + 1, t[2]
        if self._i < len(self.plan) and math.isfinite(now):
            name, start_s, _, level = self.plan[self._i]
            if now - self._origin >= start_s:
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                opts.host_tracer_level = level
                jax.profiler.start_trace(self.dir(name),
                                         profiler_options=opts)
                self.slices[name] = [time.perf_counter(), None, None]
                self._on = name


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             t_process: float | None = None, require_chip: bool = True,
             overrides: dict | None = None, control: bool = False,
             log=print) -> dict:
    """Run cell `name` once and return the result line's object.

    `overrides` go to `resolve` (the tests run tiny sizes on the CPU with
    them); `control` adds the control's readings under "control"."""
    t_process = time.perf_counter() if t_process is None else t_process
    cell = resolve(name, trace, overrides or {})
    devs = devices_for(cell.chips, require_chip)
    import jax
    from benchlib import drive, measure, traffic
    from benchlib import trace as trace_mod

    dev = devs[0]
    if dev.platform != "cpu":
        log(f"compile cache: {set_up_jax()}")
    log(f"device: {dev.platform} {dev.device_kind} x{len(devs)}, "
        f"jax {jax.__version__}, cell {name}, seed {seed}, "
        f"seconds {seconds}, trace {int(trace)}")
    system = cell.module.build(cell.config, cell.workload, cell.traffic, seed)
    items = traffic.schedule(cell.traffic, seed)
    system.warmup(items)
    setup_s = time.perf_counter() - t_process
    log(f"setup_s={setup_s!r}")

    tracer = None
    if trace:
        sl = cell.workload["trace"]
        tracer = Tracer(time.perf_counter(), float(sl["start_s"]),
                        float(sl["seconds"]), float(sl["host_seconds"]),
                        tempfile.mkdtemp(prefix="bench_trace_"))
        tracer.warm()
    counter = drive.CompileCounter()
    try:
        window = drive.run_window(system, cell.traffic, items, seconds,
                                  compiles=counter, tracer=tracer)
    finally:
        counter.close()
    log(f"compiles inside the window: {window.compiles}")
    peak = memory_peak(devs)
    reduction = None
    if trace:
        dev_slice = tracer.slices.get("device", [None, None])
        if dev_slice[1] is None:
            raise RuntimeError("the device slice never started: the window "
                               "is shorter than its start_s")
        try:
            host = (trace_mod.load_dir(tracer.dir("host"))
                    if tracer.slices.get("host", [None])[0] else None)
            reduction = trace_mod.reduce(
                trace_mod.load_dir(tracer.dir("device")),
                dev_slice[1] - dev_slice[0], host)
        finally:
            shutil.rmtree(tracer.log_dir, ignore_errors=True)
        log(measure.idle_check(window, reduction, tracer))
    ctx = types.SimpleNamespace(
        cell=cell, system=system, window=window, seconds=seconds,
        setup_s=setup_s, trace=reduction, tracer=tracer,
        peaks=peaks_for(dev.device_kind) if dev.platform != "cpu" else None)
    metrics = {}
    for m in cell.metrics:
        value = reader(m["name"].split(".")[0]).read(m["name"], ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    for line in measure.describe(window):
        log(line)

    system.release()
    checks = system.check(window, seed)
    result = {"correct": all(c["value"] <= c["limit"] for c in checks),
              "attempted": len(window.records) + window.failed,
              "failed": window.failed + window.unanswered,
              "metrics": metrics,
              "device": {"platform": dev.platform, "kind": dev.device_kind,
                         "count": len(devs), "memory_peak_bytes": peak}}
    if trace:
        result["device"]["busy_s"] = reduction.busy_s
        result["device"]["window_s"] = reduction.window_s
        result["breakdown"] = reduction.breakdown()
    if control:
        result["control"] = system.control(window, seed)
    result["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                        for c in checks}
    return result
