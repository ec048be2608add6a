"""The benchmark harness: one cell, one seed, one measured window.

Everything that belongs to one cell is data, found by name:

  bench/workloads/<cell>.json    configuration, traffic, chips, driver, FT
                                 posture, the limits of its checks
  bench/configs/<config>.json    published sizes, source, reduced, assumed
  bench/traffic/<mix>.json       parameters of the traffic generator
  bench/drivers/<driver>.py      the entry that the window drives
  bench/metrics/<metric>.py      one reader per per-layer metric
  BENCHMARK.json                 which metrics each cell reports

A run: find the chips, set up (weights from the seed, every program the
window uses compiled or loaded from the cache), measure for ``--seconds``,
read device memory, free the program's state, compare what the window
produced with the plain reference, print one JSON line.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import sys
import time
from pathlib import Path
from typing import Callable, Dict, List, Optional

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Check:
    """One number compared with its limit: correct when value <= limit."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


@dataclasses.dataclass
class Cell:
    name: str
    workload: Dict
    config: Dict
    traffic: Dict
    end_to_end: List[Dict]
    per_layer: List[Dict]
    bench: Path

    @property
    def chips(self) -> int:
        return int(self.workload["chips"])

    @property
    def limits(self) -> Dict[str, float]:
        return self.workload["limits"]


def load_json(path: Path) -> Dict:
    with open(path) as f:
        return json.load(f)


def list_workloads(bench: Path = BENCH) -> List[str]:
    return sorted(p.name[:-len(".json")]
                  for p in (bench / "workloads").glob("*.json"))


def _applies(metric: Dict, cell: str, e2e_names) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves", metric["name"]) in e2e_names


def load_cell(name: str, bench: Path = BENCH) -> Cell:
    wpath = bench / "workloads" / f"{name}.json"
    if not wpath.is_file():
        raise SystemExit(f"bench: no workload {name!r} "
                         f"(known: {', '.join(list_workloads(bench))})")
    w = load_json(wpath)
    spec = load_json(bench.parent / "BENCHMARK.json")
    e2e = [m for m in spec["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"] if _applies(m, name, names)]
    return Cell(name=name, workload=w,
                config=load_json(bench / "configs" / f"{w['config']}.json"),
                traffic=load_json(bench / "traffic" / f"{w['traffic']}.json"),
                end_to_end=e2e, per_layer=per_layer, bench=bench)


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def driver_module(cell: Cell):
    d = cell.workload["driver"]
    return load_module(cell.bench / "drivers" / f"{d}.py", f"bench_driver_{d}")


def metric_reader(cell: Cell, metric: str) -> Callable:
    mod = load_module(cell.bench / "metrics" / f"{metric}.py",
                      "bench_metric_" + metric.replace(".", "_"))
    return mod.read


def find_devices(chips: int):
    """The first ``chips`` accelerator devices; never the CPU."""
    import jax
    devs = jax.devices()
    if devs[0].platform not in ("tpu", "gpu"):
        raise NoChip(f"JAX found no accelerator (platform "
                     f"{devs[0].platform!r}); the benchmark does not fall "
                     "back to the CPU")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return devs[:chips]


def enable_compile_cache() -> str:
    """The program's persistent compilation cache
    (``$JAX_COMPILATION_CACHE_DIR`` or ``<checkout>/.jax_cache``), keeping
    every program however fast it compiled, so that only a checkout's
    first run compiles."""
    import jax
    from repro.launch import compile_cache
    path = compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class CompileCounter:
    """Programs that JAX had to produce while armed: each is a compile or
    a load from the persistent cache (``cache_hits`` of them), and each
    costs the window time that no user asked for."""

    def __init__(self):
        import jax
        self.programs = 0
        self.seconds = 0.0
        self.cache_hits = 0
        self.armed = False

        def on_duration(event, duration, **_):
            if self.armed and event == BACKEND_COMPILE:
                self.programs += 1
                self.seconds += duration

        def on_event(event, **_):
            if self.armed and event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1
        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)


def span(name: str):
    """A host span in the profiler's trace (cheap when no trace runs)."""
    import jax
    return jax.profiler.TraceAnnotation(name)


def memory_peak(devices) -> Optional[int]:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def _fmt_checks(checks: List[Check]) -> Dict:
    """Each number beside its limit; a number that could not be read (no
    finished request, a NaN) is null, and fails."""
    return {c.name: {"value": c.value if math.isfinite(c.value) else None,
                     "limit": c.limit} for c in checks}


def run(workload: str, seed: int, seconds: float, trace: bool,
        bench: Path = BENCH, find=find_devices, out=sys.stdout) -> int:
    cell = load_cell(workload, bench)
    from bench.program import import_program
    import_program()
    try:
        devices = find(cell.chips)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    peaks = load_peaks(devices[0].device_kind, bench)
    cache = enable_compile_cache()
    print(f"bench: cell {cell.name} seed {seed} seconds {seconds} trace "
          f"{int(trace)} devices {len(devices)} x {devices[0].device_kind} "
          f"compile cache {cache}", file=sys.stderr, flush=True)
    compiles = CompileCounter()
    drv = driver_module(cell).Driver(cell, seed, devices)

    t0 = time.perf_counter()
    with span("bench/setup"):
        drv.setup()
    setup_s = time.perf_counter() - t0

    from bench import trace as btrace
    tdir = None
    if trace:
        tdir = btrace.start()
    compiles.armed = True
    tw0 = time.perf_counter()
    with span("bench/window"):
        stats = drv.window(seconds)
    window_s = time.perf_counter() - tw0
    compiles.armed = False
    summary = None
    if trace:
        btrace.stop()
        summary = btrace.reduce_dir(tdir, window_name="bench/window",
                                    devices=len(devices))
        btrace.cleanup(tdir)
    mem = memory_peak(devices)
    print(f"bench: window {window_s:.3f} s; programs built in the window "
          f"{compiles.programs} ({compiles.cache_hits} from the cache, "
          f"{compiles.seconds:.3f} s); memory peak {mem}", file=sys.stderr,
          flush=True)
    for line in drv.report_lines(stats):
        print(line, flush=True)

    if trace:
        from bench.metrics_context import Context
        ctx = Context(cell=cell, stats=stats, trace=summary, peaks=peaks)
        metrics = {}
        for m in cell.per_layer:
            ctx.metric = m["name"]
            v = metric_reader(cell, m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        for note in ctx.notes:
            print(f"bench: {note}", file=sys.stderr)
    else:
        values = dict(drv.end_to_end(stats), setup_s=setup_s)
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}

    drv.release()
    checks = drv.check(stats)
    correct = bool(checks) and all(c.ok for c in checks)
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": mem}
    result = {"correct": correct, "attempted": stats["attempted"],
              "failed": stats["failed"], "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        result["breakdown"] = summary.breakdown()
    result["checks"] = _fmt_checks(checks)
    for c in checks:
        print(f"check {c.name} {c.value!r} limit {c.limit!r} "
              f"{'ok' if c.ok else 'FAILED'}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), file=out, flush=True)
    return 0


def load_peaks(kind: str, bench: Path = BENCH) -> Dict:
    table = load_json(bench / "peaks.json")
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json "
                       f"(known: {sorted(table)}); add its published peaks")
    return table[kind]
