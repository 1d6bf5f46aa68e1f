"""What every cell shares: the spec files, the chip check, the compile
cache, compile counting, the traced window and the result line.

A cell is found by name: ``BENCHMARK.json`` names its configuration
and traffic mix; ``cells/configs/<config>.json`` holds the deployment,
``cells/traffic/<mix>.json`` the mix, whose ``driver`` names the module
under ``cells/drivers/`` that drives the window, and each per-layer
metric is read by ``cells/metrics/<metric>.py``. Nothing here knows a
cell, a mix or a metric by name.
"""

from __future__ import annotations

import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
WINDOW_SPAN = "cells.window"
#: With ``--trace 1`` the profiler records the first this many seconds
#: of the window (the whole window if it is shorter): enough events to
#: read, and a trace small enough to reduce well inside a run's time.
TRACE_SECONDS = 5.0


class NoChip(RuntimeError):
    """The run found no accelerator, or fewer chips than the cell needs."""


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    bench: dict
    rehearse: bool = False

    def sizes(self, part: str) -> dict:
        """The config or traffic dict, with its ``rehearse`` sizes laid
        over it in a rehearsal."""
        d = dict(getattr(self, part))
        if self.rehearse:
            d.update(d.get("rehearse", {}))
        return d


def _read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(workload: str, rehearse: bool = False) -> Cell:
    bench = _read_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; "
                         f"BENCHMARK.json has {sorted(cells)}")
    w = cells[workload]
    cfg = next(c for c in bench["configs"] if c["name"] == w["config"])
    return Cell(name=workload, chips=int(w["chips"]),
                config=_read_json(os.path.join(ROOT, cfg["file"])),
                traffic=_read_json(os.path.join(HERE, "traffic",
                                                w["traffic"] + ".json")),
                bench=bench, rehearse=rehearse)


def driver_of(cell: Cell):
    return importlib.import_module(f"cells.drivers.{cell.traffic['driver']}")


def check_devices(chips: int, rehearse: bool):
    """The devices to run on; raises `NoChip` where JAX finds no TPU
    (outside a rehearsal) or fewer devices than the cell asks for."""
    import jax
    devices = jax.devices()
    if not rehearse and devices[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devices[0].platform}")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips; JAX found "
                     f"{len(devices)}")
    return devices[:chips]


def enable_compile_cache() -> None:
    """JAX's persistent compilation cache at the checkout's fixed
    ``.jax_cache``, whatever the environment names, so that the two
    sides of a comparison share nothing. Every compile is cached."""
    import jax
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileMeter:
    """Backend compiles (count and seconds, by function) and persistent
    cache hits and misses, from JAX's own monitoring events."""

    def __init__(self):
        import jax.monitoring
        self.by_fun: Dict[str, List[float]] = {}
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, fun_name="?", **_):
        if event == "/jax/core/compile/backend_compile_duration":
            acc = self.by_fun.setdefault(fun_name, [0, 0.0])
            acc[0] += 1
            acc[1] += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    @property
    def compiles(self) -> int:
        return sum(int(n) for n, _ in self.by_fun.values())

    def snapshot(self) -> dict:
        top = sorted(self.by_fun.items(), key=lambda kv: -kv[1][1])[:3]
        return {"compile_s": sum(t for _, t in self.by_fun.values()),
                "compiles": self.compiles, "cache_hits": self.hits,
                "cache_misses": self.misses,
                "slowest_compiles": [[f, n, t] for f, (n, t) in top]}


class Tracer:
    """The profiler around the measured window when ``--trace 1``; a
    no-op otherwise. The harness's own host spans (`span`) land in the
    same trace as the device's operations."""

    def __init__(self, on: bool):
        self.on = on
        self.dir: Optional[str] = None
        self.path: Optional[str] = None
        self.reduced: Optional[dict] = None

    def part(self, seconds: float) -> float:
        """How much of a ``seconds`` window the trace covers."""
        return min(seconds, TRACE_SECONDS) if self.on else seconds

    def start(self) -> None:
        if not self.on:
            return
        import jax
        self.dir = tempfile.mkdtemp(prefix="cells-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=opts)

    def stop(self) -> None:
        """Stop recording and write the trace out."""
        if not self.on or self.dir is None:
            return
        import glob
        import jax
        jax.profiler.stop_trace()
        paths = glob.glob(os.path.join(self.dir, "plugins", "profile",
                                       "*", "*.xplane.pb"))
        self.path = paths[0] if paths else None

    def reduce(self, span_names=()) -> None:
        """Reduce the written trace (after the window: it is slow) and
        delete it."""
        if self.dir is None:
            return
        from cells import trace_reduce
        try:
            if self.path:
                self.reduced = trace_reduce.reduce_profile(
                    trace_reduce.load(self.path), WINDOW_SPAN, span_names)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
            self.dir = None


@contextmanager
def span(name: str):
    """A host span in the profiler's trace (free when not tracing)."""
    import jax
    with jax.profiler.TraceAnnotation(name):
        yield


def splitmix64(x):
    """SplitMix64's output function over uint64 device lanes: the
    cells make their data on the device from the seed with it."""
    import jax.numpy as jnp
    z = x + jnp.uint64(0x9E3779B97F4A7C15)
    z = (z ^ (z >> jnp.uint64(30))) * jnp.uint64(0xBF58476D1CE4E5B9)
    z = (z ^ (z >> jnp.uint64(27))) * jnp.uint64(0x94D049BB133111EB)
    return z ^ (z >> jnp.uint64(31))


def sleep_until(t: float) -> None:
    while True:
        left = t - time.monotonic()
        if left <= 0:
            return
        time.sleep(min(left, 0.05))


@dataclass
class Check:
    """One number compared with its limit: the run is correct only if
    every check holds (``value <= limit``)."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


@dataclass
class Outcome:
    """What a driver hands back for the result line."""
    metrics: Dict[str, float]
    attempted: int
    failed: int
    checks: List[Check]
    memory_peak_bytes: int
    record: Dict[str, Any] = field(default_factory=dict)


def per_layer_metrics(cell: Cell, run: dict) -> Dict[str, tuple]:
    """Every per-layer metric this cell reports, read by its own reader
    in ``cells/metrics/<name>.py``. A reader that finds nothing to read
    returns None and the metric is left out."""
    out = {}
    for m in cell.bench["per_layer"]:
        if cell.name not in m.get("workloads", [cell.name]):
            continue
        path = os.path.join(HERE, "metrics", m["name"] + ".py")
        spec = importlib.util.spec_from_file_location(
            "cells_metric_" + m["name"].replace(".", "_"), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        value = mod.read(run)
        if value is not None:
            out[m["name"]] = (float(value), m["unit"])
    return out


def end_to_end_units(cell: Cell) -> Dict[str, str]:
    return {m["name"]: m["unit"] for m in cell.bench["end_to_end"]
            if cell.name in m.get("workloads", [cell.name])}


def result_line(cell: Cell, devices, outcome: Outcome, tracer: Tracer,
                trace: bool) -> dict:
    """The last line of standard output. ``checks`` comes last."""
    from cells import trace_reduce
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": int(outcome.memory_peak_bytes)}
    line: Dict[str, Any] = {"correct": all(c.ok for c in outcome.checks),
                            "attempted": int(outcome.attempted),
                            "failed": int(outcome.failed)}
    if trace:
        run = dict(outcome.record, trace=tracer.reduced,
                   device_kind=dev.device_kind)
        metrics = per_layer_metrics(cell, run)
        red = tracer.reduced
        if red is not None:
            devs = red["devices"]
            device["busy_s"] = sum(d["busy_ns"] for d in devs) \
                / len(devs) * 1e-9
            device["window_s"] = red["window_ns"] * 1e-9
    else:
        units = end_to_end_units(cell)
        metrics = {k: (float(v), units[k])
                   for k, v in outcome.metrics.items() if k in units}
    line["metrics"] = {k: {"value": v, "unit": u}
                       for k, (v, u) in metrics.items()}
    line["device"] = device
    if trace and tracer.reduced is not None:
        line["breakdown"] = trace_reduce.breakdown(tracer.reduced)
    line["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                      for c in outcome.checks}
    return line


def print_checks(checks: List[Check]) -> None:
    """The numbers compared, beside their limits, as the last lines of
    standard error."""
    for c in checks:
        print(f"check {c.name} = {c.value} (limit {c.limit}) "
              f"{'ok' if c.ok else 'FAILED'}", file=sys.stderr, flush=True)


def log(**fields) -> None:
    print(json.dumps(fields), file=sys.stderr, flush=True)


def memory_peak(devices) -> int:
    """The peak device memory of the fullest chip. The TPU runtime
    counts arrays in ``peak_bytes_in_use`` and the scratch it reserves
    for programs' temporaries apart, in ``peak_bytes_reserved``, and
    keeps that reservation once made: the peak held is their sum."""
    def peak(d):
        s = d.memory_stats() or {}
        return (int(s.get("peak_bytes_in_use", 0))
                + int(s.get("peak_bytes_reserved", 0)))
    return max(peak(d) for d in devices)


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float, hooks: Optional[Dict[str, Callable]] = None
             ) -> tuple:
    """Run one cell once; returns its result line and the driver's
    `Outcome`. ``hooks`` plant faults in the program (``plant``) or
    transform what the window produced before the check (``observe``):
    the controls and the tests under ``cells/tests``."""
    driver = driver_of(cell)
    pre = driver.before_jax(cell, seed, seconds)
    try:
        import jax
        devices = check_devices(cell.chips, cell.rehearse)
        jax.config.update("jax_enable_x64", True)
        if not cell.rehearse:
            enable_compile_cache()
        meter = CompileMeter()
        tracer = Tracer(trace)
        outcome = driver.run(cell, seed, seconds, pre, meter, tracer,
                             t_start, devices, hooks or {})
    finally:
        driver.after(pre)
    return result_line(cell, devices, outcome, tracer, trace), outcome
