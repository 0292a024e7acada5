"""The benchmark harness: finds a cell's files by name, times set-up and
the window, takes the trace, reads the per-layer metrics and decides
``correct``.

Files, each found by the name ``BENCHMARK.json`` gives:

- ``chipbench/workloads/<cell>.json``: the configuration name, the driver
  name and the traffic parameters;
- ``chipbench/configs/<config>.json``: the configuration as it is run;
- ``chipbench/drivers/<driver>.py``: a ``Job`` class with ``setup()``,
  ``window(seconds)``, ``check()`` and ``close()``;
- ``chipbench/metrics/<metric>.py``: ``read(rec)`` returns the metric, or
  None where the run has nothing for it to read.

A driver marks each call into a layer with ``run.span(name)``. Spans are
kept in memory; in a traced run each is also a
``jax.profiler.TraceAnnotation``, so that idle gaps on the device are
named by what the host was doing.
"""

from __future__ import annotations

import importlib.util
import json
import os
import shutil
import sys
import tempfile
import threading
import time
from collections import defaultdict
from contextlib import contextmanager
from dataclasses import dataclass, field

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


# ------------------------------------------------------------------ files
def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    """Import ``path`` as module ``name``, once per process."""
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise ImportError(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    """One entry of ``workloads`` with everything found by its names."""

    name: str
    chips: int
    workload: dict
    config: dict
    end_to_end: list[dict]
    per_layer: list[dict]

    @classmethod
    def load(cls, bench: dict, name: str) -> "Cell":
        entry = next((w for w in bench["workloads"] if w["name"] == name), None)
        if entry is None:
            raise KeyError(f"no cell {name!r} in BENCHMARK.json")
        workload = load_json(os.path.join(HERE, "workloads", f"{name}.json"))
        if workload["config"] != entry["config"]:
            raise ValueError(f"{name}: workload file names config {workload['config']!r}, "
                             f"BENCHMARK.json {entry['config']!r}")
        cfg_entry = next(c for c in bench["configs"] if c["name"] == entry["config"])
        config = load_json(os.path.join(ROOT, cfg_entry["file"]))
        e2e = [m for m in bench["end_to_end"] if name in m.get("workloads", [name])]
        reported = {m["name"] for m in e2e}
        per_layer = [
            m for m in bench["per_layer"]
            if (name in m["workloads"] if "workloads" in m else m["moves"] in reported)
        ]
        return cls(name, entry["chips"], workload, config, e2e, per_layer)


# ------------------------------------------------------------------ timing
class CompileClock:
    """Sums JAX's own compile-duration events (trace, lowering, backend
    compile) and counts persistent-cache hits and misses."""

    def __init__(self):
        from jax import monitoring

        self.seconds = 0.0
        self.events = 0
        self.cache_hits = 0
        self.cache_misses = 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, name, secs, **_):
        if name.startswith("/jax/core/compile/"):
            self.seconds += secs
            self.events += 1

    def _event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def mark(self) -> tuple[float, int, int, int]:
        return self.seconds, self.events, self.cache_hits, self.cache_misses


class Run:
    """What one run records: spans (seconds, window only) and where it
    keeps its files. ``workdir`` is under the system temp directory; Sea's
    tier roots outside it (the tmpfs tier under /dev/shm) are registered
    with ``own_tiers``; ``close`` removes them all."""

    def __init__(self, *, trace: bool, seed: int):
        self.trace = trace
        self.seed = seed
        self.spans: dict[str, list[float]] = defaultdict(list)
        self.in_window = False
        self._lock = threading.Lock()
        self.workdir = tempfile.mkdtemp(prefix="chipbench_")
        self.outside: list[str] = []

    def own_tiers(self, scfg):
        """Remove every tier root of Sea config ``scfg`` that lies outside
        the work directory when the run closes; returns ``scfg``."""
        for t in scfg.tiers:
            self.outside.extend(r for r in t.roots
                                if os.path.commonpath([r, self.workdir]) != self.workdir)
        return scfg

    @contextmanager
    def span(self, name: str):
        ann = None
        if self.trace:
            import jax

            ann = jax.profiler.TraceAnnotation(name)
            ann.__enter__()
        t0 = time.perf_counter()
        try:
            yield
        finally:
            dt = time.perf_counter() - t0
            if ann is not None:
                ann.__exit__(None, None, None)
            if self.in_window:
                with self._lock:
                    self.spans[name].append(dt)

    def close(self) -> None:
        for d in [self.workdir, *self.outside]:
            shutil.rmtree(d, ignore_errors=True)


@dataclass
class WindowResult:
    """What a driver's window returns."""

    seconds: float                      # host-clock length of the window
    attempted: int
    failed: int
    metrics: dict[str, float]           # end-to-end metrics it measured
    counters: dict[str, float] = field(default_factory=dict)  # window deltas
    info: dict = field(default_factory=dict)


@dataclass
class Check:
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


@dataclass
class Record:
    """What a per-layer metric reader reads."""

    cell: Cell
    window: WindowResult
    spans: dict[str, list[float]]
    trace: dict | None
    peaks: dict

    def mean_ms(self, span: str) -> float | None:
        """Mean milliseconds of the window's ``span`` spans, or None."""
        s = self.spans.get(span)
        return 1000.0 * sum(s) / len(s) if s else None


# ------------------------------------------------------------------ one run
def execute(cell: Cell, *, seed: int, seconds: float, trace: bool, devices,
            t_start: float, log=print) -> dict:
    """Set up, measure, check. Returns the result object."""
    import jax

    from chipbench import flops

    device = devices[0]
    clock = CompileClock()
    run = Run(trace=trace, seed=seed)
    driver = load_module(os.path.join(HERE, "drivers", f"{cell.workload['driver']}.py"),
                         f"chipbench_driver_{cell.workload['driver']}")
    job = driver.Job(cell, run, devices[: cell.chips])
    try:
        job.setup()
        setup_s = time.monotonic() - t_start
        c0 = clock.mark()
        log(f"[bench] set-up {setup_s:.3f} s; compile {c0[0]:.3f} s in {c0[1]} events, "
            f"persistent cache hits {c0[2]}, misses {c0[3]}")
        trace_dir = os.path.join(run.workdir, "trace") if trace else None
        if trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        run.in_window = True
        try:
            with run.span("window"):
                win = job.window(seconds)
        finally:
            run.in_window = False
            if trace:
                jax.profiler.stop_trace()
        c1 = clock.mark()
        log(f"[bench] window {win.seconds:.3f} s; compile events in it {c1[1] - c0[1]} "
            f"({c1[0] - c0[0]:.3f} s), cache misses {c1[3] - c0[3]}")
        reduced = None
        if trace:
            from chipbench import trace as trace_mod

            reduced = trace_mod.reduce_dir(trace_dir, n_devices=cell.chips, names=set(run.spans))
            shutil.rmtree(trace_dir, ignore_errors=True)
        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devices[: cell.chips])
        t_check = time.monotonic()
        checks = job.check()
        log(f"[bench] check {time.monotonic() - t_check:.3f} s; compile events in it "
            f"{clock.mark()[1] - c1[1]}, cache misses {clock.mark()[3] - c1[3]}")
    finally:
        job.close()
        run.close()

    metrics: dict[str, dict] = {}
    if trace:
        rec = Record(cell, win, dict(run.spans), reduced, flops.peaks(device.device_kind))
        for m in cell.per_layer:
            reader = load_module(os.path.join(HERE, "metrics", f"{m['name']}.py"),
                                 f"chipbench_metric_{m['name'].replace('.', '_')}")
            value = reader.read(rec)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        measured = dict(win.metrics, setup_s=setup_s)
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": measured[m["name"]], "unit": m["unit"]}

    dev = {"platform": device.platform, "kind": device.device_kind,
           "count": cell.chips, "memory_peak_bytes": int(peak)}
    if trace:
        dev["busy_s"] = reduced["busy_s"]
        dev["window_s"] = reduced["window_s"]
    result = {
        "correct": all(c.ok for c in checks) and bool(checks),
        "attempted": win.attempted,
        "failed": win.failed,
        "metrics": metrics,
        "device": dev,
    }
    if trace:
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit} for c in checks}
    return result


def main(args, t_start: float) -> int:
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chipbench: needs a TPU; JAX found {devices[0].platform!r} devices only",
              file=sys.stderr)
        return 3
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = Cell.load(bench, args.workload)
    if len(devices) < cell.chips:
        print(f"chipbench: {cell.name} needs {cell.chips} chips; JAX found {len(devices)}",
              file=sys.stderr)
        return 3
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from repro.launch.compile_cache import setup_compile_cache

    setup_compile_cache()

    def log(msg):
        print(msg, file=sys.stderr, flush=True)

    result = execute(cell, seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
                     devices=devices, t_start=t_start, log=log)
    for name, c in result["checks"].items():
        log(f"check {name}: {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(result), flush=True)
    return 0
