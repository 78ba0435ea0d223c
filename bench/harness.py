"""One run of one cell: set-up, the timed part, the check, the result.

Everything that belongs to one configuration, traffic mix or metric is
found by name: ``BENCHMARK.json`` names the cell's configuration file
and traffic mix; the configuration's ``engine`` names a module of
``bench/drivers``; the mix's ``generator`` names a module of
``bench/traffic``; every metric ``<name>`` is read by
``bench/metrics/<name>.py``; the cell's limits are in
``bench/limits/<cell>.json``.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib
import json
import resource
import sys
import tempfile
import time
from pathlib import Path
from typing import List, Optional

import numpy as np

from . import correctness, instrument, trace_reduce, traffic
from .data import synthetic
from .reference import Reference

ROOT = Path(__file__).resolve().parents[1]
SPANS = {"window", "view", "init_pass", "read_values", "wait_arrival"}


def is_span(name: str) -> bool:
    return name in SPANS or name.startswith("ops.")


@dataclasses.dataclass
class Run:
    """What the timed part of a run produced, for the metric readers."""
    workload: str
    seconds: float
    views: list
    results: list
    done: List[float]
    setup_s: float = 0.0
    window_start: float = 0.0
    first_result: object = None
    first_answer_s: Optional[float] = None
    init_s: Optional[float] = None
    lateness: List[float] = dataclasses.field(default_factory=list)
    rows_read: int = 0
    compiles: int = 0
    calls: list = dataclasses.field(default_factory=list)
    trace: Optional[dict] = None
    device_kind: str = ""

    def latencies_s(self) -> np.ndarray:
        """Due-to-answer time of every view of the window."""
        due = np.array([self.window_start + v.due_s for v in self.views])
        return np.asarray(self.done) - due

    def answered_in_window(self) -> int:
        end = self.window_start + self.seconds
        return sum(1 for d, r in zip(self.done, self.results)
                   if r is not None and d <= end)


def benchmark() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def cell(bench: dict, workload: str):
    w = next(w for w in bench["workloads"] if w["name"] == workload)
    c = next(c for c in bench["configs"] if c["name"] == w["config"])
    cfg = json.loads((ROOT / c["file"]).read_text())
    return w, cfg


def metric(name: str):
    return importlib.import_module(f"bench.metrics.{name}")


def _log(**kw):
    print(json.dumps(kw), file=sys.stderr, flush=True)


class _Window:
    """Opens the timed window: counters zeroed, ops recorded, the trace
    started when asked; closes it after the driver's last answer."""

    def __init__(self, ds, tap, counter, trace_dir):
        self.ds, self.tap, self.counter = ds, tap, counter
        self.trace_dir = trace_dir

    @contextlib.contextmanager
    def __call__(self, run: Run):
        import jax

        if self.trace_dir is not None:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        rows0 = self.ds.stats.rows_read
        req0, _ = self.counter.snapshot()
        self.tap.recording = True
        try:
            with instrument.span("window"):
                run.window_start = time.perf_counter()
                yield run.window_start
        finally:
            self.tap.recording = False
            run.compiles = self.counter.snapshot()[0] - req0
            run.rows_read = self.ds.stats.rows_read - rows0
            run.calls = list(self.tap.calls)
            if self.trace_dir is not None:
                jax.profiler.stop_trace()


def _read_trace(trace_dir: str) -> Optional[dict]:
    found = sorted(Path(trace_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not found:
        return None
    return trace_reduce.reduce(trace_reduce.extract(found[-1], is_span))


def measure(driver, ds, cfg, sch, workload, seconds, tap, counter,
            trace_dir, devices, t_start):
    """Set-up (the driver's, with the warm-up), then the timed part of
    ``sch``. Returns the run, the devices' peak memory, the set-up's
    seconds in the driver and the compile counts of set-up."""
    n = len(sch.views)
    run = Run(workload=workload, seconds=float(seconds), views=sch.views,
              results=[None] * n, done=[np.inf] * n,
              device_kind=devices[0].device_kind)
    t = time.perf_counter()
    state = driver.setup(ds, cfg, sch, run)
    t_warm = time.perf_counter() - t
    gc.collect()
    run.setup_s = time.perf_counter() - t_start
    compiles_setup = counter.snapshot()
    window = _Window(ds, tap, counter, trace_dir)
    driver.measure(ds, cfg, sch, run, window, state)
    del state
    peak = peak_bytes(devices)
    if trace_dir is not None:
        run.trace = _read_trace(trace_dir)
    return run, peak, t_warm, compiles_setup


def peak_bytes(devices) -> int:
    """The peak of device memory in use on the fullest chip so far."""
    mem = [d.memory_stats() or {} for d in devices]
    return max((m.get("peak_bytes_in_use", 0) for m in mem), default=0)


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, require_chip: bool = True,
             overrides: Optional[dict] = None) -> Optional[dict]:
    """The result object of one run, or None when the chips it needs are
    missing. ``overrides`` replaces configuration keys (tests run a
    cell's path at a small size)."""
    import jax

    bench = benchmark()
    w, cfg = cell(bench, workload)
    cfg = {**cfg, **(overrides or {})}
    devices = jax.devices()
    if require_chip and (devices[0].platform != "tpu"
                         or len(devices) < w["chips"]):
        print(f"bench: {workload} needs {w['chips']} TPU chip(s); JAX "
              f"found {len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return None
    from repro.kernels import ops

    counter = instrument.CompileCounter()
    tap = instrument.OpTap(ops)
    driver = importlib.import_module(f"bench.drivers.{cfg['engine']}")
    with tap.installed(), tempfile.TemporaryDirectory() as tmp:
        t = time.perf_counter()
        x, y, cols = synthetic.generate(cfg, seed)
        ds = instrument.traced_dataset(x, y, cols, cfg["storage"])
        t_data = time.perf_counter() - t
        gen_peak = peak_bytes(devices[:w["chips"]])
        sch = traffic.schedule(w["traffic"], ds, seconds)
        run, peak, t_warm, compiles_setup = measure(
            driver, ds, cfg, sch, workload, seconds, tap, counter,
            tmp if trace else None, devices[:w["chips"]], t_start)
        n = len(sch.views)
    # a set-up that compiled (a checkout's first run) is marked apart
    _log(phase="setup", data_s=t_data, driver_s=t_warm, setup_s=run.setup_s,
         init_pass_s=run.init_s, compile_requests=compiles_setup[0],
         cache_hits=compiles_setup[1],
         compiled=compiles_setup[0] > compiles_setup[1],
         offered=sch.params, views=n, generator_peak_bytes=gen_peak)
    lat = run.latencies_s()
    _log(phase="window", compiles_in_window=run.compiles,
         rows_read=run.rows_read,
         answered_in_window=run.answered_in_window(),
         latency_max_s=float(np.max(lat)) if n else None,
         generator_late_max_ms=1e3 * max(run.lateness, default=0.0),
         generator_late_mean_ms=1e3 * float(np.mean(run.lateness))
         if run.lateness else 0.0,
         peak_rss_bytes=resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
         * 1024, peak_bytes_in_use=peak)

    # the check: after the window, with the program's state dropped
    t = time.perf_counter()
    del ds
    gc.collect()
    ref = Reference(x, y, {a: cols[a] for a in {v.attr for v in sch.views}})
    views = ([sch.first] if sch.first else []) + sch.views
    results = ([run.first_result] if sch.first else []) + run.results
    verdict = correctness.judge(correctness.compare(views, results, ref),
                                correctness.limits(workload))
    _log(phase="check", seconds=time.perf_counter() - t)

    kind = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in bench[kind]:
        if "workloads" in m and workload not in m["workloads"]:
            continue
        value = metric(m["name"]).read(run)
        if value is None:
            if kind == "end_to_end":
                raise RuntimeError(f"end-to-end metric {m['name']} read "
                                   f"nothing in {workload}")
            continue
        metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": peak}
    out = {"correct": verdict["correct"],
           "attempted": len(views),
           "failed": sum(r is None for r in results),
           "metrics": metrics, "device": device}
    if trace and run.trace is not None:
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
        out["breakdown"] = {"device_ops": run.trace["device_ops"],
                            "idle_gaps": run.trace["idle_gaps"]}
    out["checks"] = verdict["checks"]
    for name, c in verdict["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    return out
