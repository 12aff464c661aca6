"""The harness: finds a cell's files by name, runs its driver, prints one
result line.

Nothing here names a cell, a configuration, a traffic mix or a metric.
``BENCHMARK.json`` says which metrics a cell reports; the files under
``bench/`` say what the cell is:

- ``workloads/<cell>.json``: its configuration, traffic, driver and check;
- ``configs/<config>.json``: the model's sizes as run;
- ``traffic/<traffic>.json``: the parameters the driver's generator reads;
- ``drivers/<driver>.py``: ``run(ctx) -> Run``, the timed path;
- ``metrics/<metric>.py``: ``read(run) -> float | None``, one per-layer
  metric reduced from the run's steps, counters and trace;
- ``peaks.json``: the chip's peaks, keyed by ``device_kind``.
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import os
import sys
import tempfile
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)

#: JAX lowers a jaxpr to a program once per program it has not built in
#: this process: the count of these events inside the window is the count
#: of programs built (compiled or read from the persistent cache) there
BUILD_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"


class NoChip(RuntimeError):
    """The process sees no accelerator of the kind the cell needs."""


class Files:
    """The benchmark's data files, found by name: in the first of ``dirs``
    that holds one (``bench/`` alone by default)."""

    def __init__(self, *dirs: str):
        self.dirs = dirs or (BENCH,)

    def path(self, kind: str, name: str, ext: str) -> str:
        for d in self.dirs:
            p = os.path.join(d, kind, f"{name}{ext}")
            if os.path.exists(p):
                return p
        raise FileNotFoundError(f"no {kind}/{name}{ext} under {self.dirs}")

    def _json(self, kind: str, name: str) -> dict:
        with open(self.path(kind, name, ".json")) as f:
            return json.load(f)

    def workload(self, name: str) -> dict:
        return self._json("workloads", name)

    def config(self, name: str) -> dict:
        return self._json("configs", name)

    def traffic(self, name: str) -> dict:
        return self._json("traffic", name)

    def peaks(self) -> dict:
        with open(self.path("", "peaks", ".json")) as f:
            return json.load(f)

    def _module(self, kind: str, name: str):
        path = self.path(kind, name, ".py")
        spec = importlib.util.spec_from_file_location(
            f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def driver(self, kind: str):
        return self._module("drivers", kind)

    def metric(self, name: str):
        return self._module("metrics", name)


def load_spec(path: str = os.path.join(ROOT, "BENCHMARK.json")) -> dict:
    with open(path) as f:
        return json.load(f)


def cell_metrics(spec: dict, cell: str, section: str) -> List[dict]:
    """The entries of ``spec[section]`` that cell ``cell`` reports: those
    that list it, and those with no list whose moved metric it reports."""
    entry = next((w for w in spec["workloads"] if w["name"] == cell), None)
    if entry is None:
        raise KeyError(f"no workload {cell!r} in the benchmark")
    e2e = [m["name"] for m in spec["end_to_end"]
           if "workloads" not in m or cell in m["workloads"]]
    if section == "end_to_end":
        return [m for m in spec["end_to_end"] if m["name"] in e2e]
    return [m for m in spec[section]
            if cell in m.get("workloads", ())
            or ("workloads" not in m and m["moves"] in e2e)]


def peak_for(peaks: dict, kind: str) -> dict:
    try:
        return peaks["devices"][kind]
    except KeyError:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json; "
                       "add its published peaks there") from None


def require_chips(n: int) -> list:
    """The accelerators of this process, or ``NoChip``."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"jax.devices()[0].platform is {devs[0].platform!r}: "
                     "the benchmark measures the TPU and has no fallback")
    if len(devs) < n:
        raise NoChip(f"the cell needs {n} chips and JAX sees {len(devs)}")
    return devs


@dataclass
class Run:
    """What a driver hands back: end-to-end values, counts, the numbers
    compared for ``correct``, and what per-layer readers read."""

    e2e: Dict[str, Optional[float]]
    attempted: int
    failed: int
    #: name -> {"value": ..., "limit": ...}; correct when every value is
    #: finite and within its limit
    checks: Dict[str, Dict[str, float]]
    #: per step: {"kind", "t0", "t1", ...}, on ``time.perf_counter``
    steps: List[dict] = field(default_factory=list)
    #: anything else the driver's metric readers need
    info: Dict[str, Any] = field(default_factory=dict)
    # filled by the harness
    trace: Any = None
    compiles_in_window: int = 0
    peak: Optional[dict] = None

    @property
    def correct(self) -> bool:
        import math
        return bool(self.checks) and all(
            c["value"] is not None and math.isfinite(c["value"])
            and c["value"] <= c["limit"] for c in self.checks.values())


class Ctx:
    """What a driver gets: the cell's files, its seed and length, and the
    calls that open and close the measured window."""

    def __init__(self, files: Files, cell: str, seed: int, seconds: float,
                 trace: bool, t_process: float):
        self.files = files
        self.cell = cell
        self.workload = files.workload(cell)
        self.config_name = self.workload["config"]
        self.config = files.config(self.config_name)
        self.traffic = files.traffic(self.workload["traffic"])
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.trace = bool(trace)
        self.t_process = t_process
        self.setup_s: Optional[float] = None
        self.compiles = 0
        self.memory_peak_bytes: Optional[int] = None
        self.trace_data = None
        self._counting = False
        self._trace_dir: Optional[str] = None
        self._window_span = None

    def _on_event(self, event: str, duration: float, **kw) -> None:
        if self._counting and event == BUILD_EVENT:
            self.compiles += 1

    def span(self, name: str):
        """A host span in the trace around a call into a layer (nothing
        when the run is not traced)."""
        if not self.trace:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    def window_start(self) -> float:
        """Set-up ends here: start counting builds and, when traced, the
        profiler. Returns the window's start on ``time.perf_counter``."""
        import jax
        from bench import tracefile
        if self.trace:
            self._trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
            # the device's operations and the bench.* spans; no trace of
            # every Python call, which would slow the host it measures
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.raise_error_on_start_failure = True
            jax.profiler.start_trace(self._trace_dir, profiler_options=opts)
            self._window_span = jax.profiler.TraceAnnotation(
                tracefile.WINDOW_SPAN)
            self._window_span.__enter__()
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        self._counting = True
        t = time.perf_counter()
        self.setup_s = t - self.t_process
        return t

    def window_end(self) -> None:
        """Stop counting and tracing; read the trace."""
        import shutil
        import jax
        from bench import tracefile
        self._counting = False
        jax.monitoring.unregister_event_duration_listener(self._on_event)
        if self._window_span is not None:
            self._window_span.__exit__(None, None, None)
            jax.profiler.stop_trace()
            try:
                self.trace_data = tracefile.load(self._trace_dir)
            finally:
                shutil.rmtree(self._trace_dir, ignore_errors=True)

    def read_memory_peak(self) -> Optional[int]:
        """Peak device bytes of the fullest chip so far; call it once the
        window has closed and before the check frees and rebuilds state."""
        import jax
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                 for d in jax.local_devices()]
        peaks = [p for p in peaks if p is not None]
        self.memory_peak_bytes = max(peaks) if peaks else None
        return self.memory_peak_bytes


def run_cell(files: Files, spec: dict, cell: str, seed: int, seconds: float,
             trace: bool, t_process: float, device: dict,
             peak: Optional[dict]) -> dict:
    """Run one cell through its driver and build the result object."""
    ctx = Ctx(files, cell, seed, seconds, trace, t_process)
    run: Run = files.driver(ctx.workload["driver"]).run(ctx)
    run.trace = ctx.trace_data
    run.compiles_in_window = ctx.compiles
    run.peak = peak
    metrics: Dict[str, dict] = {}
    dev = dict(device, memory_peak_bytes=ctx.memory_peak_bytes)
    result: Dict[str, Any] = {"correct": run.correct,
                              "attempted": run.attempted,
                              "failed": run.failed}
    if not trace:
        for m in cell_metrics(spec, cell, "end_to_end"):
            v = ctx.setup_s if m["name"] == "setup_s" else run.e2e.get(
                m["name"])
            if v is None:
                raise RuntimeError(f"the {ctx.workload['driver']} driver "
                                   f"gave no {m['name']} for {cell}")
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        from bench import tracefile
        for m in cell_metrics(spec, cell, "per_layer"):
            v = files.metric(m["name"]).read(run)
            if v is None:
                held = ("no trace" if run.trace is None
                        else tracefile.summary(run.trace))
                raise RuntimeError(
                    f"{m['name']} found nothing to read in {cell}, which "
                    "BENCHMARK.json lists for it: its kernel or step is "
                    f"missing from the trace or the run. The trace:\n{held}")
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        if run.trace is not None:
            dev["busy_s"] = run.trace.busy_seconds()
            dev["window_s"] = run.trace.window_seconds()
            result["breakdown"] = {"device_ops": tracefile.top_ops(run.trace),
                                   "idle_gaps": tracefile.idle_gaps(run.trace)}
    result["metrics"] = metrics
    result["device"] = dev
    result["checks"] = run.checks
    return result


def print_result(result: dict) -> None:
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(f"correct: {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)


def compile_cache_dir() -> str:
    """JAX's persistent cache: ``JAX_COMPILATION_CACHE_DIR`` where it is
    set, else one fixed directory inside the checkout."""
    return (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(ROOT, ".jax_cache"))


def main(argv: Optional[List[str]], t_process: float,
         chips: Callable[[int], list] = require_chips) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    spec = load_spec()
    entry = next((w for w in spec["workloads"]
                  if w["name"] == args.workload), None)
    if entry is None:
        print(f"no workload {args.workload!r} in BENCHMARK.json",
              file=sys.stderr)
        return 2
    try:
        devs = chips(int(entry["chips"]))
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    import jax
    jax.config.update("jax_compilation_cache_dir", compile_cache_dir())
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    files = Files()
    kind = devs[0].device_kind
    peak = peak_for(files.peaks(), kind)
    device = {"platform": devs[0].platform, "kind": kind,
              "count": len(devs)}
    result = run_cell(files, spec, args.workload, args.seed, args.seconds,
                      bool(args.trace), t_process, device, peak)
    print_result(result)
    return 0
