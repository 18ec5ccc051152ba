"""Shared pieces of the chip benchmark: files found by name, the device
check, the peaks table, compile accounting, the profiler's slice,
percentiles and the result line.

Nothing here imports the system under test.  ``run.py`` is the entry
point; each configuration's ``runner`` module (``serve_cell.py``) runs
one cell and hands back a :class:`CellResult`.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import os
import sys
from typing import Any, Callable, Dict, List, Optional, Sequence

HERE = os.path.dirname(os.path.abspath(__file__))
# the checkout's root: BENCHMARK.json and src/ live there
ROOT = os.path.abspath(os.path.join(HERE, "..", ".."))
# JAX's persistent compilation cache, at one fixed path inside the checkout
CACHE_DIR = os.path.join(HERE, ".cache", "jax")
# profiler traces (gitignored)
TRACE_DIR = os.path.join(HERE, ".cache", "trace")


class BenchError(RuntimeError):
    """The benchmark cannot run here (no chip, a missing file, a bad
    entry): the run exits non-zero and prints no result."""


# ---------------------------------------------------------------------------
# files found by name
# ---------------------------------------------------------------------------

def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: Optional[str] = None):
    """Import a Python file by path (file names may hold '.' and '-')."""
    if not os.path.exists(path):
        raise BenchError(f"missing file {os.path.relpath(path, ROOT)}")
    name = name or "bench_" + os.path.relpath(path, HERE).replace(
        os.sep, "_").replace(".", "_").replace("-", "_")
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    """One entry of ``workloads`` with everything found by its names."""
    name: str
    chips: int
    config: Dict[str, Any]
    config_dir: str
    traffic: Dict[str, Any]
    limits: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]


def _applies(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def find_cell(name: str, root: str = ROOT, bench_dir: str = HERE) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json``, with its traffic
    and limits from ``<bench_dir>/traffic`` and ``<bench_dir>/limits``."""
    bench_path = os.path.join(root, "BENCHMARK.json")
    if not os.path.exists(bench_path):
        raise BenchError("no BENCHMARK.json at the checkout's root")
    bench = load_json(bench_path)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json "
                         f"(have {sorted(cells)})")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    centry = configs[w["config"]]
    cfile = os.path.join(root, centry["file"])
    if not os.path.exists(cfile):
        raise BenchError(f"missing configuration file {centry['file']}")
    tfile = os.path.join(bench_dir, "traffic", w["traffic"] + ".json")
    if not os.path.exists(tfile):
        raise BenchError(f"missing traffic file "
                         f"{os.path.relpath(tfile, root)}")
    lfile = os.path.join(bench_dir, "limits", name + ".json")
    if not os.path.exists(lfile):
        raise BenchError(f"missing limits file "
                         f"{os.path.relpath(lfile, root)}")
    return Cell(name=name, chips=int(w["chips"]), config=load_json(cfile),
                config_dir=os.path.dirname(cfile), traffic=load_json(tfile),
                limits=load_json(lfile),
                end_to_end=[m for m in bench["end_to_end"]
                            if _applies(m, name)],
                per_layer=[m for m in bench["per_layer"]
                           if _applies(m, name)])


def metric_reader(name: str) -> Callable[[Dict[str, Any]], Optional[float]]:
    """``metrics/<name>.py``'s ``read(ctx)``: the metric's value, or
    ``None`` when the run holds nothing to read it from."""
    return load_module(os.path.join(HERE, "metrics", name + ".py")).read


# ---------------------------------------------------------------------------
# device and peaks
# ---------------------------------------------------------------------------

def peaks_for(kind: str, path: Optional[str] = None) -> Dict[str, float]:
    """Peaks of one chip of ``kind``; a kind not in the table is an
    error, never a default."""
    table = load_json(path or os.path.join(HERE, "peaks.json"))
    if kind not in table["kinds"]:
        raise BenchError(f"device kind {kind!r} is not in the peaks table "
                         f"(have {sorted(table['kinds'])})")
    return {k: float(v) for k, v in table["kinds"][kind].items()}


def check_devices(chips: int) -> List[Any]:
    """The chips this cell runs on; raises unless JAX sees at least
    ``chips`` TPU devices.  There is no fallback to the CPU."""
    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise BenchError(f"JAX found no accelerator: {e}") from e
    if not devices or devices[0].platform != "tpu":
        raise BenchError(
            f"no TPU: JAX reports platform "
            f"{devices[0].platform if devices else None!r} with "
            f"{len(devices)} device(s); this benchmark runs only on a TPU")
    if len(devices) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX sees "
                         f"{len(devices)}")
    return devices[:chips]


def device_info(devices: Sequence[Any]) -> Dict[str, Any]:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def enable_compile_cache() -> str:
    """JAX's persistent cache in the checkout, for every program however
    fast it compiled, so that a cell's second run compiles nothing."""
    import jax
    os.makedirs(CACHE_DIR, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return CACHE_DIR


# ---------------------------------------------------------------------------
# compile accounting (jax.monitoring)
# ---------------------------------------------------------------------------

class CompileClock:
    """Seconds of backend compilation (a persistent-cache read counts as
    its retrieval time), programs compiled, and persistent-cache hits,
    summed from ``jax.monitoring`` events over every thread."""

    def __init__(self) -> None:
        import jax
        self.seconds = 0.0
        self.programs = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._count)

    def _duration(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.programs += 1

    def _count(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self) -> Dict[str, float]:
        return {"compile_s": self.seconds, "programs": self.programs,
                "cache_hits": self.cache_hits}

    @staticmethod
    def delta(a: Dict[str, float], b: Dict[str, float]) -> Dict[str, float]:
        return {k: b[k] - a[k] for k in a}


# ---------------------------------------------------------------------------
# the profiler's slice of a window
# ---------------------------------------------------------------------------

class TraceSlice:
    """The profiler over ``[start, end)`` of a window's clock, polled from
    the window's loop.  A ``bench_window`` annotation spans the traced
    slice, so the trace's reduction knows its bounds on the trace's own
    clock."""

    def __init__(self, start: float, end: float, trace_dir: str) -> None:
        import shutil
        self.start, self.end = start, end
        self.trace_dir = trace_dir
        shutil.rmtree(trace_dir, ignore_errors=True)
        os.makedirs(trace_dir)
        self.running = False
        self.done = False
        self._span = None

    def poll(self, now: float) -> None:
        import jax
        import trace_reduce
        if not self.running and not self.done and now >= self.start:
            jax.profiler.start_trace(
                self.trace_dir, profiler_options=trace_reduce.trace_options())
            self._span = jax.profiler.TraceAnnotation(
                trace_reduce.WINDOW_SPAN)
            self._span.__enter__()
            self.running = True
        elif self.running and now >= self.end:
            self.close()

    def close(self) -> None:
        if not self.running:
            return
        import jax
        self._span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.running = False
        self.done = True


# ---------------------------------------------------------------------------
# statistics
# ---------------------------------------------------------------------------

def percentile(values: Sequence[float], q: float) -> float:
    """The q-th percentile (0-100), linear between order statistics."""
    if not values:
        raise ValueError("percentile of no values")
    xs = sorted(values)
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


# ---------------------------------------------------------------------------
# the result
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Check:
    """One number compared by the correctness check, with its limit; the
    run is correct when every value is at most its limit."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


@dataclasses.dataclass
class CellResult:
    attempted: int
    failed: int
    checks: List[Check]
    end_to_end: Dict[str, float]          # host-clock metrics by name
    ctx: Dict[str, Any]                   # what per-layer readers read
    devices: List[Any]
    memory_peak_bytes: int

    @property
    def correct(self) -> bool:
        return bool(self.checks) and all(c.ok for c in self.checks)


def result_line(cell: Cell, res: CellResult, *, trace: bool
                ) -> Dict[str, Any]:
    """The last line of standard output.  With ``trace`` the metrics are
    the cell's per-layer metrics, else its end-to-end metrics; the
    numbers compared for ``correct`` come last, each beside its limit."""
    metrics: Dict[str, Dict[str, Any]] = {}
    if trace:
        for m in cell.per_layer:
            value = metric_reader(m["name"])(res.ctx)
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
    else:
        for m in cell.end_to_end:
            if m["name"] not in res.end_to_end:
                raise BenchError(f"the cell reported no {m['name']}")
            metrics[m["name"]] = {"value": float(res.end_to_end[m["name"]]),
                                  "unit": m["unit"]}
    dev = {"platform": res.devices[0].platform,
           "kind": res.devices[0].device_kind, "count": len(res.devices),
           "memory_peak_bytes": int(res.memory_peak_bytes)}
    line: Dict[str, Any] = {"correct": res.correct,
                            "attempted": int(res.attempted),
                            "failed": int(res.failed), "metrics": metrics,
                            "device": dev}
    if trace:
        red = res.ctx.get("trace")
        if red is not None:
            dev["busy_s"] = red["busy_s"]
            dev["window_s"] = red["window_s"]
            line["breakdown"] = red["breakdown"]
    line["compared"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in res.checks}
    return line


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)
