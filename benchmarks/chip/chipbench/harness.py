"""Discovery, the run's context and the result line.

Everything that belongs to one configuration, traffic mix or per-layer
metric sits in files of its own, found by the names in ``BENCHMARK.json``:

* ``configs/<config>.json`` — the configuration as it is run (graph, batch
  or buckets, ``MemoryPlanConfig`` fields, optimizer, matmul precision,
  source, ``reduced``, ``assumed``, and the limits of the numbers that
  decide ``correct``), with its plain reference ``configs/<config>.py``
  beside it;
* ``traffic/<mix>.json`` — the mix's parameters; its ``loop`` names the
  general loop (``chipbench/loop_<loop>.py``) that reads them;
* ``metrics/<metric>.py`` — a reader ``read(record, ctx)`` that returns the
  metric from the run's counters, spans and trace, or None when it finds
  nothing to read.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib
import importlib.util
import json
import os
import statistics
import sys
import time
from pathlib import Path
from types import ModuleType
from typing import Any, Callable, Dict, List, Optional, Tuple

BENCH_DIR = Path(__file__).resolve().parents[1]        # benchmarks/chip
ROOT = BENCH_DIR.parents[1]                           # the checkout


class BenchError(RuntimeError):
    """The benchmark cannot run this cell here (no chip, unknown name)."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_module(path: Path, name: str) -> ModuleType:
    if not path.is_file():
        raise BenchError(f"missing {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    model: ModuleType                 # the configuration's plain reference
    loop: ModuleType                  # the traffic's general loop
    end_to_end: List[dict]
    per_layer: List[dict]
    readers: Dict[str, ModuleType]
    bench_dir: Path = BENCH_DIR


def _applies(metric: dict, cell: str, e2e_names: set) -> bool:
    """A per-layer metric is read in the cells its ``workloads`` lists or,
    without that key, in every cell that reports the metric it moves."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric["moves"] in e2e_names


def find_cell(workload: str, bench_json: Path = ROOT / "BENCHMARK.json",
              bench_dir: Path = BENCH_DIR) -> Cell:
    if not bench_json.is_file():
        raise BenchError(f"missing {bench_json}")
    bench = json.loads(bench_json.read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise BenchError(f"unknown workload {workload!r}; "
                         f"have {sorted(cells)}")
    w = cells[workload]
    config = json.loads(_read(bench_dir / "configs" / f"{w['config']}.json"))
    traffic = json.loads(_read(bench_dir / "traffic" / f"{w['traffic']}.json"))
    model = load_module(bench_dir / "configs" / f"{w['config']}.py",
                        f"chipbench_model_{w['config']}")
    loop = importlib.import_module(f"chipbench.loop_{traffic['loop']}")
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or workload in m["workloads"]]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if _applies(m, workload, names)]
    readers = {m["name"]: load_module(
        bench_dir / "metrics" / f"{m['name']}.py",
        "chipbench_metric_" + m["name"].replace(".", "_"))
        for m in per_layer}
    return Cell(workload, int(w["chips"]), config, traffic, model, loop,
                e2e, per_layer, readers, bench_dir)


def _read(path: Path) -> str:
    if not path.is_file():
        raise BenchError(f"missing {path}")
    return path.read_text()


# ---------------------------------------------------------------------------
# the device
# ---------------------------------------------------------------------------

def require_tpu(chips: int):
    """The first TPU device; a BenchError when JAX finds no TPU or fewer
    chips than the cell asks for.  There is no CPU fallback."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise BenchError(f"JAX found no TPU (platform "
                         f"{devices[0].platform!r}); the benchmark runs "
                         f"only on a TPU")
    if len(devices) < chips:
        raise BenchError(f"the cell asks for {chips} chips, JAX sees "
                         f"{len(devices)}")
    return devices[0]


def enable_compile_cache(path: Path) -> str:
    """JAX's persistent compilation cache at ``path`` (a fixed directory:
    the path is part of the cache's key), every program in it however fast
    it compiled, so that only a cell's first run in a checkout compiles."""
    import jax
    path = str(path)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def peaks(device_kind: str, bench_dir: Path = BENCH_DIR) -> Dict[str, float]:
    table = json.loads((bench_dir / "peaks.json").read_text())["devices"]
    if device_kind not in table:
        raise BenchError(f"no peaks for device kind {device_kind!r} in "
                         f"peaks.json")
    return table[device_kind]


class CompileCounter:
    """Counts the programs JAX builds (``n``) and the misses of its
    in-memory jit cache (``requests``: each is a compile or a load from the
    persistent cache)."""

    COMPILE = "/jax/core/compile/backend_compile_duration"
    REQUEST = "/jax/compilation_cache/compile_requests_use_cache"

    def __init__(self) -> None:
        import jax
        self.n = 0
        self.requests = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_time)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_time(self, event: str, duration: float, **kwargs) -> None:
        if event == self.COMPILE:
            self.n += 1

    def _on_event(self, event: str, **kwargs) -> None:
        if event == self.REQUEST:
            self.requests += 1

    @property
    def total(self) -> int:
        return self.n + self.requests


class GcCounter:
    """Counts the garbage collector's runs."""

    def __init__(self) -> None:
        self.n = 0
        gc.callbacks.append(self._on_gc)

    def _on_gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            self.n += 1


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Context:
    cell: Cell
    seed: int
    seconds: float
    trace: bool
    t0: float                          # process start, host clock
    trace_dir: Path
    compiles: Optional[CompileCounter] = None
    gcs: Optional[GcCounter] = None
    device_kind: str = ""


@dataclasses.dataclass
class Record:
    """What one run measured and checked; the metric readers read it."""
    e2e: Dict[str, float] = dataclasses.field(default_factory=dict)
    counters: Dict[str, float] = dataclasses.field(default_factory=dict)
    spans: List[Tuple[str, float, float]] = dataclasses.field(
        default_factory=list)
    trace: Any = None                  # devtrace.TraceSummary or None
    numbers: Dict[str, float] = dataclasses.field(default_factory=dict)
    attempted: int = 0
    failed: int = 0
    peak_bytes: int = 0
    window_compiles: int = 0
    window_gcs: int = 0
    window_units: int = 0

    def span_ms(self, name: str) -> Optional[float]:
        d = [b - a for n, a, b in self.spans if n == name]
        return statistics.fmean(d) * 1e3 if d else None


def settle() -> None:
    """End of set-up: collect, then freeze what survives so the window's
    collections walk only what the window allocates."""
    gc.collect()
    gc.freeze()


def timed_window(step: Callable[[int], Any], seconds: float,
                 finish: Callable[[Any], None],
                 clock: Callable[[], float] = time.perf_counter
                 ) -> Tuple[int, float, float]:
    """Run ``step(i)`` until ``seconds`` have passed at a step boundary, then
    ``finish`` the last step's outputs (block until the device is done).
    Returns ``(steps, t_start, t_end)``: the window opens at the first
    step's start and closes when ``finish`` returns."""
    t_start = clock()
    n, out = 0, None
    while True:
        out = step(n)
        n += 1
        if clock() - t_start >= seconds:
            break
    finish(out)
    return n, t_start, clock()


# where JAX_COMPILATION_CACHE_DIR says, else a fixed directory in the checkout
CACHE_DIR = Path(os.environ.get("JAX_COMPILATION_CACHE_DIR")
                 or ROOT / ".jax_cache")


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t0: float) -> dict:
    """Set up, warm, measure, check; returns the result object."""
    import jax

    from chipbench import checks, devtrace

    dev = require_tpu(cell.chips)
    cache = enable_compile_cache(CACHE_DIR)
    log(f"device {dev.device_kind} x{len(jax.devices())} ({dev.platform}); "
        f"jax {jax.__version__}; compile cache {cache}")
    ctx = Context(cell=cell, seed=int(seed), seconds=float(seconds),
                  trace=bool(trace), t0=t0,
                  trace_dir=cell.bench_dir / ".out" / "trace" / cell.name,
                  compiles=CompileCounter(), gcs=GcCounter(),
                  device_kind=dev.device_kind)
    rec = cell.loop.run(ctx)
    log(f"window: {rec.window_units} units, {rec.window_compiles} compiles "
        f"and {rec.window_gcs} garbage collections inside it")
    correct, checked = checks.compare(rec.numbers, cell.config["limits"])
    correct = correct and rec.failed == 0 and rec.attempted > 0
    if trace:
        metrics = {}
        for m in cell.per_layer:
            v = cell.readers[m["name"]].read(rec, ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    else:
        metrics = {m["name"]: {"value": float(rec.e2e[m["name"]]),
                               "unit": m["unit"]} for m in cell.end_to_end}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes":
              int(rec.peak_bytes)}
    out = {"correct": bool(correct), "attempted": int(rec.attempted),
           "failed": int(rec.failed), "metrics": metrics, "device": device}
    if trace and isinstance(rec.trace, devtrace.TraceSummary):
        device["busy_s"] = rec.trace.busy_s
        device["window_s"] = rec.trace.window_s
        out["breakdown"] = {"device_ops": rec.trace.device_ops,
                            "idle_gaps": rec.trace.idle_gaps}
    checks.print_checks(checked)
    out["checks"] = checked
    return out
