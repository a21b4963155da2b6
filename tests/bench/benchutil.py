"""Shared helpers of the chip benchmark's CPU tests: a copy of the
benchmark's files at a size a test run can hold, and an in-process run of a
cell on it with the chip check skipped."""

from __future__ import annotations

import json
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "benchmarks" / "chip"
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

TRAIN = "vgg16.train_swap"
SERVE = "resnet18_transfer.personalize"

# the cells cut so that a CPU runs them in seconds: vgg16 at batch 4 on
# three batches; four users, each with three requests
SMALL = {
    ("configs", "vgg16.json"): {"batch": 4},
    ("traffic", "train_swap.json"): {"batches": 3, "warm_min": 1,
                                     "warm_max": 1},
    ("traffic", "personalize.json"): {"users": 4, "pool": 3, "warm_min": 2,
                                      "warm_max": 2},
}


def small_bench(tmp: Path) -> tuple:
    """``(BENCHMARK.json path, bench dir)`` of a small copy under ``tmp``;
    its peak table also knows the CPU, so that traced runs read their MFU."""
    bench_dir = tmp / "chip"
    for sub in ("configs", "traffic", "metrics"):
        shutil.copytree(BENCH / sub, bench_dir / sub)
    for (sub, name), changes in SMALL.items():
        path = bench_dir / sub / name
        data = json.loads(path.read_text())
        data.update(changes)
        path.write_text(json.dumps(data))
    peaks = json.loads((BENCH / "peaks.json").read_text())
    peaks["devices"]["cpu"] = {"bf16_flops": 1e12}
    (bench_dir / "peaks.json").write_text(json.dumps(peaks))
    bench_json = tmp / "BENCHMARK.json"
    shutil.copy(ROOT / "BENCHMARK.json", bench_json)
    return bench_json, bench_dir


_CELLS = {}


def cell(workload: str, bench: tuple):
    """The cell of a small copy, found once per copy."""
    from chipbench import harness
    bench_json, bench_dir = bench
    key = (str(bench_json), workload)
    if key not in _CELLS:
        _CELLS[key] = harness.find_cell(workload, bench_json, bench_dir)
    return _CELLS[key]


def run_small(tmp: Path, workload: str, seed: int = 3, trace: bool = False,
              seconds: float = 0.0, bench=None) -> dict:
    """One run of ``workload`` on a small copy, with the chip check skipped
    and JAX's compilation cache left alone; runs of one copy share the cell
    (and so the reference's compiled programs)."""
    import jax
    from chipbench import harness
    found = cell(workload, bench or small_bench(tmp))
    saved = harness.require_tpu, harness.enable_compile_cache
    harness.require_tpu = lambda chips: jax.devices()[0]
    harness.enable_compile_cache = lambda path: "off"
    try:
        return harness.run_cell(found, seed, seconds, trace,
                                time.perf_counter())
    finally:
        harness.require_tpu, harness.enable_compile_cache = saved


def load_ref(config: str):
    """A configuration's plain reference module."""
    from chipbench.harness import load_module
    return load_module(BENCH / "configs" / f"{config}.py", f"ref_{config}")


def add_files_only(bench_json: Path, bench_dir: Path) -> str:
    """Add a configuration, a traffic mix and a per-layer metric to a copy
    of the benchmark by new files and new ``BENCHMARK.json`` entries alone;
    returns the new cell's name."""
    cfg = json.loads((bench_dir / "configs" / "vgg16.json").read_text())
    cfg["name"] = "vgg16_copy"
    (bench_dir / "configs" / "vgg16_copy.json").write_text(json.dumps(cfg))
    shutil.copy(bench_dir / "configs" / "vgg16.py",
                bench_dir / "configs" / "vgg16_copy.py")
    mix = json.loads((bench_dir / "traffic" / "train_swap.json").read_text())
    mix["batches"] = 4
    (bench_dir / "traffic" / "train_four.json").write_text(json.dumps(mix))
    (bench_dir / "metrics" / "steps_seen.py").write_text(
        '"""Steps the window ran."""\n\n\n'
        "def read(rec, ctx):\n"
        "    return rec.counters.get(\"steps\")\n")
    bench = json.loads(bench_json.read_text())
    bench["configs"].append({"name": "vgg16_copy", "source": "test",
                             "file": "configs/vgg16_copy.json",
                             "reduced": [], "why": "test"})
    name = "vgg16_copy.train_four"
    bench["workloads"].append({"name": name, "config": "vgg16_copy",
                               "traffic": "train_four", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"]:
        if "workloads" in m and TRAIN in m["workloads"]:
            m["workloads"].append(name)
    bench["per_layer"].append({"name": "steps_seen", "unit": "steps",
                               "better": "higher", "source": "host_clock",
                               "layer": "whole step",
                               "moves": "train_step_ms",
                               "workloads": [name]})
    bench_json.write_text(json.dumps(bench))
    return name
