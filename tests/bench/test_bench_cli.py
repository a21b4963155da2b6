"""The command line: it refuses a CPU backend and a checkout without the
program, printing no result, and BENCHMARK.json keeps to its schema."""

import json
import os
import re
import shutil
import subprocess
import sys

import benchutil
import pytest

ROOT = benchutil.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def run_cli(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         benchutil.TRAIN, "--seed", "2147483653", "--seconds", "1",
         "--trace", "0", *extra], cwd=cwd, env=env, capture_output=True,
        text=True, timeout=300)


def no_result(stdout):
    for line in stdout.splitlines():
        try:
            json.loads(line)
        except ValueError:
            continue
        return False
    return True


def test_refuses_a_cpu_backend():
    p = run_cli(ROOT)
    assert p.returncode != 0
    assert no_result(p.stdout)
    assert "no TPU" in p.stderr


def test_refuses_a_checkout_of_the_benchmark_alone(tmp_path):
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for path in bench["paths"]:
        shutil.copytree(ROOT / path, tmp_path / path,
                        ignore=shutil.ignore_patterns("__pycache__", ".out"))
    p = run_cli(tmp_path)
    assert p.returncode != 0
    assert no_result(p.stdout)


def test_benchmark_json_keeps_to_its_schema():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    assert list(bench) == ["command", "paths", "run_seconds", "configs",
                           "workloads", "end_to_end", "per_layer"]
    assert 1 <= bench["run_seconds"] <= 51
    for path in bench["paths"]:
        assert (ROOT / path).is_dir()
    assert (ROOT / bench["command"][-1]).is_file()
    configs = {c["name"]: c for c in bench["configs"]}
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert (ROOT / c["file"]).is_file()
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    for cell in bench["workloads"]:
        assert set(cell) == {"name", "config", "traffic", "chips", "why"}
        assert cell["config"] in configs and cell["chips"] in (1, 4)
        assert len(cell["why"]) <= 200
        reported = {n for n, m in e2e.items()
                    if "workloads" not in m or cell["name"] in m["workloads"]}
        assert "setup_s" in reported and len(reported) >= 2
        assert any(cell["name"] in m.get("workloads", [cell["name"]])
                   for m in bench["per_layer"])
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and m["better"] in ("lower", "higher")
        assert 0.01 <= m.get("bound", 0.01) <= 0.25
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        for w in m["workloads"]:
            ew = e2e[m["moves"]].get("workloads")
            assert ew is None or w in ew
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in bench[k]]
    assert len(names) == len(set(names))


@pytest.mark.parametrize("config", ["vgg16", "resnet18_transfer"])
def test_limits_cover_the_numbers(config):
    cfg = json.loads((benchutil.BENCH / "configs" /
                      f"{config}.json").read_text())
    assert all(v > 0 for v in cfg["limits"].values())
    assert cfg["matmul_precision"] == "float32"
