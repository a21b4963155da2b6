"""Launch-layer analysis tests: HLO collective parsing, roofline math,
probe extrapolation consistency (subprocess with multi-device host)."""

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from repro.launch.hlo_analysis import analyze_collectives, _shape_bytes

ROOT = Path(__file__).resolve().parents[1]

FAKE_HLO = """
HloModule test

ENTRY %main {
  %p0 = bf16[8,128]{1,0} parameter(0)
  %p1 = f32[16,16]{1,0} parameter(1)
  %ag = bf16[64,128]{1,0} all-gather(%p0), replica_groups={{0,1}}, dimensions={0}
  %ar = f32[16,16]{1,0} all-reduce(%p1), to_apply=%add
  %rs = f32[2,16]{1,0} reduce-scatter(%p1), dimensions={0}
  %cp = bf16[8,128]{1,0} collective-permute(%p0), source_target_pairs={{0,1}}
  ROOT %t = (bf16[64,128]{1,0}) tuple(%ag)
}
"""


def test_shape_bytes():
    assert _shape_bytes("bf16[8,128]{1,0}") == 8 * 128 * 2
    assert _shape_bytes("f32[16,16]") == 16 * 16 * 4
    assert _shape_bytes("(bf16[2,2], f32[3])") == 8 + 12
    assert _shape_bytes("pred[]") == 1


def test_analyze_collectives_counts_and_bytes():
    out = analyze_collectives(FAKE_HLO)
    per = out["per_op"]
    assert per["all-gather"]["count"] == 1
    assert per["all-gather"]["operand_bytes"] == 8 * 128 * 2
    assert per["all-gather"]["result_bytes"] == 64 * 128 * 2
    assert per["all-reduce"]["count"] == 1
    assert per["all-reduce"]["operand_bytes"] == 16 * 16 * 4
    assert per["reduce-scatter"]["count"] == 1
    assert per["collective-permute"]["count"] == 1
    assert out["collective_bytes"] > 0


def test_roofline_cell_math():
    from repro.launch.roofline import analyze_cell
    from repro.launch.mesh import PEAK_FLOPS_BF16, HBM_BW, ICI_BW
    rec = {
        "status": "ok", "arch": "llama3.2-3b", "shape": "train_4k",
        "mesh": "pod", "chips": 256,
        "probe": {"flops": 1e15, "bytes": 1e12, "collective_bytes": 1e11},
        "memory_analysis": {"peak_memory_in_bytes": 2 << 30},
    }
    a = analyze_cell(rec)
    assert abs(a["t_compute_s"] - 1e15 / PEAK_FLOPS_BF16) < 1e-9
    assert abs(a["t_memory_s"] - 1e12 / HBM_BW) < 1e-9
    assert abs(a["t_collective_s"] - 1e11 / ICI_BW) < 1e-9
    assert a["dominant"] == "compute"
    assert 0 < a["useful_compute_ratio"] < 1
    assert a["roofline_fraction"] <= 1.0


def test_roofline_skips_bad_cells():
    from repro.launch.roofline import analyze_cell
    assert analyze_cell({"status": "error"}) is None
    assert analyze_cell({"status": "ok", "probe": {"error": "x"},
                         "memory_analysis": {}}) is None


@pytest.mark.slow
def test_probe_linearity_small():
    """Unrolled probe FLOPs must grow linearly in depth: cost(3 layers)
    ~= fixed + 3*per_layer predicted from the 1/2-layer probes."""
    script = """
    import dataclasses, jax
    from repro.configs import ARCHS
    from repro.configs.base import ShapeConfig
    from repro.launch.probe import _lower_and_cost, probe_config
    from repro.models.model import reduce_config
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((2, 2), ("data", "model"))
    cfg = reduce_config(ARCHS["llama3.2-3b"], d_model=64, n_heads=4,
                        n_kv_heads=2, vocab=512)
    shape = ShapeConfig("t", 64, 4, "train")
    c1 = _lower_and_cost(probe_config(cfg, 1, 64), shape, mesh)
    c2 = _lower_and_cost(probe_config(cfg, 2, 64), shape, mesh)
    c3 = _lower_and_cost(probe_config(cfg, 3, 64), shape, mesh)
    per = c2["flops"] - c1["flops"]
    pred3 = c1["flops"] + 2 * per
    err = abs(c3["flops"] - pred3) / max(c3["flops"], 1)
    assert err < 0.05, (c1["flops"], c2["flops"], c3["flops"], err)
    print("probe linearity OK", err)
    """
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=4").strip()
    env["PYTHONPATH"] = str(ROOT / "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(script)],
                         capture_output=True, text=True, timeout=560,
                         env=env)
    assert out.returncode == 0, f"{out.stdout}\n{out.stderr}"


def test_dryrun_results_schema():
    """Whatever dry-run artifacts exist must carry the full schema."""
    results = ROOT / "results" / "dryrun"
    files = list(results.glob("*.json")) if results.exists() else []
    if not files:
        pytest.skip("no dry-run artifacts yet")
    for p in files:
        rec = json.loads(p.read_text())
        assert rec["status"] in ("ok", "skipped", "error"), p
        if rec["status"] == "ok":
            assert rec["chips"] in (256, 512)
            assert "cost_analysis" in rec and "collectives" in rec
            assert "memory_analysis" in rec
