"""A configuration, a traffic mix and a per-layer metric are found by the
names in BENCHMARK.json: adding one takes new files and entries alone."""

import benchutil
import pytest
from chipbench import harness, loop_train


def test_new_files_are_found_by_name(tmp_path):
    bench_json, bench_dir = benchutil.small_bench(tmp_path)
    name = benchutil.add_files_only(bench_json, bench_dir)
    cell = harness.find_cell(name, bench_json, bench_dir)
    assert cell.config["name"] == "vgg16_copy"
    assert cell.traffic["batches"] == 4
    assert cell.loop is loop_train
    assert cell.model.TRAINABLE[0] == "b0_c0"
    assert [m["name"] for m in cell.end_to_end] == [
        "train_step_ms", "peak_hbm_mb", "setup_s"]
    # the per-layer metrics name their cells: the new one reads the new cell
    assert set(cell.readers) == {"steps_seen"}
    rec = harness.Record(counters={"steps": 7})
    assert cell.readers["steps_seen"].read(rec, None) == 7
    old = harness.find_cell(benchutil.TRAIN, bench_json, bench_dir)
    assert "steps_seen" not in old.readers


@pytest.mark.parametrize("missing", ["configs/vgg16.json", "configs/vgg16.py",
                                     "traffic/train_swap.json",
                                     "metrics/fwd_bwd_ms.py"])
def test_a_missing_file_is_an_error(tmp_path, missing):
    bench_json, bench_dir = benchutil.small_bench(tmp_path)
    (bench_dir / missing).unlink()
    with pytest.raises(harness.BenchError):
        harness.find_cell(benchutil.TRAIN, bench_json, bench_dir)


def test_unknown_workload_is_an_error(tmp_path):
    bench_json, bench_dir = benchutil.small_bench(tmp_path)
    with pytest.raises(harness.BenchError):
        harness.find_cell("vgg16.nothing", bench_json, bench_dir)
