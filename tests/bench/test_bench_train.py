"""vgg16.train_swap on the CPU at batch 4: a sound run is correct and prints
the contract's line; the control and every fault the cell can have come
out not correct.

All runs share one process, so the programs the first one compiles serve
the rest."""

import json
import math

import benchutil
import jax
import jax.numpy as jnp
import pytest
from chipbench import loop_train
from chipbench import reference as ref

from repro.core.plan import CompiledMemoryPlan

TRAIN = benchutil.TRAIN
KEYS = ["correct", "attempted", "failed", "metrics", "device", "checks"]


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    return benchutil.small_bench(tmp_path_factory.mktemp("bench"))


@pytest.fixture(scope="module")
def sound(bench):
    return benchutil.run_small(None, TRAIN, seed=2**31 + 5, bench=bench)


def test_sound_run_is_correct_and_prints_the_contract_line(sound):
    assert list(sound) == KEYS             # "checks" comes last
    json.dumps(sound)                      # one JSON object
    assert sound["correct"] is True
    assert sound["attempted"] >= 1 and sound["failed"] == 0
    assert set(sound["metrics"]) == {"train_step_ms", "peak_hbm_mb",
                                     "setup_s"}
    assert sound["metrics"]["train_step_ms"]["unit"] == "ms"
    assert sound["metrics"]["setup_s"]["value"] > 0
    assert set(sound["device"]) == {"platform", "kind", "count",
                                    "memory_peak_bytes"}
    assert set(sound["checks"]) == {"loss_rel", "grad_norm_gap",
                                    "grad_rel_err", "update_norm_gap"}
    for c in sound["checks"].values():
        assert math.isfinite(c["value"]) and c["value"] <= c["limit"]


def test_traced_run_reads_the_per_layer_metrics(bench):
    out = benchutil.run_small(None, TRAIN, seed=17, trace=True, bench=bench)
    assert out["correct"] is True
    # the CPU has no device trace: the device metrics find nothing to read
    assert set(out["metrics"]) == {
        "plan_compile_s", "planned_peak_mb", "fwd_bwd_ms",
        "swap_mb_per_step", "optim_update_ms",
        "train_mfu"}
    assert out["metrics"]["swap_mb_per_step"]["value"] > 0
    assert "breakdown" not in out and list(out)[-1] == "checks"


def test_control_is_not_correct(bench, monkeypatch):
    """The reference at three bfloat16 passes, in the program's place."""
    real = loop_train.program_check_steps

    def control(prog, runtime, params, batches, n):
        p0 = loop_train.host(params)
        params, _, times = real(prog, runtime, params, batches, n)
        low = loop_train.reference_check_steps(
            prog.cfg, prog.model, p0, batches, n, precision="high")
        return params, low, times

    monkeypatch.setattr(loop_train, "program_check_steps", control)
    out = benchutil.run_small(None, TRAIN, seed=23, bench=bench)
    assert out["correct"] is False
    assert any(c["value"] > c["limit"] for c in out["checks"].values())


def _unchanged_state(monkeypatch):
    import repro.core.optim_offload as oo
    monkeypatch.setattr(oo, "offloaded_update",
                        lambda runtime, params, grads, stats=None: params)


def _half_batch(monkeypatch):
    real = CompiledMemoryPlan.loss_and_grads

    def half(self, params, x, label, *, mask=None, **kw):
        b = x.shape[0]
        mask = jnp.concatenate([jnp.ones((b // 2,), jnp.float32),
                                jnp.zeros((b - b // 2,), jnp.float32)])
        return real(self, params, x, label, mask=mask, **kw)

    monkeypatch.setattr(CompiledMemoryPlan, "loss_and_grads", half)


def _label_altered(monkeypatch):
    real = CompiledMemoryPlan.loss_and_grads

    def altered(self, params, x, label, **kw):
        label = label.at[0].set(jnp.roll(label[0], 1))
        return real(self, params, x, label, **kw)

    monkeypatch.setattr(CompiledMemoryPlan, "loss_and_grads", altered)


@pytest.mark.parametrize("plant", [_unchanged_state, _half_batch,
                                   _label_altered],
                         ids=["state_unchanged", "half_batch",
                              "label_altered"])
def test_fault_is_not_correct(bench, monkeypatch, plant):
    plant(monkeypatch)
    out = benchutil.run_small(None, TRAIN, seed=29, bench=bench)
    assert out["correct"] is False


def test_reference_layout_is_the_programs(sound):
    """The reference's weights match the program's graph leaf for leaf."""
    from repro.core.zoo import ZOO
    vgg = benchutil.load_ref("vgg16")
    loop_train.check_layout(ZOO["vgg16"](), ref.param_shapes(vgg.LAYERS),
                            vgg.TRAINABLE)
    assert jax.devices()[0].platform == "cpu"


def test_cell_added_as_files_runs(bench, tmp_path):
    """A configuration, mix and metric added as files: the new cell runs
    and its new metric is read."""
    import shutil
    bench_json, bench_dir = tmp_path / "BENCHMARK.json", tmp_path / "chip"
    shutil.copy(bench[0], bench_json)
    shutil.copytree(bench[1], bench_dir)
    name = benchutil.add_files_only(bench_json, bench_dir)
    out = benchutil.run_small(None, name, seed=31, trace=True,
                              bench=(bench_json, bench_dir))
    assert out["correct"] is True
    assert out["metrics"]["steps_seen"]["value"] >= 1
