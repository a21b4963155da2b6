"""resnet18_transfer.personalize on the CPU with four users and three
requests each: a sound run is correct and prints the contract's line; the
control and every fault the cell can have come out not correct.

All runs share one process, so the programs the first one compiles serve
the rest."""

import json
import math

import benchutil
import jax.numpy as jnp
import pytest
from chipbench import loop_personalize
from chipbench import reference as ref

import repro.serve.service as service_module
from repro.serve.servable import ServablePersonalizer

SERVE = benchutil.SERVE
KEYS = ["correct", "attempted", "failed", "metrics", "device", "checks"]


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    return benchutil.small_bench(tmp_path_factory.mktemp("bench"))


@pytest.fixture(scope="module")
def sound(bench):
    return benchutil.run_small(None, SERVE, seed=2**31 + 11, bench=bench)


def test_sound_run_is_correct_and_prints_the_contract_line(sound):
    assert list(sound) == KEYS             # "checks" comes last
    json.dumps(sound)
    assert sound["correct"] is True
    assert sound["attempted"] == 4 and sound["failed"] == 0
    assert set(sound["metrics"]) == {"peak_hbm_mb", "setup_s"}
    assert sound["metrics"]["setup_s"]["value"] > 0
    assert set(sound["checks"]) == {"loss_rel", "param_rel"}
    for c in sound["checks"].values():
        assert math.isfinite(c["value"]) and c["value"] <= c["limit"]


def test_traced_run_reads_the_per_layer_metrics(bench):
    out = benchutil.run_small(None, SERVE, seed=19, trace=True, bench=bench)
    assert out["correct"] is True
    assert set(out["metrics"]) == {"plan_compile_s", "planned_peak_mb"}
    assert out["metrics"]["planned_peak_mb"]["value"] > 0
    assert out["metrics"]["plan_compile_s"]["value"] > 0


def test_request_plan_sends_every_seed_the_same_sizes():
    traffic = {"users": 4, "even_size": 8, "odd_sizes": [1, 2, 3, 4],
               "pool": 8}
    a = loop_personalize.request_plan(traffic, 2**40 + 3)
    b = loop_personalize.request_plan(traffic, 5)
    assert a == loop_personalize.request_plan(traffic, 2**40 + 3)
    for u in range(4):
        assert sorted(n for _, n in a[u]) == sorted(n for _, n in b[u])
        assert [j for j, _ in a[u]] == list(range(8))
    assert {n for _, n in a[0]} == {8}
    assert sorted(n for _, n in a[1][:4]) == [1, 2, 3, 4]


@pytest.mark.parametrize("precision", ["high", "bfloat16"])
def test_control_reads_above_the_program(bench, precision):
    """The reference below float32 lies farther from the float32 reference
    than the program does, on both numbers."""
    cell = benchutil.cell(SERVE, bench)
    out = benchutil.run_small(None, SERVE, seed=41, bench=bench)
    prog = {k: c["value"] for k, c in out["checks"].items()}
    base = loop_personalize.host(ref.make_params(
        ref.param_shapes(cell.model.LAYERS), 41,
        loop_personalize.frozen_gains(cell.config, cell.model)))
    xs, ys = loop_personalize.make_pool(cell.config, cell.traffic, 41)
    plan = loop_personalize.request_plan(cell.traffic, 41)
    sent = {u: plan[u][:3] for u in range(len(plan))}
    want = loop_personalize.reference_replay(cell.config, cell.model, base,
                                             xs, ys, sent)
    low = loop_personalize.reference_replay(cell.config, cell.model, base,
                                            xs, ys, sent, precision=precision)
    ctl = loop_personalize.numbers(low, want)
    assert all(ctl[k] > 3 * prog[k] for k in prog)


def test_control_is_not_correct(bench, monkeypatch):
    """The reference below float32, in the program's place.  On the chip the
    written-out three passes read some 1000 times what they read on the
    CPU, as much as one bfloat16 pass does here (PERF.md), so the CPU runs
    the one pass."""
    cell = benchutil.cell(SERVE, bench)

    def control(service, traffic):
        base = loop_personalize.host(service.svc.servable.base_params)
        return loop_personalize.reference_replay(
            cell.config, cell.model, base, traffic.xs, traffic.ys,
            traffic.sent, precision="bfloat16")

    monkeypatch.setattr(loop_personalize, "served", control)
    out = benchutil.run_small(None, SERVE, seed=23, bench=bench)
    assert out["correct"] is False
    assert any(c["value"] > c["limit"] for c in out["checks"].values())


def _unchanged_state(monkeypatch):
    def no_update(self, sess, grads):
        sess.step += 1

    monkeypatch.setattr(ServablePersonalizer, "apply_update", no_update)


def _half_batch(monkeypatch):
    real = service_module.pad_to_bucket

    def half(x, y, bucket):
        xp, yp, mask = real(x, y, bucket)
        n = int(x.shape[0])
        keep = max(1, n // 2)
        mask = jnp.concatenate([jnp.ones((keep,), jnp.float32),
                                jnp.zeros((bucket - keep,), jnp.float32)])
        return xp, yp, mask

    monkeypatch.setattr(service_module, "pad_to_bucket", half)


def _label_altered(monkeypatch):
    real = service_module.pad_to_bucket

    def altered(x, y, bucket):
        y = y.at[0].set(jnp.roll(y[0], 1))
        return real(x, y, bucket)

    monkeypatch.setattr(service_module, "pad_to_bucket", altered)


@pytest.mark.parametrize("plant", [_unchanged_state, _half_batch,
                                   _label_altered],
                         ids=["state_unchanged", "half_batch",
                              "label_altered"])
def test_fault_is_not_correct(bench, monkeypatch, plant):
    plant(monkeypatch)
    out = benchutil.run_small(None, SERVE, seed=29, bench=bench)
    assert out["correct"] is False


def test_reference_layout_is_the_programs():
    """The reference's weights match the program's graph leaf for leaf,
    and it trains the head alone."""
    from chipbench import loop_train

    from repro.core.zoo import ZOO
    model = benchutil.load_ref("resnet18_transfer")
    loop_train.check_layout(ZOO["resnet18_transfer"](),
                            ref.param_shapes(model.LAYERS), model.TRAINABLE)
    assert model.TRAINABLE == ("fc",)
