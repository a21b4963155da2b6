"""The program's spans in a profiler trace: self time, coverage, idle time
by innermost span (``chipbench.spantrace``), the profiler's record of a
planned replay, and ``span_report.py`` at a small size on the CPU."""

import benchutil  # noqa: F401  (puts the benchmark on sys.path)
import jax
import pytest
from chipbench import devtrace, spantrace

from repro.core import MemoryPlanConfig, compile_plan
from repro.core.plan import Compute
from repro.core.zoo import ZOO
from repro.runtime import trace
from repro.serve import dummy_batch

DEV0, HOST = "/device:TPU:0", "/host:CPU"


def ev(plane, name, start, dur, line=None):
    return {"plane": plane, "line": line or ("XLA Ops" if "device" in plane
                                             else "python"),
            "name": name, "start_ns": float(start), "dur_ns": float(dur)}


# a window [0, 1000): loss_and_grads [0, 600) holds exec.step [50, 550),
# which holds exec.compute [100, 200) (with exec.fence [150, 180) in it)
# and exec.swap_out [300, 450); optim_update [600, 1000) holds
# optim.decode [650, 700); the device runs [0, 100) and [200, 300)
NESTED = [
    ev(HOST, devtrace.WINDOW, 0, 1000),
    ev(HOST, "loss_and_grads", 0, 600),
    ev(HOST, "exec.step", 50, 500),
    ev(HOST, "exec.compute", 100, 100),
    ev(HOST, "exec.fence", 150, 30),
    ev(HOST, "exec.swap_out", 300, 150),
    ev(HOST, "optim_update", 600, 400),
    ev(HOST, "optim.decode", 650, 50),
    ev(DEV0, "fusion.1", 0, 100),
    ev(DEV0, "fusion.2", 200, 100),
]
PROGRAM = ("exec.step", "exec.compute", "exec.fence", "exec.swap_out",
           "optim.decode")


def test_self_time_takes_out_direct_children():
    s = spantrace.self_times(NESTED, PROGRAM + ("loss_and_grads",
                                                "optim_update"))
    assert s["loss_and_grads"]["self_s"] == pytest.approx(100e-9)
    assert s["exec.step"]["self_s"] == pytest.approx(250e-9)
    assert s["exec.compute"]["self_s"] == pytest.approx(70e-9)
    assert s["exec.compute"]["total_s"] == pytest.approx(100e-9)
    assert s["exec.fence"]["self_s"] == pytest.approx(30e-9)
    assert s["exec.swap_out"]["self_s"] == pytest.approx(150e-9)
    assert s["optim_update"]["self_s"] == pytest.approx(350e-9)
    assert {k: v["count"] for k, v in s.items()} == dict.fromkeys(s, 1)
    # a span that starts outside the window is not counted
    late = NESTED + [ev(HOST, "exec.compute", 1200, 10)]
    assert spantrace.self_times(late, PROGRAM)["exec.compute"]["count"] == 1


def test_self_time_on_two_threads_and_a_repeated_name():
    events = [ev(HOST, "exec.compute", 0, 100, line="a"),
              ev(HOST, "exec.compute", 20, 10, line="b"),
              ev(HOST, "exec.compute", 40, 10, line="a")]
    s = spantrace.self_times(events, ("exec.compute",))["exec.compute"]
    assert s["count"] == 3
    assert s["total_s"] == pytest.approx(120e-9)
    assert s["self_s"] == pytest.approx(110e-9)


def test_coverage_of_the_outer_spans():
    assert spantrace.coverage(NESTED, "loss_and_grads", PROGRAM) \
        == pytest.approx(500 / 600)
    assert spantrace.coverage(NESTED, "optim_update", PROGRAM) \
        == pytest.approx(50 / 400)
    assert spantrace.coverage(NESTED, "drain", PROGRAM) is None


def test_idle_goes_to_the_innermost_open_span():
    idle = dict(spantrace.idle_by_innermost(
        NESTED, PROGRAM + ("loss_and_grads", "optim_update")))
    # idle [100, 200): exec.compute 70, exec.fence 30; [300, 1000):
    # exec.swap_out 150, exec.step 100, loss_and_grads 50, optim.decode
    # 50, optim_update 350
    assert idle == pytest.approx({
        "exec.compute": 70e-9, "exec.fence": 30e-9,
        "exec.swap_out": 150e-9, "exec.step": 100e-9,
        "loss_and_grads": 50e-9, "optim.decode": 50e-9,
        "optim_update": 350e-9})
    outside = dict(spantrace.idle_by_innermost(NESTED, PROGRAM))
    assert outside[devtrace.OUTSIDE] == pytest.approx(400e-9)
    assert spantrace.idle_by_innermost(NESTED[1:], PROGRAM) == []


def test_benchmark_idle_rule_is_unchanged_beside_program_spans():
    bench_only = [e for e in NESTED if e["name"] not in PROGRAM]
    before = dict(devtrace.reduce_events(bench_only).idle_gaps)
    beside = dict(devtrace.reduce_events(NESTED).idle_gaps)
    for name in ("loss_and_grads", "optim_update"):
        assert beside[name] == before[name]
    assert set(beside) - set(before) == set(PROGRAM)


def test_replay_under_the_profiler(tmp_path):
    """The profiler's host plane holds one span per replayed op, all inside
    the step's span, and as many transfer spans as the executor counted."""
    g = ZOO["lenet5"]()
    cp = compile_plan(g, MemoryPlanConfig(min_idle_phases=3,
                                          min_bytes=1 << 12), batch=4)
    params = cp.init_params(jax.random.PRNGKey(0))
    x, y = dummy_batch(g, 4, seed=1)
    jax.block_until_ready(cp.loss_and_grads(params, x, y)[0])
    trace.enable()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation(devtrace.WINDOW):
            loss, _, st = cp.loss_and_grads(params, x, y)
            jax.block_until_ready(loss)
    finally:
        jax.profiler.stop_trace()
        trace.enable(False)
    events = devtrace.load_events(str(tmp_path), trace.SPANS)
    s = spantrace.self_times(events, trace.SPANS)
    computes = sum(isinstance(op, Compute) for op in st.replayed_ops)
    assert st.swap_outs > 0 and st.prefetches > 0
    assert s["exec.step"]["count"] == 1
    assert s["exec.compute"]["count"] == computes
    assert s["exec.swap_out"]["count"] == st.swap_outs
    assert s["exec.swap_in"]["count"] == st.prefetches
    step = next(e for e in events if e["name"] == "exec.step")
    a, b = step["start_ns"], step["start_ns"] + step["dur_ns"]
    for e in events:
        if e["name"].startswith("exec.") and e is not step:
            assert a <= e["start_ns"] and e["start_ns"] + e["dur_ns"] <= b
    assert spantrace.coverage(events, "exec.step",
                              ("exec.compute",)) > 0.0


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    return benchutil.small_bench(tmp_path_factory.mktemp("spans"))


def test_span_report_training_at_a_small_size(bench, tmp_path):
    import span_report
    cell = benchutil.cell(benchutil.TRAIN, bench)
    out = span_report.train_report(cell, 5, 1, tmp_path / "trace")
    w = out["window"]
    stats = out["stats_per_step"]
    per = {k: v["count"] for k, v in w["per_unit"].items()}
    assert per["exec.compute"] == stats["compute"]
    assert per["exec.swap_out"] == stats["swap_outs"]
    assert per["exec.swap_in"] == stats["prefetches"]
    assert per["optim.decode"] == per["optim.encode"] \
        == stats["opt_prefetches"] == stats["opt_swap_outs"]
    assert per["loss_and_grads"] == per["optim_update"] == 1
    for key in ("exec_compute_ms", "swap_transfer_ms", "optim_codec_ms"):
        assert w[key] > 0
    for share in w["coverage"].values():
        assert 0.0 < share <= 1.0
    # the CPU has no device plane: nothing is idle there
    assert w["idle_share"] is None and w["idle_by_innermost"] == []
    assert out["spans_off"]["steps"] == out["spans_on"]["steps"] == 2
    assert trace.span("exec.step") is trace.span("plan.compile")


def test_span_report_serving_at_a_small_size(bench, tmp_path):
    import span_report
    cell = benchutil.cell(benchutil.SERVE, bench)
    out = span_report.serve_report(cell, 5, 1, tmp_path / "trace")
    w = out["window"]
    per = {k: v["count"] for k, v in w["per_unit"].items()}
    users = cell.traffic["users"]
    assert per["drain"] == 1
    for name in ("serve.prepare", "sched.open", "sched.finish",
                 "serve.apply_update"):
        assert per[name] == users, name
    assert per["sched.advance"] >= per["exec.compute"] > 0
    assert 0.0 < w["coverage"]["drain"] <= 1.0
    assert trace.span("exec.step") is trace.span("plan.compile")
