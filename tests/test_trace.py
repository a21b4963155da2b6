"""In-program spans and the compile counter (repro.runtime.trace).

Spans are checked here without a profiler: ``jax.profiler.TraceAnnotation``
is replaced by a recorder that keeps each span's name, arguments and the
span it opened inside.  The profiler's own record of the same spans is
checked in ``tests/bench/test_bench_spans.py``.
"""

import re
import time
from collections import Counter
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import MemoryPlanConfig, compile_plan
from repro.core.optim_offload import OptimRuntime, offloaded_update
from repro.core.plan import Compute
from repro.core.zoo import ZOO
from repro.runtime import trace
from repro.serve import PersonalizationService, dummy_batch

SRC = Path(__file__).resolve().parents[1] / "src" / "repro"
OPT_CFG = MemoryPlanConfig(min_idle_phases=3, min_bytes=1 << 12,
                           optim_offload=True, optim_compress=True)


class Recorder:
    """Stands in for ``jax.profiler.TraceAnnotation``: records
    ``(name, args, parent name)`` of every span entered."""

    def __init__(self):
        self.spans = []
        self._stack = []

    def __call__(self, name, **args):
        rec = self

        class _Span:
            def __enter__(self):
                parent = rec._stack[-1] if rec._stack else None
                rec.spans.append((name, args, parent))
                rec._stack.append(name)

            def __exit__(self, *exc):
                rec._stack.pop()
                return False

        return _Span()

    def count(self, name):
        return sum(1 for n, _, _ in self.spans if n == name)


@pytest.fixture
def recorded(monkeypatch):
    rec = Recorder()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", rec)
    trace.enable()
    try:
        yield rec
    finally:
        trace.enable(False)


def _batch(g, n, seed):
    return dummy_batch(g, n, seed=seed)


def test_spans_off_build_no_annotation(monkeypatch):
    def boom(*args, **kwargs):
        raise AssertionError("TraceAnnotation built with tracing off")

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", boom)
    assert trace.span("exec.compute", layer="a") is trace.span("plan.compile")
    # a planned step and its optimizer update open no span either
    g = ZOO["lenet5"]()
    cp = compile_plan(g, OPT_CFG, batch=4)
    params = cp.init_params(jax.random.PRNGKey(0))
    _, grads, _ = cp.loss_and_grads(params, *_batch(g, 4, 1))
    offloaded_update(OptimRuntime(cp.optim_plan, g), params, grads)


def test_span_on_refuses_a_name_outside_the_registry(recorded):
    with pytest.raises(ValueError):
        trace.span("exec.unknown")
    with trace.span("plan.compile", batch=1):
        pass
    assert recorded.spans == [("plan.compile", {"batch": 1}, None)]


def test_every_span_the_package_opens_is_registered():
    opened = set()
    for path in SRC.rglob("*.py"):
        opened |= set(re.findall(r'\bspan\(\s*"([\w.]+)"', path.read_text()))
    assert opened == set(trace.SPANS)
    assert len(trace.SPANS) == len(set(trace.SPANS))


@pytest.mark.parametrize("executor", ["sim", "async"])
def test_replay_spans_match_the_replayed_ops(recorded, executor):
    g = ZOO["lenet5"]()
    cp = compile_plan(g, OPT_CFG, batch=4)
    params = cp.init_params(jax.random.PRNGKey(0))
    recorded.spans.clear()
    _, _, st = cp.loss_and_grads(params, *_batch(g, 4, 1),
                                 executor=executor)
    assert st.swap_outs > 0 and st.prefetches > 0
    computes = [op for op in st.replayed_ops if isinstance(op, Compute)]
    assert recorded.count("exec.step") == 1
    assert recorded.count("exec.compute") == len(computes)
    assert recorded.count("exec.swap_out") == st.swap_outs
    assert recorded.count("exec.swap_in") == st.prefetches
    assert recorded.count("exec.drain") == 1
    assert recorded.count("exec.fence") == st.fences
    assert recorded.count("exec.opt_fence") == st.opt_fences
    # op spans name their layer and nest in the step
    assert [(a["layer"], a["kind"]) for n, a, _ in recorded.spans
            if n == "exec.compute"] == [(op.layer, op.kind)
                                        for op in computes]
    for name, _, parent in recorded.spans:
        if name == "exec.step":
            assert parent is None
        elif name in ("exec.fence", "exec.opt_fence"):
            assert parent in ("exec.compute", "exec.drain")
        else:
            assert parent == "exec.step", name


def test_plan_compile_holds_plan_verify(recorded):
    compile_plan(ZOO["lenet5"](), OPT_CFG, batch=4)
    assert recorded.spans[0] == ("plan.compile", {"batch": 4}, None)
    assert ("plan.verify", {}, "plan.compile") in recorded.spans


def test_optimizer_lane_spans_once_per_slot(recorded):
    g = ZOO["lenet5"]()
    cp = compile_plan(g, OPT_CFG, batch=4)
    params = cp.init_params(jax.random.PRNGKey(0))
    _, grads, _ = cp.loss_and_grads(params, *_batch(g, 4, 1))
    rt = OptimRuntime(cp.optim_plan, g)
    recorded.spans.clear()
    for _ in range(2):
        params = offloaded_update(rt, params, grads)
    slots = [s.layer for s in sorted(cp.optim_plan.slots,
                                     key=lambda s: s.prefetch_eo)]
    assert len(slots) > 1
    updates = [a for n, a, p in recorded.spans if n == "optim.update"]
    assert updates == [{"step": 1}, {"step": 2}]
    for part in ("optim.decode", "optim.unpack", "optim.adamw",
                 "optim.pack", "optim.encode"):
        got = [(a["step"], a["layer"], p) for n, a, p in recorded.spans
               if n == part]
        assert got == [(step, layer, "optim.update") for step in (1, 2)
                       for layer in slots], part


def test_serving_spans_follow_requests(recorded):
    g = ZOO["lenet5"]()
    svc = PersonalizationService(g, buckets=(8,), max_live_sessions=2,
                                 config=MemoryPlanConfig(
                                     min_idle_phases=3, min_bytes=1 << 12))
    svc.warmup()
    recorded.spans.clear()
    users = ("a", "b", "a")
    for i, u in enumerate(users):
        svc.enqueue(u, *_batch(g, 8, 10 + i))
    results = svc.drain()
    assert all(r.ok for r in results)
    rep = svc.report()["scheduler"]
    by_name = Counter(n for n, _, _ in recorded.spans)
    assert by_name["serve.prepare"] == len(users)
    assert by_name["sched.open"] == len(users)
    assert by_name["sched.finish"] == len(users)
    assert by_name["serve.apply_update"] == len(users)
    assert by_name["sched.advance"] == rep["phase_advances"]
    # every replayed op nests in one session's phase advance, and spans
    # of one request share its user and arrival
    for name, args, parent in recorded.spans:
        if name in ("exec.compute", "exec.swap_out", "exec.swap_in"):
            assert parent == "sched.advance", name
        if name.startswith(("sched.", "serve.")):
            assert set(args) == {"user", "arrival"}
    arrivals = {(a["user"], a["arrival"]) for n, a, _ in recorded.spans
                if n == "sched.advance"}
    assert arrivals == {("a", 1), ("b", 2), ("a", 3)}


def test_compile_counter_counts_a_fresh_jit():
    counter = trace.compile_counter()
    assert trace.compile_counter() is counter
    x = np.ones((3, 7, 11), np.float32)
    programs, seconds = counter.programs, counter.seconds
    jax.block_until_ready(jax.jit(lambda a: jnp.sin(a) * 3.25 + a)(x))
    assert counter.programs == programs + 1
    assert counter.seconds > seconds
    assert counter.until(time.perf_counter()) == (counter.programs,
                                                  counter.seconds)


def test_compile_counter_reads_up_to_a_time():
    now = [0.0]
    c = trace.CompileCounter(clock=lambda: now[0])
    assert c.until(10.0) == (0, 0.0)
    now[0] = 1.0
    c.on_duration(c.TRACE, 0.5)
    c.on_duration(c.LOWER, 0.25)
    now[0] = 2.0
    c.on_duration(c.CACHE, 0.75)     # inside the backend step below
    c.on_duration(c.BACKEND, 1.0)
    now[0] = 3.0
    c.on_duration(c.BACKEND, 2.0)
    c.on_duration("/jax/some/other_event", 9.0)
    assert (c.programs, c.loads) == (2, 1)
    assert c.seconds == pytest.approx(3.75)
    assert c.cache_s == pytest.approx(0.75)
    assert c.until(0.5) == (0, 0.0)
    assert c.until(1.0) == (0, pytest.approx(0.75))
    assert c.until(2.5) == (1, pytest.approx(1.75))
    assert c.until(3.0) == (2, pytest.approx(3.75))
