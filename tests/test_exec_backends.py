"""The pluggable executor subsystem (repro.core.exec).

Backend equivalence (the paper's 1e-4 commit gate, per backend): grads
from SimulatedBackend AND AsyncDeviceBackend match whole-graph ``jax.grad``
on every zoo model, both replay the compiled op list verbatim, and the
measured host-pool high water respects the packed bound on both.  Plus:
the ExecutionSchedule edge-case unit tests, the transfer-engine seam, and
the warn-once-per-call-site deprecation shims.
"""

import dataclasses
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.exec import (AsyncDeviceBackend, SimulatedBackend,
                             SwapExecStats, get_backend)
from repro.core.exec.layers import reference_loss_and_grads
from repro.core.exec.store import DeviceStreamEngine, SyncHostEngine
from repro.core.plan import (Compute, ExecutionSchedule, Free,
                             MemoryPlanConfig, Prefetch, SwapOut,
                             compile_plan, lower_schedule)
from repro.core.zoo import ZOO

EXEC_CFG = MemoryPlanConfig(min_idle_phases=3, min_bytes=1 << 12)

# CPU-heavy archs get the slow marker so the quick gate stays quick; the
# full suite still covers every zoo model on both backends.
_HEAVY = {"vgg16", "resnet18"}
ZOO_CASES = [
    pytest.param(name, marks=pytest.mark.slow) if name in _HEAVY
    else name
    for name in sorted(ZOO)
]


def _shrink(graph):
    for l in graph.layers:
        if l.attrs.get("in_features") == 150528:
            l.attrs["in_features"] = 96
    if graph.input_shape == (150528,):
        object.__setattr__(graph, "input_shape", (96,))
    from repro.core.graph import infer_shapes
    infer_shapes(graph)
    return graph


def _batch_for(g, batch=2):
    kx, ky = jax.random.split(jax.random.PRNGKey(1))
    if any(l.kind == "embedding" for l in g.layers):
        x = jax.random.randint(kx, (batch,) + tuple(g.input_shape), 0, 50)
    else:
        x = jax.random.normal(kx, (batch,) + tuple(g.input_shape))
    y = jax.random.normal(ky, (batch,) + tuple(g.label_shape))
    if g.layers[-1].kind == "loss_ce":
        y = jax.nn.one_hot(jnp.argmax(y, -1), y.shape[-1])
    return x, y


def _tree_allclose(a, b, rtol=1e-4, atol=1e-5):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=rtol, atol=atol)


# ---------------------------------------------------------------------------
# Backend equivalence over the whole zoo
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", ZOO_CASES)
def test_backends_match_jax_grad_on_zoo(name):
    """Both backends replay the same compiled plan to jax.grad-identical
    grads, verbatim op replay, and in-bound host-pool high water."""
    g = _shrink(ZOO[name]())
    batch = 2
    cp = compile_plan(g, EXEC_CFG, batch=batch)
    params = cp.init_params(jax.random.PRNGKey(0))
    x, y = _batch_for(g, batch)
    loss_r, grads_r = reference_loss_and_grads(g, params, x, y)

    results = {}
    for executor in ("sim", "async"):
        loss, grads, stats = cp.loss_and_grads(params, x, y,
                                               executor=executor)
        np.testing.assert_allclose(float(loss), float(loss_r), rtol=1e-5)
        _tree_allclose(grads, grads_r)
        assert stats.backend == executor
        assert stats.replayed_ops == cp.lowered.ops, executor
        assert stats.late_swap_ins == 0
        assert stats.host_high_water <= cp.host_pool_bytes
        if stats.planned_peak is not None:
            assert stats.hbm_high_water <= stats.planned_peak
        results[executor] = stats

    # the two backends executed the same schedule: identical transfer
    # accounting, bit for bit
    sim, asy = results["sim"], results["async"]
    for field in ("swap_outs", "prefetches", "dma_bytes", "hbm_high_water",
                  "host_high_water", "peak_inflight_prefetch"):
        assert getattr(sim, field) == getattr(asy, field), field


def test_async_overlap_report_vs_planned_inflight():
    g = ZOO["lenet5"]()
    cp = compile_plan(g, dataclasses.replace(EXEC_CFG, executor="async"),
                      batch=16)
    assert cp.schedule.decisions, "needs a plan with real transfers"
    params = cp.init_params(jax.random.PRNGKey(0))
    x, y = _batch_for(g, 16)
    _, _, stats = cp.loss_and_grads(params, x, y)
    assert stats.backend == "async"
    assert stats.fences == stats.prefetches > 0
    assert stats.achieved_overlap is not None
    assert 0.0 <= stats.achieved_overlap <= 1.0
    # the stream never held more in flight than the plan budgeted
    assert 0 < stats.inflight_high_water \
        <= cp.schedule.peak_inflight_prefetch
    ex = cp.report()["exec"]
    assert ex["backend"] == "async"
    assert ex["achieved_overlap"] == stats.achieved_overlap
    assert ex["inflight_high_water"] == stats.inflight_high_water
    assert ex["planned_peak_inflight_prefetch"] \
        == cp.schedule.peak_inflight_prefetch
    assert ex["inflight_vs_planned"] <= 1.0


def test_sim_backend_stats_bit_for_bit_default():
    """The default path is the simulated backend and its stats carry the
    defaulted async fields — old consumers see unchanged values."""
    g = ZOO["lenet5"]()
    cp = compile_plan(g, EXEC_CFG, batch=8)
    assert cp.config.executor == "sim"
    params = cp.init_params(jax.random.PRNGKey(0))
    x, y = _batch_for(g, 8)
    _, _, stats = cp.loss_and_grads(params, x, y)
    assert stats.backend == "sim"
    assert stats.inflight_high_water == 0
    assert stats.fences == stats.stalled_fences == 0
    assert stats.achieved_overlap is None
    assert cp.report()["exec"]["backend"] == "sim"


# ---------------------------------------------------------------------------
# Backend registry / selection plumbing
# ---------------------------------------------------------------------------

def test_get_backend_registry_and_errors():
    assert get_backend(None).name == "sim"
    assert isinstance(get_backend("sim"), SimulatedBackend)
    assert isinstance(get_backend("async"), AsyncDeviceBackend)
    custom = AsyncDeviceBackend()
    assert get_backend(custom) is custom
    with pytest.raises(ValueError, match="unknown executor backend"):
        get_backend("cuda-graphs")
    with pytest.raises(TypeError, match="ExecutorBackend"):
        get_backend(42)


def test_unknown_executor_fails_at_compile_time():
    with pytest.raises(ValueError, match="unknown executor backend"):
        compile_plan(ZOO["lenet5"](),
                     MemoryPlanConfig(executor="asycn"), batch=4)


def test_backend_report_requires_a_run():
    with pytest.raises(RuntimeError, match="run"):
        SimulatedBackend().report()


def test_engines_expose_the_transfer_seam():
    """The store's engine seam: sync engine moves bytes immediately, the
    device-stream engine tracks in-flight transfers until fenced."""
    sync = SyncHostEngine()
    a = jnp.arange(16.0)
    host = sync.swap_out("X:t", {"t": a}, a.nbytes)
    assert isinstance(host["t"], np.ndarray)
    back = sync.swap_in("X:t", host, a.nbytes)
    np.testing.assert_array_equal(np.asarray(back["t"]), np.asarray(a))

    eng = DeviceStreamEngine()
    stats = SwapExecStats()
    h = eng.swap_out("X:t", {"t": jnp.arange(16.0)}, 64)
    dev = eng.swap_in("X:t", h, 64)
    assert eng.inflight_bytes == 64
    assert eng.inflight_high_water == 64
    eng.fence("X:t", stats)
    assert eng.inflight_bytes == 0
    assert eng.fences == 1
    eng.fence("X:t", stats)       # double fence is a no-op
    assert eng.fences == 1
    np.testing.assert_array_equal(np.asarray(dev["t"]), np.arange(16.0))


def test_device_stream_swaps_to_pinned_host():
    """A swap-out really leaves device memory, and the prefetch brings the
    bytes back into it."""
    eng = DeviceStreamEngine()
    assert eng.host_memory_kind == "pinned_host"
    a = jnp.arange(32.0)
    h = eng.swap_out("X:t", {"t": a}, a.nbytes)
    assert h["t"].sharding.memory_kind == "pinned_host"
    back = eng.swap_in("X:t", h, a.nbytes)
    eng.fence("X:t", SwapExecStats())
    assert back["t"].sharding.memory_kind == "device"
    np.testing.assert_array_equal(np.asarray(back["t"]), np.arange(32.0))


def test_device_without_host_memory_has_no_swap_engine():
    """No pinned_host space means no host tier: the engine refuses rather
    than build a "host" sharding that is really device memory."""
    class DeviceOnly:
        def addressable_memories(self):
            return [jax.devices()[0].default_memory()]

    with pytest.raises(RuntimeError, match="pinned_host"):
        DeviceStreamEngine(DeviceOnly())


# ---------------------------------------------------------------------------
# ExecutionSchedule.counts()/transfers() edge cases (direct unit tests)
# ---------------------------------------------------------------------------

def test_empty_schedule_counts_and_transfers():
    empty = ExecutionSchedule(ops=())
    assert empty.counts() == {}
    assert empty.transfers() == ()


def test_counts_and_transfers_on_handmade_ops():
    ops = (
        Prefetch(eo=2, tensor="X:a", nbytes=8, device_offset=0,
                 host_offset=0, read_eo=3),
        Compute(eo=2, layer="a", kind="F"),
        SwapOut(eo=2, tensor="X:b", nbytes=16, device_offset=8,
                host_offset=8),
        Free(eo=4, tensor="X:a", nbytes=8, device_offset=0),
    )
    sched = ExecutionSchedule(ops=ops)
    assert sched.counts() == {"prefetch": 1, "compute": 1, "swapout": 1,
                              "free": 1}
    # transfers: DMA ops only, in issue order
    assert sched.transfers() == (ops[0], ops[2])


def test_zero_swap_plan_lowers_to_no_transfers():
    # min_bytes too large for anything to qualify: compute + free only
    cp = compile_plan(ZOO["lenet5"](),
                      MemoryPlanConfig(min_bytes=1 << 40), batch=4)
    assert not cp.schedule.decisions
    assert cp.lowered.transfers() == ()
    counts = cp.lowered.counts()
    assert set(counts) == {"compute", "free"}
    assert counts["compute"] == len(cp.ordered.phase_schedule())


def test_inplace_prefetch_only_plan_lowers_to_no_transfers():
    """A schedule whose every decision is an in-place prefetch moves no
    bytes: transfers() is empty though decisions exist."""
    from repro.core.execution_order import compute_execution_order
    from repro.core.offload import OffloadDecision, make_schedule

    g = ZOO["lenet5"]()
    ordered = compute_execution_order(g, 4)
    name = next(t.name for t in ordered.planned_tensors()
                if t.name.startswith("X:") and len(t.exec_orders) >= 2)
    t = ordered.tensors[name]
    write, read = t.largest_gap()
    d = OffloadDecision(name=name, nbytes=t.nbytes, write_eo=write,
                        read_eo=read, prefetch_at_eo=read - 1, inplace=True)
    sched = make_schedule((d,))
    assert sched.decisions and all(x.inplace for x in sched.decisions)
    assert sched.dma_bytes == 0 and sched.hbm_bytes_saved == 0
    lowered = lower_schedule(ordered, sched)
    assert lowered.transfers() == ()
    assert set(lowered.counts()) == {"compute", "free"}


# ---------------------------------------------------------------------------
# Deprecation shims: warn once per call site, still assertable
# ---------------------------------------------------------------------------

def test_warn_once_dedupes_per_call_site_under_default_filters():
    from repro.core import deprecation

    deprecation.reset_seen_call_sites()
    try:
        with warnings.catch_warnings(record=True) as rec:
            # "default" action: our helper's dedup is in charge
            warnings.simplefilter("default")
            for _ in range(3):
                deprecation.warn_once("shim is deprecated (dedup test)")
        assert len(rec) == 1
        assert issubclass(rec[0].category, DeprecationWarning)
    finally:
        deprecation.reset_seen_call_sites()


def test_warn_once_stays_alive_under_pytest_warns():
    from repro.core import deprecation

    # pytest.warns installs an "always" filter: every invocation must warn,
    # even from one call site, so warning assertions (and parametrized
    # re-runs of the same site) keep working
    for _ in range(2):
        with pytest.warns(DeprecationWarning, match="alive test"):
            deprecation.warn_once("shim is deprecated (alive test)")


def test_step_bundle_remat_plan_shim_warns():
    from repro.core.remat_policy import RematPlan
    from repro.train.step import StepBundle

    bundle = StepBundle(fn=None, in_shardings=None, out_shardings=None,
                        donate_argnums=(), abstract_args=(), act_rules={},
                        mesh=None, memory_plan=None)
    with pytest.warns(DeprecationWarning, match="StepBundle.remat_plan"):
        assert bundle.remat_plan is None
    assert RematPlan is not None


def test_offload_dropped_shim_still_warns():
    from repro.configs import ARCHS

    with pytest.warns(DeprecationWarning, match="offload_dropped"):
        cp = compile_plan(
            ARCHS["llama3.2-3b"],
            MemoryPlanConfig(remat=True, remat_budget_bytes=1 << 20,
                             offload_dropped=True),
            batch_tokens=1024)
    assert cp.remat_plan is not None


def test_core_free_function_shim_still_warns():
    import repro.core as core

    with pytest.warns(DeprecationWarning, match="plan_offload"):
        assert core.plan_offload is not None
