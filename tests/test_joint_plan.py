"""Joint keep/recompute/offload planner (model-config path).

Covers: 3-way pricing under the hardware cost model, optimality against the
two single-knob plans (pure remat, offload-everything) over the arch
registry and a budget sweep, honest accounting (recompute FLOPs, DMA bytes,
budget-missing names preserved), the deprecated ``offload_dropped`` alias,
the fallback-save lowering warning, and the co-optimisation scan's
fixed-point invariant on every zoo model.
"""

import dataclasses

import pytest

from repro.configs import ARCHS
from repro.core.offload import make_schedule, offload_lowering
from repro.core.plan import MemoryPlanConfig, compile_plan
from repro.core.planner import plan_memory_swapped
from repro.core.remat_policy import (plan_checkpoint_policy,
                                     plan_joint_policy, plan_step_time_s,
                                     transformer_intermediates)
from repro.core.zoo import ZOO

# A hardware point where the eviction lanes genuinely compete for the big
# dense archs (recompute density ~d_model prefers FLOPs, ~d_ff prefers DMA).
HW = {"dma_gbps": 80.0, "device_tflops": 200.0}


def _intermediates(cfg, batch_tokens=2048):
    return transformer_intermediates(
        batch_tokens=batch_tokens, d_model=cfg.d_model,
        d_ff=cfg.moe_d_ff if cfg.is_moe else cfg.d_ff,
        n_q_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
        head_dim=cfg.head_dim, moe_experts_per_token=cfg.top_k)


def _cost(plan, inter):
    return plan_step_time_s(plan, inter, **HW)


# ---------------------------------------------------------------------------
# Optimality: the joint plan never loses to either single-knob plan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch", sorted(ARCHS))
@pytest.mark.parametrize("frac", (0.0, 0.25, 0.5, 0.75))
def test_joint_plan_never_worse_than_single_knob_plans(arch, frac):
    inter = _intermediates(ARCHS[arch])
    total = sum(i.bytes_per_layer for i in inter)
    budget = int(total * frac)
    joint = plan_joint_policy(inter, budget, offload=True, **HW)
    pure = plan_joint_policy(inter, budget, offload=False)
    with pytest.warns(DeprecationWarning):
        offall = plan_checkpoint_policy(inter, budget, offload_dropped=True)
    # estimated step-time cost, all three priced under the SAME honest model
    assert _cost(joint, inter) <= _cost(pure, inter) + 1e-15
    assert _cost(joint, inter) <= _cost(offall, inter) + 1e-15
    # keep-bytes never exceed the budget
    assert joint.saved_bytes_per_layer <= budget
    # the decision partition is total: budget-missing names are preserved,
    # split between the two eviction lanes, never erased
    assert (set(joint.saved) | set(joint.dropped) | set(joint.offloaded)
            == {i.name for i in inter})
    assert not set(joint.dropped) & set(joint.offloaded)
    assert not set(joint.saved) & (set(joint.dropped) | set(joint.offloaded))


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_joint_plan_honest_accounting(arch):
    inter = _intermediates(ARCHS[arch])
    total = sum(i.bytes_per_layer for i in inter)
    joint = plan_joint_policy(inter, total // 4, offload=True, **HW)
    by = {i.name: i for i in inter}
    assert joint.recompute_flops_per_layer == sum(
        by[n].recompute_flops for n in joint.dropped)
    assert joint.offload_dma_bytes_per_layer == sum(
        2 * by[n].bytes_per_layer for n in joint.offloaded)
    # the plan's own estimate equals the honest re-pricing (same model)
    assert joint.est_step_time_s_per_layer == pytest.approx(
        _cost(joint, inter))
    decisions = joint.decisions()
    assert set(decisions) == {i.name for i in inter}


# ---------------------------------------------------------------------------
# Acceptance: a mixed decision set strictly beats both legacy modes
# ---------------------------------------------------------------------------

def test_joint_plan_mixed_decisions_beat_both_legacy_modes():
    cfg = ARCHS["llama3.2-3b"]
    bt = 2048
    inter = _intermediates(cfg, bt)
    budget = 1 << 20   # tight: every intermediate must be evicted
    joint_cp = compile_plan(cfg, MemoryPlanConfig(
        remat=True, remat_budget_bytes=budget, offload=True, **HW),
        batch_tokens=bt)
    rp = joint_cp.remat_plan
    # genuinely mixed: some intermediates recomputed AND some offloaded
    assert rp.dropped and rp.offloaded
    # the DMA price is visible on the compiled model plan, not zeroed
    assert joint_cp.report()["dma_bytes"] > 0
    assert joint_cp.dma_bytes == \
        rp.offload_dma_bytes_per_layer * cfg.n_layers
    pure_cp = compile_plan(cfg, MemoryPlanConfig(
        remat=True, remat_budget_bytes=budget, offload=False),
        batch_tokens=bt)
    with pytest.warns(DeprecationWarning):
        offall_cp = compile_plan(cfg, MemoryPlanConfig(
            remat=True, remat_budget_bytes=budget, offload_dropped=True),
            batch_tokens=bt)
    cj = _cost(rp, inter)
    assert cj < _cost(pure_cp.remat_plan, inter)      # strictly below remat
    assert cj < _cost(offall_cp.remat_plan, inter)    # and offload-all


# ---------------------------------------------------------------------------
# Deprecated aliases keep their decision sets, with honest accounting
# ---------------------------------------------------------------------------

def test_free_dma_alias_offloads_everything_with_honest_dma():
    inter = _intermediates(ARCHS["llama3.2-3b"])
    with pytest.warns(DeprecationWarning):
        plan = plan_checkpoint_policy(inter, 0, offload_dropped=True)
    assert set(plan.offloaded) == {i.name for i in inter}
    assert plan.dropped == () and plan.recompute_flops_per_layer == 0.0
    assert plan.offload_dma_bytes_per_layer == \
        2 * sum(i.bytes_per_layer for i in inter)
    # DMA was priced as free when planning — exactly why the alias is
    # deprecated; plan_step_time_s re-prices it honestly
    assert plan.est_step_time_s_per_layer == 0.0
    assert _cost(plan, inter) > 0.0


def test_pure_remat_wrapper_is_joint_planner_with_offload_lane_off():
    inter = _intermediates(ARCHS["phi4-mini-3.8b"])
    total = sum(i.bytes_per_layer for i in inter)
    assert plan_checkpoint_policy(inter, total // 2) == \
        plan_joint_policy(inter, total // 2, offload=False)
    assert plan_checkpoint_policy(inter, None) == \
        plan_joint_policy(inter, None, offload=False)


def test_zero_bandwidth_disables_offload_lane():
    # dma_gbps=0 must mean "no DMA engine" (infinite price), not crash
    inter = _intermediates(ARCHS["llama3.2-3b"])
    plan = plan_joint_policy(inter, 0, offload=True, dma_gbps=0.0,
                             device_tflops=200.0)
    assert not plan.offloaded
    assert set(plan.dropped) == {i.name for i in inter}


def test_free_dma_alias_nonzero_budget_keeps_historical_greedy_fill():
    # the alias must reproduce the old greedy flops-per-byte keep set, not
    # the byte-maximising knapsack tiebreak (every value is zero under
    # free DMA, so the knapsack is degenerate there)
    from repro.core.remat_policy import Intermediate
    inter = [Intermediate("a", 6, 100.0), Intermediate("b", 5, 10.0),
             Intermediate("c", 5, 9.0)]
    with pytest.warns(DeprecationWarning):
        plan = plan_checkpoint_policy(inter, 10, offload_dropped=True)
    assert plan.saved == ("a",)            # densest first, then b/c don't fit
    assert set(plan.offloaded) == {"b", "c"}


def test_budgetless_offload_lane_warns_instead_of_silent_noop():
    # with no budget pressure the optimum keeps everything; the facade must
    # say so rather than let offload=True silently do nothing
    cfg = dataclasses.replace(ARCHS["llama3.2-3b"], offload=True)
    with pytest.warns(UserWarning, match="nothing will be offloaded"):
        cp = compile_plan(cfg, batch_tokens=2048)
    assert not cp.remat_plan.offloaded
    assert set(cp.remat_plan.saved) == \
        {"qkv", "attn_out", "mlp_hidden", "mlp_out"}


def test_model_config_hardware_knobs_flow_through_facade():
    cfg = dataclasses.replace(
        ARCHS["llama3.2-3b"], offload=True, remat_budget_bytes=1 << 20,
        dma_gbps=80.0, device_tflops=200.0)
    cp = compile_plan(cfg, batch_tokens=2048)
    assert cp.remat_plan.offloaded     # cfg knobs alone enable the lane
    # MemoryPlanConfig overrides cfg: near-zero bandwidth prices every
    # eviction down the recompute lane
    slow = compile_plan(cfg, MemoryPlanConfig(dma_gbps=1e-6),
                        batch_tokens=2048)
    assert not slow.remat_plan.offloaded and slow.remat_plan.dropped
    assert slow.report()["recompute_flops_per_layer"] > 0


# ---------------------------------------------------------------------------
# Offload lowering is native and reported
# ---------------------------------------------------------------------------

def test_offload_lowering_native_on_this_jax():
    assert offload_lowering() == "native"
    cp = compile_plan(ARCHS["llama3.2-3b"], MemoryPlanConfig(
        remat=True, remat_budget_bytes=1 << 20, offload=True, **HW),
        batch_tokens=2048)
    assert cp.report()["offload_lowering"] == "native"
    # keep-everything plans offload nothing, so no lowering key is reported
    full = compile_plan(ARCHS["llama3.2-3b"], MemoryPlanConfig(remat=True),
                        batch_tokens=2048)
    assert "offload_lowering" not in full.report()


# ---------------------------------------------------------------------------
# Co-optimisation scan fix: the fixed point still holds on every zoo model
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", sorted(ZOO))
def test_coopt_fixed_point_invariant_on_every_zoo_model(name):
    cp = compile_plan(
        ZOO[name](), MemoryPlanConfig(min_idle_phases=3, min_bytes=1 << 12),
        batch=8)
    # fixed point: no remaining data-moving swap is droppable — removing
    # any one of them must raise the packed peak.  In-place decisions are
    # exempt: they move no data (no host slot, no DMA), so the scan keeps
    # them for the planner freedom they preserve.
    for d in cp.schedule.decisions:
        if d.inplace:
            continue
        rest = tuple(o for o in cp.schedule.decisions if o.name != d.name)
        trial = plan_memory_swapped(cp.ordered, make_schedule(rest),
                                    planner=cp.config.planner)
        assert trial.arena_bytes > cp.peak_bytes, d.name
    # the exempt decisions really are free: zero bytes in every aggregate
    inplace = [d for d in cp.schedule.decisions if d.inplace]
    assert cp.schedule.dma_bytes == 2 * sum(
        d.nbytes for d in cp.schedule.decisions if not d.inplace)
    for d in inplace:
        assert d.name + "@host" not in cp.plan.host.placements
