#!/usr/bin/env python3
"""Run the memory-planned training step and the personalization service on
a TPU, through the entry points a user calls, and check what comes out.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the sharded LM train step on four chips

One process, no child processes.  With no arguments the phases are:

1. device check — a TPU or a non-zero exit (there is no CPU fallback);
2. JAX's persistent compile cache (:mod:`repro.runtime.compile_cache`);
3. planned training — vgg16 at batch 64 on 3x32x32 inputs, compiled with
   optimizer-state offload so the verified schedule swaps activations and
   streams optimizer state through ``pinned_host``; three steps of
   ``loss_and_grads`` + ``offloaded_update`` on every executor, each
   step's grads gated against the whole-graph ``jax.grad`` reference;
4. personalization serving — 8 users fine-tune resnet18_transfer over
   buckets (4, 8) for 2 rounds, phase-interleaved, as
   ``repro.launch.serve personalize`` drives it; then one interleaved
   wave over the live sessions whose grads are gated the same way.

``--chips 4`` runs only the sharded llama3.2-3b train step (``Trainer`` on
a (data=2, model=2) mesh at the published widths, depth cut so the
one-device comparison fits one chip) and the same steps on one device.

The gradient gate is the repo's (rtol 1e-4, atol 1e-5) and is held at
float32 matmul precision on both sides; the gap at the TPU's default
(bf16-pass) precision is printed beside it.  The last line of standard
output is ``{"ok": true, "device": {...}}``, printed only when every phase
passed.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from collections import Counter
from importlib import metadata
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

SEED = 0
GRAD_RTOL, GRAD_ATOL = 1e-4, 1e-5        # the repo's gradient gate
GATE_PRECISION = "float32"

TRAIN_MODEL, TRAIN_BATCH, TRAIN_STEPS = "vgg16", 64, 3
EXECUTORS = ("sim", "async", "jit_blocks")

SERVE_MODEL, SERVE_USERS, SERVE_BUCKETS, SERVE_ROUNDS = \
    "resnet18_transfer", 8, (4, 8), 2

SHARDED_ARCH, SHARDED_LAYERS, SHARDED_STEPS = "llama3.2-3b", 2, 3
SHARDED_SEQ, SHARDED_BATCH, SHARDED_LR = 512, 4, 3e-4
# bounds for the mesh run against the one-device run (bf16 compute, the
# reduction order differs across shards)
SHARDED_LOSS_RTOL, SHARDED_GNORM_RTOL = 2e-3, 1e-2
# in its first three steps (b1 0.9, b2 0.95) an AdamW update moves a weight
# by at most 1.001*lr (Cauchy-Schwarz over the moment sums), so two runs
# whose tiny grads differ in sign part by at most about 2*lr per step
SHARDED_PARAM_ATOL = 2 * 1.01 * SHARDED_LR * SHARDED_STEPS


class SmokeFailure(RuntimeError):
    """A phase produced a wrong or missing result."""


def log(msg: str) -> None:
    print(msg, flush=True)


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


class CompileCounter:
    """Counts executables JAX builds (one event per jit-cache miss)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self) -> None:
        import jax
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **kwargs) -> None:
        if event == self.EVENT:
            self.n += 1


# ---------------------------------------------------------------------------
# 1. device check
# ---------------------------------------------------------------------------

def device_check(n_chips: int):
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: JAX found no TPU (platform "
                         f"{dev.platform!r}); this check runs only on a TPU")
    if len(devices) < n_chips:
        raise SystemExit(f"chip_smoke: {n_chips} chips asked for, JAX sees "
                         f"{len(devices)}")
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = "not installed as a package"
    log(f"device: {dev.device_kind} x{len(devices)} (platform tpu) "
        f"jax={jax.__version__} libtpu={libtpu}")
    return dev


# ---------------------------------------------------------------------------
# 3. planned training
# ---------------------------------------------------------------------------

def grad_gap(grads, ref):
    """Largest ``|a-b| / (atol + rtol*|b|)`` over every gradient element
    (<= 1 passes the gate) and the largest absolute error."""
    import numpy as np
    check(set(grads) == set(ref),
          f"grad layers differ from the reference: {sorted(grads)} vs "
          f"{sorted(ref)}")
    gate = max_abs = 0.0
    for layer, entry in ref.items():
        check(set(grads[layer]) == set(entry), f"grad names differ: {layer}")
        for name, b in entry.items():
            a = np.asarray(grads[layer][name], np.float64)
            b = np.asarray(b, np.float64)
            check(a.shape == b.shape, f"grad shape {layer}/{name}: "
                                      f"{a.shape} vs {b.shape}")
            check(bool(np.isfinite(a).all()), f"non-finite grad {layer}/{name}")
            err = np.abs(a - b)
            gate = max(gate, float(np.max(err / (GRAD_ATOL
                                                 + GRAD_RTOL * np.abs(b)))))
            max_abs = max(max_abs, float(err.max()))
    return gate, max_abs


def train_steps(cp, graph, executor: str, steps: int, counter):
    """``steps`` planned training steps; one record per step."""
    import jax
    import numpy as np

    from repro.core.exec.layers import reference_loss_and_grads
    from repro.core.exec.store import SwapExecStats
    from repro.core.optim_offload import OptimRuntime, offloaded_update
    from repro.serve.buckets import dummy_batch

    params = cp.init_params(jax.random.PRNGKey(SEED))
    runtime = OptimRuntime(cp.optim_plan, graph)
    opt_stats = SwapExecStats()
    records = []
    for step in range(steps):
        x, y = dummy_batch(graph, cp.batch, seed=SEED + 1 + step)
        jax.block_until_ready((x, y))
        compiles = counter.n
        t0 = time.perf_counter()
        loss, grads, stats = cp.loss_and_grads(params, x, y,
                                               executor=executor)
        new_params = offloaded_update(runtime, params, grads, opt_stats)
        jax.block_until_ready((loss, grads, new_params))
        wall = time.perf_counter() - t0
        compiles = counter.n - compiles
        _, ref_grads = reference_loss_and_grads(graph, params, x, y)
        gate, max_abs = grad_gap(grads, ref_grads)
        records.append({
            "step": step + 1, "loss": float(loss), "wall_s": wall,
            "compiles": compiles, "gate": gate,
            "max_abs_err": max_abs, "stats": stats,
            "exec": dict(cp.report().get("exec") or {})})
        params = new_params
    return records, opt_stats


def phase_train(counter) -> None:
    import jax
    import numpy as np

    from repro.core import MemoryPlanConfig, compile_plan
    from repro.core.zoo import ZOO

    graph = ZOO[TRAIN_MODEL]()
    t0 = time.perf_counter()
    cp = compile_plan(graph, MemoryPlanConfig(
        optim_offload=True, min_idle_phases=3, min_bytes=1 << 12),
        batch=TRAIN_BATCH)
    counts = cp.lowered.counts()
    log(f"[train] {TRAIN_MODEL} batch {TRAIN_BATCH} input "
        f"{tuple(graph.input_shape)}: plan compiled in "
        f"{time.perf_counter() - t0:.3f} s, verified="
        f"{cp.verify_report is not None and cp.verify_report.ok}, "
        f"ops={counts}")
    check(cp.verify_report is not None and cp.verify_report.ok,
          "the compiled schedule failed verification")
    for op in ("swapout", "prefetch", "optprefetch"):
        check(counts.get(op, 0) > 0, f"the plan has no {op} ops")
    planned_ops = Counter(cp.lowered.ops)
    log(f"[train] planned activation peak {cp.peak_bytes} B, optimizer "
        f"working set {cp.optim_device_bytes} B, host pool "
        f"{cp.host_pool_bytes} B, DMA {cp.dma_bytes} B per step")

    # the gap at the chip's default matmul precision, for information:
    # fp32 dots and convs then run as bf16 passes on both sides
    for ex in EXECUTORS:
        rec = train_steps(cp, graph, ex, 1, counter)[0][0]
        log(f"[train] default precision, {ex}: grad gate "
            f"{rec['gate']:.4g} (<= 1 passes), max abs err "
            f"{rec['max_abs_err']:.4g}")

    with jax.default_matmul_precision(GATE_PRECISION):
        for ex in EXECUTORS:
            records, opt_stats = train_steps(cp, graph, ex, TRAIN_STEPS,
                                             counter)
            for r in records:
                s = r["stats"]
                log(f"[train] {ex} step {r['step']}: loss {r['loss']:.6f} "
                    f"grad gate {r['gate']:.4g} max abs err "
                    f"{r['max_abs_err']:.4g} wall {r['wall_s']:.4f} s "
                    f"compiles {r['compiles']} swapouts {s.swap_outs} "
                    f"prefetches {s.prefetches} late {s.late_swap_ins} "
                    f"dma {s.dma_bytes} B hbm high water "
                    f"{s.hbm_high_water} B")
                check(bool(np.isfinite(r["loss"])),
                      f"{ex} step {r['step']}: loss is not finite")
                check(r["gate"] <= 1.0,
                      f"{ex} step {r['step']}: grads differ from the "
                      f"reference beyond rtol {GRAD_RTOL}, atol {GRAD_ATOL}")
                check(Counter(s.replayed_ops) == planned_ops
                      and s.late_swap_ins == 0 and s.swap_outs > 0
                      and s.prefetches > 0,
                      f"{ex} step {r['step']}: the replay is not the plan")
            late = sum(r["compiles"] for r in records[1:])
            log(f"[train] {ex}: compiles in steps 2-{TRAIN_STEPS}: {late}; "
                f"optimizer lane {opt_stats.opt_prefetches} prefetches, "
                f"{opt_stats.opt_swap_outs} swap-outs, "
                f"{opt_stats.opt_dma_bytes} B")
            check(late == 0, f"{ex}: steps 2-{TRAIN_STEPS} compiled {late} "
                             f"programs")
            check(opt_stats.opt_prefetches > 0 and opt_stats.opt_swap_outs > 0,
                  f"{ex}: the optimizer lane moved nothing")
            if ex != "sim":
                rep = records[-1]["exec"]
                log(f"[train] {ex}: host memory kind "
                    f"{rep.get('host_memory_kind')}, fences "
                    f"{rep.get('fences')}, stalled "
                    f"{rep.get('stalled_fences')}, in-flight high water "
                    f"{rep.get('inflight_high_water')} B")
                check(rep.get("host_memory_kind") == "pinned_host",
                      f"{ex}: swaps did not go to pinned_host")
    stats = jax.devices()[0].memory_stats() or {}
    log(f"[train] device peak_bytes_in_use {stats.get('peak_bytes_in_use')} "
        f"B (whole process so far) vs planned activation peak "
        f"{cp.peak_bytes} B + optimizer working set "
        f"{cp.optim_device_bytes} B")


# ---------------------------------------------------------------------------
# 4. personalization serving
# ---------------------------------------------------------------------------

def phase_serve() -> None:
    import jax
    import numpy as np

    from repro.core import MemoryPlanConfig
    from repro.core.exec.layers import reference_loss_and_grads
    from repro.core.zoo import ZOO
    from repro.serve import PersonalizationService, SessionWork, StepScheduler
    from repro.serve.buckets import dummy_batch

    graph = ZOO[SERVE_MODEL]()
    config = MemoryPlanConfig()
    svc = PersonalizationService(
        graph, buckets=SERVE_BUCKETS, max_live_sessions=SERVE_USERS,
        config=config, interleave=True, bus_gbps=None, seed=SEED)
    t0 = time.perf_counter()
    svc.warmup()
    log(f"[serve] {SERVE_MODEL}: warmup of buckets {SERVE_BUCKETS} "
        f"{time.perf_counter() - t0:.3f} s, arena share "
        f"{svc.admission.arena_share_bytes} B per session")
    rng = np.random.default_rng(SEED)
    for rnd in range(SERVE_ROUNDS):
        reqs = []
        for u in range(SERVE_USERS):
            # odd users send short batches (padded up), even users fill
            # the largest bucket — the launcher's traffic
            n = int(rng.integers(1, SERVE_BUCKETS[0] + 1)) if u % 2 \
                else SERVE_BUCKETS[-1]
            x, y = dummy_batch(graph, n, seed=rnd * SERVE_USERS + u)
            reqs.append(svc.enqueue(f"u{u}", x, y))
        t0 = time.perf_counter()
        svc.drain()
        wall = time.perf_counter() - t0
        for u, req in enumerate(reqs):
            res = req.result
            check(res is not None and res.ok,
                  f"round {rnd} u{u}: {res and res.status} "
                  f"{res and res.reason}")
            check(bool(np.isfinite(res.loss)), f"round {rnd} u{u}: loss")
            check(res.peak_bytes <= res.arena_share_bytes,
                  f"round {rnd} u{u}: peak above its arena share")
        log(f"[serve] round {rnd + 1}: {len(reqs)} requests ok, drain "
            f"{wall:.4f} s, losses "
            f"{[round(r.result.loss, 4) for r in reqs]}")
    rep = svc.report()
    sched = rep["scheduler"]
    log(f"[serve] completed {rep['serve']['completed']}, plan cache "
        f"{rep['plan_cache']['hits']} hits / {rep['plan_cache']['misses']} "
        f"misses, verify errors {sched['verify_errors']}, deadlocks "
        f"{rep['serve']['deadlocks']}")
    check(rep["serve"]["completed"] == SERVE_USERS * SERVE_ROUNDS,
          "not every request completed")
    check(sched["verify_errors"] == 0, "cross-session verify errors")

    # one more interleaved wave over the live sessions, each at its
    # personalized params on a full bucket, gated against jax.grad
    works, inputs = [], {}
    for i, user in enumerate(sorted(svc.admission.live)):
        bucket = SERVE_BUCKETS[i % len(SERVE_BUCKETS)]
        qos = svc.admission.qos_of(user)
        share = svc.admission.share_for(qos)
        cp = svc.cache.get_or_compile(graph, config, bucket=bucket,
                                      arena_budget_bytes=share)
        params = svc.servable.merged_params(svc.servable.sessions[user])
        x, y = dummy_batch(graph, bucket, seed=1000 + i)
        inputs[user] = (params, x, y)
        works.append(SessionWork(
            user=user, arrival=i + 1, qos=qos,
            weight=svc.admission.qos_class(qos).weight,
            base_offset=svc.admission.base_offset(user), share_bytes=share,
            cp=cp, x=x, y=y, mask=None, params_fn=lambda p=params: p))
    wave = StepScheduler()
    worst = 0.0
    with jax.default_matmul_precision(GATE_PRECISION):
        outcomes = wave.run(works)
        check(len(outcomes) == len(works), "the wave lost a session")
        for oc in outcomes:
            check(oc.status == "ok", f"wave {oc.user}: {oc.status}")
            params, x, y = inputs[oc.user]
            _, ref = reference_loss_and_grads(graph, params, x, y)
            gate, _ = grad_gap(oc.grads, ref)
            worst = max(worst, gate)
            check(gate <= 1.0, f"wave {oc.user}: grads differ from the "
                               f"reference (gate {gate:.4g})")
    wrep = wave.report()
    log(f"[serve] wave of {len(outcomes)} sessions: worst grad gate "
        f"{worst:.4g} (<= 1 passes), verify errors "
        f"{wrep['verify_errors']}, host memory kind "
        f"{wave.engine.host_memory_kind}")
    check(wrep["verify_errors"] == 0, "wave verify errors")
    check(wave.engine.host_memory_kind == "pinned_host",
          "the shared engine does not swap to pinned_host")


# ---------------------------------------------------------------------------
# --chips 4: the sharded LM train step against one device
# ---------------------------------------------------------------------------

def sharded_config():
    from repro.configs import ARCHS
    return dataclasses.replace(ARCHS[SHARDED_ARCH], n_layers=SHARDED_LAYERS)


def sharded_run(cfg, mesh):
    """SHARDED_STEPS steps of the production Trainer on ``mesh``."""
    from repro.configs.base import ShapeConfig
    from repro.models.model import build_model
    from repro.optim import make_optimizer
    from repro.train.trainer import Trainer, TrainerConfig

    shape = ShapeConfig("chip_smoke", SHARDED_SEQ, SHARDED_BATCH, "train")
    trainer = Trainer(build_model(cfg), make_optimizer("adamw", lr=SHARDED_LR),
                      mesh, shape,
                      TrainerConfig(steps=SHARDED_STEPS, log_every=1,
                                    seed=SEED))
    t0 = time.perf_counter()
    out = trainer.run()
    return out, time.perf_counter() - t0


def phase_sharded(n_chips: int) -> None:
    import jax
    import numpy as np

    from repro.configs import ARCHS
    from repro.launch.mesh import make_mesh

    cfg = sharded_config()
    full = ARCHS[SHARDED_ARCH]
    log(f"[sharded] {cfg.name}: d_model {cfg.d_model}, heads "
        f"{cfg.n_heads}/{cfg.n_kv_heads}, d_ff {cfg.d_ff}, vocab "
        f"{cfg.vocab}; depth cut {full.n_layers} -> {cfg.n_layers} layers "
        f"so the one-device run fits one chip "
        f"({cfg.param_count() / 1e9:.3f} B params); batch {SHARDED_BATCH} "
        f"x {SHARDED_SEQ} tokens, {SHARDED_STEPS} AdamW steps")
    devices = jax.devices()
    data = 2
    model = n_chips // data

    one = make_mesh((1, 1), ("data", "model"), devices=devices[:1])
    ref, ref_wall = sharded_run(cfg, one)
    ref_hist = ref["history"]
    ref_params = jax.device_get(ref["params"])
    for leaf in jax.tree_util.tree_leaves((ref["params"],
                                           ref["opt_state"])):
        leaf.delete()
    del ref

    mesh = make_mesh((data, model), ("data", "model"),
                     devices=devices[:n_chips])
    out, wall = sharded_run(cfg, mesh)
    hist = out["history"]
    check(len(hist) == len(ref_hist) == SHARDED_STEPS, "missing steps")

    per_device = {d: 0 for d in mesh.devices.flat}
    total = 0
    for leaf in jax.tree_util.tree_leaves(out["params"]):
        total += leaf.nbytes
        for shard in leaf.addressable_shards:
            per_device[shard.device] += shard.data.nbytes
    log(f"[sharded] param bytes {total}; per device "
        f"{[per_device[d] for d in mesh.devices.flat]}")
    check(all(b > 0 for b in per_device.values()),
          "a device of the mesh holds no parameters")
    check(max(per_device.values()) < 0.75 * total,
          "parameters are not spread across the mesh")

    worst_loss = worst_gnorm = 0.0
    for a, b in zip(hist, ref_hist):
        dl = abs(a["loss"] - b["loss"]) / abs(b["loss"])
        dg = abs(a["grad_norm"] - b["grad_norm"]) / abs(b["grad_norm"])
        worst_loss, worst_gnorm = max(worst_loss, dl), max(worst_gnorm, dg)
        log(f"[sharded] step {a['step']}: loss {a['loss']:.6f} vs one "
            f"device {b['loss']:.6f} (rel {dl:.3g}); grad norm "
            f"{a['grad_norm']:.6f} vs {b['grad_norm']:.6f} (rel {dg:.3g}); "
            f"wall {a['time_s']:.4f} s vs {b['time_s']:.4f} s")
        check(bool(np.isfinite(a["loss"])), "non-finite loss on the mesh")
    max_dp = 0.0
    for a, b in zip(jax.tree_util.tree_leaves(out["params"]),
                    jax.tree_util.tree_leaves(ref_params)):
        max_dp = max(max_dp, float(np.max(np.abs(
            np.asarray(a, np.float32) - np.asarray(b, np.float32)))))
    bound = SHARDED_PARAM_ATOL
    log(f"[sharded] mesh {dict(mesh.shape)}: run {wall:.3f} s vs one "
        f"device {ref_wall:.3f} s (compiles included); max param diff "
        f"{max_dp:.3g} (bound {bound:.3g})")
    check(worst_loss <= SHARDED_LOSS_RTOL,
          f"losses differ from one device by {worst_loss:.3g}")
    check(worst_gnorm <= SHARDED_GNORM_RTOL,
          f"grad norms differ from one device by {worst_gnorm:.3g}")
    check(max_dp <= bound, f"params differ from one device by {max_dp:.3g}")


# ---------------------------------------------------------------------------

def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the sharded LM train step")
    args = ap.parse_args()

    import jax

    dev = device_check(args.chips)
    from repro.runtime.compile_cache import enable_compile_cache
    log(f"compile cache: {enable_compile_cache()}")
    counter = CompileCounter()
    t0 = time.perf_counter()
    if args.chips == 4:
        phase_sharded(args.chips)
    else:
        phase_train(counter)
        phase_serve()
    log(f"phases passed in {time.perf_counter() - t0:.1f} s, "
        f"{counter.n} programs compiled")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)


if __name__ == "__main__":
    main()
