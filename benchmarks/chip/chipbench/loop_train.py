"""Closed-loop training steps through a compiled memory plan.

The traffic file gives how many distinct batches set-up makes on the device
(``batches``), how many first steps the reference follows (``check_steps``)
and the bounds of the warm-up (``warm_min``, ``warm_max``).  One object —
the compiled plan, its optimizer runtime and the params — is built from the
seed, driven through the check steps and the warm-up, and handed to the
window; every step is ``CompiledMemoryPlan.loss_and_grads`` followed by
``offloaded_update``, on batches cycled in order.

``correct`` compares the check steps with the plain reference run from the
same weights and batches once the window has closed: each step's loss, the
first step's gradients (norms and element gaps, worst leaf), and the change
of every moving leaf over the check steps.
"""

from __future__ import annotations

import contextlib
import gc
import shutil
import time
from typing import Any, Dict, List, Optional

import numpy as np

from chipbench import checks, devtrace, flops
from chipbench import reference as ref
from chipbench.harness import BenchError, Record, log, settle, timed_window

SPANS = ("loss_and_grads", "optim_update")


class Program:
    """The system under test, built as the configuration states it."""

    def __init__(self, cfg: dict, model) -> None:
        from repro.core import MemoryPlanConfig, compile_plan
        from repro.core.zoo import ZOO

        self.cfg = cfg
        self.model = model
        self.graph = ZOO[cfg["graph"]]()
        self.shapes = ref.param_shapes(model.LAYERS)
        check_layout(self.graph, self.shapes, model.TRAINABLE)
        t = time.perf_counter()
        self.cp = compile_plan(self.graph, MemoryPlanConfig(**cfg["plan"]),
                               batch=cfg["batch"])
        self.plan_compile_s = time.perf_counter() - t

    def runtime(self):
        from repro.core.optim_offload import OptimRuntime
        o = self.cfg["optimizer"]
        return OptimRuntime(self.cp.optim_plan, self.graph, lr=o["lr"],
                            b1=o["b1"], b2=o["b2"], eps=o["eps"],
                            weight_decay=o["weight_decay"])

    def loss_and_grads(self, params, x, y):
        return self.cp.loss_and_grads(params, x, y)

    def update(self, runtime, params, grads):
        from repro.core.optim_offload import offloaded_update
        return offloaded_update(runtime, params, grads)


def check_layout(graph, shapes, trainable) -> None:
    """The program's weights are the reference's, leaf for leaf."""
    prog = {l.name: {k: tuple(v) for k, v in l.weight_shapes().items()}
            for l in graph.layers if l.weight_shapes()}
    if prog != {k: {n: tuple(s) for n, s in v.items()}
                for k, v in shapes.items()}:
        raise BenchError(f"the program's weights {prog} are not the "
                         f"reference's {shapes}")
    prog_trainable = {l.name for l in graph.layers
                      if l.trainable and l.weight_shapes()}
    if prog_trainable != set(trainable):
        raise BenchError(f"the program trains {sorted(prog_trainable)}, the "
                         f"reference {sorted(trainable)}")


def make_batches(cfg: dict, traffic: dict, seed: int):
    """``traffic["batches"]`` distinct batches of ``cfg["batch"]`` rows."""
    import jax
    shape = (traffic["batches"], cfg["batch"]) + tuple(cfg["input_shape"])
    xs, ys = ref.make_images(seed, 0, shape, cfg["classes"])
    out = [(xs[i], ys[i]) for i in range(shape[0])]
    jax.block_until_ready(out)
    xs.delete()
    ys.delete()
    return out


def host(tree):
    import jax
    return jax.tree_util.tree_map(np.asarray, jax.device_get(tree))


def readings(losses, grads1, params0, params_n) -> Dict[str, Any]:
    """What the numbers are computed from (host arrays)."""
    delta = {k: {n: np.asarray(params_n[k][n], np.float64)
                 - np.asarray(params0[k][n], np.float64) for n in entry}
             for k, entry in params_n.items()}
    return {"losses": [float(v) for v in losses], "grads1": grads1,
            "delta": delta}


def numbers(prog: dict, refr: dict) -> Dict[str, float]:
    """The numbers compared with the configuration's limits."""
    out = {"loss_rel": max(checks.rel_gap(a, b)
                           for a, b in zip(prog["losses"], refr["losses"]))}
    if len(prog["losses"]) != len(refr["losses"]):
        out["loss_rel"] = float("inf")
    ref_gn = checks.norms(refr["grads1"])
    out["grad_norm_gap"] = checks.worst_norm_gap(
        checks.norms(prog["grads1"]), ref_gn)
    out["grad_rel_err"] = checks.worst_rel_err(prog["grads1"],
                                               refr["grads1"])
    out["update_norm_gap"] = checks.worst_norm_gap(
        checks.norms(prog["delta"]), checks.norms(refr["delta"]),
        keys=checks.moving_leaves(ref_gn))
    return out


def program_check_steps(prog: Program, runtime, params, batches, n: int):
    """The first ``n`` steps through the timed path; returns the params
    after them, the readings, and each step's wall time."""
    import jax
    p0 = host(params)
    losses, grads1, times = [], None, []
    for i in range(n):
        t = time.perf_counter()
        loss, grads, _ = prog.loss_and_grads(params, *batches[i])
        params = prog.update(runtime, params, grads)
        jax.block_until_ready((loss, grads, params))
        times.append(time.perf_counter() - t)
        losses.append(float(loss))
        if i == 0:
            grads1 = host(grads)
    return params, readings(losses, grads1, p0, host(params)), times


def reference_check_steps(cfg: dict, model, params0, batches, n: int,
                          precision: str = "highest",
                          fault: Optional[str] = None) -> dict:
    """The plain reference over the same first ``n`` steps.  ``fault``
    plants a fault in it, for the readings a fault gives: ``half_batch``
    (the mean over the first half of the rows) or ``label`` (row 0's label
    moved to the next class)."""
    import jax
    import jax.numpy as jnp

    o = cfg["optimizer"]
    vg = _reference_grad(model, precision)
    params = jax.tree_util.tree_map(jnp.asarray, params0)
    opt = ref.Int8AdamW(params, o["lr"], o["b1"], o["b2"], o["eps"],
                        o["weight_decay"])
    losses, grads1 = [], None
    for i in range(n):
        x, y = batches[i]
        if fault == "half_batch":
            x, y = x[: x.shape[0] // 2], y[: y.shape[0] // 2]
        elif fault == "label":
            y = y.at[0].set(jnp.roll(y[0], 1))
        loss, grads = vg(params, x, y)
        losses.append(float(loss))
        if i == 0:
            grads1 = host(grads)
        params = opt.update(params, grads)
    return readings(losses, grads1, host(params0), host(params))


# the jitted reference of each (configuration, precision), built once per
# process so that runs sharing a process share its programs
_REFERENCE_GRADS: Dict[tuple, Any] = {}


def _reference_grad(model, precision: str):
    import jax
    key = (model, precision)
    if key not in _REFERENCE_GRADS:
        ops = ref.Ops(precision)

        def loss_fn(p, x, y):
            return ref.cross_entropy(model.forward(p, x, ops, ref), y)

        _REFERENCE_GRADS[key] = jax.jit(jax.value_and_grad(loss_fn))
    return _REFERENCE_GRADS[key]


def run(ctx) -> Record:
    import jax

    cell = ctx.cell
    cfg, traffic = cell.config, cell.traffic
    rec = Record()
    n_check = int(traffic["check_steps"])
    with jax.default_matmul_precision(cfg["matmul_precision"]):
        prog = Program(cfg, cell.model)
        rec.counters["plan_compile_s"] = prog.plan_compile_s
        rec.counters["planned_peak_mb"] = (
            prog.cp.peak_bytes + prog.cp.optim_device_bytes) / 1e6
        params = ref.make_params(prog.shapes, ctx.seed)
        batches = make_batches(cfg, traffic, ctx.seed)
        runtime = prog.runtime()
        c0 = ctx.compiles.total
        params, prog_read, times = program_check_steps(
            prog, runtime, params, batches, n_check)
        log(f"check steps: losses {prog_read['losses']}, walls "
            f"{[round(t, 4) for t in times]} s, "
            f"{ctx.compiles.total - c0} programs compiled or loaded")
        k = n_check
        warm: List[float] = []
        while len(warm) < int(traffic["warm_max"]):
            t = time.perf_counter()
            loss, grads, _ = prog.loss_and_grads(params,
                                                 *batches[k % len(batches)])
            params = prog.update(runtime, params, grads)
            jax.block_until_ready((loss, grads, params))
            warm.append(time.perf_counter() - t)
            k += 1
            # warm until a step is no faster than the best before it (the
            # check steps after the first, which compiles, count too)
            seen = times[1:] + warm[:-1]
            if (len(warm) >= int(traffic["warm_min"])
                    and (not seen or warm[-1] > 0.98 * min(seen))):
                break
        log(f"warm steps: {[round(t, 4) for t in warm]} s")
        settle()

        acc = {"dma": 0}
        state = {"params": params}

        def one(i):
            x, y = batches[(k + i) % len(batches)]
            p = state["params"]
            if ctx.trace:
                t = time.perf_counter()
                with jax.profiler.TraceAnnotation(SPANS[0]):
                    loss, grads, st = prog.loss_and_grads(p, x, y)
                    jax.block_until_ready((loss, grads))
                t1 = time.perf_counter()
                with jax.profiler.TraceAnnotation(SPANS[1]):
                    p = prog.update(runtime, p, grads)
                    jax.block_until_ready(p)
                rec.spans += [(SPANS[0], t, t1),
                              (SPANS[1], t1, time.perf_counter())]
            else:
                loss, grads, st = prog.loss_and_grads(p, x, y)
                p = prog.update(runtime, p, grads)
                # a step ends when its new params are ready: the next one
                # dispatches nothing over it, so the device's peak does not
                # depend on how far the host ran ahead
                jax.block_until_ready((loss, grads, p))
            acc["dma"] += st.dma_bytes + st.opt_dma_bytes
            state["params"] = p
            return loss, grads, p

        c0, g0 = ctx.compiles.total, ctx.gcs.n
        with _traced(ctx) as window:
            n, t_start, t_end = window(one, ctx.seconds,
                                       jax.block_until_ready)
        rec.window_compiles = ctx.compiles.total - c0
        rec.window_gcs = ctx.gcs.n - g0
        rec.window_units = n
        rec.peak_bytes = _peak_bytes()
        del prog, runtime, state, params, loss, grads
        batches = batches[:n_check]
        gc.collect()

    window_s = t_end - t_start
    batch = cfg["batch"]
    rec.e2e["setup_s"] = t_start - ctx.t0
    rec.e2e["train_step_ms"] = window_s / n * 1e3
    rec.e2e["peak_hbm_mb"] = rec.peak_bytes / 1e6
    rec.counters["steps"] = n
    rec.counters["window_s"] = window_s
    rec.counters["swap_mb_per_step"] = acc["dma"] / n / 1e6
    rec.counters["train_flops_per_s"] = (
        flops.train_per_sample(cell.model.LAYERS, cell.model.TRAINABLE)
        * batch * n / window_s)
    rec.trace = _reduce_trace(ctx, SPANS)
    rec.attempted, rec.failed = n, 0
    if not np.isfinite(prog_read["losses"]).all():
        rec.failed = 1

    t = time.perf_counter()
    ref_read = reference_check_steps(cfg, cell.model, _params0(ctx), batches,
                                     n_check)
    rec.numbers = numbers(prog_read, ref_read)
    log(f"reference: losses {ref_read['losses']} in "
        f"{time.perf_counter() - t:.2f} s")
    return rec


def _params0(ctx):
    """The weights the check steps started from, made again from the seed
    (the reference takes nothing the program holds)."""
    cell = ctx.cell
    return host(ref.make_params(ref.param_shapes(cell.model.LAYERS),
                                ctx.seed))


def _peak_bytes() -> int:
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0))


@contextlib.contextmanager
def _traced(ctx):
    """``timed_window``, inside a profiler trace and the window span when
    the run is traced."""
    if not ctx.trace:
        yield timed_window
        return
    import jax
    shutil.rmtree(ctx.trace_dir, ignore_errors=True)
    jax.profiler.start_trace(str(ctx.trace_dir))
    try:
        with jax.profiler.TraceAnnotation(devtrace.WINDOW):
            yield timed_window
    finally:
        jax.profiler.stop_trace()


def _reduce_trace(ctx, spans):
    if not ctx.trace:
        return None
    try:
        events = devtrace.load_events(str(ctx.trace_dir), spans)
        return devtrace.reduce_events(events)
    finally:
        shutil.rmtree(ctx.trace_dir, ignore_errors=True)
