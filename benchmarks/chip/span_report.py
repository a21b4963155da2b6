#!/usr/bin/env python3
"""The program's spans in a traced window of one cell, on a TPU.

    python benchmarks/chip/span_report.py --workload vgg16.train_swap \
        --seed 7 --seconds 10 --steps 8 --out spans_vgg16.json

One process, three parts:

1. one run of the cell as the benchmark makes it (``harness.run_cell``,
   untraced, for ``--seconds``); the program's compile counter, registered
   at process start, then gives the seconds spent building programs before
   the window opened (``setup_compile_s``) beside the run's ``setup_s``;
2. training cells only: the cost of the spans under the profiler, as
   blocks of ``--steps`` steps with the spans off, on, on, off, each step
   timed on the host clock as the traced benchmark times it
   (``loss_and_grads`` and ``optim_update``, each up to
   ``block_until_ready``);
3. a profiled window of ``--steps`` steps (or waves) with the spans on,
   reduced to: the benchmark's idle gaps by its own rule (``devtrace``),
   with and without the program's span names; each span's count and self
   time per step; the share of the benchmark's outer spans that the
   program's spans cover; and the idle time put down to the innermost open
   span (``chipbench.spantrace``).

Runs on a TPU only, like ``run.py``; the benchmark's own runs never run
this script.
"""

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

from repro.runtime import trace  # noqa: E402

COMPILES = trace.compile_counter()


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _profiled(trace_dir: Path, body, load: bool = True):
    """``body()`` inside a profiler trace and the benchmark's window span;
    returns its value and, with ``load``, the trace's events (the
    benchmark's spans and the program's)."""
    import jax

    from chipbench import devtrace
    shutil.rmtree(trace_dir, ignore_errors=True)
    jax.profiler.start_trace(str(trace_dir))
    try:
        with jax.profiler.TraceAnnotation(devtrace.WINDOW):
            out = body()
    finally:
        jax.profiler.stop_trace()
    if not load:
        shutil.rmtree(trace_dir, ignore_errors=True)
        return out, None
    try:
        events = devtrace.load_events(
            str(trace_dir), OUTER + tuple(trace.SPANS))
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    return out, events


OUTER = ("loss_and_grads", "optim_update", "drain")


def reduce(events, units: int, outer) -> dict:
    """What a profiled window of ``units`` steps or waves says."""
    from chipbench import devtrace, spantrace

    names = tuple(trace.SPANS)
    bench_only = [e for e in events if e["name"] not in trace.SPANS]
    rule = devtrace.reduce_events(bench_only)
    beside = devtrace.reduce_events(events)
    selfs = spantrace.self_times(events, names + tuple(outer))
    out = {
        "units": units,
        "idle_gaps": rule.idle_gaps if rule else None,
        "idle_gaps_with_program_spans": (beside.idle_gaps if beside
                                         else None),
        "busy_s": rule.busy_s if rule else None,
        "window_s": rule.window_s if rule else None,
        "idle_share": rule.idle_share if rule else None,
        "device_ops": rule.device_ops if rule else None,
        "per_unit": {n: {"count": d["count"] / units,
                         "self_ms": d["self_s"] / units * 1e3,
                         "total_ms": d["total_s"] / units * 1e3}
                     for n, d in sorted(selfs.items())},
        "coverage": {o: spantrace.coverage(events, o, names)
                     for o in outer},
        "idle_by_innermost": spantrace.idle_by_innermost(
            events, names + tuple(outer)),
    }

    def self_ms(*keys):
        return sum(out["per_unit"].get(k, {}).get("self_ms", 0.0)
                   for k in keys)

    out["exec_compute_ms"] = self_ms("exec.compute")
    out["swap_transfer_ms"] = self_ms("exec.swap_out", "exec.swap_in")
    out["optim_codec_ms"] = self_ms("optim.decode", "optim.encode")
    return out


def bench_run(cell, seed: int, seconds: float) -> dict:
    """One untraced run of the cell as ``run.py`` makes it, and the
    compile counter's seconds up to its window."""
    from chipbench import harness
    res = harness.run_cell(cell, seed, seconds, False, T0)
    setup = res["metrics"]["setup_s"]["value"]
    programs, seconds_c = COMPILES.until(T0 + setup)
    log(f"benchmark run: {json.dumps(res['metrics'])}, correct "
        f"{res['correct']}; {programs} programs in {seconds_c:.2f} s "
        f"before the window")
    return {"metrics": res["metrics"], "correct": res["correct"],
            "setup_compile_s": seconds_c, "setup_programs": programs}


# ---------------------------------------------------------------------------
# training
# ---------------------------------------------------------------------------

def train_report(cell, seed: int, steps: int, trace_dir: Path) -> dict:
    import jax

    from chipbench import loop_train as lt
    from chipbench import reference as ref
    from repro.core.plan import Compute

    cfg, traffic = cell.config, cell.traffic
    out = {}
    with jax.default_matmul_precision(cfg["matmul_precision"]):
        prog = lt.Program(cfg, cell.model)
        runtime = prog.runtime()
        state = {"params": ref.make_params(prog.shapes, seed)}
        batches = lt.make_batches(cfg, traffic, seed)
        counts = []

        def one(i):
            x, y = batches[i % len(batches)]
            t = time.perf_counter()
            with jax.profiler.TraceAnnotation("loss_and_grads"):
                loss, grads, st = prog.loss_and_grads(state["params"], x, y)
                jax.block_until_ready((loss, grads))
            t1 = time.perf_counter()
            with jax.profiler.TraceAnnotation("optim_update"):
                p = prog.update(runtime, state["params"], grads)
                jax.block_until_ready(p)
            t2 = time.perf_counter()
            state["params"] = p
            counts.append({
                "compute": sum(isinstance(op, Compute)
                               for op in st.replayed_ops),
                "swap_outs": st.swap_outs, "prefetches": st.prefetches,
                "opt_prefetches": st.opt_prefetches,
                "opt_swap_outs": st.opt_swap_outs})
            return (t1 - t) * 1e3, (t2 - t1) * 1e3

        for i in range(3):
            one(i)
        # the spans' cost under the profiler: blocks off, on, on, off
        blocks = {False: [], True: []}

        def cost():
            try:
                for on in (False, True, True, False):
                    trace.enable(on)
                    blocks[on] += [one(i) for i in range(steps)]
            finally:
                trace.enable(False)

        _profiled(trace_dir, cost, load=False)
        for on, rows in blocks.items():
            key = "spans_on" if on else "spans_off"
            out[key] = {
                "fwd_bwd_ms": statistics.median(r[0] for r in rows),
                "optim_update_ms": statistics.median(r[1] for r in rows),
                "fwd_bwd_ms_mean": statistics.fmean(r[0] for r in rows),
                "optim_update_ms_mean": statistics.fmean(r[1]
                                                         for r in rows),
                "steps": len(rows)}
        off, on = out["spans_off"], out["spans_on"]
        base = off["fwd_bwd_ms"] + off["optim_update_ms"]
        out["cost_ms_per_step"] = (on["fwd_bwd_ms"] + on["optim_update_ms"]
                                   - base)
        out["cost_share"] = out["cost_ms_per_step"] / base

        counts.clear()
        trace.enable(True)
        try:
            times, events = _profiled(
                trace_dir, lambda: [one(i) for i in range(steps)])
        finally:
            trace.enable(False)
    out["window"] = reduce(events, steps, ("loss_and_grads",
                                           "optim_update"))
    out["window"]["fwd_bwd_ms"] = statistics.fmean(t[0] for t in times)
    out["window"]["optim_update_ms"] = statistics.fmean(t[1]
                                                        for t in times)
    out["stats_per_step"] = counts[0] if all(
        c == counts[0] for c in counts) else counts
    return out


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------

def serve_report(cell, seed: int, waves: int, trace_dir: Path) -> dict:
    import jax

    from chipbench import loop_personalize as lp
    from chipbench import reference as ref

    cfg, tr = cell.config, cell.traffic
    with jax.default_matmul_precision(cfg["matmul_precision"]):
        base = ref.make_params(ref.param_shapes(cell.model.LAYERS), seed,
                               lp.frozen_gains(cfg, cell.model))
        service = lp.Service(cfg, cell.model, base)
        traffic = lp.Traffic(cfg, tr, seed)
        for _ in range(int(tr["warm_max"])):
            traffic.note(service.wave(traffic.wave_requests()))

        def one():
            t = time.perf_counter()
            with jax.profiler.TraceAnnotation("drain"):
                res = service.wave(traffic.wave_requests())
            traffic.note(res)
            return time.perf_counter() - t

        trace.enable(True)
        try:
            walls, events = _profiled(
                trace_dir, lambda: [one() for _ in range(waves)])
        finally:
            trace.enable(False)
    out = {"window": reduce(events, waves, ("drain",))}
    out["window"]["wave_ms"] = statistics.fmean(walls) * 1e3
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0,
                    help="the window of the benchmark's own run (part 1)")
    ap.add_argument("--steps", type=int, default=8,
                    help="steps (waves, for a serving cell) a block")
    ap.add_argument("--out", required=True)
    args = ap.parse_args()

    from chipbench import harness
    cell = harness.find_cell(args.workload)
    dev = harness.require_tpu(cell.chips)
    trace_dir = cell.bench_dir / ".out" / "spans" / cell.name
    out = {"workload": cell.name, "seed": args.seed,
           "device": dev.device_kind,
           "bench": bench_run(cell, args.seed, args.seconds)}
    if cell.traffic["loop"] == "train":
        out.update(train_report(cell, args.seed, args.steps, trace_dir))
    else:
        out.update(serve_report(cell, args.seed, args.steps, trace_dir))
    Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    Path(args.out).write_text(json.dumps(out, indent=1))
    w = out["window"]
    print(json.dumps({k: w[k] for k in (
        "exec_compute_ms", "swap_transfer_ms", "optim_codec_ms",
        "coverage", "idle_share") if k in w}
        | {"setup_compile_s": out["bench"]["setup_compile_s"],
           "setup_s": out["bench"]["metrics"]["setup_s"]["value"]}),
        flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
