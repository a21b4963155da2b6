#!/usr/bin/env python3
"""The readings the limits of a cell are set from, in one process.

    python benchmarks/chip/readings.py --workload vgg16.train_swap \
        --seeds 11,12,13 --control-seeds 21,22,23 --out readings.json

For each of ``--seeds`` the timed path runs at the cell's own size and its
numbers are compared with the plain reference (the lower readings).  For a
training cell, for each of ``--control-seeds`` the control takes the
program's place: the reference computed one precision below the
configuration's (three bfloat16 passes in place of float32's), and the
reference with each planted fault (half of the batch left out, one label
altered); a state left unchanged reads 1 on ``update_norm_gap`` by
construction and is not run.  For a serving cell every seed serves for
``--seconds`` at the cell's own load and the control replays the same
requests.  Runs on a TPU only; the benchmark's own runs never run this
script.
"""

import argparse
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))


def train_readings(cell, seeds, control_seeds, log, witness_seeds=()):
    import jax

    from chipbench import loop_train as lt
    from chipbench import reference as ref

    cfg, traffic, model = cell.config, cell.traffic, cell.model
    n = int(traffic["check_steps"])
    out = {"program": {}, "control": {}, "half_batch": {}, "label": {},
           "cpu_reference": {}}
    with jax.default_matmul_precision(cfg["matmul_precision"]):
        prog = lt.Program(cfg, model)
        for seed in seeds:
            t = time.perf_counter()
            params = ref.make_params(prog.shapes, seed)
            batches = lt.make_batches(cfg, traffic, seed)[:n]
            p0 = lt.host(params)
            _, got, _ = lt.program_check_steps(prog, prog.runtime(), params,
                                               batches, n)
            del params
            want = lt.reference_check_steps(cfg, model, p0, batches, n)
            out["program"][seed] = lt.numbers(got, want)
            log(f"program seed {seed}: {out['program'][seed]} "
                f"({time.perf_counter() - t:.1f} s)")
            if seed in witness_seeds:
                # the same reference on the host's CPU: how far two sound
                # computations of the same steps lie apart
                cpu = jax.devices("cpu")[0]
                with jax.default_device(cpu):
                    other = lt.reference_check_steps(
                        cfg, model, p0, jax.device_put(batches, cpu), n)
                out["cpu_reference"][seed] = lt.numbers(other, want)
                log(f"cpu_reference seed {seed}: "
                    f"{out['cpu_reference'][seed]}")
        del prog
        for seed in control_seeds:
            params = lt.host(ref.make_params(
                ref.param_shapes(model.LAYERS), seed))
            batches = lt.make_batches(cfg, traffic, seed)[:n]
            want = lt.reference_check_steps(cfg, model, params, batches, n)
            for kind, kw in (("control", {"precision": "high"}),
                             ("half_batch", {"fault": "half_batch"}),
                             ("label", {"fault": "label"})):
                got = lt.reference_check_steps(cfg, model, params, batches,
                                               n, **kw)
                out[kind][seed] = lt.numbers(got, want)
                log(f"{kind} seed {seed}: {out[kind][seed]}")
    return out


def serve_readings(cell, seeds, seconds, log, witness_seeds=()):
    """Per seed, one run of the timed path at the cell's own load for
    ``seconds``, its numbers against the reference (the program), and the
    reference at three bfloat16 passes against it (the control)."""
    import jax

    from chipbench import harness
    from chipbench import loop_personalize as lp
    from chipbench import reference as ref

    cfg, model = cell.config, cell.model
    out = {"program": {}, "control": {}, "cpu_reference": {}}
    for seed in seeds:
        t = time.perf_counter()
        ctx = harness.Context(cell=cell, seed=seed, seconds=seconds,
                              trace=False, t0=t, trace_dir=HERE,
                              compiles=harness.CompileCounter(),
                              gcs=harness.GcCounter())
        s = lp.serve(ctx)
        base = lp.host(ref.make_params(ref.param_shapes(model.LAYERS), seed,
                                       lp.frozen_gains(cfg, model)))
        want = lp.reference_replay(cfg, model, base, s.xs, s.ys, s.sent)
        out["program"][seed] = lp.numbers(s.got, want)
        low = lp.reference_replay(cfg, model, base, s.xs, s.ys, s.sent,
                                  precision="high")
        out["control"][seed] = lp.numbers(low, want)
        for kind, other in (("program", s.got), ("control", low)):
            gaps = [[abs(a - b) / abs(b) for a, b in zip(
                other["losses"][u], want["losses"][u])] for u in want["losses"]]
            log(f"  {kind} loss gaps, worst user, first and last request: "
                f"{max(g[0] for g in gaps):.3g}, {max(g[-1] for g in gaps):.3g}")
        log(f"seed {seed}: {s.rec.window_units} waves, "
            f"{sum(map(len, s.sent.values()))} requests, "
            f"{s.rec.e2e['serve_steps_per_s']:.3f} steps/s; program "
            f"{out['program'][seed]}, control {out['control'][seed]} "
            f"({time.perf_counter() - t:.1f} s)")
        if seed in witness_seeds:
            # the same reference on the host's CPU: how far two sound
            # computations of the same requests lie apart
            cpu = jax.devices("cpu")[0]
            with jax.default_device(cpu):
                other = lp.reference_replay(
                    cfg, model, base, jax.device_put(s.xs, cpu),
                    jax.device_put(s.ys, cpu), s.sent)
            out["cpu_reference"][seed] = lp.numbers(other, want)
            log(f"cpu_reference seed {seed}: {out['cpu_reference'][seed]}")
        del s
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--witness-seeds", default="",
                    help="program seeds whose reference is also run on the "
                         "host's CPU, for the gap two sound computations "
                         "give")
    ap.add_argument("--seconds", type=float, default=30.0,
                    help="the window of each seed's run, for cells whose "
                         "numbers follow a run's requests")
    ap.add_argument("--out", required=True)
    args = ap.parse_args()

    from chipbench import harness

    def log(msg):
        print(msg, file=sys.stderr, flush=True)

    cell = harness.find_cell(args.workload)
    dev = harness.require_tpu(cell.chips)
    harness.enable_compile_cache(harness.CACHE_DIR)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    controls = [int(s) for s in args.control_seeds.split(",") if s]
    witness = [int(s) for s in args.witness_seeds.split(",") if s]
    if cell.traffic["loop"] == "train":
        out = train_readings(cell, seeds, controls, log, witness)
    else:
        out = serve_readings(cell, seeds, args.seconds, log, witness)
    out["device"] = dev.device_kind
    summary = {kind: {name: max(v[name] for v in by_seed.values())
                      if kind in ("program", "cpu_reference") else
                      min(v[name] for v in by_seed.values())
                      for name in next(iter(by_seed.values()))}
               for kind, by_seed in out.items()
               if isinstance(by_seed, dict) and by_seed}
    out["summary"] = summary
    Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    Path(args.out).write_text(json.dumps(out, indent=1))
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
