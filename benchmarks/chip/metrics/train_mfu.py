"""Whole step: model FLOPs of training (``chipbench.flops`` from the
reference's shapes) times samples a second over the window, against the
chips' bf16 peak (``peaks.json``), in %."""

from chipbench.harness import peaks


def read(rec, ctx):
    f = rec.counters.get("train_flops_per_s")
    if not f:
        return None
    peak = peaks(ctx.device_kind, ctx.cell.bench_dir)["bf16_flops"]
    return 100.0 * f / (ctx.cell.chips * peak)
