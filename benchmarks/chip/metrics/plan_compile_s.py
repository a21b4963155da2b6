"""Planner: host seconds of ``compile_plan`` (EO analysis, offload schedule,
arena packing, co-optimisation, verification) in set-up, over every plan
the cell compiles."""


def read(rec, ctx):
    return rec.counters.get("plan_compile_s")
