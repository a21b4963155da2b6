"""Planner: the planned device bytes, an exact count of the plan: the
packed activation arena's peak plus the optimizer's working region (for a
service, that per session times its live sessions: the derived budget)."""


def read(rec, ctx):
    return rec.counters.get("planned_peak_mb")
