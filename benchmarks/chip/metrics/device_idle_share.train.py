"""Device: the share of the traced window in which no operation ran on the
device, from the profiler trace (``chipbench.devtrace``), in %."""


def read(rec, ctx):
    if rec.trace is None:
        return None
    return 100.0 * rec.trace.idle_share
