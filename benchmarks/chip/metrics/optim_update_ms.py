"""Optimizer lane: mean host milliseconds of ``offloaded_update`` up to
``block_until_ready`` of the new params, over the traced window's steps."""


def read(rec, ctx):
    return rec.span_ms("optim_update")
