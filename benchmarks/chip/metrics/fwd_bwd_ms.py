"""Executor: mean host milliseconds of ``loss_and_grads`` up to
``block_until_ready`` of its loss and grads, over the traced window's
steps."""


def read(rec, ctx):
    return rec.span_ms("loss_and_grads")
