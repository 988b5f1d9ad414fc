"""Scheduler: bytes copied from the device to the host per step, the mean
``bytes`` stat of the program's ``sched.readback`` spans in the traced
window (the logits of every launched width class)."""
from harness import scopes


def read(ctx):
    return scopes.span_stat(ctx, "sched.readback", "bytes")
