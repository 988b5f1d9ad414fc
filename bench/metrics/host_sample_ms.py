"""Scheduler: per step of the traced window, the wall time of the program's
``sched.sample`` spans (the per-lane sampling loop (``_emit``)) less the
device-busy time inside them (``harness/scopes.py``). Read as
``host_sample_ms.chat`` and ``host_sample_ms.batch``."""
from harness import scopes


def read(ctx):
    return scopes.host_ms(ctx, "sched.sample")
