"""Scheduler: per step of the traced window, the wall time of the program's
``sched.readback`` spans (the logits' device-to-host copy) less the
device-busy time inside them (``harness/scopes.py``). Read as
``host_readback_ms.chat`` and ``host_readback_ms.batch``."""
from harness import scopes


def read(ctx):
    return scopes.host_ms(ctx, "sched.readback")
