"""Scheduler: per step of the traced window, the wall time of the program's
``sched.admit`` spans (admission (``_admit``)) less the device-busy time
inside them (``harness/scopes.py``). Read as ``host_admit_ms.chat`` and
``host_admit_ms.batch``."""
from harness import scopes


def read(ctx):
    return scopes.host_ms(ctx, "sched.admit")
