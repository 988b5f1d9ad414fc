"""Scheduler: per step of the traced window, the wall time of the program's
``sched.release`` spans (slot release and the step's bookkeeping
(``_finish_step``)) less the device-busy time inside them
(``harness/scopes.py``). Read as ``host_release_ms.chat`` and
``host_release_ms.batch``."""
from harness import scopes


def read(ctx):
    return scopes.host_ms(ctx, "sched.release")
