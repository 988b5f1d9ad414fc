"""Scheduler: per step of the traced window, the wall time of the program's
``sched.feed`` spans (the token grid, page mapping (``allocator.ensure``
and its invalidate program), the block-table upload and the decode-step
dispatch) less the device-busy time inside them (``harness/scopes.py``).
Read as ``host_feed_ms.chat`` and ``host_feed_ms.batch``."""
from harness import scopes


def read(ctx):
    return scopes.host_ms(ctx, "sched.feed")
