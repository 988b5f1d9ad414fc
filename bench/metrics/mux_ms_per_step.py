"""Kernels: per step of the traced window, the device time of the decode
program's ops in scope ``mux`` (embedding, prefix and mux apply): the
union of their intervals inside the harness's step spans
(``harness/scopes.py``)."""
from harness import scopes


def read(ctx):
    return scopes.scope_ms(ctx, "mux")
