"""Engine / model step: per step of the traced window, the device time of the
decode program's ops under none of the named scopes (residual adds,
layer-scan bookkeeping, copies): the union of their intervals inside the
harness's step spans (``harness/scopes.py``)."""
from harness import scopes


def read(ctx):
    return scopes.scope_ms(ctx, scopes.UNSCOPED)
