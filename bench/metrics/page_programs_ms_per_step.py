"""Engine / model step: per step of the traced window, the device-busy
time of the page programs, the page allocator's invalidate and slot-reset
programs (``jit__invalidate_impl``, ``jit__reset_impl``), inside the
harness's step spans."""
from harness import scopes


def read(ctx):
    return scopes.program_ms(ctx, scopes.PAGE_PROGRAMS)
