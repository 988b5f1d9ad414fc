"""Engine / model step: per step of the traced window, the union of device
operation intervals inside the harness's ``sched.step()`` spans.  Read as
``device_ms_per_step.chat`` and ``device_ms_per_step.batch``."""


def read(ctx):
    red = ctx.trace
    if red is None or not red.steps:
        return None
    return red.step_device_s() / len(red.steps) * 1e3
