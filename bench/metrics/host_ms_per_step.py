"""Scheduler: per step of the traced window, the wall time of the harness's
span around ``sched.step()`` less the device-busy time inside it.  Read as
``host_ms_per_step.chat`` and ``host_ms_per_step.batch``."""


def read(ctx):
    red = ctx.trace
    if red is None or not red.steps:
        return None
    return red.step_host_s() / len(red.steps) * 1e3
