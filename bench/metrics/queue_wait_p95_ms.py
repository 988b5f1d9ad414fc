"""Scheduler: 95th percentile, over requests due in the window, of the time
from the due time to the host-clock start of the step whose admission round
took the request in; one never admitted counts to the run's end."""
from harness.readers import percentile, window_due


def read(ctx):
    served = ctx.served
    start = {t: a for t, a, _ in served.steps}
    waits = []
    for rid in window_due(served):
        req = served.requests[rid]
        at = start.get(req.admitted_step, served.run_end) \
            if req.admitted_step >= 0 else served.run_end
        waits.append((at - served.due[rid]) * 1e3)
    return percentile(waits, 95)
