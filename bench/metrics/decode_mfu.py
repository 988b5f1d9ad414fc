"""Engine / model step: operations the traced window's decode steps need
for their live streams and lanes (``harness/flops.py``), over the traced
window's seconds times the chip's bf16 peak (``harness/peaks.py``)."""
from harness import drive, flops


def read(ctx):
    red, served = ctx.trace, ctx.served
    if red is None or ctx.peak_flops is None:
        return None
    lo, hi = served.traced_steps
    work = 0.0
    for t in range(lo, hi):
        inp = drive.input_at(served, t)
        if inp is not None:
            work += flops.step(served.config, inp.mask, inp.pos)
    if not work:
        return None
    return work / (red.window_s * ctx.peak_flops * ctx.n_chips) * 100.0
