"""Generated tokens emitted in the window over the window's seconds.
Prompt tokens fed through the ramp do not count."""


def read(ctx):
    t0, t1 = ctx.served.window
    n = sum(1 for ts in ctx.served.tok_times.values()
            for t in ts if t0 < t <= t1)
    return n / (t1 - t0) if n else None
