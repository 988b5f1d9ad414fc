"""Device: 100 x (1 - union of device operation intervals / traced window),
averaged over the cell's chips.  Read as ``device_idle_share.chat`` and
``device_idle_share.batch``."""


def read(ctx):
    red = ctx.trace
    if red is None:
        return None
    return (1.0 - red.mean_busy_s() / red.window_s) * 100.0
