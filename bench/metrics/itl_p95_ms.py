"""95th percentile of the gaps between consecutive generated tokens of one
request, over every gap that closes inside the window."""
from harness.readers import itl_ms, percentile


def read(ctx):
    return percentile(itl_ms(ctx.served), 95)
