"""Median time to first token over every request due in the window."""
from harness.readers import percentile, ttft_ms


def read(ctx):
    return percentile(ttft_ms(ctx.served), 50)
