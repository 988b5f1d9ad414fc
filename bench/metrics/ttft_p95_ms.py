"""95th percentile of time to first token over every request due in the
window, from its due time to the host holding its first token; a request
with none by the run's end counts at its wait so far."""
from harness.readers import percentile, ttft_ms


def read(ctx):
    return percentile(ttft_ms(ctx.served), 95)
