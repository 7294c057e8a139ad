"""95th percentile latency over every query answered in the window
(open loop: timed from when each query was due)."""
from layers import percentile_ms


def read(ctx):
    return percentile_ms(ctx.latency_s, 95)
