"""Median latency over every query answered in the window."""
from layers import percentile_ms


def read(ctx):
    return percentile_ms(ctx.latency_s, 50)
