"""Median host time of the exchange's round 2 per batch: the stacked
mesh launch under lambda0 to its answers on the host (exchange
layer)."""
from exchange import span_p50_ms


def read(ctx):
    return span_p50_ms(ctx, "p2h.exchange.round2")
