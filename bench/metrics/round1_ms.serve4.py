"""Median host time of the exchange's round 1 per batch: every shard's
delta scan and beam over its own segments, started on every chip before
any answer is read, to lambda0 on the host (exchange layer)."""
from exchange import span_p50_ms


def read(ctx):
    return span_p50_ms(ctx, "p2h.exchange.round1")
