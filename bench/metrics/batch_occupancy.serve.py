"""Queries per engine micro-batch over the window (engine batcher)."""


def read(ctx):
    batches = ctx.stats.get("batches", 0)
    return ctx.stats["queries"] / batches if batches else None
