"""Share of a fixed k-step top-k insertion loop that the stacked sweep
kernel runs in ``music100.serve`` (stacked sweep kernel layer)."""
from kernel_steps import topk_insert_share


def read(ctx):
    return topk_insert_share(ctx, "music100.serve")
