"""95th percentile of per-call latency over every delete and
insert_batch call in the window: a host-clock tail of a 0.05 ms
operation, too unsteady across runs to bound end to end, so it is read
beside ``queries_per_s``, which every write slows."""
from layers import percentile_ms


def read(ctx):
    return percentile_ms(ctx.write_s, 95)
