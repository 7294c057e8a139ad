"""Share of the window spent in write calls (stream layer): the
benchmark's ``bench.delete`` and ``bench.insert`` spans over the
window."""


def read(ctx):
    writes = (ctx.spans.get("bench.delete", 0.0)
              + ctx.spans.get("bench.insert", 0.0))
    return 100.0 * writes / ctx.window_s if ctx.window_s else None
