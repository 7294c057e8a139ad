"""Process start to the opening of the window: data, index build,
warm-up and, in a run that compiles, compilation."""


def read(ctx):
    return ctx.setup_s
