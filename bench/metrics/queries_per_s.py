"""Hyperplanes answered over the window's seconds."""


def read(ctx):
    return ctx.answered / ctx.window_s if ctx.window_s else None
