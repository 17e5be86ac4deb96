"""Wall time of the window over the cycles completed in it (host clock)."""


def read(ctx):
    return ctx.window_s / len(ctx.cycles)
