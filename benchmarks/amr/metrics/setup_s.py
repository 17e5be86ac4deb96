"""From the start of the harness to the end of the warm-up cycles (host clock)."""


def read(ctx):
    return ctx.setup_s
