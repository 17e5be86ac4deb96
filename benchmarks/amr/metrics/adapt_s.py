"""Host-clock seconds per cycle inside `forest.adapt`, from the harness's span."""


def read(ctx):
    return sum(c["adapt_s"] for c in ctx.cycles) / len(ctx.cycles)
