"""Host-clock seconds per cycle inside `forest.balance`, from the harness's span."""


def read(ctx):
    return sum(c["balance_s"] for c in ctx.cycles) / len(ctx.cycles)
