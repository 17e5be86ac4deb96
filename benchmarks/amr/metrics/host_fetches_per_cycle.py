"""Device-to-host fetches of the fused eval programs per cycle
(`batch.host_fetch_counts()`, summed)."""


def read(ctx):
    return ctx.counters["host_fetches"] / len(ctx.cycles)
