"""Batched element-op dispatches per cycle (`batch.dispatch_counts()`, summed)."""


def read(ctx):
    return ctx.counters["dispatches"] / len(ctx.cycles)
