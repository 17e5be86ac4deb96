"""Seconds per cycle the host waits in device->host fetches: the union of
the program's `fetch` spans over the traced cycles."""

import span_reduce


def read(ctx):
    return span_reduce.per_cycle(ctx, lambda w: span_reduce.seconds(w.named("fetch")))
