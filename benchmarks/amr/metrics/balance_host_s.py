"""Host seconds per cycle in Balance: the program's `forest.balance` spans
less the `fetch` spans inside them (where the host waits for the device),
over the traced cycles."""

import span_reduce


def read(ctx):
    return span_reduce.per_cycle(ctx, lambda w: w.host_s("forest.balance"))
