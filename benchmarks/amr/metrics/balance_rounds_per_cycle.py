"""Balance eval rounds per cycle, the initial round included: the program's
`balance.round` spans (one per eval round) over the traced cycles."""

import span_reduce


def read(ctx):
    return span_reduce.per_cycle(ctx, lambda w: len(w.named("balance.round")))
