"""Host seconds per cycle in the solver's Adapt: the program's top-level
`forest.adapt` spans (not the ones Balance runs inside `balance.refine`)
less the `fetch` spans inside them, over the traced cycles."""

import span_reduce


def read(ctx):
    return span_reduce.per_cycle(ctx, lambda w: w.host_s("forest.adapt", top_only=True))
