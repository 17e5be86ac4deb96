"""Share of the device 2:1 eval's (face, element) pairs that are leaves
and not padding: `pairs_live` over `pairs_padded` of the program's `fetch`
spans in the traced cycles."""

import span_reduce


def read(ctx):
    live = span_reduce.per_cycle(ctx, lambda w: w.stat_sum("fetch", "pairs_live"))
    padded = span_reduce.per_cycle(ctx, lambda w: w.stat_sum("fetch", "pairs_padded"))
    return live / padded if padded else None
