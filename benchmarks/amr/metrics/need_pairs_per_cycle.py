"""(face, element) pairs the device's 2:1 eval runs on per cycle, padding
included (nf x bucket(n) per eval): the `pairs_padded` stats of the
program's `fetch` spans over the traced cycles."""

import span_reduce


def read(ctx):
    return span_reduce.per_cycle(ctx, lambda w: w.stat_sum("fetch", "pairs_padded"))
