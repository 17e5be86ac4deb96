"""Device seconds per cycle in Pallas kernels: the summed durations of the
trace's ops that are `tpu_custom_call`s, over the traced cycles."""


def read(ctx):
    if ctx.trace is None or ctx.trace["pallas_s"] <= 0:
        return None  # no Pallas kernel ran in the traced window
    return ctx.trace["pallas_s"] / ctx.trace["cycles"]
