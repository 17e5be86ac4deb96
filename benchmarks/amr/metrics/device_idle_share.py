"""1 - busy / window over the traced window: busy is the union of the
device-op intervals in the profiler trace."""


def read(ctx):
    if ctx.trace is None or ctx.trace["window_s"] <= 0:
        return None
    return 1.0 - ctx.trace["busy_s"] / ctx.trace["window_s"]
