"""Backend compiles during the window (`compile_counter()`); should be 0."""


def read(ctx):
    return ctx.counters["compiles"]
