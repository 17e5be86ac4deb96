"""Seconds of set-up spent tracing programs and lowering them to MLIR
(`compile_counter()["lower_s"]`); both run in every process, before the
persistent cache is asked.  Read after the window, so the reading is the
set-up's only while the window lowers nothing: a lowered program is
compiled or found in the cache, and either counts as a compile, so with
`compiles_in_window` above 0 the metric is left out.  Logs the three
programs with the most lowering time."""

import sys


def read(ctx):
    from repro.launch.compile_cache import compile_counter

    c = compile_counter()
    if "lower_s" not in c:
        return None   # the program has no lowering counter
    if ctx.counters["compiles"] != 0:
        print(f"setup_lower_s left out: {ctx.counters['compiles']} compiles "
              "in the window", file=sys.stderr)
        return None
    top = sorted(c["lower_s_by_program"].items(), key=lambda kv: -kv[1])[:3]
    print("lowering by program (s): "
          + ", ".join(f"{name} {s:.6f}" for name, s in top), file=sys.stderr)
    return c["lower_s"]
