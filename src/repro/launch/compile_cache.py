"""Where JAX keeps its persistent compilation cache, decided in one place.

`JAX_COMPILATION_CACHE_DIR`, when set, wins: JAX reads it itself and this
module sets nothing.  Otherwise the cache goes to `.jax_cache/` at the root
of the checkout — a fixed path (the path is part of what a cache entry
matches), git-ignored, and never outside the tree.  The d=3 Pallas kernels
take seconds each to compile and Adapt/Balance compile one program per
power-of-two bucket, so every process that drives the pipeline on a chip
(`chip_smoke.py` and its rank processes, `benchmarks/run.py`) calls
`enable_compile_cache()` before its first dispatch.

`compile_counter()` counts the backend compiles of the process (a hit in
the persistent cache still counts one, and also counts as a hit), so a
caller can print how many programs a phase compiled, and the seconds spent
tracing and lowering programs, in all and per program.
"""

from __future__ import annotations

import os
from pathlib import Path

__all__ = ["ENV_VAR", "IN_TREE_DIR", "compile_cache_dir",
           "enable_compile_cache", "compile_counter"]

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
IN_TREE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def compile_cache_dir() -> str:
    """The directory the cache uses: `$JAX_COMPILATION_CACHE_DIR` if set,
    else the in-tree `.jax_cache/`."""
    return os.environ.get(ENV_VAR) or str(IN_TREE_DIR)


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache at `compile_cache_dir()` and
    return that directory.  Sets the JAX option only when the environment
    does not already name a directory."""
    import jax

    path = compile_cache_dir()
    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", path)
    return path


_counts = {"compiles": 0, "compile_s": 0.0, "cache_hits": 0,
           "lower_s": 0.0, "lower_s_by_program": {}}
_listening = False
# Set-up lowering: tracing a program to a jaxpr, then converting the jaxpr
# to an MLIR module.  Both happen before the persistent cache is asked, in
# every process, so a warm cache does not save them.
_LOWER_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                 "/jax/core/compile/jaxpr_to_mlir_module_duration")
# The lowering intervals counted so far that no later one has enclosed:
# tracing a program traces the programs it calls, and their events end
# first, inside the caller's.
_lowered: list = []


def _on_lower(event, start, end, fun_name="?", **_kw):
    if event not in _LOWER_EVENTS:
        return
    inner = 0.0
    while _lowered and _lowered[-1][0] >= start and _lowered[-1][1] <= end:
        s, e = _lowered.pop()
        inner += e - s
    own = (end - start) - inner
    # the MLIR event names the module `jit(<name>)`, the trace event `<name>`
    name = fun_name[4:-1] if fun_name.startswith("jit(") else fun_name
    by = _counts["lower_s_by_program"]
    by[name] = by.get(name, 0.0) + own
    _counts["lower_s"] += own
    _lowered.append((start, end))
    del _lowered[:-64]


def compile_counter() -> dict:
    """The live counters of this process (installed on first call; read
    differences around a region):

      compiles, compile_s   backend compiles and their seconds
      cache_hits            persistent-cache hits
      lower_s               seconds of tracing and lowering to MLIR, each
                            second counted once where programs nest
      lower_s_by_program    the same by program (function) name, each
                            program's own seconds, without those of the
                            programs it calls; the values add up to lower_s
    """
    global _listening
    if not _listening:
        import jax.monitoring as mon

        def on_duration(event, secs, **_kw):
            if event == "/jax/core/compile/backend_compile_duration":
                _counts["compiles"] += 1
                _counts["compile_s"] += secs

        def on_event(event, **_kw):
            if event == "/jax/compilation_cache/cache_hits":
                _counts["cache_hits"] += 1

        mon.register_event_duration_secs_listener(on_duration)
        mon.register_event_listener(on_event)
        mon.register_event_time_span_listener(_on_lower)
        _listening = True
    return _counts
