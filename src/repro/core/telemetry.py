"""The program's spans and counters, in one registry.

Spans are `jax.profiler.TraceAnnotation`s.  With no profiler running one
costs a few tenths of a microsecond to enter and leave (the profiler's own
is-active check); with one running it lands in the same trace as the
device's ops, on the same clock, so an idle stretch of the device can be
put down to the host step that holds it.  Every span name is one of
`SPANS`; its stats (keyword arguments such as `round=3`) ride on the event
and stay out of its name, so names stay stable.  Stats are values already
at hand: nothing is computed only to label a span.  A span inside a jitted
function would run only while tracing, so spans sit on the host side of
every dispatch.

Counters are integers keyed `<prefix>.<name>`:

  dispatch.<op>      batched element-op dispatches (`batch.dispatch_counts`)
  trace.<program>    jit traces of the fused eval programs (`batch.trace_counts`)
  fetch.<stage>      device->host fetches of the fused eval (`batch.host_fetch_counts`)
  route.compactions  runs of the routing gather, one per `eval_route` call
                     that found remote (face, element) pairs

Balance's rounds and the 2:1 eval's (face, element) pairs are read from the
spans: one `balance.round` per eval round, and the `pairs_live` /
`pairs_padded` stats of the eval's `fetch` spans.  The routing eval's
`fetch` span carries `pairs_remote` (the pairs it found remote) and
`pairs_swept` (nf x bucket, the pairs it ran on).
"""

from __future__ import annotations

from jax.profiler import TraceAnnotation

__all__ = ["SPANS", "TOP_SPANS", "span", "count", "counts", "reset_counts"]

# The top-level spans: one per forest driver call.  A nested `forest.adapt`
# (Balance refines through Adapt) sits under `balance.refine`.
TOP_SPANS = ("forest.adapt", "forest.balance", "forest.ghost")

SPANS = TOP_SPANS + (
    # forest.adapt: one `adapt.pass` per refine/coarsen pass
    "adapt.pass",        # stats: pass_no, elements
    "adapt.callback",    # the solver's indicator
    "adapt.families",    # family-head scan: which families may coarsen
    "adapt.children",    # refine: batched children + fetch
    "adapt.parents",     # coarsen: batched parents + fetch
    "keys.encode",       # SFC keys of the new leaves (`replace_elements`)
    # forest.balance: one `balance.round` per eval round
    "balance.round",     # stats: round, elements
    "balance.sweep",     # resident face sweeps + leaf-table uploads
    "balance.route",     # routing eval, query sets, posting the exchange
    "balance.answer",    # merge 1: wait queries, answer, post replies
    "balance.eval",      # interior 2:1 eval against the local leaves
    "balance.merge",     # merge 2: wait replies, fold, rebuild the cache
    "balance.eval_cache",  # boundary 2:1 eval against the remote cache
    "balance.refine",    # refine the violators, build the next queries
    # forest.ghost
    "ghost.route",       # markers, sweep, routing eval, posting queries
    "ghost.answer",      # wait queries, answer from the local leaves
    "ghost.assemble",    # wait replies, decode the ghost layer
    # batch layer: one device->host materialization; stat: site
    "fetch",
    # comm: a blocking wait on a collective in flight; stat: phase
    "comm.wait",
)
_NAMES = frozenset(SPANS)

_counts: dict[str, int] = {}


def span(name: str, **stats) -> TraceAnnotation:
    """A context manager that marks `name` (one of `SPANS`) in a profiler
    trace, with `stats` attached to the event."""
    if name not in _NAMES:
        raise ValueError(f"span {name!r} is not in telemetry.SPANS")
    return TraceAnnotation(name, **stats)


def count(name: str, n: int = 1) -> None:
    """Add `n` to counter `name`."""
    _counts[name] = _counts.get(name, 0) + n


def counts(prefix: str = "") -> dict[str, int]:
    """Snapshot of the counters whose name starts with `prefix`, keyed by
    the rest of the name."""
    k = len(prefix)
    return {name[k:]: v for name, v in _counts.items() if name.startswith(prefix)}


def reset_counts(prefix: str = "") -> None:
    """Drop the counters whose name starts with `prefix` (all by default)."""
    for name in [n for n in _counts if n.startswith(prefix)]:
        del _counts[name]
