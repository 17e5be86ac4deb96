#!/usr/bin/env python3
"""The program's own spans in the profile of a `--trace 1` run.

The program marks every step of the AMR cycle with a span
(`repro.core.telemetry.SPANS`): the forest drivers (`forest.adapt`,
`forest.balance`, `forest.ghost`) and their steps, each Balance round, and
every device->host `fetch` of the batch layer, whose stats carry the
(face, element) pairs of the 2:1 eval.  They share the trace's clock with
the device's ops, so this module gives, inside the harness's `window`
span:

* each span name's time, and its host time: the part not inside a
  `fetch` span, where the host waits for the device;
* the device's idle gaps, each named by the innermost span that holds its
  midpoint (with only the harness's phase spans in the trace this is the
  phase that holds it, as `trace_reduce.phase_at` names it);
* the counts the spans carry: Balance rounds, eval pairs.

The per-layer metrics that read the program's spans use it
(`metrics/balance_rounds_per_cycle.py` and the others); in the trace of a
program without spans they read nothing.  Run as a script after a traced
run, it prints the whole breakdown as one JSON line:

    python3 benchmarks/amr/span_reduce.py [--trace-dir DIR]
"""

from __future__ import annotations

import argparse
import json
import sys
import warnings
from pathlib import Path
from typing import NamedTuple

HERE = Path(__file__).resolve().parent
TRACE_DIR = HERE / "_trace"
for _p in (str(HERE), str(HERE.parents[1] / "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import trace_reduce  # noqa: E402

WINDOW = "window"
PHASES = ("adapt", "balance", "ghost")   # the harness's own phase spans
FETCH = "fetch"
INF = float("inf")


class Span(NamedTuple):
    name: str
    start: float   # ns on the trace's clock
    end: float
    event: object  # the profiler's event, for its stats


def stats(span: Span) -> dict:
    """The stats of a span's event.  Their type is built on first use, with
    a DeprecationWarning that must not become an error there."""
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        return dict(span.event.stats)


def program_span_names():
    """(SPANS, TOP_SPANS) of the program under test, or None when it has
    no span registry."""
    try:
        from repro.core.telemetry import SPANS, TOP_SPANS
    except ImportError:
        return None
    return SPANS, TOP_SPANS


def host_spans(profile, names) -> list[Span]:
    """The host events named in `names`, outer before inner at equal start."""
    names = set(names)
    found = []
    for plane in profile.planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in names:
                    found.append(Span(ev.name, ev.start_ns,
                                      ev.start_ns + ev.duration_ns, ev))
    found.sort(key=lambda s: (s.start, -s.end))
    return found


def seconds(group) -> float:
    """Length of the union of the spans in `group`, in seconds."""
    return trace_reduce.covered(
        trace_reduce.merge([(s.start, s.end) for s in group], -INF, INF)) / 1e9


def host_seconds(group, fetches) -> float:
    """Length of the union of `group` outside the union of `fetches`."""
    keep = trace_reduce.merge([(s.start, s.end) for s in group], -INF, INF)
    cut = trace_reduce.merge([(s.start, s.end) for s in fetches], -INF, INF)
    total, j = trace_reduce.covered(keep), 0
    for s, e in keep:
        while j < len(cut) and cut[j][1] <= s:
            j += 1
        k = j
        while k < len(cut) and cut[k][0] < e:
            total -= min(e, cut[k][1]) - max(s, cut[k][0])
            k += 1
    return total / 1e9


def top_level(spans, names) -> list[Span]:
    """The spans named in `names` that no other such span holds."""
    out, end = [], -INF
    for s in spans:
        if s.name in names and s.start >= end:
            out.append(s)
            end = s.end
    return out


def innermost(gaps, spans) -> list:
    """For each `(start, end)` gap, the innermost span holding its midpoint
    (None if none).  `spans` are in start order and nest, as the spans of
    one thread do: of those holding a time, the innermost started last."""
    out = [None] * len(gaps)
    order = sorted(range(len(gaps)), key=lambda i: gaps[i][0] + gaps[i][1])
    stack, j = [], 0
    for i in order:
        t = (gaps[i][0] + gaps[i][1]) / 2
        while j < len(spans) and spans[j].start <= t:
            if spans[j].name != WINDOW:
                stack.append(spans[j])
            j += 1
        while stack and stack[-1].end <= t:
            stack.pop()
        out[i] = stack[-1] if stack else None
    return out


def name_gaps(gaps, spans) -> list:
    """[[name, seconds]] of `(start, end)` gaps, each named by the innermost
    span holding its midpoint ('between phases' if none)."""
    return [[s.name if s is not None else "between phases", (e - b) / 1e9]
            for s, (b, e) in zip(innermost(gaps, spans), gaps)]


class WindowSpans(NamedTuple):
    """The program's and the harness's spans that start inside the window,
    clipped to it, in start order."""

    spans: list
    lo: float
    hi: float
    top: tuple      # the program's top-level span names
    profile: object

    def named(self, name: str) -> list:
        return [s for s in self.spans if s.name == name]

    def stat_sum(self, name: str, stat: str) -> int:
        return sum(int(stats(s).get(stat, 0)) for s in self.named(name))

    def host_s(self, name: str, top_only: bool = False) -> float:
        """Host seconds of `name`; with `top_only`, of its spans that no
        other top-level span holds."""
        group = (top_level(self.spans, self.top) if top_only else self.spans)
        return host_seconds([s for s in group if s.name == name], self.named(FETCH))


_cache: dict = {}


def window_spans(trace_dir=None) -> WindowSpans | None:
    """The spans of the newest trace under `trace_dir` (default: the
    harness's), read once per trace file; None when the program has no span
    registry, or none of its top-level spans is in the window."""
    names = program_span_names()
    if names is None:
        return None
    spans_all, top = names
    d = Path(trace_dir if trace_dir is not None else TRACE_DIR)
    files = list(d.rglob("*.xplane.pb"))
    if not files:
        return None
    newest = max(files, key=lambda p: p.stat().st_mtime)
    key = (str(newest), newest.stat().st_mtime)
    if key not in _cache:
        profile = trace_reduce.load(str(d))
        spans = host_spans(profile, set(spans_all) | set(PHASES) | {WINDOW})
        win = [s for s in spans if s.name == WINDOW]
        if not win:
            raise LookupError(f"no {WINDOW!r} span in the trace")
        lo, hi = win[0].start, win[0].end
        inner = [s._replace(end=min(s.end, hi)) for s in spans
                 if lo <= s.start < hi and s.name != WINDOW]
        _cache.clear()
        _cache[key] = (WindowSpans(inner, lo, hi, top, profile)
                       if any(s.name in top for s in inner) else None)
    return _cache[key]


def per_cycle(ctx, value_of):
    """`value_of(WindowSpans)` per traced cycle of a `--trace 1` run, or
    None where the trace holds no program spans."""
    if ctx.trace is None or not ctx.trace.get("cycles"):
        return None
    w = window_spans()
    if w is None:
        return None
    return value_of(w) / ctx.trace["cycles"]


def breakdown(trace_dir=None, top: int = 10,
              device_prefix: str = trace_reduce.DEVICE_PLANE,
              op_line=trace_reduce.is_op_line) -> dict:
    """Everything this module reads from one traced run, per cycle where a
    cycle is one top-level `forest.balance`; the idle gaps are those of the
    first device plane (`device_prefix`, ops from `op_line`)."""
    w = window_spans(trace_dir)
    if w is None:
        raise LookupError("no program spans in the trace")
    tops = top_level(w.spans, w.top)
    cycles = max(1, sum(1 for s in tops if s.name == "forest.balance"))
    fetches = w.named(FETCH)
    span_s = {}
    for name in sorted({s.name for s in w.spans}):
        group = w.named(name)
        span_s[name] = [seconds(group) / cycles, host_seconds(group, fetches) / cycles]
    planes = trace_reduce.events(w.profile, device_prefix, op_line)
    gaps = []
    if planes:
        m = trace_reduce.merge([(s, e) for _, s, e in planes[sorted(planes)[0]]],
                               w.lo, w.hi)
        gaps = sorted(trace_reduce.gaps(m, w.lo, w.hi), key=lambda g: g[0] - g[1])
    named = name_gaps(gaps, w.spans)
    # idle and fetch seconds per cycle by span, a fetch's by its site
    idle_by_span: dict[str, float] = {}
    for h, (name, secs) in zip(innermost(gaps, w.spans), named):
        key = f"fetch:{stats(h).get('site')}" if name == FETCH else name
        idle_by_span[key] = idle_by_span.get(key, 0.0) + secs / cycles
    fetch_s_by_site: dict[str, float] = {}
    for f in fetches:
        key = f"fetch:{stats(f).get('site')}"
        fetch_s_by_site[key] = fetch_s_by_site.get(key, 0.0) + (f.end - f.start) / 1e9 / cycles
    window_ns = w.hi - w.lo
    return {
        "window_s": window_ns / 1e9,
        "cycles": cycles,
        "spans_per_cycle": sum(1 for s in w.spans if s.name not in PHASES) / cycles,
        "outside_top_share": 1.0 - seconds(tops) * 1e9 / window_ns,
        "balance_rounds_per_cycle": len(w.named("balance.round")) / cycles,
        "adapt_top_host_s": w.host_s("forest.adapt", top_only=True) / cycles,
        "span_s": span_s,
        "idle_gaps": named[:top],
        "idle_s_by_span": dict(sorted(idle_by_span.items(), key=lambda kv: -kv[1])),
        "fetch_s_by_site": dict(sorted(fetch_s_by_site.items(), key=lambda kv: -kv[1])),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--trace-dir", default=str(TRACE_DIR))
    args = ap.parse_args(argv)
    print(json.dumps(breakdown(args.trace_dir)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
