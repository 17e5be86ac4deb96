"""Reduction of a JAX profiler trace to the numbers the benchmark reports.

A trace is the `.xplane.pb` that `jax.profiler` writes.  Device time comes
from the op-level line of each device plane (`/device:TPU:<n>`, line
`XLA Ops`); the host's phases come from the `TraceAnnotation` spans the
harness writes around the window and around each phase.  Both share the
trace's clock.

* busy: the union of the device-op intervals inside the window, averaged
  over the device planes;
* idle gaps: the stretches of the window with no device op, each named by
  the host phase that holds its midpoint;
* per-program time: the summed durations of the compiled programs of one
  name (line `XLA Modules`);
* Pallas time: the summed durations of the ops that are Pallas kernels.
"""

from __future__ import annotations

import glob
import os
from collections import defaultdict

DEVICE_PLANE = "/device:TPU:"
OP_LINE = "XLA Ops"
MODULE_LINE = "XLA Modules"
# A Pallas kernel runs as an XLA custom call with this target; the op's
# event name is its HLO text, which names the target.
PALLAS_MARK = 'custom_call_target="tpu_custom_call"'


def load(trace_dir: str):
    """The newest `.xplane.pb` under `trace_dir`, read by JAX."""
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return ProfileData.from_file(max(files, key=os.path.getmtime))


def is_op_line(name: str) -> bool:
    return name == OP_LINE


def is_module_line(name: str) -> bool:
    return name == MODULE_LINE


def events(profile, plane_prefix: str, op_line=is_op_line):
    """{plane name: [(name, start_ns, end_ns), ...]} for the planes whose
    name starts with `plane_prefix`, from the lines `op_line(name)` picks."""
    out = {}
    for plane in profile.planes:
        if not plane.name.startswith(plane_prefix):
            continue
        evs = []
        for line in plane.lines:
            if not op_line(line.name):
                continue
            for ev in line.events:
                evs.append((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns))
        out[plane.name] = evs
    return out


def spans(profile, names) -> list:
    """[(name, start_ns, end_ns)] of the host events named in `names` (the
    harness's `TraceAnnotation`s), in start order."""
    names = set(names)
    found = []
    for plane in profile.planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in names:
                    found.append((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns))
    return sorted(found, key=lambda s: s[1])


def merge(intervals, lo: float, hi: float) -> list:
    """The union of `(start, end)` intervals clipped to [lo, hi], as sorted
    disjoint intervals."""
    out = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(iv) for iv in out]


def covered(merged) -> float:
    return float(sum(e - s for s, e in merged))


def gaps(merged, lo: float, hi: float) -> list:
    """The stretches of [lo, hi] that no merged interval covers."""
    out, t = [], lo
    for s, e in merged:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def phase_at(phases, t: float) -> str:
    """Name of the phase span holding time t ('between phases' if none)."""
    for name, s, e in phases:
        if s <= t < e:
            return name
    return "between phases"


def program_name(event_name: str) -> str:
    """`jit_need_fn(1234...)` -> `jit_need_fn`: a program's name without
    the fingerprint that changes with its shapes."""
    return event_name.split("(", 1)[0]


def reduce(profile, window: str, phase_names, top: int = 10,
           device_prefix: str = DEVICE_PLANE, op_line=is_op_line,
           module_line=is_module_line) -> dict:
    """Busy and idle time of the devices inside the host span `window`.

    Returns {"window_s", "busy_s" (mean over device planes), "devices",
    "pallas_s" (summed over planes: ops that are Pallas kernels),
    "programs": {program name: device seconds, summed over planes},
    "device_ops" (the `top` largest programs), "idle_gaps" (the `top`
    longest gaps on the first device, each named by its phase)}.  Raises
    LookupError when the trace holds no window span or no device plane."""
    win = spans(profile, [window])
    if not win:
        raise LookupError(f"no {window!r} span in the trace")
    _, lo, hi = win[0]
    planes = events(profile, device_prefix, op_line)
    if not planes:
        raise LookupError(f"no device plane {device_prefix!r} in the trace")
    modules = events(profile, device_prefix, module_line)
    phases = spans(profile, phase_names)

    def inside(evs):
        return [(n, max(s, lo), min(e, hi)) for n, s, e in evs if e > lo and s < hi]

    programs = defaultdict(float)
    busy, pallas, first_gaps = [], 0.0, None
    for plane in sorted(planes):
        evs = inside(planes[plane])
        m = merge([(s, e) for _, s, e in evs], lo, hi)
        busy.append(covered(m))
        pallas += sum(e - s for n, s, e in evs if PALLAS_MARK in n) / 1e9
        for n, s, e in inside(modules.get(plane, [])):
            programs[program_name(n)] += (e - s) / 1e9
        if first_gaps is None:
            first_gaps = gaps(m, lo, hi)
    first_gaps.sort(key=lambda g: g[0] - g[1])
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy) / len(busy) / 1e9,
        "devices": len(busy),
        "pallas_s": pallas,
        "programs": dict(programs),
        "device_ops": [[n, s] for n, s in
                       sorted(programs.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[phase_at(phases, (s + e) / 2), (e - s) / 1e9]
                      for s, e in first_gaps[:top]],
    }
