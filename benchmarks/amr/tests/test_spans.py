"""The reduction of the program's own spans (`span_reduce.py`), on made-up
planes and on a small trace recorded on the CPU, and the per-layer
metrics that read them."""

from __future__ import annotations

import time
from types import SimpleNamespace as NS

import pytest

import registry
import span_reduce as S
import trace_reduce as T


def _ev(name, start, end, **stats):
    return NS(name=name, start_ns=float(start), duration_ns=float(end - start),
              stats=list(stats.items()))


def _profile(device_events, host_events):
    return NS(planes=[
        NS(name="/device:TPU:0", lines=[NS(name="XLA Ops", events=device_events)]),
        NS(name="/host:CPU", lines=[NS(name="python", events=host_events)])])


# a harness phase around a program span with two steps; the second step
# waits on the device in a fetch
HOST = [_ev("window", 0, 1000), _ev("balance", 0, 1000),
        _ev("forest.balance", 10, 990),
        _ev("balance.round", 20, 980, round=0, elements=5),
        _ev("balance.sweep", 30, 300),
        _ev("balance.eval", 300, 900),
        _ev("fetch", 400, 800, site="eval_2to1", pairs_live=20, pairs_padded=32)]
NAMES = ["window", "balance", "forest.balance", "balance.round", "balance.sweep",
         "balance.eval", "fetch"]


def test_gaps_are_named_by_the_innermost_span():
    spans = S.host_spans(_profile([], HOST), NAMES)
    gaps = [(100, 200), (850, 890), (982, 988), (995, 1000), (-10, -5), (500, 600)]
    assert S.name_gaps(gaps, spans) == [
        ["balance.sweep", pytest.approx(100e-9)], ["balance.eval", pytest.approx(40e-9)],
        ["forest.balance", pytest.approx(6e-9)], ["balance", pytest.approx(5e-9)],
        ["between phases", pytest.approx(5e-9)], ["fetch", pytest.approx(100e-9)]]


def test_only_phase_spans_name_gaps_as_the_phase_reduction_does():
    host = [_ev("window", 0, 1000), _ev("adapt", 0, 300), _ev("balance", 300, 900),
            _ev("ghost", 900, 1000)]
    dev = [_ev("op", 100, 150), _ev("op", 200, 500), _ev("op", 700, 950)]
    r = T.reduce(_profile(dev, host), "window", S.PHASES)
    spans = S.host_spans(_profile(dev, host), ["window", *S.PHASES])
    m = T.merge([(e.start_ns, e.start_ns + e.duration_ns) for e in dev], 0, 1000)
    gaps = sorted(T.gaps(m, 0, 1000), key=lambda g: g[0] - g[1])
    assert S.name_gaps(gaps, spans) == r["idle_gaps"]


def test_host_time_leaves_out_the_fetches():
    spans = S.host_spans(_profile([], HOST), NAMES)
    w = S.WindowSpans([s for s in spans if s.name != "window"], 0, 1000,
                      ("forest.balance",), None)
    assert w.host_s("forest.balance") == pytest.approx((980 - 400) * 1e-9)
    assert w.host_s("balance.eval") == pytest.approx((600 - 400) * 1e-9)
    assert S.seconds(w.named("fetch")) == pytest.approx(400e-9)
    assert w.stat_sum("fetch", "pairs_live") == 20
    assert w.stat_sum("fetch", "pairs_padded") == 32
    # a nested top-level span is not top level
    nested = S.host_spans(_profile([], [_ev("forest.balance", 0, 100),
                                        _ev("forest.adapt", 10, 50),
                                        _ev("forest.adapt", 200, 300)]),
                          ["forest.balance", "forest.adapt"])
    top = S.top_level(nested, ("forest.adapt", "forest.balance"))
    assert [(s.name, s.start) for s in top] == [("forest.balance", 0),
                                                ("forest.adapt", 200)]


def test_metrics_read_nothing_without_program_spans(monkeypatch, tmp_path):
    ctx = NS(trace={"cycles": 2})
    monkeypatch.setattr(S, "program_span_names", lambda: None)
    for name in ("balance_rounds_per_cycle", "need_pairs_per_cycle",
                 "need_live_share", "host_wait_s", "balance_host_s", "adapt_host_s"):
        assert registry.load("metrics", name).read(ctx) is None, name
    monkeypatch.undo()
    monkeypatch.setattr(S, "TRACE_DIR", tmp_path)   # no trace at all
    assert registry.load("metrics", "host_wait_s").read(ctx) is None
    assert registry.load("metrics", "host_wait_s").read(NS(trace=None)) is None


@pytest.mark.filterwarnings("ignore::DeprecationWarning")
def test_recorded_cpu_trace_names_the_program_step(tmp_path):
    """A sleep inside `balance.refine`, inside the program's
    `forest.balance`, inside the harness's `balance`: the idle gap it leaves
    is named `balance.refine`, and the metrics read the spans per cycle."""
    import jax
    import jax.numpy as jnp

    from repro.core import telemetry

    f = jax.jit(lambda x: jnp.sin(x) @ jnp.cos(x).T)
    x = jnp.ones((128, 128))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("window"):
        for _ in range(2):
            with jax.profiler.TraceAnnotation("adapt"), telemetry.span("forest.adapt"):
                with telemetry.span("fetch", site="children"):
                    f(x).block_until_ready()
            with jax.profiler.TraceAnnotation("balance"), telemetry.span("forest.balance"):
                with telemetry.span("balance.round", round=0, elements=7):
                    with telemetry.span("fetch", site="eval_2to1",
                                        pairs_live=28, pairs_padded=64):
                        f(x).block_until_ready()
                    with telemetry.span("balance.refine"):
                        time.sleep(0.03)
    jax.profiler.stop_trace()
    # XLA:CPU runs the ops on host threads: stand them in for a device
    b = S.breakdown(tmp_path, device_prefix="/host:CPU",
                    op_line=lambda n: n.startswith("tf_XLA"))
    assert b["cycles"] == 2 and b["balance_rounds_per_cycle"] == 1
    name, secs = b["idle_gaps"][0]
    assert name == "balance.refine" and secs >= 0.025
    assert b["outside_top_share"] < 0.5
    assert set(b["fetch_s_by_site"]) == {"fetch:children", "fetch:eval_2to1"}
    assert b["idle_s_by_span"]["balance.refine"] >= 0.0125
    ctx = NS(trace={"cycles": 2})
    S.TRACE_DIR, saved = tmp_path, S.TRACE_DIR
    try:
        read = {m: registry.load("metrics", m).read(ctx) for m in (
            "balance_rounds_per_cycle", "need_pairs_per_cycle", "need_live_share",
            "host_wait_s", "balance_host_s", "adapt_host_s")}
    finally:
        S.TRACE_DIR = saved
    assert read["balance_rounds_per_cycle"] == 1
    assert read["need_pairs_per_cycle"] == 64
    assert read["need_live_share"] == pytest.approx(28 / 64)
    assert 0 < read["host_wait_s"] and read["balance_host_s"] >= 0.025
    assert read["adapt_host_s"] < read["balance_host_s"]


def test_setup_lower_s_is_left_out_when_the_window_compiles():
    from repro.launch.compile_cache import compile_counter

    reader = registry.load("metrics", "setup_lower_s")
    assert reader.read(NS(counters={"compiles": 0})) == compile_counter()["lower_s"]
    assert reader.read(NS(counters={"compiles": 1})) is None
