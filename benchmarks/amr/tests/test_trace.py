"""The trace reduction: the busy-interval union and the idle share, on
made-up planes and on a small trace recorded on the CPU."""

from __future__ import annotations

import time
from types import SimpleNamespace as NS

import numpy as np
import pytest

import trace_reduce as T


def _ev(name, start, end):
    return NS(name=name, start_ns=float(start), duration_ns=float(end - start))


def _profile(device_lines, host_events):
    planes = [NS(name=f"/device:TPU:{i}", lines=[NS(name="XLA Ops", events=evs),
                                                 NS(name="XLA Modules", events=[
                                                     _ev("jit_step(123)", 0, 10**9)])])
              for i, evs in enumerate(device_lines)]
    planes.append(NS(name="/host:CPU", lines=[NS(name="python", events=host_events)]))
    return NS(planes=planes)


def test_merge_covers_the_union_once():
    m = T.merge([(5, 9), (0, 2), (1, 3), (8, 12), (20, 30)], 0, 25)
    assert m == [(0, 3), (5, 12), (20, 25)]
    assert T.covered(m) == 3 + 7 + 5
    assert T.gaps(m, 0, 25) == [(3, 5), (12, 20)]


def test_reduce_busy_idle_and_gap_names():
    host = [_ev("window", 100, 1100), _ev("adapt", 100, 400),
            _ev("balance", 400, 1000), _ev("ghost", 1000, 1100)]
    kernel = '%custom-call.3 = s32[8] custom-call(), custom_call_target="tpu_custom_call"'
    dev0 = [_ev("fusion.1", 50, 150), _ev("fusion.2", 120, 200),   # overlap
            _ev(kernel, 500, 700), _ev("fusion.1", 1050, 1200)]
    dev1 = [_ev("fusion.1", 100, 600)]
    r = T.reduce(_profile([dev0, dev1], host), "window", ["adapt", "balance", "ghost"])
    assert r["window_s"] == pytest.approx(1000e-9)
    # plane 0: [100,200) + [500,700) + [1050,1100) = 350; plane 1: 500
    assert r["busy_s"] == pytest.approx((350 + 500) / 2 * 1e-9)
    assert r["devices"] == 2
    # plane 0's gaps, longest first: [700, 1050) in balance, [200, 500) in adapt
    assert r["idle_gaps"] == [["balance", pytest.approx(350e-9)],
                              ["adapt", pytest.approx(300e-9)]]
    assert r["pallas_s"] == pytest.approx(200e-9)
    # programs come from the module line, fingerprints stripped, clipped
    assert r["device_ops"] == [["jit_step", pytest.approx(2 * 1000e-9)]]


def test_reduce_refuses_a_trace_without_device_or_window():
    host = [_ev("window", 0, 10)]
    with pytest.raises(LookupError):
        T.reduce(_profile([], host), "window", [])
    with pytest.raises(LookupError):
        T.reduce(_profile([[_ev("op", 0, 5)]], []), "window", [])


def test_recorded_cpu_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.sin(x) @ jnp.cos(x).T)
    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("window"):
        with jax.profiler.TraceAnnotation("adapt"):
            for _ in range(5):
                f(x).block_until_ready()
        with jax.profiler.TraceAnnotation("balance"):
            time.sleep(0.05)
    jax.profiler.stop_trace()
    prof = T.load(str(tmp_path))
    (name, lo, hi), = T.spans(prof, ["window"])
    assert hi - lo >= 0.05e9
    # XLA:CPU runs the ops on host threads: stand them in for a device
    r = T.reduce(prof, "window", ["adapt", "balance"], device_prefix="/host:CPU",
                 op_line=lambda n: n.startswith("tf_XLA"), module_line=lambda n: False)
    evs = [(s, e) for _, s, e in T.events(prof, "/host:CPU",
                                          lambda n: n.startswith("tf_XLA"))["/host:CPU"]]
    assert evs
    # brute force on a 1 us grid
    grid = np.zeros(int((hi - lo) // 1000) + 1, bool)
    for s, e in evs:
        a, b = max(s, lo), min(e, hi)
        if b > a:
            grid[int((a - lo) // 1000): int(np.ceil((b - lo) / 1000))] = True
    assert r["busy_s"] == pytest.approx(grid.sum() * 1e-6, rel=0.05, abs=5e-5)
    assert 0 < r["busy_s"] < r["window_s"]
    # the sleep is idle, and lies in "balance"
    assert r["idle_gaps"][0][0] == "balance"
    assert r["idle_gaps"][0][1] >= 0.04
