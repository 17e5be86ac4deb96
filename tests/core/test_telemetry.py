"""The program's span and counter registry (`repro.core.telemetry`).

The batch layer's three counter families are views over the registry and
keep the keys and values they had; in a profiler trace Balance marks each
eval round with a span and the device 2:1 eval's fetch spans carry the
(face, element) pairs it tests and pads; `compile_counter` times tracing
and lowering by program; and the spans nest the way the forest drivers run.
"""

import re
from pathlib import Path

import numpy as np
import pytest

from repro.core import batch, telemetry
from repro.core import forest as F
from repro.core.batch import _bucket
from repro.core.cmesh import cmesh_hex_brick
from repro.core.comm import CommHandle

SRC = Path(__file__).resolve().parents[2] / "src" / "repro"


def _point_refined(depth: int):
    """One quad tree at uniform level 1 (quadrants Q00, Q10, Q01, Q11 of
    the unit square), with Q00 refined toward the centre point p = (1/2,
    1/2) down to level `depth`: each level refines only the quad whose
    upper-right corner is p.  Not yet 2:1 once depth > 2."""
    comm = F.LocalComm()
    fs = F.new_uniform(2, 1, 1, comm, cmesh=cmesh_hex_brick(2, (1, 1)))
    L = fs[0].ops.L
    half = np.int64(1) << (L - 1)

    def toward_p(tree, elems):
        a = np.asarray(elems.anchor).astype(np.int64)
        lev = np.asarray(elems.level)
        h = np.int64(1) << (L - lev)
        return ((a[:, 0] + h == half) & (a[:, 1] + h == half)
                & (lev < depth)).astype(np.int32)

    return [F.adapt(f, toward_p, recursive=True) for f in fs], comm


def _spans_of(profile, names):
    out = []
    for plane in profile.planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name in names:
                    out.append((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
                                dict(ev.stats)))
    return sorted(out, key=lambda s: (s[1], -s[2]))


def _recorded(trace_dir, fn):
    """Run `fn()` under the profiler; the program's spans in its trace as
    (name, start, end, stats), outer before inner at equal start."""
    import jax
    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = Path(trace_dir).rglob("*.xplane.pb")
    return _spans_of(ProfileData.from_file(str(path)), set(telemetry.SPANS))


def _balance_rounds(fs, comm, trace_dir) -> int:
    spans = _recorded(trace_dir, lambda: F.balance(fs, comm))
    return sum(1 for s in spans if s[0] == "balance.round")


def _pairs(spans, site):
    """(pairs_live, pairs_padded) summed over the `fetch` spans at `site`."""
    st = [s[3] for s in spans if s[0] == "fetch" and s[3]["site"] == site]
    assert st, site
    return sum(x["pairs_live"] for x in st), sum(x["pairs_padded"] for x in st)


@pytest.mark.filterwarnings("ignore::DeprecationWarning")
def test_a_balanced_forest_takes_one_round(tmp_path):
    with batch.use_backend("jnp"):
        fs, comm = _point_refined(4)
        fs = F.balance(fs, comm)
        assert _balance_rounds(fs, comm, tmp_path) == 1


@pytest.mark.filterwarnings("ignore::DeprecationWarning")
@pytest.mark.parametrize("depth,rounds", [(2, 1), (3, 2), (4, 4)])
def test_ripple_rounds_as_counted_by_hand(depth, rounds, tmp_path):
    """depth 2: Q00's level-2 leaves sit next to the level-1 quads, 2:1
    already, so the one round finds nothing.  depth 3: round 1 refines Q10
    and Q01 (level 1 next to level 3); round 2 finds nothing.  depth 4:
    round 1 refines Q10 and Q01; round 2 their level-2 children at p (next
    to level 4); round 3 Q11, now next to level 3 across both its faces at
    p; round 4 finds nothing."""
    with batch.use_backend("jnp"):
        fs, comm = _point_refined(depth)
        F.balance(fs, comm)  # compile outside the trace
        assert _balance_rounds(fs, comm, tmp_path) == rounds


@pytest.mark.filterwarnings("ignore::DeprecationWarning")
def test_eval_pairs_are_faces_times_leaves_and_bucket(tmp_path):
    with batch.use_backend("jnp"):
        fs, comm = _point_refined(4)
        fs = F.balance(fs, comm)
        f = fs[0]
        n, nf = f.num_local, f.bops.nf
        assert n == 34 and _bucket(n) == 64 and nf == 4
        F.balance(fs, comm)  # compile outside the trace
        # one round: one eval_2to1, no remote cache
        spans = _recorded(tmp_path / "balance", lambda: F.balance(fs, comm))
        assert _pairs(spans, "eval_2to1") == (nf * n, nf * _bucket(n))
        # the cache eval carries the same counts
        h = F._resident_sweep(f, f.bops)
        table = f.bops.upload_table(f.tree, f.keys, f.level)
        spans = _recorded(tmp_path / "cache", lambda: f.bops.eval_cache(
            h, table, np.zeros(1, np.int32), np.zeros(1, np.uint64), 0))
        assert _pairs(spans, "eval_cache") == (nf * n, nf * _bucket(n))


def _corner(deep):
    def fn(tree, elems):
        a = np.asarray(elems.anchor)
        lev = np.asarray(elems.level)
        return ((a.sum(1) == 0) & (lev < deep)).astype(np.int32)
    return fn


def test_batch_counter_views_keep_their_dicts():
    """Adapt, Balance and Ghost of two ranks on the jnp backend: the views
    read what the separate counter dicts read before they became views."""
    with batch.use_backend("jnp"):
        batch.reset_dispatch_counts()
        batch.reset_host_fetch_counts()
        comm = F.SimComm(2)
        fs = F.new_uniform(3, 2, 1, comm)
        fs = [F.adapt(f, _corner(3), recursive=True) for f in fs]
        fs = F.balance(fs, comm)
        F.ghost(fs, comm)
        assert batch.dispatch_counts() == {
            "children": 6, "decode": 2, "eval_2to1": 4, "eval_route": 6,
            "face_sweep": 8, "morton_key": 16, "parent_and_local_index": 8}
        assert batch.host_fetch_counts() == {"eval_2to1": 4, "eval_route": 6}
        # a warm re-run traces nothing, and each view resets only its own
        batch.reset_trace_counts()
        F.balance(fs, comm)
        assert batch.trace_counts() == {}
        batch.reset_dispatch_counts()
        assert batch.dispatch_counts() == {}
        assert batch.host_fetch_counts() == {"eval_2to1": 6, "eval_route": 8}
        batch.count_dispatch("face_sweep")
        assert telemetry.counts()["dispatch.face_sweep"] == 1


def test_lowering_seconds_by_program():
    import jax
    import jax.numpy as jnp

    from repro.launch.compile_cache import compile_counter

    c = compile_counter()
    before = c["lower_s"]

    def telemetry_probe_program(x):
        return jnp.sin(x) * 3 + 1

    jax.jit(telemetry_probe_program)(jnp.arange(7.0)).block_until_ready()
    assert c["lower_s_by_program"]["telemetry_probe_program"] > 0
    assert c["lower_s"] > before
    assert sum(c["lower_s_by_program"].values()) == pytest.approx(c["lower_s"])


def test_span_names_come_from_the_registry():
    with pytest.raises(ValueError):
        telemetry.span("not.a.span")
    with telemetry.span("balance.round", round=0, elements=1):
        pass
    used = set()
    for path in SRC.rglob("*.py"):
        used |= set(re.findall(r'telemetry\.span\(\s*"([^"]+)"', path.read_text()))
    assert used and used <= set(telemetry.SPANS), used - set(telemetry.SPANS)
    assert set(telemetry.TOP_SPANS) <= used


def _holds(outer, inner):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


@pytest.mark.filterwarnings("ignore::DeprecationWarning")
def test_spans_in_a_recorded_trace(tmp_path):
    def cycle():
        b = F.balance(fs, comm)
        F.ghost(b, comm)
        CommHandle(complete=lambda: 7).wait()

    with batch.use_backend("jnp"):
        fs, comm = _point_refined(4)
        F.balance(fs, comm)  # compile outside the trace
        nf = fs[0].bops.nf
        spans = _recorded(tmp_path, cycle)
    named = {}
    for s in spans:
        named.setdefault(s[0], []).append(s)
    (bal,) = named["forest.balance"]
    rounds = named["balance.round"]
    assert [r[3]["round"] for r in rounds] == [0, 1, 2, 3]
    assert all(_holds(bal, r) for r in rounds)
    for step in ("balance.sweep", "balance.answer", "balance.eval",
                 "balance.merge", "balance.eval_cache", "balance.refine"):
        assert named[step] and all(any(_holds(r, s) for r in rounds)
                                   for s in named[step]), step
    # Balance refines through Adapt: a nested forest.adapt under balance.refine
    assert all(any(_holds(r, a) for r in named["balance.refine"])
               for a in named["forest.adapt"])
    (gh,) = named["forest.ghost"]
    assert all(_holds(gh, s) for s in named["ghost.route"] + named["ghost.answer"])
    fetches = named["fetch"]
    sites = {s[3]["site"] for s in fetches}
    assert {"eval_2to1", "eval_route", "morton_key", "children", "face_sweep"} <= sites
    # each round evaluates the whole forest as it stands at the round's start
    sizes = [r[3]["elements"] for r in rounds]
    assert _pairs(spans, "eval_2to1") == (nf * sum(sizes),
                                          nf * sum(_bucket(n) for n in sizes))
    (wait,) = named["comm.wait"]
    assert wait[3]["phase"] == "default"
