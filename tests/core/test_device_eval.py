"""Device-resident fused eval stage (`BatchedOps.sweep_full` / `eval_2to1` /
`eval_cache` / `eval_route`).

The reference backend computes every mask eagerly on the host and is the
oracle; the jnp and pallas backends must match it bit for bit — need-masks,
boundary masks, cache-eval masks, and the compacted routing rows — over all
multitree fixtures x adapt patterns x partition sizes.  On top of parity the
suite pins the per-round budget that makes the fusion a speedup at all: O(1)
batched-op dispatches, at most two host materializations per rank per round,
and ZERO jit retraces once a padding bucket is warm.
"""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _helpers import rand_simplices
from repro.core import batch, get_ops
from repro.core import cmesh as C
from repro.core import forest as F
from repro.core import u64 as u64m
from repro.core.types import Simplex

DEVICE_BACKENDS = ["jnp", pytest.param("pallas", marks=pytest.mark.slow)]

FIXTURES = {
    # name: (d, cmesh factory, base level, deep level)
    "kuhn2_d2": (2, lambda: C.cmesh_unit_cube(2), 2, 4),
    "kuhn6_d3": (3, lambda: C.cmesh_unit_cube(3), 1, 3),
    "periodic_d2": (2, lambda: C.cmesh_unit_cube(2, periodic=(True, True)), 2, 4),
    "rotated_pair": (2, C.cmesh_rotated_pair, 2, 4),
    "single_tree_d3": (3, lambda: None, 1, 3),
}


def _mk_forests(name, P, pattern, seed=0):
    d, mk_cmesh, base, deep = FIXTURES[name]
    cm = mk_cmesh()
    K = cm.num_trees if cm is not None else 2
    comm = F.SimComm(P)
    fs = F.new_uniform(d, K, base, comm, cmesh=cm)
    if pattern == "corner":
        def fn(tree, elems):
            a = np.asarray(elems.anchor)
            l = np.asarray(elems.level)
            return ((a.sum(1) == 0) & (l < deep)).astype(np.int32)

        fs = [F.adapt(f, fn, recursive=True) for f in fs]
    else:
        rng = np.random.default_rng(seed)

        def fn(tree, elems):
            return (rng.random(elems.level.shape[0]) < 0.3).astype(np.int32)

        fs = [F.adapt(f, fn, recursive=False) for f in fs]
    return fs, comm


def _sweep_and_table(bops, f):
    """Mirror the balance/ghost layer construction for one rank."""
    if f.num_local == 0:
        return None, None
    table = bops.upload_table(f.tree, f.keys, f.level)
    if f.cmesh is None:
        return bops.sweep_full(f.simplices(), f.tree), table
    sw = F.face_sweep_layer(f, f.tree, f.simplices())
    return bops.sweep_from_host(
        sw.tgt, sw.nkey, sw.valid, sw.dual, sw.level), table


def _route_rows(rp):
    return (np.asarray(rp.tree), np.asarray(rp.key), np.asarray(rp.level),
            np.asarray(rp.dual), np.asarray(rp.first), np.asarray(rp.last))


@pytest.mark.parametrize("backend", DEVICE_BACKENDS)
@pytest.mark.parametrize("pattern", ["corner", "random"])
@pytest.mark.parametrize("name", sorted(FIXTURES))
def test_fused_eval_backend_parity(name, pattern, backend):
    """reference == device backend for every fused eval output: the 2:1
    need-mask and boundary mask, the remote-cache need-mask, and the
    compacted (tree, key, level, dual, first, last) routing rows — on every
    rank of every fixture, including the cmesh cross-tree sweeps."""
    fs, comm = _mk_forests(name, 3, pattern)
    mt, mk = F.partition_markers(fs, comm)
    d = fs[0].d
    ref = batch.get_batch_ops(d, "reference")
    dev = batch.get_batch_ops(d, backend)
    # a synthetic remote-leaf cache: every OTHER rank's leaves, lex-sorted —
    # the shape eval_cache sees after balance folds replies in
    for i, f in enumerate(fs):
        g = comm.local_ranks[i]
        others = [o for j, o in enumerate(fs) if j != i and o.num_local]
        ct = np.concatenate([o.tree for o in others])
        ck = np.concatenate([o.keys for o in others])
        cl = np.concatenate([o.level for o in others])
        order = np.lexsort((cl, ck, ct))
        sw_r, tb_r = _sweep_and_table(ref, f)
        sw_d, tb_d = _sweep_and_table(dev, f)
        cache_r = ref.upload_table(ct[order], ck[order], cl[order])
        cache_d = dev.upload_table(ct[order], ck[order], cl[order])
        need_r, bm_r = ref.eval_2to1(sw_r, tb_r, mt, mk, g)
        need_d, bm_d = dev.eval_2to1(sw_d, tb_d, mt, mk, g)
        np.testing.assert_array_equal(need_d, need_r, err_msg=f"need rank {g}")
        np.testing.assert_array_equal(bm_d, bm_r, err_msg=f"bmask rank {g}")
        cn_r = ref.eval_cache(sw_r, cache_r, mt, mk, g)
        cn_d = dev.eval_cache(sw_d, cache_d, mt, mk, g)
        np.testing.assert_array_equal(cn_d, cn_r, err_msg=f"cache rank {g}")
        rp_r = _route_rows(ref.eval_route(sw_r, mt, mk, g))
        rp_d = _route_rows(dev.eval_route(sw_d, mt, mk, g))
        for col_d, col_r, what in zip(
                rp_d, rp_r, ("tree", "key", "level", "dual", "first", "last")):
            np.testing.assert_array_equal(
                col_d, col_r, err_msg=f"route {what} rank {g}")


_LEX = np.dtype([("tree", "<i4"), ("key", "<u8")])


@pytest.mark.parametrize("trees", [1, 4])
@pytest.mark.parametrize("rows", [1, 3, 16, 17])
def test_lex_lower_bound_matches_searchsorted(rows, trees):
    """The merged-sort lower bound of the 2:1 eval equals NumPy's
    `searchsorted(side="left")` over (tree, key) on a leaf table padded with
    sentinels as `upload_table` pads it: queries equal to every table row
    (sentinels included), below every row, between the last leaf and the
    sentinels, past the sentinels, and at random across the trees."""
    rng = np.random.default_rng(rows * 10 + trees)
    tree = np.sort(rng.integers(0, trees, rows)).astype(np.int32)
    keys = rng.integers(1, 1 << 63, rows, dtype=np.uint64)
    order = np.lexsort((keys, tree))
    table = batch.get_batch_ops(3, "jnp").upload_table(
        tree[order], keys[order], np.zeros(rows, np.int32))
    lt, lhi, llo, _ = (np.asarray(a) for a in table.dev)
    lk = (lhi.astype(np.uint64) << np.uint64(32)) | llo.astype(np.uint64)
    imax, kmax = np.iinfo(np.int32).max, np.iinfo(np.uint64).max
    qt = np.concatenate([
        lt, np.array([0, -1, trees, imax, imax], np.int32),
        rng.integers(0, trees + 1, 64, dtype=np.int32)])
    qk = np.concatenate([
        lk, np.array([0, kmax, 0, 1, kmax], np.uint64),
        rng.integers(0, 1 << 63, 64, dtype=np.uint64)])
    qk[-8:] = lk[rng.integers(0, len(lk), 8)]  # more exact hits, other trees
    got = jax.jit(batch.lex_lower_bound)(
        lt, lhi, llo, qt, (qk >> np.uint64(32)).astype(np.uint32),
        qk.astype(np.uint32))
    want = np.searchsorted(
        np.rec.fromarrays([lt, lk], dtype=_LEX),
        np.rec.fromarrays([qt, qk], dtype=_LEX), side="left")
    np.testing.assert_array_equal(np.asarray(got), want)


@pytest.mark.parametrize("backend", ["reference"] + DEVICE_BACKENDS)
def test_fused_eval_empty_and_missing_inputs(backend):
    """Empty ranks (sw None) and empty tables short-circuit identically."""
    bops = batch.get_batch_ops(2, backend)
    mt = np.array([0, 1], np.int32)
    mk = np.array([0, 0], np.uint64)
    need, bm = bops.eval_2to1(None, None, mt, mk, 0)
    assert need.shape == (0,) and bm.shape == (0,)
    assert bops.eval_cache(None, None, mt, mk, 0).shape == (0,)
    assert len(bops.eval_route(None, mt, mk, 0).tree) == 0
    assert bops.upload_table(
        np.zeros(0, np.int32), np.zeros(0, np.uint64), np.zeros(0, np.int32)
    ) is None


@pytest.mark.parametrize("name", ["kuhn2_d2", "single_tree_d3"])
def test_balance_round_dispatch_budget(name):
    """The O(1)-dispatch invariant: one balanced no-op round issues exactly
    one face_sweep + one eval_route + one eval_2to1 per non-empty rank and
    ZERO per-face / per-element fallback dispatches."""
    fs, comm = _mk_forests(name, 2, "corner")
    fs = F.balance(fs, comm)
    nonempty = sum(1 for f in fs if f.num_local)
    batch.reset_dispatch_counts()
    F.balance(fs, comm)
    counts = batch.dispatch_counts()
    assert counts.get("face_sweep", 0) == nonempty, counts
    assert counts.get("eval_route", 0) == nonempty, counts
    assert counts.get("eval_2to1", 0) == nonempty, counts
    assert counts.get("eval_cache", 0) == 0, counts
    for banned in ("face_neighbor", "is_inside_root", "owner_rank"):
        assert counts.get(banned, 0) == 0, counts
    batch.reset_dispatch_counts()


@pytest.mark.parametrize("backend", DEVICE_BACKENDS)
@pytest.mark.parametrize("name", ["kuhn2_d2", "single_tree_d3"])
def test_balance_round_host_fetch_budget(name, backend):
    """<=2 host materializations per rank per round on the device backends:
    the compacted routing rows and the fused need/boundary masks — never a
    per-field sweep fan-out."""
    with batch.use_backend(backend):
        fs, comm = _mk_forests(name, 2, "corner")
        fs = F.balance(fs, comm)
        nonempty = sum(1 for f in fs if f.num_local)
        batch.reset_host_fetch_counts()
        F.balance(fs, comm)
        fetches = batch.host_fetch_counts()
        assert fetches.get("eval_route", 0) == nonempty, fetches
        assert fetches.get("eval_2to1", 0) == nonempty, fetches
        assert fetches.get("eval_cache", 0) == 0, fetches
        batch.reset_host_fetch_counts()


@pytest.mark.parametrize("backend", DEVICE_BACKENDS)
def test_balance_and_ghost_do_not_retrace(backend):
    """Retrace guard: at a fixed padding bucket the fused programs compile
    once — a second balance+ghost over the same forests must not trace any
    eval program again (stable padded shapes are the point of bucketing)."""
    with batch.use_backend(backend):
        fs, comm = _mk_forests("kuhn2_d2", 2, "corner")
        fs = F.balance(fs, comm)
        F.balance(fs, comm)  # warm every bucket this workload touches
        F.ghost(fs, comm)
        batch.reset_trace_counts()
        F.balance(fs, comm)
        F.ghost(fs, comm)
        traces = batch.trace_counts()
        assert all(v == 0 for v in traces.values()), traces
        batch.reset_trace_counts()


@pytest.mark.parametrize("d", [2, 3])
def test_eval_route_kernel_matches_ref(d):
    """The pallas routing kernel (interpret mode) equals `eval_route_ref`
    elementwise: interval-end key words and [first, last] owner ranks."""
    from repro.core.batch import _pad_markers
    from repro.kernels import ref as kref
    from repro.kernels import sfc as ksfc

    o = get_ops(d)
    rng = np.random.default_rng(d)
    N, nf, P = 128, d + 1, 5
    lvl = rng.integers(0, o.L + 1, (N, nf)).astype(np.int32)
    shift = (np.uint64(d) * (np.uint64(o.L) - lvl.astype(np.uint64)))
    raw = rng.integers(0, 1 << 62, (N, nf), dtype=np.uint64)
    key = (raw >> shift) << shift  # span-aligned, as real neighbor keys are
    key &= np.uint64((1 << (d * o.L)) - 1)
    t = rng.integers(0, 4, (N, nf)).astype(np.int32)
    mt = np.sort(rng.integers(0, 4, P)).astype(np.int32)
    mk = rng.integers(0, 1 << (d * o.L), P).astype(np.uint64)
    order = np.lexsort((mk, mt))
    mt_p, mk_p = _pad_markers(mt[order], mk[order])
    mhi = (mk_p >> np.uint64(32)).astype(np.uint32)
    mlo = mk_p.astype(np.uint32)
    hi = (key >> np.uint64(32)).astype(np.uint32)
    lo = key.astype(np.uint32)
    # the kernel is elementwise over flat (face, element) pairs
    got = ksfc.eval_route_kernel(
        d, *(jnp.asarray(a).reshape(-1) for a in (t, hi, lo, lvl)),
        jnp.asarray(mt_p), jnp.asarray(mhi), jnp.asarray(mlo),
        block=64, interpret=True)
    want = kref.eval_route_ref(d, t, hi, lo, lvl, mt_p, mhi, mlo)
    for g, w, what in zip(got, want, ("end_hi", "end_lo", "first", "last")):
        np.testing.assert_array_equal(
            np.asarray(g).reshape(N, nf), np.asarray(w), err_msg=what)
