"""Pallas SFC kernels vs pure-jnp oracles: shape/level sweeps, exact equality."""

import jax.numpy as jnp
import numpy as np
import pytest

from _helpers import rand_simplices
from repro.core import u64 as u64m
from repro.core.ops import get_ops
from repro.core.types import ECLASS_HEX, ECLASS_SIMPLEX
from repro.kernels import ops as kops
from repro.kernels import ref as kref


SHAPES = [7, 250]  # small: interpret-mode compiles are expensive on 1 CPU core


@pytest.mark.parametrize("d", [2, 3])
@pytest.mark.parametrize("n", SHAPES)
def test_morton_key_kernel(d, n):
    o = get_ops(d)
    s = rand_simplices(d, n, seed=n, max_level=o.L)
    hi, lo = kops.morton_key(d, s)
    # oracle needs the padded key of the element itself
    want = o.morton_key(s)
    np.testing.assert_array_equal(np.asarray(hi), np.asarray(want.hi))
    np.testing.assert_array_equal(np.asarray(lo), np.asarray(want.lo))


@pytest.mark.parametrize("d", [2, 3])
@pytest.mark.parametrize("n", SHAPES)
def test_decode_kernel_roundtrip(d, n):
    o = get_ops(d)
    s = rand_simplices(d, n, seed=n + 1, max_level=o.L)
    key = o.morton_key(s)
    out = kops.decode(d, key, s.level)
    np.testing.assert_array_equal(np.asarray(out.anchor), np.asarray(s.anchor))
    np.testing.assert_array_equal(np.asarray(out.stype), np.asarray(s.stype))


@pytest.mark.parametrize("d", [2, 3])
@pytest.mark.parametrize("n", [130])
def test_face_neighbor_kernel(d, n):
    o = get_ops(d)
    s = rand_simplices(d, n, seed=n + 2, max_level=o.L)
    for f in range(d + 1):
        nb, dual = kops.face_neighbor(d, s, f)
        want_nb, want_dual = o.face_neighbor(s, jnp.int32(f))
        np.testing.assert_array_equal(np.asarray(nb.anchor), np.asarray(want_nb.anchor))
        np.testing.assert_array_equal(np.asarray(nb.stype), np.asarray(want_nb.stype))
        np.testing.assert_array_equal(np.asarray(dual), np.asarray(want_dual))


@pytest.mark.slow
@pytest.mark.parametrize("d", [2, 3])
@pytest.mark.parametrize("n", [130])
def test_successor_kernel(d, n):
    o = get_ops(d)
    rng = np.random.default_rng(n + 3)
    lv = rng.integers(1, 7, size=n)
    ids = np.array([rng.integers(0, o.num_elements(l) - 1) for l in lv], np.uint64)
    s = o.from_linear_id(u64m.from_int(ids), jnp.asarray(lv, jnp.int32))
    out = kops.successor(d, s)
    want = o.successor(s)
    np.testing.assert_array_equal(np.asarray(out.anchor), np.asarray(want.anchor))
    np.testing.assert_array_equal(np.asarray(out.stype), np.asarray(want.stype))


@pytest.mark.parametrize("d", [2, 3])
@pytest.mark.parametrize("n", [130])
def test_parent_kernel(d, n):
    o = get_ops(d)
    rng = np.random.default_rng(n + 4)
    lv = rng.integers(1, o.L + 1, size=n)
    ids = np.array([rng.integers(0, min(o.num_elements(l), 2**62)) for l in lv], np.uint64)
    s = o.from_linear_id(u64m.from_int(ids), jnp.asarray(lv, jnp.int32))
    p = kops.parent(d, s)
    want = o.parent(s)
    np.testing.assert_array_equal(np.asarray(p.anchor), np.asarray(want.anchor))
    np.testing.assert_array_equal(np.asarray(p.level), np.asarray(want.level))
    np.testing.assert_array_equal(np.asarray(p.stype), np.asarray(want.stype))
    iloc = kops.local_index(d, s)
    np.testing.assert_array_equal(np.asarray(iloc), np.asarray(o.local_index(s)))


@pytest.mark.parametrize("d", [2, 3])
@pytest.mark.parametrize("n", [130])
def test_children_kernel(d, n):
    o = get_ops(d)
    s = rand_simplices(d, n, seed=n + 5, max_level=o.L - 1)
    kids = kops.children(d, s)
    want = o.children_tm(s)
    np.testing.assert_array_equal(np.asarray(kids.anchor), np.asarray(want.anchor))
    np.testing.assert_array_equal(np.asarray(kids.level), np.asarray(want.level))
    np.testing.assert_array_equal(np.asarray(kids.stype), np.asarray(want.stype))


@pytest.mark.parametrize("d", [2, 3])
@pytest.mark.parametrize("n", [130])
def test_inside_root_kernel(d, n):
    """Face neighbors step outside the root: the interesting inputs."""
    o = get_ops(d)
    s = rand_simplices(d, n, seed=n + 6, max_level=o.L)
    got = kops.is_inside_root(d, s)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(o.is_inside_root(s)))
    for f in range(d + 1):
        nb, _ = o.face_neighbor(s, jnp.int32(f))
        got = kops.is_inside_root(d, nb)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(o.is_inside_root(nb)))


@pytest.mark.parametrize("d", [2, 3])
@pytest.mark.parametrize("n", [130])
def test_face_sweep_kernel(d, n):
    """The fused all-faces kernel equals its composed oracle on every output
    tile (neighbor coords/type, dual, inside mask, morton-key words)."""
    o = get_ops(d)
    s = rand_simplices(d, n, seed=n + 7, max_level=o.L)
    fields = [s.anchor[..., k] for k in range(d)]
    want = kref.face_sweep_ref(d, *fields, s.level, s.stype)
    nb, dual, inside, key = kops.face_sweep(d, s)
    got = (
        *[nb.anchor[..., k].T for k in range(d)], nb.stype.T, dual.T,
        inside.astype(jnp.int32).T, key.hi.T, key.lo.T,
    )
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@pytest.mark.parametrize("d", [2, 3])
@pytest.mark.parametrize("n", SHAPES)
def test_owner_rank_kernel(d, n):
    from repro.core.batch import _pad_markers

    o = get_ops(d)
    rng = np.random.default_rng(n + 9)
    P = 5
    mt = np.sort(rng.integers(0, 3, P)).astype(np.int32)
    mk = rng.integers(0, 1 << (d * o.L), P).astype(np.uint64)
    order = np.lexsort((mk, mt))
    mt_p, mk_p = _pad_markers(mt[order], mk[order])
    mkey = u64m.from_int(mk_p)
    t = jnp.asarray(rng.integers(0, 3, n), jnp.int32)
    key = u64m.from_int(rng.integers(0, 1 << (d * o.L), n).astype(np.uint64))
    got = kops.owner_rank(key, t, (jnp.asarray(mt_p), mkey))
    want = kref.owner_rank_ref(
        np.asarray(t), np.asarray(key.hi), np.asarray(key.lo),
        mt_p, np.asarray(mkey.hi), np.asarray(mkey.lo))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("eclass", [ECLASS_SIMPLEX, ECLASS_HEX], ids=["simplex", "hex"])
def test_key_kernels_at_level_extremes(eclass):
    """The rolled level loops at their ends, d = 3: level 0 (no digit),
    level 21 (every digit, the last element's successor wrapping to the
    first), and the level-11 digit at bits 30-32 that straddles the two
    key words, each of its bits alone, all set and all clear."""
    d = 3
    o = get_ops(d, eclass)
    L = o.L
    straddle = [b << 30 for b in (1, 2, 4, 7)]
    at_l = [0, 1, (1 << 63) - 1, (1 << 63) - 2, (1 << 30) - 1, 1 << 33,
            (1 << 63) - 1 - (7 << 30)] + straddle
    ids = [0] + at_l + [k >> 3 * (L - 11) for k in straddle + [(1 << 63) - 1]]
    lv = [0] + [L] * len(at_l) + [11] * 5
    s = o.from_linear_id(u64m.from_int(np.array(ids, np.uint64)),
                         jnp.asarray(lv, jnp.int32))

    key = kops.morton_key(d, s, eclass=eclass)
    ref_key = o.morton_key(s)
    np.testing.assert_array_equal(np.asarray(key.hi), np.asarray(ref_key.hi))
    np.testing.assert_array_equal(np.asarray(key.lo), np.asarray(ref_key.lo))
    for got, want in ((kops.decode(d, ref_key, s.level, eclass=eclass), s),
                      (kops.successor(d, s, eclass=eclass), o.successor(s))):
        np.testing.assert_array_equal(np.asarray(got.anchor), np.asarray(want.anchor))
        np.testing.assert_array_equal(np.asarray(got.stype), np.asarray(want.stype))
    fields = [s.anchor[..., k] for k in range(d)]
    want = kref.face_sweep_ref(d, *fields, s.level, s.stype, eclass=eclass)
    nb, dual, inside, key = kops.face_sweep(d, s, eclass=eclass)
    got = (*[nb.anchor[..., k].T for k in range(d)], nb.stype.T, dual.T,
           inside.astype(jnp.int32).T, key.hi.T, key.lo.T)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@pytest.mark.slow
@pytest.mark.parametrize("d", [2, 3])
def test_kernel_block_sizes(d):
    o = get_ops(d)
    s = rand_simplices(d, 100, seed=99, max_level=o.L)
    for block in (64, 256):
        hi, lo = kops.morton_key(d, s, block)
        want = o.morton_key(s)
        np.testing.assert_array_equal(np.asarray(hi), np.asarray(want.hi))
        np.testing.assert_array_equal(np.asarray(lo), np.asarray(want.lo))


@pytest.mark.parametrize("d", [2, 3])
def test_ref_module_consistency(d):
    """kernels.ref (the documented oracle) equals core.ops on raw arrays."""
    o = get_ops(d)
    s = rand_simplices(d, 256, seed=5, max_level=o.L)
    fields = [s.anchor[..., k] for k in range(d)]
    hi, lo = kref.morton_key_ref(d, *fields, s.stype)
    want = o.morton_key(s)
    np.testing.assert_array_equal(np.asarray(hi), np.asarray(want.hi))
    np.testing.assert_array_equal(np.asarray(lo), np.asarray(want.lo))
    outs = kref.decode_ref(d, hi, lo, s.level)
    np.testing.assert_array_equal(np.asarray(outs[d]), np.asarray(s.stype))
    raw = (*fields, s.level, s.stype)
    pouts = kref.parent_ref(d, *raw)
    want_p = o.parent(s)
    np.testing.assert_array_equal(np.asarray(pouts[d]), np.asarray(want_p.stype))
    np.testing.assert_array_equal(np.asarray(pouts[d + 1]), np.asarray(o.local_index(s)))
    couts = kref.children_ref(d, *raw)
    np.testing.assert_array_equal(np.asarray(couts[d]), np.asarray(o.children_tm(s).stype))
    np.testing.assert_array_equal(
        np.asarray(kref.is_inside_root_ref(d, *raw)), np.asarray(o.is_inside_root(s))
    )
    souts = kref.face_sweep_ref(d, *raw)
    for f in range(d + 1):
        nb, dual = o.face_neighbor(s, jnp.int32(f))
        np.testing.assert_array_equal(np.asarray(souts[d][..., f]), np.asarray(nb.stype))
        np.testing.assert_array_equal(np.asarray(souts[d + 1][..., f]), np.asarray(dual))
        np.testing.assert_array_equal(
            np.asarray(souts[d + 2][..., f]).astype(bool),
            np.asarray(o.is_inside_root(nb)))
        want_k = o.morton_key(nb)
        np.testing.assert_array_equal(np.asarray(souts[d + 3][..., f]), np.asarray(want_k.hi))
        np.testing.assert_array_equal(np.asarray(souts[d + 4][..., f]), np.asarray(want_k.lo))
