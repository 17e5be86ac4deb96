"""Pallas TPU kernels for the SFC hot loops: encode / decode / neighbor / successor.

These are the compute hot-spots of the paper's AMR pipeline (New and Adapt
spend essentially all their time computing consecutive indices, decoding
them, and finding face neighbors — paper Sections 4.5-4.6).

TPU adaptation (vs. the paper's scalar C):
  * Elements are processed in VMEM tiles of BLOCK lanes; each field (x, y, z,
    level, type) is its own int32 vector — SoA keeps loads contiguous and
    VPU-friendly (8x128 lanes).
  * The (cube-id, type) transition tables are *fused into the instruction
    stream* as masked-sum lookups over <= 48 packed constants per level —
    TPUs have no per-lane gather, so table lookups become compare/select
    chains on vregs, which the VPU executes at full width.
  * The 64-bit consecutive index is carried as two uint32 words (TPU vector
    units have no 64-bit integer type); see `repro.core.u64`.
  * Each per-level loop is one `lax.fori_loop` (`_encode_expr`,
    `_decode_expr`), so Mosaic emits its body once, not MAXLEVEL times: the
    unrolled d=3 face sweep's module text was 7x the hex one and took
    seconds to lower per bucket.  A shift by the loop's level is clamped
    to [0, 31]: a uint32 shift by 32 or more is not defined alike on every
    backend.

Each kernel has a pure-jnp oracle in `repro.kernels.ref` (delegating to
`repro.core.ops`), and `repro.kernels.ops` wraps them with padding + jit.
On a TPU they compile through Mosaic; on the CPU (tests, rehearsals) they
run under `interpret=True`.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.core import u64 as u64m
from repro.core.tables import MAXLEVEL, get_tables
from repro.core.types import ECLASS_HEX, ECLASS_SIMPLEX

DEFAULT_BLOCK = 1024


def faces_per_element(d: int, eclass: int = ECLASS_SIMPLEX) -> int:
    """The per-class face count that sizes face-sweep / eval-route tiles."""
    return 2 * d if eclass == ECLASS_HEX else d + 1


# ----------------------------------------------------------- packed tables
@functools.lru_cache(maxsize=None)
def _packed_tables(d: int):
    t = get_tables(d)
    nc, nt = t.num_children, t.num_types
    enc = [0] * (nt * nc)   # idx = b * nc + cid -> iloc | parent_type << 3
    dec = [0] * (nt * nc)   # idx = b * nc + iloc -> cid | child_type << 3
    nei = [0] * (nt * (d + 1))  # idx = b*(d+1)+f -> type | dual<<3 | (off+1) 2b each
    for b in range(nt):
        for cid in range(nc):
            iloc = int(t.local_index[cid, b])
            pb = int(t.parent_type[cid, b])
            enc[b * nc + cid] = iloc | (pb << 3)
        for iloc in range(nc):
            cid = int(t.cube_id_of_local[b, iloc])
            ct = int(t.type_of_local[b, iloc])
            dec[b * nc + iloc] = cid | (ct << 3)
        for f in range(d + 1):
            v = int(t.neighbor_type[b, f]) | (int(t.neighbor_face[b, f]) << 3)
            for k in range(d):
                v |= (int(t.neighbor_offset[b, f, k]) + 1) << (6 + 2 * k)
            nei[b * (d + 1) + f] = v
    return tuple(enc), tuple(dec), tuple(nei)


def _lut(consts, idx):
    """Masked-sum lookup: TPU-idiomatic replacement for per-lane gather."""
    acc = jnp.zeros(idx.shape, jnp.int32)
    for k, v in enumerate(consts):
        if v:
            acc = acc + jnp.where(idx == k, jnp.int32(v), 0)
    return acc


@functools.lru_cache(maxsize=None)
def _packed_hex_nei(d: int):
    """Hex face-neighbor constants: idx = f -> dual<<3 | (off+1) 2b per axis
    (type bits stay 0 — hexes have no types)."""
    nei = [0] * (2 * d)
    for f in range(2 * d):
        v = (f ^ 1) << 3
        for k in range(d):
            off = (2 * (f % 2) - 1) if k == f // 2 else 0
            v |= (off + 1) << (6 + 2 * k)
        nei[f] = v
    return tuple(nei)


# ---------------------------------------------------- shared body expressions
# The per-op kernel bodies below and the fused face-sweep body compose these
# pure vreg->vreg expressions; keeping them shared means the fused kernel can
# never drift from the single-op kernels it replaces.  Encode and decode
# serve both classes (a hex digit is the raw cube id: no type chain); the
# neighbor and inside expressions have hex twins (axis-aligned neighbors,
# box containment).  The bodies' `eclass` selects statically.
def _encode_expr(d: int, eclass: int, coords, b):
    """morton key (level-padded consecutive index) from Tet-id -> (hi, lo).

    Fine -> coarse over one rolled level loop (the type chain runs that
    way); level i's digit goes to bit position d*(L-i), split over the two
    words by masks on that dynamic position, every shift amount clamped to
    [0, 31].  Hexes have no type chain: the digit is the raw cube id
    (plain Morton), and `b` (all zero) rides along unread."""
    L = MAXLEVEL[d]
    enc, _, _ = _packed_tables(d)
    nc = 2 ** d

    def level(j, carry):  # j = L - i: the coordinate bit of level i
        b, hi, lo = carry
        cid = jnp.zeros(b.shape, jnp.int32)
        for k, c in enumerate(coords):
            cid = cid | (((c >> j) & 1) << k)
        if eclass == ECLASS_HEX:
            digit = cid
        else:
            packed = _lut(enc, b * nc + cid)
            digit = packed & 7
            b = packed >> 3
        digit = digit.astype(jnp.uint32)
        pos = d * j
        # lo takes the digit's bits below 32, hi the rest (a straddling
        # digit's top bits arrive shifted down by 32 - pos)
        lo_mask = jnp.where(pos < 32, np.uint32(0xFFFFFFFF), np.uint32(0))
        lo = lo | ((digit << jnp.minimum(pos, 31)) & lo_mask)
        hi = hi | ((digit << jnp.maximum(pos - 32, 0)) >> jnp.clip(32 - pos, 0, 31))
        return b, hi, lo

    zero = jnp.zeros(b.shape, jnp.uint32)
    _, hi, lo = jax.lax.fori_loop(0, L, level, (b, zero, zero))
    return hi, lo


def _decode_expr(d: int, eclass: int, hi, lo, lvl):
    """Element id from a morton key at the given level -> (coords, type),
    the inverse of `_encode_expr`: coarse -> fine over one rolled level
    loop, reading the key's top digit while the key shifts up by d.
    Digits finer than `lvl` read as 0 (the caller supplies the level)."""
    L = MAXLEVEL[d]
    _, dec, _ = _packed_tables(d)
    nc = 2 ** d
    top = d * (L - 1) - 32

    def level(j, carry):  # j = i - 1: level i, coarsest first
        b, hi, lo, xyz = carry
        digit = ((hi >> top) & np.uint32(nc - 1)).astype(jnp.int32)
        active = j < lvl
        iloc = jnp.where(active, digit, 0)
        if eclass == ECLASS_HEX:
            cid = iloc  # plain Morton: the digit IS the cube id
        else:
            packed = _lut(dec, b * nc + iloc)
            cid = packed & 7
            b = jnp.where(active, packed >> 3, b)
        hi = (hi << d) | (lo >> (32 - d))
        lo = lo << d
        xyz = tuple((x << 1) | ((cid >> k) & 1) for k, x in enumerate(xyz))
        return b, hi, lo, xyz

    zero = jnp.zeros(hi.shape, jnp.int32)
    b, _, _, xyz = jax.lax.fori_loop(0, L, level, (zero, hi, lo, (zero,) * d))
    return xyz, b


def _neighbor_expr(d: int, coords, lvl, b, f):
    """Same-level face neighbor (Algorithm 4.6) -> (coords', type', dual).
    `f` is a face-index vreg or a static Python int (the fused sweep unrolls
    it statically; a static face looks up only its own column of the table)."""
    L = MAXLEVEL[d]
    _, _, nei = _packed_tables(d)
    h = (jnp.int32(1) << (L - lvl)).astype(jnp.int32)
    if isinstance(f, int):
        packed = _lut(nei[f::d + 1], b)
    else:
        packed = _lut(nei, b * (d + 1) + f)
    out = []
    for k in range(d):
        off = ((packed >> (6 + 2 * k)) & 3) - 1
        out.append(coords[k] + off * h)
    return out, packed & 7, (packed >> 3) & 7


def _inside_expr(d: int, coords, lvl, b):
    """Constant-time inside-root test (Proposition 23 with T = root, type 0)
    -> int32 0/1 mask.  The axis permutation and boundary type sets collapse
    to per-type constants baked into the instruction stream."""
    L = MAXLEVEL[d]
    t = get_tables(d)
    p = tuple(int(v) for v in t.outside_perm[0])
    KJ = tuple(int(v) for v in t.outside_types_kj[0])
    IK = tuple(int(v) for v in t.outside_types_ik[0])
    DIAG = tuple(int(v) for v in t.outside_types_diag[0])
    ht = jnp.int32(1 << L)
    ai = coords[p[0]]
    aj = coords[p[1]]
    at_root = (lvl == 0) & (b == 0)
    for c in coords:
        at_root = at_root & (c == 0)
    if d == 2:
        inside = (aj >= 0) & (ai < ht) & (aj <= ai)
        ok_diag = _lut(KJ, b) == 0
        inside = inside & ((aj != ai) | ok_diag)
    else:
        ak = coords[p[2]]
        inside = (aj >= 0) & (ai < ht) & (ak <= ai) & (aj <= ak)
        eq_ik = ak == ai
        eq_kj = aj == ak
        ok_ik = _lut(IK, b) == 0
        ok_kj = _lut(KJ, b) == 0
        ok_diag = _lut(DIAG, b) == 0
        # mask algebra, not a nested boolean select: Mosaic cannot lower a
        # select between i1 vectors (it truncates i8 -> i1 and refuses)
        ok = ((eq_ik & eq_kj & ok_diag) | (eq_ik & ~eq_kj & ok_ik)
              | (~eq_ik & eq_kj & ok_kj) | (~eq_ik & ~eq_kj))
        inside = inside & ok
    return (at_root | ((lvl > 0) & inside)).astype(jnp.int32)


def _hex_neighbor_expr(d: int, coords, lvl, f):
    """Hex twin of `_neighbor_expr`: neighbor across face f = 2*axis + dir
    is one cube side away along `axis`; dual = f ^ 1.  `f` is a face vreg or
    a static int (the fused sweep unrolls it)."""
    L = MAXLEVEL[d]
    nei = _packed_hex_nei(d)
    h = (jnp.int32(1) << (L - lvl)).astype(jnp.int32)
    packed = _lut(nei, f) if not isinstance(f, int) else jnp.full(lvl.shape, nei[f], jnp.int32)
    out = []
    for k in range(d):
        off = ((packed >> (6 + 2 * k)) & 3) - 1
        out.append(coords[k] + off * h)
    return out, (packed >> 3) & 7


def _hex_inside_expr(d: int, coords, lvl):
    """Hex twin of `_inside_expr`: box containment in the root cube —
    anchor in [0, 2^L - h] per axis (the upper bound is h-shifted so the
    compare never overflows int32 at level 0)."""
    L = MAXLEVEL[d]
    h = (jnp.int32(1) << (L - lvl)).astype(jnp.int32)
    lim = jnp.int32(1 << L) - h
    inside = lvl >= 0
    for c in coords:
        inside = inside & (c >= 0) & (c <= lim)
    return inside.astype(jnp.int32)


# ------------------------------------------------------------ kernel bodies
def _encode_body(d: int, eclass: int, refs):
    """morton key (level-padded consecutive index) from the element id."""
    if d == 3:
        x_ref, y_ref, z_ref, b_ref, hi_ref, lo_ref = refs
        coords = (x_ref[...], y_ref[...], z_ref[...])
    else:
        x_ref, y_ref, b_ref, hi_ref, lo_ref = refs
        coords = (x_ref[...], y_ref[...])
    hi_ref[...], lo_ref[...] = _encode_expr(d, eclass, coords, b_ref[...])


def _decode_body(d: int, eclass: int, refs):
    """Element id from morton key (level implied by trailing zero digits is
    NOT recovered here; the caller supplies it and we mask fine digits)."""
    hi_ref, lo_ref, lvl_ref = refs[:3]
    xyz, b = _decode_expr(d, eclass, hi_ref[...], lo_ref[...], lvl_ref[...])
    for out_ref, v in zip(refs[3:], (*xyz, b)):
        out_ref[...] = v


def _neighbor_body(d: int, eclass: int, refs):
    """Same-level face neighbor (Algorithm 4.6): single pass, no level loop."""
    if d == 3:
        x_ref, y_ref, z_ref, lvl_ref, b_ref, f_ref, ox_ref, oy_ref, oz_ref, ob_ref, of_ref = refs
        coords = (x_ref[...], y_ref[...], z_ref[...])
        outs = (ox_ref, oy_ref, oz_ref)
    else:
        x_ref, y_ref, lvl_ref, b_ref, f_ref, ox_ref, oy_ref, ob_ref, of_ref = refs
        coords = (x_ref[...], y_ref[...])
        outs = (ox_ref, oy_ref)
    if eclass == ECLASS_HEX:
        ncoords, dual = _hex_neighbor_expr(d, coords, lvl_ref[...], f_ref[...])
        ntype = jnp.zeros(dual.shape, jnp.int32)
    else:
        ncoords, ntype, dual = _neighbor_expr(d, coords, lvl_ref[...], b_ref[...], f_ref[...])
    for k in range(d):
        outs[k][...] = ncoords[k]
    ob_ref[...] = ntype
    of_ref[...] = dual


def _face_sweep_body(d: int, eclass: int, refs):
    """Fused per-element face sweep: for ALL nf faces at once (d+1 simplex,
    2d hex), the same-level neighbor (coords/type/dual), its inside-root
    mask, and its morton key — the three ops Balance/Ghost evaluation
    composes per face, with the element's (anchor, level, type) read from
    memory exactly once.  The face loop is a static unroll around each
    face's rolled encode loop; every (field, face) pair is its own
    lane-dense (block,) output (see `_lane_dense_outputs`)."""
    if d == 3:
        x_ref, y_ref, z_ref, lvl_ref, b_ref = refs[:5]
        coords = (x_ref[...], y_ref[...], z_ref[...])
    else:
        x_ref, y_ref, lvl_ref, b_ref = refs[:4]
        coords = (x_ref[...], y_ref[...])
    out_refs = refs[d + 2:]  # field-major: d coords, type, dual, inside, hi, lo
    lvl = lvl_ref[...]
    b = b_ref[...]
    nf = faces_per_element(d, eclass)
    cols = [[] for _ in range(d + 5)]
    for f in range(nf):
        if eclass == ECLASS_HEX:
            ncoords, dual = _hex_neighbor_expr(d, coords, lvl, f)
            ntype = jnp.zeros(lvl.shape, jnp.int32)
            inside = _hex_inside_expr(d, ncoords, lvl)
        else:
            ncoords, ntype, dual = _neighbor_expr(d, coords, lvl, b, f)
            inside = _inside_expr(d, ncoords, lvl, ntype)
        hi, lo = _encode_expr(d, eclass, ncoords, ntype)
        for k in range(d):
            cols[k].append(ncoords[k])
        cols[d].append(ntype)
        cols[d + 1].append(dual)
        cols[d + 2].append(inside)
        cols[d + 3].append(hi)
        cols[d + 4].append(lo)
    for k, col in enumerate(cols):
        for f, v in enumerate(col):
            out_refs[k * nf + f][...] = v


def _successor_body(d: int, eclass: int, refs):
    """Fused successor (Algorithm 4.10): encode -> +1 at own level ->
    decode, through the shared rolled expressions.  The key's digits below
    `lvl` are zero, so adding 1 << d*(L-lvl) is the base-2^d carry chain;
    a carry out of level 1 lands above the d*L key bits, which decode
    never reads."""
    L = MAXLEVEL[d]
    coords = tuple(r[...] for r in refs[:d])
    lvl = refs[d][...]
    b = refs[d + 1][...]
    hi, lo = _encode_expr(d, eclass, coords, b)
    one = u64m.U64(jnp.zeros_like(hi), jnp.ones_like(lo))
    key = u64m.add(u64m.U64(hi, lo), u64m.select_shl(one, d * (L - lvl), d * L))
    xyz, bo = _decode_expr(d, eclass, key.hi, key.lo, lvl)
    for out_ref, v in zip(refs[d + 2:], (*xyz, bo)):
        out_ref[...] = v


def _parent_body(d: int, eclass: int, refs):
    """Parent id (Algorithm 4.3) + local index (paper Table 6), fused:
    one cube-id extraction feeds both lookups via the packed `enc` table.
    For hexes the cube id IS the local index and the parent type is 0."""
    L = MAXLEVEL[d]
    enc, _, _ = _packed_tables(d)
    nc = 2 ** d
    if d == 3:
        x_ref, y_ref, z_ref, lvl_ref, b_ref, ox_ref, oy_ref, oz_ref, ob_ref, oi_ref = refs
        coords = (x_ref[...], y_ref[...], z_ref[...])
        outs = (ox_ref, oy_ref, oz_ref)
    else:
        x_ref, y_ref, lvl_ref, b_ref, ox_ref, oy_ref, ob_ref, oi_ref = refs
        coords = (x_ref[...], y_ref[...])
        outs = (ox_ref, oy_ref)
    lvl = lvl_ref[...]
    b = b_ref[...]
    h = (jnp.int32(1) << (L - lvl)).astype(jnp.int32)
    cid = jnp.zeros(b.shape, jnp.int32)
    for k, c in enumerate(coords):
        cid = cid | jnp.where((c & h) != 0, jnp.int32(1 << k), 0)
    for k, c in enumerate(coords):
        outs[k][...] = c & ~h
    if eclass == ECLASS_HEX:
        ob_ref[...] = jnp.zeros(b.shape, jnp.int32)
        oi_ref[...] = cid
    else:
        packed = _lut(enc, b * nc + cid)
        ob_ref[...] = packed >> 3
        oi_ref[...] = packed & 7


def _children_body(d: int, eclass: int, refs):
    """All 2^d children in SFC order (Algorithm 4.5; plain Morton order for
    hexes), one lane-dense (block,) output per (field, child)."""
    L = MAXLEVEL[d]
    _, dec, _ = _packed_tables(d)
    nc = 2 ** d
    coords = tuple(r[...] for r in refs[:d])
    lvl = refs[d][...]
    b = refs[d + 1][...]
    out_refs = refs[d + 2:]  # field-major: d coords, type; nc children each
    h2 = ((jnp.int32(1) << (L - lvl)) >> 1).astype(jnp.int32)
    cols = [[] for _ in range(d)]
    type_cols = []
    for iloc in range(nc):
        if eclass == ECLASS_HEX:
            cid = jnp.full(b.shape, iloc, jnp.int32)
            type_cols.append(jnp.zeros(b.shape, jnp.int32))
        else:
            packed = _lut(dec, b * nc + iloc)
            cid = packed & 7
            type_cols.append(packed >> 3)
        for k, c in enumerate(coords):
            cols[k].append(c + h2 * ((cid >> k) & 1))
    for k, col in enumerate(cols + [type_cols]):
        for j, v in enumerate(col):
            out_refs[k * nc + j][...] = v


def _tree_transform_body(d: int, M, c, tmap, refs):
    """Cross-tree coordinate change (cmesh gluing): anchor' = M @ anchor + c
    minus h on reflected axes, type through the d!-entry typemap.  M / c /
    tmap are per-connection compile-time constants (a handful per coarse
    mesh, each tiny), so the body is straight-line vector code; the signed
    permutation turns the matmul into one lane copy (+ negate) per axis."""
    L = MAXLEVEL[d]
    if d == 3:
        x_ref, y_ref, z_ref, lvl_ref, b_ref, ox_ref, oy_ref, oz_ref, ob_ref = refs
        coords = (x_ref[...], y_ref[...], z_ref[...])
        outs = (ox_ref, oy_ref, oz_ref)
    else:
        x_ref, y_ref, lvl_ref, b_ref, ox_ref, oy_ref, ob_ref = refs
        coords = (x_ref[...], y_ref[...])
        outs = (ox_ref, oy_ref)
    lvl = lvl_ref[...]
    b = b_ref[...]
    h = (jnp.int32(1) << (L - lvl)).astype(jnp.int32)
    for k in range(d):
        (ax,) = [j for j in range(d) if M[k][j] != 0]
        if M[k][ax] > 0:
            outs[k][...] = coords[ax] + jnp.int32(c[k])
        else:
            outs[k][...] = jnp.int32(c[k]) - coords[ax] - h
    ob_ref[...] = _lut(tmap, b)


def _owner_rank_body(num_markers: int, refs):
    """Owner-rank resolution against the partition-marker table: the rank of
    key (t, k) is the index of the last marker lex-<= (t, k), clamped to 0 —
    a vectorized searchsorted.  The marker table (one entry per rank, padded
    to a power of two with +inf sentinels) is tiny and identical for every
    lane, so the P-entry scan is unrolled into straight-line compare/add
    vector code; the uint64 keys are carried as (hi, lo) uint32 pairs."""
    t_ref, hi_ref, lo_ref, mt_ref, mhi_ref, mlo_ref, o_ref = refs
    t, hi, lo = t_ref[...], hi_ref[...], lo_ref[...]
    mt, mhi, mlo = mt_ref[...], mhi_ref[...], mlo_ref[...]
    o_ref[...] = _owner_count_expr(num_markers, t, hi, lo, mt, mhi, mlo)


def _owner_count_expr(num_markers: int, t, hi, lo, mt, mhi, mlo):
    """The unrolled marker-scan expression shared by `owner_rank_kernel` and
    the fused `eval_route_kernel` (single-op and fused paths cannot drift)."""
    count = jnp.zeros(t.shape, jnp.int32)
    for k in range(num_markers):
        le = (mt[k] < t) | (
            (mt[k] == t) & ((mhi[k] < hi) | ((mhi[k] == hi) & (mlo[k] <= lo)))
        )
        count = count + le.astype(jnp.int32)
    return jnp.maximum(count - 1, 0)


def _eval_route_body(d: int, num_markers: int, refs):
    """Fused Balance/Ghost routing eval over a block of (face, element)
    pairs (elementwise — the face structure lives in the wrapper): the
    neighbor interval's last key (key | span-1, uint64 as two uint32 words
    via an O(log) select mask — keys are span-aligned) and the [first, last]
    owner-rank range of the interval against the marker table."""
    L = MAXLEVEL[d]
    (t_ref, hi_ref, lo_ref, lvl_ref, mt_ref, mhi_ref, mlo_ref,
     ohhi_ref, ohlo_ref, ofirst_ref, olast_ref) = refs
    t, hi, lo, lvl = t_ref[...], hi_ref[...], lo_ref[...], lvl_ref[...]
    mt, mhi, mlo = mt_ref[...], mhi_ref[...], mlo_ref[...]
    sb = d * (L - lvl)
    one = u64m.U64(jnp.zeros_like(hi), jnp.full_like(lo, 1))
    mask = u64m.dec(u64m.select_shl(one, sb, 63))
    kh = u64m.or_(u64m.U64(hi, lo), mask)
    ohhi_ref[...] = kh.hi
    ohlo_ref[...] = kh.lo
    ofirst_ref[...] = _owner_count_expr(num_markers, t, hi, lo, mt, mhi, mlo)
    olast_ref[...] = _owner_count_expr(num_markers, t, kh.hi, kh.lo, mt, mhi, mlo)


def _inside_body(d: int, eclass: int, refs):
    """Constant-time inside-root test (Proposition 23 with T = root, type 0;
    box containment for hexes)."""
    if d == 3:
        x_ref, y_ref, z_ref, lvl_ref, b_ref, o_ref = refs
        coords = (x_ref[...], y_ref[...], z_ref[...])
    else:
        x_ref, y_ref, lvl_ref, b_ref, o_ref = refs
        coords = (x_ref[...], y_ref[...])
    if eclass == ECLASS_HEX:
        o_ref[...] = _hex_inside_expr(d, coords, lvl_ref[...])
    else:
        o_ref[...] = _inside_expr(d, coords, lvl_ref[...], b_ref[...])


# --------------------------------------------------------------- pallas_call
def _specs(n_in, n_out, block):
    spec = pl.BlockSpec((block,), lambda i: (i,))
    return [spec] * n_in, [spec] * n_out


# Lane-dense outputs: a kernel that yields several values per element (one
# per face, one per child) writes each as its own (N,) array rather than one
# (N, k) tile.  On a TPU an (N, k) array is stored in (8, 128) tiles, so a
# k of 4-8 pads every row to 128 lanes — 16-32x the bytes, which at a few
# million elements no longer fits the chip's memory.
def _lane_dense_outputs(dtypes, k, n):
    """Field-major (N,) output shapes: output f * k + j is field f, slot j."""
    return [jax.ShapeDtypeStruct((n,), dt) for dt in dtypes for _ in range(k)]


def morton_key_kernel(d: int, *arrays, block: int = DEFAULT_BLOCK, interpret: bool = True,
                      eclass: int = ECLASS_SIMPLEX):
    """arrays: x, y, (z,), type — int32, shape (N,) with N % block == 0.
    Returns (hi, lo) uint32 morton keys."""
    n = arrays[0].shape[0]
    in_specs, out_specs = _specs(len(arrays), 2, block)
    return pl.pallas_call(
        lambda *refs: _encode_body(d, eclass, refs),
        grid=(n // block,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=[jax.ShapeDtypeStruct((n,), jnp.uint32)] * 2,
        interpret=interpret,
        name="morton_key",
    )(*arrays)


def decode_kernel(d: int, hi, lo, level, block: int = DEFAULT_BLOCK, interpret: bool = True,
                  eclass: int = ECLASS_SIMPLEX):
    """Returns x, y, (z,), type from morton keys + level."""
    n = hi.shape[0]
    in_specs, out_specs = _specs(3, d + 1, block)
    return pl.pallas_call(
        lambda *refs: _decode_body(d, eclass, refs),
        grid=(n // block,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=[jax.ShapeDtypeStruct((n,), jnp.int32)] * (d + 1),
        interpret=interpret,
        name="decode",
    )(hi, lo, level)


def face_neighbor_kernel(d: int, *arrays, block: int = DEFAULT_BLOCK, interpret: bool = True,
                         eclass: int = ECLASS_SIMPLEX):
    """arrays: x, y, (z,), level, type, face — int32 (N,).
    Returns x, y, (z,), type, dual_face of the same-level neighbor."""
    n = arrays[0].shape[0]
    in_specs, out_specs = _specs(len(arrays), d + 2, block)
    return pl.pallas_call(
        lambda *refs: _neighbor_body(d, eclass, refs),
        grid=(n // block,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=[jax.ShapeDtypeStruct((n,), jnp.int32)] * (d + 2),
        interpret=interpret,
        name="face_neighbor",
    )(*arrays)


def face_sweep_kernel(d: int, *arrays, block: int = DEFAULT_BLOCK, interpret: bool = True,
                      eclass: int = ECLASS_SIMPLEX):
    """arrays: x, y, (z,), level, type — int32 (N,) with N % block == 0.
    One fused dispatch over ALL nf faces (d+1 simplex, 2d hex): returns
    x, y, (z,), type, dual, inside, key_hi, key_lo of every same-level face
    neighbor, field-major: output k * nf + f is field k of face f, an (N,)
    array.  key_hi/lo are uint32 morton-key words; inside is an int32 0/1
    mask."""
    n = arrays[0].shape[0]
    nf = faces_per_element(d, eclass)
    out_shape = _lane_dense_outputs([jnp.int32] * (d + 3) + [jnp.uint32] * 2, nf, n)
    in_specs, out_specs = _specs(len(arrays), len(out_shape), block)
    return pl.pallas_call(
        lambda *refs: _face_sweep_body(d, eclass, refs),
        grid=(n // block,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
        name="face_sweep",
    )(*arrays)


def parent_kernel(d: int, *arrays, block: int = DEFAULT_BLOCK, interpret: bool = True,
                  eclass: int = ECLASS_SIMPLEX):
    """arrays: x, y, (z,), level, type — int32 (N,).
    Returns x, y, (z,), type of the parent plus the element's SFC local index
    (the parent's level is the caller's `level - 1`)."""
    n = arrays[0].shape[0]
    in_specs, out_specs = _specs(len(arrays), d + 2, block)
    return pl.pallas_call(
        lambda *refs: _parent_body(d, eclass, refs),
        grid=(n // block,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=[jax.ShapeDtypeStruct((n,), jnp.int32)] * (d + 2),
        interpret=interpret,
        name="parent",
    )(*arrays)


def children_kernel(d: int, *arrays, block: int = DEFAULT_BLOCK, interpret: bool = True,
                    eclass: int = ECLASS_SIMPLEX):
    """arrays: x, y, (z,), level, type — int32 (N,).
    Returns x, y, (z,), type of all 2^d SFC-ordered children, field-major:
    output k * 2^d + j is field k of child j, an (N,) array."""
    n = arrays[0].shape[0]
    out_shape = _lane_dense_outputs([jnp.int32] * (d + 1), 2 ** d, n)
    in_specs, out_specs = _specs(len(arrays), len(out_shape), block)
    return pl.pallas_call(
        lambda *refs: _children_body(d, eclass, refs),
        grid=(n // block,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
        name="children",
    )(*arrays)


def inside_root_kernel(d: int, *arrays, block: int = DEFAULT_BLOCK, interpret: bool = True,
                       eclass: int = ECLASS_SIMPLEX):
    """arrays: x, y, (z,), level, type — int32 (N,).
    Returns an int32 0/1 mask: does the element lie inside the root?"""
    n = arrays[0].shape[0]
    in_specs, out_specs = _specs(len(arrays), 1, block)
    return pl.pallas_call(
        lambda *refs: _inside_body(d, eclass, refs),
        grid=(n // block,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=[jax.ShapeDtypeStruct((n,), jnp.int32)],
        interpret=interpret,
        name="inside_root",
    )(*arrays)


def tree_transform_kernel(d: int, M, c, tmap, *arrays,
                          block: int = DEFAULT_BLOCK, interpret: bool = True):
    """arrays: x, y, (z,), level, type — int32 (N,).  M/c/tmap are the
    per-connection gluing constants as nested int tuples (c pre-wrapped to
    int32, see repro.core.cmesh.wrap_i32).
    Returns x, y, (z,), type of the elements in the neighbor tree's frame."""
    n = arrays[0].shape[0]
    in_specs, out_specs = _specs(len(arrays), d + 1, block)
    return pl.pallas_call(
        lambda *refs: _tree_transform_body(d, M, c, tmap, refs),
        grid=(n // block,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=[jax.ShapeDtypeStruct((n,), jnp.int32)] * (d + 1),
        interpret=interpret,
        name="tree_transform",
    )(*arrays)


def owner_rank_kernel(t, hi, lo, mt, mhi, mlo,
                      block: int = DEFAULT_BLOCK, interpret: bool = True):
    """t/hi/lo: element tree + key words, int32/uint32 (N,) with N % block == 0.
    mt/mhi/mlo: partition-marker tree + key words (P,), sorted, padded with
    tree = int32 max sentinels.  Returns the int32 owner rank per element."""
    n = t.shape[0]
    num_markers = mt.shape[0]
    spec = pl.BlockSpec((block,), lambda i: (i,))
    mspec = pl.BlockSpec((num_markers,), lambda i: (0,))
    return pl.pallas_call(
        lambda *refs: _owner_rank_body(num_markers, refs),
        grid=(n // block,),
        in_specs=[spec] * 3 + [mspec] * 3,
        out_specs=[spec],
        out_shape=[jax.ShapeDtypeStruct((n,), jnp.int32)],
        interpret=interpret,
        name="owner_rank",
    )(t, hi, lo, mt, mhi, mlo)[0]


def eval_route_kernel(d: int, t, hi, lo, lvl, mt, mhi, mlo,
                      block: int = DEFAULT_BLOCK, interpret: bool = True):
    """t/hi/lo/lvl: per-(face, element) pair target tree, neighbor key words
    and element level, each a flat (N,) array with N % block == 0 (the body
    is elementwise, so both element classes share it).  mt/mhi/mlo:
    sentinel-padded partition markers (P,).  Returns (khi64_hi, khi64_lo,
    first, last): the interval-end key words (uint32) and the owner-rank
    range (int32) per pair, each (N,)."""
    n = t.shape[0]
    num_markers = mt.shape[0]
    spec = pl.BlockSpec((block,), lambda i: (i,))
    mspec = pl.BlockSpec((num_markers,), lambda i: (0,))
    return pl.pallas_call(
        lambda *refs: _eval_route_body(d, num_markers, refs),
        grid=(n // block,),
        in_specs=[spec] * 4 + [mspec] * 3,
        out_specs=[spec] * 4,
        out_shape=[jax.ShapeDtypeStruct((n,), jnp.uint32)] * 2
        + [jax.ShapeDtypeStruct((n,), jnp.int32)] * 2,
        interpret=interpret,
        name="eval_route",
    )(t, hi, lo, lvl, mt, mhi, mlo)


def successor_kernel(d: int, *arrays, block: int = DEFAULT_BLOCK, interpret: bool = True,
                     eclass: int = ECLASS_SIMPLEX):
    """arrays: x, y, (z,), level, type — int32 (N,).
    Returns x, y, (z,), type of the SFC successor at the same level."""
    n = arrays[0].shape[0]
    in_specs, out_specs = _specs(len(arrays), d + 1, block)
    return pl.pallas_call(
        lambda *refs: _successor_body(d, eclass, refs),
        grid=(n // block,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=[jax.ShapeDtypeStruct((n,), jnp.int32)] * (d + 1),
        interpret=interpret,
        name="successor",
    )(*arrays)
