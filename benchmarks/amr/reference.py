"""Plain reference of what one AMR cycle must produce, in NumPy alone.

It imports nothing of the program under test and takes nothing the program
made.  Elements are Kuhn simplices (tetrahedra) or cubes (hexahedra) on the
integer lattice of the program's output format: an anchor in units of
2^-L of the root cube, a level, a type and a tree.  Every rule below comes
from the definitions in Burstedde & Holke, arXiv:1509.04627:

* type b is the simplex [0, e_i, e_i + e_j, (1,1,1)] of its cube, with
  i = b // 2 and j = (i + 2) % 3 for even b, (i + 1) % 3 for odd b
  (Algorithm 4.1);
* children follow Bey's red refinement of the vertex list (eq. (2)); a
  simplex is found from its vertex set by sorting the vertices along the
  cube's diagonal, so no child or parent table is used;
* the parent is the Kuhn simplex of the doubled cube that holds the
  child's centroid;
* the same-level face neighbour replaces one vertex by its reflection
  (the pivot rule of the Freudenthal-Kuhn triangulation);
* the TM order of children is lexicographic in (cube id, type), eq. (17),
  so a leaf's SFC key is its chain of (cube id, type) digits.

A cube's children, neighbours and Morton order are the plain ones.
Balance is the 2:1 face condition's least fixpoint: a leaf of level l is
refined while some face-neighbour region of level l holds a leaf of level
l + 2 or finer.
"""

from __future__ import annotations

import dataclasses

import numpy as np

L = 21          # coordinate bits of the output format (anchors in [0, 2^L))
KEY_LEVELS = 14  # deepest level the membership key encodes
ORDER_LEVELS = 10  # deepest level the SFC order key encodes
BLOCK = 1 << 20  # rows per block, so large meshes fit host memory

SIMPLEX, HEX = "simplex", "hex"


def _type_tables():
    perm = np.zeros((6, 3), np.int64)
    of_axes = np.full((3, 3), -1, np.int64)
    for b in range(6):
        i = b // 2
        j = (i + 2) % 3 if b % 2 == 0 else (i + 1) % 3
        perm[b] = (i, j, 3 - i - j)
        of_axes[i, j] = b
    return perm, of_axes


PERM, TYPE_OF_AXES = _type_tables()


@dataclasses.dataclass
class Leaves:
    """A set of elements, struct of arrays (int64)."""

    tree: np.ndarray
    level: np.ndarray
    anchor: np.ndarray  # (n, 3)
    stype: np.ndarray

    def __len__(self):
        return len(self.level)

    def take(self, idx) -> "Leaves":
        return Leaves(self.tree[idx], self.level[idx], self.anchor[idx],
                      self.stype[idx])

    @staticmethod
    def concat(parts) -> "Leaves":
        return Leaves(*(np.concatenate([getattr(p, k) for p in parts])
                        for k in ("tree", "level", "anchor", "stype")))

    @staticmethod
    def of(tree, level, anchor, stype) -> "Leaves":
        return Leaves(np.asarray(tree, np.int64), np.asarray(level, np.int64),
                      np.asarray(anchor, np.int64).reshape(-1, 3),
                      np.asarray(stype, np.int64))


def roots(num_trees: int) -> Leaves:
    """One level-0 element of type 0 per tree."""
    n = num_trees
    return Leaves.of(np.arange(n), np.zeros(n), np.zeros((n, 3)), np.zeros(n))


def _side(level):
    return np.left_shift(np.int64(1), L - level)


# ------------------------------------------------------------------ simplices
def vertices(x: Leaves) -> np.ndarray:
    """(n, 4, 3) vertices x0..x3 of simplices (Algorithm 4.1)."""
    h = _side(x.level)
    p = PERM[x.stype]
    rows = np.arange(len(x))
    v = np.repeat(x.anchor[:, None, :], 4, axis=1)
    v[rows, 1, p[:, 0]] += h
    v[rows, 2] = v[rows, 1]
    v[rows, 2, p[:, 1]] += h
    v[:, 3] += h[:, None]
    return v


def from_vertices(tree, v: np.ndarray) -> Leaves:
    """Simplices from (n, 4, 3) vertex sets in any order."""
    order = np.argsort(v.sum(axis=2), axis=1)
    v = np.take_along_axis(v, order[:, :, None], axis=1)
    h = v[:, 3, 0] - v[:, 0, 0]
    i = np.argmax(v[:, 1] - v[:, 0], axis=1)
    j = np.argmax(v[:, 2] - v[:, 1], axis=1)
    level = L - np.log2(h).astype(np.int64)
    return Leaves(np.asarray(tree, np.int64), level, v[:, 0], TYPE_OF_AXES[i, j])


_BEY = [((0, 0), (0, 1), (0, 2), (0, 3)), ((0, 1), (1, 1), (1, 2), (1, 3)),
        ((0, 2), (1, 2), (2, 2), (2, 3)), ((0, 3), (1, 3), (2, 3), (3, 3)),
        ((0, 1), (0, 2), (0, 3), (1, 3)), ((0, 1), (0, 2), (1, 2), (1, 3)),
        ((0, 2), (0, 3), (1, 3), (2, 3)), ((0, 2), (1, 2), (1, 3), (2, 3))]


def _simplex_children(x: Leaves) -> Leaves:
    v = vertices(x)
    kids = []
    for corners in _BEY:  # x_ij is the midpoint of x_i and x_j (eq. (2))
        kv = np.stack([(v[:, a] + v[:, b]) // 2 for a, b in corners], axis=1)
        kids.append(from_vertices(x.tree, kv))
    return Leaves.concat(kids)


def _simplex_parent(x: Leaves) -> Leaves:
    H = _side(x.level - 1)
    pa = x.anchor - x.anchor % H[:, None]
    # the centroid (times 4) relative to the parent cube, sorted descending,
    # names the axes walked from x0 to x3 (interior point: no ties)
    rel = vertices(x).sum(axis=1) - 4 * pa
    order = np.argsort(-rel, axis=1, kind="stable")
    return Leaves(x.tree, x.level - 1, pa, TYPE_OF_AXES[order[:, 0], order[:, 1]])


def _simplex_neighbors(x: Leaves):
    """Same-level face neighbours across the 4 faces and an inside-root
    mask (4, n).  Face m is the one opposite x_m."""
    v = vertices(x)
    out, inside = [], []
    for m in range(4):  # x_m' = x_(m-1) + x_(m+1) - x_m, indices mod 4
        w = v.copy()
        w[:, m] = v[:, (m - 1) % 4] + v[:, (m + 1) % 4] - v[:, m]
        nb = from_vertices(x.tree, w)
        out.append(nb)
        inside.append(_inside_root(nb))
    return out, np.stack(inside)


def _inside_root(x: Leaves) -> np.ndarray:
    """Inside the type-0 root simplex of its tree: the centroid satisfies
    1 >= c_i >= c_j >= c_k >= 0 for the root's axes (i, j, k)."""
    c = vertices(x).sum(axis=1)  # 4 x centroid
    i, j, k = PERM[0]
    top = 4 * (np.int64(1) << L)
    return ((c[:, i] <= top) & (c[:, i] >= c[:, j]) & (c[:, j] >= c[:, k])
            & (c[:, k] >= 0))


# ---------------------------------------------------------------------- cubes
_CORNER_BITS = np.array([[(c >> a) & 1 for a in range(3)] for c in range(8)],
                        np.int64)


def _hex_children(x: Leaves) -> Leaves:
    h2 = _side(x.level + 1)
    kids = [Leaves(x.tree, x.level + 1, x.anchor + h2[:, None] * _CORNER_BITS[c],
                   x.stype) for c in range(8)]
    return Leaves.concat(kids)


def _hex_parent(x: Leaves) -> Leaves:
    H = _side(x.level - 1)
    return Leaves(x.tree, x.level - 1, x.anchor - x.anchor % H[:, None], x.stype)


def _hex_neighbors(x: Leaves, periodic):
    h = _side(x.level)
    top = np.int64(1) << L
    out, inside = [], []
    for axis in range(3):
        for sign in (-1, 1):
            a = x.anchor.copy()
            a[:, axis] += sign * h
            ok = (a[:, axis] >= 0) & (a[:, axis] < top)
            if periodic[axis]:
                a[:, axis] %= top
                ok = np.ones(len(x), bool)
            out.append(Leaves(x.tree, x.level, a, x.stype))
            inside.append(ok)
    return out, np.stack(inside)


# ------------------------------------------------------------------- the mesh
@dataclasses.dataclass(frozen=True)
class Mesh:
    """The coarse mesh: element class, tree count and, for a single cube
    tree, which axes are periodic (a simplex tree's faces are all domain
    boundary: the trees are not glued)."""

    eclass: str
    num_trees: int
    periodic: tuple = (False, False, False)

    def children(self, x: Leaves) -> Leaves:
        return (_simplex_children if self.eclass == SIMPLEX else _hex_children)(x)

    def parent(self, x: Leaves) -> Leaves:
        return (_simplex_parent if self.eclass == SIMPLEX else _hex_parent)(x)

    def neighbors(self, x: Leaves):
        if self.eclass == SIMPLEX:
            return _simplex_neighbors(x)
        return _hex_neighbors(x, self.periodic)

    def uniform(self, level: int) -> Leaves:
        x = roots(self.num_trees)
        for _ in range(level):
            x = self.children(x)
        return x


def cube_id(anchor: np.ndarray, level) -> np.ndarray:
    """Cube id of the level-`level` ancestor of the elements at `anchor`:
    bit a is anchor bit L - level of axis a (x weighs 1, y 2, z 4)."""
    bits = (anchor >> (L - np.asarray(level, np.int64))[..., None]) & 1
    return bits[:, 0] + 2 * bits[:, 1] + 4 * bits[:, 2]


def keys(x: Leaves) -> np.ndarray:
    """Injective int64 key of (tree, level, anchor, type) for levels up to
    KEY_LEVELS and fewer than 2^14 trees."""
    if len(x) and (x.level.max() > KEY_LEVELS or x.tree.max() >= 1 << 14):
        raise ValueError("mesh too deep or too wide for the reference key")
    rel = x.anchor >> (L - x.level)[:, None]
    k = x.tree
    k = (k << 4) | x.level
    k = (k << 3) | x.stype
    for a in range(3):
        k = (k << KEY_LEVELS) | rel[:, a]
    return k


def _blocks(n):
    return [slice(s, min(s + BLOCK, n)) for s in range(0, n, BLOCK)]


def _deep_ancestors(mesh: Mesh, x: Leaves) -> np.ndarray:
    """Sorted keys of every element that is an ancestor two or more levels
    above some leaf: exactly the regions that hold a leaf two or more
    levels finer than themselves."""
    found = []
    cur = x.take(np.nonzero(x.level >= 2)[0])
    for _ in range(2):
        cur = mesh.parent(cur)
        _, first = np.unique(keys(cur), return_index=True)
        cur = cur.take(first)
    while len(cur):
        found.append(keys(cur))
        cur = cur.take(np.nonzero(cur.level >= 1)[0])
        cur = mesh.parent(cur)
        _, first = np.unique(keys(cur), return_index=True)
        cur = cur.take(first)
    return np.unique(np.concatenate(found)) if found else np.zeros(0, np.int64)


def balance(mesh: Mesh, x: Leaves, max_rounds: int = 64) -> Leaves:
    """The 2:1 face-balanced refinement of `x`: refine every leaf whose
    same-level face-neighbour region holds a leaf two or more levels finer,
    until none does."""
    for _ in range(max_rounds):
        deep = _deep_ancestors(mesh, x)
        need = np.zeros(len(x), bool)
        for blk in _blocks(len(x)):
            part = x.take(blk)
            nbs, inside = mesh.neighbors(part)
            for nb, ok in zip(nbs, inside):
                hit = np.zeros(len(part), bool)
                hit[ok] = np.isin(keys(nb.take(ok)), deep)
                need[blk] |= hit
        if not need.any():
            return x
        x = Leaves.concat([x.take(~need), mesh.children(x.take(need))])
    raise RuntimeError("reference balance did not converge")


def refine_while(mesh: Mesh, x: Leaves, flag) -> Leaves:
    """Refine every leaf with `flag(leaves)` true, then its children, until
    no leaf is flagged (recursive Adapt without coarsening)."""
    done = []
    while len(x):
        f = flag(x)
        done.append(x.take(~f))
        x = mesh.children(x.take(f))
    return Leaves.concat(done)


# ------------------------------------------------------------ the comparison
def sfc_keys(mesh: Mesh, x: Leaves) -> np.ndarray:
    """Each leaf's position on the curve as an int64: one 6-bit digit
    (cube id, type) per level, most significant first (eq. (17))."""
    if len(x) and x.level.max() > ORDER_LEVELS:
        raise ValueError("mesh too deep for the reference order key")
    key = np.zeros(len(x), np.int64)
    cur = Leaves(x.tree, x.level.copy(), x.anchor.copy(), x.stype.copy())
    idx = np.arange(len(x))
    for lvl in range(int(x.level.max(initial=0)), 0, -1):
        m = cur.level == lvl
        digit = cube_id(cur.anchor[m], lvl) * 8 + cur.stype[m]
        key[idx[m]] += digit << (6 * (ORDER_LEVELS - lvl))
        up = mesh.parent(cur.take(m))
        cur.anchor[m], cur.level[m], cur.stype[m] = up.anchor, up.level, up.stype
    return key


def well_formed(mesh: Mesh, x: Leaves) -> np.ndarray:
    """Mask of entries that name an element at all: a level the keys
    encode, an anchor aligned to its level inside the root cube, a type of
    the class and a tree of the mesh."""
    lvl_ok = (x.level >= 0) & (x.level <= min(KEY_LEVELS, ORDER_LEVELS))
    h = _side(np.where(lvl_ok, x.level, 0))
    a_ok = ((x.anchor >= 0) & (x.anchor < np.int64(1) << L)
            & (x.anchor % h[:, None] == 0)).all(axis=1)
    t_ok = (x.stype >= 0) & (x.stype < (6 if mesh.eclass == SIMPLEX else 1))
    tr_ok = (x.tree >= 0) & (x.tree < mesh.num_trees)
    return lvl_ok & a_ok & t_ok & tr_ok


def mismatches(got_keys: np.ndarray, want_keys: np.ndarray) -> int:
    """Size of the multiset symmetric difference of two key arrays."""
    ug, cg = np.unique(got_keys, return_counts=True)
    uw, cw = np.unique(want_keys, return_counts=True)
    _, ig, iw = np.intersect1d(ug, uw, assume_unique=True, return_indices=True)
    common = int(np.minimum(cg[ig], cw[iw]).sum())
    return len(got_keys) + len(want_keys) - 2 * common


def compare(mesh: Mesh, got: Leaves, want: Leaves) -> tuple[int, int]:
    """(entries that differ from `want` as a multiset, adjacent pairs of
    `got` out of (tree, SFC) order).  A malformed entry counts as one that
    differs."""
    ok = well_formed(mesh, got)
    bad = int((~ok).sum())
    good = got.take(ok)
    diff = bad + mismatches(keys(good), keys(want))
    if bad:
        return diff, max(len(got) - 1, 0)
    order = sfc_keys(mesh, good)
    t = good.tree
    later = (t[1:] > t[:-1]) | ((t[1:] == t[:-1]) & (order[1:] > order[:-1]))
    return diff, int((~later).sum())
