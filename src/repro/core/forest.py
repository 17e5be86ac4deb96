"""Forest-of-trees AMR on the tetrahedral SFC (paper Section 5).

Implements the top-level algorithms New, Adapt, Partition, Balance, Ghost and
Iterate over a *forest*: a coarse mesh of K root simplices ("trees"), each
adaptively refined, with leaves totally ordered by (tree, TM-index) and
partitioned across P ranks by contiguous SFC ranges.

This module is the distributed-algorithm layer.  It is written in SPMD style:
every function computes the view of the ranks resident in this process
(`comm.local_ranks` — all P under the in-process `SimComm`, exactly one
under `DistComm`/MPI), and every cross-rank exchange goes through the
`repro.core.comm.Comm` surface (allgather / alltoallv with per-phase byte
metering).  Balance and Ghost are *message based*: ranks allgather only the
P partition markers, route packed (tree, key, level) key-range queries to
owner ranks via `alltoallv`, answer them from their local sorted leaf
arrays, and iterate Balance exchanging only the boundary layer that changed
each round (the ripple scheme of Isaac-Burstedde-Ghattas).  The former
global-leaf-table implementations are retained as `balance_oracle` /
`ghost_oracle` — the simulator-era baseline the message path must match
element for element (and the wire-volume baseline in the benchmarks).

The heavy per-element math goes through the batched dispatch layer
`repro.core.batch` (reference / jnp / pallas backends over `Simplex`
batches — gathers + integer ALU, TPU/SIMD friendly), including the
marker-table `owner_rank` searchsorted that routes every query; the
variable-size bookkeeping stays in numpy on the host, matching how meshing
layers sit next to accelerator compute in production frameworks.

Inter-tree face connectivity — the paper's stated open extension (Balance and
Ghost "require additional theoretical work" across root simplices) — is
provided by the coarse-mesh layer `repro.core.cmesh`: a forest built with a
`Cmesh` follows face neighbors across tree faces (transforming elements into
the neighbor tree's frame with the per-connection gluing tables) and treats
only the Cmesh's unconnected faces as domain boundary.  A forest without a
Cmesh (`cmesh=None`) keeps the paper's single-tree semantics: every tree
face is a boundary.
"""

from __future__ import annotations

import dataclasses
from typing import Callable

import numpy as np
import jax.numpy as jnp

from . import telemetry
from . import u64 as u64m
from .batch import BatchedOps, count_dispatch as batch_count_dispatch, fetch_span, get_batch_ops
from .cmesh import Cmesh, wrap_i32
from .comm import Comm, CommHandle, DistComm, LatencyComm, LocalComm, SimComm
from .ops import ElementOps, get_ops
from .placement import target_ranks_np
from .tables import face_plane
from .types import ECLASS_SIMPLEX, Simplex, pack_wire, unpack_wire

__all__ = [
    "Forest",
    "Comm",
    "CommHandle",
    "SimComm",
    "LocalComm",
    "LatencyComm",
    "DistComm",
    "new_uniform",
    "adapt",
    "partition",
    "repartition",
    "load_imbalance",
    "partition_markers",
    "balance",
    "balance_oracle",
    "BalanceNonConvergence",
    "ghost",
    "ghost_oracle",
    "iterate",
    "validate",
    "count_global",
    "face_kind",
    "face_kinds",
    "face_sweep_layer",
    "FaceSweepLayer",
    "FACE_INTERIOR",
    "FACE_INTER_TREE",
    "FACE_DOMAIN_BOUNDARY",
]


# ------------------------------------------------------------------- forest
@dataclasses.dataclass
class Forest:
    """One rank's portion of a partitioned forest.

    Elements are stored SoA (anchor/level/type + owning tree) in ascending
    (tree, TM-index) order — the paper's linear storage along the SFC.
    """

    d: int
    num_trees: int
    rank: int
    num_ranks: int
    anchor: np.ndarray        # (n, d) int32
    level: np.ndarray         # (n,)  int32
    stype: np.ndarray         # (n,)  int32
    tree: np.ndarray          # (n,)  int32
    keys: np.ndarray          # (n,)  uint64 morton keys (level-padded ids)
    # coarse-mesh connectivity; None = isolated trees (paper's single-tree
    # semantics: every tree face is a domain boundary)
    cmesh: Cmesh | None = None

    @property
    def eclasses(self) -> tuple:
        """Element classes present in the coarse mesh (every leaf of a tree
        shares the tree's class; no cmesh means the paper's simplex-only
        setting)."""
        return (ECLASS_SIMPLEX,) if self.cmesh is None else self.cmesh.eclasses

    @property
    def eclass(self) -> int:
        """The single element class of this forest's leaves.  For a forest
        over a mixed-class cmesh this is the class of the locally present
        trees; a rank holding leaves of MORE than one class has no single
        class — group by class first (`_class_groups`)."""
        ecs = self.eclasses
        if len(ecs) == 1:
            return ecs[0]
        present = np.unique(self.cmesh.tree_eclass[self.tree])
        if len(present) > 1:
            raise ValueError(
                "forest holds leaves of multiple element classes; "
                "group by class before using per-class ops")
        return int(present[0]) if len(present) else ECLASS_SIMPLEX

    @property
    def ops(self) -> ElementOps:
        return get_ops(self.d, self.eclass)

    @property
    def bops(self) -> BatchedOps:
        """Batched element ops under the globally selected backend."""
        return get_batch_ops(self.d, eclass=self.eclass)

    @property
    def num_local(self) -> int:
        return len(self.level)

    def simplices(self) -> Simplex:
        # memoized device view: element arrays are immutable (adapt &c.
        # return NEW Forests), so the upload happens once per Forest
        s = self.__dict__.get("_simplices_cache")
        if s is None:
            s = Simplex(jnp.asarray(self.anchor), jnp.asarray(self.level),
                        jnp.asarray(self.stype))
            self.__dict__["_simplices_cache"] = s
        return s

    def replace_elements(self, anchor, level, stype, tree) -> "Forest":
        anchor = np.asarray(anchor, np.int32)
        level = np.asarray(level, np.int32)
        stype = np.asarray(stype, np.int32)
        tree = np.asarray(tree, np.int32)
        ecs = self.eclasses
        with telemetry.span("keys.encode", elements=len(level)):
            if len(ecs) == 1:
                s = Simplex(jnp.asarray(anchor), jnp.asarray(level), jnp.asarray(stype))
                keys = get_batch_ops(self.d, eclass=ecs[0]).morton_key_np(s)
            else:
                # mixed-class mesh: every tree's leaves encode with the tree's
                # class — one batched key dispatch per class present
                keys = np.zeros(len(level), np.uint64)
                te = self.cmesh.tree_eclass[tree]
                for ec in ecs:
                    m = te == ec
                    if m.any():
                        s = Simplex(jnp.asarray(anchor[m]), jnp.asarray(level[m]),
                                    jnp.asarray(stype[m]))
                        keys[m] = get_batch_ops(self.d, eclass=ec).morton_key_np(s)
        return dataclasses.replace(
            self,
            anchor=anchor, level=level, stype=stype, tree=tree, keys=keys,
        )

    def global_first_desc_key(self):
        """(tree, key) of this rank's first element; used as partition marker."""
        if self.num_local == 0:
            return (self.num_trees, np.uint64(0))
        return (int(self.tree[0]), self.keys[0])

    def repartition(self, comm: Comm, weights: np.ndarray | None = None,
                    overlap: bool = True) -> "Forest":
        """Single-local-rank convenience over the module-level `repartition`
        (the DistComm hosting, one rank per process): migrate this rank's
        elements per the global weight distribution and return the new
        local forest.  Under a multi-rank hosting (`SimComm`) call the
        module-level form with all local forests instead."""
        assert len(comm.local_ranks) == 1, (
            "Forest.repartition is the one-rank-per-process form; pass all "
            "local forests to forest.repartition under SimComm")
        return repartition(
            [self], comm, None if weights is None else [weights],
            overlap=overlap)[0]


def _empty(d, num_trees, rank, num_ranks, cmesh=None) -> Forest:
    return Forest(
        d, num_trees, rank, num_ranks,
        np.zeros((0, d), np.int32), np.zeros(0, np.int32), np.zeros(0, np.int32),
        np.zeros(0, np.int32), np.zeros(0, np.uint64), cmesh,
    )


# ---------------------------------------------------------- element classes
# The element class is a per-TREE property of the cmesh (classes are unions
# of whole trees, and cross-class faces are domain boundaries), so a forest
# over a mixed mesh splits into independent per-class groups: the collective
# drivers below run the existing single-class pipeline once per class (in
# the deterministic sorted class order, so all ranks agree) and merge the
# per-rank results back into stored (tree, key) order.  Single-class meshes
# — every pre-existing caller — take the direct path, dispatch for dispatch.


def _forest_classes(forests) -> tuple:
    f = forests[0] if isinstance(forests, (list, tuple)) else forests
    return (ECLASS_SIMPLEX,) if f.cmesh is None else f.cmesh.eclasses


def _class_groups(f: Forest):
    """[(eclass, local element indices)] for the classes locally present,
    in ascending class order."""
    ecs = _forest_classes(f)
    if len(ecs) == 1:
        return [(ecs[0], np.arange(f.num_local, dtype=np.int64))]
    te = f.cmesh.tree_eclass[f.tree]
    return [(ec, np.nonzero(te == ec)[0].astype(np.int64))
            for ec in ecs if (te == ec).any()] or [
        (ECLASS_SIMPLEX, np.arange(0, dtype=np.int64))]


def _subforest(f: Forest, idx: np.ndarray) -> Forest:
    """The forest restricted to local elements `idx` (same cmesh / ranks /
    tree ids — only the leaf arrays shrink).  Derived caches do not carry
    over: dataclasses.replace builds a fresh object."""
    return dataclasses.replace(
        f, anchor=f.anchor[idx], level=f.level[idx], stype=f.stype[idx],
        tree=f.tree[idx], keys=f.keys[idx])


def _class_subforests(forests, ec: int):
    cm = forests[0].cmesh
    return [_subforest(f, np.nonzero(cm.tree_eclass[f.tree] == ec)[0])
            for f in forests]


def _merge_class_groups(base: Forest, parts) -> Forest:
    """Concatenate per-class forests back into one rank forest in stored
    (tree, key) lex order.  Keys are already correct per part — no key
    dispatch needed."""
    tree = np.concatenate([p.tree for p in parts])
    keys = np.concatenate([p.keys for p in parts])
    order = np.lexsort((keys, tree))
    return dataclasses.replace(
        base,
        anchor=np.concatenate([p.anchor for p in parts])[order],
        level=np.concatenate([p.level for p in parts])[order],
        stype=np.concatenate([p.stype for p in parts])[order],
        tree=tree[order], keys=keys[order])


def _layer_eclass(f: Forest, tree_ids) -> int:
    """Element class of a layer batch (all its trees must share one — the
    per-class drivers guarantee it)."""
    ecs = _forest_classes(f)
    if len(ecs) == 1:
        return ecs[0]
    tid = np.asarray(tree_ids)
    if tid.size == 0:
        return ECLASS_SIMPLEX
    present = np.unique(f.cmesh.tree_eclass[tid])
    if len(present) > 1:
        raise ValueError("face_sweep_layer needs a single-class element layer")
    return int(present[0])


# ---------------------------------------------------------------------- new
def new_uniform(d: int, num_trees: int, level: int, comm: Comm,
                method: str = "decode", cmesh: Cmesh | None = None) -> list[Forest]:
    """Paper Algorithm 5.1 (New): partitioned uniform level-`level` forest.

    Returns one `Forest` per rank resident in this process (all P under
    `SimComm`, one under `DistComm`).  With `cmesh`, the trees are glued per
    its face tables and the forest's Balance/Ghost/Iterate follow neighbors
    across tree faces."""
    return [
        new_uniform_rank(d, num_trees, level, p, comm.size, method=method, cmesh=cmesh)
        for p in comm.local_ranks
    ]


def new_uniform_rank(d: int, num_trees: int, level: int, rank: int, num_ranks: int,
                     method: str = "decode", cmesh: Cmesh | None = None) -> Forest:
    """One rank's portion of a uniform refinement — communication free.

    method="decode":    vectorized Algorithm 4.8 over the index range (O(n L)
                        work but a single fused gather pipeline; the default).
    method="successor": first element via Algorithm 4.8, remainder via the
                        level-independent batch expansion (paper's New uses
                        Successor to achieve O(n); our batch analogue expands
                        whole subtrees level by level, also O(n) total work).
    """
    if cmesh is not None:
        assert cmesh.d == d and cmesh.num_trees == num_trees, (
            f"cmesh ({cmesh.d}D, {cmesh.num_trees} trees) does not match "
            f"forest ({d}D, {num_trees} trees)"
        )
    o = get_ops(d)
    # nc = 2^d for BOTH element classes, so n_per_tree and the partition
    # arithmetic are class-independent; only the per-tree decode below
    # dispatches on the tree's class.
    n_per_tree = o.num_elements(level)
    N = n_per_tree * num_trees
    g_first = (N * rank) // num_ranks
    g_last = (N * (rank + 1)) // num_ranks  # exclusive
    f = _empty(d, num_trees, rank, num_ranks, cmesh)
    if g_last <= g_first:
        return f

    trees = np.arange(g_first // n_per_tree, (g_last - 1) // n_per_tree + 1)
    anchors, levels, stypes, tree_ids = [], [], [], []
    for t in trees:
        ec = ECLASS_SIMPLEX if cmesh is None else cmesh.eclass_of(int(t))
        o_t = get_ops(d, ec)
        e_first = g_first - t * n_per_tree if t == trees[0] else 0
        e_last = g_last - t * n_per_tree if t == trees[-1] else n_per_tree
        ids = np.arange(e_first, e_last, dtype=np.uint64)
        if method == "decode":
            keys = ids << np.uint64(o_t.d * (o_t.L - level))
            s = get_batch_ops(d, eclass=ec).decode(
                u64m.from_int(keys), jnp.full(len(ids), level, jnp.int32)
            )
        elif method == "successor":
            s = _range_by_expansion(o_t, int(e_first), int(e_last), level)
        else:
            raise ValueError(method)
        anchors.append(np.asarray(s.anchor))
        levels.append(np.asarray(s.level))
        stypes.append(np.asarray(s.stype))
        tree_ids.append(np.full(len(ids), t, np.int32))
    return f.replace_elements(
        np.concatenate(anchors), np.concatenate(levels),
        np.concatenate(stypes), np.concatenate(tree_ids),
    )


def _range_by_expansion(o: ElementOps, e_first: int, e_last: int, level: int) -> Simplex:
    """Create the SFC range [e_first, e_last) at `level` with O(n) total work.

    Level-independent per element: start from the coarsest subtree roots that
    tile the range and expand children level by level (geometric series).
    This is the vectorized counterpart of the paper's Successor-based New.
    """
    nc = o.nc
    # Coarsest covering: walk levels, at each level emit subtrees fully inside
    # the remaining range.
    roots = []  # (id, lvl)
    lo, hi = e_first, e_last
    for lv in range(level + 1):
        span = nc ** (level - lv)
        lo_aligned = (lo + span - 1) // span * span
        hi_aligned = hi // span * span
        if lo_aligned > hi_aligned:
            continue
        # emit subtrees of this level covering [lo_aligned, hi_aligned) that
        # are NOT covered by a coarser subtree already emitted
        if not roots:
            ids = np.arange(lo_aligned // span, hi_aligned // span, dtype=np.uint64)
            if len(ids):
                roots.append((ids, lv))
                lo2, hi2 = lo_aligned, hi_aligned
        else:
            break
    if not roots:  # range shorter than one finest element span
        ids = np.arange(lo, hi, dtype=np.uint64)
        s = o.from_linear_id(u64m.from_int(ids), jnp.full(len(ids), level, jnp.int32))
        return s
    ids, lv = roots[0]
    head = np.arange(lo, lo2, dtype=np.uint64)
    tail = np.arange(hi2, hi, dtype=np.uint64)
    mid = o.from_linear_id(u64m.from_int(ids), jnp.full(len(ids), lv, jnp.int32))
    while lv < level:
        kids = o.children_tm(mid)
        mid = Simplex(
            kids.anchor.reshape(-1, o.d), kids.level.reshape(-1), kids.stype.reshape(-1)
        )
        lv += 1
    parts = []
    if len(head):
        parts.append(o.from_linear_id(u64m.from_int(head), jnp.full(len(head), level, jnp.int32)))
    parts.append(mid)
    if len(tail):
        parts.append(o.from_linear_id(u64m.from_int(tail), jnp.full(len(tail), level, jnp.int32)))
    return Simplex(
        jnp.concatenate([p.anchor for p in parts]),
        jnp.concatenate([p.level for p in parts]),
        jnp.concatenate([p.stype for p in parts]),
    )


# -------------------------------------------------------------------- adapt
AdaptCallback = Callable[[np.ndarray, Simplex], np.ndarray]
# callback(tree_ids, elements) -> int flags: >0 refine, 0 keep, <0 coarsen.


def _family_heads(f: Forest) -> np.ndarray:
    """Boolean mask: element i starts a complete family of 2^d siblings.

    One batched parent/local-index/key sweep over all local elements."""
    b, n, nc = f.bops, f.num_local, f.ops.nc
    heads = np.zeros(n, bool)
    if n < nc:
        return heads
    s = f.simplices()
    parent, iloc = b.parent_and_local_index(s)
    with fetch_span("local_index"):
        iloc = np.asarray(iloc)
    pkey = b.morton_key_np(parent)
    cand = np.nonzero((iloc[: n - nc + 1] == 0) & (f.level[: n - nc + 1] > 0))[0]
    ok = np.ones(len(cand), bool)
    for k in range(1, nc):
        ok &= (
            (iloc[cand + k] == k)
            & (pkey[cand + k] == pkey[cand])
            & (f.level[cand + k] == f.level[cand])
            & (f.tree[cand + k] == f.tree[cand])
        )
    heads[cand[ok]] = True
    return heads


def adapt(f: Forest, callback: AdaptCallback, recursive: bool = False,
          max_passes: int = 64) -> Forest:
    """Paper Section 5.2 (Adapt): refine/coarsen local elements by callback.

    Honors the paper's recursion assumptions: elements created by refinement
    are not coarsened within the same call, and vice versa.
    Note: like the paper's Adapt, this is process-local; families straddling
    a partition boundary are not coarsened (call `partition` first if needed).

    On a mixed-class mesh the leaves are grouped by tree element class and
    adapted per class group (the callback sees each group's (tree_ids,
    elements) separately); sibling families never straddle classes because
    classes are unions of whole trees.
    """
    with telemetry.span("forest.adapt"):
        groups = _class_groups(f)
        if len(groups) > 1:
            parts = [_adapt_impl(_subforest(f, idx), callback, recursive, max_passes)
                     for _, idx in groups]
            return _merge_class_groups(f, parts)
        return _adapt_impl(f, callback, recursive, max_passes)


def _adapt_impl(f: Forest, callback: AdaptCallback, recursive: bool,
                max_passes: int) -> Forest:
    o = f.ops
    nc = o.nc
    bops = f.bops
    refined_origin = np.zeros(f.num_local, bool)   # created by refine this call
    coarsened_origin = np.zeros(f.num_local, bool)
    for pass_no in range(max_passes):
        n = f.num_local
        if n == 0:
            return f
        with telemetry.span("adapt.pass", pass_no=pass_no, elements=n):
            s = f.simplices()
            with telemetry.span("adapt.callback"):
                flags = np.asarray(callback(f.tree, s)).astype(np.int32)
            assert flags.shape == (n,)
            with telemetry.span("adapt.families"):
                # never coarsen refine-children / never refine coarsen-parents
                flags = np.where(refined_origin & (flags < 0), 0, flags)
                flags = np.where(coarsened_origin & (flags > 0), 0, flags)
                heads = _family_heads(f)
                coarsen_head = heads.copy()
                for k in range(nc):
                    idx = np.nonzero(heads)[0] + k
                    coarsen_head[np.nonzero(heads)[0]] &= flags[idx] < 0
                # members of a coarsened family
                member = np.zeros(n, bool)
                hidx = np.nonzero(coarsen_head)[0]
                for k in range(nc):
                    member[hidx + k] = True
                refine = (flags > 0) & ~member & (f.level < o.L)
            if not refine.any() and not coarsen_head.any():
                break
            keep = ~refine & ~member

            # sizes: keep->1, refine->nc, family head->1 (others 0)
            counts = keep.astype(np.int64) + refine.astype(np.int64) * nc + coarsen_head.astype(np.int64)
            total = int(counts.sum())
            offs = np.zeros(n + 1, np.int64)
            np.cumsum(counts, out=offs[1:])
            A = np.zeros((total, o.d), np.int32)
            L = np.zeros(total, np.int32)
            B = np.zeros(total, np.int32)
            T = np.zeros(total, np.int32)
            OR = np.zeros(total, bool)
            OC = np.zeros(total, bool)
            # keeps
            kidx = np.nonzero(keep)[0]
            A[offs[kidx]] = f.anchor[kidx]
            L[offs[kidx]] = f.level[kidx]
            B[offs[kidx]] = f.stype[kidx]
            T[offs[kidx]] = f.tree[kidx]
            OR[offs[kidx]] = refined_origin[kidx]
            OC[offs[kidx]] = coarsened_origin[kidx]
            # refines
            ridx = np.nonzero(refine)[0]
            if len(ridx):
                with telemetry.span("adapt.children", elements=len(ridx)):
                    rs = Simplex(jnp.asarray(f.anchor[ridx]), jnp.asarray(f.level[ridx]),
                                 jnp.asarray(f.stype[ridx]))
                    kids = bops.children(rs)
                    with fetch_span("children"):
                        ka = np.asarray(kids.anchor)      # (m, nc, d)
                        kl = np.asarray(kids.level)
                        kb = np.asarray(kids.stype)
                    pos = offs[ridx][:, None] + np.arange(nc)[None, :]
                    A[pos.reshape(-1)] = ka.reshape(-1, o.d)
                    L[pos.reshape(-1)] = kl.reshape(-1)
                    B[pos.reshape(-1)] = kb.reshape(-1)
                    T[pos.reshape(-1)] = np.repeat(f.tree[ridx], nc)
                    OR[pos.reshape(-1)] = True
            # coarsens
            if len(hidx):
                with telemetry.span("adapt.parents", elements=len(hidx)):
                    hs = Simplex(jnp.asarray(f.anchor[hidx]), jnp.asarray(f.level[hidx]),
                                 jnp.asarray(f.stype[hidx]))
                    par = bops.parent(hs)
                    with fetch_span("parent"):
                        pa = np.asarray(par.anchor)
                        pl = np.asarray(par.level)
                        pb = np.asarray(par.stype)
                    A[offs[hidx]] = pa
                    L[offs[hidx]] = pl
                    B[offs[hidx]] = pb
                    T[offs[hidx]] = f.tree[hidx]
                    OC[offs[hidx]] = True
            f = f.replace_elements(A, L, B, T)
            refined_origin, coarsened_origin = OR, OC
            if not recursive:
                break
    return f


# ---------------------------------------------------------------- partition
# Resilience hooks: callables `hook(event, forests, comm)` fired at the
# entry and exit of the phase-changing drivers — events "balance:begin"/
# "balance:end", "repartition:begin"/"repartition:end" (and "partition:*"
# via the construction-time wrapper).  `repro.core.resilience.Autosaver`
# installs here to checkpoint the pre-phase state, so a rank crash inside
# a collective always has a consistent snapshot behind it.  Hooks fire
# OUTSIDE the phase's comm context: checkpoint traffic meters under its
# own phase, never polluting balance/repartition byte attribution.
RESILIENCE_HOOKS: list = []


def _fire_hooks(event: str, forests: list, comm: Comm) -> None:
    for hook in list(RESILIENCE_HOOKS):
        hook(event, forests, comm)


def partition(forests: list[Forest], comm: Comm,
              weights: list[np.ndarray] | None = None) -> list[Forest]:
    """Paper Section 5 (Partition): weighted SFC repartitioning, linear time.

    A thin wrapper over `repartition` — the packed-wire migration engine —
    kept for the construction-time call sites and metered under its own
    "partition" phase."""
    return repartition(forests, comm, weights=weights, _phase="partition")


def repartition(forests: list[Forest], comm: Comm,
                weights: list[np.ndarray] | None = None,
                overlap: bool = True, _phase: str = "repartition") -> list[Forest]:
    """Dynamic repartition with element migration (see `_repartition_impl`
    for the algorithm); fires the `RESILIENCE_HOOKS` begin/end events
    around the migration."""
    _fire_hooks(f"{_phase}:begin", forests, comm)
    out = _repartition_impl(forests, comm, weights=weights,
                            overlap=overlap, _phase=_phase)
    _fire_hooks(f"{_phase}:end", out, comm)
    return out


def _repartition_impl(forests: list[Forest], comm: Comm,
                      weights: list[np.ndarray] | None = None,
                      overlap: bool = True,
                      _phase: str = "repartition") -> list[Forest]:
    """Dynamic repartition with element migration — the post-adapt rebalance
    step (Holke's dissertation; p4est's `p4est_partition` between refine and
    balance).

    Every rank derives the paper's weighted Partition targets from the
    GLOBAL weight prefix sums (`placement.target_ranks_np`: midpoint rule,
    monotone), so the targets are ascending and each destination's elements
    form one contiguous run of the local SFC order.  Migrating runs ship as
    the Remark-20 wire triples (`types.pack_wire`, 13 bytes/element — the
    same blobs Balance/Ghost move) in STORED order, over one nonblocking
    `ialltoallv`; receivers recover (anchor, stype) with a single batched
    Algorithm-4.8 `decode`.  The collectives are double buffered the same
    way `balance()` hides its flights: the weight-total allgather flies
    while the local midpoint prefix sums compute, and the migration
    alltoallv flies while the kept slice is assembled (`overlap=False`
    completes each collective at its post site — bit-identical, benchmark
    baseline).

    Merging needs no sort: old ranks own ascending contiguous global
    intervals, so sender p's contribution precedes sender p+1's, and the
    kept slice slots in at p == rank.  The stored SFC order of every output
    forest is revalidated (strictly ascending (tree, key)) before return.

    Returns NEW `Forest` objects — derived structures (ghost layers, face
    sweeps, partition markers) refer to the old ownership and must be
    recomputed from the result; the weight list, when given, is one
    nonnegative float per LOCAL element in stored order.
    """
    P = comm.size
    nloc = len(forests)
    d = forests[0].d
    cm = forests[0].cmesh
    classes = _forest_classes(forests)
    if weights is None:
        weights = [np.ones(f.num_local, np.float64) for f in forests]
    weights = [np.asarray(w, np.float64) for w in weights]
    for f, w in zip(forests, weights):
        if w.shape != (f.num_local,):
            raise ValueError(
                f"need one weight per local element: {w.shape} vs "
                f"{f.num_local} elements")
        if len(w) and float(w.min()) < 0:
            raise ValueError("element weights must be nonnegative")

    def post(h: CommHandle) -> CommHandle:
        return h if overlap else CommHandle.ready(h.wait())

    with comm.phase(_phase):
        # the weight-total allgather flies while every local rank computes
        # its midpoint prefix sums (the overlap window of merge point 1)
        h_tot = post(comm.iallgather([float(w.sum()) for w in weights]))
        cums = [np.cumsum(w) - w / 2.0 for w in weights]
        tots = h_tot.wait()
        prefix = np.concatenate([[0.0], np.cumsum(tots)])
        W = float(prefix[-1])
        send, keep_off = [], []
        for i, f in enumerate(forests):
            g = comm.local_ranks[i]
            t = target_ranks_np(prefix[g] + cums[i], P, W)
            # monotone targets => destination q's elements are the stored
            # run [offs[q], offs[q+1]) — found by searchsorted, no masks
            offs = np.searchsorted(t, np.arange(P + 1))
            row = [np.zeros(0, np.uint8)] * P
            for q in range(P):
                a, b = int(offs[q]), int(offs[q + 1])
                if q != g and b > a:
                    # stored order IS SFC order: pack without sorting; the
                    # wire triples carry each element's tree class in the
                    # level byte's class bits (zeros — byte-identical to the
                    # legacy format — on a single-class simplex mesh)
                    ec_col = (0 if cm is None
                              else cm.tree_eclass[f.tree[a:b]])
                    row[q] = pack_wire(f.tree[a:b], f.keys[a:b],
                                       f.level[a:b], eclass=ec_col)
            keep_off.append((int(offs[g]), int(offs[g + 1])))
            send.append(row)
        h_mig = post(comm.ialltoallv(send))
        # overlap window of merge point 2: slice out the kept runs while
        # the migration blobs are on the wire
        kept = []
        for i, f in enumerate(forests):
            a, b = keep_off[i]
            kept.append((f.anchor[a:b], f.level[a:b], f.stype[a:b],
                         f.tree[a:b]))
        recv = h_mig.wait()
    out = []
    for i, f in enumerate(forests):
        g = comm.local_ranks[i]
        segs = []  # (src rank, tree, key, level) in ascending sender order
        for p in range(P):
            buf = recv[i][p] if p != g else None
            if buf is not None and len(buf):
                segs.append((p, *unpack_wire(buf)))
        if segs:
            rt = np.concatenate([s[1] for s in segs])
            rk = np.concatenate([s[2] for s in segs])
            rl = np.concatenate([s[3] for s in segs])
            # ONE batched Algorithm-4.8 decode per element class recovers
            # (anchor, stype) for everything this rank received, across all
            # senders (single-class meshes: exactly one dispatch, as before)
            if len(classes) == 1:
                dec = get_batch_ops(d, eclass=classes[0]).decode(
                    u64m.from_int(rk), jnp.asarray(rl, jnp.int32))
                ra, rs = np.asarray(dec.anchor), np.asarray(dec.stype)
            else:
                te = cm.tree_eclass[rt]
                ra = np.zeros((len(rt), d), np.int32)
                rs = np.zeros(len(rt), np.int32)
                for ec in classes:
                    m = te == ec
                    if m.any():
                        dec = get_batch_ops(d, eclass=ec).decode(
                            u64m.from_int(rk[m]), jnp.asarray(rl[m], jnp.int32))
                        ra[m] = np.asarray(dec.anchor)
                        rs[m] = np.asarray(dec.stype)
        # each sender's run is SFC-contiguous and senders cover ascending
        # global intervals, so concatenating in sender order (the kept
        # slice at p == g) restores the stored order without a sort
        blocks, pos, si = [], 0, 0
        for p in range(P):
            if p == g:
                blocks.append(kept[i])
            elif si < len(segs) and segs[si][0] == p:
                n = len(segs[si][3])
                blocks.append((ra[pos:pos + n], rl[pos:pos + n],
                               rs[pos:pos + n], rt[pos:pos + n]))
                pos += n
                si += 1
        f2 = f.replace_elements(
            np.concatenate([b[0] for b in blocks]),
            np.concatenate([b[1] for b in blocks]),
            np.concatenate([b[2] for b in blocks]),
            np.concatenate([b[3] for b in blocks]))
        # stored-order revalidation: migration must hand every rank one
        # strictly ascending (tree, key) run
        tt = f2.tree.astype(np.int64)
        ok = (tt[1:] > tt[:-1]) | ((tt[1:] == tt[:-1])
                                   & (f2.keys[1:] > f2.keys[:-1]))
        if not bool(ok.all()):
            raise RuntimeError(
                f"repartition broke stored SFC order on rank {g}")
        out.append(f2)
    return out


def load_imbalance(forests: list[Forest], comm: Comm,
                   weights: list[np.ndarray] | None = None) -> float:
    """max rank load / mean rank load over the world (1.0 = perfect), with
    unit weights (element counts) by default — the quantity `repartition`
    drives toward 1 and the acceptance gate the benchmarks record."""
    if weights is None:
        weights = [np.ones(f.num_local, np.float64) for f in forests]
    loads = np.asarray(
        comm.allgather([float(np.sum(w)) for w in weights]), np.float64)
    return float(loads.max() / max(float(loads.mean()), 1e-300))


def _marker_pairs(forests: list[Forest]) -> list:
    """Per local rank, the (tree, key) of its first element — the payload of
    the marker allgather (split out so `balance` can post it nonblocking)."""
    return [tuple(map(int, f.global_first_desc_key())) for f in forests]


def _markers_from_pairs(K: int, P: int, pairs) -> tuple[np.ndarray, np.ndarray]:
    """Allgathered first-element pairs -> the lex-sorted marker table.
    Empty ranks inherit the next non-empty rank's marker (trailing empties
    keep the (num_trees, 0) sentinel), so runs of duplicates route keys to
    the LAST duplicate — the non-empty rank (`owner_rank` resolves to the
    last marker lex-<= the key).  Monotonicity is a correctness invariant
    of every downstream searchsorted, so it is checked, not assumed."""
    mt = np.empty(P, np.int32)
    mk = np.empty(P, np.uint64)
    nxt = (K, 0)
    for r in range(P - 1, -1, -1):
        t, k = pairs[r]
        if t >= K:  # empty rank: route to the next non-empty range
            t, k = nxt
        mt[r], mk[r] = t, np.uint64(k)
        nxt = (t, k)
    lex = list(zip(mt.tolist(), mk.tolist()))
    if lex != sorted(lex):
        raise RuntimeError(
            f"partition markers are not lex-sorted: {lex} — the rank "
            "first-element keys disagree with the stored SFC order")
    return mt, mk


def partition_markers(forests: list[Forest], comm: Comm):
    """Allgather the partition-marker table: per rank the (tree, key) of its
    first local element (`global_first_desc_key`).  Empty ranks inherit the
    next non-empty rank's marker (trailing empties keep the (num_trees, 0)
    sentinel), so the table is lex-sorted and `owner_rank` — a vectorized
    searchsorted on the batch backends — resolves any (tree, key) to the
    rank whose contiguous SFC range holds it.  This P-entry exchange is the
    ONLY global metadata Balance/Ghost need: everything else travels as
    boundary-local key-range messages."""
    K = forests[0].num_trees
    pairs = comm.allgather(_marker_pairs(forests))
    return _markers_from_pairs(K, comm.size, pairs)


# ------------------------------------------------------- cross-tree lookups
FACE_INTERIOR = 0          # neighbor in the same tree
FACE_INTER_TREE = 1        # neighbor across a glued tree face (via Cmesh)
FACE_DOMAIN_BOUNDARY = 2   # no neighbor: true domain boundary


@dataclasses.dataclass
class FaceSweepLayer:
    """Host-side result of ONE fused `face_sweep` dispatch over an element
    layer, with the cross-tree fixup already applied: for every face of
    every element, where its neighbor region lives.  Arrays carry a leading
    face axis of length nf (d+1 for simplices, 2d for hexes); `level` is
    shared (same-level neighbors).

      tgt     (nf, n) tree whose leaf table holds the neighbor region
      nkey    (nf, n) uint64 neighbor morton key *in that tree's frame*
              (garbage where ~valid — never read it there)
      valid   (nf, n) False at the domain boundary
      anchor  (nf, n, d) / stype (nf, n): the neighbor, re-expressed in the
              target tree's frame where the face crosses into another tree
      dual    (nf, n) neighbor's face index back to us, renumbered through
              the connection's face map for cross-tree faces
      kind    (nf, n) FACE_INTERIOR / FACE_INTER_TREE / FACE_DOMAIN_BOUNDARY

    The Balance/Ghost/Iterate hot loops compute one sweep per eval layer and
    slice per-face views from it (`face`), instead of re-dispatching
    face_neighbor + is_inside_root + morton_key for every face."""

    tgt: np.ndarray
    nkey: np.ndarray
    valid: np.ndarray
    anchor: np.ndarray
    level: np.ndarray
    stype: np.ndarray
    dual: np.ndarray
    kind: np.ndarray

    def face(self, f: int):
        """The (tgt, nkey, valid, nb, dual, kind) view of one face — what the
        per-face `_face_lookup` used to return."""
        nb = Simplex(
            jnp.asarray(self.anchor[f]), jnp.asarray(self.level),
            jnp.asarray(self.stype[f]),
        )
        return (self.tgt[f], self.nkey[f], self.valid[f], nb,
                self.dual[f], self.kind[f])


def face_sweep_layer(f: Forest, tree_ids: np.ndarray, s: Simplex) -> FaceSweepLayer:
    """Fused neighbor lookup for ALL faces of the elements in `s` (any subset
    of local elements; `tree_ids` is their owning-tree column — the
    boundary-only Balance rounds pass just the changed layer here).

    One batched `face_sweep` dispatch computes every face's same-level
    neighbor, inside-root mask, and morton key; the results are materialized
    to the host once.  Faces that leave the root are then re-expressed in the
    neighbor tree's frame via `f.cmesh`: every crossing gathers its
    connection's (M, c, type/face maps) rows and ALL crossings get one
    batched transform + key recompute — no per-connection Python loop.

    This is the single seam where the old is_root_boundary notion splits
    into "interior", "inter-tree face" (followed through `f.cmesh`), and
    "domain boundary" (no Cmesh connection).

    The layer must be single-class (the per-class drivers guarantee it);
    the class is derived from `tree_ids` and selects the fused sweep's
    (d, eclass)-keyed program — one dispatch per class per eval layer."""
    ec = _layer_eclass(f, tree_ids)
    bops = get_batch_ops(f.d, eclass=ec)
    d = f.d
    nf = bops.nf
    sw = bops.face_sweep(s)
    # one host materialization per field; all later bookkeeping is numpy
    with fetch_span("face_sweep"):
        anchor = np.asarray(sw.neighbor.anchor)
        stype = np.asarray(sw.neighbor.stype)
        level = np.asarray(s.level)
        inside = np.asarray(sw.inside)
        dual = np.asarray(sw.dual)
        nkey = u64m.to_np(sw.key)
    tree_ids = np.asarray(tree_ids)
    n = level.shape[0]
    tgt = np.broadcast_to(tree_ids, (nf, n)).copy()
    valid = inside.copy()
    kind = np.where(inside, FACE_INTERIOR, FACE_DOMAIN_BOUNDARY).astype(np.int32)
    cm = f.cmesh
    if cm is not None and not inside.all():
        anchor = anchor.copy()
        stype = stype.copy()
        dual = dual.copy()
        fidx, eidx = np.nonzero(~inside)
        s_anchor = np.asarray(s.anchor)
        s_stype = np.asarray(s.stype)
        src = Simplex(
            jnp.asarray(s_anchor[eidx]), jnp.asarray(level[eidx]),
            jnp.asarray(s_stype[eidx]),
        )
        rf = cm.root_face_of(src, fidx, eclass=ec)
        t1 = tree_ids[eidx]
        conn = (rf >= 0) & (cm.face_tree[t1, np.maximum(rf, 0)] >= 0)
        keep = np.nonzero(conn)[0]
        if len(keep):
            # ONE batched transform for ALL crossings, whatever connection
            # they use: gather each crossing's per-connection (M, c, maps)
            # rows and apply anchor' = M @ anchor + c (+ the reflected-axis
            # -h shift) in int64, wrapping to int32 once at the end — int32
            # ring arithmetic wraps mod 2^32, so the single final wrap is
            # bit-identical to the per-connection int32 path.
            fk, ek, rfk, t1k = fidx[keep], eidx[keep], rf[keep], t1[keep]
            Mv = cm.face_M[t1k, rfk].astype(np.int64)      # (c, d, d)
            cv = cm.face_c[t1k, rfk].astype(np.int64)      # (c, d)
            av = anchor[fk, ek].astype(np.int64)           # (c, d)
            h = np.int64(1) << (np.int64(cm.L) - level[ek].astype(np.int64))
            neg = np.minimum(Mv.sum(axis=-1), 0)           # -1 on reflected rows
            a2 = (av[:, None, :] * Mv).sum(axis=-1) + cv + h[:, None] * neg
            old_stype = stype[fk, ek]
            anchor[fk, ek] = wrap_i32(a2)
            stype[fk, ek] = cm.face_typemap[t1k, rfk, old_stype]
            dual[fk, ek] = cm.face_facemap[t1k, rfk, old_stype, dual[fk, ek]]
            tgt[fk, ek] = cm.face_tree[t1k, rfk]
            valid[fk, ek] = True
            kind[fk, ek] = FACE_INTER_TREE
            # only the crossed entries changed anchors: recompute just their
            # keys, in one batched call (the sweep's keys stand elsewhere)
            crossed = Simplex(
                jnp.asarray(anchor[fk, ek]), jnp.asarray(level[ek]),
                jnp.asarray(stype[fk, ek]),
            )
            nkey[fk, ek] = bops.morton_key_np(crossed)
    return FaceSweepLayer(tgt, nkey, valid, anchor, level, stype, dual, kind)


def _face_lookup(f: Forest, tree_ids: np.ndarray, s: Simplex, face: int):
    """Single-face view of `face_sweep_layer` (kept for callers that really
    want one face, e.g. `face_kind`); the hot loops slice the full sweep."""
    return face_sweep_layer(f, tree_ids, s).face(face)


def face_kinds(f: Forest, s: Simplex) -> np.ndarray:
    """Classify every face of every element in one fused sweep: (nf, n)
    matrix of FACE_INTERIOR (0) / FACE_INTER_TREE (1) /
    FACE_DOMAIN_BOUNDARY (2) — the split of the old single is-root-boundary
    test under the coarse mesh.  Prefer this over looping `face_kind` per
    face: the whole matrix costs one sweep dispatch."""
    return face_sweep_layer(f, f.tree, s).kind


def face_kind(f: Forest, s: Simplex, face: int) -> np.ndarray:
    """One face's row of `face_kinds`.  NOTE: every call runs the full
    all-faces sweep and keeps one row — never loop this over faces; call
    `face_kinds` once instead."""
    return face_kinds(f, s)[face]


# ------------------------------------------------------------------ balance
class BalanceNonConvergence(RuntimeError):
    """Balance hit `max_rounds` before reaching the 2:1 fixpoint.

    Carries the diagnostic context: `rounds` (how many refine/exchange
    rounds ran) and `dirty_per_rank` (per rank, how many local elements
    still violated the 2:1 condition when the budget ran out)."""

    def __init__(self, rounds: int, dirty_per_rank):
        self.rounds = rounds
        self.dirty_per_rank = [int(c) for c in dirty_per_rank]
        super().__init__(
            f"balance did not converge after {rounds} rounds; per-rank "
            f"still-dirty element counts: {self.dirty_per_rank}"
        )


def _elem_spans(d: int, L: int, level: np.ndarray) -> np.ndarray:
    """Key-interval width 2^(d*(L-level)) of each element, as uint64."""
    return np.uint64(1) << (np.uint64(d) * (np.uint64(L) - level.astype(np.uint64)))


def _range_max(values: np.ndarray, lo: np.ndarray, hi: np.ndarray) -> np.ndarray:
    """Per-slice max(values[lo:hi]) (or -1 for empty slices), vectorized via
    maximum.reduceat over independent [lo, hi) segment pairs."""
    out = np.full(len(lo), -1, np.int32)
    m = hi > lo
    if not m.any():
        return out
    ext = np.append(np.asarray(values, np.int32), np.int32(-1))  # allow hi == len
    idx = np.nonzero(m)[0]
    pairs = np.stack([lo[idx], hi[idx]], axis=1).reshape(-1)
    out[idx] = np.maximum.reduceat(ext, pairs)[::2]
    return out


def _resident_sweep(f: Forest, bops: BatchedOps):
    """The resident face sweep of ALL of a rank's local elements, memoized
    per (Forest object, backend, element class): leaf arrays are immutable,
    so repeated Balance rounds over an unchanged rank — and a Ghost
    following a Balance — reuse the device-resident sweep instead of
    re-padding and re-dispatching it.  A cache hit still charges one
    `face_sweep` dispatch so the meters keep their evals-per-round
    semantics."""
    if f.num_local == 0:
        return None
    cache = f.__dict__.setdefault("_sweep_cache", {})
    h = cache.get((bops.backend, bops.eclass))
    if h is not None:
        batch_count_dispatch("face_sweep")
        return h
    if f.cmesh is None:
        h = bops.sweep_full(f.simplices(), f.tree)
    else:
        sw = face_sweep_layer(f, f.tree, f.simplices())
        h = bops.sweep_from_host(sw.tgt, sw.nkey, sw.valid, sw.dual, sw.level)
    cache[(bops.backend, bops.eclass)] = h
    return h


def _pack_triples(triples, eclass: int = ECLASS_SIMPLEX) -> np.ndarray:
    """(tree, key, level) triples -> deterministic 13-byte/entry wire buffer,
    lex-ordered by (tree, key, level) via np.lexsort over the column arrays
    (bit-identical to sorting the Python tuples, without the tuple churn).
    The class-group exchanges tag every entry with the group's element
    class (zero — byte-identical to the legacy format — for simplices)."""
    tl = list(triples)
    if not tl:
        return np.zeros(0, np.uint8)
    t = np.array([x[0] for x in tl], np.int32)
    k = np.array([x[1] for x in tl], np.uint64)
    lv = np.array([x[2] for x in tl], np.int32)
    order = np.lexsort((lv, k, t))
    return pack_wire(t[order], k[order], lv[order], eclass=eclass)


def balance(forests: list[Forest], comm: Comm, max_rounds: int = 64,
            overlap: bool = True) -> list[Forest]:
    """2:1 balance across faces (see `_balance_impl` for the full ripple
    algorithm); fires the `RESILIENCE_HOOKS` begin/end events around it.

    On a mixed-class mesh the ripple runs once per element class (classes
    are unions of whole trees and cross-class faces are domain boundaries,
    so the class groups are independent); every rank iterates the classes
    in the same sorted order, and the per-rank results merge back into
    stored (tree, key) order.  Single-class meshes take the direct path —
    dispatch for dispatch the pre-eclass pipeline."""
    with telemetry.span("forest.balance"):
        _fire_hooks("balance:begin", forests, comm)
        classes = _forest_classes(forests)
        if len(classes) == 1:
            out = _balance_impl(forests, comm, max_rounds=max_rounds,
                                overlap=overlap, eclass=classes[0])
        else:
            parts: list[list] = [[] for _ in forests]
            for ec in classes:
                res = _balance_impl(_class_subforests(forests, ec), comm,
                                    max_rounds=max_rounds, overlap=overlap,
                                    eclass=ec)
                for i, r in enumerate(res):
                    parts[i].append(r)
            out = [_merge_class_groups(forests[i], ps)
                   for i, ps in enumerate(parts)]
        _fire_hooks("balance:end", out, comm)
        return out


def _balance_impl(forests: list[Forest], comm: Comm, max_rounds: int = 64,
                  overlap: bool = True, eclass: int = ECLASS_SIMPLEX) -> list[Forest]:
    """2:1 balance across faces (ripple algorithm), across tree faces when
    the forest carries a Cmesh (intra-tree otherwise) — message based, with
    the boundary exchange overlapped behind interior compute.

    A leaf is refined when some face-neighbor key interval contains a leaf
    more than one level finer; neighbor regions behind a glued tree face are
    queried in the neighbor tree's frame.  No rank ever materializes the
    global leaf table: routing uses only the allgathered P partition markers
    (`partition_markers` + the fused `eval_route` owner-range program), and
    the wire carries

      * key-range queries — packed (tree, key, level) triples an element
        sends to every remote owner rank of its neighbor interval (issued
        once per element, when it is created);
      * replies — for each query whose local slice holds a leaf finer than
        the querier tolerates, one (tree, key, level) witness triple; and
      * boundary-layer notifications — after a refinement round, the NEW
        leaves are pushed only to the ranks whose registered query
        intervals they fall into (the Isaac-Burstedde-Ghattas ripple:
        each round exchanges only the boundary layer that changed).

    Received witnesses/notifications accumulate in a per-rank cache of
    remote leaves, so each round's refine decision is a purely local sweep
    (local sorted arrays + cache).

    The per-round evaluation is *device resident* on the jnp/pallas
    backends: the face sweep stays on device as a `SweepHandle`, the local
    leaves and the remote cache upload as `LeafTable`s, and three fused
    programs (`BatchedOps.eval_2to1` / `eval_cache` / `eval_route`) compute
    the 2:1 need-masks, the boundary-adjacent mask, and the compacted query
    candidates without materializing sweep fields to numpy — the host only
    slices the compacted routing rows to build wire triples.  All buffers
    are padded to power-of-two buckets so jit never retraces across rounds
    at a fixed bucket (`batch.trace_counts()`); the reference backend runs
    the same algorithms eagerly and is the bit-identical oracle.

    The round loop is *double buffered* (p4est-style overlap): round r's
    queries and notifications are posted nonblocking (`Comm.ialltoallv`) as
    soon as round r-1's refinement produced them, and the next round's
    fused face sweep runs while they are on the wire.  The first merge
    point waits them, answers the received queries, and immediately posts
    the replies — which then hide behind the interior 2:1 eval against the
    LOCAL sorted arrays (complete for every interior element, whose
    neighbor intervals lie inside this rank's marker range).  Only after
    the second merge point folds the replies do the boundary-adjacent
    elements finish against the refreshed remote-leaf cache; the
    convergence vote hides behind the refinement, and the initial marker
    allgather behind the first sweep (which double-duties as the initial
    query builder).  The split changes scheduling only: the refine
    decisions, the message bytes, and the least fixpoint are bit-identical
    to the serialized loop (`overlap=False` completes every collective at
    its post site — the benchmark baseline) and to `balance_oracle`,
    element for element.  Raises `BalanceNonConvergence` with per-rank
    diagnostics on round exhaustion.
    """
    if max_rounds < 1:
        raise ValueError("max_rounds must be >= 1")
    d = forests[0].d
    o = get_ops(d, eclass)
    L, nc = o.L, o.nc
    bops = get_batch_ops(d, eclass=eclass)
    P = comm.size
    nloc = len(forests)
    forests = list(forests)

    def post(h: CommHandle) -> CommHandle:
        # serialized mode: complete every collective where it was posted
        return h if overlap else CommHandle.ready(h.wait())

    with comm.phase("balance"):
        # markers are posted nonblocking; the first face sweep hides the wire
        K = forests[0].num_trees
        h_mk = post(comm.iallgather(_marker_pairs(forests)))
        mt = mk = None  # assigned at the marker merge point below
        # answering side: (tree, span_exp) -> {k0: (min queried level, ranks)}
        registries: list[dict] = [{} for _ in range(nloc)]
        # requesting side: remote leaves learned from replies/notifications,
        # recompiled into a lex-sorted LeafTable for the fused cache eval
        cache_entries: list[set] = [set() for _ in range(nloc)]
        cache_tables: list = [None] * nloc

        def recompile_cache(i: int) -> None:
            ents = cache_entries[i]
            if not ents:
                cache_tables[i] = None
                return
            t = np.fromiter((e[0] for e in ents), np.int32, len(ents))
            k = np.fromiter((e[1] for e in ents), np.uint64, len(ents))
            lv = np.fromiter((e[2] for e in ents), np.int32, len(ents))
            order = np.lexsort((lv, k, t))
            cache_tables[i] = bops.upload_table(t[order], k[order], lv[order])

        def sweep_handle(i: int, sel: np.ndarray | None = None):
            """The round's resident face sweep of rank i's elements (or the
            `sel` subset): ONE batched dispatch whose results stay where the
            backend computes — the fused eval programs consume the handle
            and only compacted routing rows return to the host.  The cmesh
            cross-tree path sweeps through `face_sweep_layer` (one host
            fixup) and re-uploads.  Full layers memoize on the Forest
            (`_resident_sweep`); subset layers are round-specific."""
            f = forests[i]
            if sel is None:
                return _resident_sweep(f, bops)
            if len(sel) == 0:
                return None
            s = Simplex(jnp.asarray(f.anchor[sel]), jnp.asarray(f.level[sel]),
                        jnp.asarray(f.stype[sel]))
            tree_ids = f.tree[sel]
            if f.cmesh is None:
                return bops.sweep_full(s, tree_ids)
            sw = face_sweep_layer(f, tree_ids, s)
            return bops.sweep_from_host(sw.tgt, sw.nkey, sw.valid, sw.dual,
                                        sw.level)

        def upload_tables() -> list:
            # leaf arrays are immutable per Forest: upload once per
            # (forest, backend) and reuse across rounds / repeated balances
            out = []
            for f in forests:
                if not f.num_local:
                    out.append(None)
                    continue
                cache = f.__dict__.setdefault("_leaf_table_cache", {})
                tbl = cache.get(bops.backend)
                if tbl is None:
                    tbl = bops.upload_table(f.tree, f.keys, f.level)
                    cache[bops.backend] = tbl
                out.append(tbl)
            return out

        def route_to_dests(i: int, rp) -> dict:
            """RoutePairs rows -> dest rank -> {(t, k0, l)} query sets."""
            g = comm.local_ranks[i]
            dest: dict[int, set] = {}
            for j in range(len(rp.tree)):
                q = (int(rp.tree[j]), int(rp.key[j]), int(rp.level[j]))
                for r in range(int(rp.first[j]), int(rp.last[j]) + 1):
                    if r != g:
                        dest.setdefault(r, set()).add(q)
            return dest

        def build_queries(i: int, sel: np.ndarray) -> dict:
            """Queries for an element subset (the per-round child layers):
            one fused sweep + the fused routing eval."""
            with telemetry.span("balance.route", elements=len(sel)):
                h = sweep_handle(i, sel)
                if h is None:
                    return {}
                return route_to_dests(
                    i, bops.eval_route(h, mt, mk, comm.local_ranks[i]))

        def answer(i: int, src: int, buf: np.ndarray) -> set:
            """Register one rank's queries and answer them from the local
            sorted arrays: witness triples for every query whose local slice
            holds a leaf finer than the querier tolerates.  The interval
            search is vectorized (grouped by target tree + one reduceat for
            the slice maxima); only the dict-shaped registry update and the
            few actual witnesses stay per entry."""
            f = forests[i]
            qt, qk, ql = unpack_wire(buf)
            reply: set = set()
            reg = registries[i]
            for t, k0, l in zip(qt.tolist(), qk.tolist(), ql.tolist()):
                ent = reg.setdefault((t, d * (L - l)), {})
                prev = ent.get(k0)
                ent[k0] = ((l, {src}) if prev is None
                           else (min(prev[0], l), prev[1] | {src}))
            span = _elem_spans(d, L, ql)
            starts = np.zeros(len(qt), np.int64)
            ends = np.zeros(len(qt), np.int64)
            for t in np.unique(qt):
                m = qt == t
                a0, b0 = np.searchsorted(f.tree, [t, t + 1])
                keys_t = f.keys[a0:b0]
                starts[m] = a0 + np.searchsorted(keys_t, qk[m])
                ends[m] = a0 + np.searchsorted(keys_t, qk[m] + span[m])
            mx = _range_max(f.level, starts, ends)
            for q in np.nonzero(mx > ql + 1)[0].tolist():
                a = int(starts[q])
                j = a + int(np.argmax(f.level[a:int(ends[q])]))
                reply.add((int(qt[q]), int(f.keys[j]), int(mx[q])))
            return reply

        def post_exchange(dests: list[dict], notifs: list[dict] | None) -> CommHandle:
            """Ship (notifications, queries) per destination — nonblocking;
            the next `eval_round` waits it at the round's first merge point."""
            send = []
            for i in range(nloc):
                row = []
                for q in range(P):
                    nt = notifs[i].get(q, ()) if notifs is not None else ()
                    row.append((_pack_triples(nt, eclass),
                                _pack_triples(dests[i].get(q, ()), eclass)))
                send.append(row)
            return comm.ialltoallv(send)

        def eval_round(pending: CommHandle, pre=None) -> list[np.ndarray]:
            """One double-buffered round evaluation.  Timeline:

              sweep faces + upload   <- the device sweep programs and the
                local leaf tables       round's leaf tables dispatch here
                                        and compute while the in-flight
                                        `pending` queries/notifications
                                        (posted at the END of the previous
                                        round) are on the wire
              merge 1: wait pending; answer queries; POST replies
              fold notifications; fused interior 2:1 eval (`eval_2to1`,
                local leaf table only) <- hides the in-flight replies
              merge 2: wait replies; fold; recompile caches
              fused boundary eval (`eval_cache`) against the refreshed
                remote-leaf cache

            The initial round passes the handles it already computed (they
            hid the marker allgather and built the first queries)."""
            if pre is None:
                with telemetry.span("balance.sweep"):
                    handles = [sweep_handle(i) for i in range(nloc)]
                    tables = upload_tables()
            else:
                handles, tables = pre
            with telemetry.span("balance.answer"):
                recv = pending.wait()
                reply_rows, notif_bufs = [], []
                for i in range(nloc):
                    g = comm.local_ranks[i]
                    row = [np.zeros(0, np.uint8)] * P
                    nbufs = []
                    for p in range(P):
                        if p == g or recv[i][p] is None:
                            continue
                        nbuf, qbuf = recv[i][p]
                        if len(nbuf):
                            nbufs.append(nbuf)
                        if len(qbuf):
                            row[p] = _pack_triples(answer(i, p, qbuf), eclass)
                    reply_rows.append(row)
                    notif_bufs.append(nbufs)
                hr = post(comm.ialltoallv(reply_rows))
                # everything below merge 1 overlaps the reply flight: fold the
                # received notifications, then the interior (local-only) eval
                for i in range(nloc):
                    for nbuf in notif_bufs[i]:
                        t_, k_, l_ = unpack_wire(nbuf)
                        cache_entries[i].update(
                            zip(t_.tolist(), k_.tolist(), l_.tolist()))
            with telemetry.span("balance.eval"):
                needs = []
                for i in range(nloc):
                    if handles[i] is None:
                        needs.append(np.zeros(forests[i].num_local, bool))
                    else:
                        nd, _bm = bops.eval_2to1(
                            handles[i], tables[i], mt, mk, comm.local_ranks[i])
                        needs.append(nd)
            with telemetry.span("balance.merge"):
                rrecv = hr.wait()
                for i in range(nloc):
                    g = comm.local_ranks[i]
                    for p in range(P):
                        buf = rrecv[i][p]
                        if p == g or buf is None or not len(buf):
                            continue
                        t_, k_, l_ = unpack_wire(buf)
                        cache_entries[i].update(zip(t_.tolist(), k_.tolist(), l_.tolist()))
                    recompile_cache(i)
            with telemetry.span("balance.eval_cache"):
                for i in range(nloc):
                    if handles[i] is not None and cache_tables[i] is not None:
                        needs[i] |= bops.eval_cache(
                            handles[i], cache_tables[i], mt, mk, comm.local_ranks[i])
            return needs

        def refine_and_build(needs: list[np.ndarray]):
            """Refine this round's violators and build the NEXT round's
            queries and notifications (runs while the convergence flag is
            on the wire)."""
            new_dests: list[dict] = [{} for _ in range(nloc)]
            new_notifs: list[dict] = [{} for _ in range(nloc)]
            with telemetry.span("balance.refine"):
                for i in range(nloc):
                    nd = needs[i]
                    if not nd.any():
                        continue
                    f = forests[i]
                    # the changed boundary layer: all children created this round
                    ev = np.nonzero(nd)[0]
                    ct = np.repeat(f.tree[ev], nc)
                    cl = np.repeat(f.level[ev] + 1, nc)
                    cspan = np.uint64(1) << (
                        np.uint64(d) * (L - 1 - f.level[ev]).astype(np.uint64))
                    ck = (f.keys[ev][:, None] + np.arange(nc, dtype=np.uint64)[None, :]
                          * cspan[:, None]).reshape(-1)
                    flags = nd.astype(np.int32)
                    f2 = adapt(f, lambda tree, elems, fl=flags: fl, recursive=False)
                    forests[i] = f2
                    # new children re-enter the protocol: locate them (one
                    # searchsorted per tree run of the stored (tree, key) order) ...
                    sel = np.empty(len(ct), np.int64)
                    for t in np.unique(ct):
                        m = ct == t
                        a, b = np.searchsorted(f2.tree, [t, t + 1])
                        sel[m] = a + np.searchsorted(f2.keys[a:b], ck[m])
                    new_dests[i] = build_queries(i, np.sort(sel))
                    # ... and are pushed to every rank whose registered query
                    # interval they fall into (and whom they could make refine)
                    reg = registries[i]
                    if reg:
                        exps_by_tree: dict[int, list] = {}
                        for (t, se) in reg:
                            exps_by_tree.setdefault(t, []).append(se)
                        for (t, k, l) in zip(ct.tolist(), ck.tolist(), cl.tolist()):
                            for se in exps_by_tree.get(t, ()):
                                ent = reg[(t, se)].get((k >> se) << se)
                                if ent is not None and l > ent[0] + 1:
                                    for r in ent[1]:
                                        new_notifs[i].setdefault(r, set()).add((t, k, l))
            return new_dests, new_notifs

        # one eval round each, at most max_rounds refining them; round r
        # evaluates, votes on convergence, refines and posts round r + 1's
        # messages
        for rnd in range(max_rounds + 1):
            with telemetry.span("balance.round", round=rnd,
                                elements=sum(f.num_local for f in forests)):
                if rnd == 0:
                    # initial round: the device sweeps + table uploads
                    # dispatch while the marker allgather flies, then
                    # double-duty as both the first query builder and the
                    # first eval layer; the initial halo (every element
                    # registers + queries its remote intervals) is itself
                    # posted nonblocking
                    with telemetry.span("balance.sweep"):
                        handles0 = [sweep_handle(i) for i in range(nloc)]
                        tables0 = upload_tables()
                    with telemetry.span("balance.route"):
                        mt, mk = _markers_from_pairs(K, P, h_mk.wait())
                        pending = post(post_exchange(
                            [route_to_dests(i, bops.eval_route(
                                handles0[i], mt, mk, comm.local_ranks[i]))
                             if handles0[i] is not None else {}
                             for i in range(nloc)], None))
                    needs = eval_round(pending, (handles0, tables0))
                else:
                    needs = eval_round(pending)
                if rnd == max_rounds:
                    break
                # post the convergence vote, then refine + build the next
                # round's messages while it is on the wire (a no-op when the
                # vote comes back all-clear: nothing was dirty anywhere)
                h_conv = post(comm.iallgather([int(nd.any()) for nd in needs]))
                new_dests, new_notifs = refine_and_build(needs)
                if not any(h_conv.wait()):
                    return forests
                with telemetry.span("balance.route"):
                    pending = post(post_exchange(new_dests, new_notifs))
        # budget exhausted: the last eval (which completed the last round's
        # exchange) decides converged-on-last-round vs genuinely dirty
        counts = comm.allgather([int(nd.sum()) for nd in needs])
        if not any(counts):
            return forests
    raise BalanceNonConvergence(max_rounds, counts)


def balance_oracle(forests: list[Forest], comm: Comm,
                   max_rounds: int = 64) -> list[Forest]:
    """The seed's global-leaf-table Balance, retained as the test oracle and
    wire-volume baseline: every round allgathers the full (tree, key, level)
    leaf table of every rank.  The message-based `balance` must match its
    result element for element; the benchmarks record how far its per-round
    O(N) exchange exceeds the boundary-only path's.  Mixed-class meshes run
    once per class group, like `balance`."""
    classes = _forest_classes(forests)
    if len(classes) == 1:
        return _balance_oracle_impl(forests, comm, max_rounds)
    parts: list[list] = [[] for _ in forests]
    for ec in classes:
        res = _balance_oracle_impl(_class_subforests(forests, ec), comm,
                                   max_rounds)
        for i, r in enumerate(res):
            parts[i].append(r)
    return [_merge_class_groups(forests[i], ps)
            for i, ps in enumerate(parts)]


def _balance_oracle_impl(forests: list[Forest], comm: Comm,
                         max_rounds: int) -> list[Forest]:
    if max_rounds < 1:
        raise ValueError("max_rounds must be >= 1")
    d = forests[0].d
    o = get_ops(d)
    forests = list(forests)
    nloc = len(forests)
    with comm.phase("balance_oracle"):
        for rnd in range(max_rounds):
            # Global sorted (tree, key, level) table — the simulator shortcut.
            tables = comm.allgather(
                [(f.tree, f.keys, f.level) for f in forests])
            all_tree = np.concatenate([t[0] for t in tables])
            all_keys = np.concatenate([t[1] for t in tables])
            all_level = np.concatenate([t[2] for t in tables])
            order = np.lexsort((all_keys, all_tree))
            g_tree, g_keys, g_level = all_tree[order], all_keys[order], all_level[order]
            changed = False
            last_dirty = [0] * nloc
            for fi in range(nloc):
                f = forests[fi]
                if f.num_local == 0:
                    continue
                s = f.simplices()
                need = np.zeros(f.num_local, bool)
                span = _elem_spans(d, o.L, f.level)
                sweep = face_sweep_layer(f, f.tree, s)  # one dispatch, all faces
                for face in range(sweep.tgt.shape[0]):
                    tgt, nkey, valid = sweep.tgt[face], sweep.nkey[face], sweep.valid[face]
                    # per-target-tree slices of the global sorted leaf table
                    for t in np.unique(tgt[valid]):
                        sel = np.nonzero(valid & (tgt == t))[0]
                        gsel = slice(*np.searchsorted(g_tree, [t, t + 1]))
                        keys_t, level_t = g_keys[gsel], g_level[gsel]
                        lo_t = np.searchsorted(keys_t, nkey[sel], side="left")
                        hi_t = np.searchsorted(keys_t, nkey[sel] + span[sel], side="left")
                        # any leaf in the neighbor interval finer than level+1?
                        for i, (a, b) in enumerate(zip(lo_t, hi_t)):
                            if level_t[a:b].max(initial=-1) > f.level[sel[i]] + 1:
                                need[sel[i]] = True
                if need.any():
                    changed = True
                    last_dirty[fi] = int(need.sum())
                    flags = need.astype(np.int32)
                    forests[fi] = adapt(
                        f, lambda tree, elems, fl=flags: fl, recursive=False)
            if not any(comm.allgather([int(changed)] * nloc)):
                return forests
        # per-rank counts of the last round's violators — the ripple front
        # that was still moving when the budget ran out
        counts = comm.allgather(last_dirty)
    raise BalanceNonConvergence(max_rounds, counts)


# -------------------------------------------------------------------- ghost
def _empty_ghost(d: int) -> dict:
    return {"anchor": np.zeros((0, d), np.int32), "level": np.zeros(0, np.int32),
            "stype": np.zeros(0, np.int32), "tree": np.zeros(0, np.int32),
            "owner": np.zeros(0, np.int32)}


def _ghost_from_candidates(d: int, cmesh: Cmesh | None, cand: set) -> dict:
    """Sorted-deduped (tree, key, level, owner) candidates -> ghost arrays
    (anchors/types recovered by batch decode, Remark 20) — one decode
    dispatch per element class present among the candidate trees."""
    if not cand:
        return _empty_ghost(d)
    uniq = sorted(cand)
    trees = np.array([c[0] for c in uniq], np.int32)
    keys = np.array([c[1] for c in uniq], np.uint64)
    levels = np.array([c[2] for c in uniq], np.int32)
    owners = np.array([c[3] for c in uniq], np.int32)
    anchors = np.zeros((len(uniq), d), np.int32)
    stypes = np.zeros(len(uniq), np.int32)
    te = (np.zeros(len(uniq), np.int32) if cmesh is None
          else cmesh.tree_eclass[trees])
    for ec in np.unique(te):
        m = te == ec
        gs = get_batch_ops(d, eclass=int(ec)).decode(
            u64m.from_int(keys[m]), jnp.asarray(levels[m]))
        with fetch_span("decode"):
            anchors[m] = np.asarray(gs.anchor)
            stypes[m] = np.asarray(gs.stype)
    return {"anchor": anchors, "level": levels,
            "stype": stypes, "tree": trees, "owner": owners}


def ghost(forests: list[Forest], comm: Comm, overlap: bool = True) -> list[dict]:
    """Face-ghost layer: for each rank, the remote leaves touching its
    elements across faces — following glued tree faces through the Cmesh
    when the forest carries one.  Returns per-local-rank dicts with ghost
    element arrays (in the *owning tree's* frame) and their owner ranks.

    On a mixed-class mesh the exchange runs once per element class (the
    class groups are independent: cross-class faces are domain boundaries)
    and the per-rank candidate sets union before assembly — the ghost dicts
    come out in one (tree, key, level, owner)-sorted block either way.

    Message based: each element's neighbor key interval is routed by the
    allgathered partition markers to its remote owner ranks as a packed
    (tree, key, level, dual-face) query; owners answer from their local
    sorted arrays — the plane filter runs on the *answering* side, which
    reconstructs the neighbor simplex by decoding the queried key (the wire
    stays 14 bytes per query, Remark 20) — and reply with the matching leaf
    triples.  No global leaf table is ever built (`ghost_oracle` keeps the
    old one for the tests).

    The routing pass is *device resident*: one fused face sweep per
    non-empty rank stays on the backend as a `SweepHandle` and the fused
    `BatchedOps.eval_route` programs count the remote-reaching (face,
    element) pairs and, when there are some, compact them with their
    [first, last] owner-rank ranges — the host fetches the count and those
    rows in ONE fetch per rank and packs wire quads from them.

    Both alltoallv stages are double buffered (`overlap=False` completes
    every collective at its post site — the serialized baseline): the
    marker allgather hides behind the device sweeps, and the query flight
    behind the answering-side prep (per-tree offsets into the local sorted
    arrays).  The reply flight has no independent work left to hide —
    assembly needs the payload — so it is waited where it is posted in
    both modes.  Scheduling only: payload bytes and the resulting ghost
    layers are bit-identical across overlap modes."""
    d = forests[0].d
    cm = forests[0].cmesh
    with telemetry.span("forest.ghost"):
        classes = _forest_classes(forests)
        if len(classes) == 1:
            cands = _ghost_impl(forests, comm, overlap, classes[0])
        else:
            cands = [set() for _ in forests]
            for ec in classes:
                res = _ghost_impl(_class_subforests(forests, ec), comm,
                                  overlap, ec)
                for i, c in enumerate(res):
                    cands[i] |= c
        with telemetry.span("ghost.assemble"):
            return [_ghost_from_candidates(d, cm, c) for c in cands]


def _ghost_impl(forests: list[Forest], comm: Comm, overlap: bool,
                eclass: int) -> list[set]:
    """One class group's ghost exchange; returns per-local-rank candidate
    sets of (tree, key, level, owner) — see `ghost` for the algorithm."""
    d = forests[0].d
    o = get_ops(d, eclass)
    L = o.L
    bops = get_batch_ops(d, eclass=eclass)
    # face corner geometry of THIS class: the plane filter needs the dual
    # facet's corners (any d of them span the plane) and how many of a
    # touching leaf's corners must lie on it (d for a simplex, 2^(d-1) for
    # a hex — a whole facet either way)
    fci = np.asarray(o.face_corner_indices)
    cpf = fci.shape[1]
    P = comm.size
    nloc = len(forests)

    def post(h: CommHandle) -> CommHandle:
        # serialized mode: complete every collective where it was posted
        return h if overlap else CommHandle.ready(h.wait())

    with comm.phase("ghost"):
        with telemetry.span("ghost.route"):
            # markers fly while the device routing sweeps dispatch
            K = forests[0].num_trees
            h_mk = post(comm.iallgather(_marker_pairs(forests)))
            handles = [_resident_sweep(f, bops) for f in forests]
            mt, mk = _markers_from_pairs(K, P, h_mk.wait())

            # ---- route queries: the fused eval compacts the remote-reaching
            # (face, element) pairs; the host only packs wire quads from them
            send = []
            for i in range(nloc):
                g = comm.local_ranks[i]
                dest: dict[int, set] = {}
                if handles[i] is not None:
                    rp = bops.eval_route(handles[i], mt, mk, g)
                    for j in range(len(rp.tree)):
                        q = (int(rp.tree[j]), int(rp.key[j]), int(rp.level[j]),
                             int(rp.dual[j]))
                        for r in range(int(rp.first[j]), int(rp.last[j]) + 1):
                            if r != g:
                                dest.setdefault(r, set()).add(q)
                row = []
                for q in range(P):
                    qs = sorted(dest.get(q, ()))
                    row.append(pack_wire(
                        np.array([x[0] for x in qs], np.int32),
                        np.array([x[1] for x in qs], np.uint64),
                        np.array([x[2] for x in qs], np.int32),
                        extra=np.array([x[3] for x in qs], np.int32),
                        eclass=eclass,
                    ) if qs else np.zeros(0, np.uint8))
                send.append(row)
            h_q = post(comm.ialltoallv(send))
        with telemetry.span("ghost.answer"):
            # answering-side prep hides the query flight: per-tree offsets into
            # each rank's sorted leaf arrays replace per-query searchsorted
            tree_offs = [np.searchsorted(f.tree, np.arange(K + 1))
                         for f in forests]
            recv = h_q.wait()

            # ---- answer from the local sorted arrays
            reply_rows = []
            for i, f in enumerate(forests):
                g = comm.local_ranks[i]
                row = [np.zeros(0, np.uint8)] * P
                entries = []  # (src, tree, k0, level, dual)
                for p in range(P):
                    buf = recv[i][p]
                    if p == g or buf is None or not len(buf):
                        continue
                    qt, qk, ql, qd = unpack_wire(buf, with_extra=True)
                    entries.extend(
                        (p, t, k, l, du) for t, k, l, du in
                        zip(qt.tolist(), qk.tolist(), ql.tolist(), qd.tolist()))
                replies: dict[int, set] = {}
                if entries and f.num_local:
                    offs = tree_offs[i]
                    pend = []       # (entry idx, local leaf idx) same-or-finer
                    pred_hits = []  # (entry idx, local leaf idx) coarser containing
                    for ei, (p, t, k0, l, du) in enumerate(entries):
                        t0 = int(offs[t])
                        keys_t = f.keys[t0:int(offs[t + 1])]
                        span_q = np.uint64(1) << np.uint64(d * (L - l))
                        a = int(np.searchsorted(keys_t, np.uint64(k0)))
                        b = int(np.searchsorted(keys_t, np.uint64(k0) + span_q))
                        if b > a:
                            pend.extend((ei, t0 + j) for j in range(a, b))
                        elif a > 0:
                            # coarser containing leaf: dyadic nesting makes the
                            # interval globally empty, and the leaf lives on the
                            # owner rank of k0 — answer only there (owner via one
                            # numpy compare-sum on the marker table, no dispatch)
                            own = max(int(((mt < t) | ((mt == t) & (
                                mk <= np.uint64(k0)))).sum()) - 1, 0)
                            jj = t0 + a - 1
                            span_p = np.uint64(1) << np.uint64(d * (L - int(f.level[jj])))
                            if own == g and np.uint64(f.keys[jj]) + span_p > np.uint64(k0):
                                pred_hits.append((ei, jj))
                    if pend:
                        # same-or-finer leaves must TOUCH the shared face: a
                        # whole facet's worth of their corners on the plane of
                        # the neighbor element's dual facet (the neighbor is
                        # decoded from the query key)
                        eis = sorted({ei for ei, _ in pend})
                        emap = {ei: k for k, ei in enumerate(eis)}
                        ent_k = np.array([entries[ei][2] for ei in eis], np.uint64)
                        ent_l = np.array([entries[ei][3] for ei in eis], np.int32)
                        nbs = bops.decode(u64m.from_int(ent_k), jnp.asarray(ent_l))
                        with fetch_span("ghost_coords"):
                            nbc = np.asarray(o.coordinates(nbs), np.int64)
                        js = sorted({j for _, j in pend})
                        jmap = {j: k for k, j in enumerate(js)}
                        jarr = np.asarray(js, np.int64)
                        cs = Simplex(jnp.asarray(f.anchor[jarr]),
                                     jnp.asarray(f.level[jarr]),
                                     jnp.asarray(f.stype[jarr]))
                        with fetch_span("ghost_coords"):
                            ccoords = np.asarray(o.coordinates(cs), np.int64)
                        planes: dict[int, tuple] = {}
                        for ei, j in pend:
                            if ei not in planes:
                                planes[ei] = face_plane(
                                    nbc[emap[ei]][fci[int(entries[ei][4])][:d]])
                            nrm, rhs = planes[ei]
                            if (ccoords[jmap[j]] @ nrm == rhs).sum() == cpf:
                                replies.setdefault(entries[ei][0], set()).add(
                                    (int(f.tree[j]), int(f.keys[j]), int(f.level[j])))
                    for ei, j in pred_hits:
                        replies.setdefault(entries[ei][0], set()).add(
                            (int(f.tree[j]), int(f.keys[j]), int(f.level[j])))
                for p, rs in replies.items():
                    row[p] = _pack_triples(rs, eclass)
                reply_rows.append(row)
            h_r = post(comm.ialltoallv(reply_rows))
        with telemetry.span("ghost.assemble"):
            rrecv = h_r.wait()

            # ---- collect candidates: replies from rank p are leaves owned by p
            out = []
            for i, f in enumerate(forests):
                g = comm.local_ranks[i]
                cand: set = set()
                for p in range(P):
                    buf = rrecv[i][p]
                    if p == g or buf is None or not len(buf):
                        continue
                    t_, k_, l_ = unpack_wire(buf)
                    cand.update((t, k, l, p) for t, k, l in
                                zip(t_.tolist(), k_.tolist(), l_.tolist()))
                out.append(cand)
        return out


def ghost_oracle(forests: list[Forest], comm: Comm) -> list[dict]:
    """The seed's global-leaf-table Ghost, retained as the test oracle and
    wire-volume baseline: allgathers every rank's full (tree, key, level)
    arrays and searches them directly.  The message-based `ghost` must
    produce identical ghost layers.  Mixed-class meshes run once per class
    group, like `ghost`."""
    d = forests[0].d
    cm = forests[0].cmesh
    classes = _forest_classes(forests)
    if len(classes) == 1:
        cands = _ghost_oracle_impl(forests, comm, classes[0])
    else:
        cands = [set() for _ in forests]
        for ec in classes:
            res = _ghost_oracle_impl(_class_subforests(forests, ec), comm, ec)
            for i, c in enumerate(res):
                cands[i] |= c
    return [_ghost_from_candidates(d, cm, c) for c in cands]


def _ghost_oracle_impl(forests: list[Forest], comm: Comm,
                       eclass: int) -> list[set]:
    d = forests[0].d
    o = get_ops(d, eclass)
    bops = get_batch_ops(d, eclass=eclass)
    fci = np.asarray(o.face_corner_indices)
    cpf = fci.shape[1]
    nloc = len(forests)
    with comm.phase("ghost_oracle"):
        tables = comm.allgather([(f.tree, f.keys, f.level) for f in forests])
    all_tree = np.concatenate([t[0] for t in tables])
    all_keys = np.concatenate([t[1] for t in tables])
    all_level = np.concatenate([t[2] for t in tables])
    all_owner = np.concatenate(
        [np.full(len(t[0]), p) for p, t in enumerate(tables)])
    order = np.lexsort((all_keys, all_tree))
    g_tree, g_keys, g_level, g_owner = (
        all_tree[order], all_keys[order], all_level[order], all_owner[order],
    )

    out = []
    for i in range(nloc):
        f = forests[i]
        p_me = comm.local_ranks[i]
        if f.num_local == 0:
            out.append(set())
            continue
        s = f.simplices()
        cand = []
        sweep = face_sweep_layer(f, f.tree, s)  # one dispatch, all faces
        for face in range(sweep.tgt.shape[0]):
            tgt, nkey, valid, nb, dual, _ = sweep.face(face)
            nbc = None  # (n, corners, d), computed only when candidates exist
            for t in np.unique(tgt[valid]):
                sel = np.nonzero(valid & (tgt == t))[0]
                gsel = slice(*np.searchsorted(g_tree, [t, t + 1]))
                keys_t, level_t, owner_t = g_keys[gsel], g_level[gsel], g_owner[gsel]
                span = _elem_spans(d, o.L, f.level[sel])
                lo = np.searchsorted(keys_t, nkey[sel], side="left")
                hi = np.searchsorted(keys_t, nkey[sel] + span, side="left")
                # same-or-finer leaves inside the neighbor region that TOUCH
                # the shared face: a descendant of the neighbor shares our
                # face iff a whole facet's worth of its corners (d for a
                # simplex, 2^(d-1) for a hex) lie on the shared face's plane
                # (inside the region, plane membership implies face overlap).
                # Collect candidates first, then decode their coordinates in
                # one batch — only boundary-interval leaves pay for geometry.
                pend = []
                for i2, (a, b) in enumerate(zip(lo, hi)):
                    for j in range(a, b):
                        if owner_t[j] != p_me:
                            pend.append((i2, j))
                if pend:
                    if nbc is None:
                        nbc = np.asarray(o.coordinates(nb), np.int64)
                    js = sorted({j for _, j in pend})
                    jmap = {j: k for k, j in enumerate(js)}
                    cs = bops.decode(
                        u64m.from_int(keys_t[js]), jnp.asarray(level_t[js])
                    )
                    ccoords = np.asarray(o.coordinates(cs), np.int64)
                    planes = {}
                    for i2, j in pend:
                        if i2 not in planes:
                            planes[i2] = face_plane(
                                nbc[sel[i2]][fci[int(dual[sel[i2]])][:d]]
                            )
                        nrm, rhs = planes[i2]
                        if (ccoords[jmap[j]] @ nrm == rhs).sum() == cpf:
                            cand.append((t, keys_t[j], level_t[j], owner_t[j]))
                # coarser leaf containing the neighbor: predecessor check
                pred = np.maximum(lo - 1, 0)
                for i2, pj in enumerate(pred):
                    if len(keys_t) == 0:
                        continue
                    span_pred = np.uint64(1) << (
                        np.uint64(d) * (np.uint64(o.L) - np.uint64(level_t[pj]))
                    )
                    if (keys_t[pj] <= nkey[sel][i2] < keys_t[pj] + span_pred
                            and owner_t[pj] != p_me and lo[i2] == hi[i2]):
                        cand.append((t, keys_t[pj], level_t[pj], owner_t[pj]))
        out.append({(int(t), int(k), int(l), int(w)) for t, k, l, w in cand})
    return out


# ------------------------------------------------------------------ iterate
def iterate(f: Forest, elem_fn=None, face_fn=None):
    """Paper's Iterate: run callbacks over local elements and interior local
    face pairs, including pairs straddling glued tree faces when the forest
    carries a Cmesh.

    Each pair row is (i, j, face_i, face_j).  Same-level pairs are delivered
    once (i < j in storage order); hanging faces are delivered once per fine
    sub-face as a (fine i, coarse j) pair, discovered from the fine side —
    the coarser leaf is found by walking the neighbor's ancestor keys (pure
    prefix masking), and face_j is the coarse facet containing the shared
    face.

    On a mixed-class mesh the pair discovery runs per element class (one
    fused sweep per class; cross-class faces are domain boundaries, so no
    pair straddles classes) and `face_fn` is called ONCE with all pairs,
    whose indices are in the forest's local element indexing throughout."""
    results = []
    if elem_fn is not None:
        results.append(elem_fn(f.tree, f.simplices()))
    if face_fn is not None:
        groups = _class_groups(f)
        if len(groups) == 1:
            pairs = _iterate_pairs(f, None, groups[0][0])
        else:
            pairs = []
            for ec, idx in groups:
                pairs.extend(_iterate_pairs(f, idx, ec))
        results.append(face_fn(f, np.array(pairs, np.int64).reshape(-1, 4)))
    return results


def _iterate_pairs(f: Forest, idx: np.ndarray | None, eclass: int) -> list:
    """Local face pairs of one class group (`idx` — None means all local
    elements), reported in the forest's local indexing."""
    o = get_ops(f.d, eclass)
    d, L = f.d, o.L
    fci = np.asarray(o.face_corner_indices)
    if idx is None:
        s = f.simplices()
        tree_ids = f.tree
        gid = np.arange(f.num_local, dtype=np.int64)
    else:
        s = Simplex(jnp.asarray(f.anchor[idx]), jnp.asarray(f.level[idx]),
                    jnp.asarray(f.stype[idx]))
        tree_ids = f.tree[idx]
        gid = np.asarray(idx, np.int64)
    # neighbors never leave the class (classes are unions of whole trees),
    # so the subset's own (tree, key, level) index resolves every lookup
    key_index = {}
    pos = {}  # local index -> subset row, for coordinate lookups
    for k, g in enumerate(gid.tolist()):
        key_index[(int(f.tree[g]), int(f.keys[g]), int(f.level[g]))] = g
        pos[g] = k
    own_coords = None  # lazy: only adapted meshes have hanging faces
    pairs = []
    sweep = face_sweep_layer(f, tree_ids, s)  # one dispatch per class
    for face in range(sweep.tgt.shape[0]):
        tgt, nkey, valid, nb, dual, _ = sweep.face(face)
        nlvl = np.asarray(nb.level)
        nbc = None
        for i in np.nonzero(valid)[0]:
            gi = int(gid[i])
            j = key_index.get((int(tgt[i]), int(nkey[i]), int(nlvl[i])))
            if j is not None:
                # same-level pairs are discovered from both sides: keep
                # one (self-pairs across periodic gluings keep face<dual)
                if gi < j or (gi == j and face < int(dual[i])):
                    pairs.append((gi, j, face, int(dual[i])))
                continue
            # hanging face: the neighbor region may be covered by one
            # COARSER leaf — its key is an ancestor prefix of nkey
            for lc in range(int(nlvl[i]) - 1, -1, -1):
                mkey = int(nkey[i]) & ~((1 << (d * (L - lc))) - 1)
                j = key_index.get((int(tgt[i]), mkey, lc))
                if j is None:
                    continue
                if nbc is None:
                    nbc = np.asarray(o.coordinates(nb), np.int64)
                if own_coords is None:
                    own_coords = np.asarray(o.coordinates(s), np.int64)
                shared = nbc[i][fci[int(dual[i])]]
                jc = own_coords[pos[j]]
                # the coarse facet whose plane contains the shared face
                for fc in range(o.nf):
                    nrm, rhs = face_plane(jc[fci[fc][:d]])
                    if (shared @ nrm == rhs).all():
                        pairs.append((gi, j, face, fc))
                        break
                else:
                    raise AssertionError("hanging face without coarse facet")
                break
    return pairs


# ----------------------------------------------------------------- validate
def validate(forests: list[Forest], ghosts: list[dict] | None = None) -> bool:
    """Forest invariants: *globally* ascending (tree, TM-index) leaf order in
    stored rank-major order (not merely sortable), leaves pairwise
    non-overlapping (no ancestor relations), all inside their root, complete
    volume coverage per tree — and, when `ghosts` is given, ghost-layer
    consistency: every ghost entry is an actual remote leaf on its claimed
    owner rank (including entries reached across glued tree faces)."""
    d = forests[0].d
    o = get_ops(d)
    all_tree = np.concatenate([f.tree for f in forests])
    all_keys = np.concatenate([f.keys for f in forests])
    all_level = np.concatenate([f.level for f in forests])
    # global (tree, key) order must hold as stored across ranks — the SFC
    # partition invariant the markers rely on
    t, k, l = all_tree, all_keys, all_level
    if len(t) > 1:
        same = t[1:] == t[:-1]
        if not np.all((t[1:] > t[:-1]) | same):
            return False
        if not np.all(k[1:][same] > k[:-1][same]):
            return False
        # non-overlap: successor key must be >= current key + span
        span = np.uint64(1) << (np.uint64(d) * (np.uint64(o.L) - l.astype(np.uint64)))
        if not np.all(k[1:][same] >= (k[:-1] + span[:-1])[same]):
            return False
    # inside root (per element class: the containment test is class-keyed)
    for f in forests:
        for ec, idx in _class_groups(f):
            if len(idx) == 0:
                continue
            sub = Simplex(jnp.asarray(f.anchor[idx]), jnp.asarray(f.level[idx]),
                          jnp.asarray(f.stype[idx]))
            if not np.asarray(
                    get_batch_ops(d, eclass=ec).is_inside_root(sub)).all():
                return False
    # coverage: sum of 2^{-d*level} == num_trees
    vol = (1.0 / (1 << d) ** all_level.astype(np.float64)).sum()
    K = forests[0].num_trees
    if not abs(vol - K) < 1e-9 * max(K, 1):
        return False
    # ghost consistency across ranks (and tree faces)
    if ghosts is not None and any(len(g["level"]) for g in ghosts):
        owner_of = {}
        for p, f in enumerate(forests):
            for i in range(f.num_local):
                owner_of[(int(f.tree[i]), int(f.keys[i]), int(f.level[i]))] = p
        cm = forests[0].cmesh
        for p, g in enumerate(ghosts):
            if len(g["level"]) == 0:
                continue
            te = (np.zeros(len(g["level"]), np.int32) if cm is None
                  else cm.tree_eclass[g["tree"]])
            gkeys = np.zeros(len(g["level"]), np.uint64)
            for ec in np.unique(te):
                m = te == ec
                gs = Simplex(jnp.asarray(g["anchor"][m]),
                             jnp.asarray(g["level"][m]),
                             jnp.asarray(g["stype"][m]))
                gkeys[m] = get_batch_ops(d, eclass=int(ec)).morton_key_np(gs)
            for j in range(len(gkeys)):
                q = int(g["owner"][j])
                if q == p:
                    return False
                if owner_of.get((int(g["tree"][j]), int(gkeys[j]), int(g["level"][j]))) != q:
                    return False
    return True


def count_global(forests: list[Forest], comm: Comm | None = None) -> int:
    """Total element count.  Without `comm` this sums the given (local)
    forests — the full global count under `SimComm` hosting, where every
    rank is local.  With `comm`, the local sums are allgathered, so the call
    is correct under distributed hosting too."""
    if comm is None:
        return int(sum(f.num_local for f in forests))
    return int(sum(comm.allgather([int(f.num_local) for f in forests])))
