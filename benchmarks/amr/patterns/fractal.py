"""`fractal`: the configuration's own fractal refinement (its `fractal`
key), applied to the set-up base in every cycle.

A simplex pattern names the Bey `types` that refine (Burstedde & Holke,
Fig. 12); a cube pattern names the `child_ids` that refine from
`refine_below` on, below which every element refines (p4est
`timings.c`).  Both stop at `max_level`.  The configuration's
`seed_symmetry` says how the seed changes the pattern without changing
its count or work.
"""

from __future__ import annotations

import numpy as np

import reference as ref


class Pattern:
    def __init__(self, gen):
        spec = gen.config["fractal"]
        self.max_level = int(spec["max_level"])
        self.refine_below = int(spec.get("refine_below", 0))
        sym = gen.config.get("seed_symmetry", "none")
        if "types" in spec:
            self.match_types = np.asarray(spec["types"], np.int64)
            self.match_ids = None
            if sym != "none":
                raise ValueError(f"no seed symmetry {sym!r} for a type pattern")
        else:
            ids = np.asarray(spec["child_ids"], np.int64)
            if sym == "child_id_xor":  # a mirror image of the pattern
                ids = ids ^ (gen.seed % 8)
            elif sym != "none":
                raise ValueError(f"unknown seed symmetry {sym!r}")
            self.match_ids, self.match_types = np.sort(ids), None

    def refine(self, level, anchor, stype) -> np.ndarray:
        if self.match_types is not None:
            hit = np.isin(stype, self.match_types)
        else:
            hit = np.isin(ref.cube_id(anchor, level), self.match_ids)
        hit = hit | (level < self.refine_below)
        return hit & (level < self.max_level)

    def flags(self, k, tree, level, anchor, stype) -> np.ndarray:
        return self.refine(level, anchor, stype).astype(np.int32)

    def expected_adapt(self, mesh, k, base):
        return ref.refine_while(mesh, base, lambda x: self.refine(
            x.level, x.anchor, x.stype))


def analytic_count(config: dict) -> int:
    """Leaves after the fractal Adapt, by a transfer matrix over element
    types (simplices) or a recurrence (cubes): a check of the generator
    that builds no mesh."""
    spec = config["fractal"]
    trees, base = int(config["trees"]), int(config["base_level"])
    lo, hi = max(int(spec.get("refine_below", 0)), base), int(spec["max_level"])
    if "types" in spec:
        mesh = ref.Mesh(ref.SIMPLEX, 1)
        kids = {}
        for b in range(6):
            one = ref.Leaves.of([0], [0], [[0, 0, 0]], [b])
            kids[b] = np.bincount(mesh.children(one).stype, minlength=6)
        c = np.zeros(6, dtype=object)
        c[0] = trees
        for _ in range(lo):
            c = sum((c[b] * kids[b].astype(object) for b in range(6)),
                    np.zeros(6, dtype=object))
        refine = set(int(t) for t in spec["types"])
        f = {}  # leaves under one element of type b at level lvl
        def count(b, lvl):
            if lvl >= hi or b not in refine:
                return 1
            if (b, lvl) not in f:
                f[b, lvl] = sum(int(kids[b][t]) * count(t, lvl + 1) for t in range(6))
            return f[b, lvl]
        return int(sum(c[b] * count(b, lo) for b in range(6)))
    s = len(set(int(i) for i in spec["child_ids"]))
    per = 1
    for _ in range(hi - lo):
        per = (8 - s) + s * per
    # below `lo` everything refines; at `lo`, half the cubes match
    n_lo = trees * 8 ** lo
    return (n_lo * s // 8) * per + (n_lo - n_lo * s // 8) if hi > lo else n_lo
