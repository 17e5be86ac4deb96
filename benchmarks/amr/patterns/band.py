"""`band`: a moving interface, after t8code's
`benchmarks/time_forest_partition.cxx`.

The leaves whose level-`base_level` cell has its centre within
`half_width` (a share of the root's extent) of a plane refine `depth`
levels; all others coarsen back to the base level.  The plane is normal
to `axis`, moves `step` of the extent per cycle and wraps.  The trees
run at phases spread evenly over a period, and the seed deals the phases
out to the trees, so every seed refines the same number of cells in
every cycle, at other places.
"""

from __future__ import annotations

import numpy as np

import reference as ref

L = ref.L


class Pattern:
    def __init__(self, gen):
        m = gen.mix
        top = 1 << L
        self.base_level = gen.base_level
        self.positions = int(round(1 / float(m["step"])))
        if top % self.positions:
            raise ValueError("step must divide the root extent")
        self.step = top // self.positions
        self.half_width = int(round(float(m["half_width"]) * top))
        self.depth = int(m["depth"])
        self.axis = int(m["axis"])
        self.max_level = self.base_level + self.depth
        # tree t runs offset[t] positions ahead
        n = gen.mesh.num_trees
        rng = np.random.default_rng(gen.seed)
        self.offset = (rng.permutation(n) * self.positions) // n

    def inside(self, k: int, tree, anchor) -> np.ndarray:
        top = np.int64(1) << L
        H = np.int64(1) << (L - self.base_level)
        centre = anchor[:, self.axis] - anchor[:, self.axis] % H + H // 2
        plane = ((k + self.offset[tree]) % self.positions) * self.step
        dist = (centre - plane) % top
        dist = np.minimum(dist, top - dist)
        return dist <= self.half_width

    def flags(self, k, tree, level, anchor, stype) -> np.ndarray:
        inside = self.inside(k, np.asarray(tree), anchor)
        up = inside & (level < self.max_level)
        down = ~inside & (level > self.base_level)
        return up.astype(np.int32) - down.astype(np.int32)

    def expected_adapt(self, mesh, k, base):
        # the band's mesh has no memory: the cells in the band are refined
        # `depth` levels, the rest are at the base level
        return ref.refine_while(mesh, base, lambda x: (
            self.inside(k, x.tree, x.anchor) & (x.level < self.max_level)))
