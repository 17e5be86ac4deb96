"""The one traffic generator: turns a mix's data file and a configuration
into the adapt callback of each cycle, and into the mesh the reference
expects after each cycle's Adapt.

A mix (`traffic/<mix>.json`) is data: the `pattern` it follows, that
pattern's parameters, whether the mesh carries over from cycle to cycle
(`carry`) and how many warm-up cycles set-up runs (`warmup_cycles`).
The pattern is a module `patterns/<pattern>.py` found by name; its
`Pattern(gen)` gives `flags(k, tree, level, anchor, stype)` for the
program's callback and `expected_adapt(mesh, k, base)` for the
reference.  The configuration's coarse mesh is a module
`meshes/<coarse_mesh>.py`, also found by name.

The seed changes only what keeps the element count and the work: see
each pattern's module.  Flags follow the program's Adapt contract: > 0
refine, 0 keep, < 0 coarsen.
"""

from __future__ import annotations

import numpy as np

import reference as ref
import registry


class Traffic:
    """The cycles of one cell: `flags(k, tree, level, anchor, stype)` for
    the program's callback, `expected_adapt(k)` for the reference."""

    def __init__(self, config: dict, mix: dict, seed: int):
        self.config, self.mix, self.seed = config, mix, int(seed)
        self.carry = bool(mix["carry"])
        self.warmup_cycles = int(mix["warmup_cycles"])
        self.coarse = registry.load("meshes", config["coarse_mesh"])
        self.mesh = self.coarse.reference(config)
        self.base_level = int(config["base_level"])
        self.pattern = registry.load("patterns", mix["pattern"]).Pattern(self)

    def flags(self, k: int, tree, level, anchor, stype) -> np.ndarray:
        """Adapt flags of cycle k for leaves given as NumPy arrays."""
        return self.pattern.flags(k, np.asarray(tree), np.asarray(level, np.int64),
                                  np.asarray(anchor, np.int64),
                                  np.asarray(stype, np.int64))

    def callback(self, k: int):
        """The program's Adapt callback for cycle k."""
        def cb(tree, elems):
            return self.flags(k, tree, elems.level, elems.anchor, elems.stype)
        return cb

    def base(self) -> ref.Leaves:
        return self.mesh.uniform(self.base_level)

    def expected_adapt(self, k: int, base: ref.Leaves | None = None) -> ref.Leaves:
        """The leaves the reference expects after cycle k's Adapt."""
        base = self.base() if base is None else base
        return self.pattern.expected_adapt(self.mesh, k, base)
