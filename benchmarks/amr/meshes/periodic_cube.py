"""One unit-cube hex tree whose opposite faces are glued along the axes
that `periodic` names (p4est's `periodic` connectivity when all three
are)."""

import reference as ref
from registry import BenchError


def program(config):
    from repro.core.cmesh import cmesh_hex_brick

    if int(config["trees"]) != 1:
        raise BenchError("the periodic cube is one tree")
    return cmesh_hex_brick(3, (1, 1, 1), periodic=tuple(config["periodic"]))


def reference(config) -> ref.Mesh:
    return ref.Mesh(ref.HEX, 1, tuple(config["periodic"]))
