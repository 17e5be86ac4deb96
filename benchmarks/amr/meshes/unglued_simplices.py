"""`trees` unglued type-0 Kuhn simplices: every root face is domain
boundary, so no face neighbour lies in another tree."""

import reference as ref


def program(config):
    """The program's coarse mesh: None, `new_uniform`'s unglued trees."""
    return None


def reference(config) -> ref.Mesh:
    return ref.Mesh(ref.SIMPLEX, int(config["trees"]))
