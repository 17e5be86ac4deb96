"""Finds a piece of the benchmark by its name: `<kind>/<name>.py` under
the benchmark's directory, loaded as a module.

The kinds are `metrics` (a per-layer or end-to-end metric's reader),
`patterns` (a traffic pattern that a mix names) and `meshes` (a coarse
mesh that a configuration names).  A later cell adds a file of its own
and never edits one that is there.
"""

from __future__ import annotations

import importlib.util
from pathlib import Path

HERE = Path(__file__).resolve().parent


class BenchError(RuntimeError):
    """The cell cannot run here (no chip, unknown name, bad file)."""


def load(kind: str, name: str):
    """The module `<kind>/<name>.py` of the benchmark's directory."""
    path = HERE / kind / f"{name}.py"
    if not path.is_file():
        raise BenchError(f"no {kind} module {kind}/{name}.py for {name!r}")
    spec = importlib.util.spec_from_file_location(f"amr_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
