"""The benchmark's own tests: the harness modules import by bare name."""

import sys
from pathlib import Path

_AMR = Path(__file__).resolve().parents[1]
for _p in (_AMR, _AMR.parents[1] / "src"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))
