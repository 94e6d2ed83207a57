"""bench/tests run by hand, on the CPU:

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests

(the repo's own ``testpaths`` is ``tests/``, so tier 1 does not collect
them).  Puts the checkout root and ``src/`` on the path."""
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)
