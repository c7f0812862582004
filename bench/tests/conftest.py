"""The benchmark's own tests (not part of the repository's tier-1 suite):

    JAX_PLATFORMS=cpu python -m pytest bench/tests -q

They import ``bench/run.py`` and its modules from ``bench/`` and the
program from ``src/``."""

import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
for p in (str(BENCH.parent / "src"), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)
