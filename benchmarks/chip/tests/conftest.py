"""CPU tests of the on-chip benchmark: the harness's own files import by
their plain names, the solver from the checkout's ``src``."""
import os
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
BENCH = HERE.parent
ROOT = BENCH.parents[1]

os.environ.setdefault("JAX_PLATFORMS", "cpu")
for p in (str(ROOT / "src"), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)
