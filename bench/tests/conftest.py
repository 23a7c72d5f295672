"""The benchmark's own tests, on the CPU at small sizes:

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
for p in (str(REPO / "src"), str(REPO)):
    if p not in sys.path:
        sys.path.insert(0, p)
