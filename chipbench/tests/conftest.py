"""Tests of the benchmark itself, on the CPU:

    JAX_PLATFORMS=cpu python -m pytest -q chipbench/tests

The checkout root and ``src/`` go on ``sys.path`` so that ``chipbench`` and
the program import as they do in a run."""
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
