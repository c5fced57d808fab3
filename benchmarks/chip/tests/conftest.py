"""The benchmark's own CPU tests: ``python -m pytest -q benchmarks/chip/tests``
from the root of the checkout. They never touch a chip."""
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parent), str(HERE.parents[2] / "src")]
