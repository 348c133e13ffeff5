"""bench/tests: the benchmark's own checks, run by the builder
(``JAX_PLATFORMS=cpu python -m pytest bench/tests -q``), not by tier-1."""
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(BENCH))
