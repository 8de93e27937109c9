import os
import sys
from pathlib import Path

# The benchmark's own checks run on the CPU; the chip runs are bench/run.py.
os.environ.setdefault("JAX_PLATFORMS", "cpu")

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))
