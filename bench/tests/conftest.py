import os
import sys
from pathlib import Path

# The benchmark's tests run on the CPU; the harness finds the program under
# <checkout>/src and itself under <checkout>/bench.
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, str(Path(__file__).resolve().parents[2]))
