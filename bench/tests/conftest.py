"""The benchmark's own tests: run with

    PYTHONPATH=src python -m pytest bench/tests

Four host devices stand in for the four chips of the mesh cell.  Must
run before the first jax import in the process.
"""

import os
import sys
from pathlib import Path

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")
os.environ.setdefault("JAX_PLATFORMS", "cpu")

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (str(BENCH), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
