"""Tests of the benchmark on the CPU: ``python3 -m pytest bench/tests``."""
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# four CPU devices, so that the four-chip path (a (4,1) mesh, the mix as a
# collective) runs here too; one-device cells use the first
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")
BENCH = Path(__file__).resolve().parents[1]
for p in (BENCH, BENCH / "tests", BENCH.parent / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
