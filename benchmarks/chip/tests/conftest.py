"""Run from the checkout's root: ``python -m pytest benchmarks/chip/tests``.
The tests rehearse the harness on the CPU at tiny sizes; none needs a chip."""
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")

BENCH = Path(__file__).resolve().parents[1]
for p in (BENCH.parents[1] / "src", BENCH, BENCH / "drivers", BENCH / "configs",
          BENCH / "tests"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
