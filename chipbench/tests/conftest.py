"""The benchmark's own checks run on the CPU: `python -m pytest chipbench/tests`."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# four virtual devices, for the cell that is added across chips
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")
HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (ROOT, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)
