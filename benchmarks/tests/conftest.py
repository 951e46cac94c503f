"""CPU tests of the benchmark's own code. Run by hand:

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q

They are outside tier-1's `tests/` and change no count there.
"""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
CHECKOUT = os.path.dirname(BENCH)
for p in (CHECKOUT, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
# four virtual devices, for the cell that runs across chips
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: ResNet-50 at 224 px on the CPU, minutes a test")
