"""The benchmark's own tests run on the CPU, by path:

    python -m pytest bench/tests

Four host devices stand in for the four-chip mesh of the sharded cell."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
if "jax" not in sys.modules:
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=4").strip()

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
