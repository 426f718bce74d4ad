"""Test harness configuration.

Tests run on the CPU, on a virtual 8-device mesh (SURVEY §4.5): the
suite is started with ``JAX_PLATFORMS=cpu`` (and pins it here for a bare
``pytest`` invocation), and requests 8 virtual CPU devices for the
mesh-sharding tests before jax is imported.  Execution on the chip is
``chip_smoke.py``'s job, never the unit suite's; the one test file that
loads the TPU compiler (``test_chip_compile.py``) does so inside a
fixture, for a described topology.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

from raft_tla_tpu.utils.platform import (enable_persistent_cache,  # noqa: E402
                                         force_cpu)

force_cpu()

# Persistent compilation cache: the expand/step programs take tens of
# seconds to compile on the CPU; caching makes re-runs cheap.  Placed by
# the same rule as every entry point (utils/platform.py).
enable_persistent_cache()
