"""Test harness config.

JAX-based tests run on the CPU backend with a virtual 8-device topology so
multi-chip sharding logic is exercised without TPU hardware (SURVEY.md §4
multi-node story).  Both are plain environment settings, made here before
anything imports jax.  The chip is reached only through ``chip_smoke.py``
(run by the chip tool), never from the test suite.
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# persistent XLA compilation cache: the crypto kernels are compile-heavy and
# shape-stable, so warm runs of the device test tier drop from minutes to
# seconds.  Safe to share across processes; keyed by HLO + compile options.
from cpzk_tpu import jaxrt  # noqa: E402

jaxrt.enable_compile_cache()
