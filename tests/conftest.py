"""Test harness: force an 8-device virtual CPU platform before JAX loads.

Multi-chip sharding paths (parallel/) are validated on a virtual CPU mesh per
the build contract; the real TPU chip is exercised by chip_smoke.py, not the
suite.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"  # the suite never asks for an accelerator
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402  (import after env setup)

jax.config.update("jax_threefry_partitionable", True)
# The suite is hermetic: no persistent compile cache, wherever an entry point
# under test points it (utils/compile_cache.py).  An XLA:CPU executable
# reloaded from the cache is not bit-identical to a fresh compile, which the
# bitwise default-off tests would see as a numerics change.
jax.config.update("jax_enable_compilation_cache", False)
