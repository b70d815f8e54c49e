"""Test harness: force an 8-device virtual CPU mesh.

The reference exercises multi-GPU logic without a cluster via Legion's
proc abstraction; our analogue (SURVEY.md §4) is jax's host-platform
device multiplexing.  Must run before jax initializes its backend.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

# The suite stays OFF jax's persistent compilation cache.  The apps
# point it at <checkout>/.ffcache when their main() runs
# (apps/common.enable_compile_cache), and many tests call those mains
# in-process; with the cache disabled here that path setting is inert.
# Why off: under jax 0.4.37 the XLA:CPU executable deserializer
# SEGFAULTED deterministically in the orbax-heavy checkpoint tests with
# min_compile_time 0, and a safe 1.0 s threshold saved ~10% warm
# (measured 2026-08-04, ISSUE 3; not re-measured on 0.9.0, where every
# XLA:CPU cache hit also logs a machine-feature mismatch error).
jax.config.update("jax_enable_compilation_cache", False)

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture
def rng():
    return np.random.default_rng(0)
