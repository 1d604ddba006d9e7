"""Test configuration: force an 8-device virtual CPU platform before JAX initializes.

Multi-chip sharding paths (pipeline splits over a stage mesh, ppermute boundary
transfers) are exercised on a spoofed 8-device CPU mesh, per the reference test
strategy gap analysis (SURVEY.md section 4): the reference has no tests at all; we
test every layer of the stack on CPU so TPU runs are config changes, not code changes.
"""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import jax

jax.config.update("jax_platforms", "cpu")

# persistent compilation cache, placed by the same rule as every program
# entry point (JAX_COMPILATION_CACHE_DIR, else <checkout>/.jax_cache). EVERY
# executable is kept (threshold 0): the suite's wall time is thousands of
# sub-second compiles, and at JAX's default 1 s threshold (or the 0.5 s this
# file used before) a rerun recompiled nearly all of them — tests/test_decode
# alone went 41 s -> 22 s warm once they were kept
from edgellm_tpu.utils.startup import configure_compile_cache

configure_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

import numpy as np
import pytest


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)
