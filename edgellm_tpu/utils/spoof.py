"""Spoof an n-device CPU platform for sharding code on any host.

One definition of the recipe the multi-chip dry-run, the ring-mode
measurement tool, and the test suite all rely on: force
``--xla_force_host_platform_device_count`` (replacing any prior value) and
redirect jax to CPU. Safe to call even when jax was already imported:
backends are lazy, so the redirect works as long as no backend has
initialized yet.
"""
from __future__ import annotations

import os
import re


def spoof_cpu_devices(n_devices: int) -> None:
    flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                   os.environ.get("XLA_FLAGS", ""))
    os.environ["XLA_FLAGS"] = (
        flags + f" --xla_force_host_platform_device_count={n_devices}").strip()
    os.environ["JAX_PLATFORMS"] = "cpu"

    import jax

    jax.config.update("jax_platforms", "cpu")
