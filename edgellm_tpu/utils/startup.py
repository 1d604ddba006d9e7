"""Start-up helpers every entry point shares: where the persistent XLA
compilation cache lives, and the stamp that names the device a result ran on.

Compile cache, one rule for ``run.main``, ``bench.py``, ``chip_smoke.py`` and
the test suite: when ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself
and nothing is set in code; otherwise the cache is ``<checkout>/.jax_cache``
(gitignored). The directory is part of the cache key, so it is a fixed path —
never a temp name, pid or timestamp — and a second process started from the
same checkout finds what the first compiled.
"""
from __future__ import annotations

import os

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"

#: the checkout root: the directory holding the ``edgellm_tpu`` package
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def configure_compile_cache() -> str:
    """Place the compile cache and return the directory in use. Call before
    the first compilation; idempotent."""
    placed = os.environ.get(CACHE_ENV)
    if placed:
        return placed
    import jax

    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def device_stamp() -> dict:
    """The device a result ran on, as JAX reports it. Every printed result
    carries this, so a CPU run can never be read as a chip run."""
    import jax

    devices = jax.devices()
    return {"platform": devices[0].platform,
            "device_kind": devices[0].device_kind,
            "device_count": len(devices)}
