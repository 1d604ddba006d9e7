"""Tracing and timing helpers (SURVEY.md section 5: the reference's only
observability is tqdm progress bars; here: real XLA traces + wall-clock helpers).

``trace("/tmp/trace")`` wraps ``jax.profiler.trace`` — view the result with
TensorBoard or Perfetto to see per-op device time, including the ``ppermute``
boundary transfers and Pallas codec kernels. ``timed``/``throughput`` give
honest wall-clock numbers by blocking on device completion.
"""
from __future__ import annotations

import contextlib
import time

import jax


@contextlib.contextmanager
def trace(log_dir: str):
    """Deprecated shim over :func:`edgellm_tpu.obs.tracing.trace_capture`
    (same contract: capture an XLA profiler trace for the enclosed block,
    raise when the profiler cannot start). New code should
    use ``obs.tracing.trace_capture`` directly — it composes with the host
    span tracer and the ``--trace-out`` Chrome trace export."""
    import warnings

    from ..obs.tracing import trace_capture

    warnings.warn("utils.profiling.trace is deprecated; use "
                  "edgellm_tpu.obs.tracing.trace_capture",
                  DeprecationWarning, stacklevel=3)
    with trace_capture(log_dir):
        yield


def _block(x):
    return jax.tree_util.tree_map(
        lambda a: a.block_until_ready() if hasattr(a, "block_until_ready") else a, x)


def timed(fn, *args, warmup: int = 1, iters: int = 10, **kwargs):
    """(mean seconds per call, last result); compiles/warms up first."""
    result = None
    for _ in range(max(warmup, 0)):
        result = _block(fn(*args, **kwargs))
    t0 = time.monotonic()
    for _ in range(iters):
        result = _block(fn(*args, **kwargs))
    return (time.monotonic() - t0) / iters, result


def throughput(fn, *args, tokens: int, **kwargs) -> dict:
    """Tokens/second for a step processing ``tokens`` tokens."""
    sec, _ = timed(fn, *args, **kwargs)
    return {"s_per_step": sec, "tokens_per_s": tokens / sec}


def measure_peak_tflops(sizes=(4096, 6144), pool: int = 4,
                        attempts: int = 3):
    """The chip's ACHIEVABLE bf16 matmul rate (TF/s): best sustained rate of a
    few large square matmuls, measured with the differential-scan harness
    (``tools.pallas_probe._timed_scan``), which cancels the fixed per-call
    dispatch and readback cost. Reported next to the published peak, never in
    place of it.

    Returns None if no attempt lands in a physically sane band (a short
    differential can vanish into call jitter; callers must not divide by a
    garbage peak)."""
    import statistics

    import numpy as np
    import jax.numpy as jnp

    from ..tools.pallas_probe import _timed_scan

    rng = np.random.default_rng(0)
    best = None
    for n in sizes:
        a = jnp.asarray(rng.standard_normal((n, n)).astype(np.float32)
                        ).astype(jnp.bfloat16)
        bs = jnp.asarray(rng.standard_normal((pool, n, n)).astype(np.float32)
                         ).astype(jnp.bfloat16)
        # MEDIAN of the sane attempts. The sanity band is PHYSICAL (no
        # accelerator does 2000 bf16 TF/s), not a configured peak: banding on
        # a table value would reject every honest sample on a faster chip.
        vals = []
        for _ in range(attempts):
            t = _timed_scan(
                lambda b_mat: jnp.dot(a, b_mat, preferred_element_type=jnp.float32),
                bs, pool, lengths=(32, 256))
            tflops = 2.0 * n ** 3 / t / 1e12
            if 10.0 < tflops < 2000.0:
                vals.append(tflops)
        if vals:
            best = max(best or 0.0, statistics.median(vals))
    return best
