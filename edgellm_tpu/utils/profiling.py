"""Timing helpers (SURVEY.md section 5: the reference's only observability is
tqdm progress bars; here: wall-clock helpers; XLA traces are
``obs.tracing.trace_capture``).

``timed``/``throughput`` give honest wall-clock numbers by blocking on device
completion.
"""
from __future__ import annotations

import time

import jax


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


# Differential-scan timing (a jitted call + scalar readback carries a fixed
# dispatch cost that dwarfs a ~30 us kernel):
# - the same body is scanned at two lengths (``N1``/``N2``) and the
#   per-iteration time is ``(t2 - t1) / (N2 - N1)``, which cancels that cost;
# - each iteration indexes a pool of PRE-STAGED DISTINCT inputs via a
#   loop-carried index, defeating XLA's loop-invariant hoisting (a hoisted
#   body would time as a no-op);
# - every output leaf feeds the scan carry, so no output op is dead-code
#   eliminated;
# - ``float(...)`` on the carry is the host sync that ends each timed call.

#: differential-timing scan lengths; per-iter = (t[N2] - t[N1]) / (N2 - N1).
#: N2 is sized so a ~30 us kernel accumulates >50 ms of work delta — well
#: above per-call jitter — and ScanTimer.differential quadruples the lengths
#: (recompiling) when a body is still too fast to resolve.
_N1, _N2 = 128, 2048
#: a measured work delta below this is indistinguishable from call jitter
_MIN_DELTA_S = 0.05


class ScanTimer:
    """Differential-scan timer for one body, caching the compiled scan
    executables per length so REPEATED measurements (the interleaved-pair
    medians) cost readbacks, not retrace+recompile."""

    def __init__(self, build_body, pool_tree, pool: int):
        self.build_body = build_body
        self.pool_tree = pool_tree
        self.pool = pool
        self._runs: dict = {}

    def _run_for(self, length):
        import jax.numpy as jnp

        if length in self._runs:
            return self._runs[length]
        build_body, pool = self.build_body, self.pool

        @jax.jit
        def run(tree):
            def body(carry, idx):
                x = jax.tree_util.tree_map(
                    lambda a: jax.lax.dynamic_index_in_dim(a, idx, 0,
                                                           keepdims=False), tree)
                out = build_body(x)
                leaves = jax.tree_util.tree_leaves(out)
                # FULL reduction over every leaf: a single-element read would
                # let XLA's slice-pushdown shrink the body (dot(a,b)[0,0]
                # becomes a vector dot and times as a no-op). The reduce fuses
                # into the producer, so it adds no extra HBM round trip.
                acc = sum(jnp.sum(l.astype(jnp.float32)) for l in leaves if l.size)
                return carry + acc, None

            carry, _ = jax.lax.scan(body, jnp.float32(0.0),
                                    jnp.arange(length) % pool)
            return carry

        self._runs[length] = run
        return run

    def _rep_of(self, run, reps=2):
        float(run(self.pool_tree))  # compile + warm
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            float(run(self.pool_tree))  # host sync ends the timed call
            ts.append(time.perf_counter() - t0)
        return min(ts)

    def differential(self, lengths=None) -> float:
        n1, n2 = lengths or (_N1, _N2)
        for _ in range(3):
            t1 = self._rep_of(self._run_for(n1))
            t2 = self._rep_of(self._run_for(n2))
            delta, span = t2 - t1, n2 - n1
            if delta >= _MIN_DELTA_S:
                return delta / span
            n1, n2 = n1 * 4, n2 * 4  # too fast to resolve: quadruple the work
        # still inside the jitter band after escalating: NaN, never a rate
        # made of noise (callers omit the affected fields)
        return float("nan")


def measure_peak_tflops(sizes=(4096, 6144), pool: int = 4,
                        attempts: int = 3):
    """The chip's ACHIEVABLE bf16 matmul rate (TF/s): best sustained rate of a
    few large square matmuls, measured with the differential-scan harness
    (:class:`ScanTimer`), which cancels the fixed per-call dispatch and
    readback cost. Reported next to the published peak, never in
    place of it.

    Returns None if no attempt lands in a physically sane band (a short
    differential can vanish into call jitter; callers must not divide by a
    garbage peak)."""
    import statistics

    import numpy as np
    import jax.numpy as jnp

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
            t = ScanTimer(
                lambda b_mat: jnp.dot(a, b_mat, preferred_element_type=jnp.float32),
                bs, pool).differential((32, 256))
            tflops = 2.0 * n ** 3 / t / 1e12
            if 10.0 < tflops < 2000.0:
                vals.append(tflops)
        if vals:
            best = max(best or 0.0, statistics.median(vals))
    return best
