"""Silicon probe for the attention kernels: Pallas (whole-S / blocked) vs
XLA's fused ``jax.nn.dot_product_attention`` at the model shapes the sweeps
actually run.

A phase-robust estimator: each variant is timed with the differential scan
(``utils.profiling.ScanTimer``), measurements are taken in interleaved
(pallas, xla) pairs, and the reported speedup is the median of per-pair
ratios, so slow drift between measurements cancels.
:func:`parity_shape` is the correctness half: one kernel plan, compiled
(never interpreted) on the current backend, against XLA's attention.

Reference workload being covered: both Pythia experiments evaluate at
window = 2048 (``Experiments/Pythia-70M/initial_exp.py:86``,
``last_row_exp.py:72-74``) — the shape that motivated the blocked kernel.
"""
from __future__ import annotations

import json
from statistics import median

import numpy as np

from ..utils.profiling import ScanTimer

#: (name, batch, heads, kv_heads, seq, head_dim) — the sweep shapes:
#: pythia window-2048 (reference's own evaluation window), the flagship ring
#: config's full-sequence shape, llama-1b at the standard window, and the
#: two whole-S shapes already validated in round 4 (regression guards).
SHAPES = [
    ("pythia-70m_s2048", 8, 8, 8, 2048, 64),
    ("qwen2-0.5b_s2048", 8, 14, 2, 2048, 64),
    ("llama-3.2-1b_s512", 32, 32, 8, 512, 64),
    ("qwen2-0.5b_s512", 64, 14, 2, 512, 64),
    ("qwen2-1.5b_s512", 32, 12, 2, 512, 128),
]


def probe_shape(name: str, b: int, h: int, kv: int, s: int, hd: int,
                *, pool: int = 2, reps: int = 3, stats: bool = False,
                seed: int = 0) -> dict:
    """Time kernel vs XLA attention at one shape -> result dict."""
    import jax
    import jax.numpy as jnp

    from ..models import flash_attention as fa

    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(pool, b, s, h, hd)), jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(pool, b, s, kv, hd)), jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(pool, b, s, kv, hd)), jnp.bfloat16)
    tree = (q, k, v)

    plan = fa._shape_plan(s, h, kv, hd)
    if plan is None:
        return {"shape": name, "plan": None}

    if stats:
        def pallas_body(x):
            out, st = fa.causal_attention_stats(*x, interpret=False, plan=plan)
            return (out, *st)
    else:
        def pallas_body(x):
            return fa.causal_attention(*x, interpret=False, plan=plan)

    def xla_body(x):
        return jax.nn.dot_product_attention(*x, is_causal=True)

    import math

    tp = ScanTimer(pallas_body, tree, pool)
    tx = ScanTimer(xla_body, tree, pool)
    # drop pairs with an unresolved (NaN) differential: a median over NaNs
    # is undefined and a NaN field would make the bench sidecar
    # spec-invalid JSON
    pairs = [(p, x) for p, x in
             ((tp.differential(), tx.differential()) for _ in range(reps))
             if math.isfinite(p) and math.isfinite(x)]
    result = {"shape": name, "dims": [b, h, kv, s, hd], "plan": list(plan),
              "stats": stats}
    if not pairs:  # every rep stayed inside the jitter band: no rate fields
        return result
    p_s = median(p for p, _ in pairs)
    x_s = median(x for _, x in pairs)
    ratio = median(x / p for p, x in pairs)
    # full-square accounting (the kernels compute and mask the causal upper
    # triangle — measured faster than any skip; see flash_attention.py)
    flops = 4.0 * b * h * s * s * hd
    result.update({
        "pallas_us": round(p_s * 1e6, 1), "xla_us": round(x_s * 1e6, 1),
        "pallas_tflops": round(flops / p_s / 1e12, 1),
        "xla_tflops": round(flops / x_s / 1e12, 1),
        "speedup_vs_xla": round(ratio, 2),
    })
    return result


#: max |kernel - reference| allowed by :func:`parity_shape`, as a fraction of
#: the largest |v| (every output row is a convex combination of v rows). The
#: reference is the dense formulation at ``default_matmul_precision("highest")``;
#: the kernel runs at the program's default precision, where the MXU takes
#: fp32 operands in a single bf16 pass: q.k and p.v each see their operands
#: rounded to bf16 (2**-9 relative apiece), so 2**-7 covers both matmuls, and
#: a bf16 kernel rounds its output once more. First run on the v5e: 0.009 -
#: 0.015 absolute at max|v| ~ 4.5, i.e. 2e-3 - 3.4e-3 of it. A wrong mask,
#: head mapping or block index moves outputs by O(max|v|) — far outside.
PARITY_REL = {"float32": 2.0 ** -7, "bfloat16": 1.5 * 2.0 ** -7}
#: the stats are probabilities in [0, 1] kept in fp32 by every kernel
PARITY_STATS_ATOL = 1e-2


def parity_shape(b: int, h: int, kv: int, s: int, hd: int, *,
                 dtype: str = "float32", stats: bool = False, plan=None,
                 seed: int = 0) -> dict:
    """Run one attention kernel plan on the CURRENT backend (compiled by
    Mosaic on a TPU, interpreted elsewhere) and compare it with the dense
    causal-softmax formulation in fp32 at full matmul precision. ``plan``
    defaults to what the shape gate picks for this dtype. Returns the
    measured errors; raises ``AssertionError`` past the tolerance."""
    import jax
    import jax.numpy as jnp

    from ..models import flash_attention as fa

    dt = jnp.dtype(dtype)
    if plan is None:
        plan = fa._shape_plan(s, h, kv, hd, itemsize=dt.itemsize)
    if plan is None:
        raise ValueError(f"no kernel covers S={s}, H={h}, KV={kv}, hd={hd} "
                         f"at {dt.name}")
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(b, s, h, hd)), dt)
    k = jnp.asarray(rng.normal(size=(b, s, kv, hd)), dt)
    v = jnp.asarray(rng.normal(size=(b, s, kv, hd)), dt)
    interpret = fa._use_interpret()

    if stats:
        out, (col, last) = jax.jit(
            lambda *x: fa.causal_attention_stats(*x, interpret=interpret,
                                                 plan=plan))(q, k, v)
    else:
        out = jax.jit(lambda *x: fa.causal_attention(
            *x, interpret=interpret, plan=plan))(q, k, v)

    @jax.jit
    def reference(q, k, v):
        rep = h // kv
        qf, kf, vf = (x.astype(jnp.float32) for x in (q, k, v))
        kf, vf = jnp.repeat(kf, rep, axis=2), jnp.repeat(vf, rep, axis=2)
        sc = jnp.einsum("bqhd,bthd->bhqt", qf, kf) / np.sqrt(hd)
        causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
        p = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
        return (jnp.einsum("bhqt,bthd->bqhd", p, vf),
                jnp.sum(p, axis=2) / s, p[:, :, -1, :])

    with jax.default_matmul_precision("highest"):
        want, want_col, want_last = reference(q, k, v)

    def err(got, ref):
        return float(jnp.max(jnp.abs(got.astype(jnp.float32) - ref)))

    atol = PARITY_REL[dt.name] * float(jnp.max(jnp.abs(v.astype(jnp.float32))))
    result = {"dims": [b, h, kv, s, hd], "dtype": dt.name,
              "plan": [plan[0], list(plan[1]) if plan[1] else None],
              "stats": stats, "interpret": interpret,
              "out_max_abs_err": err(out, want), "atol": atol}
    ok = result["out_max_abs_err"] <= result["atol"]
    if stats:
        result["col_max_abs_err"] = err(col, want_col)
        result["last_max_abs_err"] = err(last, want_last)
        result["stats_atol"] = PARITY_STATS_ATOL
        ok = ok and max(result["col_max_abs_err"],
                        result["last_max_abs_err"]) <= PARITY_STATS_ATOL
    assert ok and bool(jnp.all(jnp.isfinite(out.astype(jnp.float32)))), \
        f"attention kernel parity failed: {result}"
    return result


def probe_all(*, stats: bool = False, shapes=None) -> list[dict]:
    out = []
    for args in (shapes or SHAPES):
        out.append(probe_shape(*args, stats=stats))
        print(json.dumps(out[-1]), flush=True)
    return out


def main():
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--stats", action="store_true",
                    help="time the stats-capture variants instead")
    ap.add_argument("--shape", default=None,
                    help="probe only the named shape")
    a = ap.parse_args()
    shapes = [t for t in SHAPES if a.shape is None or t[0] == a.shape]
    probe_all(stats=a.stats, shapes=shapes)


if __name__ == "__main__":
    main()
