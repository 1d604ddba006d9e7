"""AOT window-batch preflight: pick the largest batch that FITS, never OOM.

Rather than launch, hit RESOURCE_EXHAUSTED and retry smaller
(``run_with_oom_backoff``), AOT-compile the sweep's big executables (the
stats forward, the ratio-vmapped suffix sweep and the baseline tail scorer)
at each candidate batch and read XLA's ``memory_analysis()`` — compilation
allocates no HBM — then run only the batch whose estimated peak fits the
limit the device itself reports.

The estimate for one executable is ``argument + output + temp`` bytes; on top
of the worst call the sweep keeps TWO boundary-hidden stacks alive (the
drained group's and the in-flight next group's, from the submit/drain
double-buffering) plus the captured stats, which are added analytically.
``budget_frac`` absorbs what the estimate cannot see (allocator slack,
fragmentation, the small executables).

The lower/compile/``memory_analysis()`` primitive lives in
:mod:`edgellm_tpu.analysis.aot` — shared with the config-lattice verifier
(``lint/lattice.py``) so the two AOT consumers cannot drift.
"""
from __future__ import annotations

from typing import Optional, Sequence

from ..analysis.aot import call_total_bytes, is_over_hbm

#: back-compat alias — callers and tests predate the analysis.aot extraction
_is_over_hbm = is_over_hbm


def _budget_bytes(hbm_bytes: Optional[int], budget_frac: float) -> int:
    """``budget_frac`` of the device memory. With no explicit ``hbm_bytes``
    the limit is what the default device reports
    (``memory_stats()["bytes_limit"]``) — never an assumed chip size."""
    if hbm_bytes is None:
        import jax

        stats = jax.devices()[0].memory_stats()
        if not stats or "bytes_limit" not in stats:
            raise RuntimeError(
                f"device {jax.devices()[0]} reports no memory limit "
                f"(memory_stats() = {stats!r}); pass hbm_bytes explicitly")
        hbm_bytes = int(stats["bytes_limit"])
    return int(hbm_bytes * budget_frac)


def estimate_sweep_peak_bytes(cfg, window_batch: int, max_length: int,
                              tail: int, layer: int, codec: str,
                              n_ratios: int, dtype,
                              layers: Optional[Sequence[int]] = None) -> dict:
    """Estimated HBM peak of the token sweep at one window batch (bytes).

    ``layers`` is the full ``layers_of_interest`` tuple (defaults to
    ``(layer,)``) — the stats forward collects hiddens only at those layers
    and captures stats only up to the deepest one, so the estimate mirrors
    the executables ``run_token_sweep`` actually compiles."""
    import jax
    import jax.numpy as jnp

    from ..eval.harness import (DEDUP_ZERO_CODECS, _stats_forward,
                                _suffix_sweep)
    from ..models import init_params

    layers = tuple(int(l) for l in (layers if layers is not None else (layer,)))
    W, S, D = window_batch, max_length, cfg.hidden_size
    n_interest = len(set(layers))
    n_stats = max(layers) + 1
    params_shape = jax.eval_shape(
        lambda k: init_params(cfg, k, dtype=dtype), jax.random.key(0))
    ids = jax.ShapeDtypeStruct((W, S), jnp.int32)
    targets = jax.ShapeDtypeStruct((W, S), jnp.int32)

    # argument+output+temp bytes, or None when the TPU compiler itself
    # rejects the program as over-HBM — a provable doesn't-fit, still with
    # zero allocation (shared driver: analysis/aot.py)
    call_bytes = call_total_bytes

    want_final = codec in DEDUP_ZERO_CODECS
    stats = call_bytes(_stats_forward(cfg, layers, want_final=want_final)
                       .lower(params_shape, ids))

    hidden = jax.ShapeDtypeStruct((W, S, D), dtype)
    imp = jax.ShapeDtypeStruct((W, S), jnp.float32)
    ratios = jax.ShapeDtypeStruct((n_ratios,), jnp.float32)
    ks = jax.ShapeDtypeStruct((n_ratios,), jnp.int32)
    suffix = call_bytes(_suffix_sweep(cfg, layer, codec, tail)
                        .lower(params_shape, hidden, targets, imp, ratios, ks))
    base = 0
    if want_final:
        # the baseline tail scorer is a THIRD executable since round 5 split
        # it out of the stats forward (_base_tail): its streamed-unembed
        # temps must be in the estimate too, or the preflight approves a
        # batch that OOMs at the baseline-scoring call
        from ..eval.harness import _base_tail

        base = call_bytes(_base_tail(cfg, tail)
                          .lower(params_shape, hidden, targets))

    if stats is None or suffix is None or base is None:  # proven over-HBM
        return {"stats_call": stats, "suffix_call": suffix, "base_call": base,
                "hiddens_stack": 0, "peak": float("inf")}
    itemsize = jnp.dtype(dtype).itemsize
    hiddens_stack = n_interest * W * S * D * itemsize  # collected boundaries
    stats_buf = 2 * n_stats * W * cfg.num_heads * S * 4  # col_mean + last_row
    # worst single call + the other live group state the call's args don't hold:
    # the suffix sees one (W,S,D) slice as an arg while BOTH groups' full
    # stacks are alive (submit/drain double buffering)
    peak = max(stats + hiddens_stack,  # stats call + previous group's stack
               suffix + 2 * hiddens_stack + 2 * stats_buf,
               base + 2 * hiddens_stack + 2 * stats_buf)
    return {"stats_call": stats, "suffix_call": suffix, "base_call": base,
            "hiddens_stack": hiddens_stack, "peak": peak}


def preflight_token_sweep_batch(cfg, requested: int, *, max_length: int,
                                stride: int, layers_of_interest: Sequence[int],
                                ratios: Sequence[float], dtype,
                                codec: str = "int4_token_select",
                                hbm_bytes: Optional[int] = None,
                                budget_frac: float = 0.8) -> int:
    """Sweep-shaped wrapper around :func:`largest_fitting_window_batch`,
    shared by bench.py and run.py: sizes the EARLIEST split layer (longest
    suffix = biggest executable) and counts the ratio axis the way
    run_token_sweep compiles it (nonzero ratios only for dedup codecs)."""
    from ..eval.harness import DEDUP_ZERO_CODECS

    n_ratios = (sum(1 for r in ratios if float(r) != 0.0)
                if codec in DEDUP_ZERO_CODECS else len(ratios))
    wb, _ = largest_fitting_window_batch(
        cfg, requested, max_length=max_length, tail=stride + 1,
        layer=min(int(l) for l in layers_of_interest), codec=codec,
        n_ratios=max(n_ratios, 1), dtype=dtype,
        hbm_bytes=hbm_bytes, budget_frac=budget_frac,
        layers=tuple(int(l) for l in layers_of_interest))
    return wb


def largest_fitting_relevance_batch(cfg, requested: int, *, max_length: int,
                                    dtype, hbm_bytes: Optional[int] = None,
                                    budget_frac: float = 0.8,
                                    min_window_batch: int = 1) -> int:
    """Largest window batch whose LRP vjp executable fits — same AOT
    memory-analysis approach as the sweep preflight (the (L, W, H, S, S)
    probs + their cotangents dominate)."""
    import jax
    import jax.numpy as jnp

    from ..importance.relevance import _chunk_relevance
    from ..models import init_params

    budget = _budget_bytes(hbm_bytes, budget_frac)
    params_shape = jax.eval_shape(
        lambda k: init_params(cfg, k, dtype=dtype), jax.random.key(0))
    wb = requested
    while wb > min_window_batch:
        ids = jax.ShapeDtypeStruct((wb, max_length), jnp.int32)
        total = call_total_bytes(_chunk_relevance(cfg).lower(params_shape, ids))
        if total is not None and total <= budget:
            return wb
        wb = max(wb // 2, min_window_batch)
    return wb


def largest_fitting_window_batch(cfg, requested: int, *, max_length: int,
                                 tail: int, layer: int, codec: str,
                                 n_ratios: int, dtype,
                                 hbm_bytes: Optional[int] = None,
                                 budget_frac: float = 0.8,
                                 min_window_batch: int = 1,
                                 layers: Optional[Sequence[int]] = None) -> tuple:
    """Halve ``requested`` until the estimated peak fits -> (wb, estimate)."""
    budget = _budget_bytes(hbm_bytes, budget_frac)
    wb = requested
    while True:
        est = estimate_sweep_peak_bytes(cfg, wb, max_length, tail, layer,
                                        codec, n_ratios, dtype, layers=layers)
        if est["peak"] <= budget or wb <= min_window_batch:
            return wb, est
        wb = max(wb // 2, min_window_batch)
