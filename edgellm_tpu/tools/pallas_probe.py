"""On-silicon proof of the Pallas codec path.

Every ``*_pallas`` wire codec the split runtime auto-substitutes on TPU
(``parallel/split.py``) is exercised here on the REAL backend — no
``interpret=True`` — and compared leaf-by-leaf against its jnp twin:

- integer payload leaves (packed nibbles / crumbs / int8 codes) must be
  bit-identical;
- float leaves (scales, minima, bf16 high-precision slices) and the decoded
  reconstruction are checked to <= 2 ulp (the documented kernel deviation:
  XLA may fuse ``(c / 7) * s`` in a different order than Mosaic) — or, for a
  leaf past that, to 2 ulp AT THE INPUT'S LARGEST MAGNITUDE in absolute
  terms: ``ternary_mean``'s per-channel mean is an XLA reduction in BOTH
  twins, and on the v5e XLA orders it differently in the two graphs (1.3e-8
  apart on means of N(0,1) rows — hundreds of ulp of a near-zero mean, nothing
  next to the activations; every integer leaf stayed bit-identical);
- encode/decode throughput is measured in GB/s, alongside the jnp twin's, so
  the fused-vs-unfused speedup is recorded per codec.

The result is a JSON-able dict that ``bench.py`` embeds as the ``"pallas"``
block of the bench detail line and sidecar (kernels lower through Mosaic,
match on hardware, and their throughput is recorded); ``chip_smoke.py`` runs
the parity half (``timing=False``, which never writes the probe cache). The
same probe runs in the test suite on CPU (interpret mode) so the parity logic
itself is covered without a chip.

Timing notes (a jitted call + scalar readback carries a fixed dispatch cost
that dwarfs a ~30 us codec kernel):
- DIFFERENTIAL timing cancels it: the same body is scanned at two lengths
  (``N1``/``N2``) and the per-iteration time is ``(t2 - t1) / (N2 - N1)``;
- each iteration indexes a pool of PRE-STAGED DISTINCT inputs via a
  loop-carried index, defeating XLA's loop-invariant hoisting (a hoisted
  ``encode(x)`` would time as a no-op);
- every payload leaf feeds the scan carry, so no output op is dead-code
  eliminated;
- ``float(...)`` on the carry is the host sync that ends each timed call.

Reference provenance: the kernels replace the per-channel Python loop at
``Experiments/Qwen2-0.5B/qwen_layer_wise.py:125-152`` (SURVEY.md section 3.5);
this probe is the evidence they run on the hardware the loop never targeted.
"""
from __future__ import annotations

import json
import time
from typing import Optional

import numpy as np

#: codec names (registry names) — every codec with a kernel twin. The
#: selective codec is NOT here: its twin was deleted in round 5 on
#: measurement (gather-bound; the pallas boundary broke XLA's gather->quant
#: fusion and probed 0.96-0.97x across rounds) — probe_all() appends the
#: recorded exclusion so the decision stays in every bench artifact.
PROBE_CODECS = (
    "int4_per_token",
    "int8_per_token",
    "int8_per_channel",
    "int4_per_channel",
    "ternary_mean",
    "ternary_max",
)


def _codec_pair(name: str):
    from edgellm_tpu.codecs.packing import get_wire_codec
    from edgellm_tpu.codecs.pallas_kernels import pallas_variant

    jnp_codec = get_wire_codec(name)
    return jnp_codec, pallas_variant(jnp_codec)


def _ulp_diff(got: np.ndarray, want: np.ndarray) -> int:
    """Max distance in representable steps between two same-dtype float arrays."""
    if got.size == 0:
        return 0
    kind = {2: np.int16, 4: np.int32, 8: np.int64}[got.dtype.itemsize]
    lowest = np.int64(np.iinfo(kind).min)  # the bit pattern of -0.0
    gi = got.view(kind).astype(np.int64)
    wi = want.view(kind).astype(np.int64)
    # map the sign-magnitude float encoding onto a monotone integer line:
    # negatives (sign bit set) become -(magnitude), with -0.0 -> 0
    gi = np.where(gi < 0, lowest - gi, gi)
    wi = np.where(wi < 0, lowest - wi, wi)
    return int(np.abs(gi - wi).max())


def _float_leaf_ok(got: np.ndarray, want: np.ndarray, max_ulp: int,
                   abs_tol: float, what: str) -> int:
    """A float leaf matches within ``max_ulp`` elementwise, or — where a
    near-zero value makes ulp meaningless — within ``abs_tol`` absolutely.
    Returns the ulp distance; raises naming ``what`` otherwise."""
    ulp = _ulp_diff(got, want)
    if ulp > max_ulp:
        worst = float(np.abs(got.astype(np.float64)
                             - want.astype(np.float64)).max())
        assert worst <= abs_tol, \
            f"{what}: {ulp} ulp > {max_ulp} and |diff| {worst:.3g} > {abs_tol:.3g}"
    return ulp


def _compare_payloads(got: dict, want: dict, max_ulp: int,
                      abs_tol: float = 0.0, what: str = "payload"):
    """(n_int_leaves bit-identical, worst float-leaf ulp). Raises on mismatch."""
    assert set(got) == set(want), (sorted(got), sorted(want))
    n_int, worst = 0, 0
    for key in sorted(want):
        g, w = np.asarray(got[key]), np.asarray(want[key])
        assert g.dtype == w.dtype and g.shape == w.shape, \
            f"{what}.{key}: {g.dtype}{g.shape} vs {w.dtype}{w.shape}"
        if np.issubdtype(w.dtype, np.integer):
            np.testing.assert_array_equal(g, w, err_msg=f"{what}.{key}")
            n_int += 1
        else:
            worst = max(worst, _float_leaf_ok(g, w, max_ulp, abs_tol,
                                              f"{what}.{key}"))
    return n_int, worst


def _nbytes(tree) -> int:
    import jax

    return int(sum(np.prod(l.shape) * l.dtype.itemsize
                   for l in jax.tree_util.tree_leaves(tree)))


#: differential-timing scan lengths; per-iter = (t[N2] - t[N1]) / (N2 - N1).
#: N2 is sized so a ~30 us kernel accumulates >50 ms of work delta — well
#: above per-call jitter — and _timed_scan quadruples the lengths
#: (recompiling) when a body is still too fast to resolve.
_N1, _N2 = 128, 2048
#: a measured work delta below this is indistinguishable from call jitter
_MIN_DELTA_S = 0.05

# Bench mode times the encode->decode ROUNDTRIP of every codec (2 scan
# executables per codec — separate encode/decode timing would double the
# compile count). EDGELLM_PROBE_ALL=1 adds the separate encode/decode split.


class _ScanTimer:
    """Differential-scan timer for one body, caching the compiled scan
    executables per length so REPEATED measurements (the interleaved-pair
    medians) cost readbacks, not retrace+recompile."""

    def __init__(self, build_body, pool_tree, pool: int):
        self.build_body = build_body
        self.pool_tree = pool_tree
        self.pool = pool
        self._runs: dict = {}

    def _run_for(self, length):
        import jax
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


def _timed_scan(build_body, pool_tree, pool: int, lengths=None) -> float:
    """Seconds per iteration of ``build_body`` applied to pool entry
    ``i % pool`` (leading axis of every ``pool_tree`` leaf = pool). One element
    of every output leaf is folded into the carry so nothing is DCE'd; the
    loop-carried index defeats hoisting. Differential over two scan lengths
    cancels the fixed per-call dispatch and readback cost."""
    return _ScanTimer(build_body, pool_tree, pool).differential(lengths)


def probe_codec(name: str, *, batch: int = 8, seq: int = 512, dim: int = 896,
                pool: int = 16, timing: bool = True, timing_detail: bool = False,
                max_ulp: int = 2, seed: int = 0) -> dict:
    """Parity + throughput for one codec pair on the CURRENT default backend."""
    import jax
    import jax.numpy as jnp

    jnp_codec, pallas_codec = _codec_pair(name)
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((batch, seq, dim)).astype(np.float32))
    imp = jnp.asarray(rng.random(seq).astype(np.float32))
    args = (x, imp) if jnp_codec.needs_importance else (x,)

    want = jax.jit(jnp_codec.encode)(*args)
    got = jax.jit(pallas_codec.encode)(*args)
    jax.block_until_ready((want, got))
    # max_ulp steps at the input's largest magnitude, as an absolute bound
    abs_tol = max_ulp * float(np.spacing(np.abs(np.asarray(x)).max()))
    n_int, enc_ulp = _compare_payloads(got, want, max_ulp, abs_tol, name)

    dec_want = np.asarray(jax.jit(jnp_codec.decode)(want))
    dec_got = np.asarray(jax.jit(pallas_codec.decode)(got))
    dec_ulp = _float_leaf_ok(dec_got, dec_want, max_ulp, abs_tol,
                             f"{name} decode")

    from edgellm_tpu.codecs.pallas_kernels import default_substituted
    from edgellm_tpu.codecs.probe_cache import base_name

    result = {
        "codec": name,
        "backend": jax.default_backend(),
        "shape": [batch, seq, dim],
        # whether the TPU default path substitutes this kernel (the measured-
        # win policy: this chip's probe cache, frozen set as no-data
        # fallback; split.apply_default_codec_backend); non-default twins
        # stay probed for parity and remain pinnable via *_pallas names
        "default_substituted": default_substituted(base_name(name)),
        "int_leaves_bit_identical": n_int,
        "encode_max_ulp": enc_ulp,
        "decode_max_ulp": dec_ulp,
        "float_abs_tol": abs_tol,
        "payload_bytes": _nbytes(want),
    }
    if not timing:
        return result

    import math

    in_bytes = int(np.prod(x.shape)) * 4
    payload_bytes = result["payload_bytes"]
    moved = 2 * (in_bytes + payload_bytes)  # enc: read+write, dec: read+write
    xs = jnp.asarray(rng.standard_normal((pool,) + x.shape).astype(np.float32))

    def roundtrip_body(codec):
        # return the payload ALONGSIDE the decoded output: _timed_scan folds
        # every leaf of the returned tree into the carry, so even a payload
        # leaf the decode side ignores cannot be dead-code-eliminated out of
        # the timed body
        def body(xi):
            p = (codec.encode(xi, imp) if codec.needs_importance
                 else codec.encode(xi))
            return p, codec.decode(p)

        return body

    # INTERLEAVED pairs, median ratio: timing all pallas scans then all jnp
    # scans lets slow drift in the machine's state masquerade as a codec
    # speed change. Each adjacent (pallas, jnp) pair shares its conditions;
    # the per-pair ratio cancels them and the median over pairs rejects a
    # single bad window. Executables cache, so the extra scans cost
    # readbacks, not compiles. A NaN differential (body inside call jitter
    # even after escalation) drops the pair rather than emit a physically
    # impossible rate (NaN would also break the JSON line).
    import statistics

    def paired_medians(make_p, make_j, tree, reps=3):
        """(median pallas time, median per-pair jnp/pallas ratio); the jnp
        side of a pair is only timed when the pallas differential resolved
        (escalating scans for a value that could never be emitted are the
        probe's biggest time sink). One _ScanTimer per side: the compiled
        scan executables are built once and every further rep is readbacks."""
        timer_p = _ScanTimer(make_p, tree, pool)
        timer_j = _ScanTimer(make_j, tree, pool)
        tps, ratios = [], []
        for _ in range(reps):
            tp = timer_p.differential()
            if not math.isfinite(tp):
                continue
            tps.append(tp)
            tj = timer_j.differential()
            if math.isfinite(tj):
                ratios.append(tj / tp)
        return (statistics.median(tps) if tps else float("nan"),
                statistics.median(ratios) if ratios else float("nan"))

    t_rt_p, rt_ratio = paired_medians(roundtrip_body(pallas_codec),
                                      roundtrip_body(jnp_codec), xs)
    if math.isfinite(t_rt_p):
        result["roundtrip_gbps"] = round(moved / t_rt_p / 1e9, 2)
        result["roundtrip_us"] = round(t_rt_p * 1e6, 1)
    if math.isfinite(rt_ratio):
        result["roundtrip_speedup_vs_jnp"] = round(rt_ratio, 2)
        # the UNROUNDED ratio is what the probe cache persists: the
        # WIN_MARGIN=1.05 hysteresis must never compare against a display
        # value a 1.045 reading was rounded up into (ADVICE r5 #3)
        result["roundtrip_speedup_vs_jnp_raw"] = rt_ratio
    if not timing_detail:
        return result

    payloads = jax.vmap(jnp_codec.encode, in_axes=(0, None) if len(args) == 2
                        else 0)(*((xs, imp) if len(args) == 2 else (xs,)))
    jax.block_until_ready(payloads)

    def enc_body(codec):
        if codec.needs_importance:
            return lambda xi: codec.encode(xi, imp)
        return codec.encode

    # same interleaved-pair estimator as the roundtrip: the split numbers
    # must not contradict the roundtrip just because the phase drifted
    # between the pallas and jnp measurements
    t_enc_p, enc_ratio = paired_medians(enc_body(pallas_codec),
                                        enc_body(jnp_codec), xs)
    t_dec_p, dec_ratio = paired_medians(pallas_codec.decode, jnp_codec.decode,
                                        payloads)
    if math.isfinite(t_enc_p):
        result["encode_gbps"] = round((in_bytes + payload_bytes) / t_enc_p / 1e9, 2)
        result["encode_us"] = round(t_enc_p * 1e6, 1)
    if math.isfinite(t_dec_p):
        result["decode_gbps"] = round((payload_bytes + in_bytes) / t_dec_p / 1e9, 2)
        result["decode_us"] = round(t_dec_p * 1e6, 1)
    if math.isfinite(enc_ratio):
        result["encode_speedup_vs_jnp"] = round(enc_ratio, 2)
    if math.isfinite(dec_ratio):
        result["decode_speedup_vs_jnp"] = round(dec_ratio, 2)
    return result


def probe_all(*, timing: Optional[bool] = None, batch: int = 8, seq: int = 512,
              dim: int = 896, pool: int = 16) -> dict:
    """The ``"pallas"`` bench detail block: every substituted codec, parity + GB/s.

    ``timing=None`` enables timing only on a real TPU backend (interpret-mode
    timings would be meaningless).
    """
    import jax

    import os

    on_tpu = jax.default_backend() == "tpu"
    if timing is None:
        timing = on_tpu
    detail = os.environ.get("EDGELLM_PROBE_ALL", "0") == "1"
    codecs = []
    for name in PROBE_CODECS:
        codecs.append(probe_codec(
            name, batch=batch, seq=seq, dim=dim, pool=pool,
            timing=timing, timing_detail=timing and detail))
    from edgellm_tpu.codecs.pallas_kernels import SELECTIVE_EXCLUSION

    codecs.append({
        "codec": "selective_int4",
        "default_substituted": False,
        "excluded": SELECTIVE_EXCLUSION,
        # the measurements the deletion decision rests on (v5e, r4/r5)
        "measured": {"roundtrip_speedup_vs_jnp_r4": 0.97,
                     "roundtrip_speedup_vs_jnp_r5": 0.96,
                     "encode_speedup_vs_jnp_r5": 0.97,
                     "decode_speedup_vs_jnp_r5": 0.99},
    })
    cache_path = None
    if timing:
        # persist this run's measured speedups as THE substitution policy for
        # this chip (codecs/probe_cache.py), then re-annotate each block with
        # the post-record policy: what the NEXT sweep on this chip will
        # substitute, derived from measurement, never a stale constant
        from edgellm_tpu.codecs.pallas_kernels import default_substituted
        from edgellm_tpu.codecs.probe_cache import base_name, record

        cache_path = record(codecs)
        if cache_path:
            for c in codecs:
                if "excluded" not in c:  # deleted twins stay excluded
                    c["default_substituted"] = default_substituted(
                        base_name(c["codec"]))
    return {
        "backend": jax.default_backend(),
        "interpret": not on_tpu,
        "shape": [batch, seq, dim],
        "parity": "int leaves bit-identical; float leaves and decode <= 2 ulp "
                  "(or <= 2 ulp of the input's max magnitude, absolute)",
        "timing": None if not timing else (
            "roundtrip per codec" + (" + encode/decode split" if detail else
                                     " (EDGELLM_PROBE_ALL=1 adds the split)")),
        "probe_cache": cache_path,
        "codecs": codecs,
    }


def main():
    print(json.dumps(probe_all(), indent=2))


if __name__ == "__main__":
    main()
