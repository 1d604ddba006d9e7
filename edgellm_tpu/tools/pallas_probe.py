"""On-silicon proof of the Pallas codec path.

Every kernel twin the split runtime substitutes on a TPU
(``codecs.pallas_kernels.pallas_variant``) is exercised here on the REAL
backend — no ``interpret=True`` — and compared leaf-by-leaf against its jnp
twin:

- integer payload leaves (packed nibbles / crumbs) must be bit-identical;
- float leaves (scales) and the decoded reconstruction are checked to
  <= 2 ulp (the documented kernel deviation: XLA may fuse ``(c / 7) * s`` in
  a different order than Mosaic) — or, for a leaf past that, to 2 ulp AT THE
  INPUT'S LARGEST MAGNITUDE in absolute terms: ``ternary_mean``'s per-channel
  mean is an XLA reduction in BOTH twins, and on the v5e XLA orders it
  differently in the two graphs (1.3e-8 apart on means of N(0,1) rows —
  hundreds of ulp of a near-zero mean, nothing next to the activations; every
  integer leaf stayed bit-identical).

The result is a JSON-able dict; ``chip_smoke.py`` runs it on the chip. The
same probe runs in the test suite on CPU (interpret mode) so the parity logic
itself is covered without a chip. It times nothing: no cell's hop is large
enough to time a twin against its jnp codec (ROADMAP S7).

Reference provenance: the kernels replace the per-channel Python loop at
``Experiments/Qwen2-0.5B/qwen_layer_wise.py:125-152`` (SURVEY.md section 3.5);
this probe is the evidence they run on the hardware the loop never targeted.
"""
from __future__ import annotations

import json

import numpy as np

#: codec names (registry names) — every codec with a kernel twin. The
#: selective codec is NOT here: its twin was deleted in round 5 on
#: measurement (gather-bound; the pallas boundary broke XLA's gather->quant
#: fusion and probed 0.96-0.97x across rounds) — probe_all() appends the
#: recorded exclusion so the decision stays in every probe artifact.
PROBE_CODECS = (
    "int4_per_token",
    "int4_per_channel",
    "ternary_mean",
    "ternary_max",
)


def _codec_pair(name: str):
    from edgellm_tpu.codecs.packing import get_wire_codec

    return get_wire_codec(name), get_wire_codec(f"{name}_pallas")


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


def probe_codec(name: str, *, batch: int = 8, seq: int = 512, dim: int = 896,
                max_ulp: int = 2, seed: int = 0) -> dict:
    """Parity of one codec pair on the CURRENT default backend."""
    import jax
    import jax.numpy as jnp

    jnp_codec, pallas_codec = _codec_pair(name)
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((batch, seq, dim)).astype(np.float32))

    want = jax.jit(jnp_codec.encode)(x)
    got = jax.jit(pallas_codec.encode)(x)
    jax.block_until_ready((want, got))
    # max_ulp steps at the input's largest magnitude, as an absolute bound
    abs_tol = max_ulp * float(np.spacing(np.abs(np.asarray(x)).max()))
    n_int, enc_ulp = _compare_payloads(got, want, max_ulp, abs_tol, name)

    dec_want = np.asarray(jax.jit(jnp_codec.decode)(want))
    dec_got = np.asarray(jax.jit(pallas_codec.decode)(got))
    dec_ulp = _float_leaf_ok(dec_got, dec_want, max_ulp, abs_tol,
                             f"{name} decode")
    return {
        "codec": name,
        "backend": jax.default_backend(),
        "shape": [batch, seq, dim],
        "int_leaves_bit_identical": n_int,
        "encode_max_ulp": enc_ulp,
        "decode_max_ulp": dec_ulp,
        "float_abs_tol": abs_tol,
        "payload_bytes": _nbytes(want),
    }


def probe_all(*, batch: int = 8, seq: int = 512, dim: int = 896) -> dict:
    """Every kernel twin against its jnp codec, plus the recorded exclusion."""
    import jax

    from edgellm_tpu.codecs.pallas_kernels import SELECTIVE_EXCLUSION

    codecs = [probe_codec(name, batch=batch, seq=seq, dim=dim)
              for name in PROBE_CODECS]
    codecs.append({
        "codec": "selective_int4",
        "excluded": SELECTIVE_EXCLUSION,
        # the measurements the deletion decision rests on (v5e, r4/r5)
        "measured": {"roundtrip_speedup_vs_jnp_r4": 0.97,
                     "roundtrip_speedup_vs_jnp_r5": 0.96,
                     "encode_speedup_vs_jnp_r5": 0.97,
                     "decode_speedup_vs_jnp_r5": 0.99},
    })
    return {
        "backend": jax.default_backend(),
        "interpret": jax.default_backend() != "tpu",
        "shape": [batch, seq, dim],
        "parity": "int leaves bit-identical; float leaves and decode <= 2 ulp "
                  "(or <= 2 ulp of the input's max magnitude, absolute)",
        "codecs": codecs,
    }


def main():
    print(json.dumps(probe_all(), indent=2))


if __name__ == "__main__":
    main()
