"""Pallas TPU kernels for the boundary codec hot path.

The reference's clearest kernel-shaped code is its per-channel Python loop over
896 channels (``qwen_layer_wise.py:125-152``, SURVEY.md section 3.5); here the
codec ops are single fused TPU kernels: quantize + nibble/crumb-pack in one VMEM
pass (fp32 in -> packed uint8 + scales out, one HBM round-trip instead of
quantize/clip/round/pack each materializing an intermediate), and the matching
unpack + dequantize.

Layout notes (see ``pallas_guide.md``):
- blocks tile the token axis; the feature axis stays whole (a lane multiple for
  real models: 896, 512) so per-token reductions are single-block row reductions;
- packing pairs element i with element i + D/2 (contiguous halves — full-lane
  slices, no strided lane access); identical to ``packing.pack_int4``;
- interpret mode runs the same kernels on CPU (used by the test suite; the
  wrappers auto-select based on the backend).

Kernel inventory (each bit-identical to its jnp twin in ``packing`` — tested):
- ``int4_per_token``: per-row max-abs scale + quantize + pack, fully fused;
- ``int8_per_token``: per-row affine (min/max -> scale, zero-point) + quantize;
- channel-scale ternary quantize+pack (``ternary_mean`` / ``ternary_max``;
  the (B,S) channel-scale reduction stays in XLA);
- channel-scale int8 quantize and int4 quantize+pack (``int8_per_channel`` /
  ``int4_per_channel`` — the reference's 896-channel Python loop as one pass).

``selective_int4`` deliberately has NO kernel twin — a measured round-5
deletion, not a gap: the codec is gather-bound and XLA fuses the quantize
into the gather chain, so the twin could only lose (``SELECTIVE_EXCLUSION``
carries the numbers; the probe records it every bench run).

``pallas_wire_codec`` / ``pallas_int8_per_token`` / ``pallas_ternary`` wrap
these in the :class:`~edgellm_tpu.codecs.packing.WireCodec` interface;
``pallas_variant`` maps any jnp wire codec to its Pallas twin (the split
runtime substitutes automatically on TPU where the probe cache says the twin
wins on this chip).
"""
from __future__ import annotations

import dataclasses
import functools
import os
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .packing import WireCodec


def _use_interpret() -> bool:
    return jax.default_backend() != "tpu"


def _encode_kernel(x_ref, packed_ref, scale_ref):
    """One token-tile: per-row max-abs scale -> int4 codes -> packed nibbles."""
    x = x_ref[:]  # (T, D) fp32
    half = x.shape[-1] // 2
    max_val = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    safe = jnp.where(max_val > 0, max_val, 1.0)
    codes = jnp.round(jnp.clip(x / safe * 7.0, -8.0, 7.0)).astype(jnp.int32) + 8
    lo, hi = codes[:, :half], codes[:, half:]
    packed_ref[:] = (lo | (hi << 4)).astype(jnp.uint8)
    scale_ref[:] = safe


def _decode_kernel(packed_ref, scale_ref, out_ref):
    """Unpack nibbles -> dequantize. ONE body for every int4 scale granularity:
    ``scale_ref[:]`` broadcasts a per-row (T, 1), global (1, 1), or per-channel
    (1, D) scale block identically, so the unpack logic exists exactly once.
    Arithmetic order matches the per-token jnp twin bit-for-bit; the per-channel
    twin multiplies scale before the /7 (<=1 ulp apart, within the decode
    tolerance the twin tests pin)."""
    packed = packed_ref[:].astype(jnp.int32)  # (T, D/2)
    lo = (packed & 0xF) - 8
    hi = ((packed >> 4) & 0xF) - 8
    codes = jnp.concatenate([lo, hi], axis=-1).astype(jnp.float32)
    out_ref[:] = codes / 7.0 * scale_ref[:]


def _tile(n_tokens: int) -> int:
    """Token-tile size: sublane-friendly, bounded by the token count."""
    for t in (256, 128, 64, 32, 16, 8):
        if n_tokens % t == 0:
            return t
    return n_tokens


@functools.partial(jax.jit, static_argnames=("interpret",))
def int4_encode_pallas(x: jnp.ndarray, interpret: bool | None = None):
    """(N, D) fp32 -> (packed (N, D/2) uint8, scale (N, 1) fp32), fused."""
    if interpret is None:
        interpret = _use_interpret()
    n, d = x.shape
    t = _tile(n)
    grid = (n // t,)
    return pl.pallas_call(
        _encode_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((t, d), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((t, d // 2), lambda i: (i, 0)),
            pl.BlockSpec((t, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, d // 2), jnp.uint8),
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
        ],
        interpret=interpret,
    )(x.astype(jnp.float32))


@functools.partial(jax.jit, static_argnames=("interpret",))
def int4_decode_pallas(packed: jnp.ndarray, scale: jnp.ndarray,
                       interpret: bool | None = None) -> jnp.ndarray:
    """Inverse of :func:`int4_encode_pallas` -> (N, D) fp32."""
    if interpret is None:
        interpret = _use_interpret()
    n, dh = packed.shape
    t = _tile(n)
    return pl.pallas_call(
        _decode_kernel,
        grid=(n // t,),
        in_specs=[
            pl.BlockSpec((t, dh), lambda i: (i, 0)),
            pl.BlockSpec((t, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((t, dh * 2), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, dh * 2), jnp.float32),
        interpret=interpret,
    )(packed, scale)


def _int8_affine_encode_kernel(x_ref, q_ref, scale_ref, mn_ref):
    """Per-row affine int8: scale = (max-min)/255, zero-point from min."""
    x = x_ref[:]  # (T, D) fp32
    mn = jnp.min(x, axis=-1, keepdims=True)
    mx = jnp.max(x, axis=-1, keepdims=True)
    scale = (mx - mn) * jnp.float32(1.0 / 255.0)  # matches packing.py bit-for-bit
    safe = jnp.where(scale > 0, scale, 1.0)
    zp = jnp.round(-128.0 - mn / safe)
    q_ref[:] = jnp.clip(jnp.round(x / safe) + zp, -128, 127).astype(jnp.int8)
    scale_ref[:] = scale
    mn_ref[:] = mn


def _int8_affine_decode_kernel(q_ref, scale_ref, mn_ref, out_ref):
    scale, mn = scale_ref[:], mn_ref[:]
    safe = jnp.where(scale > 0, scale, 1.0)
    zp = jnp.round(-128.0 - mn / safe)
    deq = (q_ref[:].astype(jnp.float32) - zp) * safe
    out_ref[:] = jnp.where(scale > 0, deq, mn)


@functools.partial(jax.jit, static_argnames=("interpret",))
def int8_affine_encode_pallas(x: jnp.ndarray, interpret: bool | None = None):
    """(N, D) fp32 -> (q (N, D) int8, scale (N, 1) fp32, mn (N, 1) fp32)."""
    if interpret is None:
        interpret = _use_interpret()
    n, d = x.shape
    t = _tile(n)
    return pl.pallas_call(
        _int8_affine_encode_kernel,
        grid=(n // t,),
        in_specs=[pl.BlockSpec((t, d), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((t, d), lambda i: (i, 0)),
            pl.BlockSpec((t, 1), lambda i: (i, 0)),
            pl.BlockSpec((t, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, d), jnp.int8),
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
        ],
        interpret=interpret,
    )(x.astype(jnp.float32))


@functools.partial(jax.jit, static_argnames=("interpret",))
def int8_affine_decode_pallas(q: jnp.ndarray, scale: jnp.ndarray, mn: jnp.ndarray,
                              interpret: bool | None = None) -> jnp.ndarray:
    """Inverse of :func:`int8_affine_encode_pallas` -> (N, D) fp32."""
    if interpret is None:
        interpret = _use_interpret()
    n, d = q.shape
    t = _tile(n)
    return pl.pallas_call(
        _int8_affine_decode_kernel,
        grid=(n // t,),
        in_specs=[
            pl.BlockSpec((t, d), lambda i: (i, 0)),
            pl.BlockSpec((t, 1), lambda i: (i, 0)),
            pl.BlockSpec((t, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((t, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, d), jnp.float32),
        interpret=interpret,
    )(q, scale, mn)


# The scalar-scale int4 quantize core that once backed a selective_int4
# kernel twin was DELETED in round 5, on measurement: the codec is
# gather-bound, and XLA fuses the quantize into its
# gather consumers, so a pallas_call boundary can only break that fusion —
# the twin probed 0.97x (r4) and, split, encode 0.97x / decode 0.99x (r5) on
# the v5e. The in-kernel alternatives lose structurally: a VMEM row gather
# is sublane-granular (1-row copies waste 7/8 of the VPU), a one-hot-matmul
# gather multiplies traffic by k (3.8 GFLOP at the probe shape vs a ~19 MB
# bandwidth floor), and a scalar-prefetch DMA gather needs a B*S-step grid.
# An invperm-gather decode restructure was also measured (58-60 us vs the
# scatter path's 51-58) and rejected. The jnp codec IS the TPU-native
# implementation; the probe records this exclusion (tools/pallas_probe.py).


def _chan_int8_encode_kernel(x_ref, scale_ref, q_ref):
    """Per-channel symmetric int8 quantize with provided channel scales (1, D)."""
    q_ref[:] = jnp.round(x_ref[:] / scale_ref[:] * 127.0).astype(jnp.int8)


def _chan_int8_decode_kernel(q_ref, scale_ref, out_ref):
    # divide (not reciprocal-multiply): matches the jnp twin bit-for-bit
    out_ref[:] = q_ref[:].astype(jnp.float32) * scale_ref[:] / 127.0


@functools.partial(jax.jit, static_argnames=("interpret",))
def chan_int8_encode_pallas(x: jnp.ndarray, scale: jnp.ndarray,
                            interpret: bool | None = None) -> jnp.ndarray:
    """(N, D) fp32 + channel scales (1, D) -> int8 codes (N, D)."""
    if interpret is None:
        interpret = _use_interpret()
    n, d = x.shape
    t = _tile(n)
    return pl.pallas_call(
        _chan_int8_encode_kernel,
        grid=(n // t,),
        in_specs=[
            pl.BlockSpec((t, d), lambda i: (i, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((t, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, d), jnp.int8),
        interpret=interpret,
    )(x.astype(jnp.float32), scale.reshape(1, -1).astype(jnp.float32))


@functools.partial(jax.jit, static_argnames=("interpret",))
def chan_int8_decode_pallas(q: jnp.ndarray, scale: jnp.ndarray,
                            interpret: bool | None = None) -> jnp.ndarray:
    """Inverse of :func:`chan_int8_encode_pallas` -> (N, D) fp32."""
    if interpret is None:
        interpret = _use_interpret()
    n, d = q.shape
    t = _tile(n)
    return pl.pallas_call(
        _chan_int8_decode_kernel,
        grid=(n // t,),
        in_specs=[
            pl.BlockSpec((t, d), lambda i: (i, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((t, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, d), jnp.float32),
        interpret=interpret,
    )(q, scale.reshape(1, -1).astype(jnp.float32))


def _chan_int4_encode_kernel(x_ref, scale_ref, packed_ref):
    """Per-channel symmetric int4 quantize + nibble pack, channel scales (1, D).

    No clip: |x| <= channel max by construction, so codes land in [-7, 7]
    (mirrors the jnp twin ``packing._int4_per_channel`` bit-for-bit)."""
    x = x_ref[:]
    half = x.shape[-1] // 2
    codes = jnp.round(x / scale_ref[:] * 7.0).astype(jnp.int32) + 8
    packed_ref[:] = (codes[:, :half] | (codes[:, half:] << 4)).astype(jnp.uint8)


@functools.partial(jax.jit, static_argnames=("interpret",))
def chan_int4_encode_pallas(x: jnp.ndarray, scale: jnp.ndarray,
                            interpret: bool | None = None) -> jnp.ndarray:
    """(N, D) fp32 + channel scales (1, D) -> packed (N, D/2) uint8."""
    if interpret is None:
        interpret = _use_interpret()
    n, d = x.shape
    t = _tile(n)
    return pl.pallas_call(
        _chan_int4_encode_kernel,
        grid=(n // t,),
        in_specs=[
            pl.BlockSpec((t, d), lambda i: (i, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((t, d // 2), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, d // 2), jnp.uint8),
        interpret=interpret,
    )(x.astype(jnp.float32), scale.reshape(1, -1).astype(jnp.float32))


@functools.partial(jax.jit, static_argnames=("interpret",))
def chan_int4_decode_pallas(packed: jnp.ndarray, scale: jnp.ndarray,
                            interpret: bool | None = None) -> jnp.ndarray:
    """Inverse of :func:`chan_int4_encode_pallas` -> (N, D) fp32."""
    if interpret is None:
        interpret = _use_interpret()
    n, dh = packed.shape
    t = _tile(n)
    return pl.pallas_call(
        _decode_kernel,
        grid=(n // t,),
        in_specs=[
            pl.BlockSpec((t, dh), lambda i: (i, 0)),
            pl.BlockSpec((1, dh * 2), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((t, dh * 2), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, dh * 2), jnp.float32),
        interpret=interpret,
    )(packed, scale.reshape(1, -1).astype(jnp.float32))


def _ternary_encode_kernel(x_ref, scale_ref, packed_ref):
    """Ternary quantize + 2-bit pack with provided per-channel scales (1, D)."""
    x = x_ref[:]
    quarter = x.shape[-1] // 4
    codes = (jnp.clip(jnp.round(x / scale_ref[:]), -1, 1).astype(jnp.int32) + 1)
    packed_ref[:] = (codes[:, :quarter]
                     | (codes[:, quarter:2 * quarter] << 2)
                     | (codes[:, 2 * quarter:3 * quarter] << 4)
                     | (codes[:, 3 * quarter:] << 6)).astype(jnp.uint8)


def _ternary_decode_kernel(packed_ref, scale_ref, out_ref):
    packed = packed_ref[:].astype(jnp.int32)
    parts = [((packed >> (2 * i)) & 0x3) - 1 for i in range(4)]
    codes = jnp.concatenate(parts, axis=-1).astype(jnp.float32)
    out_ref[:] = codes * scale_ref[:]


@functools.partial(jax.jit, static_argnames=("interpret",))
def ternary_encode_pallas(x: jnp.ndarray, scale: jnp.ndarray,
                          interpret: bool | None = None) -> jnp.ndarray:
    """(N, D) fp32 + channel scales (1, D) -> packed (N, D/4) uint8."""
    if interpret is None:
        interpret = _use_interpret()
    n, d = x.shape
    t = _tile(n)
    return pl.pallas_call(
        _ternary_encode_kernel,
        grid=(n // t,),
        in_specs=[
            pl.BlockSpec((t, d), lambda i: (i, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((t, d // 4), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, d // 4), jnp.uint8),
        interpret=interpret,
    )(x.astype(jnp.float32), scale.reshape(1, -1).astype(jnp.float32))


@functools.partial(jax.jit, static_argnames=("interpret",))
def ternary_decode_pallas(packed: jnp.ndarray, scale: jnp.ndarray,
                          interpret: bool | None = None) -> jnp.ndarray:
    """Inverse of :func:`ternary_encode_pallas` -> (N, D) fp32."""
    if interpret is None:
        interpret = _use_interpret()
    n, dq = packed.shape
    t = _tile(n)
    return pl.pallas_call(
        _ternary_decode_kernel,
        grid=(n // t,),
        in_specs=[
            pl.BlockSpec((t, dq), lambda i: (i, 0)),
            pl.BlockSpec((1, dq * 4), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((t, dq * 4), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, dq * 4), jnp.float32),
        interpret=interpret,
    )(packed, scale.reshape(1, -1).astype(jnp.float32))


# ---------- WireCodec wrappers ----------


def pallas_wire_codec() -> WireCodec:
    """``int4_per_token`` wire codec backed by the fused Pallas kernels.

    Bit-identical payloads and reconstruction vs the jnp ``int4_per_token``
    codec (tested), usable as a split-runtime hop codec.
    """

    def encode(h):
        b, s, d = h.shape
        packed, scale = int4_encode_pallas(h.reshape(b * s, d))
        return {"packed": packed.reshape(b, s, d // 2),
                "scale": scale.reshape(b, s, 1)}

    def decode(p):
        b, s, dh = p["packed"].shape
        out = int4_decode_pallas(p["packed"].reshape(b * s, dh),
                                 p["scale"].reshape(b * s, 1))
        return out.reshape(b, s, dh * 2)

    return WireCodec("int4_per_token_pallas", encode, decode)


def pallas_int8_per_token() -> WireCodec:
    """``int8_per_token`` wire codec backed by the fused affine kernels."""

    def encode(h):
        b, s, d = h.shape
        q, scale, mn = int8_affine_encode_pallas(h.reshape(b * s, d))
        return {"q": q.reshape(b, s, d), "scale": scale.reshape(b, s, 1),
                "mn": mn.reshape(b, s, 1)}

    def decode(p):
        b, s, d = p["q"].shape
        out = int8_affine_decode_pallas(p["q"].reshape(b * s, d),
                                        p["scale"].reshape(b * s, 1),
                                        p["mn"].reshape(b * s, 1))
        return out.reshape(b, s, d)

    return WireCodec("int8_per_token_pallas", encode, decode)


def pallas_ternary(kind: str) -> WireCodec:
    """``ternary_mean`` / ``ternary_max`` with the quantize+pack fused; the
    (batch, seq) channel-scale reduction stays in XLA (a single fused reduce)."""

    def encode(h):
        b, s, d = h.shape
        if kind == "mean":
            scale = jnp.mean(h, axis=(0, 1), keepdims=True) + 1e-8
        else:
            cmax = jnp.max(jnp.abs(h), axis=(0, 1), keepdims=True)
            scale = jnp.where(cmax > 0, cmax, 1.0)
        packed = ternary_encode_pallas(h.reshape(b * s, d), scale.reshape(1, d))
        return {"packed": packed.reshape(b, s, d // 4), "scale": scale}

    def decode(p):
        b, s, dq = p["packed"].shape
        out = ternary_decode_pallas(p["packed"].reshape(b * s, dq),
                                    p["scale"].reshape(1, dq * 4))
        return out.reshape(b, s, dq * 4)

    return WireCodec(f"ternary_{kind}_pallas", encode, decode,
                     batch_invariant=False)


def pallas_per_channel(bits: int) -> WireCodec:
    """``int8_per_channel`` / ``int4_per_channel`` with the quantize(+pack)
    fused; the (batch, seq) channel abs-max reduction stays in XLA (one fused
    reduce). This is the reference's 896-iteration channel loop
    (``qwen_layer_wise.py:125-152``) as a single kernel pass.

    The int4 kernel earns its keep by fusing the nibble pack. The int8 kernel
    is a plain elementwise op XLA fuses equally well on its own — it exists so
    every quantizing hop codec has a kernel twin (uniform Pallas hop pipeline,
    BASELINE.json north star), not for a fusion win."""

    def encode(h):
        b, s, d = h.shape
        cmax = jnp.max(jnp.abs(h), axis=(0, 1), keepdims=True)
        safe = jnp.where(cmax > 0, cmax, 1.0)
        flat = h.reshape(b * s, d)
        if bits == 8:
            return {"q": chan_int8_encode_pallas(flat, safe.reshape(1, d))
                    .reshape(b, s, d), "scale": safe}
        return {"packed": chan_int4_encode_pallas(flat, safe.reshape(1, d))
                .reshape(b, s, d // 2), "scale": safe}

    def decode(p):
        if bits == 8:
            b, s, d = p["q"].shape
            out = chan_int8_decode_pallas(p["q"].reshape(b * s, d),
                                          p["scale"].reshape(1, d))
            return out.reshape(b, s, d)
        b, s, dh = p["packed"].shape
        out = chan_int4_decode_pallas(p["packed"].reshape(b * s, dh),
                                      p["scale"].reshape(1, dh * 2))
        return out.reshape(b, s, dh * 2)

    return WireCodec(f"int{bits}_per_channel_pallas", encode, decode,
                     batch_invariant=False)


#: Why there is NO ``pallas_selective_int4`` (deleted round 5; the full
#: measurement story sits where its quantize cores used to live, above
#: :func:`int4_decode_pallas`'s channel siblings): the selective codec is
#: gather-bound and its jnp implementation is the TPU-native one. The probe
#: embeds this string so the exclusion stays a recorded decision, not an
#: absence (``tools/pallas_probe.py``).
SELECTIVE_EXCLUSION = (
    "selective_int4 has no kernel twin BY MEASUREMENT (v5e, rounds 4-5): the "
    "codec is gather-bound; XLA fuses the int4 quantize into its gather "
    "consumers, so a pallas_call boundary only breaks that fusion (twin "
    "probed 0.97x roundtrip; split: encode 0.97x, decode 0.99x). In-kernel "
    "gathers lose structurally on TPU: VMEM row copies are sublane-granular, "
    "a one-hot-matmul gather multiplies traffic by k, a scalar-prefetch DMA "
    "gather needs a B*S-step grid. The jnp codec IS the TPU-native path.")


_PALLAS_FACTORIES = {
    "int4_per_token": pallas_wire_codec,
    "int8_per_token": pallas_int8_per_token,
    "int8_per_channel": lambda: pallas_per_channel(8),
    "int4_per_channel": lambda: pallas_per_channel(4),
    "ternary_mean": lambda: pallas_ternary("mean"),
    "ternary_max": lambda: pallas_ternary("max"),
}

#: NO-DATA FALLBACK for the substitution policy: base codecs whose fused
#: kernel beat the jnp/XLA path on the round-4/5 probe of a v5e
#: (differential-scan roundtrip, interleaved pairs, median-decided — single
#: runs swung +-30%; older code, not re-measured since). Round-4 decision
#: data (5 reps each): int4_per_token 1.33x (fuses the scale reduce +
#: quantize + nibble pack), int4_per_channel ~1.4x, ternary ~1.4x; EXCLUDED: int8_per_token 0.80x, int8_per_channel
#: ~0.92x — passes XLA already fuses into one bandwidth-bound sweep, where a
#: kernel only adds launch/layout overhead. The LIVE policy is the probe
#: cache (``codecs/probe_cache.py``): every bench's probe records each
#: codec's measured speedup keyed by chip fingerprint, and substitution
#: consults that first — this constant only decides when the current chip
#: has never been probed. Substitution must be EARNED — a default path
#: slower than doing nothing is worse than no kernel.
PALLAS_DEFAULT_WINS = frozenset({
    "int4_per_token", "int4_per_channel", "ternary_mean", "ternary_max"})


def default_substituted(base: str) -> bool:
    """The substitution policy for one base codec name: this chip's probe
    cache when it has data, the frozen fallback set when it does not."""
    from . import probe_cache

    win = probe_cache.measured_win(base)
    if win is None:
        return base in PALLAS_DEFAULT_WINS
    return win


def pallas_variant(codec: WireCodec, *, measured_wins_only: bool = False
                   ) -> Optional[WireCodec]:
    """The Pallas-backed twin of a jnp wire codec, or None when no fused kernel
    exists (identity casts — nothing to fuse). With ``measured_wins_only`` the
    twin is returned only when it is a probed on-silicon win for THIS chip
    (:func:`default_substituted`) — the TPU default-substitution policy;
    explicit ``*_pallas`` pins are always honored."""
    if codec.name.endswith("_pallas"):
        return codec
    if codec.name in _PALLAS_FACTORIES:
        if measured_wins_only and not default_substituted(codec.name):
            return None
        # the twins share the jnp codecs' pathological-input saturation, so
        # kernel/jnp payload parity holds on sanitized inputs too
        from .packing import _saturating

        return _saturating(_PALLAS_FACTORIES[codec.name]())
    # selective_int4: no kernel twin exists — a measured deletion, not a gap
    # (SELECTIVE_EXCLUSION); the jnp codec is returned-as-is by the runtimes'
    # `pallas_variant(c) or c` fallback on every path, including forced
    # EDGELLM_PALLAS=1 substitution
    return None


# ---------------------------------------------------------------------------
# Fused boundary hops: quantize -> seal -> transport in one shot
# ---------------------------------------------------------------------------
# A separate hop is five XLA ops (encode -> seal -> ppermute -> verify ->
# decode) and BENCH_r03/r04 show the packed payload paying an extra HBM
# round-trip before the collective (int8_per_token roundtrip 0.80x,
# int8_per_channel 0.91-0.94x vs the jnp twins). The fused family moves the
# quantize INTO the transport (EQuARX-style):
#
# - "wire" mode: encode + seal, then bitcast the whole sealed tree into ONE
#   flat uint8 buffer (codecs.wire_format.WireFormat) and cross the cut with
#   a single ppermute instead of one per payload leaf; the receiver slices
#   the buffer back, verifies, and decodes. Pure XLA + the existing Pallas
#   encode/decode kernels -- runs everywhere (CPU tests it in interpret
#   mode), and collapses per-leaf collective launches into one.
# - "remote" mode: one Pallas kernel per hop that quantizes each token tile
#   in VMEM and pltpu.make_async_remote_copy's it straight to the neighbor,
#   double-buffered so tile i's DMA overlaps tile i+1's quantize and tile
#   i-1's dequantize; the in-kernel checksum reproduces
#   wire_format._leaf_crc bit-for-bit, so the sealed bytes on the
#   interconnect are the SAME bytes the unfused ladder would have sent.
#   TPU-only (remote DMA has no interpret mode) and scoped to
#   REMOTE_CAPABLE codecs.
#
# Both modes decode the exact payload bytes the fallback would have decoded,
# so zero-fault fused hops are token-identical through generate_split; the
# plan gate (fused_hop_plan) refuses unless the win is forced or PROBED on
# this chip, and a refused gate leaves the pre-fusion graph byte-identical
# (the graphlint fused-disabled fingerprint contracts pin this).

#: base codecs a fused hop can carry: everything with a Pallas twin. The
#: exclusion of selective_int4 is measured, not incidental -- see
#: SELECTIVE_EXCLUSION (gather-bound, and its importance sidecar makes the
#: payload data-dependent, which the static wire layout can't carry).
FUSED_CAPABLE = frozenset(_PALLAS_FACTORIES)

#: base codecs with a single-kernel remote-DMA hop. int8_per_token first:
#: it is the default split hop codec AND the worst r03/r04 regression
#: (0.80x), i.e. the codec where only fusing the transport can win.
REMOTE_CAPABLE = frozenset({"int8_per_token"})


@dataclasses.dataclass(frozen=True)
class FusedHopPlan:
    """One hop's fused-transport decision (like ``kernel_plan``'s: a plan
    object you can log, not a bare bool). ``base`` is the probe-cache key
    (codec name sans ``_pallas``); ``reason`` records why the gate said yes
    so bench sidecars can carry the provenance."""

    mode: str    # "wire" | "remote"
    base: str
    reason: str


def _fused_base(codec) -> Optional[str]:
    name = getattr(codec, "name", None)
    if name is None:
        return None
    return name[:-len("_pallas")] if name.endswith("_pallas") else name


def fused_hop_plan(codec, *, link_active: bool = False,
                   backend: Optional[str] = None) -> Optional[FusedHopPlan]:
    """The gating ladder for one hop codec -> a plan, or None (= keep the
    separate encode/ppermute/decode ladder, byte-identical pre-fusion graph).

    1. ``EDGELLM_FUSED_HOP=0`` -- hard off (the fused-disabled identity
       contract traces this build against the default CPU build).
    2. An active FaultyLink owns the hop (retries, FEC framing, hedging,
       tiering) -- the fused kernel would bypass injection, so refuse.
    3. The base codec must be FUSED_CAPABLE and carry no importance sidecar.
    4. ``EDGELLM_FUSED_HOP=wire|remote`` forces a mode (remote only on TPU
       for a REMOTE_CAPABLE base -- it cannot even trace elsewhere);
       ``=1`` forces the best available mode.
    5. Default: the win must be EARNED -- TPU backend AND this chip's probe
       cache says ``fused_hop:<base>`` beat the separate ladder
       (``measured_win is True``; None means never probed -> refuse, same
       policy as kernel-twin substitution: a default path slower than doing
       nothing is worse than no fusion).
    """
    env = os.environ.get("EDGELLM_FUSED_HOP", "").strip().lower()
    if env == "0" or codec is None or link_active:
        return None
    base = _fused_base(codec)
    if base not in FUSED_CAPABLE or getattr(codec, "needs_importance", False):
        return None
    if backend is None:
        backend = jax.default_backend()
    remote_ok = backend == "tpu" and base in REMOTE_CAPABLE
    if env in ("wire", "remote"):
        if env == "remote" and not remote_ok:
            return None
        return FusedHopPlan(env, base, f"forced: EDGELLM_FUSED_HOP={env}")
    if env == "1":
        return FusedHopPlan("remote" if remote_ok else "wire",
                            base, "forced: EDGELLM_FUSED_HOP=1")
    if backend != "tpu":
        return None
    from . import probe_cache

    if probe_cache.measured_win(f"fused_hop:{base}") is not True:
        return None
    return FusedHopPlan("remote" if remote_ok else "wire", base,
                        "probe-cache measured win on this chip")


def fused_wire_hop(codec, hidden: jnp.ndarray, source: int, axis_name: str,
                   idx: jnp.ndarray) -> jnp.ndarray:
    """Fused "wire" hop ``source -> source+1``: encode, seal, flatten the
    sealed tree to ONE uint8 buffer, cross the cut with a single ppermute,
    then slice/verify/decode on the receiver. Same bytes, same seal, same
    checksum as the separate ladder (codecs.wire_format owns the layout) --
    just one collective launch per hop instead of one per payload leaf.

    The verify stays live in the graph: a corrupt arrival substitutes the
    receiver's own ``hidden`` (exactly what a zero-budget FaultyLink would
    do), so DCE can't silently drop the integrity check."""
    from .wire_format import WireFormat, seal_payload, verify_payload

    wf = WireFormat.for_codec(codec, hidden.shape, hidden.dtype)
    buf = wf.to_wire(seal_payload(codec.encode(hidden)))
    moved = jax.lax.ppermute(buf, axis_name, [(source, source + 1)])
    arrived = wf.from_wire(moved)
    ok = verify_payload(arrived)
    decoded = codec.decode(arrived["p"]).astype(hidden.dtype)
    return jnp.where(idx == source + 1,
                     jnp.where(ok, decoded, hidden), hidden)


# -- remote mode: the single-kernel quantize->DMA hop (int8 per-token) ------

_GOLD = 0x9E3779B1  # per-leaf checksum salt stride (wire_format)
_SALT_MN, _SALT_Q, _SALT_SCALE = 0, _GOLD, (2 * _GOLD) & 0xFFFFFFFF


def _sum_u32(x):
    """Wrapping uint32 sum. The TPU lowering has no unsigned reductions;
    two's-complement int32 addition wraps to the same bits."""
    total = jnp.sum(pltpu.bitcast(x, jnp.int32), keepdims=True)  # (1, 1)
    return pltpu.bitcast(total, jnp.uint32)[0, 0]


def _crc_f32_rows(vals, row0, salt: int):
    """In-kernel wire_format._leaf_crc for a (T, 1) f32 column whose rows sit
    at global offset ``row0``: little-endian byte k of row r weighs
    ``(2*(4r+k+salt)+1) * _CRC_MULT`` -- exact uint32 arithmetic."""
    from .wire_format import _CRC_MULT

    t = vals.shape[0]
    u = pltpu.bitcast(vals, jnp.uint32)
    rows = jax.lax.broadcasted_iota(jnp.uint32, (t, 1), 0) + row0
    crc = jnp.uint32(0)
    for k in range(4):
        pos = jnp.uint32(4) * rows + jnp.uint32(k) + jnp.uint32(salt)
        w = (jnp.uint32(2) * pos + jnp.uint32(1)) * jnp.uint32(_CRC_MULT)
        crc = crc + _sum_u32(((u >> jnp.uint32(8 * k)) & jnp.uint32(0xFF)) * w)
    return crc


def _crc_i8_tile(q, row0, salt: int):
    """In-kernel wire_format._leaf_crc for a (T, D) int8 tile at global row
    offset ``row0`` (one byte per element, row-major positions)."""
    from .wire_format import _CRC_MULT

    t, d = q.shape
    rows = jax.lax.broadcasted_iota(jnp.uint32, (t, d), 0) + row0
    cols = jax.lax.broadcasted_iota(jnp.uint32, (t, d), 1)
    pos = rows * jnp.uint32(d) + cols + jnp.uint32(salt)
    w = (jnp.uint32(2) * pos + jnp.uint32(1)) * jnp.uint32(_CRC_MULT)
    b = (q.astype(jnp.int32) & 0xFF).astype(jnp.uint32)
    return _sum_u32(b * w)


def _remote_hop_kernel(n_dev: int, n_tiles: int, axis_name: str,
                       x_ref, out_ref, ok_ref,
                       send_q, send_mn, send_scale, head_send,
                       recv_q, recv_mn, recv_scale, head_recv,
                       send_crc, recv_crc, send_sems, recv_sems, head_sems):
    """Grid step i of (n_tiles + 1): quantize token tile i into send slot
    i%2 and start its remote copies (overlapping the previous tile's DMA),
    then wait + dequantize tile i-1 from the recv slots; the final step
    ships the 8-byte head (canary + checksum) and verifies.

    Every device sends to its right neighbor (uniform SPMD ring -- the
    symmetric program is deadlock-free: step 0 has no waits, and step i's
    waits depend only on the left neighbor's step i sends). The receiver
    gate (``idx == source+1``) lives OUTSIDE the kernel, so off-path
    devices' arrivals are computed and ignored, trading one redundant
    neighbor transfer for a kernel with no data-dependent control flow."""
    from .wire_format import CANARY

    i = pl.program_id(0)
    t = send_q.shape[1]
    my = jax.lax.axis_index(axis_name)
    right = jax.lax.rem(my + 1, n_dev)
    left = jax.lax.rem(my + n_dev - 1, n_dev)
    slot = jax.lax.rem(i, 2)
    prev_slot = jax.lax.rem(i + 1, 2)

    def leaf_copy(leaf, src, dst, s):
        return pltpu.make_async_remote_copy(
            src_ref=src.at[s], dst_ref=dst.at[s],
            send_sem=send_sems.at[leaf, s], recv_sem=recv_sems.at[leaf, s],
            device_id={axis_name: right}, device_id_type=pltpu.DeviceIdType.MESH)

    @pl.when(i == 0)
    def _prologue():
        # neighborhood barrier: nobody DMAs until both neighbors entered
        # the kernel (their recv buffers exist); then zero the accumulators
        barrier = pltpu.get_barrier_semaphore()
        pltpu.semaphore_signal(barrier, inc=1, device_id={axis_name: left},
                               device_id_type=pltpu.DeviceIdType.MESH)
        pltpu.semaphore_signal(barrier, inc=1, device_id={axis_name: right},
                               device_id_type=pltpu.DeviceIdType.MESH)
        pltpu.semaphore_wait(barrier, 2)
        send_crc[0] = jnp.uint32(0)
        recv_crc[0] = jnp.uint32(0)

    @pl.when(jnp.logical_and(i >= 2, i < n_tiles))
    def _reclaim_slot():
        # tile i reuses tile i-2's send slot: drain those DMAs first
        for leaf in range(3):
            leaf_copy(leaf, (send_q, send_mn, send_scale)[leaf],
                      (recv_q, recv_mn, recv_scale)[leaf], slot).wait_send()

    @pl.when(i < n_tiles)
    def _quantize_and_send():
        # per-row affine int8 -- bit-for-bit _int8_affine_encode_kernel
        x = x_ref[:]
        mn = jnp.min(x, axis=-1, keepdims=True)
        mx = jnp.max(x, axis=-1, keepdims=True)
        scale = (mx - mn) * jnp.float32(1.0 / 255.0)
        safe = jnp.where(scale > 0, scale, 1.0)
        zp = jnp.round(-128.0 - mn / safe)
        q = jnp.clip(jnp.round(x / safe) + zp, -128, 127).astype(jnp.int8)
        send_q[slot] = q
        send_mn[slot] = mn
        send_scale[slot] = scale
        row0 = (i * t).astype(jnp.uint32)
        send_crc[0] = (send_crc[0]
                       + _crc_f32_rows(mn, row0, _SALT_MN)
                       + _crc_i8_tile(q, row0, _SALT_Q)
                       + _crc_f32_rows(scale, row0, _SALT_SCALE))
        for leaf, (src, dst) in enumerate(((send_q, recv_q),
                                           (send_mn, recv_mn),
                                           (send_scale, recv_scale))):
            leaf_copy(leaf, src, dst, slot).start()

    @pl.when(i >= 1)
    def _receive_and_decode():
        # tile i-1 has landed (or we block until the left neighbor sends it)
        for leaf in range(3):
            leaf_copy(leaf, (send_q, send_mn, send_scale)[leaf],
                      (recv_q, recv_mn, recv_scale)[leaf],
                      prev_slot).wait_recv()
        q = recv_q[prev_slot]
        mn = recv_mn[prev_slot]
        scale = recv_scale[prev_slot]
        row0 = ((i - 1) * t).astype(jnp.uint32)
        recv_crc[0] = (recv_crc[0]
                       + _crc_f32_rows(mn, row0, _SALT_MN)
                       + _crc_i8_tile(q, row0, _SALT_Q)
                       + _crc_f32_rows(scale, row0, _SALT_SCALE))
        # bit-for-bit _int8_affine_decode_kernel
        safe = jnp.where(scale > 0, scale, 1.0)
        zp = jnp.round(-128.0 - mn / safe)
        deq = (q.astype(jnp.float32) - zp) * safe
        out_ref[:] = jnp.where(scale > 0, deq, mn)

    @pl.when(i == n_tiles)
    def _finalize():
        # drain every send still in flight (kernel must not exit with live
        # DMAs): tiles n_tiles-1 and (when it exists) n_tiles-2
        for s in ((0, 1) if n_tiles >= 2 else (0,)):
            for leaf in range(3):
                leaf_copy(leaf, (send_q, send_mn, send_scale)[leaf],
                          (recv_q, recv_mn, recv_scale)[leaf], s).wait_send()
        # ship the 8-byte integrity head: [canary, crc] in the first two
        # lanes of a padded u32 vector (vector stores only -- no scalar
        # writes into VMEM)
        lane = jax.lax.broadcasted_iota(jnp.uint32, head_send.shape, 1)
        head_send[:] = jnp.where(
            lane == 0, jnp.uint32(CANARY),
            jnp.where(lane == 1, send_crc[0], jnp.uint32(0)))
        head = pltpu.make_async_remote_copy(
            src_ref=head_send, dst_ref=head_recv,
            send_sem=head_sems.at[0], recv_sem=head_sems.at[1],
            device_id={axis_name: right}, device_id_type=pltpu.DeviceIdType.MESH)
        head.start()
        head.wait_recv()
        got = jnp.where(lane < 2, head_recv[:], jnp.uint32(0))
        want = jnp.where(
            lane == 0, jnp.uint32(CANARY),
            jnp.where(lane == 1, recv_crc[0], jnp.uint32(0)))
        ok_ref[0] = jnp.all(got == want).astype(jnp.int32)
        head.wait_send()


def fused_remote_hop(codec, hidden: jnp.ndarray, source: int, axis_name: str,
                     idx: jnp.ndarray, *, n_dev: int) -> jnp.ndarray:
    """Fused "remote" hop: ONE Pallas kernel quantizes the activation tile
    by tile and remote-DMAs the sealed int8 payload straight to the right
    neighbor (uniform ring), double-buffered so each tile's send overlaps
    the next tile's quantize and the previous tile's dequantize. The bytes
    on the interconnect are exactly the wire-format sealed tree the unfused
    ladder would ppermute (same leaves, same checksum math), so the fused
    hop stays token-identical under zero faults. TPU-only; the plan gate
    (``fused_hop_plan``) guarantees this is never traced elsewhere.

    Status (PR 21, four v5e chips): it has never run. It traces and passes
    the Python-side TPU lowering, and Mosaic then refuses it — "Slice shape
    along dimension 2 must be aligned to tiling (128), but is 1": the
    per-slot slices of the (2, T, 1) minima/scale scratch. Reachable only by
    ``EDGELLM_FUSED_HOP=remote|1`` or a probe-cache key nothing writes;
    forcing it on a TPU raises."""
    from .packing import sanitize_hidden

    b, s_len, d = hidden.shape
    x = sanitize_hidden(hidden).astype(jnp.float32).reshape(b * s_len, d)
    n = b * s_len
    t = _tile(n)
    n_tiles = n // t

    grid = (n_tiles + 1,)
    kernel = functools.partial(_remote_hop_kernel, n_dev, n_tiles, axis_name)
    decoded, ok = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((t, d),
                               lambda i: (jnp.minimum(i, n_tiles - 1), 0))],
        out_specs=[
            pl.BlockSpec((t, d), lambda i: (jnp.maximum(i - 1, 0), 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, d), jnp.float32),
            jax.ShapeDtypeStruct((1,), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((2, t, d), jnp.int8),      # send_q
            pltpu.VMEM((2, t, 1), jnp.float32),   # send_mn
            pltpu.VMEM((2, t, 1), jnp.float32),   # send_scale
            pltpu.VMEM((1, 128), jnp.uint32),     # head_send
            pltpu.VMEM((2, t, d), jnp.int8),      # recv_q
            pltpu.VMEM((2, t, 1), jnp.float32),   # recv_mn
            pltpu.VMEM((2, t, 1), jnp.float32),   # recv_scale
            pltpu.VMEM((1, 128), jnp.uint32),     # head_recv
            pltpu.SMEM((1,), jnp.uint32),         # send_crc
            pltpu.SMEM((1,), jnp.uint32),         # recv_crc
            pltpu.SemaphoreType.DMA((3, 2)),      # send_sems (leaf, slot)
            pltpu.SemaphoreType.DMA((3, 2)),      # recv_sems
            pltpu.SemaphoreType.DMA((2,)),        # head send/recv
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), collective_id=0),
    )(x)
    decoded = decoded.reshape(b, s_len, d).astype(hidden.dtype)
    return jnp.where(idx == source + 1,
                     jnp.where(ok[0] != 0, decoded, hidden), hidden)


def fused_hop(plan: FusedHopPlan, codec, hidden: jnp.ndarray, source: int,
              axis_name: str, idx: jnp.ndarray, *, n_dev: int) -> jnp.ndarray:
    """Dispatch one planned fused hop (``fused_hop_plan`` decided the mode)."""
    if plan.mode == "remote":
        return fused_remote_hop(codec, hidden, source, axis_name, idx,
                                n_dev=n_dev)
    return fused_wire_hop(codec, hidden, source, axis_name, idx)
