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
- channel-scale ternary quantize+pack (``ternary_mean`` / ``ternary_max``;
  the (B,S) channel-scale reduction stays in XLA);
- channel-scale int4 quantize+pack (``int4_per_channel`` — the reference's
  896-channel Python loop as one pass).

The int8 codecs have no twin: quantizing to int8 packs nothing, and no
default ever picked the twins they once had (ROADMAP S7). ``selective_int4``
deliberately has NO kernel twin either — a measured round-5 deletion, not a
gap: the codec is gather-bound and XLA fuses the quantize into the gather
chain, so the twin could only lose (``SELECTIVE_EXCLUSION`` carries the
numbers; the probe records it).

``pallas_wire_codec`` / ``pallas_int4_per_channel`` / ``pallas_ternary`` wrap
these in the :class:`~edgellm_tpu.codecs.packing.WireCodec` interface;
``pallas_variant`` is the one place a hop's codec is chosen: on a TPU a base
codec the table below holds runs as its twin, everything else as itself.
"""
from __future__ import annotations

import functools
from typing import Optional

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


def pallas_int4_per_channel() -> WireCodec:
    """``int4_per_channel`` with the quantize + nibble pack fused; the
    (batch, seq) channel abs-max reduction stays in XLA (one fused reduce).
    This is the reference's 896-iteration channel loop
    (``qwen_layer_wise.py:125-152``) as a single kernel pass."""

    def encode(h):
        b, s, d = h.shape
        cmax = jnp.max(jnp.abs(h), axis=(0, 1), keepdims=True)
        safe = jnp.where(cmax > 0, cmax, 1.0)
        packed = chan_int4_encode_pallas(h.reshape(b * s, d), safe.reshape(1, d))
        return {"packed": packed.reshape(b, s, d // 2), "scale": safe}

    def decode(p):
        b, s, dh = p["packed"].shape
        out = chan_int4_decode_pallas(p["packed"].reshape(b * s, dh),
                                      p["scale"].reshape(1, dh * 2))
        return out.reshape(b, s, dh * 2)

    return WireCodec("int4_per_channel_pallas", encode, decode,
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


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


#: The kernel twins, by the base codec each stands in for. The table IS the
#: substitution policy: these four have been substituted on a TPU since round
#: 4; no cell's hop is large enough to time them (ROADMAP S7).
_PALLAS_TWINS = {
    "int4_per_token": pallas_wire_codec,
    "int4_per_channel": pallas_int4_per_channel,
    "ternary_mean": lambda: pallas_ternary("mean"),
    "ternary_max": lambda: pallas_ternary("max"),
}


def pallas_twin(base: str) -> WireCodec:
    """The kernel twin of base codec ``base``, on any backend: what the
    registry builds for an explicit ``<base>_pallas`` name."""
    # the twins share the jnp codecs' pathological-input saturation, so
    # kernel/jnp payload parity holds on sanitized inputs too
    from .packing import _saturating

    return _saturating(_PALLAS_TWINS[base]())


def pallas_variant(codec: WireCodec) -> Optional[WireCodec]:
    """The codec a hop asked for ``codec`` runs in its place, or None to run
    ``codec`` itself: its kernel twin on a TPU where the table holds one. An
    explicit ``*_pallas`` name is a codec, not a switch: the table does not
    hold it, so it runs as itself everywhere. ``selective_int4`` has no twin
    (``SELECTIVE_EXCLUSION``)."""
    if _on_tpu() and codec.name in _PALLAS_TWINS:
        return pallas_twin(codec.name)
    return None
