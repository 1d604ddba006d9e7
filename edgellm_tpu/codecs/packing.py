"""Packed wire codecs: the bytes that actually cross the device boundary.

The reference never packs anything — its quantization is an in-place fp
quantize->dequantize and its compression claims are analytic bit counts
(SURVEY.md section 5, ``BASELINE.md``). Here every codec has a real packed
representation: ``encode`` produces integer payload buffers (int4 nibbles packed
two-per-byte, ternary codes four-per-byte) plus fp scales, ``decode`` inverts the
packing, and ``payload_bytes`` is measured from the buffers that cross
``lax.ppermute`` in the split runtime — not asserted.

Numerical contract: for every codec, ``decode(encode(x))`` equals the matching
*simulate* codec's quantize->dequantize output exactly (tested), so a split run
with a wire codec reproduces the reference's simulated-quantization perplexities
while moving real compressed bytes.
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np


def pack_int4(codes: jnp.ndarray) -> jnp.ndarray:
    """Pack int4 codes in [-8, 7] (last axis even) into uint8, two per byte.

    Wire layout: element i pairs with element i + D/2 (low nibble = first half,
    high nibble = second half). Contiguous-half pairing keeps the packing a pair
    of full-lane slices on TPU (the interleaved 0::2/1::2 layout would be a
    strided lane access) — the Pallas kernels share this convention.
    """
    half = codes.shape[-1] // 2
    u = (codes.astype(jnp.int32) + 8).astype(jnp.uint8)  # [0, 15]
    return u[..., :half] | (u[..., half:] << 4)


def unpack_int4(packed: jnp.ndarray) -> jnp.ndarray:
    """Inverse of :func:`pack_int4` -> int8 codes in [-8, 7]."""
    lo = (packed & 0xF).astype(jnp.int8) - 8
    hi = (packed >> 4).astype(jnp.int8) - 8
    return jnp.concatenate([lo, hi], axis=-1)


def pack_ternary(codes: jnp.ndarray) -> jnp.ndarray:
    """Pack ternary codes in {-1, 0, 1} (last axis % 4 == 0) into uint8, four per
    byte. Same contiguous-quarter pairing as :func:`pack_int4`."""
    quarter = codes.shape[-1] // 4
    u = (codes.astype(jnp.int32) + 1).astype(jnp.uint8)  # [0, 2], 2 bits each
    parts = [u[..., i * quarter:(i + 1) * quarter] for i in range(4)]
    return parts[0] | (parts[1] << 2) | (parts[2] << 4) | (parts[3] << 6)


def unpack_ternary(packed: jnp.ndarray) -> jnp.ndarray:
    """Inverse of :func:`pack_ternary` -> int8 codes in {-1, 0, 1}."""
    parts = [((packed >> (2 * i)) & 0x3).astype(jnp.int8) - 1 for i in range(4)]
    return jnp.concatenate(parts, axis=-1)


def _nbytes(tree) -> int:
    return int(sum(np.prod(a.shape) * a.dtype.itemsize
                   for a in jax.tree_util.tree_leaves(tree)))


#: saturation bound for pathological encoder inputs; well inside fp32 range so
#: downstream scale arithmetic (division, reciprocal-multiply) stays finite
SATURATE_MAG = 1e30


def sanitize_hidden(h: jnp.ndarray, max_mag: float = SATURATE_MAG) -> jnp.ndarray:
    """Deterministic saturation of pathological activations before encoding:
    NaN -> 0, +-Inf and magnitudes beyond ``max_mag`` clamp to ``+-max_mag``.
    A bit-exact identity for ordinary finite inputs (clip and a false-predicate
    where both return x unchanged), so codec parity with the simulate path is
    untouched — but no wire codec ever turns a poisoned activation into silent
    garbage bytes: every payload decodes to something finite."""
    h = jnp.clip(h, -max_mag, max_mag)  # NaN propagates through clip...
    return jnp.where(jnp.isnan(h), jnp.zeros_like(h), h)  # ...and lands here


def _saturating(codec: "WireCodec", max_mag: float = SATURATE_MAG) -> "WireCodec":
    """Wrap a codec's encode with :func:`sanitize_hidden` (identity for finite
    inputs). Every registry codec and every Pallas twin passes through this."""
    enc = codec.encode
    if codec.needs_importance:
        def wrapped(h, importance):
            return enc(sanitize_hidden(h, max_mag), importance)
    else:
        def wrapped(h):
            return enc(sanitize_hidden(h, max_mag))
    return dataclasses.replace(codec, encode=wrapped)


@dataclasses.dataclass(frozen=True)
class WireCodec:
    """One boundary codec: ``encode(hidden) -> payload`` (pytree of arrays that
    cross the wire), ``decode(payload) -> hidden``. ``payload_bytes`` measures the
    encoded size of one (B, S, D) activation.

    ``batch_invariant``: True when encode/decode treat batch rows independently
    (per-token codecs, identity casts). Codecs whose scales reduce over the batch
    or sequence axes (global / per-channel) are NOT safe under data-parallel
    sharding of the batch axis — each shard would compute a different scale than
    a single-device run; the split runtime rejects that combination."""

    name: str
    encode: Callable
    decode: Callable
    batch_invariant: bool = True
    #: True when ``encode`` takes (hidden, importance) — the split runtime must
    #: supply a per-hop importance vector (token-selective mixed precision)
    needs_importance: bool = False

    def payload_bytes(self, hidden_shape, dtype=jnp.float32) -> int:
        spec = jax.ShapeDtypeStruct(hidden_shape, dtype)
        if self.needs_importance:
            # batch > 1 implies per-row importance (per-row ordering/scale wire
            # format — the low-index side channel is B x k, not k)
            b, s = hidden_shape[0], hidden_shape[1]
            imp = jax.ShapeDtypeStruct((s,) if b == 1 else (b, s), jnp.float32)
            return _nbytes(jax.eval_shape(self.encode, spec, imp))
        return _nbytes(jax.eval_shape(self.encode, spec))


def _identity_codec(name: str, dtype) -> WireCodec:
    # saturate to the WIRE dtype's own range (fp16 overflows far below
    # SATURATE_MAG), so a huge input crosses as the dtype max, never as Inf
    max_mag = min(SATURATE_MAG, float(jnp.finfo(dtype).max))
    return _saturating(WireCodec(
        name=name,
        encode=lambda h: {"x": h.astype(dtype)},
        decode=lambda p: p["x"].astype(jnp.float32),
    ), max_mag)


def _int8_per_token() -> WireCodec:
    """Per-token affine int8: D bytes + 2 fp32 scalars (scale, min) per token
    (the intent of ``pythia_model.py:57-68``). The zero-point is recomputed from
    (scale, min) on the decode side; constant tokens (scale == 0) reconstruct to
    exactly ``min`` — matching the simulate codec's pass-through."""

    def encode(h):
        mn = jnp.min(h, axis=-1, keepdims=True)
        mx = jnp.max(h, axis=-1, keepdims=True)
        # multiply by the fp32 reciprocal rather than divide: a constant divide
        # is strength-reduced differently under jit vs eager (1-ulp drift), and
        # the Pallas twin must produce bit-identical scales
        scale = (mx - mn) * jnp.float32(1.0 / 255.0)
        safe = jnp.where(scale > 0, scale, 1.0)
        zp = jnp.round(-128.0 - mn / safe)
        q = jnp.clip(jnp.round(h / safe) + zp, -128, 127).astype(jnp.int8)
        return {"q": q, "scale": scale, "mn": mn}

    def decode(p):
        safe = jnp.where(p["scale"] > 0, p["scale"], 1.0)
        zp = jnp.round(-128.0 - p["mn"] / safe)
        deq = (p["q"].astype(jnp.float32) - zp) * safe
        return jnp.where(p["scale"] > 0, deq, p["mn"])

    return WireCodec("int8_per_token", encode, decode)


def _int4_global() -> WireCodec:
    """Symmetric int4 with one global max-abs scale — the packed twin of the
    reference's headline simulated codec (``qwen_layer_wise.py:58-70``)."""

    def encode(h):
        max_val = jnp.max(jnp.abs(h))
        safe = jnp.where(max_val > 0, max_val, 1.0)
        codes = jnp.round(jnp.clip(h / safe * 7.0, -8.0, 7.0)).astype(jnp.int8)
        return {"packed": pack_int4(codes), "scale": safe[None]}

    def decode(p):
        return unpack_int4(p["packed"]).astype(jnp.float32) / 7.0 * p["scale"][0]

    return WireCodec("int4_global", encode, decode, batch_invariant=False)


def _int4_per_token() -> WireCodec:
    """Symmetric int4, one max-abs scale per token (D/2 bytes + 4 per token)."""

    def encode(h):
        max_val = jnp.max(jnp.abs(h), axis=-1, keepdims=True)
        safe = jnp.where(max_val > 0, max_val, 1.0)
        codes = jnp.round(jnp.clip(h / safe * 7.0, -8.0, 7.0)).astype(jnp.int8)
        return {"packed": pack_int4(codes), "scale": safe}

    def decode(p):
        return unpack_int4(p["packed"]).astype(jnp.float32) / 7.0 * p["scale"]

    return WireCodec("int4_per_token", encode, decode)


def _ternary(kind: str) -> WireCodec:
    """Per-channel ternary (packed twin of ``channel_1_mean`` / ``channel_1_max``,
    ``qwen_layer_wise.py:135-150``): D/4 bytes per token + D fp32 channel scales."""

    def encode(h):
        if kind == "mean":
            scale = jnp.mean(h, axis=(0, 1), keepdims=True) + 1e-8
            codes = jnp.clip(jnp.round(h / scale), -1, 1).astype(jnp.int8)
        else:
            cmax = jnp.max(jnp.abs(h), axis=(0, 1), keepdims=True)
            scale = jnp.where(cmax > 0, cmax, 1.0)
            codes = jnp.clip(jnp.round(h / scale), -1, 1).astype(jnp.int8)
        return {"packed": pack_ternary(codes), "scale": scale}

    def decode(p):
        return unpack_ternary(p["packed"]).astype(jnp.float32) * p["scale"]

    return WireCodec(f"ternary_{kind}", encode, decode, batch_invariant=False)


def _ternary_per_token() -> WireCodec:
    """Per-token symmetric ternary: D/4 packed crumbs + one fp32 max-abs scale
    per token. The degradation ladder's floor tier (``codecs.faults``): unlike
    the per-channel ternary codecs its scale reduces only over the feature
    axis, so it is batch-invariant — legal under data parallelism and the
    stage x seq runtime, and usable for single-token decode hops."""

    def encode(h):
        mx = jnp.max(jnp.abs(h), axis=-1, keepdims=True)
        scale = jnp.where(mx > 0, mx, 1.0)
        codes = jnp.clip(jnp.round(h / scale), -1, 1).astype(jnp.int8)
        return {"packed": pack_ternary(codes), "scale": scale}

    def decode(p):
        return unpack_ternary(p["packed"]).astype(jnp.float32) * p["scale"]

    return WireCodec("ternary_per_token", encode, decode)


def _int8_per_channel() -> WireCodec:
    """Per-channel symmetric int8 (packed twin of ``channel_8``)."""

    def encode(h):
        # an all-zero channel encodes to zero codes and decodes to exactly zero,
        # so no zero-channel sidecar is needed
        cmax = jnp.max(jnp.abs(h), axis=(0, 1), keepdims=True)
        safe = jnp.where(cmax > 0, cmax, 1.0)
        codes = jnp.round(h / safe * 127.0).astype(jnp.int8)
        return {"q": codes, "scale": safe}

    def decode(p):
        return p["q"].astype(jnp.float32) * p["scale"] / 127.0

    return WireCodec("int8_per_channel", encode, decode, batch_invariant=False)


def _int4_per_channel() -> WireCodec:
    """Per-channel symmetric int4 (packed twin of ``channel_4``)."""

    def encode(h):
        cmax = jnp.max(jnp.abs(h), axis=(0, 1), keepdims=True)
        safe = jnp.where(cmax > 0, cmax, 1.0)
        codes = jnp.round(h / safe * 7.0).astype(jnp.int8)
        return {"packed": pack_int4(codes), "scale": safe}

    def decode(p):
        return unpack_int4(p["packed"]).astype(jnp.float32) * p["scale"] / 7.0

    return WireCodec("int4_per_channel", encode, decode, batch_invariant=False)


def _jnp_quant_pack(low: jnp.ndarray, safe: jnp.ndarray) -> jnp.ndarray:
    """(B, k, D) fp32 + global scale -> packed (B, k, D/2) int4 nibbles."""
    codes = jnp.round(jnp.clip(low / safe * 7.0, -8.0, 7.0)).astype(jnp.int8)
    return pack_int4(codes)


def _jnp_unpack_dequant(packed: jnp.ndarray, safe: jnp.ndarray) -> jnp.ndarray:
    return unpack_int4(packed).astype(jnp.float32) / 7.0 * safe


def _local_selective_scale(low, nonempty: bool, per_row: bool):
    """Default int4 scale for the selective codec: max|low| with the zero /
    empty-k guard. ``nonempty`` is the static ``k > 0``."""
    if per_row:
        mx = (jnp.max(jnp.abs(low), axis=(1, 2)) if nonempty
              else jnp.zeros((low.shape[0],), jnp.float32))
    else:
        mx = jnp.max(jnp.abs(low)) if nonempty else jnp.asarray(0.0)
    return jnp.where(mx > 0, mx, 1.0)


def selective_int4(ratio: float, high: str = "bf16", *,
                   quant_pack=None, unpack_dequant=None, scale_fn=None,
                   name_suffix: str = "") -> WireCodec:
    """Token-selective mixed-precision boundary codec (BASELINE.json configs[2]).

    The reference's headline scheme: the ``ratio`` least-important tokens cross
    as symmetric int4 with one global scale over the selected slice
    (``qwen_layer_wise.py:54-70``), the remaining tokens cross at ``high``
    precision (fp16/bf16 is the reference's notional transfer baseline, fp32 is
    bit-exact vs the in-place simulation). The wire carries two COMPACTED
    buffers — ``k = floor(ratio*S)`` is static, so the low/high split has static
    shapes — plus the side channel needed to reassemble on the far side: ONLY
    the ``k`` low-token indices, as int16 (S <= 32767). The high tokens are
    shipped in position-ascending order, so their placement is derived on the
    decode side as the sorted complement of the low-index set — no full
    permutation crosses the wire (2k bytes vs the naive 4S; the reference's
    analytic byte counts ignore the side channel entirely, the measured
    ``payload_bytes`` here does not).

    ``encode(hidden, importance)``; the split runtime threads the importance
    vector to importance-carrying hops. ``importance`` may be a shared (S,)
    vector (the reference's batch-1 shape — wire format unchanged) or per-row
    (B, S): each evaluation window then carries its OWN ordering and scale,
    exactly as the reference selects per window at batch 1
    (``Qwen2-0.5B/main.py:161-165``), which is what makes this codec usable
    under data-parallel window batching.

    ``quant_pack(low, scale)`` / ``unpack_dequant(packed, scale)`` override the
    int4 compute core (the Pallas wrapper passes its fused kernels; the wire
    format and all selection/reassembly logic stay in this one definition).
    ``scale`` arrives as a scalar (shared path) or (B, 1, 1) (per-row path).
    ``scale_fn(low, nonempty, per_row)`` overrides the scale reduction (the
    ring-sharded local mode passes a ``pmax``-agreed global scale).
    """
    if not 0.0 <= ratio <= 1.0:
        raise ValueError(f"ratio must be in [0, 1], got {ratio}")
    high_dtype = {"fp32": jnp.float32, "bf16": jnp.bfloat16, "fp16": jnp.float16}[high]
    quant_pack = quant_pack or _jnp_quant_pack
    unpack_dequant = unpack_dequant or _jnp_unpack_dequant
    scale_fn = scale_fn or _local_selective_scale

    def encode(h, importance):
        b, s, d = h.shape
        if s > 32767:
            raise ValueError(f"selective_int4 int16 index side channel needs "
                             f"S <= 32767, got {s}")
        k = int(ratio * s)
        importance = jnp.asarray(importance)
        if importance.ndim == 2:  # per-row ordering + scale
            order = jnp.argsort(importance, axis=-1)  # (B, S), ascending
            rows = jnp.arange(b)[:, None]
            low = h[rows, order[:, :k]]  # (B, k, D)
            safe = scale_fn(low, k > 0, True)  # (B,)
            # high tokens ship position-ascending: their placement is implied
            # by the low-index set, so only the k low indices cross the wire
            high_pos = jnp.sort(order[:, k:], axis=-1)
            return {
                "low": (quant_pack(low, safe[:, None, None]) if k
                        else jnp.zeros((b, 0, d // 2), jnp.uint8)),
                "scale": safe,
                "high": h[rows, high_pos].astype(high_dtype),
                "order": order[:, :k].astype(jnp.int16),
            }
        order = jnp.argsort(importance)  # ascending, stable — least important first
        low_idx = order[:k]
        high_pos = jnp.sort(order[k:])  # position-ascending (see per-row note)
        low = jnp.take(h, low_idx, axis=1)  # (B, k, D)
        safe = scale_fn(low, k > 0, False)
        return {
            "low": quant_pack(low, safe) if k else jnp.zeros((b, 0, d // 2), jnp.uint8),
            "scale": safe[None],
            "high": jnp.take(h, high_pos, axis=1).astype(high_dtype),
            "order": low_idx.astype(jnp.int16),
        }

    def decode(p):
        b = p["high"].shape[0]
        k = p["low"].shape[1]
        d = p["low"].shape[2] * 2 if k else p["high"].shape[2]
        s = k + p["high"].shape[1]
        out = jnp.zeros((b, s, d), jnp.float32)
        if p["order"].ndim == 2:  # per-row
            low_idx = p["order"].astype(jnp.int32)  # (B, k)
            rows = jnp.arange(b)[:, None]
            mask = jnp.ones((b, s), bool).at[rows, low_idx].set(False)
            high_pos = jax.vmap(lambda m: jnp.nonzero(m, size=s - k)[0])(mask)
            low = unpack_dequant(p["low"], p["scale"][:, None, None]) \
                if k else jnp.zeros((b, 0, d), jnp.float32)
            out = out.at[rows, low_idx].set(low)
            return out.at[rows, high_pos].set(p["high"].astype(jnp.float32))
        low_idx = p["order"].astype(jnp.int32)  # (k,)
        mask = jnp.ones((s,), bool).at[low_idx].set(False)
        high_pos = jnp.nonzero(mask, size=s - k)[0]  # sorted complement
        low = unpack_dequant(p["low"], p["scale"][0]) \
            if k else jnp.zeros((b, 0, d), jnp.float32)
        out = out.at[:, low_idx, :].set(low)
        return out.at[:, high_pos, :].set(p["high"].astype(jnp.float32))

    # high tokens cross at `high` precision: saturate to THAT dtype's range
    return _saturating(
        WireCodec(f"selective_int4_r{ratio}_{high}{name_suffix}", encode, decode,
                  batch_invariant=False, needs_importance=True),
        min(SATURATE_MAG, float(jnp.finfo(high_dtype).max)))


def _pallas(base_name: str) -> Callable[[], WireCodec]:
    """Lazy factory for a Pallas-backed codec (pallas_kernels imports this
    module, so the import must happen at call time)."""

    def factory() -> WireCodec:
        from .pallas_kernels import pallas_twin

        return pallas_twin(base_name)

    return factory


def get_wire_codec(name: str) -> WireCodec:
    """Codec registry. Names map to the reference's boundary compression schemes
    (fp16 is its notional uncompressed transfer baseline, BASELINE.md). The
    ``*_pallas`` names are the kernel twins as codecs of their own; on a TPU
    the split runtime runs a base codec as its twin where one exists
    (``pallas_kernels.pallas_variant``)."""
    # identity codecs, selective_int4, and the Pallas twins sanitize inside
    # their own factories (dtype-specific bounds / shared twin path); the
    # quantizing jnp codecs are wrapped here
    factories = {
        "fp32": lambda: _identity_codec("fp32", jnp.float32),
        "bf16": lambda: _identity_codec("bf16", jnp.bfloat16),
        "fp16": lambda: _identity_codec("fp16", jnp.float16),
        "int8_per_token": lambda: _saturating(_int8_per_token()),
        "int8_per_channel": lambda: _saturating(_int8_per_channel()),
        "int4_global": lambda: _saturating(_int4_global()),
        "int4_per_token": lambda: _saturating(_int4_per_token()),
        "int4_per_channel": lambda: _saturating(_int4_per_channel()),
        "ternary_mean": lambda: _saturating(_ternary("mean")),
        "ternary_max": lambda: _saturating(_ternary("max")),
        "ternary_per_token": lambda: _saturating(_ternary_per_token()),
        "int4_per_token_pallas": _pallas("int4_per_token"),
        "int4_per_channel_pallas": _pallas("int4_per_channel"),
        "ternary_mean_pallas": _pallas("ternary_mean"),
        "ternary_max_pallas": _pallas("ternary_max"),
    }
    if name not in factories:
        raise ValueError(f"unknown wire codec {name!r}; options: {sorted(factories)}")
    return factories[name]()


WIRE_CODECS = ("fp32", "bf16", "fp16", "int8_per_token", "int8_per_channel",
               "int4_global", "int4_per_token", "int4_per_channel",
               "ternary_mean", "ternary_max", "ternary_per_token",
               "int4_per_token_pallas", "int4_per_channel_pallas",
               "ternary_mean_pallas", "ternary_max_pallas")
