"""The boundary wire format: what bytes actually cross a cut.

The wire layout lives in one place so every hop implementation — the
separate encode/``ppermute``/decode ladder, the faulty link, FEC parity
framing — moves the *same bytes* in the *same order*:

- :func:`seal_payload` / :func:`verify_payload` / :func:`payload_checksum`:
  the 8-byte integrity sidecar (canary word + weighted-byte checksum) sealed
  next to every payload pytree. The per-byte weights are odd
  (``(2i+1) * Knuth``), and an odd weight is invertible mod 2**32 — so any
  single corrupted byte always changes the sum; a dropped payload zeroes the
  canary. (``codecs.faults`` re-exports them.)
- :func:`flatten_bytes` / :func:`unflatten_bytes`: every leaf's bytes
  bitcast to uint8 and concatenated in tree-flatten order, and the inverse
  against a template tree (static slices — shapes/dtypes are trace-time
  constants): the byte order FEC chunking stands on.
- :class:`WireFormat`: the layout of one hop's flat wire buffer for a given
  (codec, activation shape): ``[canary u32][crc u32][payload leaves in
  tree-flatten order]``, with static byte accounting (``wire_nbytes ==
  payload bytes + 8``).

Because the seal word, checksum, and byte order live here, fault injection
(:func:`~edgellm_tpu.codecs.faults.inject_faults` corrupting the flat
buffer), FEC repair (chunking the same stream) and hedging interoperate: a
flat buffer round-trips through ``WireFormat.from_wire`` into the exact
sealed tree the ladder builds.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import numpy as np
import jax
import jax.numpy as jnp

#: canary word sealed next to every payload; a dropped hop arrives all-zero
#: and fails this check even when the zeroed payload's checksum is trivially 0
CANARY = 0x5EA1C0DE

#: Knuth's multiplicative-hash constant; ``(2i+1) * _CRC_MULT`` gives every
#: byte position a distinct ODD weight mod 2**32 (odd => invertible => any
#: single-byte change always moves the checksum)
_CRC_MULT = 2654435761


def tree_nbytes(tree: Any) -> int:
    """Static byte size of a payload pytree (shapes/dtypes are trace-time
    constants, so the byte-budget comparison is a python bool under jit)."""
    return int(sum(int(np.prod(a.shape)) * a.dtype.itemsize
                   for a in jax.tree_util.tree_leaves(tree)))


def _leaf_crc(leaf, salt: int):
    """Weighted byte sum of one leaf in uint32. Weights are odd (see
    _CRC_MULT), so flipping any single byte always changes the sum."""
    b = jax.lax.bitcast_convert_type(leaf, jnp.uint8).reshape(-1)
    if b.size == 0:
        return jnp.uint32(0)
    i = jnp.arange(b.size, dtype=jnp.uint32) + jnp.uint32(salt & 0xFFFFFFFF)
    w = (jnp.uint32(2) * i + jnp.uint32(1)) * jnp.uint32(_CRC_MULT)
    return jnp.sum(b.astype(jnp.uint32) * w, dtype=jnp.uint32)


def payload_checksum(payload: Any) -> jnp.ndarray:
    """uint32 checksum over every byte of every leaf; the per-leaf salt keys
    the positional weights so leaves can't trade bytes."""
    crc = jnp.uint32(0)
    for j, leaf in enumerate(jax.tree_util.tree_leaves(payload)):
        crc = crc + _leaf_crc(leaf, j * 0x9E3779B1)
    return crc


def seal_payload(payload: Any) -> dict:
    """Wrap a codec payload with its integrity sidecar (8 bytes: canary +
    checksum) — the tree that actually crosses the wire under faults."""
    return {"canary": jnp.full((1,), CANARY, jnp.uint32),
            "crc": payload_checksum(payload)[None],
            "p": payload}


def verify_payload(sealed: dict) -> jnp.ndarray:
    """Scalar bool: the arrived payload is intact (canary alive AND checksum
    matches a fresh computation over the arrived bytes)."""
    return jnp.logical_and(sealed["canary"][0] == jnp.uint32(CANARY),
                           payload_checksum(sealed["p"]) == sealed["crc"][0])


def flatten_bytes(tree: Any) -> jnp.ndarray:
    """Every leaf's bytes, concatenated in tree-flatten order -> (N,) uint8."""
    parts = []
    for leaf in jax.tree_util.tree_leaves(tree):
        parts.append(jax.lax.bitcast_convert_type(leaf, jnp.uint8).reshape(-1))
    return jnp.concatenate(parts) if parts else jnp.zeros((0,), jnp.uint8)


def unflatten_bytes(stream: jnp.ndarray, like: Any) -> Any:
    """Inverse of :func:`flatten_bytes` against a template tree (shapes and
    dtypes are trace-time constants, so every slice is static)."""
    leaves, treedef = jax.tree_util.tree_flatten(like)
    out, off = [], 0
    for leaf in leaves:
        itemsize = leaf.dtype.itemsize
        n = leaf.size * itemsize
        b = stream[off:off + n]
        off += n
        if itemsize == 1:
            x = jax.lax.bitcast_convert_type(b, leaf.dtype)
        else:
            x = jax.lax.bitcast_convert_type(b.reshape(-1, itemsize),
                                             leaf.dtype)
        out.append(x.reshape(leaf.shape))
    return jax.tree_util.tree_unflatten(treedef, out)


@dataclasses.dataclass(frozen=True)
class WireFormat:
    """The flat-buffer wire layout of one hop for a fixed (codec, activation
    shape): ``[canary u32][crc u32][payload leaves in tree-flatten order]``.

    ``sealed_spec`` is the abstract sealed tree (``ShapeDtypeStruct`` leaves)
    the buffer round-trips through; every byte count is a static trace-time
    constant."""

    codec_name: str
    sealed_spec: Any

    @classmethod
    def for_codec(cls, codec, hidden_shape, dtype=jnp.float32) -> "WireFormat":
        """The wire format of ``codec`` hopping one (B, S, D) activation."""
        payload = jax.eval_shape(codec.encode,
                                 jax.ShapeDtypeStruct(hidden_shape, dtype))
        sealed = jax.eval_shape(seal_payload, payload)
        return cls(codec_name=codec.name, sealed_spec=sealed)

    @property
    def payload_nbytes(self) -> int:
        """Codec payload bytes — matches ``WireCodec.payload_bytes``."""
        return tree_nbytes(self.sealed_spec["p"])

    @property
    def wire_nbytes(self) -> int:
        """Total flat-buffer bytes: payload + the 8-byte integrity sidecar."""
        return tree_nbytes(self.sealed_spec)

    def to_wire(self, sealed: dict) -> jnp.ndarray:
        """Sealed tree -> the (wire_nbytes,) uint8 buffer that crosses the
        cut. Pure bitcasts — bit-exact round-trip with :meth:`from_wire`."""
        return flatten_bytes(sealed)

    def from_wire(self, buf: jnp.ndarray) -> dict:
        """Arrived flat buffer -> sealed tree (static slices against the
        spec); feed it to :func:`verify_payload` and the codec's decode."""
        return unflatten_bytes(buf, self.sealed_spec)
