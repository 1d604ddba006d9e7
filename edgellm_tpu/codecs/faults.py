"""Boundary-wire fault layer: seeded fault injection, payload integrity, and
bounded-retry / degradation policies for the split pipeline's ``ppermute`` hops.

The split runtimes model every cut as a lossless collective; the reference's
edge-network premise says otherwise. This module makes the wire *faulty on
purpose* — reproducibly — and makes the receiver notice:

- :class:`FaultConfig`: a seeded, jit-compatible injector spec. Bit flips hit
  the packed payload bytes through a ``bitcast_convert_type`` byte view (any
  leaf dtype), scale corruption multiplies float leaves, whole-hop drops zero
  the entire sealed payload, and a per-hop byte budget statically squeezes
  hops whose packed payload no longer fits. Everything is driven by
  ``fold_in`` chains off one seed, so two runs with the same seed corrupt the
  same bytes on the same hops.
- :func:`seal_payload` / :func:`verify_payload`: a canary word plus a weighted
  byte checksum folded into every payload pytree before the ``ppermute`` and
  checked after it. The per-byte weights are odd (``(2i+1) * Knuth``), and an
  odd weight is invertible mod 2**32 — so any single corrupted byte always
  changes the sum; a dropped payload zeroes the canary. Corruption is
  *detected and counted*, never silently decoded into the next stage.
- :class:`FaultyLink`: the hop protocol under faults — encode, seal, inject,
  ``ppermute``, verify, with ``LinkPolicy.max_retries`` statically-unrolled
  re-sends (every attempt re-rolls its injection key, so a retry can genuinely
  recover), and on exhausted retries either a zero-state substitution with a
  counted degradation flag or a counted pass-through of the corrupted decode.
- :class:`TierController`: the host-side hysteresis half of graceful
  degradation — consecutive corrupted chunks step the hop codecs down a
  precision ladder (int8 -> int4 -> ternary), consecutive clean chunks step
  back up. Codec tiers change payload *shapes*, so switching happens between
  jitted calls, never inside one.

With ``FaultConfig.enabled`` false the runtimes build the exact pre-fault
graph — the zero-rate path is bit-identical to a fault-free build, and tests
assert it.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional, Sequence

import numpy as np
import jax
import jax.numpy as jnp

from ..lint import graph_contract
# the wire primitives (canary + checksum seal, byte accounting) live in
# wire_format.py; re-exported here verbatim
from .wire_format import (CANARY, _CRC_MULT, _leaf_crc,  # noqa: F401
                          payload_checksum, seal_payload, tree_nbytes,
                          verify_payload)

#: per-hop counter names accumulated by :class:`FaultyLink` (all (n_hops,)
#: int32, receiver-side, psum-replicated by the pipeline protocol):
#: hops = transfers attempted, detected = corrupted arrivals caught by the
#: integrity check, retried = re-sends actually needed, recovered = hops that
#: failed at least once but eventually verified, substituted = hops that
#: exhausted retries and fell back per the policy, budget_dropped = hops whose
#: packed payload statically exceeded the byte budget. A self-healing link
#: (:mod:`~edgellm_tpu.codecs.fec`) appends "repaired" (corrupted arrivals
#: healed in band by XOR parity) and "hedge_wins" (hops a non-primary
#: staggered route delivered first) via :attr:`FaultyLink.counter_keys`.
COUNTER_KEYS = ("hops", "detected", "retried", "recovered", "substituted",
                "budget_dropped")


@dataclasses.dataclass(frozen=True)
class FaultConfig:
    """Seeded wire-fault rates. All rates are per *attempt*; ``bitflip_rate``
    is per payload byte, ``scale_corrupt_rate`` per float element,
    ``drop_rate`` per hop. ``byte_budget`` (bytes) statically squeezes any hop
    whose packed payload exceeds it. ``enabled`` False builds the exact
    fault-free graph."""

    bitflip_rate: float = 0.0
    scale_corrupt_rate: float = 0.0
    drop_rate: float = 0.0
    byte_budget: Optional[int] = None
    seed: int = 0

    def __post_init__(self):
        for f in ("bitflip_rate", "scale_corrupt_rate", "drop_rate"):
            v = getattr(self, f)
            if isinstance(v, bool) or not isinstance(v, (int, float)):
                raise ValueError(f"{f} must be a number, got {v!r}")
            if not 0.0 <= v <= 1.0:
                raise ValueError(f"{f} must be in [0, 1], got {v}")
        if self.byte_budget is not None and (
                isinstance(self.byte_budget, bool)
                or not isinstance(self.byte_budget, int)
                or self.byte_budget <= 0):
            raise ValueError(f"byte_budget must be a positive integer, "
                             f"got {self.byte_budget!r}")
        if isinstance(self.seed, bool) or not isinstance(self.seed, int):
            raise ValueError(f"seed must be an integer, got {self.seed!r}")

    @property
    def enabled(self) -> bool:
        return (self.bitflip_rate > 0 or self.scale_corrupt_rate > 0
                or self.drop_rate > 0 or self.byte_budget is not None)


@dataclasses.dataclass(frozen=True)
class LinkPolicy:
    """What the receiver does about a hop that fails integrity.

    ``max_retries`` re-sends are statically unrolled inside the jitted hop
    (each with a fresh injection key). When all attempts fail:
    ``on_fail="substitute"`` forwards a zero hidden state and counts the hop
    as degraded; ``on_fail="passthrough"`` decodes the corrupted payload
    anyway (the "silently poisoned" baseline, but counted). ``tiers`` names
    the codec degradation ladder the host-side :class:`TierController` walks
    (int8 -> int4 -> ternary by default when adaptive mode is requested);
    ``degrade_after`` / ``recover_after`` are its hysteresis thresholds in
    consecutive chunks."""

    max_retries: int = 0
    on_fail: str = "substitute"
    tiers: tuple = ()
    degrade_after: int = 2
    recover_after: int = 8

    def __post_init__(self):
        if self.on_fail not in ("substitute", "passthrough"):
            raise ValueError(f"on_fail must be 'substitute' or 'passthrough', "
                             f"got {self.on_fail!r}")
        for f, lo in (("max_retries", 0), ("degrade_after", 1),
                      ("recover_after", 1)):
            v = getattr(self, f)
            if isinstance(v, bool) or not isinstance(v, int) or v < lo:
                raise ValueError(f"{f} must be an integer >= {lo}, got {v!r}")


def inject_faults(sealed: dict, key: jax.Array,
                  cfg: FaultConfig) -> dict:
    """Corrupt a sealed payload tree per ``cfg``, deterministically from
    ``key``. Bit flips and drops hit every leaf (sidecar included — a flipped
    checksum is a detected corruption too); scale corruption hits float
    leaves. Zero-rate configs return the tree untouched (same graph)."""
    leaves, treedef = jax.tree_util.tree_flatten(sealed)
    drop = (jax.random.uniform(jax.random.fold_in(key, 0xD0)) < cfg.drop_rate
            if cfg.drop_rate > 0 else None)
    out = []
    for j, x in enumerate(leaves):
        kj = jax.random.fold_in(key, j)
        if cfg.bitflip_rate > 0 and x.size:
            b = jax.lax.bitcast_convert_type(x, jnp.uint8)
            k_hit, k_bit = jax.random.split(kj)
            hit = jax.random.bernoulli(k_hit, cfg.bitflip_rate, b.shape)
            bit = jax.random.randint(k_bit, b.shape, 0, 8).astype(jnp.uint8)
            b = b ^ jnp.where(hit, jnp.left_shift(jnp.uint8(1), bit),
                              jnp.uint8(0))
            x = jax.lax.bitcast_convert_type(b, x.dtype)
        if (cfg.scale_corrupt_rate > 0 and x.size
                and jnp.issubdtype(x.dtype, jnp.floating)):
            k_sc = jax.random.fold_in(kj, 0x5C)
            hit = jax.random.bernoulli(k_sc, cfg.scale_corrupt_rate, x.shape)
            # affine blowup: moves every value, zeros included
            x = jnp.where(hit, x * x.dtype.type(-997.0) + x.dtype.type(1.0), x)
        if drop is not None:
            x = jnp.where(drop, jnp.zeros_like(x), x)
        out.append(x)
    return jax.tree_util.tree_unflatten(treedef, out)


def _bump(counters: dict, key: str, hop: int, cond) -> dict:
    new = dict(counters)
    new[key] = counters[key].at[hop].add(jnp.asarray(cond).astype(jnp.int32))
    return new


@dataclasses.dataclass(frozen=True)
class FaultyLink:
    """The hop protocol under faults — a static closure the pipeline unroll
    calls in place of the bare encode/ppermute/decode when faults are on.

    ``fec`` (a :class:`~edgellm_tpu.codecs.fec.FECConfig`) and ``hedge``
    (a :class:`~edgellm_tpu.codecs.fec.HedgeConfig`) arm the self-healing
    ladder — in-band XOR-parity repair and staggered redundant routes; with
    both absent or disabled, :meth:`hop` is the exact PR 2 protocol and the
    traced graph is bit-identical to a pre-FEC build."""

    faults: FaultConfig
    policy: LinkPolicy
    fec: Optional[Any] = None
    hedge: Optional[Any] = None

    @property
    def healing(self) -> bool:
        return ((self.fec is not None and self.fec.enabled)
                or (self.hedge is not None and self.hedge.enabled))

    @property
    def counter_keys(self) -> tuple:
        keys = COUNTER_KEYS
        if self.fec is not None and self.fec.enabled:
            keys = keys + ("repaired",)
        if self.hedge is not None and self.hedge.enabled:
            keys = keys + ("hedge_wins",)
        return keys

    def init_counters(self, n_hops: int) -> dict:
        return {k: jnp.zeros((n_hops,), jnp.int32) for k in self.counter_keys}

    @graph_contract(
        "faults.hop",
        # per cut: every statically-unrolled attempt re-sends every sealed
        # leaf (payload + canary + crc); the psum count is the structural
        # output replication plus one per replicated counter. The lint driver
        # traces a faulted split forward and supplies the measured ctx.
        collectives=lambda ctx: {"ppermute": ctx["hop_eqns"],
                                 "psum": ctx["n_psum"]},
        wire_dtypes=lambda ctx: ctx["wire_dtypes"],
        wire_bytes=lambda ctx: ctx["wire_bytes"])
    def hop(self, codec: Any, hidden: jnp.ndarray, s: int, axis_name: str,
            idx: jnp.ndarray, key: jax.Array, counters: dict,
            hop_imp: Optional[jnp.ndarray] = None) -> tuple:
        """One faulty boundary crossing stage s -> s+1 (inside shard_map).

        Encode once; then up to 1+max_retries sealed transmissions, each with
        its own injection key. Every device runs every attempt (static
        unroll); the receiver's verify gates which attempt's decode is kept,
        and counters accumulate receiver-side only so the later psum counts
        each hop exactly once. Returns (new hidden, counters)."""
        if self.healing:
            from .fec import healing_hop

            return healing_hop(self, codec, hidden, s, axis_name, idx, key,
                               counters, hop_imp)
        if codec.needs_importance:
            payload = codec.encode(hidden, hop_imp)
        else:
            payload = codec.encode(hidden)
        over_budget = (self.faults.byte_budget is not None
                       and tree_nbytes(payload) > self.faults.byte_budget)
        sealed = seal_payload(payload)
        k_hop = jax.random.fold_in(key, s)
        recv = idx == s + 1
        ok = jnp.asarray(False)
        first_fail = jnp.asarray(False)
        decoded = jnp.zeros_like(hidden)
        last_dec = jnp.zeros_like(hidden)
        counters = _bump(counters, "hops", s, recv)
        if over_budget:
            counters = _bump(counters, "budget_dropped", s, recv)
        for a in range(1 + max(self.policy.max_retries, 0)):
            needed = jnp.logical_not(ok)  # this attempt actually transmits
            corrupted = inject_faults(sealed, jax.random.fold_in(k_hop, a),
                                      self.faults)
            moved = jax.tree_util.tree_map(
                lambda x: jax.lax.ppermute(x, axis_name, [(s, s + 1)]),
                corrupted)
            ok_a = verify_payload(moved)
            if over_budget:  # squeezed link: the payload never fits
                ok_a = jnp.logical_and(ok_a, False)
            dec_a = codec.decode(moved["p"])
            decoded = jnp.where(jnp.logical_and(needed, ok_a), dec_a, decoded)
            last_dec = jnp.where(needed, dec_a, last_dec)
            counters = _bump(counters, "detected", s,
                             recv & needed & ~ok_a)
            if a > 0:
                counters = _bump(counters, "retried", s, recv & needed)
            if a == 0:
                first_fail = jnp.logical_not(ok_a)
            ok = jnp.logical_or(ok, ok_a)
        counters = _bump(counters, "recovered", s, recv & ok & first_fail)
        if self.policy.on_fail == "substitute":
            counters = _bump(counters, "substituted", s, recv & ~ok)
            final = jnp.where(ok, decoded, jnp.zeros_like(hidden))
        else:  # passthrough: accept the corrupted decode, but count it
            counters = _bump(counters, "substituted", s, recv & ~ok)
            final = jnp.where(ok, decoded, last_dec)
        return jnp.where(recv, final, hidden), counters


class TierController:
    """Host-side hysteresis over a codec degradation ladder.

    ``observe(corrupted)`` once per evaluation chunk: ``degrade_after``
    consecutive corrupted chunks step to the next (lower-precision) tier,
    ``recover_after`` consecutive clean chunks step back up. Both streaks
    reset on a switch, so the controller can't oscillate every chunk."""

    def __init__(self, n_tiers: int, degrade_after: int = 2,
                 recover_after: int = 8):
        if n_tiers < 1:
            raise ValueError("need at least one tier")
        self.n_tiers = n_tiers
        self.degrade_after = degrade_after
        self.recover_after = recover_after
        self.tier = 0
        self.switches = 0
        self._bad = 0
        self._good = 0

    def observe(self, corrupted: bool) -> int:
        if corrupted:
            self._bad += 1
            self._good = 0
            if self._bad >= self.degrade_after and self.tier < self.n_tiers - 1:
                self.tier += 1
                self.switches += 1
                self._bad = 0
        else:
            self._good += 1
            self._bad = 0
            if self._good >= self.recover_after and self.tier > 0:
                self.tier -= 1
                self.switches += 1
                self._good = 0
        return self.tier


def sum_counters(counter_list: Optional[Sequence[dict]]) -> Optional[dict]:
    """Host-side total of per-call counter dicts -> {key: (n_hops,) int64
    ndarray}. None/empty in, None out."""
    if not counter_list:
        return None
    tot = {k: np.zeros_like(np.asarray(counter_list[0][k]), dtype=np.int64)
           for k in counter_list[0]}
    for c in counter_list:
        for k, v in c.items():
            tot[k] = tot[k] + np.asarray(v, dtype=np.int64)
    return tot


def flatten_counters(counters: Optional[dict]) -> dict:
    """Collapse a per-hop counter dict ({key: (n_hops,) ints}) to per-key
    scalar totals: {key: int}. The shape reports and the obs metric adapters
    want; None/empty in, {} out."""
    if not counters:
        return {}
    return {k: int(np.asarray(v, dtype=np.int64).sum())
            for k, v in counters.items()}
