"""Self-healing boundary links: in-band FEC repair, hedged hops, and a
host-side link-health SLO controller over the faulty split wire.

PR 2's fault layer *detects* corruption (canary + weighted-byte checksum) but
every detected hop costs a full re-transmission, a codec tier, or a zeroed
substitute. This module spends a declared fraction of the wire on parity so
single-event corruption is repaired IN BAND, with zero extra hops:

- :class:`FECConfig` + :func:`fec_encode` / :func:`fec_decode`: the sealed
  payload's byte stream is interleaved round-robin into
  ``group_size * n_groups`` data chunks; chunk ``c`` joins parity group
  ``c % n_groups``, so a contiguous burst up to ``n_groups`` chunks wide
  lands in distinct groups. Every group carries one XOR parity chunk, and
  every chunk (parity included) carries a canary-folded weighted-byte
  checksum word — the per-byte weights are odd (PR 2's ``(2i+1) * Knuth``
  construction), so any single corrupted byte in a chunk always trips its
  word, and the canary fold keeps a zeroed (dropped) chunk from agreeing
  with its zeroed word. A mismatching chunk is *located* by its word and
  *repaired* by a masked ``where``-select of ``parity ^ xor(group)`` — pure
  jit-compatible integer ops. Two bad chunks in one group exceed XOR parity;
  the outer PR 2 seal then fails and the hop falls back to retry.
- :func:`healing_hop`: the extended hop ladder — detect -> repair -> retry
  -> hedge -> (host-side) degrade -> substitute. With
  :class:`HedgeConfig` the payload rides ``routes`` staggered ``ppermute``
  transmissions per attempt, each with an independent injection key, and the
  receiver keeps the first verified copy — trading wire for latency on
  drop-dominated links where parity can't help (a drop zeroes every chunk).
- :class:`LinkHealth`: the SLO half — a host-side sibling of
  :class:`~edgellm_tpu.codecs.faults.TierController` that keeps windowed
  corruption / repair / retry / hedge-win rates from the per-call counter
  deltas, compares the *unrepaired* corruption rate against an error budget
  (its burn rate), degrades the codec tier while the budget burns, and
  re-promotes once it recovers — with a full-window re-measure plus a
  clock-based dwell between switches, so the tier can't flap.

With ``FECConfig.enabled`` false and no hedging, :class:`FaultyLink` never
calls into this module — the build is the exact PR 2/3 graph, bit-identical,
and a graphlint fingerprint contract asserts it.
"""
from __future__ import annotations

import dataclasses
import threading
from collections import deque
from typing import Any, Optional

import numpy as np
import jax
import jax.numpy as jnp

from ..lint import graph_contract
from ..utils.clock import MONOTONIC, Clock
from ..utils.concurrency import guarded_by
from .faults import (_CRC_MULT, _bump, inject_faults, seal_payload,
                     tree_nbytes, verify_payload)
# the byte-stream flatten/unflatten live in wire_format.py; aliased to the
# historical private names
from .wire_format import flatten_bytes as _flatten_bytes
from .wire_format import unflatten_bytes as _unflatten_bytes

#: folded into every chunk checksum word so an all-zero (dropped) chunk and
#: its zeroed word can never agree
_CHUNK_CANARY = 0x5EA1C0DE


@dataclasses.dataclass(frozen=True)
class FECConfig:
    """Parity layout for the sealed boundary payload.

    ``group_size`` data chunks share one XOR parity chunk (the overhead knob:
    parity costs ~``1/group_size`` of the payload, plus 4 bytes of checksum
    word per chunk); ``n_groups`` parity groups interleave the byte stream,
    so a contiguous corruption burst up to ``n_groups`` chunks wide stays
    single-chunk-per-group — still repairable. ``enabled`` False builds the
    exact pre-FEC graph."""

    enabled: bool = True
    group_size: int = 4
    n_groups: int = 4

    def __post_init__(self):
        if not isinstance(self.enabled, bool):
            raise ValueError(f"enabled must be a boolean, got {self.enabled!r}")
        for f in ("group_size", "n_groups"):
            v = getattr(self, f)
            if isinstance(v, bool) or not isinstance(v, int) or v < 1:
                raise ValueError(f"{f} must be an integer >= 1, got {v!r}")

    @property
    def n_data_chunks(self) -> int:
        return self.group_size * self.n_groups

    def chunk_len(self, sealed_nbytes: int) -> int:
        return max(1, -(-int(sealed_nbytes) // self.n_data_chunks))

    def wire_nbytes(self, sealed_nbytes: int) -> int:
        """Static byte size of the FEC wire tree for a sealed payload of
        ``sealed_nbytes`` bytes: padded data + parity chunks + one uint32
        checksum word per chunk."""
        n_chunks = self.n_data_chunks + self.n_groups
        return n_chunks * self.chunk_len(sealed_nbytes) + 4 * n_chunks

    def overhead(self, sealed_nbytes: int) -> float:
        """Fractional wire overhead vs sending the sealed payload bare."""
        return self.wire_nbytes(sealed_nbytes) / max(sealed_nbytes, 1) - 1.0


@dataclasses.dataclass(frozen=True)
class HedgeConfig:
    """Hedged-hop mode: send ``routes`` staggered copies per attempt and keep
    the first verified one. Wire cost scales with ``routes``; latency (counted
    retries) falls on drop-dominated links."""

    enabled: bool = True
    routes: int = 2

    def __post_init__(self):
        if not isinstance(self.enabled, bool):
            raise ValueError(f"enabled must be a boolean, got {self.enabled!r}")
        if (isinstance(self.routes, bool) or not isinstance(self.routes, int)
                or self.routes < 2):
            raise ValueError(f"routes must be an integer >= 2, "
                             f"got {self.routes!r}")


def _chunk_words(chunks: jnp.ndarray) -> jnp.ndarray:
    """Per-chunk canary-folded weighted byte sums: (C, L) uint8 -> (C,)
    uint32. Weights are odd per position (invertible mod 2**32 — any single
    corrupted byte in a chunk always moves its word) and salted per chunk so
    chunks can't trade bytes; the XOR fold keeps an all-zero chunk from
    matching an all-zero word."""
    n_chunks, chunk_len = chunks.shape
    i = jnp.arange(chunk_len, dtype=jnp.uint32)[None, :]
    salt = (jnp.arange(n_chunks, dtype=jnp.uint32)
            * jnp.uint32(0x9E3779B1))[:, None]
    w = (jnp.uint32(2) * (i + salt) + jnp.uint32(1)) * jnp.uint32(_CRC_MULT)
    s = jnp.sum(chunks.astype(jnp.uint32) * w, axis=1, dtype=jnp.uint32)
    return s ^ jnp.uint32(_CHUNK_CANARY)


def fec_encode(sealed: Any, cfg: FECConfig) -> dict:
    """Sealed payload tree -> FEC wire tree ``{"chunks", "words"}``.

    ``chunks`` stacks the ``group_size * n_groups`` interleaved data chunks
    and the ``n_groups`` XOR parity chunks as one (C, L) uint8 array;
    ``words`` carries each chunk's locate-checksum. Byte i of the sealed
    stream lands in data chunk ``i % n_data_chunks`` (round-robin), and data
    chunk ``c`` belongs to parity group ``c % n_groups``."""
    stream = _flatten_bytes(sealed)
    d = cfg.n_data_chunks
    chunk_len = cfg.chunk_len(stream.size)
    pad = d * chunk_len - stream.size
    if pad:
        stream = jnp.pad(stream, (0, pad))
    data = stream.reshape(chunk_len, d).T  # (d, L): chunk c = byte i % d
    grouped = data.reshape(cfg.group_size, cfg.n_groups, chunk_len)
    parity = grouped[0]
    for s in range(1, cfg.group_size):
        parity = parity ^ grouped[s]
    chunks = jnp.concatenate([data, parity], axis=0)
    return {"chunks": chunks, "words": _chunk_words(chunks)}


def fec_decode(wire: dict, cfg: FECConfig, like: Any) -> tuple:
    """Arrived FEC wire tree -> (sealed tree, any_chunk_bad, repaired).

    Recomputes every chunk word; a mismatch locates the chunk. A group with
    exactly one bad data chunk and a good parity chunk is repaired by the
    masked XOR select ``parity ^ xor(all data in group) ^ bad_chunk`` (for a
    falsely-accused chunk — its word corrupted, its bytes fine — that select
    is the identity, so the repair is safely a no-op). Groups with two or
    more bad data chunks, or a dropped hop (every chunk bad), are beyond XOR
    parity and left for the retry ladder; the caller's outer
    :func:`~edgellm_tpu.codecs.faults.verify_payload` stays the authority on
    the reconstruction."""
    chunks, words = wire["chunks"], wire["words"]
    d = cfg.n_data_chunks
    chunk_len = chunks.shape[1]
    bad = _chunk_words(chunks) != words  # (d + n_groups,)
    bad_data = bad[:d].reshape(cfg.group_size, cfg.n_groups)
    bad_parity = bad[d:]
    n_bad = jnp.sum(bad_data.astype(jnp.int32), axis=0)  # per group
    repairable = jnp.logical_and(n_bad == 1, jnp.logical_not(bad_parity))
    grouped = chunks[:d].reshape(cfg.group_size, cfg.n_groups, chunk_len)
    gx = chunks[d:]  # parity ^ xor(data) == 0 when the group is intact
    for s in range(cfg.group_size):
        gx = gx ^ grouped[s]
    candidate = gx[None] ^ grouped  # the missing chunk, per slot
    fix = jnp.logical_and(bad_data, repairable[None])[:, :, None]
    grouped = jnp.where(fix, candidate, grouped)
    n = tree_nbytes(like)
    stream = grouped.reshape(d, chunk_len).T.reshape(-1)[:n]
    return _unflatten_bytes(stream, like), jnp.any(bad), jnp.any(fix)


@graph_contract(
    "fec.hop",
    # per cut: every transmission (attempts x hedge routes) re-sends the
    # 2-leaf FEC wire tree (chunk matrix + word vector); psums are the
    # structural output replication plus one per replicated counter. The
    # lint driver traces a FEC-enabled split forward and supplies the ctx.
    collectives=lambda ctx: {"ppermute": ctx["hop_eqns"],
                             "psum": ctx["n_psum"]},
    wire_dtypes=lambda ctx: ctx["wire_dtypes"],
    wire_bytes=lambda ctx: ctx["wire_bytes"])
def healing_hop(link: Any, codec: Any, hidden: jnp.ndarray, s: int,
                axis_name: str, idx: jnp.ndarray, key: jax.Array,
                counters: dict,
                hop_imp: Optional[jnp.ndarray] = None) -> tuple:
    """One self-healing boundary crossing stage s -> s+1 (inside shard_map).

    The full ladder per hop: seal, (FEC-encode,) then for every statically
    unrolled attempt send ``routes`` staggered copies — each with a fresh
    injection key — and on arrival locate + XOR-repair bad chunks before the
    outer integrity verdict gates which copy's decode is kept. ``detected``
    counts corrupted arrivals (repaired ones included), ``repaired`` the
    arrivals healed in band, ``hedge_wins`` the hops a non-primary route
    delivered first, ``retried`` the attempts (not routes) that actually
    re-transmitted. :class:`~edgellm_tpu.codecs.faults.FaultyLink.hop`
    dispatches here only when FEC or hedging is enabled — the disabled build
    never traces this function."""
    fec = link.fec if (link.fec is not None and link.fec.enabled) else None
    routes = (link.hedge.routes
              if link.hedge is not None and link.hedge.enabled else 1)
    if codec.needs_importance:
        payload = codec.encode(hidden, hop_imp)
    else:
        payload = codec.encode(hidden)
    over_budget = (link.faults.byte_budget is not None
                   and tree_nbytes(payload) > link.faults.byte_budget)
    sealed = seal_payload(payload)
    wire = fec_encode(sealed, fec) if fec is not None else sealed
    k_hop = jax.random.fold_in(key, s)
    recv = idx == s + 1
    ok = jnp.asarray(False)
    first_fail = jnp.asarray(False)
    decoded = jnp.zeros_like(hidden)
    last_dec = jnp.zeros_like(hidden)
    counters = _bump(counters, "hops", s, recv)
    if over_budget:
        counters = _bump(counters, "budget_dropped", s, recv)
    t = 0  # transmission index = fresh fault draw
    for a in range(1 + max(link.policy.max_retries, 0)):
        attempt_needed = None
        for r in range(routes):
            take = jnp.logical_not(ok)  # no earlier copy verified yet
            if r == 0:
                attempt_needed = take
            corrupted = inject_faults(wire, jax.random.fold_in(k_hop, t),
                                      link.faults)
            moved = jax.tree_util.tree_map(
                lambda x: jax.lax.ppermute(x, axis_name, [(s, s + 1)]),
                corrupted)
            if fec is not None:
                arrived, arrived_bad, did_repair = fec_decode(moved, fec,
                                                              sealed)
            else:
                arrived = moved
            ok_a = verify_payload(arrived)
            if over_budget:  # squeezed link: the payload never fits
                ok_a = jnp.logical_and(ok_a, False)
            dec_a = codec.decode(arrived["p"])
            decoded = jnp.where(jnp.logical_and(take, ok_a), dec_a, decoded)
            last_dec = jnp.where(take, dec_a, last_dec)
            if fec is not None:
                # chunk words can collide on multi-byte damage; the outer
                # seal is the authority, so a failed verdict counts detected
                arrived_bad = jnp.logical_or(arrived_bad, ~ok_a)
                counters = _bump(counters, "detected", s,
                                 recv & take & arrived_bad)
                counters = _bump(counters, "repaired", s,
                                 recv & take & did_repair & ok_a)
            else:
                counters = _bump(counters, "detected", s, recv & take & ~ok_a)
            if routes > 1 and r > 0:
                counters = _bump(counters, "hedge_wins", s,
                                 recv & take & ok_a)
            if t == 0:
                first_fail = jnp.logical_not(ok_a)
            ok = jnp.logical_or(ok, ok_a)
            t += 1
        if a > 0:
            counters = _bump(counters, "retried", s, recv & attempt_needed)
    counters = _bump(counters, "recovered", s, recv & ok & first_fail)
    counters = _bump(counters, "substituted", s, recv & ~ok)
    if link.policy.on_fail == "substitute":
        final = jnp.where(ok, decoded, jnp.zeros_like(hidden))
    else:  # passthrough: accept the last corrupted decode, but count it
        final = jnp.where(ok, decoded, last_dec)
    return jnp.where(recv, final, hidden), counters


# ---------------------------------------------------------------------------
# host-side SLO control
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class LinkHealthConfig:
    """SLO budget for :class:`LinkHealth`. ``error_budget`` is the tolerated
    fraction of hops left corrupted after in-band repair; the burn rate is
    the windowed unrepaired-corruption rate divided by that budget.
    ``degrade_burn`` / ``promote_burn`` are the switch thresholds (with
    ``promote_burn`` strictly below ``degrade_burn`` — rate hysteresis), and
    ``min_dwell_s`` is the wall-clock floor between tier switches (time
    hysteresis; the clock is injectable for tests)."""

    window: int = 16
    error_budget: float = 0.02
    degrade_burn: float = 1.0
    promote_burn: float = 0.25
    min_dwell_s: float = 0.0

    def __post_init__(self):
        if (isinstance(self.window, bool) or not isinstance(self.window, int)
                or self.window < 1):
            raise ValueError(f"window must be an integer >= 1, "
                             f"got {self.window!r}")
        for f, lo, hi in (("error_budget", 0.0, 1.0),
                          ("degrade_burn", 0.0, float("inf")),
                          ("promote_burn", 0.0, float("inf")),
                          ("min_dwell_s", 0.0, float("inf"))):
            v = getattr(self, f)
            if isinstance(v, bool) or not isinstance(v, (int, float)):
                raise ValueError(f"{f} must be a number, got {v!r}")
            if not lo <= v <= hi or (f in ("error_budget", "degrade_burn")
                                     and v <= 0):
                raise ValueError(f"{f} out of range: {v!r}")
        if self.promote_burn >= self.degrade_burn:
            raise ValueError(
                f"promote_burn ({self.promote_burn}) must be below "
                f"degrade_burn ({self.degrade_burn}) — no hysteresis band")


#: the counter names LinkHealth folds into its window (missing keys read 0,
#: so pre-FEC counter dicts observe cleanly)
_HEALTH_KEYS = ("hops", "detected", "repaired", "retried", "substituted",
                "hedge_wins")


@guarded_by("_lock", fields=["tier", "switches", "observations", "_window",
                             "_last_switch"])
class LinkHealth:
    """Host-side link SLO tracker and tier driver.

    ``observe(delta)`` once per call/chunk with that call's counter deltas
    (any :data:`~edgellm_tpu.codecs.faults.COUNTER_KEYS`-style dict of
    per-hop arrays or scalars). Over a full sliding window it keeps the
    corruption / repair / retry / hedge-win rates, and burns the error
    budget with the *unrepaired* corruption rate: ``burn >= degrade_burn``
    steps the codec tier down, ``burn <= promote_burn`` steps it back up.
    Every switch clears the window (the new tier gets a full re-measure) and
    arms the ``min_dwell_s`` clock, so a noisy link cannot flap the tier.

    Thread-safe: the decode thread observes while the obs scrape thread
    reads :meth:`summary` and the rate properties, so window/tier state
    mutates under ``_lock``. The registry publish happens *outside* the
    lock (it re-enters :meth:`summary`, and holding a lock across the
    metrics adapters would be a threadlint EG102/EG103 hazard)."""

    def __init__(self, n_tiers: int = 1,
                 config: Optional[LinkHealthConfig] = None,
                 clock: Clock = MONOTONIC):
        if n_tiers < 1:
            raise ValueError("need at least one tier")
        self.cfg = config if config is not None else LinkHealthConfig()
        self.n_tiers = n_tiers
        self.clock = clock
        self._lock = threading.Lock()
        self.tier = 0
        self.switches = 0
        self.observations = 0
        self._window: deque = deque(maxlen=self.cfg.window)
        self._last_switch: Optional[float] = None

    def observe(self, counters: Optional[dict]) -> int:
        tot = {k: 0 for k in _HEALTH_KEYS}
        if counters:
            for k in _HEALTH_KEYS:
                if k in counters:
                    tot[k] = int(np.asarray(counters[k]).sum())
        with self._lock:
            self._window.append(tot)
            self.observations += 1
            if len(self._window) == self.cfg.window:
                burn = self._burn_rate_locked()
                now = self.clock()
                dwell_ok = (self._last_switch is None
                            or now - self._last_switch >= self.cfg.min_dwell_s)
                if (burn >= self.cfg.degrade_burn and dwell_ok
                        and self.tier < self.n_tiers - 1):
                    self.tier += 1
                    self.switches += 1
                    self._last_switch = now
                    self._window.clear()
                elif (burn <= self.cfg.promote_burn and dwell_ok
                      and self.tier > 0):
                    self.tier -= 1
                    self.switches += 1
                    self._last_switch = now
                    self._window.clear()
            tier = self.tier
        self._publish()
        return tier

    def _publish(self) -> None:
        """Mirror the windowed SLO fields into the global obs registry.
        Lazy import + enabled gate: with observability off (the default)
        this is one attribute check per observation."""
        from ..obs.metrics import get_registry, record_link_health

        if get_registry().enabled:
            record_link_health(self.summary())

    def _sum_locked(self, key: str) -> int:
        return sum(o[key] for o in self._window)

    @property
    def corruption_rate(self) -> float:
        with self._lock:
            return self._sum_locked("detected") / max(
                self._sum_locked("hops"), 1)

    @property
    def repair_rate(self) -> float:
        """Fraction of detected corruption healed in band."""
        with self._lock:
            return self._sum_locked("repaired") / max(
                self._sum_locked("detected"), 1)

    @property
    def retry_rate(self) -> float:
        with self._lock:
            return self._sum_locked("retried") / max(
                self._sum_locked("hops"), 1)

    @property
    def hedge_win_rate(self) -> float:
        with self._lock:
            return self._sum_locked("hedge_wins") / max(
                self._sum_locked("hops"), 1)

    def _burn_rate_locked(self) -> float:
        unrepaired = (self._sum_locked("detected")
                      - self._sum_locked("repaired"))
        return ((unrepaired / max(self._sum_locked("hops"), 1))
                / self.cfg.error_budget)

    @property
    def burn_rate(self) -> float:
        """Windowed unrepaired-corruption rate over the error budget; >= 1
        means the link is out of SLO at the current tier."""
        with self._lock:
            return self._burn_rate_locked()

    def summary(self) -> dict:
        with self._lock:
            return {
                "tier": self.tier,
                "switches": self.switches,
                "observations": self.observations,
                "window": len(self._window),
                "error_budget": self.cfg.error_budget,
                "burn_rate": self._burn_rate_locked(),
                "corruption_rate": self._sum_locked("detected") / max(
                    self._sum_locked("hops"), 1),
                "repair_rate": self._sum_locked("repaired") / max(
                    self._sum_locked("detected"), 1),
                "retry_rate": self._sum_locked("retried") / max(
                    self._sum_locked("hops"), 1),
                "hedge_win_rate": self._sum_locked("hedge_wins") / max(
                    self._sum_locked("hops"), 1),
            }
