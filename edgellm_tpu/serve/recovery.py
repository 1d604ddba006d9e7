"""Survivable decode: checkpointed generation state, failover, watchdogs.

PR 2 made the split-boundary *link* survivable; this module makes the
*generation* survivable when a whole stage/device dies or the host hangs:

- :class:`DecodeCheckpoint` — a versioned, atomic on-disk snapshot of
  everything an in-flight decode needs to resume **token-identically**: the
  per-stage KV caches (position offsets ride in ``cache/length``), the
  caller's RNG key (serialized via ``jax.random.key_data``), the sampled
  token prefix, and the PR-2 fault/tier counters.  The file format is
  magic + version + length + CRC32 over the payload, so a truncated or
  bit-flipped checkpoint fails with a typed :class:`CheckpointError`
  naming the problem — never a pytree unflatten traceback.  Writes reuse
  the ``.part``-then-rename pattern of ``hf_loader.fetch_with_retry``.
- :class:`StageFailure` / :class:`StageLostError` — whole-stage loss
  injection, distinct from PR 2's link faults: at a configured decode step
  the stage goes dark and every call into the runtime raises the typed
  error until the caller fails over (``serve.decode`` re-plans the split
  boundary onto the survivors and recomputes the lost KV cache from the
  generation prefix).
- :class:`Watchdog` — a host-side monotonic-clock deadline for decode/eval
  loops: on expiry it writes a best-effort checkpoint and raises
  :class:`DecodeTimeout` instead of hanging forever.  The clock is
  injectable so tests fire it deterministically.
- :class:`LocalRuntime` — a single-device runtime duck-typing
  ``SplitRuntime``'s decode surface (``place_params`` / ``prefill_decode``
  / ``decode_step``), the failover target when only one stage survives.

Nothing here imports ``edgellm_tpu.parallel`` — the split runtimes import
:class:`StageLostError` from here, and the serve loop imports the split
machinery lazily inside its failover path, so the layering stays acyclic.
"""
from __future__ import annotations

import dataclasses
import functools
import json
import os
import struct
import zlib
from typing import Any, Callable, Optional

import numpy as np
import jax

from ..models.paged_kv import KVTierMismatchError
from ..models.transformer import KVCache, decode_step, prefill
from ..obs.flight import flight_dump_for
from ..obs.tracing import span as obs_span
from ..utils.clock import MONOTONIC, Clock


# ---------------------------------------------------------------------------
# typed errors
# ---------------------------------------------------------------------------


class CheckpointError(RuntimeError):
    """A decode checkpoint could not be written or restored (missing file,
    bad magic, truncation, checksum mismatch, or a plan/model signature that
    does not match the resuming runtime)."""


class CheckpointTierMismatchError(KVTierMismatchError, CheckpointError):
    """A checkpoint's KV pages are at a different ``kv_codec`` tier than the
    restoring pool. One error type for both audiences: checkpoint callers
    (``except CheckpointError``) and the unified cross-tier refusal surface
    (``except KVTierMismatchError``) — restore never transcodes."""


class DecodeTimeout(TimeoutError):
    """The host-side watchdog deadline expired mid-loop. A best-effort
    checkpoint was written first when a checkpoint sink was available."""


class StageLostError(RuntimeError):
    """A pipeline stage is dark: every call into the runtime fails until the
    caller fails over to a re-planned runtime."""

    def __init__(self, stage: int):
        super().__init__(
            f"pipeline stage {stage} is dark (marked lost); fail over to a "
            f"re-planned runtime or restore from a checkpoint")
        self.stage = int(stage)


# ---------------------------------------------------------------------------
# configuration
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class StageFailure:
    """Whole-stage loss injection: ``stage`` goes dark at decode step
    ``at_step`` (step 0 = the prefill; in the eval harness the step is the
    chunk index). Distinct from PR 2's link faults — no retry can recover a
    dead device; only failover can."""

    stage: int
    at_step: int

    def __post_init__(self):
        if self.stage < 0:
            raise ValueError(f"stage must be >= 0, got {self.stage}")
        if self.at_step < 0:
            raise ValueError(f"at_step must be >= 0, got {self.at_step}")


@dataclasses.dataclass(frozen=True)
class RecoveryConfig:
    """Everything the survivable decode loop needs, in one knob bundle.

    checkpoint_path: where :class:`DecodeCheckpoint` snapshots land (atomic
        ``.part`` + rename). Required for ``checkpoint_every`` /
        ``halt_at_step`` and for the watchdog's best-effort write.
    checkpoint_every: write a checkpoint every N decode steps (0 = only the
        watchdog's best-effort write and the ``halt_at_step`` hook).
    deadline_s: per-step/per-chunk watchdog deadline (None = no watchdog).
    stage_failure: a :class:`StageFailure` to inject (None = no injection).
    replan: allow the failover path to re-plan the split boundary onto the
        surviving stage(s); with False a lost stage is fatal (the typed
        :class:`StageLostError` propagates).
    max_failovers: hard cap on failovers per generation.
    halt_at_step: test/ops hook — write a checkpoint after decode step k and
        return the partial generation (simulates a kill at an arbitrary
        step without killing the process).
    clock: monotonic time source for the watchdog (a
        :class:`~edgellm_tpu.utils.clock.Clock`; injectable for tests).
    """

    checkpoint_path: Optional[str] = None
    checkpoint_every: int = 0
    deadline_s: Optional[float] = None
    stage_failure: Optional[StageFailure] = None
    replan: bool = True
    max_failovers: int = 1
    halt_at_step: Optional[int] = None
    clock: Clock = MONOTONIC

    def __post_init__(self):
        if self.checkpoint_every < 0:
            raise ValueError("checkpoint_every must be >= 0")
        if self.deadline_s is not None and self.deadline_s <= 0:
            raise ValueError(f"deadline_s must be > 0, got {self.deadline_s}")
        if self.max_failovers < 1:
            raise ValueError("max_failovers must be >= 1")
        if ((self.checkpoint_every or self.halt_at_step is not None)
                and not self.checkpoint_path):
            raise ValueError(
                "checkpoint_every/halt_at_step require checkpoint_path")


@dataclasses.dataclass
class RecoveryCounters:
    """Recovery bookkeeping, reported like PR 2's fault counters: per-call
    totals in the ``stats`` dict / eval result."""

    failovers: int = 0
    replans: int = 0
    recompute_tokens: int = 0
    resume_ok: int = 0
    checkpoints_written: int = 0
    watchdog_fires: int = 0

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


# ---------------------------------------------------------------------------
# watchdog
# ---------------------------------------------------------------------------


class Watchdog:
    """Per-chunk deadline on a monotonic clock.

    ``check`` is called at loop boundaries: within the deadline it re-arms
    (pet-the-dog) and returns; past it, it writes a best-effort checkpoint
    through ``checkpoint_fn`` (errors swallowed — the timeout must surface
    even when the disk is also unhappy) and raises :class:`DecodeTimeout`.
    A host that never reaches ``check`` because a device call blocks forever
    is out of scope for a host-side timer; the deadline guards slow steps
    and inter-chunk hangs, which is where eval loops actually stall.
    """

    def __init__(self, deadline_s: float, clock: Clock = MONOTONIC):
        if deadline_s <= 0:
            raise ValueError(f"deadline_s must be > 0, got {deadline_s}")
        self.deadline_s = float(deadline_s)
        self._clock = clock
        self._armed_at: Optional[float] = None

    def arm(self) -> None:
        self._armed_at = self._clock()

    def expired(self) -> bool:
        return (self._armed_at is not None
                and self._clock() - self._armed_at > self.deadline_s)

    def check(self, checkpoint_fn: Optional[Callable[[], None]] = None,
              what: str = "decode step") -> None:
        if self._armed_at is None:
            self.arm()
            return
        elapsed = self._clock() - self._armed_at
        if elapsed <= self.deadline_s:
            self.arm()
            return
        if checkpoint_fn is not None:
            try:
                checkpoint_fn()
            except Exception:  # noqa: BLE001 — best-effort by contract
                pass
        exc = DecodeTimeout(
            f"{what} exceeded the {self.deadline_s:g}s deadline "
            f"(elapsed {elapsed:.3f}s); a best-effort checkpoint was "
            f"attempted — resume from it instead of re-running")
        # post-mortem at the raise site: the recorder (when armed) captures
        # the span ring + counters exactly once per exception instance, no
        # matter how many catch sites also call dump_for
        flight_dump_for(exc, what=what, deadline_s=self.deadline_s,
                        elapsed_s=round(elapsed, 3))
        raise exc


# ---------------------------------------------------------------------------
# the checkpoint container + binary format
# ---------------------------------------------------------------------------

_MAGIC = b"EDGERECV"
_VERSION = 1
# magic(8) | u32 version | u64 payload_len | u32 crc32(payload)
_HEADER = struct.Struct("<8sIQI")


def _np_dtype(name: str) -> np.dtype:
    try:
        return np.dtype(name)
    except TypeError:
        pass
    try:  # bfloat16 & friends live in ml_dtypes, which jax always ships
        import ml_dtypes

        return np.dtype(getattr(ml_dtypes, name))
    except (ImportError, AttributeError) as e:
        raise CheckpointError(f"checkpoint leaf has unknown dtype "
                              f"{name!r}") from e


class DecodeCheckpoint:
    """A flat ``{name: ndarray}`` dict plus a JSON-able ``meta`` dict, with a
    self-verifying binary serialization.

    Leaves are stored as raw bytes (``.tobytes()``) with their dtype string
    and shape — bit-exact round-trips for every dtype including bfloat16,
    with no pickle in the loop. The payload is framed by magic + version +
    length + CRC32, so restore never feeds a damaged file to the unflattener.

    Stream snapshots (``ContinuousBatcher.checkpoint_stream``) store the
    CONTIGUOUS KV prefix, never pages: a stream whose pages were
    prefix-shared gathers to the same bytes as an unshared one, and restore
    adopts the rows privately — sharing is re-established only by the
    destination pool's own radix index, never carried by the checkpoint.
    """

    def __init__(self, arrays: dict, meta: dict):
        self.arrays = {k: np.asarray(v) for k, v in arrays.items()}
        self.meta = dict(meta)

    def save(self, path: str) -> str:
        with obs_span("recovery.checkpoint_save", path=path) as sp:
            names = sorted(self.arrays)
            leaves = [{"name": n, "dtype": str(self.arrays[n].dtype),
                       "shape": list(self.arrays[n].shape)} for n in names]
            header = json.dumps({"meta": self.meta, "leaves": leaves},
                                sort_keys=True).encode()
            body = b"".join(np.ascontiguousarray(self.arrays[n]).tobytes()
                            for n in names)
            payload = struct.pack("<I", len(header)) + header + body
            blob = _HEADER.pack(_MAGIC, _VERSION, len(payload),
                                zlib.crc32(payload)) + payload
            if sp is not None:
                sp.args["bytes"] = len(blob)
            d = os.path.dirname(os.path.abspath(path))
            os.makedirs(d, exist_ok=True)
            tmp = path + ".part"  # atomic, as in hf_loader.fetch_with_retry
            with open(tmp, "wb") as f:
                f.write(blob)
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, path)
        return path

    @classmethod
    def load(cls, path: str) -> "DecodeCheckpoint":
        with obs_span("recovery.checkpoint_load", path=path):
            try:
                return cls._load_impl(path)
            except CheckpointError as e:
                # a refused restore is a post-mortem moment: snapshot the
                # ring before the caller unwinds (once per instance)
                flight_dump_for(e, path=path)
                raise

    @classmethod
    def _load_impl(cls, path: str) -> "DecodeCheckpoint":
        try:
            with open(path, "rb") as f:
                blob = f.read()
        except OSError as e:
            raise CheckpointError(f"cannot read checkpoint {path}: {e}") from e
        if len(blob) < _HEADER.size:
            raise CheckpointError(
                f"checkpoint {path} is truncated ({len(blob)} bytes < "
                f"{_HEADER.size}-byte header)")
        magic, version, length, crc = _HEADER.unpack_from(blob)
        if magic != _MAGIC:
            raise CheckpointError(
                f"{path} is not a decode checkpoint (bad magic {magic!r})")
        if version > _VERSION:
            raise CheckpointError(
                f"checkpoint {path} has version {version}, this build reads "
                f"<= {_VERSION}; upgrade before resuming")
        payload = blob[_HEADER.size:]
        if len(payload) != length:
            raise CheckpointError(
                f"checkpoint {path} is truncated: header promises {length} "
                f"payload bytes, file has {len(payload)}")
        if zlib.crc32(payload) != crc:
            raise CheckpointError(
                f"checkpoint {path} is corrupted (CRC32 mismatch); restore "
                f"refused — delete it and resume from an older snapshot")
        try:
            (hlen,) = struct.unpack_from("<I", payload)
            header = json.loads(payload[4:4 + hlen].decode())
            meta, leaves = header["meta"], header["leaves"]
        except (struct.error, ValueError, KeyError, UnicodeDecodeError) as e:
            raise CheckpointError(
                f"checkpoint {path} has an unreadable header: {e}") from e
        arrays, off = {}, 4 + hlen
        for leaf in leaves:
            dt = _np_dtype(leaf["dtype"])
            shape = tuple(leaf["shape"])
            n = dt.itemsize * int(np.prod(shape, dtype=np.int64)) if shape \
                else dt.itemsize
            raw = payload[off:off + n]
            if len(raw) != n:
                raise CheckpointError(
                    f"checkpoint {path} leaf {leaf['name']!r} is short "
                    f"({len(raw)} of {n} bytes)")
            arrays[leaf["name"]] = np.frombuffer(raw, dt).reshape(shape).copy()
            off += n
        return cls(arrays, meta)


def runtime_plan_meta(rt: Any) -> dict:
    """The plan/model signature a checkpoint records and resume validates:
    enough to refuse resuming split state onto a different cut layout or a
    different model. Duck-typed — any runtime with ``cfg`` (and, for split
    runtimes, ``split``/``codecs``) works."""
    cfg = rt.cfg
    meta = {
        "mode": "split" if hasattr(rt, "split") else "local",
        "model": {"family": cfg.family, "num_layers": cfg.num_layers,
                  "hidden_size": cfg.hidden_size, "num_heads": cfg.num_heads,
                  "vocab_size": cfg.vocab_size},
    }
    if hasattr(rt, "split"):
        meta["cuts"] = [int(c) for c in rt.split.cuts]
        meta["hop_codecs"] = [c.name for c in rt.codecs]
        # µ-batch pipelining changes no tokens, but a resumed runtime with a
        # different schedule would re-trace decode executables mid-stream
        # and, under faults, draw per-µ-batch fault keys differently — so
        # the schedule is part of the plan signature (1 == sequential)
        pipe = getattr(rt, "pipeline", None)
        meta["num_microbatches"] = int(pipe.num_microbatches) if pipe else 1
    return meta


# ---------------------------------------------------------------------------
# single-device fallback runtime
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("cfg", "capacity",
                                             "compute_dtype"))
def _local_prefill(cfg, params, input_ids, capacity, compute_dtype):
    return prefill(cfg, params, input_ids, capacity,
                   compute_dtype=compute_dtype)


@functools.partial(jax.jit, static_argnames=("cfg", "compute_dtype"),
                   donate_argnames=("cache",))
def _local_step(cfg, params, cache, token_ids, compute_dtype):
    # cache donated: the failover runtime updates its KV buffers in place,
    # same as the split step executable (graph contract "decode.step")
    return decode_step(cfg, params, cache, token_ids,
                       compute_dtype=compute_dtype)


class LocalRuntime:
    """Single-device decode runtime with ``SplitRuntime``'s decode surface.

    The failover target when only one stage survives (no cut is left to
    plan), and the recovery-enabled path for unsplit ``generate``: the cache
    is the same ``{"k", "v", "length"}`` dict the split runtime uses, so the
    checkpoint layer and the serve loop treat both identically. No hops, no
    codecs, no counters — ``link_counters`` reports None like a fault-free
    split runtime."""

    def __init__(self, cfg, compute_dtype=None):
        from ..models.hybrid import refuse_beyond_kv_rows

        refuse_beyond_kv_rows(cfg, "the recovery runtime (LocalRuntime: "
                                    "DecodeCheckpoint, failover)")
        self.cfg = cfg
        self.compute_dtype = compute_dtype
        self.codecs: list = []
        self.faults = None

    def place_params(self, params: dict) -> dict:
        return params  # single device: nothing to shard

    def prefill_decode(self, params: dict, input_ids: jnp.ndarray,
                       capacity: int, fault_step: int = 0) -> tuple:
        logits, kv = _local_prefill(self.cfg, params, input_ids,
                                    int(capacity), self.compute_dtype)
        return logits, {"k": kv.k, "v": kv.v, "length": kv.length}

    def decode_step(self, params: dict, cache: dict,
                    token_ids: jnp.ndarray) -> tuple:
        logits, kv = _local_step(
            self.cfg, params,
            KVCache(cache["k"], cache["v"], cache["length"]), token_ids,
            self.compute_dtype)
        return logits, {"k": kv.k, "v": kv.v, "length": kv.length}

    def mark_stage_lost(self, stage: int) -> None:
        raise ValueError(
            "LocalRuntime runs on a single device — there is no pipeline "
            "stage to lose; stage_failure injection needs a split runtime")

    def link_counters(self, reset: bool = False) -> Optional[dict]:
        return None

    def decode_hop_bytes(self, batch: int) -> list:
        return []  # nothing crosses a wire
