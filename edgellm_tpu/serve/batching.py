"""Continuous batching over the paged KV cache: one compiled ragged step.

``serve/decode.py``'s ``generate`` runs ONE request shape per call — mixed
traffic pads to the worst case or recompiles, ROADMAP item 1's gap. This
module schedules many streams through ONE jitted decode step built on
``models/paged_kv.py``:

- a fixed pool of ``max_slots`` slots rides through
  :func:`~edgellm_tpu.models.paged_kv.paged_decode_step` every step; the page
  table, per-slot lengths, last tokens, RNG key data, step indices and
  temperatures are all TRACED inputs, so admitting, evicting, finishing or
  growing a stream never retraces — the steady state is jit-miss-free by
  construction and :func:`batched_step_cache_size` exposes the counter so
  tests assert it. The host fills one numpy row per running slot for each of
  them, so a step costs the same handful of transfers and ONE dispatch
  whatever ``max_slots`` is (two on the split path: step, then sampler),
  after the one-select merge that hands a slot the token the step before
  sampled for it: ``step()`` launches step N+1 before it reads step N's
  tokens, which stay on the device until the call after;
- prompts are prefetched through the SAME ``_prefill_jit`` executable
  ``generate`` uses, the first token sampled with the same ``fold_in(key, 0)``
  — then the prompt's KV is adopted into the stream's pages; that token 0
  too stays on the device until the device work after it is dispatched (the
  next admission's prefill, or the step's launch, which feeds it);
- sampling inside the batched step reproduces ``decode._sample`` per slot
  bitwise: each slot's key is wrapped INSIDE the jit from the stream's
  ``(2,) uint32`` key data (:func:`_key_data`, fixed when the stream is made:
  the bits of ``jax.random.key(rng_seed)``), ``fold_in`` and ``categorical``
  are vmapped over per-slot (key, step) pairs, greedy rows select the argmax
  lane — so every stream's tokens are bit-identical to running it alone
  through ``generate`` (the ``batching.decode-step-identity`` graphlint
  contract re-proves this on every lint run);
- when the pool runs out of pages the youngest running stream is evicted:
  its pages are gathered back to a contiguous host prefix (byte-identical to
  a contiguous cache) and the stream re-queues; re-admission adopts the
  prefix instead of re-prefilling, and the resumed tokens are bit-identical
  because the per-step keys depend only on (stream key, step index);
- eviction payloads round-trip through
  :class:`~edgellm_tpu.serve.recovery.DecodeCheckpoint` when a
  ``checkpoint_dir`` is configured, so a killed batcher restores mid-flight
  streams from disk; a per-step
  :class:`~edgellm_tpu.serve.recovery.Watchdog` guards wedged steps with the
  same typed :class:`~edgellm_tpu.serve.recovery.DecodeTimeout` the serving
  front already handles;
- a :class:`~edgellm_tpu.models.paged_kv.PrefixCacheConfig` on the
  ``BatchingConfig`` turns on prefix sharing: fresh admits consult a radix
  index of token blocks, map every matched page into the new slot's table
  with ZERO prefill compute (only the unmatched suffix runs, through
  ``decode._prefill_suffix_jit``), and the first in-place write to a shared
  page copy-on-write-forks it; refcount-0 index pages are reclaimed
  LRU-first under pool pressure. Decode output stays token-identical to the
  non-shared path (same pages, same attention span — different bookkeeping);
- passing ``split_runtime=``/``placed_params=`` drives the SAME scheduler
  through ``SplitRuntime.decode_step_paged`` instead of the local pool: the
  host-side :class:`~edgellm_tpu.models.paged_kv.PagedKVCache` runs in
  bookkeeping-only mode (``materialize=False``), the K/V pages live
  per-stage on the mesh (``SplitRuntime.init_paged_pool``), and every ragged
  step crosses the boundary once per cut through the quantized hop ladder —
  batched serving over a split plan, no longer local-pool-only.
- a stack walked by layer kinds (``models/hybrid.py``) has a step executable of
  its own: ``_batched_hybrid_step_jit`` with the per-slot recurrent state of a
  ``granitemoehybrid`` or ``lfm2_moe`` stack (the leaves
  ``hybrid.state_shapes`` names, one donated pytree),
  ``_batched_window_step_jit`` with the second page group of a ``mellum`` stack
  (each sliding-window layer's ring of pages, ``PagedKVCache.window_pool`` /
  ``window_table``): admission adopts a prompt's tail into the rings, eviction
  gathers them with the full layers' rows, and what cannot carry them refuses
  by name. A ``mistral4`` stack rides ``_batched_hybrid_step_jit`` too: its
  pool is ONE leaf of latent rows (``paged_kv.LatentPool``), handed over where
  a K/V pool's one leaf goes, with no state store; admission adopts the
  prefill's rows (``adopt_latent``) and eviction gathers them as stored. A
  ``keye_vl2`` stack rides it with its pool of TWO leaves handed over whole
  (``paged_kv.IndexedPagePool``: K/V rows and index keys under one table);
  ``adopt`` and ``gather_slot`` move both. A ``deepseek_v32`` stack the same
  (``paged_kv.IndexedLatentPool``: latent rows and index keys; ``adopt_latent``
  takes both).

``ServeFront`` integration lives in ``serve/frontend.py`` (``batcher=``):
admission control, brownout and breakers all apply before a request reaches
the batcher — this module is only the inner scheduler.
"""
from __future__ import annotations

import bisect
import functools
import gc
import logging
import os
import threading
import time
from collections import OrderedDict, defaultdict, deque
from dataclasses import dataclass, field
from typing import Any, Optional

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from ..models.configs import ModelConfig
from ..models.hybrid import paged_decode_step_hybrid, refuse_beyond_kv_rows
from ..models.sparse_attn import EVERY_ROW, ROW_GATHER, sparse_read_path
from ..models.paged_kv import INDEX_WALK, INDEXED_POOLS, LATENT_POOLS, \
    PAGE_GATHER, PAGE_WALK, LatentPool, PagePool, OutOfPages, OutOfSlots, PagedKVCache, \
    PrefixCacheConfig, decode_read_path, index_read_path, \
    index_walk_geometry, paged_decode_step, pool_run_pages, \
    resolve_kv_codec, walk_geometry
from ..models.flash_attention import leading_runs
from ..models.transformer import KVCache
from ..obs import context as obs_context
from ..obs.flight import flight_dump_for
from ..obs.tracing import THREAD_USAGE, compile_totals, host_counters, \
    phase as obs_phase, span as obs_span, thread_usage
from ..utils.concurrency import guarded_by
from .decode import _prefill_jit, _prefill_suffix_jit, _sample
from .recovery import (CheckpointError, CheckpointTierMismatchError,
                       DecodeCheckpoint, Watchdog)


def _model_sig(cfg: ModelConfig) -> dict:
    """The same model signature ``recovery.runtime_plan_meta`` records, so a
    paged stream checkpoint refuses restore onto a different model."""
    return {"family": cfg.family, "num_layers": cfg.num_layers,
            "hidden_size": cfg.hidden_size, "num_heads": cfg.num_heads,
            "vocab_size": cfg.vocab_size}


@dataclass(frozen=True)
class BatchingConfig:
    """Pool geometry + scheduler knobs. One compiled step per geometry."""

    page_size: int = 16
    num_pages: int = 65          # includes the reserved trash page 0
    max_slots: int = 4
    pages_per_slot: int = 8
    compute_dtype: Any = None
    cache_dtype: Any = jnp.float32
    checkpoint_dir: Optional[str] = None
    step_deadline_s: Optional[float] = None
    # prefix sharing: a PrefixCacheConfig turns on the radix prefix index +
    # copy-on-write pages (models.paged_kv); None = pre-sharing behavior,
    # bit-for-bit (the batching.prefix-disabled-identity graphlint contract)
    prefix_cache: Optional[PrefixCacheConfig] = None
    # KV-at-rest tier (models.paged_kv.KV_PAGE_CODECS): "fp" stores plain
    # cache_dtype pages and traces the exact pre-quantization step (the
    # batching.kvq-disabled-identity graphlint contract); quantized tiers
    # store packed codes + per-row scales, shrinking bytes-per-token so the
    # same HBM budget admits 2-4x the concurrency (use num_pages_for_bytes
    # to size the pool at fixed bytes)
    kv_codec: str = "fp"

    def __post_init__(self):
        if self.page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {self.page_size}")
        if self.num_pages < 2:
            raise ValueError(
                f"num_pages must be >= 2 (page 0 is reserved), got "
                f"{self.num_pages}")
        if self.max_slots < 1:
            raise ValueError(f"max_slots must be >= 1, got {self.max_slots}")
        if self.pages_per_slot < 1:
            raise ValueError(
                f"pages_per_slot must be >= 1, got {self.pages_per_slot}")
        if self.step_deadline_s is not None and self.step_deadline_s <= 0:
            raise ValueError("step_deadline_s must be positive")
        if self.prefix_cache is not None and not isinstance(
                self.prefix_cache, PrefixCacheConfig):
            raise ValueError(
                f"prefix_cache must be a PrefixCacheConfig or None, got "
                f"{type(self.prefix_cache).__name__}")
        resolve_kv_codec(self.kv_codec)  # refuse unknown tier names early

    @property
    def span(self) -> int:
        return self.pages_per_slot * self.page_size


def _key_data(seed: int) -> np.ndarray:
    """``jax.random.key_data(jax.random.key(seed))`` as a host array, with no
    device work where the default PRNG allows it: threefry2x32 with x64 off
    seeds a key as ``[0, low 32 bits of the seed]`` (``threefry_seed`` shifts
    an int32 right by 32 and masks it with 0xFFFFFFFF). Any other
    implementation or x64 is read back from JAX once."""
    if (jax.config.jax_default_prng_impl == "threefry2x32"
            and not jax.config.jax_enable_x64):
        return np.array([0, seed & 0xFFFFFFFF], np.uint32)
    return np.asarray(jax.random.key_data(jax.random.key(seed)))


@dataclass
class Stream:
    """One request's host-side state across admit/evict/finish."""

    sid: int
    prompt: np.ndarray            # (S,) int32
    max_new_tokens: int
    temperature: float
    rng_seed: int
    status: str = "waiting"       # waiting | running | finished
    slot: int = -1
    tokens: list = field(default_factory=list)  # sampled ids, host ints
    resume: Optional[dict] = None  # gathered {"k","v","length"} for re-admit
    resume_prefix: bool = False   # re-publish the prompt's pages on adopt
    admit_seq: int = -1           # admission order; youngest = largest
    evictions: int = 0
    queued_t: float = 0.0         # monotonic stamp: entered the waiting queue
    # tokens sampled and not yet read: on the device, in no list. A launched
    # step's (one between two calls of ``step()``, two from a launch to the
    # commit of the step before it) and, from a fresh admission to the read
    # of its token 0 (inside the same call), that token
    pending: int = 0
    # the bits of ``key``, fixed for the stream's life: its row of the step's
    # key table, copied per step with no device work
    key_data: np.ndarray = field(init=False, repr=False)

    def __post_init__(self):
        self.key_data = _key_data(self.rng_seed)

    @property
    def t(self) -> int:
        """Next decode-step index == tokens sampled so far, the ones still
        in flight among them (token 0 comes from the prefill, exactly as in
        ``generate``). ``tokens`` holds those the host has read."""
        return len(self.tokens) + self.pending

    @property
    def key(self) -> jax.Array:
        return jax.random.key(self.rng_seed)


@jax.named_scope("unembed_sample")
def _batched_sample(logits, key_data, steps, temps):
    """Per-slot ``decode._sample``, vectorized bit-identically: slot i's
    token equals ``_sample(logits[i:i+1], fold_in(key_i, step_i), temp_i)``
    with ``key_i`` the typed key over row i of ``key_data`` (the
    ``(max_slots, 2) uint32`` table of :func:`_key_data` rows, wrapped here so
    the host never makes a key per slot) — fold_in/categorical vmap to the
    same draws as their single-row calls, argmax rows are batch-invariant,
    and the where just selects which lane slot i uses (temperature stays a
    TRACED per-slot value, so greedy and sampled streams share one
    executable)."""
    keys = jax.random.wrap_key_data(key_data)
    folded = jax.vmap(jax.random.fold_in)(keys, steps)
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    safe = jnp.where(temps > 0.0, temps, 1.0)
    cat = jax.vmap(jax.random.categorical)(
        folded, logits / safe[:, None]).astype(jnp.int32)
    return jnp.where(temps > 0.0, cat, greedy)


@functools.partial(jax.jit,
                   static_argnames=("cfg", "compute_dtype"),
                   donate_argnums=(2,))
def _batched_step_jit(cfg: ModelConfig, params: dict, pool, page_table,
                      lengths, token_ids, key_data, steps, temps,
                      compute_dtype):
    """The ragged step and its sampler for every tier: ``pool`` (a PagePool
    or a QuantPagePool) is donated whole and comes back updated. The fp
    tier's jaxpr is the one the kvq-disabled-identity contract pins."""
    logits, pool = paged_decode_step(
        cfg, params, pool, page_table, lengths, token_ids,
        compute_dtype=compute_dtype)
    return _batched_sample(logits, key_data, steps, temps), pool


@functools.partial(jax.jit,
                   static_argnames=("cfg", "compute_dtype"),
                   donate_argnums=(2, 3, 4))
def _batched_hybrid_step_jit(cfg: ModelConfig, params: dict, pool, state,
                             expert_tokens, page_table, lengths,
                             token_ids, key_data, steps, temps,
                             compute_dtype):
    """The ragged step of a stack with recurrent state
    (``models/hybrid.py``): the K/V pages of the attention layers, the
    per-slot state store of the recurrent layers (every leaf its kinds keep)
    and the per-expert assignment counter are all donated and come back
    updated. A SEPARATE jit: the one-block families keep the executable
    above. ``pool`` is the page pool's ONE leaf, K-then-V rows or a stack of
    latent layers' rows (a stack of sparse-attention layers: its
    ``IndexedPagePool`` / ``IndexedLatentPool`` whole, both leaves donated);
    ``state`` None (and
    back) where the stack keeps none."""
    if compute_dtype is not None:
        params = jax.tree_util.tree_map(
            lambda a: a.astype(compute_dtype)
            if jnp.issubdtype(a.dtype, jnp.floating) else a, params)
    logits, pool, state, expert_tokens = paged_decode_step_hybrid(
        cfg, params, pool, state, expert_tokens, page_table, lengths,
        token_ids)
    return (_batched_sample(logits, key_data, steps, temps),
            pool, state, expert_tokens)


@functools.partial(jax.jit,
                   static_argnames=("cfg", "compute_dtype"),
                   donate_argnums=(2, 3, 4))
def _batched_window_step_jit(cfg: ModelConfig, params: dict, pool, window_pool,
                             expert_tokens, page_table, window_table, lengths,
                             token_ids, key_data, steps, temps,
                             compute_dtype):
    """The ragged step of a stack with sliding-window layers beside full ones
    (``models/hybrid.py``'s walk, no recurrent state): the full layers' page
    pool, the window layers' pool of rings (each a PagePool, addressed through
    its own table) and the per-expert assignment counter are donated and come
    back updated. A SEPARATE jit: the other families keep their executables.
    A ``dots3_note`` stack's two groups are an ``IndexedLatentPool`` (both
    leaves donated) and a ``LatentPool`` of rings."""
    if compute_dtype is not None:
        params = jax.tree_util.tree_map(
            lambda a: a.astype(compute_dtype)
            if jnp.issubdtype(a.dtype, jnp.floating) else a, params)
    # (a pool of two leaves goes and comes back whole, as the step above
    # hands it over; a ring's pool is one leaf, K/V rows or latent rows)
    whole = isinstance(pool, INDEXED_POOLS)
    logits, main, _, expert_tokens, win = paged_decode_step_hybrid(
        cfg, params, pool if whole else pool[0], None, expert_tokens,
        page_table, lengths, token_ids, window=(window_pool[0], window_table))
    return (_batched_sample(logits, key_data, steps, temps),
            main if whole else type(pool)(main), type(window_pool)(win),
            expert_tokens)


def batched_step_cache_size() -> int:
    """Executables compiled for the ragged step so far in this process — the
    jit-miss counter :meth:`ContinuousBatcher.step` reports deltas of."""
    return (_batched_step_jit._cache_size()
            + _batched_hybrid_step_jit._cache_size()
            + _batched_window_step_jit._cache_size()
            + _feed_jit._cache_size())


# the split step returns (max_slots, V) logits from decode_step_paged; the
# sampler is the SAME vmapped _batched_sample, jitted standalone so split
# streams keep the local path's per-slot bit-identity guarantee
_split_sample_jit = jax.jit(_batched_sample)


def _fed_tokens(token_ids, prev_toks):
    """The step's ``token_ids`` where the host knows only some: a slot whose
    last token is still in flight carries ``IN_FLIGHT`` and takes the step
    before's sample for it, on the device."""
    return jnp.where(token_ids == IN_FLIGHT, prev_toks, token_ids)


#: ``token_ids`` of a slot that rode the step in flight (no token id is < 0)
IN_FLIGHT = -1

# one tiny executable ahead of all four launch branches, which keep their
# programs: 0.55 us a run on a v5e beside a 15.6 ms step (PERF.md "PR 42")
_feed_jit = jax.jit(_fed_tokens)


def _with_tok0(toks, slot, tok0):
    """The ids a launch feeds from the device (``_fed_tokens``' second
    argument) with an admission's unread token 0 at its slot."""
    return toks.at[slot].set(tok0[0])


# the slot is traced: one executable for every slot, warmed by the first
# launch behind an admission (an admission's executable, like its prefill:
# ``compiles`` sees it, ``jit_misses`` counts the step's own)
_tok0_feed_jit = jax.jit(_with_tok0)


@dataclass
class _Launched:
    """A launched step whose tokens the host has not read."""

    step: int                     # its index among the launches
    toks: jax.Array               # (max_slots,) sampled ids, on the device
    riders: list                  # the streams that rode it, each in its slot
    t0: float                     # the clock at its launch
    watchdog: Optional[Watchdog]  # armed at its launch


#: clocks of ``stats``/``report()``, seconds, additive (what each is: there)
_CLOCKS = ("step_wall_s", "admit_s", "grow_s", "build_s", "launch_s",
           "sync_s", "commit_s", "queue_wait_s", "admit_step_wall_s",
           "between_s", "tok0_hold_s", "step_cpu_s", "admit_cpu_s",
           "stall_excess_s", "stall_off_cpu_s")


@guarded_by("_stats_lock", fields=["stats"])
class ContinuousBatcher:
    """Admit/evict streams mid-flight into one compiled ragged decode step.

    Lifecycle: :meth:`submit` queues a stream; :meth:`step` admits waiting
    streams into free slots (prefill + page adoption), launches ONE jitted
    step for every running slot, then reads the step launched a call
    earlier: appends each slot's sampled token and retires finished streams.
    When the pool cannot cover a growth it evicts the youngest running
    stream back to the waiting queue with its gathered KV prefix.
    :meth:`run` loops :meth:`step` to completion. ``results[sid]`` holds
    each finished stream's (max_new_tokens,) int32 tokens.

    The step runs ONE launch ahead of its reads: when ``step()`` returns, at
    most one launched step's tokens are still on the device, and the next
    launch takes them from there (``_feed_jit``), so the device has step N+1
    queued while the host reads and commits step N. A stream ends by count,
    so who rides, where each writes and what each samples with are known
    without the ids. The host counts a token in flight (``Stream.t``,
    ``pool.lengths``); ``Stream.tokens`` holds read tokens only. Whatever
    needs a stream's tokens on the host, or rows no step is writing, calls
    :meth:`_drain` first: the old order, sync then commit.

    An admission's token 0 is a token in flight like any other: it is read
    once the device work after it is dispatched. Inside the admit loop that
    is the next admission's prefill and adopt (the read runs ONE admission
    behind the dispatch, so at most two prefills' outputs are live); for the
    loop's last admission it is the step's launch, which takes the id from
    the device (``_tok0_feed_jit``), and the read stands behind it in the
    same call, in ``batch.step.sync``. A call of ``step()`` returns with
    every token 0 it admitted in ``Stream.tokens``; ``_tok0s`` holds the
    unread ones in between, and :meth:`_drain` reads them too.
    """

    def __init__(self, cfg: ModelConfig, params: dict,
                 bcfg: Optional[BatchingConfig] = None, *,
                 split_runtime: Any = None, placed_params: Any = None):
        self.cfg = cfg
        self.params = params
        self.bcfg = bcfg if bcfg is not None else BatchingConfig()
        self.rt = split_runtime
        if cfg.is_hybrid:
            # refused here, at construction, and by name: each would need a
            # snapshot of the recurrent state, or of a window layer's ring,
            # that it does not take
            if split_runtime is not None:
                refuse_beyond_kv_rows(cfg, "the split runtime (SplitRuntime)")
            if self.bcfg.checkpoint_dir is not None:
                refuse_beyond_kv_rows(
                    cfg, "checkpoint_dir (checkpoint_stream/restore_stream)")
        if split_runtime is not None:
            if placed_params is None:
                raise ValueError(
                    "split_runtime needs placed_params (the SplitRuntime's "
                    "placed parameter tree)")
            if self.bcfg.compute_dtype is not None:
                raise ValueError(
                    "compute_dtype is a local-pool knob; the split runtime "
                    "owns its own dtypes — leave it None")
            if getattr(split_runtime, "pipelined", False):
                m = split_runtime.pipeline.num_microbatches
                if self.bcfg.max_slots % m != 0:
                    raise ValueError(
                        f"max_slots={self.bcfg.max_slots} must be a multiple "
                        f"of num_microbatches={m}: every ragged decode step "
                        f"feeds the full slot set through the pipelined "
                        f"schedule, which splits it into {m} equal µ-batches")
                if self.bcfg.kv_codec != "fp":
                    raise ValueError(
                        f"kv_codec={self.bcfg.kv_codec!r} composes with the "
                        f"unpipelined split runtime only; the pipelined "
                        f"µ-batch schedule has no quantized paged step yet")
        self.placed = placed_params
        # split mode: the host PagedKVCache is the ALLOCATOR only (page
        # table, lengths, free list); the actual K/V pages live per-stage on
        # the mesh and move through the runtime's paged scatter/gather
        self.pool = PagedKVCache(
            cfg, num_pages=self.bcfg.num_pages,
            page_size=self.bcfg.page_size, max_slots=self.bcfg.max_slots,
            pages_per_slot=self.bcfg.pages_per_slot,
            dtype=self.bcfg.cache_dtype,
            materialize=split_runtime is None,
            prefix_cache=self.bcfg.prefix_cache,
            kv_codec=self.bcfg.kv_codec)
        self._split_pool = (
            split_runtime.init_paged_pool(self.bcfg.num_pages,
                                          self.bcfg.page_size,
                                          dtype=self.bcfg.cache_dtype,
                                          kv_codec=self.bcfg.kv_codec)
            if split_runtime is not None else None)
        self._streams: dict[int, Stream] = {}
        self._waiting: deque[int] = deque()
        self._slot_to_sid: dict[int, int] = {}
        self._next_sid = 0
        self._admit_seq = 0
        self.results: dict[int, np.ndarray] = {}
        # running aggregates only (a server takes millions of steps: no list
        # grows by the step); a scrape reads report() mid-step: writes lock
        self._stats_lock = threading.Lock()
        self.stats = {"steps": 0, "submitted": 0, "admitted": 0, "evicted": 0,
                      "finished": 0, "jit_misses": 0, "emitted_tokens": 0,
                      "prefill_s": 0.0, "prefill_tokens": 0, "decode_s": 0.0,
                      "occ_sum": 0.0, "slot_sum": 0.0, "alloc_sum": 0.0,
                      "alloc_n": 0, "compiles": 0, "compile_s": 0.0,
                      "routed_assignments": 0, "admit_steps": 0,
                      "steps_ahead": 0, "admits_ahead": 0, "stalls": 0,
                      "attend_pages_walked": 0, "attend_pages_spanned": 0,
                      "attend_pages_in_runs": 0, "steps_judged": 0,
                      "window_pages_walked": 0, "window_pages_spanned": 0,
                      "sparse_rows_live": 0, "sparse_rows_attended": 0,
                      "index_rows_scored": 0, "index_pages_walked": 0,
                      "index_pages_in_runs": 0,
                      "step_wall_hist": _new_step_wall_hist(),
                      **dict.fromkeys(_CLOCKS, 0.0)}
        # the read a sparse-attention layer's decode is built with (None: the
        # stack has no such layer), by the positions a slot can hold
        self.sparse_read = (
            sparse_read_path(cfg, self.bcfg.span, self.pool.pool)
            if cfg.sparse_layers else None)
        # the reads the step's full and window layers are built with (by
        # pool), and the read of a sparse layer's index keys
        (self.decode_read, self.window_read, self.attend_fetches_per_page,
         self.attend_walk, self.index_read, self.index_walk) = \
            self._read_paths()
        # the scheduler thread's own, lock-free between folds: clocks and
        # counts; the clock at each token 0; the last launched step's return
        self._acc: dict[str, float] = defaultdict(int)
        self._tok0_at: list[float] = []
        self._returned: Optional[float] = None
        # the step whose tokens are still on the device; the last read's clock
        self._inflight: Optional[_Launched] = None
        self._read_at = 0.0
        self._judge = _StepJudge()  # what a launched step is judged against
        # the admissions whose token 0 is still on the device, oldest first:
        # (stream, token 0, prompt positions matched, the admission's start);
        # and the clock at the last admission's end
        self._tok0s: deque[tuple] = deque()
        self._admit_end = 0.0
        # where a step's token ids are put: on a mesh, replicated like the
        # sampler's output they are merged with, so that a merged array and
        # an uploaded one are one kind of argument to the step (two kinds,
        # two compiles); on one chip wherever an upload goes
        self._ids_on = (NamedSharding(split_runtime.mesh, PartitionSpec())
                        if split_runtime is not None else None)
        # what a launch with no step in flight sets an unread token 0 into
        self._no_toks = jax.device_put(
            np.zeros((self.bcfg.max_slots,), np.int32), self._ids_on)
        # routed-expert counters of a hybrid stack: assignments per held
        # expert per layer, summed on the device inside the step and read by
        # report() alone (no host sync a step); assignments made, counted
        # here on the host from the running set
        self._expert_tokens = self._expert_tokens_host = None
        if cfg.is_hybrid:
            shape = (cfg.expert_layers, cfg.counted_experts)
            self._expert_tokens = jnp.zeros(shape, jnp.int32)
            self._expert_tokens_host = np.zeros(shape, np.int64)  # last read
        # the step's key table with no stream in it: every row key 0's data,
        # what a free slot samples (and discards) with
        self._free_key_rows = np.tile(_key_data(0), (self.bcfg.max_slots, 1))

    # -- submission --------------------------------------------------------

    def submit(self, prompt_ids, max_new_tokens: int, *,
               temperature: float = 0.0, rng_seed: int = 0) -> int:
        """Queue a stream; same argument semantics as ``generate`` with
        ``rng_key = jax.random.key(rng_seed)``. Returns the stream id."""
        prompt = np.asarray(prompt_ids, np.int32).reshape(-1)
        if prompt.size < 1:
            raise ValueError("empty prompt")
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if float(temperature) < 0.0:
            raise ValueError("temperature must be >= 0")
        need = prompt.size + max_new_tokens - 1  # final token is not written
        if need > self.bcfg.span:
            raise ValueError(
                f"prompt {prompt.size} + {max_new_tokens} new tokens needs "
                f"{need} cache positions > slot span {self.bcfg.span} "
                f"(pages_per_slot={self.bcfg.pages_per_slot} x "
                f"page_size={self.bcfg.page_size})")
        c0 = compile_totals()
        sid = self._next_sid
        with obs_span("batch.submit", sid=sid, prompt_len=int(prompt.size),
                      max_new_tokens=int(max_new_tokens)):
            self._next_sid += 1
            self._streams[sid] = Stream(
                sid, prompt, int(max_new_tokens), float(temperature),
                int(rng_seed), queued_t=time.monotonic())
            self._waiting.append(sid)
            # not through _acc: a front may submit from another thread than
            # the one that steps
            c1 = compile_totals()
            with self._stats_lock:
                self.stats["submitted"] += 1
                self.stats["compiles"] += c1[0] - c0[0]
                self.stats["compile_s"] += c1[1] - c0[1]
        return sid

    def pop_result(self, sid: int) -> np.ndarray:
        """Return and forget a finished stream's tokens. Long-lived callers
        (``ServeFront.drain_batched``) consume results through this so
        finished streams don't accumulate in ``results``/``_streams``."""
        toks = self.results.pop(sid)
        self._streams.pop(sid, None)
        return toks

    def probe_prefix(self, prompt_ids) -> int:
        """Router affinity lookup: how many leading tokens of this prompt the
        paged pool's radix index already holds (a pure dry-run — no stats, no
        refcounts). 0 when prefix sharing is off, so a cluster router can
        probe any replica uniformly."""
        if self.pool.prefix is None:
            return 0
        prompt = np.asarray(prompt_ids, np.int32).reshape(-1)
        return int(self.pool.probe_prefix(prompt)["tokens"])

    def discard(self, sid: int) -> None:
        """Drop a stream in any state and forget its result — the orphan
        hatch: an aborted drain would otherwise leave its inflight streams
        queued forever with no caller to collect them, rerunning on the next
        drain. Frees a running stream's slot and pages."""
        st = self._streams.get(sid)
        if st is not None and st.status == "running":
            self._drain()  # it may be riding the step in flight
        st = self._streams.pop(sid, None)
        self.results.pop(sid, None)
        if st is None:
            return
        if st.status == "running":
            self.pool.free_slot(st.slot)
            del self._slot_to_sid[st.slot]
        elif st.status == "waiting":
            try:
                self._waiting.remove(sid)
            except ValueError:
                pass
        st.status = "discarded"

    # -- admission / eviction ----------------------------------------------

    def _cache_len(self, st: Stream) -> int:
        """Positions st's cache holds at the top of step t: the prompt plus
        the t-1 tokens already fed back (token t-1 is pending feed), the row
        a step in flight writes among them."""
        return st.prompt.size + max(st.t - 1, 0)

    def _microbatch_of(self, slot: int) -> int:
        """Which µ-batch a slot rides in under the pipelined split schedule
        (0 when pipelining is off or the pool is local) — the attribution
        label admit spans and stream checkpoints both record."""
        pipe = (getattr(self.rt, "pipeline", None)
                if self.rt is not None else None)
        m = int(pipe.num_microbatches) if pipe is not None else 1
        return int(slot // (self.bcfg.max_slots // m)) if m > 1 else 0

    def _try_admit(self, sid: int) -> bool:
        """Admit waiting stream ``sid`` if a slot and its pages are free:
        dispatch its prefill, token-0 sample and adopt (a resume: its adopt),
        then read the token 0s of the admissions before it. Its own stays on
        the device (``_tok0s``, ``Stream.pending`` 1) until the next device
        work is dispatched: the next admission's, or the step's launch."""
        st = self._streams[sid]
        need_len = (int(st.resume["length"]) if st.resume is not None
                    else st.prompt.size)
        # feasibility: +1 because the admitting step itself must be
        # coverable. Prefix sharing shrinks the bill — indexed pages map in
        # for free (minus one fork page when the match ends mid-page) — and
        # index-only pages count as available (``ensure`` reclaims them
        # LRU-first under pressure), which is exactly where the
        # more-admits-at-fixed-pool capacity win comes from.
        need_pages = self.pool.pages_for(need_len + 1)
        if st.resume is None and self.pool.prefix is not None:
            pr = self.pool.probe_prefix(st.prompt,
                                        max_tokens=st.prompt.size - 1)
            need_pages = need_pages - pr["pages"] + pr["forks"]
        if need_pages > (self.pool.num_free_pages
                         + self.pool.reclaimable_index_pages):
            return False
        try:
            slot = self.pool.alloc_slot()
        except OutOfSlots:
            return False
        resumed = st.resume is not None
        with obs_phase("batch.admit", sid=sid, slot=slot,
                       microbatch=self._microbatch_of(slot),
                       prompt_len=int(st.prompt.size),
                       resumed=resumed) as ph:
            t0 = time.monotonic()
            try:
                tok0, matched = self._admit_fill(st, slot)
            except OutOfPages:
                # the feasibility probe over-promised (an interior index
                # page can be unreclaimable while a descendant is
                # slot-held): undo cleanly — nothing was committed to the
                # stream yet
                self.pool.free_slot(slot)
                return False
            ph.set(matched=matched)
            self._acc["queue_wait_s"] += t0 - st.queued_t
            self._acc["admitted"] += 1
            st.status, st.slot = "running", slot
            st.admit_seq = self._admit_seq
            self._admit_seq += 1
            self._slot_to_sid[slot] = sid
            # the device has this admission's work: the token 0s before it
            # are read now, ONE admission behind the dispatch
            self._acc["admits_ahead"] += len(self._tok0s)
            self._read_tok0s()
            if tok0 is None:
                self._admitted_at(st, None, matched, t0)
            else:
                # in flight: it rides the step unread, the launch feeds it
                st.pending = 1
                self._tok0s.append((st, tok0, matched, t0))
        return True

    def _read_tok0s(self) -> None:
        """Read every unread token 0, oldest first (the device runs their
        prefills in that order): a host sync each, ``batch.admit.tok0_sync``
        wherever it stands. A stream of one token ends here, by count."""
        while self._tok0s:
            st, tok0, matched, t0 = self._tok0s.popleft()
            with obs_phase("batch.admit.tok0_sync", sid=st.sid):
                tok = int(np.asarray(tok0)[0])
            self._admitted_at(st, tok0, matched, t0)
            st.pending -= 1
            st.tokens.append(tok)
            if len(st.tokens) >= st.max_new_tokens:
                self._finish(st)

    def _admit_fill(self, st: Stream, slot: int) -> tuple:
        """Land one stream's KV into ``slot``'s pages — resume payload,
        full prefill, or (on a prefix-index hit) shared pages plus a
        suffix-only prefill. Returns (the sampled token 0 for fresh admits,
        None for resumes; the prompt positions a prefix hit mapped in).
        Raises :class:`OutOfPages` with the slot still consistent (the
        caller undoes via ``free_slot``)."""
        sid = st.sid
        if st.resume is not None:
            need_len = int(st.resume["length"])
            # resumes adopt privately: the payload mixes prompt and
            # generated rows, so re-sharing would index decode output.
            # Quantized tiers carry PACKED codes + scales (never fp rows),
            # so evict -> readmit round-trips the pool bytes exactly.
            packed = "k_codes" in st.resume
            with obs_phase("batch.admit.adopt", sid=sid):
                if self.rt is not None:
                    self.pool.ensure(slot, need_len)
                    dest = self.pool._flat_indices(slot, need_len)
                    if packed:
                        self._split_pool = self.rt.adopt_paged_rows_packed(
                            self._split_pool, st.resume["k_codes"],
                            st.resume["v_codes"], st.resume["k_scale"],
                            st.resume["v_scale"], dest)
                    else:
                        self._split_pool = self.rt.adopt_paged_rows(
                            self._split_pool, st.resume["k"],
                            st.resume["v"], dest)
                    self.pool.lengths[slot] = need_len
                elif packed:
                    self.pool.adopt_packed(
                        slot, st.resume["k_codes"], st.resume["v_codes"],
                        st.resume["k_scale"], st.resume["v_scale"], need_len)
                elif "rows" in st.resume:
                    self.pool.adopt_latent(slot, st.resume["rows"], need_len,
                                           index=st.resume.get("index"))
                else:
                    self.pool.adopt(slot, jnp.asarray(st.resume["k"]),
                                    jnp.asarray(st.resume["v"]), need_len,
                                    index=st.resume.get("index"))
                if self.pool.state is not None:
                    self.pool.adopt_state(
                        slot, *(st.resume[leaf] for leaf in self.pool.state))
                if "wk" in st.resume:
                    self.pool.adopt_window(slot, st.resume["wk"],
                                           st.resume["wv"], need_len)
                elif "wrows" in st.resume:   # a ring of latent rows
                    self.pool.adopt_window(slot, st.resume["wrows"], None,
                                           need_len)
            st.resume = None
            if st.resume_prefix and self.pool.prefix is not None:
                # migration adopts opt in to re-publishing: the payload's
                # first ``prompt.size`` rows are pure prompt KV (the prefill
                # worker hands off at t == 1), so the radix index survives
                # the transfer. register_prefix walks only the prompt
                # tokens — generated rows are never indexed.
                self.pool.register_prefix(slot, st.prompt)
            return None, 0
        s = st.prompt.size
        matched = 0
        if self.pool.prefix is not None:
            # claim at most s-1 positions: at least one suffix token must
            # run so token 0 has logits to sample from
            matched = self.pool.share_prefix(slot, st.prompt,
                                             max_tokens=s - 1)
        if matched > 0:
            tok0 = (self._prefill_suffix_split(st, slot, matched) if
                    self.rt is not None else
                    self._prefill_suffix_local(st, slot, matched))
        elif self.rt is not None:
            # the exact generate_split() prefill: same executable, same
            # token-0 key, then the per-stage cache rows scatter into the
            # mesh pools at this slot's pages
            with obs_phase("batch.admit.prefill", sid=sid):
                logits, cache = self.rt.prefill_decode(
                    self.placed, jnp.asarray(st.prompt[None, :]),
                    self.bcfg.span)
                tok0 = _sample(logits[:, -1], jax.random.fold_in(st.key, 0),
                               st.temperature)
            with obs_phase("batch.admit.adopt", sid=sid):
                self.pool.ensure(slot, s)
                dest = self.pool._flat_indices(slot, s)
                self._split_pool = self.rt.adopt_paged(
                    self._split_pool, cache, 0, dest, s)
                self.pool.lengths[slot] = s
        else:
            # the exact generate() prefill: same executable, same
            # capacity semantics (KV values are capacity-invariant),
            # same token-0 key
            with obs_phase("batch.admit.prefill", sid=sid):
                last_logits, cache = _prefill_jit(
                    self.cfg, self.params, jnp.asarray(st.prompt[None, :]),
                    self.bcfg.span, self.bcfg.compute_dtype)
                tok0 = _sample(last_logits, jax.random.fold_in(st.key, 0),
                               st.temperature)
            with obs_phase("batch.admit.adopt", sid=sid):
                if self.cfg.latent_layers:
                    self.pool.adopt_latent(
                        slot, cache.rows[:, 0, :s], s,
                        index=(cache.index[:, 0, :s]
                               if self.cfg.sparse_layers else None))
                else:
                    self.pool.adopt(
                        slot, cache.k[:, 0, :s], cache.v[:, 0, :s], s,
                        index=(cache.index[:, 0, :s]
                               if self.cfg.sparse_layers else None))
                if self.cfg.recurrent_state:
                    # the other kind of state a prefill hands on
                    self.pool.adopt_state(
                        slot, *(cache.state[leaf][:, 0]
                                for leaf in self.pool.state))
                if self.cfg.window_latent_layers:
                    # the window layers' latent rows, the tail a ring holds
                    r0 = self.pool.window_ring_start(s)
                    self.pool.adopt_window(slot, cache.wrows[:, 0, r0:s],
                                           None, s)
                elif self.cfg.window_layers:
                    # the sliding layers take the tail their rings hold
                    r0 = self.pool.window_ring_start(s)
                    self.pool.adopt_window(slot, cache.wk[:, 0, r0:s],
                                           cache.wv[:, 0, r0:s], s)
        if self.pool.prefix is not None:
            # publish this prompt's pages (full blocks + partial tail) so
            # later admits share them; already-indexed blocks just refresh
            # their LRU stamps
            self.pool.register_prefix(slot, st.prompt)
        return tok0, matched

    def _prefill_suffix_local(self, st: Stream, slot: int,
                              matched: int) -> jax.Array:
        """Prefix-hit admit, local pool: the ``matched`` shared rows are
        already mapped into ``slot``; gather them into a contiguous cache,
        run ``decode._prefill_suffix_jit`` over ONLY the unmatched suffix,
        and scatter the new rows back (COW-forking the shared tail page).
        Token 0 uses the same ``fold_in(key, 0)`` as the full-prefill path —
        parity with it is the executed ``batching.prefix-token-identity``
        contract."""
        s = st.prompt.size
        with obs_phase("batch.admit.prefill", sid=st.sid):
            state = self.pool.gather_slot(slot)  # the matched prefix rows
            cdtype = (self.bcfg.compute_dtype if self.bcfg.compute_dtype
                      is not None else jnp.float32)
            nl, _, kv, hd = state["k"].shape
            kc = jnp.zeros((nl, 1, self.bcfg.span, kv, hd), cdtype)
            vc = jnp.zeros_like(kc)
            cache = KVCache(kc.at[:, 0, :matched].set(state["k"]),
                            vc.at[:, 0, :matched].set(state["v"]),
                            jnp.asarray(matched, jnp.int32))
            logits, cache = _prefill_suffix_jit(
                self.cfg, self.params,
                jnp.asarray(st.prompt[None, matched:]), cache,
                self.bcfg.compute_dtype)
            tok0 = _sample(logits[:, -1], jax.random.fold_in(st.key, 0),
                           st.temperature)
        with obs_phase("batch.admit.adopt", sid=st.sid):
            self.pool.adopt_rows(slot, cache.k[:, 0, matched:s],
                                 cache.v[:, 0, matched:s], matched, s)
        return tok0

    def _prefill_suffix_split(self, st: Stream, slot: int,
                              matched: int) -> jax.Array:
        """The split twin of :meth:`_prefill_suffix_local`: gather the
        matched rows from the per-stage pools, run the runtime's
        ``verify_step`` (the K-position split pass — B=1, sequential
        schedule) over the suffix tokens, apply the COW fork copies to the
        mesh pools, and scatter the suffix rows into this slot's pages."""
        s = st.prompt.size
        with obs_phase("batch.admit.prefill", sid=st.sid):
            idx = self.pool._flat_indices(slot, matched)
            k_seq, v_seq = self.rt.gather_paged(self._split_pool, idx)
            ns, sz = k_seq.shape[:2]
            kv, hd = k_seq.shape[3:]
            kc = np.zeros((ns, sz, 1, self.bcfg.span, kv, hd), k_seq.dtype)
            vc = np.zeros_like(kc)
            kc[:, :, 0, :matched] = k_seq
            vc[:, :, 0, :matched] = v_seq
            cache = {"k": jnp.asarray(kc), "v": jnp.asarray(vc),
                     "length": jnp.asarray(matched, jnp.int32)}
            logits, cache = self.rt.verify_step(
                self.placed, cache, jnp.asarray(st.prompt[None, matched:]))
            tok0 = _sample(logits[:, -1], jax.random.fold_in(st.key, 0),
                           st.temperature)
        with obs_phase("batch.admit.adopt", sid=st.sid):
            pairs = self.pool.ensure_writable(slot, s)  # bookkeeping forks
            if pairs:
                self._split_pool = self.rt.copy_paged_pages(
                    self._split_pool, [o for o, _ in pairs],
                    [n for _, n in pairs])
            dest = self.pool._flat_indices(slot, s)[matched:]
            self._split_pool = self.rt.adopt_paged_rows(
                self._split_pool, cache["k"][:, :, 0, matched:s],
                cache["v"][:, :, 0, matched:s], dest)
            self.pool.lengths[slot] = s
        return tok0

    def _gather_state(self, slot: int) -> dict:
        """One slot's contiguous K/V prefix as the resume/checkpoint payload.
        Local pool: ``gather_slot``'s (L, n, KV, hd) dict. Split: the
        per-stage (n_stages, sz, n, KV, hd) twin from ``gather_paged`` —
        byte-identical to the rows ``adopt_paged`` scattered, so re-admission
        through ``adopt_paged_rows`` resumes token-identically. Quantized
        tiers gather the PACKED form (codes + scales, raw pool bytes) so the
        round-trip is bit-exact with no requantize."""
        quant = self.bcfg.kv_codec != "fp"
        if self.rt is None:
            # a hybrid stack's payload also carries the slot's recurrent
            # state ({"conv", "ssm"}) or its sliding layers' rings ({"wk",
            # "wv"}); other families add nothing
            return {**(self.pool.gather_slot_packed(slot) if quant
                       else self.pool.gather_slot(slot)),
                    **self.pool.gather_state(slot),
                    **self.pool.gather_window(slot)}
        n = int(self.pool.lengths[slot])
        idx = self.pool._flat_indices(slot, max(n, 1))
        if quant:
            kc, vc, ks, vs = self.rt.gather_paged_packed(
                self._split_pool, idx)
            return {"k_codes": kc[:, :, :n], "v_codes": vc[:, :, :n],
                    "k_scale": ks[:, :, :n], "v_scale": vs[:, :, :n],
                    "length": np.asarray(n, np.int32)}
        k_seq, v_seq = self.rt.gather_paged(self._split_pool, idx)
        return {"k": k_seq[:, :, :n], "v": v_seq[:, :, :n],
                "length": np.asarray(n, np.int32)}

    def evict(self, sid: int) -> None:
        """Push a running stream back to the waiting queue, gathering its
        pages to a contiguous prefix (byte-identical to a contiguous cache,
        so re-admission — here or after a disk round-trip — resumes
        token-identically)."""
        self._drain()  # its tokens on the host, its rows written
        st = self._streams[sid]
        if st.status != "running":
            raise ValueError(f"stream {sid} is not running")
        st.resume = self._gather_state(st.slot)
        self.pool.free_slot(st.slot)
        del self._slot_to_sid[st.slot]
        st.status, st.slot = "waiting", -1
        st.evictions += 1
        st.queued_t = time.monotonic()
        self._waiting.appendleft(sid)  # resumed work goes to the head
        with self._stats_lock:
            self.stats["evicted"] += 1
        if self.bcfg.checkpoint_dir is not None:
            # bound so the checkpoint-save span carries the stream id
            with obs_context.bind(sid=sid):
                self.checkpoint_stream(
                    sid, os.path.join(self.bcfg.checkpoint_dir,
                                      f"stream_{sid}.ckpt"))

    # -- disaggregated prefill handoff ------------------------------------

    def prefill_hold(self, sid: int) -> Optional[Stream]:
        """Disaggregated-prefill admission: admit waiting stream ``sid``
        NOW — the exact fresh-admit prefill runs and token 0 is sampled
        with the same ``fold_in(key, 0)`` as colocated serving — then pin
        its slot with a migration hold instead of decoding. The caller
        (``serve.disagg``'s prefill worker) streams the slot's pages out
        via :meth:`gather_rows` and retires it with
        :meth:`release_handoff`. Returns the Stream, or None when the pool
        cannot admit right now. A ``max_new_tokens == 1`` stream finishes
        at admission (token 0 is the whole answer) and comes back already
        ``finished`` with no held slot."""
        refuse_beyond_kv_rows(
            self.cfg, "disaggregated prefill (prefill_hold / page migration)")
        st = self._streams[sid]
        if st.status != "waiting":
            raise ValueError(f"stream {sid} is not waiting")
        c0 = compile_totals()
        try:
            self._drain()
            if not self._try_admit(sid):
                return None
            self._drain()  # its token 0 on the host: nothing follows it
        finally:
            # admission outside step(): prefill_s and queue_wait_s move,
            # admit_s and step_wall_s (clocks of step() calls) do not
            self._fold_acc(c0)
        self._waiting.remove(sid)
        if st.status == "running":
            self.pool.hold_slot(st.slot)
        return st

    def gather_rows(self, slot: int, start: int, stop: int) -> dict:
        """Rows ``[start, stop)`` of ``slot`` in the pool's at-rest form —
        one migrated page's payload chunk (packed codes + scales on
        quantized tiers, fp rows otherwise; split mode gathers the
        per-stage layout). Concatenating every chunk along the row axis
        reproduces :meth:`_gather_state`'s arrays exactly."""
        self._drain()
        if self.rt is None:
            if self.bcfg.kv_codec != "fp":
                return self.pool.gather_slot_rows_packed(slot, start, stop)
            return self.pool.gather_slot_rows(slot, start, stop)
        idx = self.pool._flat_indices(slot, stop)[start:]
        if self.bcfg.kv_codec != "fp":
            kc, vc, ks, vs = self.rt.gather_paged_packed(
                self._split_pool, idx)
            return {"k_codes": kc, "v_codes": vc,
                    "k_scale": ks, "v_scale": vs}
        k_seq, v_seq = self.rt.gather_paged(self._split_pool, idx)
        return {"k": k_seq, "v": v_seq}

    def release_handoff(self, sid: int) -> None:
        """Retire a prefill-handoff stream: drop the migration hold and
        free the staging slot (its pages have verifiably landed in the
        decode pool, or the handoff was abandoned). The prompt's pages
        stay in the staging prefix index, if enabled, for later shared
        prefills."""
        self._drain()
        st = self._streams.pop(sid)
        if st.status == "running":
            self.pool.release_slot_hold(st.slot)
            self.pool.free_slot(st.slot)
            del self._slot_to_sid[st.slot]
            st.status, st.slot = "finished", -1
        self.results.pop(sid, None)

    def _evict_for_pages(self, needed: int, protect: set) -> bool:
        """Evict youngest-admitted running streams (never ``protect``) until
        ``needed`` pages are free. Youngest-first keeps old streams' work.
        The step in flight is read first: a stream it finishes frees its
        pages and is no victim."""
        self._drain()
        while self.pool.num_free_pages < needed:
            victims = [st for st in self._streams.values()
                       if st.status == "running" and st.sid not in protect]
            if not victims:
                return False
            self.evict(max(victims, key=lambda s: s.admit_seq).sid)
        return True

    def _finish(self, st: Stream) -> None:
        self.results[st.sid] = np.asarray(st.tokens, np.int32)
        self.pool.free_slot(st.slot)
        del self._slot_to_sid[st.slot]
        st.status, st.slot = "finished", -1
        with self._stats_lock:
            self.stats["finished"] += 1
            self.stats["emitted_tokens"] += len(st.tokens)

    # -- the ragged step ---------------------------------------------------

    def _running(self) -> list[Stream]:
        return [self._streams[sid] for sid in self._slot_to_sid.values()]

    def _riders(self) -> list[Stream]:
        """The running streams the next launch carries: a stream whose last
        token is in flight keeps its slot until that token is read, and
        rides no further step."""
        return [st for st in self._running() if st.t < st.max_new_tokens]

    def _grow_writable(self, st: Stream) -> None:
        """Cover this step's write position for ``st`` — allocate growth
        pages AND copy-on-write any shared page the position lands in (the
        first decode write after a prefix-sharing admit forks the shared
        tail page here). With sharing off this is exactly ``pool.ensure``."""
        pairs = self.pool.ensure_writable(st.slot, self._cache_len(st) + 1)
        if pairs and self.rt is not None:
            # bookkeeping-only pool: route the fork copies to the mesh pools
            self._split_pool = self.rt.copy_paged_pages(
                self._split_pool, [o for o, _ in pairs],
                [n for _, n in pairs])

    def _step_cache_size(self) -> int:
        """Executables behind this batcher's ragged step — local: the fused
        step+sample jit; split: the runtime's per-geometry paged step plus
        the standalone sampler. Deltas across a step are the jit misses."""
        if self.rt is not None:
            step_fn = self.rt._paged_decode_fns(self.bcfg.num_pages,
                                                self.bcfg.page_size,
                                                kv_codec=self.bcfg.kv_codec)
            return (step_fn._cache_size() + _split_sample_jit._cache_size()
                    + _feed_jit._cache_size())
        return batched_step_cache_size()

    def _fold_acc(self, c0: tuple, whole: Optional[obs_phase] = None) -> None:
        """Fold the scheduler thread's clocks and counts since the last
        fold into ``stats`` — the one ``_stats_lock`` acquisition of a
        ``step()`` — with the backend compiles since the reading ``c0``."""
        c1 = compile_totals()
        acc = self._acc
        with self._stats_lock:
            self._fold_step(acc, whole, self.stats["step_wall_hist"])
            for k, v in acc.items():
                self.stats[k] += v
            self.stats["compiles"] += c1[0] - c0[0]
            self.stats["compile_s"] += c1[1] - c0[1]
        acc.clear()

    def step(self) -> int:
        """Admit what fits, launch ONE compiled ragged step over every slot
        that rides, then read and commit the step launched a call earlier.
        Returns the number of streams whose step it launched or, when it
        launched none, whose tokens it committed (0 = nothing running,
        nothing in flight and nothing admittable).

        The call is the ``batch.step`` span and the ``step_wall_s`` clock,
        whichever way it returns; its six phases (``batch.step.admit`` /
        ``grow`` / ``build`` / ``launch`` / ``sync`` / ``commit``, clocks
        ``admit_s`` ... ``commit_s``) tile it. The first four are step N+1's,
        the launch this call makes; ``sync`` and ``commit`` are step N's and
        carry its ``step=``. The admit loop reads each admission's token 0
        once the next one is dispatched (``admit_s`` holds those waits); the
        last one's is read in ``sync``, behind the launch and step N's tokens
        (``sync_s`` holds that wait)."""
        c0 = compile_totals()
        whole = obs_phase("batch.step", self._acc, "step_wall_s",
                          cpu_key="step_cpu_s", step=int(self.stats["steps"]),
                          running=len(self._slot_to_sid),
                          waiting=len(self._waiting))
        try:
            with whole:
                return self._step_phases(whole)
        finally:
            self._fold_acc(c0, whole)

    def _step_phases(self, whole: obs_phase) -> int:
        acc = self._acc
        step_no = int(self.stats["steps"])  # launches so far: this one's index
        # admit in FIFO order until a stream doesn't fit (no overtaking:
        # admission order stays deterministic)
        with obs_phase("batch.step.admit", acc, "admit_s", after=whole,
                       cpu_key="admit_cpu_s", step=step_no) as ph:
            admitted = 0
            while self._waiting:
                sid = self._waiting[0]
                if not self._try_admit(sid):
                    break
                self._waiting.popleft()
                admitted += 1
            ph.set(admitted=admitted)
            riders = self._riders()
        if not riders:
            return self._drain(acc, ph)  # nothing to launch: read what flies
        # every riding slot must be able to take this step's token; evict
        # youngest streams when the pool can't cover a growth (oldest first
        # keeps them protected longest)
        with obs_phase("batch.step.grow", acc, "grow_s", after=ph,
                       step=step_no) as ph:
            evicted0 = self.stats["evicted"]
            for st in sorted(riders, key=lambda s: s.admit_seq):
                if st.status != "running":
                    continue  # already evicted by a predecessor's growth
                try:
                    self._grow_writable(st)
                except OutOfPages as e:
                    # a growth may need a fresh page (pages_for grew) OR a
                    # COW fork page (the write position sits in a shared
                    # page) — either way at least one page must come free
                    need = max(1,
                               self.pool.pages_for(self._cache_len(st) + 1)
                               - len(self.pool._slot_pages[st.slot]))
                    if not self._evict_for_pages(need, {st.sid}):
                        # unservable growth: capture the pool state
                        # post-mortem before the scheduler unwinds (once per
                        # instance)
                        flight_dump_for(e, sid=st.sid, slot=st.slot,
                                        free_pages=self.pool.num_free_pages)
                        raise
                    self._grow_writable(st)
            ph.set(evicted=self.stats["evicted"] - evicted0)
            riders = self._riders()
        if not riders:
            return self._drain(acc, ph)

        with obs_phase("batch.step.build", acc, "build_s", after=ph,
                       step=step_no) as ph:
            b = self.bcfg.max_slots
            token_ids = np.zeros((b,), np.int32)
            steps = np.zeros((b,), np.int32)
            temps = np.zeros((b,), np.float32)
            key_data = self._free_key_rows.copy()
            for st in riders:
                # a token in flight is counted and not known: the device
                # has it, and feeds it
                token_ids[st.slot] = (IN_FLIGHT if st.pending
                                      else st.tokens[-1])
                steps[st.slot] = st.t
                temps[st.slot] = st.temperature
                key_data[st.slot] = st.key_data
            # the pool's lengths array is the step's write/mask positions:
            # slot i's cache holds prompt + t-1 fed tokens (== pool lengths
            # by construction, a launch counts its row); inactive slots write
            # the trash page, and so does a slot that keeps its stream but
            # rides no more
            idle = [st.slot for st in self._running()
                    if st.t >= st.max_new_tokens]
            page_table, lengths = self.pool.device_tables(idle)
            misses0 = self._step_cache_size()
            # the pages under each slot's length, the row this step writes
            # included (an idle slot's one trash page)
            reached = self.pool.lengths // self.pool.page_size + 1
            reached[idle] = 1
            if self.decode_read == PAGE_WALK:
                # what a layer's attend fetches this step, a page a DMA,
                # against the table entries a page gather reads whatever
                # they hold
                acc["attend_pages_walked"] = int(np.sum(reached))
                acc["attend_pages_spanned"] = b * self.bcfg.pages_per_slot
            if self.window_read == PAGE_WALK:
                # the same of a window layer's ring: the entries the stream
                # has reached, every one once the ring has turned
                ring = self.pool.window_pages
                acc["window_pages_walked"] = int(np.sum(
                    np.minimum(reached, ring)))
                acc["window_pages_spanned"] = b * ring
            if self.sparse_read is not None:
                # a sparse layer's rows this step, the one it writes among
                # them: a rider's live rows, those its query attends, and
                # those its indexer scores (none where no slot can select)
                live = self.pool.lengths[[st.slot for st in riders]] + 1
                acc["sparse_rows_live"] = int(live.sum())
                acc["sparse_rows_attended"] = int(
                    np.minimum(live, self.cfg.index_topk).sum())
                acc["index_rows_scored"] = (
                    0 if self.sparse_read == EVERY_ROW else int(live.sum()))
            if self.index_read == INDEX_WALK:
                # what a layer's index walk fetches this step: the same
                # pages, of the pool's other leaf
                acc["index_pages_walked"] = int(np.sum(reached))
        with obs_phase("batch.step.launch", acc, "launch_s", after=ph,
                       step=step_no) as ph:
            prev = self._inflight
            watchdog = None
            if self.bcfg.step_deadline_s is not None:
                watchdog = Watchdog(self.bcfg.step_deadline_s)
                watchdog.arm()
            t0 = time.monotonic()
            uploaded = token_ids = jax.device_put(token_ids, self._ids_on)
            # what the device feeds: the step in flight's tokens and the
            # token 0 this call's last admission left unread, at its slot
            feed = prev.toks if prev is not None else None
            for st, tok0, _, _ in self._tok0s:
                feed = _tok0_feed_jit(
                    self._no_toks if feed is None else feed, st.slot, tok0)
            acc["admits_ahead"] += len(self._tok0s)
            if feed is not None:
                token_ids = _feed_jit(token_ids, feed)
            if self.rt is not None:
                # one ragged split step: every cut hops ONE (max_slots, 1, D)
                # quantized activation block, the sampler is the same
                # vmapped _batched_sample the local step fuses in
                logits, self._split_pool = self.rt.decode_step_paged(
                    self.placed, self._split_pool, page_table, lengths,
                    token_ids)
                toks = _split_sample_jit(
                    logits, jnp.asarray(key_data), jnp.asarray(steps),
                    jnp.asarray(temps))
            elif self.cfg.window_layers:
                (toks, self.pool.pool, self.pool.window_pool,
                 self._expert_tokens) = _batched_window_step_jit(
                    self.cfg, self.params, self.pool.pool,
                    self.pool.window_pool, self._expert_tokens, page_table,
                    self.pool.device_window_table(idle), lengths,
                    token_ids, jnp.asarray(key_data),
                    jnp.asarray(steps), jnp.asarray(temps),
                    self.bcfg.compute_dtype)
            elif self.cfg.is_hybrid:
                # the pool's one leaf (K-then-V rows, or a latent stack's
                # rows) and the state store, where the stack keeps one
                # (a pool of two leaves goes and comes back whole)
                kind = type(self.pool.pool)
                leaf = (self.pool.pool if kind in INDEXED_POOLS
                        else self.pool.pool[0])
                toks, leaf, self.pool.state, self._expert_tokens = (
                    _batched_hybrid_step_jit(
                        self.cfg, self.params, leaf, self.pool.state,
                        self._expert_tokens, page_table, lengths,
                        token_ids, jnp.asarray(key_data),
                        jnp.asarray(steps), jnp.asarray(temps),
                        self.bcfg.compute_dtype))
                self.pool.pool = (leaf if kind in INDEXED_POOLS
                                  else kind(leaf))
            else:
                toks, self.pool.pool = _batched_step_jit(
                    self.cfg, self.params, self.pool.pool, page_table,
                    lengths, token_ids, jnp.asarray(key_data),
                    jnp.asarray(steps), jnp.asarray(temps),
                    self.bcfg.compute_dtype)
            toks.copy_to_host_async()  # read a call later, already on its way
            # (counted here, behind the launch: the chip has its step, and
            # a step that follows an admission is not held up by the count)
            acc["attend_pages_in_runs"] = self._pages_in_runs(
                reached, self.attend_walk)
            if self.index_read == INDEX_WALK:
                acc["index_pages_in_runs"] = self._pages_in_runs(
                    reached, self.index_walk)
            if step_no == 0:
                # the merges every later launch runs, compiled by the first: a
                # caller that warmed one step has warmed the steady state
                _feed_jit(uploaded, toks)
                for st, tok0, _, _ in self._tok0s:
                    _tok0_feed_jit(toks, st.slot, tok0)
            # the host counts the token in flight: the next build's step
            # index, write position and "ends by count" all hold it
            for st in riders:
                st.pending += 1
                self.pool.lengths[st.slot] = self._cache_len(st)
            self._inflight = _Launched(step_no, toks, riders, t0, watchdog)
            acc["steps"] = 1
            acc["steps_ahead"] = int(prev is not None)
            acc["jit_misses"] = self._step_cache_size() - misses0
            if self.cfg.is_hybrid:
                acc["routed_assignments"] = (
                    len(riders) * self.cfg.experts_per_tok
                    * self.cfg.expert_layers)
            del toks, token_ids, uploaded, feed, page_table, lengths
        self._read(prev, acc, ph)
        return len(riders)

    def _drain(self, acc: Optional[dict] = None,
               after: Optional[obs_phase] = None) -> int:
        """Read and commit the step in flight, if one is, and read the
        token 0s still on the device: the old order, sync then commit. Called
        by whatever needs every stream's tokens on the host or rows that no
        step is writing, and by a ``step()`` that has nothing to launch
        (which hands over its clocks: everywhere else the two spans' time is
        the enclosing phase's or nobody's). Returns the number of streams it
        committed a step's token for."""
        fl, self._inflight = self._inflight, None
        return self._read(fl, acc, after)

    def _read(self, fl: Optional[_Launched], acc: Optional[dict] = None,
              after: Optional[obs_phase] = None) -> int:
        """``batch.step.sync`` and ``batch.step.commit`` of the launched
        step ``fl``: wait for its tokens, append each to its stream, retire
        the streams that have all of theirs. The sync holds the reads of the
        unread token 0s too, behind the step's (the device ran it first);
        with no step to read it carries no ``step=`` and nothing commits."""
        if fl is None and not self._tok0s:
            return 0
        with obs_phase("batch.step.sync", acc, "sync_s", after=after,
                       **({} if fl is None else {"step": fl.step})) as ph:
            if fl is not None:
                toks_host = np.asarray(fl.toks)  # ONE host sync per step
                # from this step's launch, or the read before it where that
                # came later, to its read: the steps' times do not overlap
                now = time.monotonic()
                self._acc["decode_s"] += now - max(fl.t0, self._read_at)
                self._read_at = now
            self._read_tok0s()
        if fl is None:
            return 0
        with obs_phase("batch.step.commit", acc, "commit_s", after=ph,
                       step=fl.step) as ph:
            finished0 = self.stats["finished"]
            for st in fl.riders:
                # toks_host is already on host (the single np.asarray sync
                # above); this int() is numpy scalar unboxing, not a device
                # sync
                st.pending -= 1
                st.tokens.append(int(toks_host[st.slot]))  # graphlint: disable=EG005
                if len(st.tokens) >= st.max_new_tokens:
                    self._finish(st)
            ph.set(finished=self.stats["finished"] - finished0)
            # unique_live_tokens counts each physical page once: with prefix
            # sharing, summing per-slot lengths would over-count aliased
            # pages against a reserved-capacity denominator that holds them
            # once (identical to live_tokens when nothing is shared)
            live = self.pool.unique_live_tokens
            occ = live / self.pool.token_capacity
            # live tokens per RESERVED token — the denominator is only the
            # pages actually allocated, the paged answer to static
            # batching's worst-case (batch x capacity) reservation
            reserved = (self.pool.num_pages - 1
                        - self.pool.num_free_pages) * self.pool.page_size
            self._acc["occ_sum"] += occ
            self._acc["slot_sum"] += (len(self._slot_to_sid)
                                      / self.bcfg.max_slots)
            if reserved:
                self._acc["alloc_sum"] += live / reserved
                self._acc["alloc_n"] += 1
            if fl.watchdog is not None:
                fl.watchdog.check()
        return len(fl.riders)

    def run(self, max_steps: int = 100_000) -> dict[int, np.ndarray]:
        """Drive :meth:`step` until every submitted stream finished."""
        for _ in range(max_steps):
            if not self._waiting and not self._slot_to_sid:
                break
            if self.step() == 0 and self._waiting:
                exc = OutOfPages(
                    "no stream can make progress: the pool cannot hold even "
                    "one waiting stream — shrink prompts or grow the pool")
                flight_dump_for(exc, waiting=len(self._waiting),
                                free_pages=self.pool.num_free_pages)
                raise exc
        return self.results

    # -- what the fold keeps of one step -----------------------------------

    def _admitted_at(self, st: Stream, tok0, matched: int,
                     t0: float) -> float:
        """The clock where the admission begun at ``t0`` ends, which closes
        its ``prefill_s``: from ``t0``, or the end of the admission before it
        where that came later, so that no second is counted for two. For a
        fresh admission the end is where its token 0 has come to lie on the
        host, which ``tok0_hold_s`` counts from: behind the next admission's
        dispatch or the step's launch, not in its own ``batch.admit``; its
        ``prompt - matched`` positions were prefilled. A resume ends where
        its adopt is dispatched: it prefills nothing and holds no token."""
        now = time.monotonic()
        self._acc["prefill_s"] += now - max(t0, self._admit_end)
        self._admit_end = now
        if tok0 is not None:
            self._tok0_at.append(now)
            self._acc["prefill_tokens"] += int(st.prompt.size - matched)
        return now

    def _fold_step(self, acc: dict, whole: Optional[obs_phase],
                   hist: list) -> None:
        """What a ``step()`` that launched leaves beside its sums, from the
        readings it took anyway (``whole`` is its ``batch.step`` phase, ``acc``
        its clocks; ``hist`` is ``stats["step_wall_hist"]`` and the caller
        holds the lock): its row of the step-wall table, the caller's time
        since the launched step before it, and for a step that admitted, its
        wall and how long each token 0 lay on the host before the call
        returned it. A call that launched nothing is in none of them, and the
        launched step after it has no step before it; nor has the one after
        a step that left nothing running or waiting (a caller need not poll
        an empty batcher): an idle batcher's wait is no hand-off.
        ``prefill_hold()`` (no ``whole``) holds no token.

        And its verdict (``_StepJudge``): the step's wall against the median
        of its kind's, the caller's time before it against
        ``STALL_EXCESS_S``. A stall goes to the ring, to ``stalls`` /
        ``stall_excess_s`` / ``stall_off_cpu_s`` and to the log; a step that
        had an expectation to be held against counts in ``steps_judged``."""
        tok0_at = self._tok0_at
        judge = self._judge
        if whole is not None:
            # what the thread and the process have spent, at every call's end:
            # a stall's record differences the last two
            judge.read(self.stats["evicted"])
        if whole is None or not acc.get("steps"):
            if whole is not None:
                self._returned = None
            tok0_at.clear()
            return
        wall = acc["step_wall_s"]
        row = hist[bisect.bisect_right(_STEP_WALL_EDGES, wall)]
        row[0] += 1
        for i, k in enumerate(_PHASES, 1):
            row[i] += acc[k]
        stalls = []
        if self._returned is not None:
            acc["between_s"] = between = whole.start - self._returned
            if between >= STALL_EXCESS_S:
                stalls.append(judge.between(acc, whole, self._returned))
        idle = not self._slot_to_sid and not self._waiting
        self._returned = None if idle else whole.end
        judge.cpu_returned = whole.cpu_end
        if acc.get("admitted"):
            acc["admit_steps"] = 1
            acc["admit_step_wall_s"] = wall
            acc["tok0_hold_s"] = sum(whole.end - t for t in tok0_at)
            tok0_at.clear()
        judged, stall = judge.step(acc, whole)
        acc["steps_judged"] = int(judged)
        if stall is not None:
            stalls.append(stall)
        if stalls:
            acc["stalls"] = len(stalls)
            acc["stall_excess_s"] = sum(s["excess_s"] for s in stalls)
            acc["stall_off_cpu_s"] = sum(s["off_cpu_s"] for s in stalls)

    # -- checkpoint / restore ----------------------------------------------

    def checkpoint_stream(self, sid: int, path: str) -> str:
        """Snapshot one stream — running (pages gathered) or waiting with a
        resume payload — as a :class:`DecodeCheckpoint`, restorable into ANY
        pool geometry whose span covers it (the payload is the contiguous
        prefix, not pages)."""
        refuse_beyond_kv_rows(self.cfg, "checkpoint_stream")
        self._drain()  # the snapshot's tokens and rows are the same step's
        st = self._streams[sid]
        if st.status == "running":
            state = self._gather_state(st.slot)
        elif st.resume is not None:
            state = st.resume
        else:
            raise CheckpointError(
                f"stream {sid} ({st.status}) has no cache state to snapshot")
        if "k_codes" in state:
            # quantized tier: the CRC-framed payload is the PACKED layout
            # (codes + per-row scales) — restore scatters the same bytes
            # back, so the round-trip is bit-exact across pool geometries
            arrays = {"cache/k_codes": state["k_codes"],
                      "cache/v_codes": state["v_codes"],
                      "cache/k_scale": state["k_scale"],
                      "cache/v_scale": state["v_scale"]}
        else:
            arrays = {"cache/k": state["k"], "cache/v": state["v"]}
        arrays.update({"cache/length": state["length"],
                       "prompt_ids": st.prompt[None, :].astype(np.int32),
                       "tokens": np.asarray(st.tokens, np.int32)[None, :]})
        meta = {"mode": self._ckpt_mode(), "model": _model_sig(self.cfg),
                "sid": int(sid),
                "step": int(st.t - 1), "rng_seed": int(st.rng_seed),
                "temperature": float(st.temperature),
                "max_new_tokens": int(st.max_new_tokens)}
        if self.bcfg.kv_codec != "fp":
            # fp checkpoints keep the pre-quantization meta key set, so old
            # snapshots and fp batchers stay mutually restorable
            meta["kv_codec"] = self.bcfg.kv_codec
        if self.rt is not None:
            # split payloads are per-stage rows — refuse restore onto a
            # different placement the same way recovery checkpoints do
            meta["cuts"] = [int(c) for c in self.rt.split.cuts]
            meta["hop_codecs"] = [c.name for c in self.rt.codecs]
            # the pipelined schedule partitions the slot set into µ-batches;
            # record the count (a plan-signature axis, cross-checked on
            # restore) and — for a running stream — which µ-batch its slot
            # currently rides in, so operators can attribute per-µ-batch
            # fault counters back to streams
            pipe = getattr(self.rt, "pipeline", None)
            m = int(pipe.num_microbatches) if pipe is not None else 1
            meta["num_microbatches"] = m
            if st.status == "running" and m > 1:
                meta["microbatch"] = int(st.slot // (self.bcfg.max_slots // m))
        return DecodeCheckpoint(arrays, meta).save(path)

    def _ckpt_mode(self) -> str:
        return "paged" if self.rt is None else "paged_split"

    def restore_stream(self, path: str) -> int:
        """Re-queue a checkpointed stream; its remaining tokens come out
        bit-identical to the uninterrupted run (per-step keys depend only on
        the seed and the step index, the KV prefix is restored bit-exactly)."""
        refuse_beyond_kv_rows(self.cfg, "restore_stream")
        self._drain()
        ckpt = DecodeCheckpoint.load(path)
        meta = ckpt.meta
        if meta.get("mode") != self._ckpt_mode():
            raise CheckpointError(
                f"{path} is a {meta.get('mode')!r} checkpoint, this batcher "
                f"restores {self._ckpt_mode()!r} stream snapshots")
        if meta.get("model") != _model_sig(self.cfg):
            raise CheckpointError(
                f"{path} was written for model {meta.get('model')!r}, this "
                f"batcher runs {_model_sig(self.cfg)!r}")
        ck = meta.get("kv_codec", "fp")
        if ck != self.bcfg.kv_codec:
            # REFUSAL, not transcode: the payload is raw pool bytes at the
            # checkpoint's tier; rewriting them would silently change the
            # stream's numerics mid-flight (paged_kv.load_state_dict makes
            # the same call for whole-pool snapshots)
            raise CheckpointTierMismatchError(
                offered=ck, pool=self.bcfg.kv_codec, where="restore_stream",
                detail=f"{path} stores {ck!r} KV pages; restore into a "
                       f"batcher built at the checkpoint's tier")
        if self.rt is not None:
            pipe = getattr(self.rt, "pipeline", None)
            want = {"cuts": [int(c) for c in self.rt.split.cuts],
                    "hop_codecs": [c.name for c in self.rt.codecs],
                    # default 1 keeps pre-pipeline checkpoints restorable
                    "num_microbatches": (int(pipe.num_microbatches)
                                         if pipe is not None else 1)}
            for k, v in want.items():
                if meta.get(k, 1 if k == "num_microbatches" else None) != v:
                    raise CheckpointError(
                        f"{path} {k}={meta.get(k)!r} does not match this "
                        f"runtime's {k}={v!r}")
        sid = self.submit(ckpt.arrays["prompt_ids"][0],
                          int(meta["max_new_tokens"]),
                          temperature=float(meta["temperature"]),
                          rng_seed=int(meta["rng_seed"]))
        st = self._streams[sid]
        st.tokens = [int(x) for x in ckpt.arrays["tokens"][0]]
        if ck != "fp":
            st.resume = {"k_codes": ckpt.arrays["cache/k_codes"],
                         "v_codes": ckpt.arrays["cache/v_codes"],
                         "k_scale": ckpt.arrays["cache/k_scale"],
                         "v_scale": ckpt.arrays["cache/v_scale"],
                         "length": int(ckpt.arrays["cache/length"])}
        else:
            st.resume = {"k": ckpt.arrays["cache/k"],
                         "v": ckpt.arrays["cache/v"],
                         "length": int(ckpt.arrays["cache/length"])}
        return sid

    # -- reporting ---------------------------------------------------------

    def report(self) -> dict:
        host = host_counters()  # four small files: read outside the lock
        with self._stats_lock:
            stats = dict(self.stats)  # one consistent snapshot for the scrape
            hist = [row[:] for row in stats["step_wall_hist"]]
            stall_log = list(self._judge.log)
            self._judge.seen(host)
        n = stats["steps"]
        alloc_n = stats["alloc_n"]
        dec = stats["decode_s"]
        emitted = stats["emitted_tokens"]
        pipeline = (self.rt.pipeline_summary()
                    if getattr(self.rt, "pipelined", False) else None)
        return {
            **({"pipeline": pipeline} if pipeline is not None else {}),
            "streams": stats["submitted"],
            "finished": stats["finished"],
            "steps": n,
            "admitted": stats["admitted"],
            "evicted": stats["evicted"],
            "jit_misses": stats["jit_misses"],
            "prefill_s": stats["prefill_s"],
            # launch to read of every step, counted from the read before it
            # where that came later: one step's seconds never hold another's
            "decode_s": dec,
            # (prefill_s above: every admission from its start, or the end of
            # the one before it where that came later, to its token 0 on the
            # host, a resume's to its adopt's dispatch: no second twice)
            # all additive, so report1 - report0 is a window's worth: every
            # step() call entry to return, its six phases, the waiting-queue
            # time of admitted streams, and the backend compiles (any jit's,
            # where jit_misses sees the step executable only) that happened
            # inside submit()/step()/prefill_hold(). And of the step() calls
            # that launched a step alone (_fold_step): admit_step_wall_s, the
            # wall of those that admitted (admit_steps of them); between_s,
            # from the return of one to the entry of the next while there was
            # work, the caller's loop; tok0_hold_s, from a fresh admission's
            # token 0 on the host to the return of the call that admitted it.
            # step_cpu_s / admit_cpu_s: the scheduler thread's CPU seconds
            # (time.thread_time) over what step_wall_s / admit_s enclose: the
            # wall less these is what the thread waited or was descheduled
            **{k: stats[k] for k in _CLOCKS},
            "admit_steps": stats["admit_steps"],
            # the launched steps that were judged against their kind (the
            # launched steps with the same admissions, prefill tokens and
            # "did it evict": _StepJudge), and the stalls: a judged step whose
            # wall lay STALL_EXCESS_S over the median of its kind's last
            # walls, or the caller's time between two steps of that much.
            # stall_excess_s (above): the seconds over, stall_off_cpu_s: those
            # of them the thread was not on a CPU beyond its kind's habit.
            # All additive. stall_log: the last stalls, oldest first, one
            # record each (_StepJudge.between / .step say what is in one)
            "steps_judged": stats["steps_judged"],
            "stalls": stats["stalls"],
            "stall_log": stall_log,
            # the machine's own running totals now (obs.tracing.host_counters:
            # CPU throttling of the container, pressure), None where the
            # machine shows none: report1 - report0 is a window's worth
            "host": host,
            # the launched steps whose predecessor's tokens were still unread
            # at the launch: the device had the next step before the host had
            # this one's tokens (additive; over ``steps``, the share of
            # launches that ran ahead)
            "steps_ahead": stats["steps_ahead"],
            # the fresh admissions whose token 0 was still unread when the
            # device work after them was dispatched, the next admission's or
            # the step's launch: the device had that work before the host had
            # the token (additive; over ``admitted``, the share that ran
            # ahead). One drained early is not among them (an eviction in the
            # call that admitted it, prefill_hold(), a call that launched
            # nothing), nor is a resume, which has no token 0
            "admits_ahead": stats["admits_ahead"],
            # prompt positions admissions prefilled, in prefill_s: a prefix
            # hit's matched positions and a resume's rows are not among them
            "prefill_tokens": stats["prefill_tokens"],
            # the launched steps by wall: row i the steps whose step_wall_s
            # lay in [edge i-1, edge i) of step_wall_edges_s, row 0 under the
            # first edge, the last row at or over the last; a row is [steps,
            # admit_s, grow_s, build_s, launch_s, sync_s, commit_s] of them,
            # additive entry by entry
            "step_wall_hist": hist,
            "step_wall_edges_s": list(_STEP_WALL_EDGES),
            "compiles": stats["compiles"],
            "compile_s": stats["compile_s"],
            "decode_tokens_per_s": (emitted / dec) if dec > 0 else 0.0,
            "occupancy_mean": (stats["occ_sum"] / n) if n else 0.0,
            "slot_util_mean": (stats["slot_sum"] / n) if n else 0.0,
            "alloc_util_mean": ((stats["alloc_sum"] / alloc_n)
                                if alloc_n else 0.0),
            "span": self.bcfg.span,
            "token_capacity": self.pool.token_capacity,
            # the decode read the step was built with, and (additive; 0 on
            # the page gather) the pages a layer's page walk fetched against
            # the table entries a gather would have read; the same of a
            # window layer's ring (the gather, 0 and 0 where no layer slides)
            "decode_read": self.decode_read,
            "attend_fetches_per_page": self.attend_fetches_per_page,
            "attend_pages_walked": stats["attend_pages_walked"],
            "attend_pages_spanned": stats["attend_pages_spanned"],
            # of the walked pages, those fetched as part of a run of adjacent
            # pages, and the copies a layer's walk started for all of them
            "attend_pages_in_runs": stats["attend_pages_in_runs"],
            "attend_dmas": (
                stats["attend_pages_walked"] - stats["attend_pages_in_runs"]
                // self.attend_walk[1] * (self.attend_walk[1] - 1)),
            "window_read": self.window_read,
            "window_pages_walked": stats["window_pages_walked"],
            "window_pages_spanned": stats["window_pages_spanned"],
            **self._sparse_report(stats),
            **({"prefix": self.pool.prefix_report()}
               if self.pool.prefix is not None else {}),
            **self._hybrid_report(stats),
        }

    def _read_paths(self) -> tuple:
        """(``decode_read``, ``window_read``, ``attend_fetches_per_page``,
        ``attend_walk``, ``index_read``, ``index_walk``): what
        ``decode_read_path`` says of the pool the
        full-attention layers read and of the window layers' pool of rings
        (no such pool: the gather, which no step then takes), the DMAs the
        walk starts for a page that goes alone, one a leaf of the pool it
        walks (0: the read is the gather), and the walk's (pages a block,
        pages it takes with one DMA where the groups that lead a block name
        adjacent ones) (``paged_kv.walk_geometry``; a run of 1: every page
        goes alone); then what ``index_read_path`` says of a sparse stack's
        index keys (None: no step scores one) and that walk's block and run
        (``paged_kv.index_walk_geometry``).
        Down here: a line added above would move the prefill kernels' call
        sites, as below."""
        whole = self._split_pool if self.rt is not None else self.pool.pool
        rings = self.pool.window_pool
        # what a sparse layer reads as a full layer does: its K/V leaf (or
        # its leaf of latent rows)
        full = whole
        if isinstance(whole, INDEXED_POOLS):
            full = (LatentPool(whole.rows) if isinstance(whole, LATENT_POOLS)
                    else PagePool(whole.kv))
        # (a step that gathers its chosen rows one by one walks no K/V page;
        # the masked walk is the walk)
        read = (PAGE_GATHER if self.sparse_read == ROW_GATHER
                else decode_read_path(full))
        pps = self.bcfg.pages_per_slot
        ppb, run = (walk_geometry(full, pps) if read == PAGE_WALK
                    else (1, 1))
        index_read = (index_read_path(whole)
                      if self.sparse_read not in (None, EVERY_ROW) else None)
        index_walk = (index_walk_geometry(whole, pps)
                      if index_read == INDEX_WALK else (1, 1))
        # the allocator read the rule off the configuration (it holds no
        # pages in split mode), a walk's caller reads it off ITS leaf: the
        # pool's runs are the longest any leaf's walk takes
        walked = read == PAGE_WALK or index_read == INDEX_WALK
        runs = pool_run_pages(whole, pps) if walked else self.pool.run_pages
        assert runs == self.pool.run_pages, \
            f"the pool hands out runs of {self.pool.run_pages} pages, the " \
            f"walks of its leaves take {runs} with one DMA"
        return (read,
                decode_read_path(rings) if rings is not None else PAGE_GATHER,
                len(full) if read == PAGE_WALK else 0, (ppb, run),
                index_read, index_walk)

    def _pages_in_runs(self, reached, walk: tuple) -> int:
        """Of the pages a layer's walk fetches this step (``reached`` a slot,
        an idle slot's 1; ``walk``: its (block, run), ``attend_walk`` or
        ``index_walk``), those that go as part of a run: the groups that
        lead a block of the host's table as adjacent pages and are live
        whole, which is the table the kernel is handed
        (``flash_attention.leading_runs``, the same function). Of the slots
        that reach a whole group and the blocks they reach: an almost idle
        batch pays for its two streams, not for 192 rows."""
        ppb, run = walk
        rows = np.flatnonzero(reached >= run) if run > 1 else ()
        if not len(rows):
            return 0
        live = reached[rows, None]
        lead = leading_runs(self.pool.page_table[
            rows, :-(-int(live.max()) // ppb) * ppb], run, ppb)
        live = np.clip(live - ppb * np.arange(lead.shape[1]), 0, ppb) // run
        return run * int(np.sum(np.minimum(lead, live)))

    def _sparse_report(self, stats: dict) -> dict:
        """What a stack of sparse-attention layers adds to ``report()``: the
        reads its decode is built with (of the chosen K/V rows, and of the
        index keys) and the attend of its prefill's blocks
        (``sparse_attn.sparse_prefill_path`` of the dtype a prefill computes
        in), three additive counters of rows a sparse layer, counted
        on the host from the riders' lengths: live, attended (``min(length,
        index_topk)`` a rider) and scored by the indexer; and, as
        ``attend_pages_walked`` / ``_in_runs`` are of the K/V walk, the pages
        a layer's index walk fetched and those of them that went as part of
        a run (0 and 0 on the page gather)."""
        if self.sparse_read is None:
            return {}
        from ..models.sparse_attn import sparse_prefill_path  # (down here:
        # a line added to the imports would move the kernels' call sites)
        return {"sparse_read": self.sparse_read,
                "index_read": self.index_read,
                "sparse_prefill": sparse_prefill_path(
                    self.cfg, self.bcfg.compute_dtype
                    or self.params["embed"].dtype),
                **{k: int(stats[k]) for k in (
                    "sparse_rows_live", "sparse_rows_attended",
                    "index_rows_scored", "index_pages_walked",
                    "index_pages_in_runs")}}

    def _hybrid_report(self, stats: dict) -> dict:
        """What a stack with recurrent state and routed experts adds to
        ``report()``: the bytes of the per-slot state store, and the routing
        counters (additive, like the clocks). ``expert_tokens`` is read off
        the device HERE and nowhere else: it waits for the step in flight."""
        if not self.cfg.is_hybrid:
            return {}
        try:
            self._expert_tokens_host = np.asarray(self._expert_tokens,
                                                  np.int64)
        except RuntimeError:
            # a scrape from another thread caught the handle between the
            # step's donation and its return: keep the last reading
            pass
        tokens, held = self._expert_tokens_host, self.cfg.local_experts
        # imported here: a line added above would move the kernels' call
        # sites, whose line numbers are in every step's compile-cache key
        from ..models.moe import grouped_product
        return {"state_bytes": self.pool.state_bytes,
                "state_leaf_bytes": self.pool.state_leaf_bytes,
                # the path a prefill past moe.DENSE_MAX_TOKENS takes through
                # its grouped expert products in this process
                "grouped_product": grouped_product(self.cfg),
                # the sliding layers' rings, rows a window layer: those
                # inside some stream's window now, and all the rings hold
                "window_rows_live": self.pool.window_rows_live,
                "window_rows_capacity": self.pool.window_rows_capacity,
                # a latent stack's rows, a latent layer: of live streams, and
                # what the pages hold; a position's stored bytes a layer
                "latent_rows_live": self.pool.latent_rows_live,
                "latent_rows_capacity": self.pool.latent_rows_capacity,
                "kv_row_bytes": self.pool.kv_row_bytes,
                "expert_tokens": tokens[:, :held].tolist(),
                "routed_assignments": int(stats["routed_assignments"]),
                "routed_local": int(tokens[:, :held].sum()),
                # assignments to identity experts (no weights, no chip's
                # share): the counter's last column where the router has any
                "zero_assignments": int(tokens[:, held:].sum())}


# -- the step-wall table ---------------------------------------------------
# (down here because the lines above ``_admit_fill``'s call sites keep their
# numbers: the Pallas prefill's Mosaic body carries its call stack's line
# numbers into the compile-cache key, PERF.md section 6 "PR 32")

#: the six clocks that tile ``step_wall_s``, a row's columns after its count
_PHASES = ("admit_s", "grow_s", "build_s", "launch_s", "sync_s", "commit_s")

#: bucket edges of ``step_wall_hist``, seconds: 2**(k/4) from 2**-12 (0.244
#: ms) to 2**4 (16 s), so that no bucket is wider than 18.93%
_STEP_WALL_EDGES = tuple(2.0 ** (k / 4 - 12) for k in range(65))


def _new_step_wall_hist() -> list:
    """An empty table: a row under the first edge, one a bucket, one at or
    over the last edge; a row is [steps, *seconds of the six phases]."""
    return [[0] + [0.0] * len(_PHASES)
            for _ in range(len(_STEP_WALL_EDGES) + 1)]


# -- the stall record --------------------------------------------------------
# (down here for the same reason)

#: a launched step is a stall when its wall lies this far over the median of
#: its kind's, and the caller's time between two steps when it is this long:
#: half the 103-124 ms PERF.md section 6 "PR 49" saw, five plain steps' wall
STALL_EXCESS_S = 0.05
#: a kind is judged by the median of its last _KIND_WALLS steps once it has
#: _KIND_MIN; the table keeps the _KINDS_MAX kinds seen last, the ring the
#: last _STALL_LOG_LEN stalls
_KIND_WALLS, _KIND_MIN, _KINDS_MAX, _STALL_LOG_LEN = 9, 5, 128, 64
_LOG = logging.getLogger(__name__)


def _median(values) -> float:
    s = sorted(values)
    return (s[(len(s) - 1) // 2] + s[len(s) // 2]) / 2


def _gc_collections() -> list:
    return [g["collections"] for g in gc.get_stats()]


class _StepJudge:
    """What ``ContinuousBatcher._fold_step`` holds a launched step against,
    and the ring of those that failed; written by the scheduler thread under
    the fold's lock, which ``report()`` copies the ring under.

    A step's KIND is (streams it admitted, prompt positions it prefilled,
    whether it evicted): the same device work to first order, and a closed
    loop has a handful. A kind keeps its last ``_KIND_WALLS`` steps as rows
    of [wall, the six phases, wall - the thread's CPU seconds]; with
    ``_KIND_MIN`` of them the median wall is what the next step of the kind
    is expected to take. On a sound step's path: one ``thread_usage`` and
    one ``time.process_time`` a call, one lookup, a median of nine, one
    append. Everything else is read at a stall."""

    def __init__(self):
        self.kinds: OrderedDict = OrderedDict()  # kind -> its last rows
        self.log: deque = deque(maxlen=_STALL_LOG_LEN)
        # (thread_usage(), time.process_time(), stats["evicted"]) at the end
        # of the last step() call and of the one before it
        self.now = self.before = (thread_usage(), time.process_time(), 0)
        # the thread's clock where ``_returned`` was read
        self.cpu_returned = 0.0
        # what a stall differences against: the collections a generation and
        # the host's counters (with the clock) at the last stall or report()
        self.gc_seen = _gc_collections()
        self.host_seen = (host_counters(), time.monotonic())

    def read(self, evicted: int) -> None:
        self.before, self.now = self.now, (
            thread_usage(), time.process_time(), evicted)

    def seen(self, host: dict) -> None:
        """``host`` is the host's counters now (a stall's, ``report()``'s):
        the next stall's deltas, these and the collections, count from
        here. Under the fold's lock, like the ring."""
        self.gc_seen, self.host_seen = _gc_collections(), (
            host, time.monotonic())

    def between(self, acc: dict, whole: obs_phase, returned: float) -> dict:
        """The record of a caller that took ``STALL_EXCESS_S`` or more from
        the last launched step's return (``returned``) to the entry of
        ``whole``: all of it is excess, its CPU seconds are the thread's
        between the two calls."""
        wall = whole.start - returned
        cpu = whole.cpu_start - self.cpu_returned
        return self._stall("between", acc, whole, returned, wall, 0.0,
                           dict.fromkeys(_PHASES, 0.0), cpu, wall - cpu)

    def step(self, acc: dict, whole: obs_phase) -> tuple:
        """(whether the launched step of ``whole`` had an expectation, its
        stall record or None), and the step joins its kind."""
        wall, cpu = acc["step_wall_s"], acc["step_cpu_s"]
        kind = (acc.get("admitted", 0), acc.get("prefill_tokens", 0),
                self.now[2] != self.before[2])
        rows = self.kinds.get(kind)
        if rows is None:
            rows = self.kinds[kind] = deque(maxlen=_KIND_WALLS)
            if len(self.kinds) > _KINDS_MAX:
                self.kinds.popitem(last=False)
        else:
            self.kinds.move_to_end(kind)
        row = (wall, *[acc[k] for k in _PHASES], wall - cpu)
        judged, stall = len(rows) >= _KIND_MIN, None
        if judged:
            expected = _median([r[0] for r in rows])
            if wall - expected > STALL_EXCESS_S:
                meds = [_median(col) for col in zip(*rows)]
                over = {k: row[i] - meds[i] for i, k in enumerate(_PHASES, 1)}
                stall = self._stall(
                    max(over, key=over.get)[:-2], acc, whole, whole.start,
                    wall, expected, dict(zip(_PHASES, row[1:])), cpu,
                    row[-1] - meds[-1])
        rows.append(row)
        return judged, stall

    def _stall(self, where: str, acc: dict, whole: obs_phase, t_s: float,
               wall: float, expected: float, phases: dict, cpu: float,
               off_cpu: float) -> dict:
        """One record, appended to the ring and logged. ``step`` is the
        ``step=`` of the ``batch.step`` span (a capture's key), ``t_s`` the
        stall's start on ``time.monotonic``; ``where`` the phase with the
        largest excess over its kind's median, or ``between``. ``cpu_s`` is
        the thread's in ``wall_s``, ``off_cpu_s`` the wall less it, less the
        kind's median of the same. Counted from the end of the call before,
        so with the caller's time in them: ``cpu_user_s`` / ``cpu_sys_s``,
        ``nvcsw`` (it blocked) / ``nivcsw`` (it was preempted), ``minflt`` /
        ``majflt`` (None where the platform keeps none a thread) and
        ``proc_cpu_s``, all the process's threads. ``gc`` is the collections
        a generation and ``host_delta`` the host's counters since the
        reading ``host_age_s`` ago: the last stall or ``report()``."""
        (u0, p0, _), (u1, p1, _) = self.before, self.now
        usage = (dict.fromkeys(THREAD_USAGE) if u0 is None or u1 is None else
                 {k: b - a for k, a, b in zip(THREAD_USAGE, u0, u1)})
        gc0, (host0, at0), host = self.gc_seen, self.host_seen, host_counters()
        self.seen(host)
        rec = {
            "step": whole.attrs.get("step"), "t_s": t_s, "where": where,
            "wall_s": wall, "expected_s": expected,
            "excess_s": wall - expected,
            "admitted": acc.get("admitted", 0),
            "prefill_tokens": acc.get("prefill_tokens", 0),
            "running": whole.attrs.get("running"),
            "waiting": whole.attrs.get("waiting"),
            **phases, "cpu_s": cpu, "off_cpu_s": off_cpu, **usage,
            "proc_cpu_s": p1 - p0,
            "gc": [b - a for a, b in zip(gc0, self.gc_seen)],
            "host": host,
            "host_delta": {k: None if None in (host0[k], v) else v - host0[k]
                           for k, v in host.items()},
            "host_age_s": self.host_seen[1] - at0,
        }
        self.log.append(rec)
        throttled = rec["host_delta"]["cpu_throttled_s"]
        _LOG.warning(
            "batch.step %s stalled in %s: %.1f ms over its kind's %.1f ms, "
            "%.0f%% of it off the CPU (blocked %s times, preempted %s); "
            "the container %s in the last %.1f s",
            rec["step"], where, 1e3 * rec["excess_s"], 1e3 * expected,
            100.0 * off_cpu / rec["excess_s"], usage["nvcsw"],
            usage["nivcsw"],
            "shows no cpu.stat" if throttled is None else
            "was throttled %.1f ms (%s periods)" % (
                1e3 * throttled, rec["host_delta"]["nr_throttled"]),
            rec["host_age_s"])
        return rec
