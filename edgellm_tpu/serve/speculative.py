"""Speculative decoding across the split boundary: stage-0 draft, k-token
batched verify.

Every vanilla decode token costs a full round of boundary hops, so per-stream
latency is bounded by link round-trips no matter how fast the fused hops get
— the TAH-QUANT regime where activation transfer dominates step time. This
module amortizes the hop k-fold: a cheap DRAFT model — the stage-0 prefix of
the full model's layers, early-exiting through the full model's final norm
and unembedding — proposes k-1 tokens entirely on stage 0 (no hops), and the
split model VERIFIES the whole window in ONE ``SplitRuntime.verify_step``:
each cut moves one quantized (1, k, D) activation block through the
unchanged fused/faulty/FEC hop ladder instead of k single-token payloads.

The burst protocol (committed tokens ``c_0..c_{n-1}``; the target cache
holds the prompt plus ``c_0..c_{n-2}`` — the last sampled token is never fed
back yet, the same invariant the vanilla loop keeps):

1. draft ``d_1..d_{k-1}`` by greedy argmax, feeding ``c_{n-1}`` first;
2. verify inputs ``x = [c_{n-1}, d_1, .., d_{k-1}]`` in one q_len=k pass —
   position j's logits are exactly the distribution for global step
   ``n + j`` given the drafts up to j were right;
3. accept: at ``temperature == 0`` draft j is accepted iff it equals the
   argmax of position j-1's logits, so every emitted token is the argmax the
   vanilla loop would have produced — greedy spec output is TOKEN-IDENTICAL
   to vanilla ``generate_split`` by construction. At ``temperature > 0``
   standard residual resampling applies against the argmax (point-mass)
   draft: accept ``d_j`` with probability ``p(d_j)``, else sample from
   ``p`` with ``p(d_j)`` zeroed and renormalized — the emitted marginal is
   exactly ``p`` (distribution-identical, not bitwise: the accept/reject
   draws use their own ``fold_in`` lanes);
4. commit: the verify pass already wrote all k K/V rows; acceptance is a
   LENGTH rewrite (garbage past the fill level is masked — rollback moves no
   data). The draft cache rolls the same way, plus one catch-up draft step
   on a fully-accepted burst to backfill the row its k-1 draft steps never
   wrote.

Every burst emits 1..k tokens for ONE boundary round-trip, so measured
hops-per-token is ``bursts / emitted`` — below 1.0 whenever the draft agrees
at all (k=1 degenerates to the vanilla cost and serves as the correctness
anchor). Both the draft step and the verify step are compiled once per
(capacity, k): the fill level rides as a traced scalar, so the loop is
jit-miss-free after the first burst.

Checkpointing reuses ``serve.decode._write_checkpoint`` unchanged: a burst
boundary IS the vanilla loop invariant, so the same ``DecodeCheckpoint``
round-trips and :func:`resume_speculative` resumes token-identically (the
draft cache is rebuilt by a draft prefill over the committed prefix; burst
boundaries depend only on the committed prefix, so the resumed burst
sequence matches the uninterrupted run's).
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any, Optional

import numpy as np
import jax
import jax.numpy as jnp
from jax.typing import ArrayLike

from ..models.configs import ModelConfig
from ..models.transformer import (KVCache, _slice_layers,
                                  cache_from_state_dict, decode_step, prefill)
from ..obs.latency import LatencyObserver
from ..obs.metrics import (CounterSource, get_registry, record_decode_stats,
                           record_link_counters, record_link_health,
                           record_recovery_counters, record_spec_stats,
                           record_wire_bytes)
from ..obs import context as obs_context
from ..obs.tracing import span as obs_span
from ..obs.tracing import tracing_enabled
from .decode import (_emit_hop_spans, _sample, _validate_decode_args,
                     _write_checkpoint)
from .recovery import (CheckpointError, DecodeCheckpoint, DecodeTimeout,
                       RecoveryConfig, RecoveryCounters, Watchdog,
                       runtime_plan_meta)

MAX_SPEC_K = 16  # verify window ceiling: beyond this the draft rarely holds
DRAFT_SOURCES = ("stage0",)


@dataclasses.dataclass(frozen=True)
class SpecConfig:
    """Speculative-decode knobs. ``k`` is the verify window (q_len): each
    burst drafts k-1 tokens and verifies k positions in one split pass.
    ``draft_source`` names where the draft comes from — ``"stage0"`` is the
    truncated-layer early-exit head over the first ``draft_layers`` layers
    (default: everything stage 0 already owns, i.e. first cut + 1). The
    acceptance rule is implied by the temperature: lossless greedy exact
    match at 0, residual resampling above. ``enabled=False`` is the
    contractual no-op: the serving loop never touches the draft or the
    verify executable, so the built graphs are jaxpr-fingerprint-identical
    to the pre-spec ones (graphlint re-proves this every run)."""

    enabled: bool = True
    k: int = 4
    draft_source: str = "stage0"
    draft_layers: Optional[int] = None

    def __post_init__(self):
        if not isinstance(self.k, int) or isinstance(self.k, bool):
            raise ValueError(f"k must be an int, got {self.k!r}")
        if not 1 <= self.k <= MAX_SPEC_K:
            raise ValueError(
                f"k must be in [1, {MAX_SPEC_K}], got {self.k}")
        if self.draft_source not in DRAFT_SOURCES:
            raise ValueError(
                f"unknown draft_source {self.draft_source!r}; "
                f"supported: {DRAFT_SOURCES}")
        if self.draft_layers is not None and (
                not isinstance(self.draft_layers, int)
                or isinstance(self.draft_layers, bool)
                or self.draft_layers < 1):
            raise ValueError(
                f"draft_layers must be a positive int or None, got "
                f"{self.draft_layers!r}")


def draft_from_params(cfg: ModelConfig, raw_params: dict, spec: SpecConfig,
                      cut: Optional[int] = None) -> tuple:
    """Build the stage-0 early-exit draft: the first ``draft_layers`` layers
    of the full model, re-using the FULL model's embedding, final norm and
    unembedding as the exit head (no extra weights, no training — the
    residual stream is read out early). ``cut`` (the first split cut) bounds
    ``draft_layers`` so the draft never needs weights stage 0 doesn't hold.
    Returns (draft_cfg, draft_params) for ``transformer.prefill``/
    ``decode_step``."""
    from ..models.hybrid import refuse_beyond_kv_rows

    refuse_beyond_kv_rows(cfg, "speculative decoding (the draft and the "
                                "verify rollback)")
    limit = (cut + 1) if cut is not None else cfg.num_layers
    n = spec.draft_layers if spec.draft_layers is not None else limit
    if not 1 <= n <= limit:
        raise ValueError(
            f"draft_layers={n} must be in [1, {limit}] — stage 0 owns "
            f"layers 0..{limit - 1} and the draft must run hop-free there")
    draft_cfg = dataclasses.replace(cfg, num_layers=n)
    draft_params = {k: v for k, v in raw_params.items() if k != "layers"}
    draft_params["layers"] = _slice_layers(raw_params["layers"], 0, n)
    return draft_cfg, draft_params


# the draft runs the unsplit transformer entry points on stage 0's device —
# no hops, no collectives; cfg/capacity are static, the cache is donated, so
# the whole run compiles exactly one prefill and one step executable
@functools.partial(jax.jit,
                   static_argnames=("cfg", "capacity", "compute_dtype"))
def _draft_prefill_jit(cfg: ModelConfig, params: dict, input_ids, capacity,
                       compute_dtype):
    logits, cache = prefill(cfg, params, input_ids, capacity,
                            compute_dtype=compute_dtype)
    return logits[:, -1], cache


@functools.partial(jax.jit, static_argnames=("cfg", "compute_dtype"),
                   donate_argnames=("cache",))
def _draft_step_jit(cfg: ModelConfig, params: dict, cache: KVCache,
                    token_ids, compute_dtype):
    logits, cache = decode_step(cfg, params, cache, token_ids,
                                compute_dtype=compute_dtype)
    # the draft proposal is always the argmax (a point-mass draft keeps the
    # residual-resampling math exact at any temperature)
    return jnp.argmax(logits, axis=-1).astype(jnp.int32), cache


def draft_step_cache_size() -> int:
    """Executables compiled for the draft step so far in this process — the
    jit-miss counter the spec loop reports deltas of."""
    return _draft_step_jit._cache_size()


def spec_capacity(prompt_len: int, max_new_tokens: int, k: int) -> int:
    """Cache rows a speculative run can touch: the last burst may start with
    ``max_new_tokens - 1`` committed tokens and still write all k verify
    rows past the vanilla fill level."""
    return max(prompt_len + max_new_tokens,
               prompt_len + max_new_tokens + k - 2)


def generate_speculative(rt: Any, placed_params: dict, prompt_ids: ArrayLike,
                         max_new_tokens: int,
                         *,
                         spec: SpecConfig,
                         capacity: Optional[int] = None,
                         temperature: float = 0.0,
                         rng_key: Optional[jax.Array] = None,
                         fault_step: int = 0,
                         stats: Optional[dict] = None,
                         recovery: Optional[RecoveryConfig] = None,
                         raw_params: Optional[dict] = None,
                         link_health: Optional[Any] = None,
                         compute_dtype=None,
                         observe: Optional[LatencyObserver] = None
                         ) -> jnp.ndarray:
    """``generate_split`` with the speculative burst loop. Same contract and
    return shape ((1, max_new_tokens) int32 — speculation is a per-stream
    latency lever, so batch is 1); greedy output is token-identical to the
    vanilla loop on the same seed/plan. ``raw_params`` (the unplaced pytree)
    is required: the stage-0 draft is sliced out of it. ``recovery``
    supports checkpointing/halt/watchdog at burst granularity; stage-failure
    injection is refused (failover re-plans the runtime mid-run, which would
    reshape the verify window — run failover drills on the vanilla loop)."""
    if not spec.enabled:
        raise ValueError("generate_speculative called with spec.enabled="
                         "False; use generate_split (which this disabled "
                         "config leaves byte-identical)")
    if not hasattr(rt, "verify_step"):
        raise ValueError(
            "speculative decoding needs the split runtime's k-token "
            f"verify_step; {type(rt).__name__} has none")
    if raw_params is None:
        raise ValueError(
            "speculative decoding needs raw_params= (the unplaced parameter "
            "pytree) to slice out the stage-0 draft layers")
    if recovery is not None and recovery.stage_failure is not None:
        raise ValueError(
            "speculative decoding does not compose with stage-failure "
            "injection (failover re-plans the runtime mid-run); run "
            "failover drills on the vanilla loop")
    need = spec_capacity(np.asarray(prompt_ids).shape[-1], max_new_tokens,
                         spec.k)
    if capacity is None:
        capacity = need
    elif capacity < need:
        raise ValueError(
            f"speculative cache overflow: the verify burst writes past the "
            f"vanilla fill level, needs capacity >= {need}, got {capacity}")
    prompt_ids, capacity, temperature, key = _validate_decode_args(
        prompt_ids, max_new_tokens, capacity, temperature, rng_key)
    if prompt_ids.shape[0] != 1:
        raise ValueError(
            f"speculative decoding is per-stream (batch=1), got batch "
            f"{prompt_ids.shape[0]}; route batches through the batcher")
    cut = None
    if getattr(rt, "split", None) is not None and rt.split.cuts:
        cut = int(rt.split.cuts[0])
    draft_cfg, draft_params = draft_from_params(rt.cfg, raw_params, spec, cut)
    return _spec_loop(rt, placed_params, prompt_ids, max_new_tokens,
                      capacity, temperature, key, fault_step, spec,
                      draft_cfg, draft_params, compute_dtype, stats,
                      recovery, link_health=link_health, observe=observe)


def _spec_loop(rt, placed, prompt_ids, max_new_tokens: int, capacity: int,
               temperature: float, key, fault_step: int, spec: SpecConfig,
               draft_cfg: ModelConfig, draft_params: dict, compute_dtype,
               stats: Optional[dict], rec: Optional[RecoveryConfig],
               link_health=None, resume_state=None, resumed: bool = False,
               observe: Optional[LatencyObserver] = None) -> jnp.ndarray:
    """The burst loop. ``resume_state`` = (last_done_step, toks, cache)
    continues a checkpointed run from the burst boundary at step
    ``last_done_step`` (the draft cache is rebuilt by a draft prefill over
    the committed prefix)."""
    b, s = prompt_ids.shape
    k = spec.k
    counters = RecoveryCounters()
    wd = (Watchdog(rec.deadline_s, clock=rec.clock)
          if rec is not None and rec.deadline_s is not None else None)
    run_meta = {"capacity": int(capacity), "temperature": float(temperature),
                "max_new_tokens": int(max_new_tokens),
                "fault_step": int(fault_step), "prompt_len": int(s),
                "batch": int(b),
                "speculative": {"k": int(k),
                                "draft_source": spec.draft_source,
                                "draft_layers": int(draft_cfg.num_layers)}}
    counters0 = rt.link_counters() if isinstance(rt, CounterSource) else None
    draft_misses0 = draft_step_cache_size()
    halted_at = None
    if observe is not None:
        observe.start()
    if wd is not None:
        wd.arm()

    def checkpoint(toks, cache, t):
        _write_checkpoint(rec, rt, counters, prompt_ids, toks, cache, key,
                          t, run_meta)

    t0 = time.monotonic()
    if resume_state is None:
        with obs_span("generate_spec.prefill", batch=b, prompt_len=s):
            logits, cache = rt.prefill_decode(placed, prompt_ids, capacity,
                                              fault_step=fault_step)
            tok = _sample(logits[:, -1], jax.random.fold_in(key, 0),
                          temperature)
            # draft prefill over the same prompt: fills the stage-0 cache to
            # the same level (its token-0 logits are discarded — token 0 is
            # the target's, same as vanilla)
            _, dcache = _draft_prefill_jit(draft_cfg, draft_params,
                                           prompt_ids, capacity,
                                           compute_dtype)
            jax.block_until_ready(tok)
        if observe is not None:
            observe.first_token(tok)
        t1 = time.monotonic()
        toks = [np.asarray(tok, np.int32)]
        if rec is not None and rec.halt_at_step == 0:
            checkpoint(toks, cache, 0)
            halted_at = 0
        elif (rec is not None and rec.checkpoint_every
                and rec.checkpoint_path):
            checkpoint(toks, cache, 0)
    else:
        last_done, toks_in, cache = resume_state
        toks = [np.asarray(x, np.int32).reshape(b) for x in toks_in]
        prompt_np = np.asarray(prompt_ids, np.int32)
        fed = (np.concatenate(
            [prompt_np] + [t[:, None] for t in toks[:-1]], axis=1)
            if len(toks) > 1 else prompt_np)
        with obs_span("generate_spec.resume_draft_prefill",
                      prefix_len=int(fed.shape[1])):
            _, dcache = _draft_prefill_jit(draft_cfg, draft_params,
                                           jnp.asarray(fed), capacity,
                                           compute_dtype)
        t1 = t0

    n = len(toks)
    drafted = accepted = rejected = bursts = 0
    emitted_total = 0
    with obs_span("generate_spec.burst_loop", k=k,
                  budget=max_new_tokens - n):
        while halted_at is None and n < max_new_tokens:
            t_prev = n - 1
            # ---- draft k-1 tokens on stage 0, greedy, hop-free ----
            feed = [toks[-1]]  # x_0 = last committed token
            for _ in range(1, k):
                dtok, dcache = _draft_step_jit(
                    draft_cfg, draft_params, dcache,
                    jnp.asarray(feed[-1]), compute_dtype)
                feed.append(np.asarray(dtok, np.int32))
            drafted += k - 1
            # ---- verify all k positions in ONE split pass (one hop round
            # per cut, carrying the (1, k, D) block) ----
            x = jnp.asarray(np.stack(feed, axis=1))  # (1, k)
            vlogits, vcache = rt.verify_step(placed, cache, x)
            bursts += 1
            # ---- accept ----
            emitted = []  # np (1,) int32 per token
            acc = 0
            full = True
            for j in range(1, k):
                pkey = jax.random.fold_in(key, n + j - 1)
                if temperature == 0.0:
                    # greedy exact match: the emitted token IS the vanilla
                    # argmax whether or not the draft agreed
                    ej = np.asarray(_sample(vlogits[:, j - 1], pkey, 0.0),
                                    np.int32)
                    emitted.append(ej)
                    # acceptance IS host control flow: this sync decides the
                    # burst's commit length, it cannot stay on device
                    if int(ej[0]) == int(feed[j][0]):  # graphlint: disable=EG005
                        acc += 1
                    else:
                        full = False
                        break
                else:
                    probs = jax.nn.softmax(vlogits[0, j - 1] / temperature)
                    dj = int(feed[j][0])  # graphlint: disable=EG005
                    u = jax.random.uniform(jax.random.fold_in(pkey, 1))
                    # same: the accept/reject draw gates the python loop
                    if float(u) < float(probs[dj]):  # graphlint: disable=EG005
                        emitted.append(feed[j])
                        acc += 1
                    else:
                        resid = probs.at[dj].set(0.0)
                        rtok = jax.random.categorical(
                            jax.random.fold_in(pkey, 2), jnp.log(resid))
                        emitted.append(
                            np.asarray(rtok, np.int32).reshape(1))
                        full = False
                        break
            if full:
                # every draft held: the bonus token comes free from the last
                # verify position, with the vanilla key for its step index
                bonus = _sample(vlogits[:, k - 1],
                                jax.random.fold_in(key, n + k - 1),
                                temperature)
                emitted.append(np.asarray(bonus, np.int32))
            rejected += (k - 1) - acc
            accepted += acc
            emitted = emitted[:max_new_tokens - n]  # budget clamp
            m = len(emitted)
            emitted_total += m
            if observe is not None:
                for e in emitted:
                    observe.token(e)
            toks.extend(emitted)
            # ---- commit: length rewrites only (masked garbage past the
            # fill level makes rollback exact, no data movement) ----
            n += m
            cache = {"k": vcache["k"], "v": vcache["v"],
                     "length": jnp.asarray(s + n - 1, jnp.int32)}
            if m == k:
                # fully accepted: the draft's k-1 steps never wrote the last
                # fed token's KV row — one catch-up step backfills it (same
                # shapes, same executable, logits discarded)
                _, dcache = _draft_step_jit(
                    draft_cfg, draft_params, dcache,
                    jnp.asarray(feed[k - 1]), compute_dtype)
            dcache = KVCache(dcache.k, dcache.v,
                             jnp.asarray(s + n - 1, jnp.int32))
            # ---- recovery hooks, at burst granularity (bound to the burst
            # index so checkpoint/timeout spans carry spec_burst) ----
            t = n - 1
            if rec is not None:
                with obs_context.bind(spec_burst=bursts):
                    if rec.halt_at_step is not None and t >= rec.halt_at_step:
                        checkpoint(toks, cache, t)
                        halted_at = t
                        break
                    if (rec.checkpoint_every and rec.checkpoint_path
                            and (t_prev // rec.checkpoint_every
                                 < t // rec.checkpoint_every)):
                        checkpoint(toks, cache, t)
                    if wd is not None:
                        ckpt_fn = ((lambda: checkpoint(toks, cache, t))
                                   if rec.checkpoint_path else None)
                        try:
                            wd.check(ckpt_fn)
                        except DecodeTimeout:
                            counters.watchdog_fires += 1
                            if stats is not None:
                                stats["recovery_counters"] = \
                                    counters.as_dict()
                            raise

    out = jnp.asarray(np.stack(toks, axis=1))  # (1, len(toks))
    jax.block_until_ready(out)
    t2 = time.monotonic()
    if resumed and halted_at is None:
        counters.resume_ok += 1

    spec_stats = {
        "k": int(k), "draft_layers": int(draft_cfg.num_layers),
        "bursts": bursts, "drafted": drafted, "accepted": accepted,
        "rejected": rejected,
        "acceptance_rate": (accepted / drafted) if drafted else 0.0,
        "hops_per_token": (bursts / emitted_total) if emitted_total else 0.0,
        "draft_step_cache_misses": draft_step_cache_size() - draft_misses0,
    }
    counters1 = rt.link_counters() if isinstance(rt, CounterSource) else None
    delta = None
    if counters1 is not None:
        delta = {kk: [int(x) for x in (v if counters0 is None
                                       else v - counters0[kk])]
                 for kk, v in counters1.items()}
    if link_health is not None:
        link_health.observe(delta)
    record_link_counters(delta)
    if link_health is not None:
        record_link_health(link_health.summary())
    record_spec_stats(spec_stats)
    if get_registry().enabled and isinstance(rt, CounterSource):
        record_wire_bytes(rt.verify_hop_bytes(b, k), kind="verify",
                          steps=bursts)
    if tracing_enabled() and hasattr(rt, "hop_attribution"):
        # one hop round per burst: the per-hop wire cost is the k-token
        # verify payload times the burst count
        _emit_hop_spans(
            rt, delta, [x * bursts for x in rt.verify_hop_bytes(b, k)],
            link_tier=getattr(link_health, "tier", None),
            spec_bursts=int(bursts))
    if stats is not None:
        stats.update(
            capacity=capacity,
            prefill_s=t1 - t0,
            decode_s=t2 - t1,
            decode_steps=emitted_total,
            decode_tokens_per_s=(emitted_total / (t2 - t1))
            if emitted_total and t2 > t1 else 0.0,
            speculative=spec_stats,
        )
        if halted_at is not None:
            stats["halted_at_step"] = halted_at
        if rec is not None or resumed:
            # resumed runs report counters even recovery-free, matching the
            # vanilla survivable loop (resume_ok is the signal callers read)
            stats["recovery_counters"] = counters.as_dict()
        if delta is not None:
            stats["link_counters"] = delta
        if link_health is not None:
            stats["link_health"] = link_health.summary()
        if observe is not None:
            stats.update(observe.summary())
        record_decode_stats(stats)
    if rec is not None or resumed:
        record_recovery_counters(counters)
    if observe is not None:
        observe.publish()
    return out


def resume_speculative(rt: Any, placed_params: dict, checkpoint_path: str, *,
                       spec: SpecConfig,
                       stats: Optional[dict] = None,
                       recovery: Optional[RecoveryConfig] = None,
                       raw_params: Optional[dict] = None,
                       observe: Optional[LatencyObserver] = None
                       ) -> jnp.ndarray:
    """Resume a checkpointed speculative generation and return the FULL
    (1, max_new) token matrix, token-identical to the uninterrupted run:
    checkpoints land only on burst boundaries, burst boundaries depend only
    on the committed prefix, and the per-step keys depend only on (seed,
    step index). Validates the same plan/model meta as ``resume_split`` plus
    the checkpoint's ``speculative`` block against ``spec`` (a window or
    draft mismatch would re-shape the burst sequence). A vanilla (spec-free)
    checkpoint resumes fine at ``temperature == 0`` — greedy identity does
    not care where the boundaries fall."""
    if not spec.enabled:
        raise ValueError("resume_speculative called with spec.enabled=False;"
                         " use resume_split")
    if raw_params is None:
        raise ValueError(
            "speculative resume needs raw_params= (the unplaced parameter "
            "pytree) to rebuild the stage-0 draft")
    with obs_span("decode.checkpoint_resume", path=checkpoint_path):
        ckpt = DecodeCheckpoint.load(checkpoint_path)
    meta = ckpt.meta
    want = runtime_plan_meta(rt)
    for kk, label in (("mode", "runtime mode"), ("model", "model signature"),
                      ("cuts", "split cuts"), ("hop_codecs", "hop codecs")):
        if meta.get(kk) != want.get(kk):
            raise CheckpointError(
                f"checkpoint {checkpoint_path} was written for {label} "
                f"{meta.get(kk)!r}, the resuming runtime has "
                f"{want.get(kk)!r}; rebuild the runtime to match")
    cut = None
    if getattr(rt, "split", None) is not None and rt.split.cuts:
        cut = int(rt.split.cuts[0])
    draft_cfg, draft_params = draft_from_params(rt.cfg, raw_params, spec, cut)
    sm = meta.get("speculative")
    if sm is not None:
        got = {"k": int(spec.k), "draft_source": spec.draft_source,
               "draft_layers": int(draft_cfg.num_layers)}
        if {kk: sm.get(kk) for kk in got} != got:
            raise CheckpointError(
                f"checkpoint {checkpoint_path} was written with speculative "
                f"config {sm!r}, the resuming run has {got!r}; a window or "
                f"draft mismatch breaks the token-identical-resume "
                f"guarantee")
    prompt_ids = jnp.asarray(ckpt.arrays["prompt_ids"])
    tokens = ckpt.arrays["tokens"]  # (1, step+1)
    key = jax.random.wrap_key_data(jnp.asarray(ckpt.arrays["rng_key"]))
    cache = cache_from_state_dict({"k": ckpt.arrays["cache/k"],
                                   "v": ckpt.arrays["cache/v"],
                                   "length": ckpt.arrays["cache/length"]})
    toks = [tokens[:, i] for i in range(tokens.shape[1])]
    step = int(meta["step"])
    if len(toks) != step + 1:
        raise CheckpointError(
            f"checkpoint {checkpoint_path} is inconsistent: step {step} "
            f"with {len(toks)} sampled tokens")
    rec = recovery
    if rec is not None and rec.stage_failure is not None:
        raise ValueError(
            "speculative decoding does not compose with stage-failure "
            "injection; run failover drills on the vanilla loop")
    if stats is not None:
        stats["resumed_from_step"] = step
        if "link_counters" in meta:
            stats["checkpoint_link_counters"] = meta["link_counters"]
    return _spec_loop(
        rt, placed_params, prompt_ids, int(meta["max_new_tokens"]),
        int(meta["capacity"]), float(meta["temperature"]), key,
        int(meta["fault_step"]), spec, draft_cfg, draft_params, None,
        stats, rec, resume_state=(step, toks, cache), resumed=True,
        observe=observe)
