"""Batched autoregressive generation over the KV-cached decode runtime.

The serving loop the ROADMAP's north star needs and the evaluation entry
points cannot provide: ``forward`` reprocesses the whole window per emitted
token (N tokens = N full prefills), while this loop runs ONE prefill and then
O(1) ``decode_step`` calls against the cache.

Compilation contract: the per-step executable is compiled once per
(batch, capacity) shape. Capacity is static (it fixes the cache buffers);
``cache.length`` is a traced scalar, so every fill level of the cache — and
every emitted token — reuses the same executable. ``generate`` exposes the
jit cache-miss delta in its ``stats`` dict precisely so tests can assert the
no-retrace property instead of trusting it.

Sampling: ``temperature == 0`` is greedy argmax; ``temperature > 0`` draws
from ``categorical(logits / temperature)`` with a per-step ``fold_in`` of the
caller's key, so a fixed key is reproducible and steps are decorrelated. The
temperature is a static jit arg — the greedy executable contains no RNG at
all.

Survivability (``recovery=`` on both loops, see ``serve.recovery``): the
same loop can periodically snapshot its full generation state (KV cache,
position offset, RNG key, token prefix, fault counters) to an atomic
:class:`~edgellm_tpu.serve.recovery.DecodeCheckpoint`, guard each step with a
monotonic watchdog, survive an injected (or real) whole-stage loss by
re-planning the split onto the survivors and recomputing the lost KV state
from the generation prefix, and resume from a checkpoint token-identically
(:func:`resume_split`). With ``recovery=None`` — or a config with every
feature off — the loop drives the exact same runtime executables as before:
recovery is host-side orchestration, never a different graph.
"""
from __future__ import annotations

import time
from typing import Any, Optional

import numpy as np
import jax
import jax.numpy as jnp
from jax.typing import ArrayLike

from ..lint import graph_contract
from ..models.configs import ModelConfig
from ..models.transformer import (KVCache, _cast_params, block_verify,
                                  cache_from_state_dict, cache_state_dict,
                                  decode_step, embed, precompute_rope,
                                  prefill, unembed)
from ..obs.latency import LatencyObserver
from ..obs.metrics import (CounterSource, get_registry, record_decode_stats,
                           record_link_counters, record_link_health,
                           record_pipeline_stats, record_recovery_counters,
                           record_wire_bytes)
from ..obs.tracing import span as obs_span
from ..obs.tracing import tracing_enabled
from .recovery import (CheckpointError, DecodeCheckpoint, DecodeTimeout,
                       LocalRuntime, RecoveryConfig, RecoveryCounters,
                       StageLostError, Watchdog, runtime_plan_meta)


def _sample(logits: jnp.ndarray, key: jax.Array,
            temperature: float) -> jnp.ndarray:
    """(B, V) fp32 logits -> (B,) int32 token ids."""
    if temperature == 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return jax.random.categorical(key, logits / temperature).astype(jnp.int32)


@graph_contract("decode.prefill", collectives={})
def _prefill_impl(cfg: ModelConfig, params: dict, prompt_ids: jnp.ndarray,
                  capacity: int,
                  compute_dtype: Optional[Any]) -> tuple[jnp.ndarray, KVCache]:
    if cfg.is_hybrid:
        # the hybrid stack unembeds the last position only
        from ..models.hybrid import prefill_hybrid

        return prefill_hybrid(cfg, _cast_params(params, compute_dtype),
                              prompt_ids, capacity, last_only=True)
    logits, cache = prefill(cfg, params, prompt_ids, capacity,
                            compute_dtype=compute_dtype)
    return logits[:, -1], cache  # only the last position seeds generation


@graph_contract("decode.prefill_suffix", collectives={},
                donate=lambda ctx: ctx.get("donate_min", 2))
def _prefill_suffix_impl(cfg: ModelConfig, params: dict,
                         suffix_ids: jnp.ndarray, cache: KVCache,
                         compute_dtype: Optional[Any]
                         ) -> tuple[jnp.ndarray, KVCache]:
    """Prefill ONLY the unmatched suffix of a prompt whose prefix KV rows
    are already in ``cache`` (rows ``0 .. cache.length`` — gathered from
    shared pages by the prefix-cache admit path). A K-position twin of
    ``decode_step``: embed the (B, K) suffix, rotate at the absolute
    positions ``cache.length .. cache.length+K-1``, scan ``block_verify``
    over the layers (write K rows, attend causally against the filled
    prefix), and return ((B, K, V) fp32 logits, cache grown by K). Compiled
    once per (batch, K, capacity) shape — the admit path's analogue of the
    one-executable-per-geometry rule."""
    params = _cast_params(params, compute_dtype)
    hidden = embed(params, suffix_ids)  # (B, K, D)
    pos = cache.length
    kq = suffix_ids.shape[1]
    cos, sin = precompute_rope(cfg, cache.capacity)
    cos_t = jax.lax.dynamic_slice_in_dim(cos, pos, kq)
    sin_t = jax.lax.dynamic_slice_in_dim(sin, pos, kq)

    def body(h, xs):
        lp, kc, vc = xs
        h, kc, vc = block_verify(cfg, lp, h, cos_t, sin_t, kc, vc, pos)
        return h, (kc, vc)

    hidden, (k_new, v_new) = jax.lax.scan(
        body, hidden, (params["layers"], cache.k, cache.v))
    logits = unembed(cfg, params, hidden)  # (B, K, V) fp32
    return logits, KVCache(k_new, v_new, pos + kq)


@graph_contract("decode.step", collectives={},
                donate=lambda ctx: ctx.get("donate_min", 2))
def _step_impl(cfg: ModelConfig, params: dict, cache: KVCache,
               token_ids: jnp.ndarray, key: jax.Array, temperature: float,
               compute_dtype: Optional[Any]) -> tuple[jnp.ndarray, KVCache]:
    logits, cache = decode_step(cfg, params, cache, token_ids,
                                compute_dtype=compute_dtype)
    return _sample(logits, key, temperature), cache


_prefill_jit = jax.jit(_prefill_impl,
                       static_argnames=("cfg", "capacity", "compute_dtype"))
# suffix prefill donates its cache: the gathered shared-prefix rows flow in,
# the suffix rows land in place. One executable per (batch, K, capacity);
# like full prefill, its compiles are NOT counted as step-cache jit misses.
_prefill_suffix_jit = jax.jit(_prefill_suffix_impl,
                              static_argnames=("cfg", "compute_dtype"),
                              donate_argnums=(3,))
# the cache is donated: each step's (B, capacity) KV buffers alias the previous
# step's in the lowered executable instead of being copied per token (the
# "decode.step" graph contract asserts the aliasing survives)
_step_jit = jax.jit(_step_impl,
                    static_argnames=("cfg", "temperature", "compute_dtype"),
                    donate_argnames=("cache",))


def decode_step_cache_size() -> int:
    """Number of per-step executables compiled so far in this process — the
    jit-cache-miss counter ``generate`` reports deltas of."""
    return _step_jit._cache_size()


def _emit_hop_spans(rt: Any, delta: Optional[dict],
                    per_hop_bytes: Optional[list], *,
                    link_tier: Optional[int] = None,
                    **extra: Any) -> None:
    """One zero-duration ``split.hop`` span per boundary cut, at call
    granularity: {hop, cut layer, codec, wire bytes, ladder outcome} plus
    the caller's extras (µ-batch count, spec-burst count) — and, via the
    ambient :class:`~edgellm_tpu.obs.context.TraceContext`, the request
    labels. Tracing-gated so disabled tracing skips even the attribution
    arithmetic; runtimes without a boundary (LocalRuntime) have no
    ``hop_attribution`` and emit nothing."""
    if not tracing_enabled() or not hasattr(rt, "hop_attribution"):
        return
    for row in rt.hop_attribution(delta, per_hop_bytes,
                                  link_tier=link_tier):
        with obs_span("split.hop", **row, **extra):
            pass


def _validate_decode_args(prompt_ids, max_new_tokens, capacity, temperature,
                          rng_key):
    prompt_ids = jnp.asarray(prompt_ids)
    if prompt_ids.ndim != 2:
        raise ValueError(f"prompt_ids must be (B, S), got {prompt_ids.shape}")
    _, s = prompt_ids.shape
    if max_new_tokens < 1:
        raise ValueError("max_new_tokens must be >= 1")
    capacity = s + max_new_tokens if capacity is None else int(capacity)
    if s + max_new_tokens > capacity:
        raise ValueError(
            f"cache capacity overflow: prompt {s} + {max_new_tokens} new "
            f"tokens > capacity {capacity}")
    temperature = float(temperature)
    if temperature < 0.0:
        raise ValueError("temperature must be >= 0")
    key = jax.random.key(0) if rng_key is None else rng_key
    return prompt_ids, capacity, temperature, key


def generate(cfg: ModelConfig, params: dict, prompt_ids: ArrayLike,
             max_new_tokens: int,
             *,
             capacity: Optional[int] = None,
             temperature: float = 0.0,
             rng_key: Optional[jax.Array] = None,
             compute_dtype=None,
             stats: Optional[dict] = None,
             recovery: Optional[RecoveryConfig] = None,
             observe: Optional[LatencyObserver] = None) -> jnp.ndarray:
    """Generate ``max_new_tokens`` per batch row after a KV-cached prefill.

    prompt_ids: (B, S) int token ids. Returns (B, max_new_tokens) int32.
    ``capacity`` (static; default exactly prompt+new) bounds the cache —
    prompts that would overflow it raise instead of silently wrapping.
    ``stats``, when given, is filled with timing and the per-step jit
    cache-miss delta (0 on a warm shape, 1 on a cold one).

    ``observe``: a :class:`~edgellm_tpu.obs.latency.LatencyObserver` records
    TTFT and per-token latency histograms, blocking once per sampled token
    (the data-dependency boundary — never per op); its SLO summary is folded
    into ``stats``. ``observe=None`` (default) leaves the loop untouched.

    ``recovery``: a :class:`~edgellm_tpu.serve.recovery.RecoveryConfig`
    routes the generation through the survivable loop (checkpointing +
    watchdog) on a :class:`LocalRuntime` adapter around the same
    ``prefill``/``decode_step`` math; stage failover does not apply on a
    single device. ``recovery=None`` is the original loop, untouched.
    """
    prompt_ids, capacity, temperature, key = _validate_decode_args(
        prompt_ids, max_new_tokens, capacity, temperature, rng_key)
    b, s = prompt_ids.shape
    if recovery is not None:
        from ..models.hybrid import refuse_beyond_kv_rows

        refuse_beyond_kv_rows(cfg, "generate(recovery=...) (checkpoints, "
                                    "the survivable loop)")
        rt = LocalRuntime(cfg, compute_dtype)
        return _survivable_loop(rt, params, prompt_ids, max_new_tokens,
                                capacity, temperature, key, 0, stats,
                                recovery, raw_params=params, observe=observe)
    misses0 = decode_step_cache_size()
    if observe is not None:
        observe.start()

    t0 = time.monotonic()
    with obs_span("generate.prefill", batch=b, prompt_len=s):
        last_logits, cache = _prefill_jit(cfg, params, prompt_ids, capacity,
                                          compute_dtype)
        tok = _sample(last_logits, jax.random.fold_in(key, 0), temperature)
        jax.block_until_ready(tok)
    if observe is not None:
        observe.first_token(tok)
    t1 = time.monotonic()

    toks = [tok]
    with obs_span("generate.decode_loop", steps=max_new_tokens - 1):
        for t in range(1, max_new_tokens):
            tok, cache = _step_jit(cfg, params, cache, tok,
                                   jax.random.fold_in(key, t), temperature,
                                   compute_dtype)
            if observe is not None:
                observe.token(tok)
            toks.append(tok)
    out = jnp.stack(toks, axis=1)  # (B, max_new_tokens)
    jax.block_until_ready(out)
    t2 = time.monotonic()

    if stats is not None:
        steps = max_new_tokens - 1  # tokens emitted by the decode loop proper
        stats.update(
            capacity=capacity,
            prefill_s=t1 - t0,
            decode_s=t2 - t1,
            decode_steps=steps,
            decode_tokens_per_s=(b * steps / (t2 - t1)) if steps else 0.0,
            decode_step_cache_misses=decode_step_cache_size() - misses0,
        )
        if observe is not None:
            stats.update(observe.summary())
        record_decode_stats(stats)
    if observe is not None:
        observe.publish()
    return out


def generate_split(rt: Any, placed_params: dict, prompt_ids: ArrayLike,
                   max_new_tokens: int,
                   *,
                   capacity: Optional[int] = None,
                   temperature: float = 0.0,
                   rng_key: Optional[jax.Array] = None,
                   fault_step: int = 0,
                   stats: Optional[dict] = None,
                   recovery: Optional[RecoveryConfig] = None,
                   raw_params: Optional[dict] = None,
                   link_health: Optional[Any] = None,
                   speculative: Optional[Any] = None,
                   observe: Optional[LatencyObserver] = None) -> jnp.ndarray:
    """``generate`` over the pipeline-SPLIT decode runtime: one split prefill,
    then O(1) :meth:`SplitRuntime.decode_step` calls, every emitted token
    crossing each cut as a packed wire payload — and, when the runtime was
    built with faults, a sealed/verified/retried one (each step's fault stream
    is keyed by the cache fill level, so generation is seed-reproducible).

    ``rt`` is a :class:`~edgellm_tpu.parallel.split.SplitRuntime`;
    ``placed_params`` comes from ``rt.place_params``. ``fault_step`` seeds the
    prefill's fault stream (vary it across prompts to decorrelate them).
    ``stats`` gains the same timing fields as ``generate`` plus, under faults,
    ``link_counters`` — the per-hop detected/retried/recovered/substituted
    totals incurred by THIS call.

    ``link_health`` (a :class:`~edgellm_tpu.codecs.fec.LinkHealth`) observes
    this call's counter deltas and lands its windowed SLO summary — burn
    rate, corruption/repair/retry/hedge-win rates — in
    ``stats["link_health"]``; the caller reads ``link_health.tier`` between
    calls to walk the codec ladder (tier changes swap runtimes, so they
    cannot happen inside one call).

    ``recovery`` routes the call through the survivable loop: periodic
    :class:`DecodeCheckpoint` snapshots, a per-step watchdog, stage-failure
    injection, and boundary re-planning failover (which needs ``raw_params``
    — the unplaced parameter pytree — to re-place onto the surviving
    devices). ``recovery=None`` is the original loop on the exact same
    runtime executables.

    ``speculative``: an enabled :class:`~edgellm_tpu.serve.speculative.
    SpecConfig` routes the call through the draft/verify burst loop (greedy
    output token-identical, one boundary hop round per burst instead of per
    token; needs ``raw_params`` for the stage-0 draft). ``None`` — or a
    disabled config — is PURE host-side dispatch: the loop below runs
    unchanged and builds the exact pre-spec graphs (the graphlint identity
    contract holds because this branch never touches the verify executable).
    """
    if speculative is not None and getattr(speculative, "enabled", False):
        # lazy import: speculative imports this module's helpers
        from .speculative import generate_speculative

        return generate_speculative(
            rt, placed_params, prompt_ids, max_new_tokens, spec=speculative,
            capacity=capacity, temperature=temperature, rng_key=rng_key,
            fault_step=fault_step, stats=stats, recovery=recovery,
            raw_params=raw_params, link_health=link_health, observe=observe)
    prompt_ids, capacity, temperature, key = _validate_decode_args(
        prompt_ids, max_new_tokens, capacity, temperature, rng_key)
    b, s = prompt_ids.shape
    if recovery is not None:
        return _survivable_loop(rt, placed_params, prompt_ids, max_new_tokens,
                                capacity, temperature, key, fault_step, stats,
                                recovery, raw_params=raw_params,
                                observe=observe)
    counters0 = rt.link_counters() if isinstance(rt, CounterSource) else None
    if observe is not None:
        observe.start()

    t0 = time.monotonic()
    with obs_span("generate_split.prefill", batch=b, prompt_len=s):
        logits, cache = rt.prefill_decode(placed_params, prompt_ids, capacity,
                                          fault_step=fault_step)
        tok = _sample(logits[:, -1], jax.random.fold_in(key, 0), temperature)
        jax.block_until_ready(tok)
    if observe is not None:
        observe.first_token(tok)
    t1 = time.monotonic()

    toks = [tok]
    with obs_span("generate_split.decode_loop", steps=max_new_tokens - 1):
        for t in range(1, max_new_tokens):
            step_logits, cache = rt.decode_step(placed_params, cache, tok)
            tok = _sample(step_logits, jax.random.fold_in(key, t), temperature)
            if observe is not None:
                observe.token(tok)
            toks.append(tok)
    out = jnp.stack(toks, axis=1)  # (B, max_new_tokens)
    jax.block_until_ready(out)
    t2 = time.monotonic()

    counters1 = rt.link_counters() if isinstance(rt, CounterSource) else None
    delta = None
    if counters1 is not None:
        delta = {k: [int(x) for x in (v if counters0 is None
                                      else v - counters0[k])]
                 for k, v in counters1.items()}
    if link_health is not None:
        link_health.observe(delta)
    record_link_counters(delta)
    if link_health is not None:
        record_link_health(link_health.summary())
    pipelined = bool(getattr(rt, "pipelined", False))
    hop_bytes: Optional[list] = None
    if isinstance(rt, CounterSource) and (get_registry().enabled
                                          or tracing_enabled()):
        # under the µ-batch schedule each cut moves M smaller payloads per
        # step — report the bytes the wire actually carried
        hop_bytes = (rt.pipelined_decode_hop_bytes(b) if pipelined
                     else rt.decode_hop_bytes(b))
    if get_registry().enabled and hop_bytes is not None:
        record_wire_bytes(hop_bytes, kind="decode", steps=max_new_tokens - 1)
    _emit_hop_spans(
        rt, delta,
        None if hop_bytes is None
        else [x * (max_new_tokens - 1) for x in hop_bytes],
        link_tier=getattr(link_health, "tier", None),
        microbatches=int(getattr(getattr(rt, "pipeline", None),
                                 "num_microbatches", 1) if pipelined else 1))
    if pipelined:
        record_pipeline_stats(rt.pipeline_summary())
    if stats is not None:
        steps = max_new_tokens - 1
        stats.update(
            capacity=capacity,
            prefill_s=t1 - t0,
            decode_s=t2 - t1,
            decode_steps=steps,
            decode_tokens_per_s=(b * steps / (t2 - t1)) if steps else 0.0,
        )
        if pipelined:
            stats["pipeline"] = rt.pipeline_summary()
        if delta is not None:
            stats["link_counters"] = delta
        if link_health is not None:
            stats["link_health"] = link_health.summary()
        if observe is not None:
            stats.update(observe.summary())
        record_decode_stats(stats)
    if observe is not None:
        observe.publish()
    return out


# ---------------------------------------------------------------------------
# the survivable loop: checkpoints, watchdog, stage failover, resume
# ---------------------------------------------------------------------------


def _write_checkpoint(rec: RecoveryConfig, rt, counters: RecoveryCounters,
                      prompt_ids, toks: list, cache, key, t: int,
                      run_meta: dict) -> None:
    """Snapshot everything step t+1 needs — token-identically — to the
    atomic checkpoint file. ``toks`` holds steps 0..t; the cache holds the
    prompt plus steps 0..t-1 (step t's token has not been fed back yet),
    which is exactly the loop state at the top of iteration t+1."""
    with obs_span("decode.checkpoint_write", step=t):
        arrays = {
            "prompt_ids": np.asarray(prompt_ids, np.int32),
            "tokens": np.stack([np.asarray(x) for x in toks], axis=1)
            .astype(np.int32),
            "rng_key": np.asarray(jax.random.key_data(key)),
        }
        cs = cache_state_dict(cache)
        arrays.update({"cache/k": cs["k"], "cache/v": cs["v"],
                       "cache/length": cs["length"]})
        meta = {**runtime_plan_meta(rt), **run_meta, "step": int(t),
                "recovery_counters": counters.as_dict()}
        link = rt.link_counters() if isinstance(rt, CounterSource) else None
        if link is not None:
            meta["link_counters"] = {k: [int(x) for x in v]
                                     for k, v in link.items()}
        DecodeCheckpoint(arrays, meta).save(rec.checkpoint_path)
        counters.checkpoints_written += 1


def _decode_failover(rt, raw_params, lost_stage: int, prompt_ids, toks: list,
                     capacity: int, fault_step: int,
                     counters: RecoveryCounters, rec: RecoveryConfig):
    """Re-plan the split onto the surviving stage(s) and rebuild the decode
    state there. The lost stage's KV cache is unrecoverable (its boundary
    inputs died with it), so the honest migration is a re-prefill of the
    whole generation prefix — prompt plus every token sampled so far — on
    the new plan; the re-prefill's last-position logits are exactly what the
    failed step would have produced, so the caller samples from them with
    the step's own folded key and continues. Returns
    (new_rt, new_placed, cache, last_logits)."""
    if not rec.replan:
        raise StageLostError(lost_stage)
    if counters.failovers >= rec.max_failovers:
        raise StageLostError(lost_stage)
    if raw_params is None:
        raise ValueError(
            "stage failover needs raw_params= (the unplaced parameter "
            "pytree) to re-place weights onto the surviving devices")
    counters.failovers += 1
    with obs_span("decode.failover", lost_stage=lost_stage):
        return _decode_failover_impl(rt, raw_params, lost_stage, prompt_ids,
                                     toks, capacity, fault_step, counters)


def _decode_failover_impl(rt, raw_params, lost_stage: int, prompt_ids,
                          toks: list, capacity: int, fault_step: int,
                          counters: RecoveryCounters):
    """The replan + re-place + re-prefill body of :func:`_decode_failover`
    (split out so the failover span covers exactly the expensive work)."""
    grid = np.asarray(rt.mesh.devices)  # (stage, data, model)
    survivors = np.delete(grid, lost_stage, axis=0)
    cfg = rt.cfg
    if survivors.shape[0] >= 2:
        # lazy import: serve -> parallel only on the failover path keeps the
        # module layering acyclic (parallel imports serve.recovery's error)
        from jax.sharding import Mesh

        from ..parallel.split import SplitRuntime

        new_split = rt.split.replan(cfg.num_layers, survivors.shape[0])
        # the µ-batch schedule survives failover: the batch is unchanged and
        # the replanned cuts reuse the (batch-invariant) original codec, so
        # the pipelined runtime's validation still holds on the new mesh
        new_rt = SplitRuntime(cfg, new_split,
                              Mesh(survivors, ("stage", "data", "model")),
                              faults=rt.faults, policy=rt.policy,
                              pipeline=getattr(rt, "pipeline", None))
    else:
        new_rt = LocalRuntime(cfg)  # one survivor: nothing left to cut
    counters.replans += 1
    new_placed = new_rt.place_params(raw_params)
    # via host: the sampled tokens are committed to the dead mesh, and the
    # re-planned runtime lives on a different device set
    prompt_np = np.asarray(prompt_ids)
    prefix = jnp.asarray(
        prompt_np if not toks else
        np.concatenate([prompt_np,
                        np.stack([np.asarray(x) for x in toks], axis=1)],
                       axis=1))
    logits, cache = new_rt.prefill_decode(new_placed, prefix, capacity,
                                          fault_step=fault_step)
    counters.recompute_tokens += int(prefix.shape[0] * prefix.shape[1])
    return new_rt, new_placed, cache, logits[:, -1]


def _survivable_loop(rt, placed, prompt_ids, max_new_tokens: int,
                     capacity: int, temperature: float, key, fault_step: int,
                     stats: Optional[dict], rec: RecoveryConfig,
                     raw_params: Optional[dict],
                     resume_state=None, resumed: bool = False,
                     observe: Optional[LatencyObserver] = None) -> jnp.ndarray:
    """The decode loop with recovery orchestration around the unchanged
    runtime executables. ``resume_state`` = (last_done_step, toks, cache)
    continues a checkpointed generation from step ``last_done_step + 1``."""
    counters = RecoveryCounters()
    wd = (Watchdog(rec.deadline_s, clock=rec.clock)
          if rec.deadline_s is not None else None)
    b, s = prompt_ids.shape
    sf = rec.stage_failure
    fail_pending = sf is not None
    run_meta = {"capacity": int(capacity), "temperature": float(temperature),
                "max_new_tokens": int(max_new_tokens),
                "fault_step": int(fault_step), "prompt_len": int(s),
                "batch": int(b)}
    counters0 = rt.link_counters() if isinstance(rt, CounterSource) else None
    halted_at = None
    if observe is not None:
        observe.start()

    def post_step(t, toks, cache) -> bool:
        """halt hook, periodic checkpoint, watchdog — in that order; returns
        True when the loop must stop (simulated kill)."""
        if rec.halt_at_step is not None and rec.halt_at_step == t:
            _write_checkpoint(rec, rt, counters, prompt_ids, toks, cache,
                              key, t, run_meta)
            return True
        if (rec.checkpoint_every and rec.checkpoint_path
                and t % rec.checkpoint_every == 0):
            _write_checkpoint(rec, rt, counters, prompt_ids, toks, cache,
                              key, t, run_meta)
        if wd is not None:
            ckpt_fn = ((lambda: _write_checkpoint(
                rec, rt, counters, prompt_ids, toks, cache, key, t, run_meta))
                if rec.checkpoint_path else None)
            try:
                wd.check(ckpt_fn)
            except DecodeTimeout:
                counters.watchdog_fires += 1
                if stats is not None:
                    stats["recovery_counters"] = counters.as_dict()
                raise
        return False

    t0 = time.monotonic()
    if wd is not None:
        wd.arm()
    if resume_state is None:
        if fail_pending and sf.at_step == 0:
            rt.mark_stage_lost(sf.stage)
        try:
            logits, cache = rt.prefill_decode(placed, prompt_ids, capacity,
                                              fault_step=fault_step)
            last = logits[:, -1]
        except StageLostError as e:
            fail_pending = False
            rt, placed, cache, last = _decode_failover(
                rt, raw_params, e.stage, prompt_ids, [], capacity,
                fault_step, counters, rec)
        tok = _sample(last, jax.random.fold_in(key, 0), temperature)
        jax.block_until_ready(tok)
        if observe is not None:
            observe.first_token(tok)
        t1 = time.monotonic()
        toks = [tok]
        start_t = 1
        if post_step(0, toks, cache):
            halted_at = 0
    else:
        last_done, toks, cache = resume_state
        tok = toks[-1]
        t1 = t0
        start_t = last_done + 1

    if halted_at is None:
        for t in range(start_t, max_new_tokens):
            if fail_pending and sf.at_step == t:
                rt.mark_stage_lost(sf.stage)
            try:
                step_logits, cache = rt.decode_step(placed, cache, tok)
                tok = _sample(step_logits, jax.random.fold_in(key, t),
                              temperature)
            except StageLostError as e:
                fail_pending = False
                rt, placed, cache, last = _decode_failover(
                    rt, raw_params, e.stage, prompt_ids, toks, capacity,
                    fault_step, counters, rec)
                tok = _sample(last, jax.random.fold_in(key, t), temperature)
            if observe is not None:
                observe.token(tok)
            toks.append(tok)
            if post_step(t, toks, cache):
                halted_at = t
                break

    # assemble via host: after a failover the prefix is committed to the dead
    # mesh and the tail to the survivors' — jnp.stack would refuse the mix
    out = jnp.asarray(np.stack([np.asarray(x) for x in toks], axis=1))
    jax.block_until_ready(out)
    t2 = time.monotonic()
    if resumed and halted_at is None:
        counters.resume_ok += 1

    delta = None
    if isinstance(rt, CounterSource) and (stats is not None
                                          or tracing_enabled()):
        counters1 = rt.link_counters()
        if counters1 is not None:
            # after a failover the runtime is new, so deltas vs the original
            # runtime's baseline are meaningless — report absolute totals
            delta = {k: [int(x) for x in
                         (v if counters0 is None or counters.failovers
                          else v - counters0[k])]
                     for k, v in counters1.items()}
    steps = len(toks) - (0 if resume_state is not None else 1)
    if stats is not None:
        stats.update(
            capacity=capacity,
            prefill_s=t1 - t0,
            decode_s=t2 - t1,
            decode_steps=steps,
            decode_tokens_per_s=(b * steps / (t2 - t1)) if steps
            and t2 > t1 else 0.0,
        )
        if halted_at is not None:
            stats["halted_at_step"] = halted_at
        stats["recovery_counters"] = counters.as_dict()
        if delta is not None:
            stats["link_counters"] = delta
            record_link_counters(delta)
        if observe is not None:
            stats.update(observe.summary())
        record_decode_stats(stats)
    if tracing_enabled() and hasattr(rt, "hop_attribution"):
        pipelined = bool(getattr(rt, "pipelined", False))
        hop_bytes = (rt.pipelined_decode_hop_bytes(b) if pipelined
                     else rt.decode_hop_bytes(b))
        _emit_hop_spans(
            rt, delta, [x * max(steps, 0) for x in hop_bytes],
            microbatches=int(getattr(getattr(rt, "pipeline", None),
                                     "num_microbatches", 1)
                             if pipelined else 1),
            failovers=int(counters.failovers))
    record_recovery_counters(counters)
    if observe is not None:
        observe.publish()
    return out


def resume_split(rt: Any, placed_params: dict, checkpoint_path: str, *,
                 stats: Optional[dict] = None,
                 recovery: Optional[RecoveryConfig] = None,
                 raw_params: Optional[dict] = None,
                 speculative: Optional[Any] = None,
                 observe: Optional[LatencyObserver] = None) -> jnp.ndarray:
    """Resume a checkpointed generation and return the FULL (B, max_new)
    token matrix — the checkpointed prefix plus the tokens decoded here,
    token-identical to the uninterrupted same-seed run.

    ``speculative``: an enabled SpecConfig resumes through the burst loop
    (:func:`~edgellm_tpu.serve.speculative.resume_speculative` — spec
    checkpoints land on burst boundaries, so the resumed stream matches the
    uninterrupted speculative run token for token); ``None``/disabled is the
    vanilla resume below, untouched.

    ``rt``/``placed_params`` must match the checkpoint's plan and model
    signature (validated; a mismatch is a typed :class:`CheckpointError` —
    same-plan resume restores the KV cache bit-exactly instead of
    recomputing it). ``recovery`` optionally re-arms checkpointing/watchdog/
    failover for the resumed tail; its ``stage_failure`` steps are absolute
    decode-step indices, comparable to the checkpoint's ``step``. Works for
    both split runtimes and :class:`LocalRuntime` (unsplit ``generate``
    checkpoints)."""
    if speculative is not None and getattr(speculative, "enabled", False):
        from .speculative import resume_speculative

        return resume_speculative(
            rt, placed_params, checkpoint_path, spec=speculative,
            stats=stats, recovery=recovery, raw_params=raw_params,
            observe=observe)
    with obs_span("decode.checkpoint_resume", path=checkpoint_path):
        ckpt = DecodeCheckpoint.load(checkpoint_path)
    meta = ckpt.meta
    want = runtime_plan_meta(rt)
    # num_microbatches defaults to 1 (sequential) so pre-pipeline
    # checkpoints resume onto unpipelined runtimes unchanged
    for k, label, dflt in (("mode", "runtime mode", None),
                           ("model", "model signature", None),
                           ("cuts", "split cuts", None),
                           ("hop_codecs", "hop codecs", None),
                           ("num_microbatches", "pipeline µ-batch count", 1)):
        if meta.get(k, dflt) != want.get(k, dflt):
            raise CheckpointError(
                f"checkpoint {checkpoint_path} was written for {label} "
                f"{meta.get(k)!r}, the resuming runtime has {want.get(k)!r}; "
                f"rebuild the runtime to match (or re-plan explicitly)")
    prompt_ids = jnp.asarray(ckpt.arrays["prompt_ids"])
    tokens = ckpt.arrays["tokens"]  # (B, step+1)
    key = jax.random.wrap_key_data(jnp.asarray(ckpt.arrays["rng_key"]))
    cache = cache_from_state_dict({"k": ckpt.arrays["cache/k"],
                                   "v": ckpt.arrays["cache/v"],
                                   "length": ckpt.arrays["cache/length"]})
    toks = [jnp.asarray(tokens[:, i]) for i in range(tokens.shape[1])]
    step = int(meta["step"])
    if len(toks) != step + 1:
        raise CheckpointError(
            f"checkpoint {checkpoint_path} is inconsistent: step {step} "
            f"with {len(toks)} sampled tokens")
    rec = recovery if recovery is not None else RecoveryConfig()
    if stats is not None:
        stats["resumed_from_step"] = step
        if "link_counters" in meta:
            stats["checkpoint_link_counters"] = meta["link_counters"]
    return _survivable_loop(
        rt, placed_params, prompt_ids, int(meta["max_new_tokens"]),
        int(meta["capacity"]), float(meta["temperature"]), key,
        int(meta["fault_step"]), stats, rec, raw_params,
        resume_state=(step, toks, cache), resumed=True, observe=observe)
