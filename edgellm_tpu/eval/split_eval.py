"""Sliding-window perplexity over the REAL split runtime.

Where ``harness.py`` reproduces the reference's *simulated* boundary (in-place
quant-dequant), this driver runs the same metric with the model actually cut
across mesh devices: every chunk's forward crosses each cut as a packed payload
over ``lax.ppermute``. This is the end-to-end path for the BASELINE.json
configs — two-stage Pythia with no quantization (configs[0]), uniform 8-bit
Qwen2 (configs[1]), importance-guided mixed 4/8-bit (configs[2]), and the
3-device multi-hop Qwen2-1.5B chain (configs[4]).

Byte accounting comes from the split runtime's measured payload sizes; the
result records bytes/token per hop alongside the PPL.

Durability matches the simulate sweep drivers (and the reference's
partial-sum checkpointing, ``Qwen2-0.5B/main.py:184-192``): an axes-validated
JSON checkpoint written every ``checkpoint_every`` chunks enables EXACT resume
— identical final PPL and measured byte totals — plus an append-only
``metrics.jsonl`` stream.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Optional, Sequence

import numpy as np
import jax
import jax.numpy as jnp

from ..models.configs import ModelConfig
from ..models.transformer import nll_from_logits, run_layers_from_ids
from ..importance import importance_per_layer
from ..parallel import SplitConfig, SplitRuntime, make_stage_mesh
from ..codecs.packing import WireCodec, get_wire_codec, selective_int4
from ..codecs.faults import FaultConfig, LinkPolicy, TierController, sum_counters
from ..codecs.fec import FECConfig, HedgeConfig, LinkHealth, LinkHealthConfig
from ..obs.metrics import (record_link_counters, record_link_health,
                           record_recovery_counters, record_wire_bytes)
from ..obs.tracing import span as obs_span
from ..obs.tracing import tracing_enabled
from ..utils.clock import MONOTONIC
from ..serve.decode import _emit_hop_spans
from ..serve.recovery import (DecodeTimeout, RecoveryCounters, StageFailure,
                              StageLostError, Watchdog)
from .harness import (ResumableDriver, _emit, _iter_window_groups,
                      _run_pipelined, fetch_global)


def parse_hop_codec(spec: str, n_seq: int = 1) -> object:
    """Codec spec -> registry name or WireCodec.

    Plain names pass through (``"int4_per_token"``, ``"int4_per_token_pallas"``);
    token-selective specs use ``"selective_int4:<ratio>[:<high>][:<mode>]"``
    (e.g. ``"selective_int4:0.25:bf16"``) or ``"selective_int4_pallas:..."``
    to pin the fused-kernel implementation explicitly.

    With ``n_seq > 1`` (the stage x seq runtime) selective specs resolve to the
    ring-sharded variant (``codecs.ring_codecs.ring_selective_int4``):
    ``mode`` picks ``"global"`` (exact dense selection via an importance
    all_gather — the default) or ``"local"`` (wire-optimal shard-local
    selection, globally agreed scale).
    """
    if not spec.startswith("selective_int4"):
        return spec
    parts = spec.split(":")
    ratio = float(parts[1]) if len(parts) > 1 else 0.25
    high = parts[2] if len(parts) > 2 else "bf16"
    mode = parts[3] if len(parts) > 3 else "global"
    if n_seq > 1:
        if parts[0].endswith("_pallas"):
            # no fused ring variant exists; silently substituting the jnp ring
            # codec would discard the user's explicit kernel pin
            raise ValueError(
                f"{parts[0]!r} has no ring (n_seq > 1) implementation; use "
                f"'selective_int4:...' and let the backend choose")
        from ..codecs.ring_codecs import ring_selective_int4

        return ring_selective_int4(ratio, high, n_seq=n_seq, mode=mode)
    if len(parts) > 3:
        raise ValueError(f"selective mode {mode!r} only applies to the "
                         f"stage x seq runtime (n_seq > 1)")
    if parts[0].endswith("_pallas"):
        from ..codecs.pallas_kernels import SELECTIVE_EXCLUSION

        # the kernel twin was DELETED round 5 on measurement; honoring the
        # pin silently with the jnp codec would misreport what ran
        raise ValueError(f"'selective_int4_pallas' no longer exists: "
                         f"{SELECTIVE_EXCLUSION}")
    return selective_int4(ratio, high)


@functools.lru_cache(maxsize=None)
def _importance_fn(cfg: ModelConfig, method: str):
    @jax.jit
    def fn(params, ids, head_weights):
        _, aux = run_layers_from_ids(cfg, params, ids, capture_stats=True)
        return importance_per_layer(aux["stats"], method, head_weights)  # (L, B, S)

    return fn


def run_split_eval(
    cfg: ModelConfig,
    params,
    token_ids: np.ndarray,
    *,
    cuts: Sequence[int],
    hop_codecs: Sequence,
    max_length: int,
    stride: int,
    importance_method: Optional[str] = None,
    head_weights: Optional[np.ndarray] = None,
    mesh=None,
    max_chunks: Optional[int] = None,
    progress=None,
    time_hops: bool = True,
    window_batch: int = 1,
    n_seq: int = 1,
    checkpoint_path: Optional[str] = None,
    checkpoint_every: int = 1000,
    metrics_path: Optional[str] = None,
    faults: Optional[object] = None,
    link_policy: Optional[object] = None,
    fec: Optional[object] = None,
    hedge: Optional[object] = None,
    link_health: Optional[object] = None,
    deadline_s: Optional[float] = None,
    stage_failure: Optional[object] = None,
    recovery: Optional[dict] = None,
    pipeline: Optional[object] = None,
    _clock=MONOTONIC,
) -> dict:
    """Token-weighted sliding-window PPL with the model split at ``cuts``.

    ``n_seq > 1`` selects the composed stage x seq runtime
    (:class:`~edgellm_tpu.parallel.ring.SplitRingRuntime`): within every
    pipeline stage the sequence is ring-sharded over a "seq" mesh axis and each
    boundary hop moves the local per-token-compressed shard — the long-context
    path, where no device ever holds the full sequence at a cut. Requires
    per-token (batch-invariant) hop codecs; windows whose length is not a
    multiple of ``n_seq`` are right-padded with masked (-100) positions, which
    is exact under causal attention.

    ``hop_codecs`` entries may be names, codec-spec strings, or WireCodec
    instances. Token-selective hops take their importance from
    ``importance_method`` (computed at the hop's cut layer by a stats pass —
    the same scores the simulate harness uses).

    ``window_batch``: run up to W full-length evaluation windows through the
    pipeline as one batch (identical accumulation — per-row NLL weighting, and
    token-selective hops carry per-row importance so every window keeps its own
    ordering and scale). With the mesh's "data" axis populated the batch is
    additionally sharded across it; a final partial group is padded up to the
    axis size with repeated windows whose loss weight is zero (the padding does
    cross the wire and is counted in the pushed-token/byte totals).

    ``faults`` (a :class:`~edgellm_tpu.codecs.faults.FaultConfig` or kwargs
    dict) turns the boundary wire faulty: every hop is sealed with the
    integrity check, corrupted per the seeded rates, and handled per
    ``link_policy`` (:class:`LinkPolicy` or dict). The chunk index is the fault
    step, so a fixed seed corrupts the same hops of the same chunks on every
    run. When ``link_policy.tiers`` names a codec ladder, a host-side
    :class:`TierController` walks it: chunks whose hops report corruption step
    the codecs down a tier (``degrade_after`` consecutive), clean chunks step
    back up (``recover_after``) — the controller observes at drain time, so
    under the two-deep submit pipeline a switch takes effect one group late.
    Per-hop counters, the tier trail, and degraded-chunk totals land in the
    result. Robustness state is per-run: a resumed run restarts counters and
    the tier ladder at tier 0 (the checkpointed PPL partial sums stay exact).

    Self-healing (PR 5): ``fec`` (:class:`~edgellm_tpu.codecs.fec.FECConfig`
    or kwargs dict) adds interleaved XOR parity to every sealed hop so a
    single corrupted chunk per parity group is repaired in band — zero extra
    hops; ``hedge`` (:class:`~edgellm_tpu.codecs.fec.HedgeConfig` or dict)
    sends each attempt over staggered redundant routes and keeps the first
    verified copy (for drop-dominated links, where parity can't help).
    ``link_health`` (:class:`~edgellm_tpu.codecs.fec.LinkHealthConfig` or
    dict) replaces the streak-based TierController with the SLO tracker:
    windowed corruption/repair/retry/hedge-win rates from the per-chunk
    counter deltas, burn-rate-driven degradation AND re-promotion over
    ``link_policy.tiers``, with a full-window re-measure plus ``min_dwell_s``
    of clock hysteresis between switches. All three require an enabled
    ``faults`` config (the link machinery otherwise never enters the graph);
    disabled configs build the exact PR 2/3 graph.

    Survivability (PR 3): ``deadline_s`` arms a host-side monotonic
    :class:`~edgellm_tpu.serve.recovery.Watchdog` that is petted after every
    drained chunk — a stalled eval writes a best-effort resume checkpoint and
    raises a typed :class:`DecodeTimeout` instead of hanging (``_clock`` is
    injectable so tests fire it deterministically). ``stage_failure`` (a
    :class:`StageFailure` or ``{"stage", "at_step"}`` dict; ``at_step`` is a
    chunk index here) marks that stage dark; the harness then re-plans the
    split boundary onto the surviving stages (evenly-spaced cuts, the first
    hop's codec on every new hop), re-places the weights, rebuilds the tier
    ladder for the new hop count, and continues the SAME accumulation —
    partial sums, chunk counters, and the metrics stream carry across the
    failover. ``recovery`` tunes the failover (``{"replan": bool,
    "max_failovers": int}``); post-failover byte totals are accounted per
    plan generation in ``result["recovery"]``. Stage failure needs the plain
    split runtime (``n_seq == 1``) — the stage x seq ring has no failover.
    With all three left at their defaults the harness builds the exact
    pre-recovery graph: the knobs are host-side orchestration only.
    """
    if isinstance(faults, dict):
        faults = FaultConfig(**faults)
    if isinstance(link_policy, dict):
        link_policy = dataclasses.replace(
            LinkPolicy(**link_policy),
            tiers=tuple(link_policy.get("tiers", ())))
    fault_on = faults is not None and faults.enabled
    policy = link_policy if link_policy is not None else LinkPolicy()
    if isinstance(fec, dict):
        fec = FECConfig(**fec)
    if isinstance(hedge, dict):
        hedge = HedgeConfig(**hedge)
    if isinstance(link_health, dict):
        link_health = LinkHealthConfig(**link_health)
    healing_requested = ((fec is not None and fec.enabled)
                         or (hedge is not None and hedge.enabled)
                         or link_health is not None)
    if healing_requested and not fault_on:
        raise ValueError(
            "fec/hedge/link_health require an enabled faults config — the "
            "link machinery only exists in the graph when a fault can fire")
    if isinstance(stage_failure, dict):
        stage_failure = StageFailure(**stage_failure)
    if stage_failure is not None and n_seq > 1:
        raise ValueError(
            "stage_failure needs the plain split runtime: the stage x seq "
            "ring has no failover re-planning (n_seq must be 1)")
    recovery_on = (deadline_s is not None or stage_failure is not None
                   or bool(recovery))
    recovery = dict(recovery or {})
    unknown = set(recovery) - {"replan", "max_failovers"}
    if unknown:
        raise ValueError(f"unknown recovery key(s): {sorted(unknown)}")
    rec_replan = bool(recovery.get("replan", True))
    rec_max_failovers = int(recovery.get("max_failovers", 1))
    if rec_max_failovers < 1:
        raise ValueError("recovery.max_failovers must be >= 1")
    rcounters = RecoveryCounters()
    wd = Watchdog(deadline_s, clock=_clock) if deadline_s is not None else None
    codecs = [parse_hop_codec(c, n_seq) if isinstance(c, str) else c
              for c in hop_codecs]
    split = SplitConfig(cuts=tuple(cuts), hop_codecs=tuple(codecs))
    if n_seq > 1:
        from ..parallel.ring import make_sp_stage_mesh

        if mesh is None:
            mesh = make_sp_stage_mesh(split.n_stages, n_seq)
    elif mesh is None:
        mesh = make_stage_mesh(split.n_stages)

    if (pipeline is not None and getattr(pipeline, "enabled", False)
            and n_seq > 1):
        raise ValueError(
            "micro-batch pipelining composes with the plain split runtime "
            "only; the stage x seq ring runtime already overlaps its hops "
            "with the ring rotation — drop pipeline or set n_seq=1")

    def _make_runtime(tier_codecs):
        if n_seq > 1:
            from ..parallel.ring import SplitRingRuntime

            return SplitRingRuntime(cfg, split.cuts, list(tier_codecs), mesh,
                                    faults=faults, policy=link_policy,
                                    fec=fec, hedge=hedge)
        return SplitRuntime(
            cfg, SplitConfig(cuts=split.cuts, hop_codecs=tuple(tier_codecs)),
            mesh, faults=faults, policy=link_policy, fec=fec, hedge=hedge,
            pipeline=pipeline)

    # tier 0 is the configured codec set; lower tiers swap EVERY hop to one
    # uniform fallback codec (payload shapes change, hence separate runtimes
    # — parameter placement is codec-independent, so ``placed`` is shared)
    ladder = [list(codecs)]
    controller = None
    health = None
    if fault_on and policy.tiers:
        for name in policy.tiers:
            c = get_wire_codec(name)  # fail fast on a bad ladder entry
            if (pipeline is not None and getattr(pipeline, "enabled", False)
                    and not c.batch_invariant and not c.needs_importance):
                raise ValueError(
                    f"degradation-ladder tier '{name}' couples batch rows; "
                    "its wire scales would change under the µ-batch split — "
                    "use batch-invariant fallback tiers or drop pipeline")
            ladder.append([name] * len(codecs))
    if link_health is not None:
        # the SLO tracker supersedes the streak controller: burn-rate-driven
        # degradation AND re-promotion, clock-hysteresis via the injectable
        # eval clock (so tests can fake it)
        health = LinkHealth(len(ladder), link_health, clock=_clock)
    elif fault_on and policy.tiers:
        controller = TierController(len(ladder), policy.degrade_after,
                                    policy.recover_after)
    runtimes = {0: _make_runtime(ladder[0])}
    rt = runtimes[0]
    placed = rt.place_params(params)
    needs_imp = [c.needs_importance for c in rt.codecs]
    if any(needs_imp) and importance_method is None:
        raise ValueError("token-selective hop codecs require importance_method")
    # only pay the stats forward when some hop actually consumes importance;
    # under the stage x seq runtime the stats come from the ring rotation
    # itself (importance_sp) — no device ever holds the full sequence
    if any(needs_imp) and importance_method is not None:
        if n_seq > 1:
            from ..parallel.ring import importance_sp

            def imp_fn(params_, ids_, hw_):
                return importance_sp(cfg, params_, ids_, mesh,
                                     importance_method, head_weights=hw_)
        else:
            imp_fn = _importance_fn(cfg, importance_method)
    else:
        imp_fn = None
    hw = None if head_weights is None else jnp.asarray(head_weights)
    n_data = dict(mesh.shape).get("data", 1)
    if window_batch % n_data:
        raise ValueError(f"window_batch {window_batch} must be a multiple of the "
                         f"mesh data axis size {n_data}")
    if getattr(rt, "pipelined", False):
        # fail before the first chunk, not inside the first traced forward
        rt.pipeline.validate_batch(window_batch, "window_batch")
    # a partial tail group pads up to the data axis AND the µ-batch grid
    # (n_data == 1 whenever pipelined: the runtime enforces a stage-only mesh)
    group_pad = n_data * (rt.pipeline.num_microbatches
                          if getattr(rt, "pipelined", False) else 1)

    # resume axes: the USER-LEVEL split spec (requested codec specs, not the
    # runtime's possibly Pallas-substituted names, so a checkpoint written on a
    # CPU host resumes on TPU and vice versa)
    axes = {
        "model": {"family": cfg.family, "num_layers": cfg.num_layers,
                  "hidden_size": cfg.hidden_size, "num_heads": cfg.num_heads,
                  "vocab_size": cfg.vocab_size},
        "cuts": [int(c) for c in cuts],
        "hop_codecs": [c if isinstance(c, str) else c.name for c in hop_codecs],
        "max_length": int(max_length), "stride": int(stride),
        "importance_method": importance_method,
        "window_batch": int(window_batch), "n_seq": int(n_seq),
        "mesh": {k: int(v) for k, v in dict(mesh.shape).items()},
    }
    if pipeline is not None and getattr(pipeline, "enabled", False):
        # the µ-batch count changes per-chunk wire traffic and fault-counter
        # shapes — a plan axis, so resume refuses a mismatched schedule.
        # Only written when pipelining is ON (axes compare by strict dict
        # equality, so an unconditional key would orphan pre-pipeline
        # checkpoints)
        axes["num_microbatches"] = int(pipeline.num_microbatches)
    if fault_on:
        # a checkpoint written under one fault regime must not silently resume
        # under another (JSON round-trips lists, so tuples are listified here)
        axes["faults"] = dataclasses.asdict(faults)
        axes["link_policy"] = {**dataclasses.asdict(policy),
                               "tiers": list(policy.tiers)}
        if fec is not None:
            axes["fec"] = dataclasses.asdict(fec)
        if hedge is not None:
            axes["hedge"] = dataclasses.asdict(hedge)
        if link_health is not None:
            axes["link_health"] = dataclasses.asdict(link_health)
    if stage_failure is not None:
        axes["stage_failure"] = dataclasses.asdict(stage_failure)
    rd = ResumableDriver(checkpoint_path, axes, checkpoint_every)
    total_nll, n_tokens = 0.0, 0.0
    fwd_tokens = 0  # every token pushed through the pipeline (incl. overlap/pad)
    real_fwd_tokens = 0  # same, minus batch-pad windows and seq-pad positions
    hop_bytes_total = [0] * len(rt.codecs)  # measured per chunk, tail included
    if rd.state is not None:
        total_nll, n_tokens = rd.state["total_nll"], rd.state["n_tokens"]
        fwd_tokens = rd.state["fwd_tokens"]
        real_fwd_tokens = rd.state["real_fwd_tokens"]
        hop_bytes_total = list(rd.state["hop_bytes_total"])

    def save_checkpoint():
        with obs_span("eval.checkpoint_write"):
            rd.save({"total_nll": total_nll, "n_tokens": n_tokens,
                     "fwd_tokens": fwd_tokens,
                     "real_fwd_tokens": real_fwd_tokens,
                     "hop_bytes_total": hop_bytes_total})

    bytes_cache: dict = {}
    degraded_chunks = 0  # chunks that ran below tier 0
    tier_log: list = []  # (chunk_index, tier) at every controller switch
    gen = 0  # plan generation: bumped on every failover re-plan
    # gen 0 shares the checkpointed hop_bytes_total list; post-failover plans
    # have a different hop count, so their bytes accumulate per generation
    gen_bytes = {0: hop_bytes_total}
    sf_pending = stage_failure is not None

    def _eval_failover(lost: int):
        """Re-plan the boundary onto the survivors and swap every per-tier
        runtime; the accumulated partial sums carry over untouched (the PPL
        metric does not depend on where the boundary sits)."""
        nonlocal mesh, split, placed, gen, ladder
        if not rec_replan or rcounters.failovers >= rec_max_failovers:
            raise  # the active StageLostError stays fatal
        rcounters.failovers += 1
        from jax.sharding import Mesh

        with obs_span("eval.failover", lost_stage=lost):
            survivors = np.delete(np.asarray(mesh.devices), lost, axis=0)
            mesh = Mesh(survivors, ("stage", "data", "model"))
            split = split.replan(cfg.num_layers, survivors.shape[0])
            rcounters.replans += 1
            ladder = [list(split.hop_codecs)]
            if controller is not None or health is not None:
                for name in policy.tiers:
                    ladder.append([name] * len(split.hop_codecs))
            runtimes.clear()
            runtimes[0] = _make_runtime(ladder[0])
            placed = runtimes[0].place_params(params)
            gen += 1
            gen_bytes[gen] = [0] * len(split.hop_codecs)

    def submit_group(group):
        nonlocal sf_pending
        n_real = len(group)
        s_unpadded = group[0].input_ids.shape[1]
        counts = [c.num_loss_tokens for c in group]
        # pad a partial group up to the data-axis size (and, when pipelined,
        # the µ-batch grid) with repeated windows; their loss weight is zero
        while len(group) % group_pad:
            group = group + [group[-1]]
            counts = counts + [0]
        ids = np.concatenate([c.input_ids for c in group])
        targets = np.concatenate([c.target_ids for c in group])
        if n_seq > 1 and ids.shape[1] % n_seq:
            # right-pad to a seq-shardable length; padded positions are masked
            # (-100) and, under causal attention, invisible to scored ones
            pad = n_seq - ids.shape[1] % n_seq
            ids = np.pad(ids, ((0, 0), (0, pad)))
            targets = np.pad(targets, ((0, 0), (0, pad)), constant_values=-100)
        ids, targets = jnp.asarray(ids), jnp.asarray(targets)
        if sf_pending and group[0].index >= stage_failure.at_step:
            sf_pending = False
            for r in runtimes.values():
                r.mark_stage_lost(stage_failure.stage)
        if health is not None:
            tier = health.tier
        else:
            tier = controller.tier if controller is not None else 0
        # the chunk index drives the fault stream: same seed => same chunks
        # corrupted, run after run (ignored when the link is off)
        fstep = group[0].index

        def _forward():
            if tier not in runtimes:  # built on first demand, cached thereafter
                runtimes[tier] = _make_runtime(ladder[tier])
            art = runtimes[tier]
            needs_t = [c.needs_importance for c in art.codecs]
            if imp_fn is not None and any(needs_t):
                imp = imp_fn(params, ids, hw)  # (L, W, S)
                hop_imp = [(imp[cut] if len(group) > 1 else imp[cut, 0])
                           if need else None
                           for cut, need in zip(split.cuts, needs_t)]
                logits = art.forward(placed, ids, hop_importance=hop_imp,
                                     fault_step=fstep)
            else:
                logits = art.forward(placed, ids, fault_step=fstep)
            return art, logits

        try:
            with obs_span("eval.submit_group", chunk=group[0].index,
                          tier=tier):
                art, logits = _forward()
        except StageLostError as e:
            _eval_failover(e.stage)
            art, logits = _forward()  # same chunk, re-planned boundary
        # this chunk's (still on-device) counters, for the tier controller
        chunk_counters = art._counter_accum[-1] if fault_on else None
        nlls = nll_from_logits(logits, targets, per_example=True)
        return (group, n_real, s_unpadded, counts, ids.shape, nlls, tier,
                chunk_counters, art, gen)

    def drain_group(rec):
        with obs_span("eval.drain_group", chunk=rec[0][-1].index):
            _drain_impl(rec)

    def _drain_impl(rec):
        nonlocal total_nll, n_tokens, fwd_tokens, real_fwd_tokens
        nonlocal degraded_chunks
        (group, n_real, s_unpadded, counts, (w, s_chunk), nlls, tier,
         chunk_counters, art, g) = rec
        # the per-example NLLs ride the mesh's data axis, which is the one
        # axis allowed to span processes in a multi-host run
        total_nll += float(fetch_global(nlls).astype(np.float64)
                           @ np.asarray(counts, np.float64))
        n_tokens += sum(counts)
        fwd_tokens += w * s_chunk
        real_fwd_tokens += n_real * s_unpadded
        key = (g, tier, w, s_chunk)
        if key not in bytes_cache:  # payloads are shape-determined
            bytes_cache[key] = art.hop_bytes(w, s_chunk)
        for i, b in enumerate(bytes_cache[key]):
            gen_bytes[g][i] += b
        if tier:
            degraded_chunks += 1
        if health is not None:
            prev = health.tier
            if health.observe(chunk_counters) != prev:
                tier_log.append((group[-1].index, health.tier))
        elif controller is not None:
            corrupted = any(
                int(np.asarray(chunk_counters[k]).sum())
                for k in ("detected", "budget_dropped"))
            prev = controller.tier
            if controller.observe(corrupted) != prev:
                tier_log.append((group[-1].index, controller.tier))
        if progress:
            progress(group[-1].index)
        if rd.advance(group, count=n_real):
            save_checkpoint()
            rec_out = {
                "chunk": group[-1].index, "chunks": rd.chunks,
                "n_tokens": n_tokens,
                "ppl": float(np.exp(total_nll / max(n_tokens, 1e-9))),
                "hop_bytes_total": hop_bytes_total}
            if fault_on:
                rec_out["tier"] = tier
            if health is not None:
                rec_out["burn_rate"] = health.burn_rate
            _emit(metrics_path, rec_out)
        if wd is not None:
            # pet-the-dog once per drained chunk; a stall past the deadline
            # writes a best-effort resume checkpoint and raises typed
            try:
                wd.check(save_checkpoint, what="eval chunk")
            except DecodeTimeout:
                rcounters.watchdog_fires += 1
                raise

    _run_pipelined(
        _iter_window_groups(token_ids, max_length, stride,
                            window_batch=window_batch,
                            start_chunk=rd.start_chunk,
                            max_count=rd.remaining(max_chunks)),
        submit_group, drain_group)
    wall = rd.wall()  # cumulative across resumes
    save_checkpoint()

    seq = min(max_length, len(np.asarray(token_ids).reshape(-1)))
    result = {
        "ppl": float(np.exp(total_nll / max(n_tokens, 1e-9))),
        "total_nll": total_nll,
        "n_tokens": n_tokens,
        "chunks": rd.chunks,
        "wall_s": wall,
        "tokens_per_s": fwd_tokens / max(wall, 1e-9),
        "scored_tokens_per_s": n_tokens / max(wall, 1e-9),
        "cuts": list(split.cuts),
        "hop_codecs": [c.name for c in rt.codecs],
        # analytic per-token rate at the steady window size, plus the ACTUAL
        # byte totals accumulated chunk by chunk (short tail windows and
        # selective codecs' length-dependent splits included)
        "bytes_per_token_per_hop": rt.bytes_per_token(seq),
        "measured_hop_bytes_total": hop_bytes_total,
        "measured_bytes_per_fwd_token_per_hop": [
            b / max(fwd_tokens, 1) for b in hop_bytes_total],
        # fwd_tokens counts every pipeline-pushed token (batch-pad windows and
        # seq-pad positions included — they DO cross the wire); these separate
        # wire traffic from useful throughput for small corpora / big batches
        "real_fwd_tokens": real_fwd_tokens,
        "pad_fraction": 1.0 - real_fwd_tokens / max(fwd_tokens, 1),
        "real_tokens_per_s": real_fwd_tokens / max(wall, 1e-9),
        "mesh": dict(mesh.shape),
    }
    if fault_on:
        agg = None  # per-hop counters summed over every tier's runtime
        for r in runtimes.values():
            c = r.link_counters()
            if c is None:
                continue
            if agg is None:
                agg = {k: v.copy() for k, v in c.items()}
            else:
                for k in agg:
                    agg[k] += c[k]
        result["faults"] = dataclasses.asdict(faults)
        result["link_policy"] = {**dataclasses.asdict(policy),
                                 "tiers": list(policy.tiers)}
        result["link_counters"] = {k: [int(x) for x in v]
                                   for k, v in (agg or {}).items()}
        result["tier_ladder"] = [[c if isinstance(c, str) else c.name
                                  for c in t] for t in ladder]
        result["tier_switches"] = [list(t) for t in tier_log]
        result["final_tier"] = (health.tier if health is not None
                                else controller.tier
                                if controller is not None else 0)
        result["degraded_chunks"] = degraded_chunks
        if fec is not None:
            result["fec"] = dataclasses.asdict(fec)
        if hedge is not None:
            result["hedge"] = dataclasses.asdict(hedge)
        if health is not None:
            result["link_health"] = health.summary()
    if recovery_on:
        rec_block = {
            "deadline_s": deadline_s,
            "stage_failure": (dataclasses.asdict(stage_failure)
                              if stage_failure is not None else None),
            "counters": rcounters.as_dict(),
            "plan_generations": gen + 1,
        }
        if rcounters.failovers:
            rec_block["replanned_cuts"] = list(split.cuts)
            rec_block["failover_hop_codecs"] = [c.name
                                               for c in runtimes[0].codecs]
            rec_block["failover_hop_bytes_total"] = {
                str(g): list(b) for g, b in gen_bytes.items() if g > 0}
            rec_block["failover_mesh"] = dict(mesh.shape)
        result["recovery"] = rec_block
    if getattr(rt, "pipelined", False):
        result["pipeline"] = rt.pipeline_summary()
    if time_hops and rd.chunks:
        t_seq = seq if n_seq <= 1 else seq + (-seq) % n_seq
        # after a failover, time the boundary that actually finished the run
        timed_rt = runtimes[0] if rcounters.failovers else rt
        with obs_span("eval.time_hops", seq=t_seq):
            result["per_hop_ms"] = timed_rt.time_hops(1, t_seq)
        # the ring runtime is a whole-window forward — no per-token decode
        # surface, so nothing to time at the (B, 1, D) shape
        if hasattr(timed_rt, "time_decode_hops"):
            with obs_span("eval.time_decode_hops"):
                result["per_decode_hop_ms"] = timed_rt.time_decode_hops(1)
        # the flat lists above are positional; label each entry with the
        # boundary it measures so multi-hop configs (split4 multihop) can
        # attribute WHICH cut is slow without cross-referencing the config
        timed_cuts = list(timed_rt.split.cuts)
        result["per_hop_timing"] = [
            {"hop": s, "cut_layer": int(timed_cuts[s]),
             "codec": timed_rt.codecs[s].name,
             "forward_ms": result["per_hop_ms"][s],
             **({"decode_ms": result["per_decode_hop_ms"][s]}
                if "per_decode_hop_ms" in result else {})}
            for s in range(len(timed_cuts))]
    # mirror this sweep's totals into the global registry (no-ops when
    # observability is off): wire bytes, fault/health/recovery counters
    record_wire_bytes(hop_bytes_total, kind="eval_forward")
    final_rt = runtimes[0] if recovery_on and rcounters.failovers else rt
    if fault_on:
        record_link_counters(result["link_counters"])
        if health is not None:
            record_link_health(result["link_health"])
    if recovery_on:
        record_recovery_counters(rcounters)
    if tracing_enabled() and hasattr(final_rt, "hop_attribution"):
        # one attribution span per boundary cut for the whole sweep: cut
        # layer, codec, total wire bytes moved, and the worst ladder outcome
        _emit_hop_spans(final_rt, result.get("link_counters"),
                        list(hop_bytes_total),
                        link_tier=getattr(health, "tier", None),
                        chunks=int(rd.chunks))
    final_rec = {"final": True, "chunks": rd.chunks, "n_tokens": n_tokens,
                 "ppl": result["ppl"], "wall_s": wall,
                 "hop_bytes_total": hop_bytes_total,
                 "pad_fraction": result["pad_fraction"]}
    if fault_on:
        final_rec["link_counters"] = result["link_counters"]
        final_rec["degraded_chunks"] = degraded_chunks
        if health is not None:
            final_rec["burn_rate"] = health.burn_rate
    if recovery_on:
        final_rec["failovers"] = rcounters.failovers
    _emit(metrics_path, final_rec)
    return result


def run_fault_sweep(
    cfg: ModelConfig,
    params,
    token_ids: np.ndarray,
    *,
    rates: Sequence[float],
    knob: str = "drop_rate",
    seed: int = 0,
    byte_budget: Optional[int] = None,
    link_policy: Optional[object] = None,
    **eval_kwargs,
) -> list:
    """PPL / throughput / counter curve as a function of fault rate.

    Runs :func:`run_split_eval` once per entry of ``rates``, setting ``knob``
    (``"drop_rate"``, ``"bitflip_rate"``, or ``"scale_corrupt_rate"``) on a
    fresh :class:`FaultConfig` each time. Rate 0 with no ``byte_budget`` runs
    the plain fault-free graph — the sweep's exact baseline point. Each result
    dict gains ``fault_knob`` / ``fault_rate``; remaining kwargs pass through
    (cuts, hop_codecs, max_length, stride, ...). Healing kwargs
    (``fec``/``hedge``/``link_health``) are withheld from fault-free points —
    the clean graph has no link to heal, so the baseline stays exact.
    """
    if knob not in ("drop_rate", "bitflip_rate", "scale_corrupt_rate"):
        raise ValueError(f"unknown fault knob {knob!r}")
    out = []
    for r in rates:
        fc = FaultConfig(**{knob: float(r)}, byte_budget=byte_budget,
                         seed=seed)
        kw = eval_kwargs
        if not fc.enabled:
            kw = {k: v for k, v in eval_kwargs.items()
                  if k not in ("fec", "hedge", "link_health")}
        res = run_split_eval(cfg, params, token_ids,
                             faults=fc if fc.enabled else None,
                             link_policy=link_policy, **kw)
        res["fault_knob"] = knob
        res["fault_rate"] = float(r)
        out.append(res)
    return out


def run_kv_tier_eval(
    cfg: ModelConfig,
    params,
    token_ids: np.ndarray,
    *,
    kv_codec: str = "fp",
    max_length: int,
    stride: int,
    page_size: int = 16,
    window_batch: int = 4,
    max_chunks: Optional[int] = None,
    compute_dtype=None,
    metrics_path: Optional[str] = None,
    progress=None,
) -> dict:
    """Token-weighted sliding-window PPL with the KV cache held AT REST in
    one ``kv_codec`` tier (models.paged_kv.KV_PAGE_CODECS).

    The boundary sweep measures what wire compression costs; this measures
    what PAGE compression costs, with the same window/stride/masking recipe
    and the same token weighting, so the two curves are directly comparable.
    Every window is teacher-force decoded through a paged pool one position
    at a time — the exact serving data path (quantize-on-append, gather then
    dequantize in the attend), not a whole-window forward, so the PPL delta vs the
    ``"fp"`` tier is the delta a served stream actually experiences. One
    executable per (window_batch, window_length) group shape; full-length
    groups all share one, the short corpus tail gets its own.
    """
    from ..models.paged_kv import resolve_kv_codec as _resolve_tier
    from ..models.paged_kv import (init_pool, init_quant_pool, kv_page_bytes,
                                   paged_decode_step)

    codec = _resolve_tier(kv_codec)
    fn_cache: dict = {}

    def _make_fn(w, t, pps, num_pages):
        def fn(p, pt, ids, targets):
            pool = (init_quant_pool(cfg, num_pages, page_size, codec.name)
                    if codec.quantized
                    else init_pool(cfg, num_pages, page_size))

            def body(pool_c, xs):
                tok, tgt, step = xs
                lengths = jnp.full((w,), step, jnp.int32)
                logits, pool2 = paged_decode_step(
                    cfg, p, pool_c, pt, lengths, tok,
                    compute_dtype=compute_dtype)
                logp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
                valid = tgt != -100
                safe = jnp.where(valid, tgt, 0)
                nll = -jnp.take_along_axis(logp, safe[:, None], 1)[:, 0]
                return pool2, (jnp.where(valid, nll, 0.0), valid)

            # feed positions 0..t-2; the step-s logits score target s+1 —
            # the same shift nll_from_logits applies to whole-window logits
            xs = (ids[:, :-1].T, targets[:, 1:].T, jnp.arange(t - 1))
            _, (nlls, valids) = jax.lax.scan(body, pool, xs)
            return nlls.sum(0), valids.sum(0).astype(jnp.float32)
        return jax.jit(fn)

    total_nll, n_tokens, chunks = 0.0, 0.0, 0
    t0 = time.perf_counter()
    for group in _iter_window_groups(token_ids, max_length, stride,
                                     window_batch=window_batch,
                                     max_count=max_chunks):
        ids = np.concatenate([c.input_ids for c in group])       # (W, T)
        targets = np.concatenate([c.target_ids for c in group])
        counts = np.array([c.num_loss_tokens for c in group], np.float64)
        w, t = ids.shape
        pps = -(-t // page_size)
        num_pages = 1 + w * pps                  # page 0 stays the trash page
        key = (w, t)
        if key not in fn_cache:
            fn_cache[key] = _make_fn(w, t, pps, num_pages)
        pt = jnp.asarray(np.arange(1, num_pages, dtype=np.int32)
                         .reshape(w, pps))
        nll_sum, n_valid = fn_cache[key](params, pt, jnp.asarray(ids),
                                         jnp.asarray(targets))
        per_window = (np.asarray(nll_sum, np.float64)
                      / np.maximum(np.asarray(n_valid, np.float64), 1.0))
        total_nll += float(per_window @ counts)
        n_tokens += float(counts.sum())
        chunks += len(group)
        if progress:
            progress(group[-1].index)
    wall = time.perf_counter() - t0
    result = {
        "kv_codec": codec.name,
        "ppl": float(np.exp(total_nll / max(n_tokens, 1e-9))),
        "total_nll": total_nll,
        "n_tokens": n_tokens,
        "chunks": chunks,
        "wall_s": wall,
        "page_size": page_size,
        "window_batch": window_batch,
        # bytes one page costs at this tier (all layers, K+V, codes+scales) —
        # the capacity story: fp_bytes / tier_bytes pages fit per fp page
        "kv_page_bytes": kv_page_bytes(cfg, page_size, kv_codec=codec.name),
        "kv_page_bytes_fp": kv_page_bytes(cfg, page_size),
    }
    _emit(metrics_path, {"final": True, **{k: result[k] for k in
                         ("kv_codec", "ppl", "n_tokens", "chunks", "wall_s",
                          "kv_page_bytes")}})
    return result


def run_kv_tier_sweep(
    cfg: ModelConfig,
    params,
    token_ids: np.ndarray,
    *,
    tiers: Sequence[str] = ("fp", "int8_per_channel", "int4_per_channel"),
    **eval_kwargs,
) -> list:
    """PPL / page-bytes curve as a function of KV-at-rest tier.

    Runs :func:`run_kv_tier_eval` once per entry of ``tiers`` — the KV twin
    of :func:`run_fault_sweep`'s rate sweep, with the ``"fp"`` entry as the
    exact baseline point (plain fp pages, the pre-quantization data path).
    Each result gains ``ppl_delta_vs_fp`` when the sweep includes ``"fp"``.
    """
    out = [run_kv_tier_eval(cfg, params, token_ids, kv_codec=t, **eval_kwargs)
           for t in tiers]
    base = next((r["ppl"] for r in out if r["kv_codec"] == "fp"), None)
    if base is not None:
        for r in out:
            r["ppl_delta_vs_fp"] = (r["ppl"] - base) / base
    return out
