"""Pipeline-split forward over a device mesh with packed boundary transfers.

Maps the reference's conceptual architecture (a causal LM cut at "boundary
layers", activations compressed across each cut — ``README.md:16-23``) onto a TPU
mesh:

- mesh axes: ``("stage", "data", "model")`` — pipeline stages (explicit
  ``ppermute`` hops), data parallelism over evaluation windows, and tensor
  parallelism of the per-stage weights (Megatron-style column/row splits with
  an explicit in-block ``psum`` — see ``place_params._layer_pspec``).
- each stage owns a contiguous slice of the stacked layer parameters; stages are
  padded to equal layer counts with zero layers that are masked to identity, so
  the whole pipeline is one ``shard_map`` body with a static stage unroll.
- at each cut the boundary activation is ENCODED to a packed payload (int4
  nibbles, ternary crumbs, int8 + scales — ``edgellm_tpu.codecs.packing``), the
  payload pytree crosses to the next device via ``lax.ppermute`` over ICI, and is
  DECODED on arrival. Bytes-per-token is measured from the payload buffers.

This executes the *same math* as the reference's in-place simulation (verified in
tests: a wire-codec split run reproduces the simulate-codec PPL exactly) while
actually moving compressed bytes between devices. The multi-hop chain
(BASELINE.json configs[4]: 3-device Qwen2-1.5B with per-hop codecs) is the same
code with two cuts.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional, Sequence

import numpy as np
import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models.configs import ModelConfig
from ..models.transformer import (block, block_decode, block_verify, embed,
                                  unembed, precompute_rope, KVCache)
from ..models import paged_kv
from ..models.paged_kv import KVTierMismatchError, block_decode_paged, \
    pool_tier, resolve_kv_codec
from ..codecs.packing import get_wire_codec, WireCodec
from ..codecs.faults import FaultConfig, FaultyLink, LinkPolicy, sum_counters
from ..codecs.pallas_kernels import pallas_variant
from ..lint import graph_contract
from ..serve.recovery import StageLostError


#: axes of a staged pool before its page axis: (n_stages, stage_size)
_STAGED = 2


@functools.partial(jax.jit, static_argnames=("head",), donate_argnums=(0,))
def _adopt_paged_impl(pool, k_seq, v_seq, dest, head=None):
    """One stream's (n_stages, sz, n, KV, hd) prefill K/V into the per-stage
    pools at flat token indices ``dest``: ``paged_kv.adopt_at`` over the
    staged pool (the sharded stage axis stays sliced, ``stage_size`` folds
    into the row index). Donated in-place update; elementwise along "stage",
    so the pool sharding propagates hop-free."""
    return paged_kv.adopt_at(pool, k_seq, v_seq, dest, _STAGED, head)


@jax.named_scope("unembed_sample")
def _unembed_last(cfg, placed, hidden):
    """The ragged step's (B, V) fp32 logits, under the scope the local
    step's unembed and the sampler share."""
    return unembed(cfg, placed, hidden)[:, -1]


def make_stage_mesh(n_stages: int, n_data: int = 1, n_model: int = 1,
                    devices=None) -> Mesh:
    """Build a ("stage", "data", "model") mesh from the first
    n_stages*n_data*n_model available devices."""
    need = n_stages * n_data * n_model
    devices = np.asarray(devices if devices is not None else jax.devices())
    if devices.size < need:
        raise ValueError(f"need {need} devices, have {devices.size}")
    grid = devices.reshape(-1)[:need].reshape(n_stages, n_data, n_model)
    return Mesh(grid, ("stage", "data", "model"))


def apply_default_codec_backend(codecs: list) -> list:
    """Resolve hop-codec specs (names or ``WireCodec`` instances) to the codec
    each hop runs: a base codec's Pallas twin where
    ``pallas_kernels.pallas_variant`` answers one (on a TPU, for the codecs
    its table holds), the codec itself everywhere else. An explicit
    ``*_pallas`` name is a codec like any other and is kept. Shared by every
    runtime that owns hop codecs."""
    codecs = [c if isinstance(c, WireCodec) else get_wire_codec(c) for c in codecs]
    return [pallas_variant(c) or c for c in codecs]


def regroup_layers(layers: dict, bounds: list, stage_size: int) -> tuple:
    """(L, ...) stacked layers -> (n_stages, stage_size, ...) padded groups +
    validity mask. Padding layers are zeros and masked to identity in the
    stage body."""
    n_stages = len(bounds)
    groups, valid = {}, np.zeros((n_stages, stage_size), np.bool_)
    for s, (start, stop) in enumerate(bounds):
        valid[s, : stop - start] = True
    for k, v in layers.items():
        arr = np.zeros((n_stages, stage_size) + v.shape[1:], np.asarray(v).dtype)
        for s, (start, stop) in enumerate(bounds):
            arr[s, : stop - start] = np.asarray(v[start:stop])
        groups[k] = arr
    return groups, valid


def cross_cut(codec, hidden, s: int, axis_name: str, idx, imp=None,
              link=None, fault_key=None, counters=None) -> tuple:
    """The hop of cut ``s``, the one ladder every stage runner crosses a cut
    with (inside shard_map on ``axis_name``): ENCODE the boundary activation
    (with its importance side channel where the codec takes one), ``ppermute``
    each payload leaf ``s -> s + 1``, DECODE on arrival, all under the scope
    ``split.hop.{s}``. An active ``link`` owns the crossing instead (seal,
    inject, verify, retry, keyed by ``fault_key``) and advances ``counters``.
    Returns (hidden, counters)."""
    with jax.named_scope(f"split.hop.{s}"):
        if link is not None:
            return link.hop(codec, hidden, s, axis_name, idx, fault_key,
                            counters, hop_imp=imp)
        payload = (codec.encode(hidden) if imp is None
                   else codec.encode(hidden, imp))
        moved = jax.tree_util.tree_map(
            lambda a: jax.lax.ppermute(a, axis_name, [(s, s + 1)]), payload)
        return jnp.where(idx == s + 1, codec.decode(moved), hidden), counters


def run_pipeline_stages(n_stages: int, codecs: list, run_stage, hidden,
                        hop_imps=None, axis_name: str = "stage",
                        link=None, fault_key=None):
    """The pipeline-unroll + boundary-hop protocol, shared by SplitRuntime and
    the stage x seq SplitRingRuntime (must run inside shard_map on
    ``axis_name``).

    Every device executes ``run_stage`` (its local layer scan) once per unroll
    step, keeping the result only when the step index matches its stage; at
    each cut the boundary activation is ENCODED to a packed payload, crossed to
    the next device via ``ppermute``, and DECODED on arrival. The final psum
    replicates the last stage's output structurally (no vma typing needed for
    Pallas-backed codecs).

    ``link`` (a :class:`~edgellm_tpu.codecs.faults.FaultyLink`) reroutes every
    hop through the faulty-wire protocol — seal, inject, verify, retry — keyed
    by ``fault_key``; the return value then becomes ``(out, counters)`` with
    the per-hop counters psum-replicated over ``axis_name``. With ``link``
    None this is byte-for-byte the original lossless path."""
    idx = jax.lax.axis_index(axis_name)
    counters = link.init_counters(n_stages - 1) if link is not None else None
    for s in range(n_stages):
        with jax.named_scope("split.stage"):
            computed = run_stage(hidden)
        hidden = jnp.where(idx == s, computed, hidden)
        if s == n_stages - 1:
            break
        imp = hop_imps[s] if codecs[s].needs_importance else None
        hidden, counters = cross_cut(codecs[s], hidden, s, axis_name, idx, imp,
                                     link, fault_key, counters)
    out = jax.lax.psum(
        jnp.where(idx == n_stages - 1, hidden, jnp.zeros_like(hidden)), axis_name)
    if link is None:
        return out
    counters = {k: jax.lax.psum(v, axis_name) for k, v in counters.items()}
    return out, counters


def keep_carry(keep, new, old):
    """``new`` where ``keep`` else ``old``, leaf by leaf: how a stage body
    whose carry has nowhere to send an unwanted write (a contiguous KV cache)
    drops the update of a dead unroll iteration — one select of the whole
    carry. A paged pool has the trash page instead and never comes here."""
    return jax.tree_util.tree_map(
        lambda n, o: jnp.where(keep, n, o), new, old)


def run_pipeline_stages_carry(n_stages: int, codecs: list, run_stage, hidden,
                              carry, axis_name: str = "stage",
                              link=None, fault_key=None):
    """:func:`run_pipeline_stages` for stage bodies that thread stage-local
    state (the decode KV cache, the page pool): ``run_stage(hidden, carry,
    keep) -> (hidden, carry)``. Every device runs the body in each of the
    ``n_stages`` unroll iterations; ``keep`` (traced, per device) is True in
    the ONE iteration where the hidden it transforms is the real pipeline
    activation. The stage body owns its carry, as under
    :func:`run_pipeline_stages_carry_microbatched`: what it returns is taken
    as it is, so it must leave the carry's live contents alone when ``keep``
    is False — a contiguous cache by selecting its update away
    (:func:`keep_carry`), a paged pool by sending the dead iteration's row
    write to the trash page, which costs no pass over the pool. Only the
    small hidden state is selected here. Per-stage state so updates exactly
    once per token, and nothing but the (B, 1, D) boundary activation ever
    crosses a cut. Returns (final hidden, carry), plus the psum-replicated
    fault counters when ``link`` is given (see :func:`run_pipeline_stages`)."""
    idx = jax.lax.axis_index(axis_name)
    counters = link.init_counters(n_stages - 1) if link is not None else None
    for s in range(n_stages):
        keep = idx == s
        with jax.named_scope("split.stage"):
            computed, carry = run_stage(hidden, carry, keep)
        hidden = jnp.where(keep, computed, hidden)
        if s == n_stages - 1:
            break
        hidden, counters = cross_cut(codecs[s], hidden, s, axis_name, idx,
                                     None, link, fault_key, counters)
    out = jax.lax.psum(
        jnp.where(idx == n_stages - 1, hidden, jnp.zeros_like(hidden)), axis_name)
    if link is None:
        return out, carry
    counters = {k: jax.lax.psum(v, axis_name) for k, v in counters.items()}
    return out, carry, counters


@dataclasses.dataclass(frozen=True)
class PipelineConfig:
    """Micro-batch pipelining of the stage unroll (ROADMAP item 4).

    ``num_microbatches`` (M): the batch is split into M contiguous row
    groups and the stage loop runs a GPipe-style fill/steady/drain schedule
    of M + n_stages - 1 unroll steps, so in steady state every stage
    computes a different µ-batch in the same step while the quantized
    boundary activations of the others are on the wire — instead of one
    stage computing and n_stages - 1 idling. M == 1 is the disabled
    configuration: the runtime dispatches to the ORIGINAL sequential
    unroll, byte-identical to a build that never saw this class (the
    "split.*.pipeline-disabled-identity" lint pins hold it to that).

    The schedule preserves token identity with the sequential path at any
    M: each µ-batch flows through exactly the same per-stage math and the
    same per-cut codec, just interleaved in time. That holds only when
    codecs treat batch rows independently (``WireCodec.batch_invariant``;
    scales reduced over the whole batch would change with the µ-batch
    split), which :class:`SplitRuntime` validates at construction.
    """

    num_microbatches: int = 1

    def __post_init__(self):
        if self.num_microbatches < 1:
            raise ValueError(
                f"num_microbatches must be >= 1, got {self.num_microbatches}")

    @property
    def enabled(self) -> bool:
        return self.num_microbatches > 1

    def validate_batch(self, batch: int, what: str = "batch") -> int:
        """Check ``batch`` splits evenly into µ-batches; return rows per
        µ-batch. Every pipelined entry point calls this, so a bad batch
        fails loudly host-side instead of tracing a ragged schedule."""
        m = self.num_microbatches
        if batch < m or batch % m:
            raise ValueError(
                f"{what} {batch} must be a positive multiple of "
                f"num_microbatches={m} (each µ-batch needs >= 1 row)")
        return batch // m

    def summary(self, n_stages: int) -> dict:
        """Host-side schedule accounting: unroll length, per-stage
        occupancy (every stage is busy M of the M + n - 1 steps) and the
        analytic bubble fraction (n - 1) / (M + n - 1) — the number
        BENCH_PIPE gates against the sequential (n - 1) / n bound."""
        m, n = self.num_microbatches, n_stages
        t = m + n - 1
        return {
            "enabled": self.enabled,
            "num_microbatches": m,
            "n_stages": n,
            "unroll_steps": t,
            "stage_occupancy": [m / t] * n,
            "bubble_fraction_schedule": (n - 1) / t,
            "bubble_fraction_sequential": (n - 1) / n,
        }


def _microbatch_imp(codec, hop_imps, s: int, mb: int, mb_rows: int):
    """The importance entry one (cut, µ-batch) hop ships: per-row (B, S)
    importance is sliced to the µ-batch's own rows (static slice — mb is a
    Python int in the schedule), shared (S,) importance is passed whole."""
    if not codec.needs_importance:
        return None
    imp = hop_imps[s]
    if imp.ndim == 2:
        return jax.lax.slice_in_dim(imp, mb * mb_rows, (mb + 1) * mb_rows,
                                    axis=0)
    return imp


def run_pipeline_stages_microbatched(n_stages: int, codecs: list,
                                     num_microbatches: int, run_stage, hidden,
                                     hop_imps=None, axis_name: str = "stage",
                                     link=None, fault_key=None):
    """Micro-batch pipelined twin of :func:`run_pipeline_stages` (must run
    inside shard_map on ``axis_name``).

    Fill/steady/drain over T = M + n_stages - 1 unroll steps. Each device
    keeps one µ-batch-sized activation register; at step t the device at
    stage s is working on µ-batch b = t - s (valid iff 0 <= b < M — the
    fill and drain triangles are masked, their compute discarded). Stage 0
    ingests µ-batch t while t < M; the last stage emits µ-batch
    t - (n_stages - 1) as it completes. Hops run in REVERSED cut order so a
    cut's send reads the activation its stage just computed before the
    upstream cut's receive overwrites the register with the next µ-batch.
    Because both t and s are Python ints, the µ-batch index mb = t - s of
    every hop is static: hops outside [0, M) are simply not traced (the
    wire carries exactly M payloads per cut, which the
    "split.*.pipelined" lint contracts count), and under ``link`` each
    µ-batch draws its own fault key (``fold_in(fault_key, mb)``) and bumps
    its own counter row — the return value's counters are {key: (M,
    n_hops)}, one row per µ-batch, psum-replicated like the sequential
    path's.

    Output: the M emitted (B/M, ...) blocks are stacked, psum-replicated
    in ONE collective, and re-flattened to the caller's (B, ...) batch —
    same contract as the sequential function, one psum in the graph."""
    idx = jax.lax.axis_index(axis_name)
    m = int(num_microbatches)
    n_hops = n_stages - 1
    batch = hidden.shape[0]
    mb_rows = batch // m
    micro = [jax.lax.slice_in_dim(hidden, b * mb_rows, (b + 1) * mb_rows,
                                  axis=0) for b in range(m)]
    counters = [None if link is None else link.init_counters(n_hops)
                for _ in range(m)]
    act = jnp.zeros_like(micro[0])
    outs = []
    for t in range(m + n_stages - 1):
        if t < m:
            act = jnp.where(idx == 0, micro[t], act)
        here = t - idx  # which µ-batch THIS device holds (traced)
        valid = (here >= 0) & (here < m)
        with jax.named_scope("split.stage"):
            computed = run_stage(act)
        act = jnp.where(valid, computed, act)
        if 0 <= t - (n_stages - 1) < m:
            outs.append(jnp.where(idx == n_stages - 1, act,
                                  jnp.zeros_like(act)))
        for s in reversed(range(n_hops)):
            mb = t - s  # static: only in-flight (cut, µ-batch) hops trace
            if not 0 <= mb < m:
                continue
            act, counters[mb] = cross_cut(
                codecs[s], act, s, axis_name, idx,
                _microbatch_imp(codecs[s], hop_imps, s, mb, mb_rows), link,
                None if link is None else jax.random.fold_in(fault_key, mb),
                counters[mb])
    out = jax.lax.psum(jnp.stack(outs), axis_name)  # (M, B/M, ...)
    out = out.reshape((batch,) + out.shape[2:])
    if link is None:
        return out
    counters = {k: jax.lax.psum(jnp.stack([c[k] for c in counters]),
                                axis_name)
                for k in counters[0]}
    return out, counters


def run_pipeline_stages_carry_microbatched(n_stages: int, codecs: list,
                                           num_microbatches: int, run_stage,
                                           hidden, carry,
                                           axis_name: str = "stage",
                                           link=None, fault_key=None):
    """:func:`run_pipeline_stages_microbatched` for stage bodies that
    thread stage-local state (the decode KV caches): ``run_stage(h_mu,
    carry, b, valid) -> (h_mu, carry)`` where ``b`` is the device's current
    µ-batch index clipped into [0, M) (traced — each device is at a
    different µ-batch in the same unroll step) and ``valid`` gates the fill
    and drain triangles. The stage body owns the µ-batch view of its carry
    — slicing the µ-batch's cache rows at ``b`` and masking the write-back
    when ``valid`` is False (contiguous caches) or redirecting it to the
    trash page (paged pools) — so each µ-batch's cache rows update exactly
    once per token, same as the sequential schedule. Returns (hidden,
    carry) plus the {key: (M, n_hops)} psum-replicated counters when
    ``link`` is given."""
    idx = jax.lax.axis_index(axis_name)
    m = int(num_microbatches)
    n_hops = n_stages - 1
    batch = hidden.shape[0]
    mb_rows = batch // m
    micro = [jax.lax.slice_in_dim(hidden, b * mb_rows, (b + 1) * mb_rows,
                                  axis=0) for b in range(m)]
    counters = [None if link is None else link.init_counters(n_hops)
                for _ in range(m)]
    act = jnp.zeros_like(micro[0])
    outs = []
    for t in range(m + n_stages - 1):
        if t < m:
            act = jnp.where(idx == 0, micro[t], act)
        here = t - idx
        valid = (here >= 0) & (here < m)
        b = jnp.clip(here, 0, m - 1)
        with jax.named_scope("split.stage"):
            computed, carry = run_stage(act, carry, b, valid)
        act = jnp.where(valid, computed, act)
        if 0 <= t - (n_stages - 1) < m:
            outs.append(jnp.where(idx == n_stages - 1, act,
                                  jnp.zeros_like(act)))
        for s in reversed(range(n_hops)):
            mb = t - s
            if not 0 <= mb < m:
                continue
            act, counters[mb] = cross_cut(
                codecs[s], act, s, axis_name, idx, None, link,
                None if link is None else jax.random.fold_in(fault_key, mb),
                counters[mb])
    out = jax.lax.psum(jnp.stack(outs), axis_name)
    out = out.reshape((batch,) + out.shape[2:])
    if link is None:
        return out, carry
    counters = {k: jax.lax.psum(jnp.stack([c[k] for c in counters]),
                                axis_name)
                for k in counters[0]}
    return out, carry, counters


def hop_payload_bytes(codecs, cfg, batch: int, seq: int) -> list:
    """Measured payload bytes per hop for one (batch, seq, D) boundary
    activation — the BASELINE.json metric's numerator, shared by every runtime.
    (For the stage x seq runtime each device moves its local sequence shard;
    per-token codecs' payloads are sequence-additive, so the total equals one
    full-sequence encode.)"""
    shape = (batch, seq, cfg.hidden_size)
    return [c.payload_bytes(shape) for c in codecs]


def measure_hop_times(mesh, codecs, cfg, batch: int, seq: int, *,
                      iters: int = 20, warmup: int = 1,
                      hidden_spec: P = P()) -> list:
    """Per-hop boundary-transfer time (ms): encode -> ppermute over "stage" ->
    decode, isolated from stage compute. ``hidden_spec`` places the probe
    activation on the mesh (replicated for the plain split runtime,
    seq-sharded ``P(None, "seq")`` for the stage x seq runtime, which times the
    local-shard payloads its hops actually move).

    ``warmup`` is clamped to >= 1: the first call compiles the hop
    executable, and a compile second leaking into a per-hop millisecond
    poisons every downstream SLO/bench number (the BENCH_SOAK rule)."""
    from ..utils.profiling import timed

    warmup = max(1, int(warmup))

    results = []
    hidden = jax.random.normal(
        jax.random.key(0), (batch, seq, cfg.hidden_size), jnp.float32)
    # match forward's wire format: batched windows ship per-row importance
    # (B x S order side channel), so time that payload, not the shared one
    imp = (jnp.arange(seq, dtype=jnp.float32) if batch == 1 else
           jnp.broadcast_to(jnp.arange(seq, dtype=jnp.float32), (batch, seq)))
    # the probe importance shards over the token axis exactly like the hidden:
    # hidden_spec's axes are (batch, tokens[, features]), so the token entry
    # is hidden_spec[1] (None for the replicated plain-split probe, "seq" for
    # the stage x seq probe)
    token_axis = hidden_spec[1] if len(hidden_spec) > 1 else None
    imp_spec = (P(token_axis) if imp.ndim == 1
                else P(hidden_spec[0] if hidden_spec else None, token_axis))
    for s, codec in enumerate(codecs):

        def hop_body(h, imp_loc):
            idx = jax.lax.axis_index("stage")
            if codec.needs_importance:
                payload = codec.encode(h, imp_loc)
            else:
                payload = codec.encode(h)
            moved = jax.tree_util.tree_map(
                lambda a: jax.lax.ppermute(a, "stage", [(s, s + 1)]), payload)
            decoded = codec.decode(moved)
            return jax.lax.psum(
                jnp.where(idx == s + 1, decoded, jnp.zeros_like(decoded)), "stage")

        fn = jax.jit(shard_map(hop_body, mesh=mesh,
                               in_specs=(hidden_spec, imp_spec),
                               out_specs=hidden_spec, check_vma=False))
        sec, _ = timed(fn, hidden, imp, warmup=warmup, iters=iters)
        results.append(sec * 1000.0)
    return results


@dataclasses.dataclass(frozen=True)
class SplitConfig:
    """Where the model is cut and what crosses each cut.

    cuts: boundary layers — the activation is transferred *after* layer ``cuts[i]``
        (the reference's ``layer_of_interest`` / ``quant_layer``).
    hop_codecs: one entry per cut — either a registry name
        (``edgellm_tpu.codecs.packing.WIRE_CODECS``) or a ``WireCodec`` instance
        for parameterized codecs like ``selective_int4(ratio, high)``.
    """

    cuts: tuple
    hop_codecs: tuple

    def __post_init__(self):
        if len(self.hop_codecs) != len(self.cuts):
            raise ValueError("need exactly one hop codec per cut")
        if list(self.cuts) != sorted(set(self.cuts)):
            raise ValueError("cuts must be strictly increasing")

    @property
    def n_stages(self) -> int:
        return len(self.cuts) + 1

    def stage_bounds(self, num_layers: int) -> list:
        """[(start, stop)] per stage; stage i owns layers [start, stop)."""
        edges = [0] + [c + 1 for c in self.cuts] + [num_layers]
        if not all(0 <= c < num_layers - 1 for c in self.cuts):
            raise ValueError(f"cuts {self.cuts} out of range for {num_layers} layers")
        return list(zip(edges[:-1], edges[1:]))

    def roundtrip_boundary_fn(self):
        """The split's single-device oracle, as a ``boundary_fn(layer_idx,
        hidden)`` for ``forward`` / ``prefill`` / ``decode_step``: each hop
        codec's encode -> decode round trip (the jnp codec) applied after its
        cut layer — mathematically what the sharded runtime computes for
        codecs without an importance sidecar, so a wrong shard order, a
        missed hop or a bad collective shows up as a wrong-but-finite value."""
        wire = [c if isinstance(c, WireCodec) else get_wire_codec(c)
                for c in self.hop_codecs]

        def boundary_fn(idx, h):
            for cut, codec in zip(self.cuts, wire):
                h = jnp.where(idx == cut,
                              codec.decode(codec.encode(h)).astype(h.dtype), h)
            return h

        return boundary_fn

    def replan(self, num_layers: int, n_stages: int,
               codec=None) -> "SplitConfig":
        """Recompute the split for a different stage count — the runtime
        re-planning failover needs when a stage dies (MCAP-style: the split
        point is a runtime decision, not a construction-time constant).

        Cuts are evenly spaced over ``num_layers``; every new cut carries
        ``codec`` (default: this plan's first hop codec — there is no
        per-cut tuning signal left once the original cut set is gone).
        ``n_stages == 1`` degenerates to the cut-free single-stage plan."""
        if not 1 <= n_stages <= num_layers:
            raise ValueError(
                f"cannot re-plan {num_layers} layers onto {n_stages} stage(s)")
        if n_stages == 1:
            return SplitConfig(cuts=(), hop_codecs=())
        if codec is None:
            if not self.hop_codecs:
                raise ValueError("re-planning a cut-free split needs an "
                                 "explicit codec")
            codec = self.hop_codecs[0]
        cuts = tuple(round(i * num_layers / n_stages) - 1
                     for i in range(1, n_stages))
        return SplitConfig(cuts=cuts, hop_codecs=(codec,) * len(cuts))


class SplitRuntime:
    """Executes a pipeline-split forward for one (cfg, split, mesh) combination.

    Usage::

        mesh = make_stage_mesh(2)
        rt = SplitRuntime(cfg, SplitConfig(cuts=(3,), hop_codecs=("int4_global",)), mesh)
        placed = rt.place_params(params)
        logits = rt.forward(placed, ids)          # boundary crossed via ppermute
        rt.hop_bytes(batch, seq)                  # measured payload bytes per hop
    """

    def __init__(self, cfg: ModelConfig, split: SplitConfig, mesh: Mesh,
                 faults: Optional[FaultConfig] = None,
                 policy: Optional[LinkPolicy] = None,
                 fec: Optional[Any] = None,
                 hedge: Optional[Any] = None,
                 pipeline: Optional[PipelineConfig] = None):
        from ..models.hybrid import refuse_beyond_kv_rows

        refuse_beyond_kv_rows(cfg, "the split runtime (SplitRuntime)")
        self.cfg = cfg
        self.split = split
        self.mesh = mesh
        self.faults = faults
        self.policy = policy if policy is not None else LinkPolicy()
        self.fec = fec
        self.hedge = hedge
        self.pipeline = pipeline
        # an all-zero-rate config builds the exact fault-free graph: the link
        # machinery only exists in the jaxpr when a fault can actually fire
        # (and a disabled FEC/hedge config traces the exact PR 2 hop)
        self._link = (FaultyLink(faults, self.policy, fec=fec, hedge=hedge)
                      if faults is not None and faults.enabled else None)
        self._counter_accum: list = []
        self._mb_counter_accum: list = []  # pipelined: {key: (M, n_hops)}
        self._lost_stage: Optional[int] = None
        self.bounds = split.stage_bounds(cfg.num_layers)
        self.stage_size = max(stop - start for start, stop in self.bounds)
        self.codecs: list[WireCodec] = apply_default_codec_backend(
            list(split.hop_codecs))
        n_model = mesh.shape["model"]
        if n_model > 1:
            bad = [(name, dim) for name, dim in
                   [("num_heads", cfg.num_heads), ("num_kv_heads", cfg.num_kv_heads),
                    ("intermediate_size", cfg.intermediate_size)] if dim % n_model]
            if bad:
                raise ValueError(
                    f"tensor parallelism n_model={n_model} requires head/FFN dims "
                    f"divisible by the axis; offending: {bad}")
        n_stages = split.n_stages
        if mesh.shape["stage"] != n_stages:
            raise ValueError(
                f"mesh has {mesh.shape['stage']} stage slots, split needs {n_stages}")
        if mesh.shape["data"] > 1:
            # token-selective codecs are exempt: ``forward`` forces per-row
            # (B, S) importance for batched windows, making their ordering and
            # scale row-local — identical on any batch sharding
            bad = [c.name for c in self.codecs
                   if not c.batch_invariant and not c.needs_importance]
            if bad:
                raise ValueError(
                    f"codecs {bad} compute scales over the batch axis and would "
                    f"diverge from a single-device run under data parallelism "
                    f"(n_data={mesh.shape['data']}); use per-token codecs or n_data=1")
        if pipeline is not None and pipeline.enabled:
            if n_stages < 2:
                raise ValueError(
                    "micro-batch pipelining needs a cut to hide hops behind; "
                    f"got n_stages={n_stages} with num_microbatches="
                    f"{pipeline.num_microbatches}")
            if mesh.shape["data"] > 1 or mesh.shape["model"] > 1:
                raise ValueError(
                    "micro-batch pipelining supports stage-only meshes "
                    "(n_data=n_model=1): the µ-batch split owns the batch "
                    f"axis; got data={mesh.shape['data']}, "
                    f"model={mesh.shape['model']}")
            # same row-locality argument as the data-parallel check above:
            # a batch-wide codec scale changes when the batch is split into
            # µ-batches, which would break token parity with the sequential
            # schedule (token-selective codecs again ship per-row importance
            # under any batch > 1, making their payloads row-local)
            bad = [c.name for c in self.codecs
                   if not c.batch_invariant and not c.needs_importance]
            if bad:
                raise ValueError(
                    f"codecs {bad} compute scales over the batch axis; their "
                    f"payloads change when the batch splits into "
                    f"{pipeline.num_microbatches} µ-batches, breaking the "
                    f"token-identity guarantee — use per-token codecs or "
                    f"num_microbatches=1")
        self._forward = self._build_forward()
        self._decode_fns_cache: dict = {}  # capacity -> (prefill_fn, step_fn)
        self._paged_fns_cache: dict = {}   # pool geometry -> step_fn
        self._verify_fns_cache: dict = {}  # (capacity, k) -> verify_fn

    # ---------- stage liveness ----------

    def mark_stage_lost(self, stage: int) -> None:
        """Record a dark stage (failure injection, or a caller's own device
        health signal): every subsequent forward/prefill/step raises the
        typed :class:`StageLostError` until the caller fails over — re-plans
        the split onto the survivors (``SplitConfig.replan``) and rebuilds
        the runtime. Host-side state only: the compiled executables are
        untouched, so a runtime that never loses a stage runs the exact
        pre-recovery graph."""
        if not 0 <= stage < self.split.n_stages:
            raise ValueError(f"stage {stage} out of range for "
                             f"{self.split.n_stages} stages")
        self._lost_stage = stage

    @property
    def lost_stage(self) -> Optional[int]:
        return self._lost_stage

    def _check_alive(self) -> None:
        if self._lost_stage is not None:
            raise StageLostError(self._lost_stage)

    # ---------- parameter placement ----------

    # Megatron-style column/row pairing for the "model" axis: the first matmul
    # of each pair is column-split (head-contiguous for q/k/v, F-contiguous for
    # the MLP up/gate), the second is row-split, and the row-split partial
    # product is psum-reduced inside the block (transformer.attention/mlp).
    _TP_COL_SPLIT = frozenset(
        {"wq", "wk", "wv", "bq", "bk", "bv", "w_gate", "w_up", "w_in", "b_in"})
    _TP_ROW_SPLIT = frozenset({"wo", "w_down", "w_out"})

    def _layer_pspec(self, key: str, ndim: int) -> P:
        """PartitionSpec for one stacked layer-group array (n_stages, sz, ...)."""
        if self.mesh.shape["model"] > 1:
            if key in self._TP_COL_SPLIT:  # split the last (output-feature) axis
                return P(*(("stage",) + (None,) * (ndim - 2) + ("model",)))
            if key in self._TP_ROW_SPLIT:  # split the input-feature axis
                return P("stage", None, "model")
        return P("stage")

    def place_params(self, params: dict) -> dict:
        """Shard the parameter pytree over the mesh: layer groups along "stage",
        attention/MLP weights additionally column/row-split along "model"
        (real tensor parallelism — each model-axis device holds 1/n of the
        heads and FFN columns and computes its slice; see ``_layer_pspec``),
        everything else replicated. Hidden activations ride the "data" axis on
        the batch dimension."""
        groups, valid = regroup_layers(params["layers"], self.bounds, self.stage_size)
        stage_spec = NamedSharding(self.mesh, P("stage"))
        repl = NamedSharding(self.mesh, P())
        placed = {
            "layers": {
                k: jax.device_put(v, NamedSharding(self.mesh, self._layer_pspec(k, v.ndim)))
                for k, v in groups.items()},
            "layers_valid": jax.device_put(valid, stage_spec),
        }
        for k, v in params.items():
            if k != "layers":
                placed[k] = jax.device_put(v, repl)
        return placed

    # ---------- forward ----------

    def _build_forward(self):
        cfg, n_stages, sz = self.cfg, self.split.n_stages, self.stage_size
        codecs = self.codecs
        mesh = self.mesh
        link = self._link
        # resolved once at build time: the disabled / M == 1 build traces
        # the ORIGINAL schedule functions (the pipeline-disabled-identity
        # lint pins hold it byte-identical)
        n_micro = (self.pipeline.num_microbatches if self.pipelined else 1)

        tp_axis = "model" if mesh.shape["model"] > 1 else None

        def stage_body(local_layers, local_valid, hidden, cos, sin, hop_imps,
                       fault_step=None):
            """Runs inside shard_map: one device = one pipeline stage (and one
            tensor-parallel shard of it when the "model" axis is populated)."""
            lv = {k: v[0] for k, v in local_layers.items()}  # (sz, ...)
            valid = local_valid[0]  # (sz,)
            # the carry becomes stage-varying after the first scan step; promote
            # the replicated input so the vma types line up
            hidden = jax.lax.pcast(hidden, ("stage",), to="varying")

            def scan_body(h, xs):
                lp, ok = xs
                out, _ = block(cfg, lp, h, cos, sin, capture_stats=False,
                               tp_axis=tp_axis)
                return jnp.where(ok, out, h), None

            def run_stage(h):
                computed, _ = jax.lax.scan(scan_body, h, (lv, valid))
                return computed

            if link is None:
                if n_micro > 1:
                    return run_pipeline_stages_microbatched(
                        n_stages, codecs, n_micro, run_stage, hidden, hop_imps)
                return run_pipeline_stages(n_stages, codecs, run_stage, hidden,
                                           hop_imps)
            # one fold per forward call keeps chunks decorrelated while two
            # same-seed runs replay the identical fault sequence
            key = jax.random.fold_in(jax.random.key(link.faults.seed),
                                     fault_step)
            if n_micro > 1:
                return run_pipeline_stages_microbatched(
                    n_stages, codecs, n_micro, run_stage, hidden, hop_imps,
                    link=link, fault_key=key)
            return run_pipeline_stages(n_stages, codecs, run_stage, hidden,
                                       hop_imps, link=link, fault_key=key)

        # batch axis rides the "data" mesh axis (data parallelism over evaluation
        # windows); each data-parallel group runs the full pipeline over "stage"
        batch_spec = P("data") if mesh.shape["data"] > 1 else P()

        layer_pspec = self._layer_pspec

        @jax.jit
        def fn(placed, input_ids, hop_imps, fault_step=None):
            hidden = embed(placed, input_ids)
            cos, sin = precompute_rope(cfg, input_ids.shape[1])
            lspecs = {k: layer_pspec(k, v.ndim) for k, v in placed["layers"].items()}
            # per-row (H, B, S) importance rides the "data" axis with the batch;
            # shared (H, S) importance is replicated (ndim is static under jit)
            imp_spec = (P(None, "data") if hop_imps.ndim == 3
                        and mesh.shape["data"] > 1 else P())
            if link is None:
                out = shard_map(
                    stage_body,
                    mesh=mesh,
                    in_specs=(lspecs, P("stage"), batch_spec, P(), P(), imp_spec),
                    out_specs=batch_spec,
                    # vma tracking cannot type pallas_call outputs inside the body
                    # (hop codecs may be Pallas kernels); replication is enforced
                    # structurally by the final psum instead
                    check_vma=False,
                )(placed["layers"], placed["layers_valid"], hidden, cos, sin,
                  hop_imps)
                return unembed(cfg, placed, out)
            out, counters = shard_map(
                stage_body,
                mesh=mesh,
                in_specs=(lspecs, P("stage"), batch_spec, P(), P(), imp_spec,
                          P()),
                out_specs=(batch_spec, P()),
                check_vma=False,
            )(placed["layers"], placed["layers_valid"], hidden, cos, sin,
              hop_imps, fault_step)
            return unembed(cfg, placed, out), counters

        return fn

    @graph_contract(
        "split.forward",
        # one ppermute per payload leaf per cut, one structural psum; the
        # driver supplies the measured counts/bytes from the codec registry
        collectives=lambda ctx: {"ppermute": ctx["hop_eqns"], "psum": 1},
        wire_dtypes=lambda ctx: ctx["wire_dtypes"],
        wire_bytes=lambda ctx: ctx["wire_bytes"])
    @graph_contract(
        "split.forward.pipelined",
        # µ-batch schedule: every cut moves M payloads of (B/M, S, D) —
        # hop_eqns scales by M, wire bytes are M x the µ-batch payload, and
        # the M emitted blocks still replicate through ONE stacked psum
        collectives=lambda ctx: {"ppermute": ctx["hop_eqns"], "psum": 1},
        wire_dtypes=lambda ctx: ctx["wire_dtypes"],
        wire_bytes=lambda ctx: ctx["wire_bytes"])
    def forward(self, placed_params: dict, input_ids: jnp.ndarray,
                hop_importance: Optional[Sequence] = None,
                fault_step: int = 0) -> jnp.ndarray:
        """ids -> fp32 logits, with every cut crossed as a packed ppermute.

        ``hop_importance``: per-hop token-importance entries, required when any
        hop codec is token-selective (``needs_importance``); hops that don't
        use importance may pass None entries. Each entry is (S,), or — when
        batching evaluation windows — (B, S) so every window keeps its OWN
        ordering and codec scale (the reference selects per window at batch 1,
        ``Qwen2-0.5B/main.py:161-165``; with the "data" mesh axis populated the
        rows ride it alongside the hidden batch).

        ``fault_step``: the fault layer's per-call PRNG fold (pass the chunk
        index so each chunk draws distinct faults; a traced scalar, so it
        never retraces). Ignored when faults are off. Per-hop fault counters
        accumulate on the runtime — read them with :meth:`link_counters`."""
        self._check_alive()
        n_hops = len(self.codecs)
        batch, seq = input_ids.shape
        if self.pipelined:
            self.pipeline.validate_batch(batch, "forward batch")
        imps = list(hop_importance) if hop_importance is not None else [None] * n_hops
        if len(imps) != n_hops:
            raise ValueError(f"expected {n_hops} hop_importance entries, got {len(imps)}")
        for c, imp in zip(self.codecs, imps):
            if c.needs_importance and imp is None:
                raise ValueError(f"hop codec {c.name} requires an importance vector")
            if c.needs_importance and batch > 1 and (
                    jnp.ndim(imp) != 2 or jnp.shape(imp)[0] != batch):
                # one (S,) vector (or a single broadcast row) cannot speak for
                # several evaluation windows: each window has its own token
                # ordering in the reference
                raise ValueError(
                    f"hop codec {c.name} with batch {batch} needs per-row "
                    f"({batch}, S) importance (got shape {jnp.shape(imp)})")
        per_row = any(i is not None and jnp.ndim(i) == 2 for i in imps) or (
            batch > 1 and any(c.needs_importance for c in self.codecs))
        blank = jnp.zeros((batch, seq) if per_row else (seq,), jnp.float32)
        stacked = (jnp.zeros((0,) + blank.shape, jnp.float32) if not imps else
                   jnp.stack([blank if i is None
                              else jnp.broadcast_to(jnp.asarray(i, jnp.float32),
                                                    blank.shape)
                              for i in imps]))
        if self._link is None:
            return self._forward(placed_params, input_ids, stacked)
        logits, counters = self._forward(placed_params, input_ids, stacked,
                                         jnp.asarray(fault_step, jnp.int32))
        self._accum_counters(counters)
        return logits

    @property
    def pipelined(self) -> bool:
        """True when the µ-batch schedule is armed (num_microbatches > 1).
        False — including for ``PipelineConfig(num_microbatches=1)`` — means
        every entry point dispatches to the original sequential unroll,
        byte-identical to a pre-pipeline build (lint-pinned)."""
        return self.pipeline is not None and self.pipeline.enabled

    def pipeline_summary(self) -> dict:
        """Schedule accounting for the obs gauges and bench artifacts: µ-batch
        count, unroll length, per-stage occupancy, analytic bubble fraction.
        Meaningful (occupancy 1/n per stage) even when pipelining is off."""
        pipe = self.pipeline if self.pipeline is not None else PipelineConfig()
        return pipe.summary(self.split.n_stages)

    def _accum_counters(self, counters) -> None:
        """Park one call's replicated counter pytree. Pipelined steps return
        {key: (M, n_hops)} — the per-µ-batch rows accumulate separately
        (:meth:`microbatch_counters`) and the hop totals fold into the same
        (n_hops,) stream :meth:`link_counters` has always reported."""
        first = next(iter(counters.values()))
        if getattr(first, "ndim", 1) == 2:
            self._mb_counter_accum.append(counters)
            counters = {k: v.sum(axis=0) for k, v in counters.items()}
        self._counter_accum.append(counters)

    def link_counters(self, reset: bool = False) -> Optional[dict]:
        """Per-hop fault counters accumulated over every forward/prefill/step
        call so far: {name: (n_hops,) int64}. None when faults are off.
        Reading forces a sync of the pending counter arrays — call it at
        reporting boundaries, not per chunk."""
        if self._link is None:
            return None
        tot = sum_counters(self._counter_accum)
        if tot is None:
            n_hops = len(self.codecs)
            tot = {k: np.zeros((n_hops,), np.int64)
                   for k in self._link.init_counters(n_hops)}
        if reset:
            self._counter_accum = []
        return tot

    def microbatch_counters(self, reset: bool = False) -> Optional[dict]:
        """Per-µ-batch fault counters from pipelined steps: {name: (M,
        n_hops) int64} — row m is the faults µ-batch m's payloads drew on
        each cut (each µ-batch folds its own fault key, so the rows are
        decorrelated). None when faults are off or pipelining is disabled.
        Sequential calls on the same runtime (prefill, verify) are not
        µ-batched and only appear in :meth:`link_counters`."""
        if self._link is None or not self.pipelined:
            return None
        tot = sum_counters(self._mb_counter_accum)
        if tot is None:
            m, n_hops = self.pipeline.num_microbatches, len(self.codecs)
            tot = {k: np.zeros((m, n_hops), np.int64)
                   for k in self._link.init_counters(n_hops)}
        if reset:
            self._mb_counter_accum = []
        return tot

    def hop_attribution(self, delta: Optional[dict],
                        per_hop_bytes: Optional[list] = None, *,
                        link_tier: Optional[int] = None) -> list:
        """Host-side per-cut attribution rows for the tracing plane: one row
        per boundary hop carrying {hop, cut layer, codec tier, wire bytes,
        ladder outcome} — what a request-scoped hop span records.

        ``delta`` is one call's :meth:`link_counters` delta (None when the
        link machinery is off); ``per_hop_bytes`` the call's per-hop wire
        bytes (already multiplied by its step/burst count); ``link_tier``
        the LinkHealth degradation tier if the caller tracks one. The
        outcome collapses the resilience ladder to the *worst* thing that
        happened on the hop, in severity order: substituted > hedged >
        retried > repaired > degraded (tier > 0) > clean. Pure host
        arithmetic on already-synced numpy counters — nothing here touches
        a traced value.
        """
        def counted(key: str, i: int) -> int:
            if not delta or key not in delta:
                return 0
            v = delta[key]
            try:
                return int(v[i])
            except (TypeError, IndexError):
                return int(v)

        rows = []
        for i, codec in enumerate(self.codecs):
            if counted("substituted", i):
                outcome = "substituted"
            elif counted("hedge_wins", i):
                outcome = "hedged"
            elif counted("retried", i):
                outcome = "retried"
            elif counted("repaired", i):
                outcome = "repaired"
            elif link_tier:
                outcome = "degraded"
            else:
                outcome = "clean"
            wire = 0.0
            if per_hop_bytes is not None and i < len(per_hop_bytes):
                wire = float(per_hop_bytes[i])
            rows.append({"hop": i, "cut": int(self.split.cuts[i]),
                         "codec": codec.name, "wire_bytes": wire,
                         "outcome": outcome})
        return rows

    # ---------- incremental decode ----------
    #
    # The regime where the paper's boundary-quantization question bites
    # hardest: at decode time each cut moves ONE token's hidden state per
    # step, so codec overhead dominates the hop. The per-stage KV caches
    # never cross a cut — each stage keeps its own layers' cache sharded on
    # "stage"; only the (B, 1, D) activation is encoded/ppermuted/decoded.

    def _check_decode_supported(self):
        if self.mesh.shape["data"] > 1 or self.mesh.shape["model"] > 1:
            raise ValueError(
                "split decode supports stage-only meshes (n_data=n_model=1); "
                f"got data={self.mesh.shape['data']}, model={self.mesh.shape['model']}")
        bad = [c.name for c in self.codecs if c.needs_importance]
        if bad:
            raise ValueError(
                f"token-selective hop codecs {bad} have no importance source "
                f"for a single decode position; use per-token/channel codecs")

    def _decode_fns(self, capacity: int):
        """Build (or fetch) the jitted prefill/step executables for one cache
        capacity. Capacity is static (it fixes the cache buffers); the fill
        level rides as a traced scalar, so each capacity compiles exactly one
        step executable no matter how many tokens are emitted."""
        if capacity in self._decode_fns_cache:
            return self._decode_fns_cache[capacity]
        cfg, n_stages, sz = self.cfg, self.split.n_stages, self.stage_size
        codecs, mesh = self.codecs, self.mesh
        layer_pspec = self._layer_pspec
        link = self._link
        n_micro = (self.pipeline.num_microbatches if self.pipelined else 1)

        def _hop_protocol(run_stage, hidden, carry, fault_key):
            """Dispatch the carry protocol with or without the faulty link —
            the link-free branch is byte-for-byte the original call."""
            if link is None:
                out, c = run_pipeline_stages_carry(
                    n_stages, codecs, run_stage, hidden, carry)
                return out, c, None
            return run_pipeline_stages_carry(
                n_stages, codecs, run_stage, hidden, carry,
                link=link, fault_key=fault_key)

        def _hop_protocol_pipelined(run_stage, hidden, carry, fault_key):
            """The µ-batch schedule's twin of ``_hop_protocol`` —
            ``run_stage`` takes the pipelined (h_mu, carry, b, valid)
            contract. Only decode steps route here; prefill fills the whole
            cache in one sequential pass either way."""
            if link is None:
                out, c = run_pipeline_stages_carry_microbatched(
                    n_stages, codecs, n_micro, run_stage, hidden, carry)
                return out, c, None
            return run_pipeline_stages_carry_microbatched(
                n_stages, codecs, n_micro, run_stage, hidden, carry,
                link=link, fault_key=fault_key)

        def stage_prefill(local_layers, local_valid, hidden, cos, sin,
                          fault_step=None):
            lv = {k: v[0] for k, v in local_layers.items()}  # (sz, ...)
            valid = local_valid[0]
            s = hidden.shape[1]
            hidden = jax.lax.pcast(hidden, ("stage",), to="varying")
            zeros = jnp.zeros((sz,) + hidden.shape[:1] + (capacity,)
                              + (cfg.num_kv_heads, cfg.head_dim), hidden.dtype)

            def scan_body(h, xs):
                lp, ok = xs
                out, _, (kl, vl) = block(cfg, lp, h, cos, sin,
                                         capture_stats=False, return_kv=True)
                return jnp.where(ok, out, h), (kl, vl)

            def run_stage(h, cache, keep):
                computed, (ks, vs) = jax.lax.scan(scan_body, h, (lv, valid))
                kc, vc = cache  # (sz, B, capacity, KV, hd)
                return computed, keep_carry(
                    keep, (kc.at[:, :, :s].set(ks), vc.at[:, :, :s].set(vs)),
                    cache)

            fkey = None if link is None else jax.random.fold_in(
                jax.random.fold_in(jax.random.key(link.faults.seed), 0x9EF1),
                fault_step)
            out, (kc, vc), counters = _hop_protocol(
                run_stage, hidden, (zeros, zeros), fkey)
            if link is None:
                return out, kc[None], vc[None]
            return out, kc[None], vc[None], counters

        def stage_step(local_layers, local_valid, hidden, k_loc, v_loc,
                       cos_t, sin_t, pos):
            lv = {k: v[0] for k, v in local_layers.items()}
            valid = local_valid[0]
            hidden = jax.lax.pcast(hidden, ("stage",), to="varying")

            def scan_body(h, xs):
                lp, ok, kl, vl = xs
                out, kl2, vl2 = block_decode(cfg, lp, h, cos_t, sin_t,
                                             kl, vl, pos)
                # padding layers are identity AND must not touch their cache
                return jnp.where(ok, out, h), (jnp.where(ok, kl2, kl),
                                               jnp.where(ok, vl2, vl))

            def run_stage(h, cache, keep):
                # a contiguous cache has no trash page: a dead iteration's
                # update is selected away, whole
                h2, written = jax.lax.scan(scan_body, h, (lv, valid, *cache))
                return h2, keep_carry(keep, written, cache)

            # the cache fill level is the fault step: distinct per emitted
            # token, identical across same-seed runs, no extra traced arg
            fkey = None if link is None else jax.random.fold_in(
                jax.random.fold_in(jax.random.key(link.faults.seed), 0x57E9),
                pos)
            if n_micro == 1:
                out, (kc, vc), counters = _hop_protocol(
                    run_stage, hidden, (k_loc[0], v_loc[0]), fkey)
            else:
                mb_rows = hidden.shape[0] // n_micro

                def run_stage_mu(h_mu, cache, b, ok):
                    # each device sits at µ-batch b of the schedule: advance
                    # ONLY that µ-batch's cache rows, and write nothing on
                    # the fill/drain steps where ok is False
                    kc, vc = cache  # (sz, B, capacity, KV, hd)
                    start = b * mb_rows
                    kc_mu = jax.lax.dynamic_slice_in_dim(kc, start, mb_rows,
                                                         axis=1)
                    vc_mu = jax.lax.dynamic_slice_in_dim(vc, start, mb_rows,
                                                         axis=1)
                    h2, (kc2, vc2) = jax.lax.scan(scan_body, h_mu,
                                                  (lv, valid, kc_mu, vc_mu))
                    kc = jnp.where(ok, jax.lax.dynamic_update_slice_in_dim(
                        kc, kc2, start, axis=1), kc)
                    vc = jnp.where(ok, jax.lax.dynamic_update_slice_in_dim(
                        vc, vc2, start, axis=1), vc)
                    return h2, (kc, vc)

                out, (kc, vc), counters = _hop_protocol_pipelined(
                    run_stage_mu, hidden, (k_loc[0], v_loc[0]), fkey)
            if link is None:
                return out, kc[None], vc[None]
            return out, kc[None], vc[None], counters

        @jax.jit
        def prefill_fn(placed, input_ids, fault_step=None):
            hidden = embed(placed, input_ids)
            cos, sin = precompute_rope(cfg, input_ids.shape[1])
            lspecs = {k: layer_pspec(k, v.ndim)
                      for k, v in placed["layers"].items()}
            if link is None:
                out, kc, vc = shard_map(
                    stage_prefill, mesh=mesh,
                    in_specs=(lspecs, P("stage"), P(), P(), P()),
                    out_specs=(P(), P("stage"), P("stage")),
                    check_vma=False,
                )(placed["layers"], placed["layers_valid"], hidden, cos, sin)
                return unembed(cfg, placed, out), kc, vc
            out, kc, vc, counters = shard_map(
                stage_prefill, mesh=mesh,
                in_specs=(lspecs, P("stage"), P(), P(), P(), P()),
                out_specs=(P(), P("stage"), P("stage"), P()),
                check_vma=False,
            )(placed["layers"], placed["layers_valid"], hidden, cos, sin,
              fault_step)
            return unembed(cfg, placed, out), kc, vc, counters

        # per-stage KV buffers are donated: each emitted token updates the
        # (n_stages, sz, B, capacity) caches in place instead of copying them
        # (the "split.decode_step" contract asserts the aliasing survives)
        @functools.partial(jax.jit, donate_argnums=(1, 2))
        def step_fn(placed, k_cache, v_cache, length, token_ids):
            hidden = embed(placed, token_ids[:, None])  # (B, 1, D)
            cos, sin = precompute_rope(cfg, capacity)
            cos_t = jax.lax.dynamic_slice_in_dim(cos, length, 1)
            sin_t = jax.lax.dynamic_slice_in_dim(sin, length, 1)
            lspecs = {k: layer_pspec(k, v.ndim)
                      for k, v in placed["layers"].items()}
            if link is None:
                out, kc, vc = shard_map(
                    stage_step, mesh=mesh,
                    in_specs=(lspecs, P("stage"), P(), P("stage"), P("stage"),
                              P(), P(), P()),
                    out_specs=(P(), P("stage"), P("stage")),
                    check_vma=False,
                )(placed["layers"], placed["layers_valid"], hidden,
                  k_cache, v_cache, cos_t, sin_t, length)
                return unembed(cfg, placed, out)[:, -1], kc, vc
            out, kc, vc, counters = shard_map(
                stage_step, mesh=mesh,
                in_specs=(lspecs, P("stage"), P(), P("stage"), P("stage"),
                          P(), P(), P()),
                out_specs=(P(), P("stage"), P("stage"), P()),
                check_vma=False,
            )(placed["layers"], placed["layers_valid"], hidden,
              k_cache, v_cache, cos_t, sin_t, length)
            return unembed(cfg, placed, out)[:, -1], kc, vc, counters

        self._decode_fns_cache[capacity] = (prefill_fn, step_fn)
        return self._decode_fns_cache[capacity]

    def prefill_decode(self, placed_params: dict, input_ids: jnp.ndarray,
                       capacity: int, fault_step: int = 0) -> tuple:
        """Pipeline-split prefill that also fills the per-stage KV caches.
        Returns (logits (B, S, V) fp32, cache dict) — feed the cache to
        :meth:`decode_step`. Cache k/v: (n_stages, sz, B, capacity, KV, hd),
        sharded P("stage") like the layer groups they mirror."""
        self._check_alive()
        self._check_decode_supported()
        s = input_ids.shape[1]
        if not 0 < s <= capacity:
            raise ValueError(
                f"prompt length {s} must be in [1, capacity={capacity}]")
        prefill_fn, _ = self._decode_fns(int(capacity))
        if self._link is None:
            logits, kc, vc = prefill_fn(placed_params, input_ids)
        else:
            logits, kc, vc, counters = prefill_fn(
                placed_params, input_ids, jnp.asarray(fault_step, jnp.int32))
            self._accum_counters(counters)
        return logits, {"k": kc, "v": vc, "length": jnp.asarray(s, jnp.int32)}

    @graph_contract(
        "split.decode_step",
        collectives=lambda ctx: {"ppermute": ctx["hop_eqns"], "psum": 1},
        wire_dtypes=lambda ctx: ctx["wire_dtypes"],
        wire_bytes=lambda ctx: ctx["wire_bytes"],
        donate=lambda ctx: ctx.get("donate_min", 2))
    @graph_contract(
        "split.decode_step.pipelined",
        # µ-batch twin of split.decode_step: M payloads of (B/M, 1, D) per
        # cut per step (pipelined_decode_hop_bytes), ONE stacked psum, and
        # the KV donation discipline intact under the schedule
        collectives=lambda ctx: {"ppermute": ctx["hop_eqns"], "psum": 1},
        wire_dtypes=lambda ctx: ctx["wire_dtypes"],
        wire_bytes=lambda ctx: ctx["wire_bytes"],
        donate=lambda ctx: ctx.get("donate_min", 2))
    def decode_step(self, placed_params: dict, cache: dict,
                    token_ids: jnp.ndarray) -> tuple:
        """One decode position across the pipeline: each cut quantizes the
        single-token hidden state through its wire codec (under faults, via
        the sealed/verified link, keyed by the cache fill level). Returns
        (logits (B, V) fp32, updated cache)."""
        self._check_alive()
        if self.pipelined:
            self.pipeline.validate_batch(int(cache["k"].shape[2]),
                                         "decode batch")
        capacity = cache["k"].shape[3]
        _, step_fn = self._decode_fns(int(capacity))
        if self._link is None:
            logits, kc, vc = step_fn(placed_params, cache["k"], cache["v"],
                                     cache["length"], token_ids)
        else:
            logits, kc, vc, counters = step_fn(
                placed_params, cache["k"], cache["v"], cache["length"],
                token_ids)
            self._accum_counters(counters)
        return logits, {"k": kc, "v": vc, "length": cache["length"] + 1}

    def decode_hop_bytes(self, batch: int) -> list:
        """Measured payload bytes per hop for ONE decode step's (batch, 1, D)
        boundary activation — bytes/token is this divided by ``batch``."""
        return hop_payload_bytes(self.codecs, self.cfg, batch, 1)

    def pipelined_decode_hop_bytes(self, batch: int) -> list:
        """:meth:`decode_hop_bytes` under the µ-batch schedule: each cut
        moves M payloads of (batch/M, 1, D) per step instead of one
        (batch, 1, D) payload (identical totals for row-local codecs, but
        per-µ-batch sidecars — scales, seals — replicate M-fold). Falls back
        to the sequential accounting when pipelining is off or ``batch``
        doesn't µ-batch."""
        if (not self.pipelined
                or batch % self.pipeline.num_microbatches or batch < 1):
            return self.decode_hop_bytes(batch)
        m = self.pipeline.num_microbatches
        return [m * b for b in
                hop_payload_bytes(self.codecs, self.cfg, batch // m, 1)]

    # ---------- speculative verify ----------
    #
    # The k-token twin of the decode step: serve/speculative drafts k tokens
    # on stage 0 and this verifies them all in ONE split pass — each cut
    # moves one quantized (B, k, D) activation block instead of k single-
    # token hops, amortizing the boundary round-trip (and the whole
    # faulty/FEC/hedge hop ladder, which is shape-generic and flows
    # unchanged) k-fold per accepted run.

    def _verify_fns(self, capacity: int, k: int):
        """Build (or fetch) the jitted q_len=k verify executable for one
        (capacity, k) pair. Both are static (cache buffer shape / verify
        window); the fill level rides as a traced scalar, so every verify
        burst of a run reuses one executable — the spec loop is jit-miss-free
        after the first burst. Always the sequential schedule: speculation
        is per-stream (B == 1), so there is no batch to µ-batch — a
        pipelined runtime's verify bursts trace the unchanged pre-pipeline
        graph."""
        key = (capacity, k)
        if key in self._verify_fns_cache:
            return self._verify_fns_cache[key]
        cfg, n_stages, sz = self.cfg, self.split.n_stages, self.stage_size
        codecs, mesh = self.codecs, self.mesh
        layer_pspec = self._layer_pspec
        link = self._link

        def _hop_protocol(run_stage, hidden, carry, fault_key):
            if link is None:
                out, c = run_pipeline_stages_carry(
                    n_stages, codecs, run_stage, hidden, carry)
                return out, c, None
            return run_pipeline_stages_carry(
                n_stages, codecs, run_stage, hidden, carry,
                link=link, fault_key=fault_key)

        def stage_verify(local_layers, local_valid, hidden, k_loc, v_loc,
                         cos_t, sin_t, pos):
            lv = {k2: v[0] for k2, v in local_layers.items()}
            valid = local_valid[0]
            hidden = jax.lax.pcast(hidden, ("stage",), to="varying")

            def scan_body(h, xs):
                lp, ok, kl, vl = xs
                out, kl2, vl2 = block_verify(cfg, lp, h, cos_t, sin_t,
                                             kl, vl, pos)
                # padding layers are identity AND must not touch their cache
                return jnp.where(ok, out, h), (jnp.where(ok, kl2, kl),
                                               jnp.where(ok, vl2, vl))

            def run_stage(h, cache, keep):
                # a contiguous cache has no trash page: a dead iteration's
                # update is selected away, whole
                h2, written = jax.lax.scan(scan_body, h, (lv, valid, *cache))
                return h2, keep_carry(keep, written, cache)

            # the cache fill level keys the fault step, exactly like the
            # single-token step: distinct per burst, identical across
            # same-seed runs (a resumed run replays the same fill levels)
            fkey = None if link is None else jax.random.fold_in(
                jax.random.fold_in(jax.random.key(link.faults.seed), 0x57E9),
                pos)
            out, (kc, vc), counters = _hop_protocol(
                run_stage, hidden, (k_loc[0], v_loc[0]), fkey)
            if link is None:
                return out, kc[None], vc[None]
            return out, kc[None], vc[None], counters

        # same KV donation discipline as step_fn: each burst updates the
        # (n_stages, sz, B, capacity) caches in place (the
        # "split.verify_step" contract asserts the aliasing survives)
        @functools.partial(jax.jit, donate_argnums=(1, 2))
        def verify_fn(placed, k_cache, v_cache, length, token_ids):
            hidden = embed(placed, token_ids)  # (B, k, D)
            cos, sin = precompute_rope(cfg, capacity)
            cos_t = jax.lax.dynamic_slice_in_dim(cos, length, k)
            sin_t = jax.lax.dynamic_slice_in_dim(sin, length, k)
            lspecs = {k2: layer_pspec(k2, v.ndim)
                      for k2, v in placed["layers"].items()}
            if link is None:
                out, kc, vc = shard_map(
                    stage_verify, mesh=mesh,
                    in_specs=(lspecs, P("stage"), P(), P("stage"), P("stage"),
                              P(), P(), P()),
                    out_specs=(P(), P("stage"), P("stage")),
                    check_vma=False,
                )(placed["layers"], placed["layers_valid"], hidden,
                  k_cache, v_cache, cos_t, sin_t, length)
                return unembed(cfg, placed, out), kc, vc
            out, kc, vc, counters = shard_map(
                stage_verify, mesh=mesh,
                in_specs=(lspecs, P("stage"), P(), P("stage"), P("stage"),
                          P(), P(), P()),
                out_specs=(P(), P("stage"), P("stage"), P()),
                check_vma=False,
            )(placed["layers"], placed["layers_valid"], hidden,
              k_cache, v_cache, cos_t, sin_t, length)
            return unembed(cfg, placed, out), kc, vc, counters

        self._verify_fns_cache[key] = verify_fn
        return verify_fn

    @graph_contract(
        "split.verify_step",
        collectives=lambda ctx: {"ppermute": ctx["hop_eqns"], "psum": 1},
        wire_dtypes=lambda ctx: ctx["wire_dtypes"],
        wire_bytes=lambda ctx: ctx["wire_bytes"],
        donate=lambda ctx: ctx.get("donate_min", 2))
    def verify_step(self, placed_params: dict, cache: dict,
                    token_ids: jnp.ndarray) -> tuple:
        """Verify k drafted positions in one split pass: ``token_ids`` is
        (B, k) — the last committed token followed by the k-1 draft tokens —
        and each cut quantizes ONE (B, k, D) activation block through its
        wire codec. All k K/V rows are written at ``cache["length"]``; the
        returned cache claims all of them (``length + k``) and the caller
        commits the accepted prefix by shrinking ``length`` (garbage past
        the fill level is masked, so rollback is a length rewrite — no data
        movement). Returns (logits (B, k, V) fp32, updated cache)."""
        self._check_alive()
        self._check_decode_supported()
        capacity = cache["k"].shape[3]
        kq = token_ids.shape[1]
        verify_fn = self._verify_fns(int(capacity), int(kq))
        if self._link is None:
            logits, kc, vc = verify_fn(placed_params, cache["k"], cache["v"],
                                       cache["length"], token_ids)
        else:
            logits, kc, vc, counters = verify_fn(
                placed_params, cache["k"], cache["v"], cache["length"],
                token_ids)
            self._accum_counters(counters)
        return logits, {"k": kc, "v": vc, "length": cache["length"] + kq}

    def verify_hop_bytes(self, batch: int, k: int) -> list:
        """Measured payload bytes per hop for ONE verify burst's (batch, k, D)
        boundary activation — the whole burst's wire cost; divide by the
        accepted run length for bytes/token."""
        return hop_payload_bytes(self.codecs, self.cfg, batch, k)

    # ---------- paged incremental decode ----------
    #
    # The continuous-batching twin of the block above: per-stage KV caches
    # page exactly like serve/batching's local pools (fixed-size pages, a
    # traced page table, trash page 0), so streams with different prompt
    # lengths and fill levels share ONE compiled ragged step per pool
    # geometry while every cut still moves its quantized (B, 1, D) boundary
    # activation.  Pool layout: (n_stages, sz, num_pages, page_size, 2 * KV *
    # hd) (paged_kv.PagePool's K-then-V row) sharded P("stage") — each stage
    # owns its own layers' pages, pages never cross a cut.

    def init_paged_pool(self, num_pages: int, page_size: int,
                        dtype=jnp.float32, kv_codec: str = "fp"):
        """Zeroed per-stage paged KV pools, placed sharded on "stage": the
        chip's pool types (models.paged_kv) with leading axes (n_stages,
        stage_size), a PagePool on the fp tier, a QuantPagePool — packed
        codes plus per-row fp32 scales — on a quantized ``kv_codec``; the
        paged methods read the tier off the pool. Page 0 is the trash page —
        host-side page tables must never hand it out."""
        self._check_decode_supported()
        if num_pages < 2:
            raise ValueError("need num_pages >= 2 (page 0 is the trash page)")
        cfg = self.cfg
        codec = resolve_kv_codec(kv_codec)
        zeros = functools.partial(
            jax.jit, static_argnums=(0, 1),
            out_shardings=NamedSharding(self.mesh, P("stage")))(jnp.zeros)
        rows = (self.split.n_stages, self.stage_size, num_pages, page_size)
        kv = cfg.num_kv_heads
        if not codec.quantized:
            return paged_kv.PagePool(
                zeros(rows + (2 * kv * cfg.head_dim,), dtype))
        codes = rows + (kv * codec.code_lanes(cfg.head_dim),)
        return paged_kv.QuantPagePool(
            zeros(codes, codec.code_dtype), zeros(codes, codec.code_dtype),
            zeros(rows + (kv,), jnp.float32), zeros(rows + (kv,), jnp.float32))

    def adopt_paged(self, pool, cache: dict, row: int, dest: np.ndarray,
                    length: int):
        """Move one stream's prefilled contiguous cache (``prefill_decode``
        row ``row``) into pool pages at flat token indices ``dest``
        ((length,) int32, from PagedKVCache._flat_indices). Donates the pool
        buffers — the scatter is stage-elementwise, no collectives, whole
        pages a slice where ``dest`` (a HOST array, in position order) fills
        them. On a quantized pool the fp rows quantize on append."""
        return _adopt_paged_impl(
            pool, cache["k"][:, :, row, :length],  # (n_stages, sz, n, KV, hd)
            cache["v"][:, :, row, :length], jnp.asarray(dest, jnp.int32),
            head=paged_kv.page_head(dest, pool.page_size))

    def adopt_paged_rows(self, pool, k_seq, v_seq, dest: np.ndarray):
        """Scatter an already-contiguous (n_stages, sz, n, KV, hd) K/V prefix
        — a :meth:`gather_paged` payload, possibly round-tripped through a
        checkpoint — into pool pages at flat token indices ``dest``. The
        re-admission half of eviction for the split batcher. Quantized pools
        requantize fp rows here; bit-exact resume uses the packed twin."""
        return _adopt_paged_impl(
            pool, jnp.asarray(k_seq), jnp.asarray(v_seq),
            jnp.asarray(dest, jnp.int32),
            head=paged_kv.page_head(dest, pool.page_size))

    def adopt_paged_rows_packed(self, pool, k_codes, v_codes, k_scale,
                                v_scale, dest: np.ndarray):
        """Scatter a :meth:`gather_paged_packed` payload back — raw codes +
        scales, no requantize, so evict -> readmit is bit-exact."""
        if pool_tier(pool) == "fp":
            raise KVTierMismatchError(
                offered="quantized", pool="fp",
                where="adopt_paged_rows_packed",
                detail="packed payloads need a quantized pool; fp pools "
                       "adopt fp rows via adopt_paged_rows")
        return paged_kv._adopt_packed_impl(
            pool, jnp.asarray(k_codes), jnp.asarray(v_codes),
            jnp.asarray(k_scale), jnp.asarray(v_scale),
            jnp.asarray(dest, jnp.int32), lead=_STAGED,
            head=paged_kv.page_head(dest, pool.page_size))

    def copy_paged_pages(self, pool, src, dst):
        """Apply prefix-cache COW forks to the per-stage pools: duplicate
        pages ``src`` to ``dst`` (parallel 1-D index lists from
        ``PagedKVCache.ensure_writable``'s (old, new) pairs). Donates the
        pool buffers; stage-elementwise, no collectives. Quantized pools
        copy codes AND scales — a fork is a byte move, never a requantize."""
        return paged_kv._copy_pages_impl(pool, jnp.asarray(src, jnp.int32),
                                         jnp.asarray(dst, jnp.int32),
                                         lead=_STAGED)

    def gather_paged(self, pool, idx: np.ndarray) -> tuple:
        """Gather one stream's (n_stages, sz, n, KV, hd) K/V prefix from pool
        pages at flat token indices ``idx`` — byte-identical to the
        contiguous cache rows :meth:`adopt_paged` scattered (the split twin
        of ``PagedKVCache.gather_slot``, for eviction and checkpointing).
        Returns host (k_seq, v_seq) numpy arrays; the pool is NOT consumed.
        Quantized pools come back DEQUANTIZED to fp32 (the suffix-prefill
        compute form); the packed twin preserves the raw bytes."""
        k_seq, v_seq = paged_kv._gather_impl(
            pool, jnp.asarray(idx, jnp.int32), lead=_STAGED,
            kv=self.cfg.num_kv_heads)
        return np.asarray(k_seq), np.asarray(v_seq)

    def gather_paged_packed(self, pool, idx: np.ndarray) -> tuple:
        """Quantized-pool eviction/checkpoint form: host (k_codes, v_codes,
        k_scale, v_scale) numpy arrays at flat token indices ``idx`` — the
        raw pool bytes, so the adopt_paged_rows_packed round-trip is
        bit-exact by construction."""
        if pool_tier(pool) == "fp":
            raise KVTierMismatchError(
                offered="quantized", pool="fp",
                where="gather_paged_packed",
                detail="the packed gather form needs a quantized pool; fp "
                       "pools use gather_paged")
        out = paged_kv._gather_packed_impl(pool, jnp.asarray(idx, jnp.int32),
                                           lead=_STAGED)
        return tuple(np.asarray(a) for a in out)

    def _paged_decode_fns(self, num_pages: int, page_size: int,
                          kv_codec: str = "fp"):
        """Build (or fetch) the jitted ragged step executable for one pool
        geometry and tier. Page table and lengths are TRACED — one executable
        per (num_pages, page_size, max_slots, pages_per_slot) shape serves
        every admit/evict/fill state (the jit-miss-free property batching
        relies on). The pool crosses ``shard_map`` as one pytree and is the
        stage scan's CARRY, so a quantized tier's codes and scales ride
        beside each other and every leaf is updated where it lies: both
        schedules run ONE stage body (``layer_scan``), which sends a write
        that must not happen — a dead unroll iteration's, a fill / drain
        step's, a padding layer's — to the trash page instead of selecting
        the pool. Quantized tiers are unpipelined only — the µ-batch
        schedule has not been run on them (ContinuousBatcher refuses the
        combination up front)."""
        key = ("paged", num_pages, page_size, kv_codec)
        if key in self._paged_fns_cache:
            return self._paged_fns_cache[key]
        n_micro = (self.pipeline.num_microbatches if self.pipelined else 1)
        if kv_codec != "fp" and n_micro > 1:
            raise ValueError(
                "quantized paged decode composes with the unpipelined split "
                "runtime only (n_micro must be 1)")
        cfg, n_stages, sz = self.cfg, self.split.n_stages, self.stage_size
        codecs, mesh = self.codecs, self.mesh
        layer_pspec = self._layer_pspec
        link = self._link
        tree_map = jax.tree_util.tree_map

        def _hop_protocol(run_stage, hidden, carry, fault_key):
            if link is None:
                out, c = run_pipeline_stages_carry(
                    n_stages, codecs, run_stage, hidden, carry)
                return out, c, None
            return run_pipeline_stages_carry(
                n_stages, codecs, run_stage, hidden, carry,
                link=link, fault_key=fault_key)

        def _hop_protocol_pipelined(run_stage, hidden, carry, fault_key):
            if link is None:
                out, c = run_pipeline_stages_carry_microbatched(
                    n_stages, codecs, n_micro, run_stage, hidden, carry)
                return out, c, None
            return run_pipeline_stages_carry_microbatched(
                n_stages, codecs, n_micro, run_stage, hidden, carry,
                link=link, fault_key=fault_key)

        def stage_step_paged(local_layers, local_valid, hidden, pool_loc,
                             page_table, lengths, cos_b, sin_b):
            lv = {k: v[0] for k, v in local_layers.items()}
            valid = local_valid[0]
            layers = jnp.arange(sz, dtype=jnp.int32)
            hidden = jax.lax.pcast(hidden, ("stage",), to="varying")

            def layer_scan(table, lens, cb, sb):
                """``(hidden, pool, live) -> (hidden, pool)``: this stage's
                layers over its pool, the stage body of both schedules. The
                pool's ``(sz, P, ps, width)`` leaves are CARRIED whole beside
                the hidden state and the layer index is scanned, as
                ``paged_kv.paged_decode_step`` does on one chip: each layer's
                row scatter and page gathers address ``layer*P + page`` of
                the donated buffer where it lies; nothing is sliced into
                ``xs``, stacked out of ``ys`` or selected.

                A write that must not happen goes to the trash page (page 0,
                where idle slots' writes already go): ``live`` False is a
                dead unroll iteration (this device is not the stage whose
                turn it is) or a fill / drain step of the µ-batch schedule, a
                False in ``valid`` a padding layer of an uneven cut. Only the
                WRITE is rerouted: the gathers read the real pages (a dead
                iteration reading through an all-zero table costs 19.5 ms of
                a 65 ms step more, the one trash page fetched over and over:
                PERF.md §6 "PR 31"), and what is computed from them is
                dropped by the small selects on the hidden state. Built once
                a trace where the tables are the step's own: every unroll
                step then scans the same body."""
                # the walk's table of leading runs is the page table's, not
                # a layer's: made before the scan, once (``walk_lead``)
                lead = paged_kv.walk_lead(pool_loc, table)

                def scan_body(carry, xs):
                    (h, pool), (lp, ok, write, layer) = carry, xs
                    out, pool = block_decode_paged(
                        cfg, lp, h, cb, sb, pool, layer, table, lens,
                        write_table=jnp.where(write, table, 0), lead=lead)
                    # a padding layer is the identity on the hidden state
                    return (jnp.where(ok, out, h), pool), None

                return lambda h, pool, live: jax.lax.scan(
                    scan_body, (h, pool),
                    (lv, valid, valid & live, layers))[0]

            def run_stage_mu(h_mu, pool, b, ok):
                # the pool is shared across slots so it is NOT sliced per
                # µ-batch: the same body sees its µ-batch's slot rows of the
                # tables, and a fill / drain step (ok False) is a dead one
                mb_rows = h_mu.shape[0]
                return layer_scan(*(
                    jax.lax.dynamic_slice_in_dim(a, b * mb_rows, mb_rows,
                                                 axis=0)
                    for a in (page_table, lengths, cos_b, sin_b)))(
                        h_mu, pool, ok)

            # the deepest slot's fill level keys the fault step: distinct as
            # decoding advances, identical across same-seed replays of the
            # same admit/evict schedule
            fkey = None if link is None else jax.random.fold_in(
                jax.random.fold_in(jax.random.key(link.faults.seed), 0x57E9),
                jnp.max(lengths))
            pool = tree_map(lambda a: a[0], pool_loc)
            if n_micro == 1:
                out, pool, counters = _hop_protocol(
                    layer_scan(page_table, lengths, cos_b, sin_b), hidden,
                    pool, fkey)
            else:
                out, pool, counters = _hop_protocol_pipelined(
                    run_stage_mu, hidden, pool, fkey)
            pool = tree_map(lambda a: a[None], pool)
            if link is None:
                return out, pool
            return out, pool, counters

        # the pool is donated: every ragged step scatters in place, same
        # aliasing discipline the "split.decode_step_paged" contract asserts
        @functools.partial(jax.jit, donate_argnums=(1,))
        def step_paged_fn(placed, pool, page_table, lengths, token_ids):
            hidden = embed(placed, token_ids[:, None])  # (B, 1, D)
            span = page_table.shape[1] * page_size
            cos, sin = precompute_rope(cfg, span)
            cos_b = cos[lengths]  # (B, rot) — each slot's own position
            sin_b = sin[lengths]
            lspecs = {k: layer_pspec(k, v.ndim)
                      for k, v in placed["layers"].items()}
            # P("stage") on the pool is a prefix of its pytree: every leaf
            out, *rest = shard_map(
                stage_step_paged, mesh=mesh,
                in_specs=(lspecs, P("stage"), P(), P("stage"),
                          P(), P(), P(), P()),
                out_specs=((P(), P("stage")) if link is None
                           else (P(), P("stage"), P())),
                check_vma=False,
            )(placed["layers"], placed["layers_valid"], hidden,
              pool, page_table, lengths, cos_b, sin_b)
            return (_unembed_last(cfg, placed, out), *rest)

        self._paged_fns_cache[key] = step_paged_fn
        return step_paged_fn

    @graph_contract(
        "split.decode_step_paged",
        collectives=lambda ctx: {"ppermute": ctx["hop_eqns"], "psum": 1},
        wire_dtypes=lambda ctx: ctx["wire_dtypes"],
        wire_bytes=lambda ctx: ctx["wire_bytes"],
        donate=lambda ctx: ctx.get("donate_min", 1))
    @graph_contract(
        "split.decode_step_paged.pipelined",
        # the ragged twin under the µ-batch schedule: M payloads of
        # (max_slots/M, 1, D) per cut, pools still donated, one psum
        collectives=lambda ctx: {"ppermute": ctx["hop_eqns"], "psum": 1},
        wire_dtypes=lambda ctx: ctx["wire_dtypes"],
        wire_bytes=lambda ctx: ctx["wire_bytes"],
        donate=lambda ctx: ctx.get("donate_min", 1))
    def decode_step_paged(self, placed_params: dict, pool,
                          page_table: jnp.ndarray, lengths: jnp.ndarray,
                          token_ids: jnp.ndarray) -> tuple:
        """One ragged decode position across the pipeline: every active slot
        advances at its OWN fill level; each cut quantizes the single-token
        hidden batch through its wire codec. page_table (max_slots,
        pages_per_slot) / lengths (max_slots,) come from a host-side
        PagedKVCache (cache_dim=... n/a — the host object tracks pages, this
        runs the math). Returns (logits (max_slots, V) fp32, updated pool).
        Per-slot tokens are bit-identical to :meth:`decode_step` at the same
        position (tests/test_batching.py asserts it end to end)."""
        self._check_alive()
        self._check_decode_supported()
        if self.pipelined:
            self.pipeline.validate_batch(int(np.shape(page_table)[0]),
                                         "paged decode slot count")
        step_fn = self._paged_decode_fns(pool.num_pages, pool.page_size,
                                         kv_codec=pool_tier(pool))
        logits, pool, *counters = step_fn(
            placed_params, pool, jnp.asarray(page_table, jnp.int32),
            jnp.asarray(lengths, jnp.int32), token_ids)
        if counters:
            self._accum_counters(counters[0])
        return logits, pool

    # ---------- accounting ----------

    def hop_bytes(self, batch: int, seq: int) -> list:
        """Measured payload bytes per hop for one (batch, seq, D) activation."""
        return hop_payload_bytes(self.codecs, self.cfg, batch, seq)

    def bytes_per_token(self, seq: int) -> list:
        """Per-hop boundary bytes per token (the BASELINE.json metric)."""
        return [b / seq for b in self.hop_bytes(1, seq)]

    def time_hops(self, batch: int, seq: int, iters: int = 20,
                  warmup: int = 1) -> list:
        """Measured per-hop boundary-transfer time (ms): encode -> ppermute ->
        decode of one (batch, seq, D) activation, isolated from the stage
        compute so the observability numbers attribute wire cost separately
        (the reference has no transfer at all to time — SURVEY.md section 5).
        Always pre-warmed (``warmup`` clamps to >= 1) so compile seconds
        never pollute the per-hop ms."""
        return measure_hop_times(self.mesh, self.codecs, self.cfg, batch, seq,
                                 iters=iters, warmup=warmup)

    def time_decode_hops(self, batch: int = 1, iters: int = 20,
                         warmup: int = 1) -> list:
        """:meth:`time_hops` at the decode shape — one (batch, 1, D) token
        per step, the regime where codec overhead dominates the hop and
        where an unwarmed jit would mis-report compile time as transfer
        time (the per-hop payload is a few KB; the first-call compile is
        seconds)."""
        return measure_hop_times(self.mesh, self.codecs, self.cfg, batch, 1,
                                 iters=iters, warmup=warmup)
