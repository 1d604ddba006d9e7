"""The registered metric/span name tables — the single vocabulary every
``registry.counter/gauge/histogram(...)`` and ``span(...)`` call site must
draw from.

Why a table: a typo'd metric name (``"edgellm_hop_bytez"``) is not an error
anywhere — the registry happily creates the series, dashboards scrape
nothing, and the mistake is only found weeks later by a human staring at an
empty panel. graphlint rule EG007 (``lint/ast_rules.py``) closes that hole
statically: every *literal* name passed to a metric factory or a span
constructor must appear here, and every f-string name must match one of the
registered ``*`` templates (the holes are the runtime-varying segment, e.g.
the fault-counter key in ``edgellm_link_*_total``). Dynamic names (a
variable first argument) are out of scope — the lint stands down rather
than guess.

This module is imported by the lint layer, so it must stay stdlib-only and
import nothing from the rest of the package.
"""
from __future__ import annotations

from fnmatch import fnmatchcase
from typing import FrozenSet, Tuple

__all__ = [
    "METRIC_NAMES", "METRIC_TEMPLATES", "SCOPE_NAMES", "SCOPE_TEMPLATES",
    "SPAN_NAMES", "SPAN_TEMPLATES", "metric_registered", "scope_registered",
    "span_registered",
]

#: every literal metric family name in the package (registry factories and
#: direct Counter/Gauge/Histogram constructions)
METRIC_NAMES: FrozenSet[str] = frozenset({
    # serve front (pre-date the edgellm_ prefix; renaming would break the
    # serve-report consumers, so they are registered as-is)
    "serve_requests_total",
    "serve_ttft_s",
    "serve_latency_s",
    "serve_retries_charged_total",
    "serve_brownout_level",
    "serve_queue_depth",
    # decode loop
    "edgellm_decode_jit_cache_misses_total",
    "edgellm_decode_steps_total",
    "edgellm_decode_prefill_s",
    "edgellm_decode_decode_s",
    "edgellm_decode_ttft_seconds",
    "edgellm_decode_token_latency_seconds",
    # boundary wire
    "edgellm_wire_bytes_total",
    # pipelined decode
    "edgellm_pipeline_microbatches",
    "edgellm_pipeline_bubble_fraction",
    "edgellm_pipeline_bubble_fraction_measured",
    "edgellm_pipeline_stage_occupancy",
    # speculative decode
    "edgellm_spec_acceptance_rate",
    "edgellm_spec_hops_per_token",
    # prefix-sharing paged KV cache
    "edgellm_prefix_hits_total",
    "edgellm_prefix_misses_total",
    "edgellm_prefix_saved_tokens_total",
    "edgellm_prefix_cow_forks_total",
    "edgellm_prefix_hit_rate",
    "edgellm_prefix_shared_pages",
    "edgellm_prefix_index_pages",
    # tracing plane
    "edgellm_flight_dumps_total",
    "edgellm_obs_scrapes_total",
    # cluster router (serve/cluster.py)
    "edgellm_cluster_replicas",
    "edgellm_cluster_live_replicas",
    "edgellm_cluster_pressure",
    "edgellm_cluster_parked",
    "edgellm_cluster_placements_total",
    "edgellm_cluster_kills_total",
    "edgellm_cluster_respawns_total",
    "edgellm_cluster_readmitted_total",
    "edgellm_cluster_recompute_tokens_total",
    "edgellm_cluster_autoscale_events_total",
    # disaggregated prefill/decode (serve/disagg.py)
    "edgellm_disagg_migrations_total",
    "edgellm_disagg_pages_migrated_total",
    "edgellm_disagg_wire_bytes_total",
    "edgellm_disagg_recompute_tokens_total",
    "edgellm_disagg_readmitted_total",
    "edgellm_disagg_prefill_workers",
    "edgellm_disagg_queue_depth",
    "edgellm_disagg_degraded",
    # gray-failure plane (serve/overload.py StragglerDetector +
    # serve/cluster.py hedging + deadline propagation)
    "edgellm_gray_stragglers",
    "edgellm_gray_hedge_delay_s",
    "edgellm_gray_hedges_total",
    "edgellm_gray_hedge_wins_total",
    "edgellm_gray_deadline_expired_total",
    "edgellm_gray_demotions_total",
})

#: templates for adapter families whose middle segment is a runtime key
#: (fault-counter names, recovery counters, link-health gauges); an f-string
#: call site lints against these with its holes as ``*``
METRIC_TEMPLATES: Tuple[str, ...] = (
    "edgellm_link_*_total",
    "edgellm_recovery_*_total",
    "edgellm_link_health_*",
    "edgellm_spec_*_total",
)

#: every literal span name
SPAN_NAMES: FrozenSet[str] = frozenset({
    # serve/decode.py
    "generate.prefill",
    "generate.decode_loop",
    "generate_split.prefill",
    "generate_split.decode_loop",
    "decode.checkpoint_write",
    "decode.checkpoint_resume",
    "decode.failover",
    # serve/speculative.py
    "generate_spec.prefill",
    "generate_spec.resume_draft_prefill",
    "generate_spec.burst_loop",
    # serve/recovery.py
    "recovery.checkpoint_save",
    "recovery.checkpoint_load",
    # serve/frontend.py + serve/batching.py (request-scoped tracing plane)
    "serve.submit",
    "serve.execute",
    "batch.submit",
    # ContinuousBatcher.step and its six phases (obs.tracing.phase: always
    # on a profiler capture, clocks admit_s ... commit_s of report()), and
    # one admission with its device dispatches, and the host sync on its
    # token 0 (which stands behind the device work after the admission: in
    # the loop's next batch.admit, or in batch.step.sync behind the launch)
    "batch.step",
    "batch.step.admit",
    "batch.step.grow",
    "batch.step.build",
    "batch.step.launch",
    "batch.step.sync",
    "batch.step.commit",
    "batch.admit",
    "batch.admit.prefill",
    "batch.admit.adopt",
    "batch.admit.tok0_sync",
    # per-cut boundary-hop attribution (decode, speculative, eval)
    "split.hop",
    # eval/split_eval.py
    "eval.checkpoint_write",
    "eval.failover",
    "eval.submit_group",
    "eval.drain_group",
    "eval.time_hops",
    "eval.time_decode_hops",
    # lint graph-layer probe
    "lint.obs-identity-probe",
    # serve/cluster.py replica lifecycle (rare paths only — the router's
    # per-request hot path stays span-free for the 10⁶-request soak)
    "cluster.kill",
    "cluster.respawn",
    "cluster.autoscale",
    # serve/disagg.py migration lifecycle (per-page hop attribution rides
    # on disagg.migrate_page's sid/wid/page attrs)
    "disagg.prefill",
    "disagg.migrate",
    "disagg.migrate_page",
    "disagg.adopt",
    "disagg.degrade",
    "disagg.kill",
    "disagg.readmit",
    # gray-failure plane: hedges and straggler verdict flips are rare by
    # construction (bounded by max_hedge_fraction / dwell hysteresis), so
    # spanning them keeps the per-request hot path span-free
    "cluster.hedge",
    "gray.demote",
})

#: span-name templates (none yet — span names are all static today); kept so
#: EG007 treats spans and metrics uniformly
SPAN_TEMPLATES: Tuple[str, ...] = ()


#: every ``jax.named_scope`` the package opens inside traced code: the path
#: segment a device operation's ``op_name`` metadata carries, so device time
#: is summed by a name the program owns and not by ``fusion.222``
SCOPE_NAMES: FrozenSet[str] = frozenset({
    "paged_kv.write",     # the per-layer K/V row scatter of the ragged step
    "paged_kv.adopt",     # a prefilled or resumed prefix scattered into pages
    "attn.decode",        # the paged decode attention of one layer (with
                          # the per-head q/k norms, the output gate and the
                          # post-norm of a layer that holds them)
    "attn.window",        # ... of one sliding-window layer: its projections,
                          # the ring's row write, page gather and attend
                          # (norms, gate and post-norm as above)
    "attn.latent",        # ... of one latent layer, absorbed: projections,
                          # rotation, absorption, page gather, attend, the
                          # V up-projection and the output projection
    "attn.latent.expand",  # a latent layer's prefill: keys and values
                           # rebuilt per head, attention by query blocks
    # a sparse-attention layer (models/sparse_attn.py)
    "attn.sparse",        # ... its decode: projections, norms, rotation, both
                          # row writes, the indexer, the selection, the
                          # selected rows' read and attend, W_o
    "attn.sparse.index",  # the indexer's projections and its score pass over
                          # the live index keys (decode: the index walk, or
                          # the page gather and a dot; prefill: a block of
                          # query rows at a time)
    "attn.sparse.select",  # the top-k and what turns it into row ids (decode)
                           # or a mask on a block's scores (prefill)
    "attn.sparse.prefill",  # a sparse layer over a whole prompt, by blocks
    # a sparse LATENT layer (models/sparse_mla.py): the two scopes above for
    # its indexer and its selection, and an outer scope of its own a form, so
    # that a reader tells it from attn.sparse and attn.latent
    "attn.sparse_latent",  # ... its decode: projections, norms, rotations,
                           # both row writes, the indexer, the selection,
                           # the absorbed read of the chosen rows, W_kvb's V
                           # half and W_o
    "attn.sparse_latent.prefill",  # ... over a whole prompt, by blocks of
                                   # query rows (absorbed; on the masked
                                   # kernel expanded, a body at a time)
    # a window layer whose ring holds LATENT rows (dots3_note)
    "attn.window_latent",  # ... its decode: projections, norms, rotation, the
                           # ring's row write, the walk (or gather) of the
                           # ring under the band, W_kvb's V half, the heads'
                           # gate and W_o
    "attn.window_latent.write",  # the ring's row write within it: not the
                                 # growing pool's paged_kv.write, so a reader
                                 # of the full layers' scopes reads them alone
    "attn.window_latent.prefill",  # ... over a whole prompt: expanded, by
                                   # blocks of query rows under the band
    "mlp",                # a dense feed-forward; in a stack walked by layer
                          # kinds, a leading dense layer's and its post-norm,
                          # or every sublayer's dense SwiGLU (longcat_flash)
    "unembed_sample",     # final norm + LM head + the per-slot sampler
    "split.stage",        # one stage iteration of the split unroll
    # a stack with recurrent state and routed experts (models/mamba2.py,
    # models/moe.py, the per-slot state store of models/paged_kv.py)
    "ssm.proj",           # a Mamba-2 layer's in and out projections
    "ssm.step",           # decode: window update, recurrence, gated norm
    "ssm.scan",           # prefill: the convolution and the chunked scan
    # a gated short convolution (models/shortconv.py), prefill and decode
    "shortconv.proj",     # the in (B | C | x) and out projections
    "shortconv.conv",     # the gates, the taps, the window's read and write
    "moe.route",          # router logits, top-k, softmax over the chosen
                          # (or sigmoid scores, the selection bias, the
                          # top-k, the normalised and scaled weights; or the
                          # whole softmax's scores, chosen and scaled alike;
                          # the group-limited selection's mask where the
                          # router's outputs lie in groups)
    "moe.experts",        # the held experts' part for the tokens routed here
                          # (and the norm after the expert sublayer, where a
                          # layer has one; the identity experts' part and the
                          # count of their assignments, where a router has
                          # them: two elementwise fusions the trace cannot
                          # tell from the combine, so no scope of their own)
    "moe.experts.grouped",  # a prefill's three grouped products gate / up /
                            # down over the rows sorted by held expert
    "moe.shared",         # the shared expert on every token
    "state.adopt",        # a slot's recurrent state overwritten (or zeroed)
})

#: scope templates: ``split.hop.<cut>`` is one boundary hop (encode, the
#: collective-permute, decode), the hole the cut's index
SCOPE_TEMPLATES: Tuple[str, ...] = (
    "split.hop.*",
)


def _registered(pattern: str, names: FrozenSet[str],
                templates: Tuple[str, ...]) -> bool:
    if "*" in pattern:
        # an f-string call site: its hole pattern must be a registered
        # template verbatim — matching a template *partially* would let
        # ``f"edgellm_link_{x}z_total"`` slip through
        return pattern in templates
    return pattern in names or any(fnmatchcase(pattern, t)
                                   for t in templates)


def metric_registered(name_or_pattern: str) -> bool:
    """True when a literal metric name (or the ``*``-holed pattern of an
    f-string call site) is in the registered vocabulary."""
    return _registered(name_or_pattern, METRIC_NAMES, METRIC_TEMPLATES)


def span_registered(name_or_pattern: str) -> bool:
    """Span-name twin of :func:`metric_registered`."""
    return _registered(name_or_pattern, SPAN_NAMES, SPAN_TEMPLATES)


def scope_registered(name_or_pattern: str) -> bool:
    """``jax.named_scope`` twin of :func:`metric_registered`."""
    return _registered(name_or_pattern, SCOPE_NAMES, SCOPE_TEMPLATES)
