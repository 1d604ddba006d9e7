"""CLI sweep driver, compatible with the reference's ``params.json`` convention.

The reference drives each experiment with ``python main.py`` next to a flat
``params.json`` (keys: ``ratios``, ``layers_of_interest``, ``stride``,
``max_length``, ``experiment``, ``methods`` — ``Pythia-70M/main.py:23-32``,
``Qwen2-0.5B/main.py:107-119``). Here one entry point covers every experiment:

    python -m edgellm_tpu.run --params params.json --model qwen2-0.5b \
        --corpus corpus.npy [--weights ckpt.safetensors] [--output-dir out]

Dispatch mirrors the reference:
- ``experiment: "initial"``   -> Pythia initial sweep (affine-int8 rank / top-rho)
- ``experiment: "last_row"``  -> token-selective int4 sweep (Pythia defaults)
- ``experiment: "relevance"`` -> LRP head-relevance extraction
- ``experiment: "split"``     -> real mesh-split eval (ppermute boundary hops)
- ``experiment: "distances"`` -> layer-pair JS-divergence matrix + heatmap
  (the ``distributions_distance_across_layers.ipynb`` cell 16-18 analysis)
- ``experiment: "serve"``     -> deterministic soak through the overload-robust
  serving front (admission control, circuit breakers, brownout; ``"serving"``
  params block, ``--serve-report``)
- methods containing "channel" -> per-channel codec sweep (``main.py:118-119``)
- otherwise                   -> the Qwen-style token sweep

Corpus input is a ``.npy``/``.npz`` of token ids, or a raw ``.txt`` plus
``--tokenizer`` (a local HF tokenizer path; this environment has no network).
Weights: a local torch checkpoint via ``--weights`` (state_dict ``.pt`` or
HF directory), else random init (smoke/benchmark mode).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
from typing import Optional

import numpy as np


def _load_corpus(args, vocab_size: int) -> np.ndarray:
    if args.corpus is None:
        rng = np.random.default_rng(args.seed)
        return rng.integers(0, vocab_size, args.synthetic_corpus_len)
    if args.corpus.endswith((".npy", ".npz")):
        data = np.load(args.corpus)
        if hasattr(data, "files"):
            data = data[data.files[0]]
        return np.asarray(data).reshape(-1)
    # raw text: reproduce the reference's corpus construction — documents joined
    # with "\n\n" (Qwen2-0.5B/main.py:122-124). A text file is assumed to already
    # be the joined corpus.
    if args.tokenizer is None:
        raise SystemExit("--tokenizer is required for raw-text corpora")
    from transformers import AutoTokenizer

    tok = AutoTokenizer.from_pretrained(args.tokenizer)
    with open(args.corpus) as f:
        text = f.read()
    return np.asarray(tok(text, return_tensors="np").input_ids).reshape(-1)


def _load_model(args):
    import jax
    from .models import PRESETS, init_params, params_from_state_dict, config_from_hf

    if args.weights:
        # torch-free fast path: .safetensors file, or an HF directory laid out
        # with safetensors shards + config.json
        from .models.safetensors_io import load_checkpoint

        if args.weights.endswith(".safetensors"):
            if args.model not in PRESETS:
                raise SystemExit(f"--model must be one of {sorted(PRESETS)} with a "
                                 f"bare .safetensors file")
            return load_checkpoint(args.weights, PRESETS[args.model])
        if os.path.isdir(args.weights) and any(
                f.endswith(".safetensors") for f in os.listdir(args.weights)):
            return load_checkpoint(args.weights)

        import torch

        if os.path.isdir(args.weights):
            from transformers import AutoConfig, AutoModelForCausalLM

            hf_cfg = AutoConfig.from_pretrained(args.weights)
            cfg = config_from_hf(hf_cfg)
            model = AutoModelForCausalLM.from_pretrained(args.weights)
            sd = model.state_dict()
        else:
            if args.model not in PRESETS:
                raise SystemExit(f"--model must be one of {sorted(PRESETS)} with --weights file")
            cfg = PRESETS[args.model]
            sd = torch.load(args.weights, map_location="cpu")
        return cfg, params_from_state_dict(cfg, sd)
    cfg = PRESETS[args.model]
    return cfg, init_params(cfg, jax.random.key(args.seed))


#: every key any experiment reads, with the experiments that consume it —
#: unknown keys fail fast instead of being silently ignored (a typo'd
#: "hop_codec" used to run the whole eval with defaults)
_PARAM_KEYS = {
    "experiment": "all",
    "max_length": "all", "stride": "all",
    "methods": "token/channel sweeps",
    "layers_of_interest": "initial/token/channel sweeps",
    "ratios": "initial/token sweeps",
    "cuts": "split/serve", "hop_codecs": "split/serve",
    "importance_method": "split",
    "n_seq": "split", "n_data": "split", "n_model": "split",
    "faults": "split/serve", "link_policy": "split/serve",
    "fec": "split/serve", "hedge": "split/serve",
    "link_health": "split/serve",
    "deadline": "split", "stage_failure": "split", "recovery": "split",
    "pipeline": "split/serve",
    "serving": "serve",
    "batching": "serve",
    "prefix_cache": "serve",
    "kv_at_rest": "serve",
    "speculative": "serve",
    "cluster": "serve",
    "disagg": "serve",
    "gray": "serve",
    "max_compiles": "distances",
    "observability": "all",
    "budget": "all (latticelint AOT peak)",
}
_EXPERIMENTS = ("", "initial", "last_row", "relevance", "split", "distances",
                "serve")
_REQUIRED = {"split": ("cuts", "hop_codecs"),
             "serve": ("serving",),
             "initial": ("layers_of_interest", "ratios")}


def _validate_params_json(p: dict) -> None:
    """Fail fast — naming the offending key — before any device work starts.

    Checks the key set, per-experiment required keys, basic value shapes, and
    resolves every codec name (hop codecs, fault-ladder tiers) and fault/policy
    field against the real constructors, so a typo'd params.json dies in
    milliseconds instead of after the model loads."""
    def die(msg):
        raise SystemExit(f"params.json: {msg}")

    if not isinstance(p, dict):
        die(f"expected a JSON object, got {type(p).__name__}")
    unknown = sorted(set(p) - set(_PARAM_KEYS))
    if unknown:
        die(f"unknown key(s) {unknown}; known keys: {sorted(_PARAM_KEYS)}")
    exp = p.get("experiment", "")
    if exp not in _EXPERIMENTS:
        die(f"unknown experiment {exp!r}; options: {list(_EXPERIMENTS)}")
    if "observability" in p:
        from .obs import ObservabilityConfig

        ob = p["observability"]
        if not isinstance(ob, dict):
            die(f"observability must be an object of ObservabilityConfig "
                f"fields, got {ob!r}")
        fields = {f.name for f in dataclasses.fields(ObservabilityConfig)}
        bad = sorted(set(ob) - fields)
        if bad:
            die(f"observability: unknown field(s) {bad}; "
                f"known: {sorted(fields)}")
        try:
            ObservabilityConfig(**ob)
        except (TypeError, ValueError) as e:
            die(f"observability: {e}")
    if "budget" in p:
        # the latticelint contract: a shipped config pins its lint-geometry
        # AOT peak so a graph change that balloons temp bytes is a finding
        b = p["budget"]
        if not isinstance(b, dict):
            die(f"budget must be an object with 'aot_peak_bytes' (and an "
                f"optional 'note'), got {b!r}")
        bad = sorted(set(b) - {"aot_peak_bytes", "note"})
        if bad:
            die(f"budget: unknown field(s) {bad}; "
                f"known: ['aot_peak_bytes', 'note']")
        if "aot_peak_bytes" not in b:
            die("budget needs 'aot_peak_bytes' (the latticelint AOT ceiling)")
        if (not isinstance(b["aot_peak_bytes"], int)
                or isinstance(b["aot_peak_bytes"], bool)
                or b["aot_peak_bytes"] < 1):
            die(f"budget.aot_peak_bytes must be a positive integer, "
                f"got {b['aot_peak_bytes']!r}")
        if "note" in b and not isinstance(b["note"], str):
            die(f"budget.note must be a string, got {b['note']!r}")
    if exp not in ("split", "serve") and (
            "faults" in p or "link_policy" in p or "fec" in p
            or "hedge" in p or "link_health" in p):
        die("faults/link_policy/fec/hedge/link_health only apply to "
            "experiments 'split' and 'serve'")
    if exp != "split" and ("deadline" in p or "stage_failure" in p
                           or "recovery" in p):
        die("deadline/stage_failure/recovery only apply to experiment 'split'")
    if exp != "serve" and "serving" in p:
        die("serving only applies to experiment 'serve'")
    if exp != "serve" and "batching" in p:
        die("batching only applies to experiment 'serve'")
    for k in _REQUIRED.get(exp, ()):
        if k not in p:
            die(f"experiment {exp!r} requires key {k!r}")
    if exp not in ("split", "serve", "initial", "relevance", "distances"):
        # token/channel sweeps (the default dispatch) sweep layers (x ratios
        # for the token sweep; the channel sweep has no ratio axis)
        methods = p.get("methods", [])
        need = ["layers_of_interest"]
        if not (methods and isinstance(methods[0], str)
                and "channel" in methods[0]):
            need.append("ratios")
        for k in need:
            if k not in p:
                die(f"experiment {exp or '(token sweep)'!r} requires key {k!r}")
    for k in ("max_length", "stride", "n_seq", "n_data", "n_model",
              "max_compiles"):
        if k in p and (not isinstance(p[k], int) or isinstance(p[k], bool)
                       or p[k] < 1):
            die(f"{k} must be a positive integer, got {p[k]!r}")
    for k in ("methods", "layers_of_interest", "ratios", "cuts", "hop_codecs"):
        if k in p and not isinstance(p[k], list):
            die(f"{k} must be a list, got {type(p[k]).__name__}")
    if exp == "serve" and ("cuts" in p) != ("hop_codecs" in p):
        die("serve: cuts and hop_codecs go together")
    if exp in ("split", "serve") and "cuts" in p:
        if not p["cuts"] or not all(
                isinstance(c, int) and not isinstance(c, bool) and c >= 0
                for c in p["cuts"]):
            die(f"cuts must be a non-empty list of layer indices, "
                f"got {p['cuts']!r}")
        if len(p["hop_codecs"]) != len(p["cuts"]):
            die(f"hop_codecs has {len(p['hop_codecs'])} entries for "
                f"{len(p['cuts'])} cut(s)")
        from .codecs.packing import get_wire_codec
        from .eval.split_eval import parse_hop_codec

        for spec in p["hop_codecs"]:
            if not isinstance(spec, str):
                die(f"hop_codecs entries must be codec spec strings, "
                    f"got {spec!r}")
            try:
                resolved = parse_hop_codec(spec, p.get("n_seq", 1))
                if isinstance(resolved, str):
                    get_wire_codec(resolved)
            except (ValueError, KeyError) as e:
                die(f"bad hop codec {spec!r}: {e}")
    if exp in ("split", "serve"):
        from .codecs.faults import FaultConfig, LinkPolicy

        for key, cls in (("faults", FaultConfig), ("link_policy", LinkPolicy)):
            if key not in p:
                continue
            if not isinstance(p[key], dict):
                die(f"{key} must be an object of {cls.__name__} fields, "
                    f"got {p[key]!r}")
            fields = {f.name for f in dataclasses.fields(cls)}
            bad = sorted(set(p[key]) - fields)
            if bad:
                die(f"{key}: unknown field(s) {bad}; known: {sorted(fields)}")
            try:
                obj = cls(**{**p[key], "tiers": tuple(p[key].get("tiers", ()))}
                          if key == "link_policy" else p[key])
            except (TypeError, ValueError) as e:
                die(f"{key}: {e}")
            if key == "link_policy":
                for t in obj.tiers:
                    try:
                        get_wire_codec(t)
                    except ValueError as e:
                        die(f"link_policy.tiers: {e}")
        from .codecs.fec import FECConfig, HedgeConfig, LinkHealthConfig

        for key, cls in (("fec", FECConfig), ("hedge", HedgeConfig),
                         ("link_health", LinkHealthConfig)):
            if key not in p:
                continue
            if not isinstance(p[key], dict):
                die(f"{key} must be an object of {cls.__name__} fields, "
                    f"got {p[key]!r}")
            fields = {f.name for f in dataclasses.fields(cls)}
            bad = sorted(set(p[key]) - fields)
            if bad:
                die(f"{key}: unknown field(s) {bad}; known: {sorted(fields)}")
            try:
                cls(**p[key])
            except (TypeError, ValueError) as e:
                die(f"{key}: {e}")
            if "faults" not in p or not FaultConfig(**p["faults"]).enabled:
                die(f"{key} requires an enabled 'faults' config (the link "
                    f"machinery only exists in the graph when a fault can "
                    f"fire)")
        if "deadline" in p:
            d = p["deadline"]
            if isinstance(d, bool) or not isinstance(d, (int, float)) or d <= 0:
                die(f"deadline must be a positive number of seconds, got {d!r}")
        if "stage_failure" in p:
            from .serve.recovery import StageFailure

            sf = p["stage_failure"]
            if not isinstance(sf, dict):
                die(f"stage_failure must be an object of StageFailure fields, "
                    f"got {sf!r}")
            fields = {f.name for f in dataclasses.fields(StageFailure)}
            bad = sorted(set(sf) - fields)
            if bad:
                die(f"stage_failure: unknown field(s) {bad}; "
                    f"known: {sorted(fields)}")
            try:
                obj = StageFailure(**sf)
            except (TypeError, ValueError) as e:
                die(f"stage_failure: {e}")
            if obj.stage > len(p["cuts"]):
                die(f"stage_failure.stage {obj.stage} out of range for "
                    f"{len(p['cuts']) + 1} pipeline stage(s)")
            if p.get("n_seq", 1) > 1:
                die("stage_failure needs the plain split runtime (n_seq == 1)")
        if "recovery" in p:
            r = p["recovery"]
            if not isinstance(r, dict):
                die(f"recovery must be an object, got {r!r}")
            bad = sorted(set(r) - {"replan", "max_failovers"})
            if bad:
                die(f"recovery: unknown field(s) {bad}; "
                    f"known: ['max_failovers', 'replan']")
            if "replan" in r and not isinstance(r["replan"], bool):
                die(f"recovery.replan must be a boolean, got {r['replan']!r}")
            mf = r.get("max_failovers", 1)
            if isinstance(mf, bool) or not isinstance(mf, int) or mf < 1:
                die(f"recovery.max_failovers must be a positive integer, "
                    f"got {mf!r}")
    if "serving" in p:
        from .serve.frontend import ServeFrontConfig
        from .serve.overload import (AdmissionConfig, BreakerConfig,
                                     BrownoutConfig, RetryBudgetConfig)
        from .serve.soak import SoakConfig

        sv = p["serving"]
        if not isinstance(sv, dict):
            die(f"serving must be an object of ServeFrontConfig fields "
                f"(plus 'soak'), got {sv!r}")
        top = {f.name for f in dataclasses.fields(ServeFrontConfig)} | {"soak"}
        bad = sorted(set(sv) - top)
        if bad:
            die(f"serving: unknown field(s) {bad}; known: {sorted(top)}")
        for key, cls in (("admission", AdmissionConfig),
                         ("breaker", BreakerConfig),
                         ("brownout", BrownoutConfig),
                         ("retry_budget", RetryBudgetConfig),
                         ("soak", SoakConfig)):
            if key not in sv:
                continue
            if not isinstance(sv[key], dict):
                die(f"serving.{key} must be an object of {cls.__name__} "
                    f"fields, got {sv[key]!r}")
            fields = {f.name for f in dataclasses.fields(cls)}
            bad = sorted(set(sv[key]) - fields)
            if bad:
                die(f"serving.{key}: unknown field(s) {bad}; "
                    f"known: {sorted(fields)}")
            try:
                cls(**sv[key])
            except (TypeError, ValueError) as e:
                die(f"serving.{key}: {e}")
        try:
            _serve_front_config(sv)
        except (TypeError, ValueError) as e:
            die(f"serving: {e}")
        ks = (sv.get("soak") or {}).get("kill_stage")
        if ks is not None and "cuts" in p and ks > len(p["cuts"]):
            die(f"serving.soak.kill_stage {ks} out of range for "
                f"{len(p['cuts']) + 1} pipeline stage(s)")
    if "batching" in p:
        from .serve.batching import BatchingConfig

        b = p["batching"]
        if not isinstance(b, dict):
            die(f"batching must be an object of BatchingConfig fields, "
                f"got {b!r}")
        # dtype fields are runtime objects, not JSON — keep them out of the
        # schema so a typo'd key dies with the real field list; prefix_cache
        # and kv_codec have their own top-level params blocks
        fields = {f.name for f in dataclasses.fields(BatchingConfig)} \
            - {"compute_dtype", "cache_dtype", "prefix_cache", "kv_codec"}
        bad = sorted(set(b) - fields)
        if bad:
            die(f"batching: unknown field(s) {bad}; known: {sorted(fields)}")
        try:
            bcfg = BatchingConfig(**b)
        except (TypeError, ValueError) as e:
            die(f"batching: {e}")
        sk = (p.get("serving", {}).get("soak") or {})
        need = (sk.get("prompt_len", 8) + sk.get("max_new_tokens", 8) - 1)
        if need > bcfg.span:
            die(f"batching: soak requests need {need} cache positions > slot "
                f"span {bcfg.span} (pages_per_slot x page_size)")
    if "prefix_cache" in p:
        from .models.paged_kv import PrefixCacheConfig

        if exp != "serve":
            die("prefix_cache only applies to experiment 'serve'")
        if "batching" not in p:
            die("prefix_cache rides the continuous batcher's paged pool — "
                "add a 'batching' block")
        pc = p["prefix_cache"]
        if not isinstance(pc, dict):
            die(f"prefix_cache must be an object of PrefixCacheConfig "
                f"fields, got {pc!r}")
        fields = {f.name for f in dataclasses.fields(PrefixCacheConfig)}
        bad = sorted(set(pc) - fields)
        if bad:
            die(f"prefix_cache: unknown field(s) {bad}; "
                f"known: {sorted(fields)}")
        if "enabled" in pc and not isinstance(pc["enabled"], bool):
            die(f"prefix_cache.enabled must be a boolean, "
                f"got {pc['enabled']!r}")
        for k in ("min_shared_block", "max_index_pages"):
            if k in pc and (not isinstance(pc[k], int)
                            or isinstance(pc[k], bool) or pc[k] < 0):
                die(f"prefix_cache.{k} must be a non-negative integer, "
                    f"got {pc[k]!r}")
        try:
            PrefixCacheConfig(**pc)
        except (TypeError, ValueError) as e:
            die(f"prefix_cache: {e}")
    if "kv_at_rest" in p:
        from .models.paged_kv import KV_PAGE_CODECS, resolve_kv_codec

        if exp != "serve":
            die("kv_at_rest only applies to experiment 'serve'")
        if "batching" not in p:
            die("kv_at_rest compresses the continuous batcher's paged pool "
                "— add a 'batching' block")
        kq = p["kv_at_rest"]
        if not isinstance(kq, dict):
            die(f"kv_at_rest must be an object with a 'codec' tier (and "
                f"optional 'pool_bytes'), got {kq!r}")
        bad = sorted(set(kq) - {"codec", "pool_bytes"})
        if bad:
            die(f"kv_at_rest: unknown field(s) {bad}; "
                f"known: ['codec', 'pool_bytes']")
        if "codec" not in kq:
            die(f"kv_at_rest needs a 'codec' tier name; "
                f"options: {sorted(KV_PAGE_CODECS)}")
        try:
            resolve_kv_codec(kq["codec"])
        except (TypeError, ValueError) as e:
            die(f"kv_at_rest: {e}")
        if "pool_bytes" in kq and (not isinstance(kq["pool_bytes"], int)
                                   or isinstance(kq["pool_bytes"], bool)
                                   or kq["pool_bytes"] < 1):
            die(f"kv_at_rest.pool_bytes must be a positive integer, "
                f"got {kq['pool_bytes']!r}")
    if "pipeline" in p:
        from .parallel.split import PipelineConfig

        if exp not in ("split", "serve"):
            die("pipeline only applies to experiments 'split' and 'serve'")
        if "cuts" not in p:
            die("pipeline schedules micro-batches across the split boundary "
                "— add 'cuts'/'hop_codecs'")
        pl = p["pipeline"]
        if not isinstance(pl, dict):
            die(f"pipeline must be an object of PipelineConfig fields, "
                f"got {pl!r}")
        fields = {f.name for f in dataclasses.fields(PipelineConfig)}
        bad = sorted(set(pl) - fields)
        if bad:
            die(f"pipeline: unknown field(s) {bad}; known: {sorted(fields)}")
        try:
            pc = PipelineConfig(**pl)
        except (TypeError, ValueError) as e:
            die(f"pipeline: {e}")
        if pc.enabled and p.get("n_seq", 1) > 1:
            die("pipeline needs the plain split runtime (n_seq == 1); the "
                "stage x seq runtime overlaps hops with its ring rotation")
        if pc.enabled and "batching" in p:
            ms = p["batching"].get("max_slots", 4)
            if ms % pc.num_microbatches:
                die(f"batching.max_slots {ms} must be a multiple of "
                    f"pipeline.num_microbatches {pc.num_microbatches}")
        if pc.enabled and "speculative" in p:
            sp_on = p["speculative"].get("enabled", True)
            if sp_on:
                die("pipeline + speculative: the spec loop verifies one "
                    "stream at a time (B == 1), leaving nothing to "
                    "micro-batch — drop one of the two blocks")
        if pc.enabled and p.get("kv_at_rest", {}).get("codec", "fp") != "fp":
            # mirror of _paged_decode_fns's refusal: the µ-batch
            # trash-page routing has not been run on a quantized pool
            die("kv_at_rest + pipeline: quantized paged decode composes "
                "with the unpipelined split runtime only — drop 'pipeline' "
                "or use codec 'fp'")
    if "speculative" in p:
        from .serve.speculative import SpecConfig

        if exp != "serve":
            die("speculative only applies to experiment 'serve'")
        if "cuts" not in p:
            die("speculative decode verifies across the boundary — add "
                "'cuts'/'hop_codecs'")
        sp = p["speculative"]
        if not isinstance(sp, dict):
            die(f"speculative must be an object of SpecConfig fields, "
                f"got {sp!r}")
        fields = {f.name for f in dataclasses.fields(SpecConfig)}
        bad = sorted(set(sp) - fields)
        if bad:
            die(f"speculative: unknown field(s) {bad}; "
                f"known: {sorted(fields)}")
        try:
            sc = SpecConfig(**sp)
        except (TypeError, ValueError) as e:
            die(f"speculative: {e}")
        if sc.enabled and "batching" in p:
            die("speculative runs the one-stream spec loop; the batcher's "
                "ragged step verifies one token per slot — drop "
                "'speculative' or 'batching'")
    if "cluster" in p:
        from .serve.cluster import (AutoscalerConfig, ClusterConfig,
                                    RespawnConfig)
        from .serve.overload import BreakerConfig, RetryBudgetConfig

        if exp != "serve":
            die("cluster only applies to experiment 'serve'")
        if "batching" not in p:
            die("cluster replicas each run the continuous batcher — add a "
                "'batching' block")
        if "speculative" in p:
            die("cluster + speculative: the spec loop is single-stream with "
                "no replica routing story — drop one of the two blocks")
        cl = p["cluster"]
        if not isinstance(cl, dict):
            die(f"cluster must be an object of ClusterConfig fields, "
                f"got {cl!r}")
        top = {f.name for f in dataclasses.fields(ClusterConfig)}
        bad = sorted(set(cl) - top)
        if bad:
            die(f"cluster: unknown field(s) {bad}; known: {sorted(top)}")
        for key, cls in (("breaker", BreakerConfig),
                         ("retry_budget", RetryBudgetConfig),
                         ("respawn", RespawnConfig),
                         ("autoscaler", AutoscalerConfig)):
            if key not in cl:
                continue
            if not isinstance(cl[key], dict):
                die(f"cluster.{key} must be an object of {cls.__name__} "
                    f"fields, got {cl[key]!r}")
            fields = {f.name for f in dataclasses.fields(cls)}
            bad = sorted(set(cl[key]) - fields)
            if bad:
                die(f"cluster.{key}: unknown field(s) {bad}; "
                    f"known: {sorted(fields)}")
        try:
            ccfg = _cluster_config(cl)
        except (TypeError, ValueError) as e:
            die(f"cluster: {e}")
        if ccfg.num_replicas < 2:
            die(f"cluster.num_replicas must be >= 2 (a one-replica cluster "
                f"is the plain serve front — drop the 'cluster' block), "
                f"got {ccfg.num_replicas}")
        if (p.get("serving", {}).get("soak") or {}).get(
                "kill_stage") is not None:
            die("cluster + serving.soak.kill_stage: the stage kill is the "
                "single-front chaos hook — replica kills belong to the "
                "router (ClusterFront.kill_replica, exercised by the "
                "cluster tests/bench)")
    if "disagg" in p:
        from .codecs.faults import FaultConfig
        from .codecs.fec import FECConfig, HedgeConfig
        from .serve.disagg import DisaggConfig

        if exp != "serve":
            die("disagg only applies to experiment 'serve'")
        if "speculative" in p:
            die("disagg + speculative: the spec loop is single-stream with "
                "no prefill/decode split story — drop one of the two blocks")
        if "batching" not in p:
            die("disagg splits the continuous batcher into prefill and "
                "decode workers — add a 'batching' block")
        dg = p["disagg"]
        if not isinstance(dg, dict):
            die(f"disagg must be an object of DisaggConfig fields, "
                f"got {dg!r}")
        top = {f.name for f in dataclasses.fields(DisaggConfig)}
        bad = sorted(set(dg) - top)
        if bad:
            die(f"disagg: unknown field(s) {bad}; known: {sorted(top)}")
        for key, cls in (("fec", FECConfig), ("hedge", HedgeConfig),
                         ("faults", FaultConfig)):
            if dg.get(key) is None:
                continue
            if not isinstance(dg[key], dict):
                die(f"disagg.{key} must be an object of {cls.__name__} "
                    f"fields, got {dg[key]!r}")
            fields = {f.name for f in dataclasses.fields(cls)}
            bad = sorted(set(dg[key]) - fields)
            if bad:
                die(f"disagg.{key}: unknown field(s) {bad}; "
                    f"known: {sorted(fields)}")
        try:
            _disagg_config(dg)
        except (TypeError, ValueError) as e:
            die(f"disagg: {e}")
    if "gray" in p:
        from .serve.cluster import GrayConfig

        if exp != "serve":
            die("gray only applies to experiment 'serve'")
        if "cluster" not in p:
            die("gray hardening (straggler demotion, request hedging) is a "
                "router policy — add a 'cluster' block")
        gy = p["gray"]
        if not isinstance(gy, dict):
            die(f"gray must be an object of GrayConfig fields, got {gy!r}")
        top = {f.name for f in dataclasses.fields(GrayConfig)}
        bad = sorted(set(gy) - top)
        if bad:
            die(f"gray: unknown field(s) {bad}; known: {sorted(top)}")
        try:
            _gray_config(gy)
        except (TypeError, ValueError) as e:
            die(f"gray: {e}")


def _pipeline_config(p: dict):
    """Build the :class:`PipelineConfig` a ``"pipeline"`` params block
    describes (None when absent) — validated by :func:`_validate_params_json`
    before anything touches devices."""
    if "pipeline" not in p:
        return None
    from .parallel.split import PipelineConfig

    return PipelineConfig(**p["pipeline"])


def _serve_front_config(sv: dict):
    """Build the :class:`ServeFrontConfig` a ``"serving"`` params block
    describes: nested objects become the matching sub-configs, scalar keys
    pass through, and the soak definition (``"soak"``) is the harness's,
    not the front's. Raises ``TypeError``/``ValueError`` on bad fields —
    the validator turns those into field-naming ``die()``s."""
    from .serve.frontend import ServeFrontConfig
    from .serve.overload import (AdmissionConfig, BreakerConfig,
                                 BrownoutConfig, RetryBudgetConfig)

    kwargs = {k: v for k, v in sv.items() if k != "soak"}
    for key, cls in (("admission", AdmissionConfig),
                     ("breaker", BreakerConfig),
                     ("brownout", BrownoutConfig),
                     ("retry_budget", RetryBudgetConfig)):
        if key in kwargs:
            kwargs[key] = cls(**kwargs[key])
    return ServeFrontConfig(**kwargs)


def _cluster_config(cl: dict):
    """Build the :class:`ClusterConfig` a ``"cluster"`` params block
    describes — nested policy objects (breaker, retry budget, respawn
    backoff, autoscaler bounds) become the matching sub-configs. Raises
    ``TypeError``/``ValueError``/``ClusterConfigError`` on bad fields; the
    validator turns those into field-naming ``die()``s."""
    from .serve.cluster import AutoscalerConfig, ClusterConfig, RespawnConfig
    from .serve.overload import BreakerConfig, RetryBudgetConfig

    kwargs = dict(cl)
    for key, cls in (("breaker", BreakerConfig),
                     ("retry_budget", RetryBudgetConfig),
                     ("respawn", RespawnConfig),
                     ("autoscaler", AutoscalerConfig)):
        if key in kwargs:
            kwargs[key] = cls(**kwargs[key])
    return ClusterConfig(**kwargs)


def _disagg_config(dg: dict):
    """Build the :class:`DisaggConfig` a ``"disagg"`` params block
    describes — nested migration-ladder objects (``fec``, ``hedge``,
    ``faults``) become the matching codec configs. Raises
    ``TypeError``/``ValueError`` on bad fields; the validator turns those
    into field-naming ``die()``s."""
    from .codecs.faults import FaultConfig
    from .codecs.fec import FECConfig, HedgeConfig
    from .serve.disagg import DisaggConfig

    kwargs = dict(dg)
    for key, cls in (("fec", FECConfig), ("hedge", HedgeConfig),
                     ("faults", FaultConfig)):
        if kwargs.get(key) is not None:
            kwargs[key] = cls(**kwargs[key])
    return DisaggConfig(**kwargs)


def _gray_config(gy: dict):
    """Build the :class:`GrayConfig` a ``"gray"`` params block describes —
    flat scalar fields only (the straggler/hedge thresholds). Raises
    ``TypeError``/``ValueError``/``ClusterConfigError`` on bad fields; the
    validator turns those into field-naming ``die()``s. A params block that
    is present but does not say otherwise is armed: configs opt in by
    writing the block at all, so ``enabled`` defaults to True here (the
    dataclass default False serves programmatic construction)."""
    from .serve.cluster import GrayConfig

    kwargs = dict(gy)
    kwargs.setdefault("enabled", True)
    return GrayConfig(**kwargs)


def _attach_front_obs(front) -> None:
    """Point the live endpoint's ``/healthz`` at this serve front (breaker
    states, brownout level, queue depth) when ``--obs-port`` or the params
    ``obs_port`` armed one — the global server starts before the front
    exists, so the front attaches itself here."""
    from .obs.server import get_global

    srv = get_global()
    if srv is not None:
        srv.health_fn = front.health_summary


def _batcher_failure_rc(records, exc: Optional[BaseException] = None) -> int:
    """Exit status of a batched serve soak. ``ServeFront.drain_batched``
    turns ANY exception out of ``batcher.run`` into ``failed`` records with
    reason ``batcher:<ExceptionType>`` and keeps serving; a soak that hit one
    must not exit 0 — on a chip the swallowed exception is how a compiler
    refusal would otherwise read as a table of outcomes. Policy outcomes
    (``shed``, ``rejected``, ``timed_out``) are the front doing its job and
    stay rc 0."""
    broken = [r for r in records
              if r.outcome == "failed" and r.reason.startswith("batcher:")]
    if not broken:
        return 0
    if exc is not None:
        import traceback

        traceback.print_exception(exc, file=sys.stderr)
    print(f"serve: {len(broken)} request(s) failed inside the batcher "
          f"({broken[0].reason})", file=sys.stderr, flush=True)
    return 1


def _print_serve_report(report: dict) -> None:
    """Human-readable tail for ``--serve-report``: outcome counts,
    reject/shed reasons, per-breaker states, and the brownout/retry-budget
    posture after the soak."""
    print("serve report:")
    for k in sorted(report["outcomes"]):
        print(f"  outcome {k:<14} {report['outcomes'][k]}")
    for k in sorted(report.get("reasons", {})):
        print(f"  reason  {k:<28} {report['reasons'][k]}")
    for name, b in sorted(report["breakers"].items()):
        print(f"  breaker {name:<8} {b['state']:<9} opens={b['opens']} "
              f"failures={b['total_failures']}")
    bo = report["brownout"]
    print(f"  brownout level={bo['level']} mode={bo['mode']} "
          f"switches={bo['switches']} sheds={bo['sheds']}")
    rb = report["retry_budget"]
    print(f"  retry budget spent={rb['spent']} denied={rb['denied']} "
          f"available={rb['available']:.1f}")
    pf = report.get("prefix")
    if pf:
        print(f"  prefix  hits={pf['hits']} misses={pf['misses']} "
              f"hit_rate={pf['hit_rate']:.3f} "
              f"prefill_tokens_saved={pf['saved_tokens']}")
        print(f"  prefix  cow_forks={pf['cow_forks']} "
              f"shared_pages={pf['shared_pages']} "
              f"index_pages={pf['index_pages']} "
              f"evictions={pf['index_evictions']} "
              f"reclaimed={pf['reclaimed_pages']}")


def _print_fault_report(result: dict) -> None:
    """Human-readable tail for ``--fault-report``, routed through the obs
    metrics registry: link counters, link-health gauges, and recovery
    counters all land in one registry and print as ONE unified table
    (was three hand-formatted ones), plus the tier trail."""
    from .codecs.faults import flatten_counters
    from .obs.metrics import (MetricsRegistry, format_table,
                              record_link_counters, record_link_health,
                              record_recovery_counters)

    counters = result.get("link_counters")
    if not counters:
        print("fault report: no link counters recorded (faults were off)")
        return
    reg = MetricsRegistry(enabled=True)
    record_link_counters(counters, registry=reg)
    for k, total in flatten_counters(counters).items():
        reg.counter(f"edgellm_link_{k}_total").inc(total, hop="total")
    record_link_health(result.get("link_health"), registry=reg)
    record_recovery_counters((result.get("recovery") or {}).get("counters"),
                             registry=reg)
    print(format_table(reg, title="fault report (obs metrics registry)"))
    if result.get("tier_switches"):
        print(f"  tier switches: {result['tier_switches']} "
              f"(final tier {result.get('final_tier', 0)}, "
              f"{result.get('degraded_chunks', 0)} degraded chunk(s))")


def _print_trace_report(tracer) -> None:
    """Human-readable tail for ``--trace-report``: one block per request id
    showing the span tree the host tracer recorded — wall time, TTFT (first
    span start -> end of prefill), nested span durations, and every boundary
    hop's {cut, codec, wire bytes, ladder outcome} attribution line. Spans
    without a request id (warmup, eval sweeps) are counted but not listed."""
    events = tracer.to_chrome_trace()["traceEvents"]
    by_rid: dict = {}
    unattributed = 0
    for ev in events:
        rid = (ev.get("args") or {}).get("rid")
        if rid is None:
            unattributed += 1
        else:
            by_rid.setdefault(str(rid), []).append(ev)
    if not by_rid:
        print(f"trace report: no request-attributed spans "
              f"({unattributed} unattributed span(s); tracing off, or "
              f"nothing was submitted)")
        return

    def _order(rid: str):
        # "r12" sorts numerically, anything else lexically after
        tail = rid.lstrip("r")
        return (0, int(tail), rid) if tail.isdigit() else (1, 0, rid)

    print(f"trace report: {len(by_rid)} request(s), "
          f"{sum(len(v) for v in by_rid.values())} attributed span(s)"
          + (f", {unattributed} unattributed" if unattributed else ""))
    for rid in sorted(by_rid, key=_order):
        evs = sorted(by_rid[rid], key=lambda e: (e["ts"], -e["dur"]))
        t0 = min(e["ts"] for e in evs)
        wall_ms = (max(e["ts"] + e["dur"] for e in evs) - t0) / 1e3
        prefill = [e for e in evs if e["name"] == "generate.prefill"]
        head = f"  {rid}: {wall_ms:.2f} ms wall"
        if prefill:
            head += (f", ttft "
                     f"{(prefill[0]['ts'] + prefill[0]['dur'] - t0) / 1e3:.2f}"
                     f" ms")
        print(head)
        open_until: list = []  # end timestamps of still-open ancestors
        for e in evs:
            while open_until and e["ts"] >= open_until[-1]:
                open_until.pop()
            pad = "    " + "  " * len(open_until)
            a = dict(e.get("args") or {})
            a.pop("rid", None)
            if e["name"] == "split.hop":
                line = (f"hop {a.pop('hop', '?')}: "
                        f"cut={a.pop('cut', '?')} codec={a.pop('codec', '?')}"
                        f" wire_bytes={a.pop('wire_bytes', '?')} "
                        f"outcome={a.pop('outcome', '?')}")
            else:
                line = f"{e['name']} {e['dur'] / 1e3:.2f} ms"
            if a:
                line += " " + " ".join(f"{k}={a[k]}" for k in sorted(a))
            print(pad + line)
            open_until.append(e["ts"] + e["dur"])


def main(argv=None) -> int:
    from .utils.startup import configure_compile_cache, device_stamp

    configure_compile_cache()
    # --lint short-circuits before the parser: the graphlint gate needs no
    # params.json, and running it first means a contract violation is caught
    # before any experiment spends accelerator time (REPRODUCING §8)
    if "--lint" in (sys.argv[1:] if argv is None else argv):
        from .lint.__main__ import main as lint_main

        return lint_main(["--no-mypy"])
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--params", required=True, help="reference-style params.json")
    from .models import PRESETS

    ap.add_argument("--model", default="qwen2-0.5b", choices=sorted(PRESETS),
                    help="model preset")
    ap.add_argument("--corpus", help=".npy/.npz token ids or raw .txt (with --tokenizer); "
                                     "omitted -> synthetic corpus (smoke mode)")
    ap.add_argument("--tokenizer", help="local HF tokenizer path for raw-text corpora")
    ap.add_argument("--weights", help="local torch state_dict (.pt) or HF model dir; "
                                      "omitted -> random init (smoke mode)")
    ap.add_argument("--head-weights", help="LRP head weights .json (L x H) for weighted_importance")
    ap.add_argument("--output-dir", default=".")
    ap.add_argument("--max-chunks", type=int, help="stop after N chunks (smoke/CI)")
    ap.add_argument("--window-batch", type=int, default=8,
                    help="evaluation windows batched per forward in the token, "
                         "initial, channel, and split experiments (identical "
                         "accumulation; feeds the MXU; for split with a data "
                         "mesh axis, must be a multiple of its size)")
    ap.add_argument("--profile", metavar="DIR",
                    help="capture an XLA profiler trace of the experiment into "
                         "DIR (view with TensorBoard/Perfetto; includes "
                         "ppermute hops and Pallas codec kernels)")
    ap.add_argument("--checkpoint-every", type=int, default=1000)
    ap.add_argument("--deadline-s", type=float,
                    help="split experiment: per-chunk watchdog deadline in "
                         "seconds — a stalled eval writes a best-effort resume "
                         "checkpoint and exits with a typed DecodeTimeout "
                         "instead of hanging (overrides params.json "
                         "\"deadline\")")
    ap.add_argument("--metrics-out", metavar="PATH",
                    help="enable the obs metrics registry and write its final "
                         "snapshot to PATH after the experiment — Prometheus "
                         "text format for .prom/.txt, JSON otherwise "
                         "(REPRODUCING §10)")
    ap.add_argument("--trace-out", metavar="PATH",
                    help="enable host-side span tracing and write the Chrome "
                         "trace-event JSON to PATH (load at ui.perfetto.dev); "
                         "composes with --profile's XLA capture")
    ap.add_argument("--obs-port", type=int, metavar="PORT",
                    help="serve the live telemetry endpoint on "
                         "127.0.0.1:PORT for the duration of the run "
                         "(/metrics Prometheus text, /healthz JSON, "
                         "/snapshot.json, /trace Chrome JSON); 0 binds an "
                         "OS-assigned port, printed at startup; overrides "
                         "params.json observability.obs_port "
                         "(REPRODUCING §17)")
    ap.add_argument("--trace-report", action="store_true",
                    help="after the experiment, pretty-print per-request "
                         "span trees from the host tracer — wall time, TTFT, "
                         "and every boundary hop's {cut, codec, wire bytes, "
                         "ladder outcome} attribution; implies tracing")
    ap.add_argument("--serve-report", action="store_true",
                    help="serve experiment: after the soak, pretty-print the "
                         "outcome counts, reject/shed reasons, breaker "
                         "states, and the brownout/retry-budget posture")
    ap.add_argument("--fault-report", action="store_true",
                    help="split experiment: after the sweep, pretty-print the "
                         "summed per-hop link counters (detected / repaired / "
                         "retried / hedge wins / substituted), the tier trail, "
                         "and the link-health budget burn")
    ap.add_argument("--distributed", action="store_true",
                    help="join a multi-host run via jax.distributed.initialize() "
                         "before touching devices; split meshes become "
                         "slice-aware (stage/seq/model axes pinned within a "
                         "slice, only the data axis crosses DCN)")
    ap.add_argument("--lint", action="store_true",
                    help="run the graphlint static-analysis gate (AST rules "
                         "+ jaxpr contracts, python -m edgellm_tpu.lint) and "
                         "exit — handled before any other flag is required")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--synthetic-corpus-len", type=int, default=4096)
    args = ap.parse_args(argv)

    if args.distributed:
        from .parallel import initialize_distributed

        n_proc = initialize_distributed()
        print(f"distributed: process {__import__('jax').process_index()} "
              f"of {n_proc}", flush=True)

    if args.params.lstrip().startswith("{"):  # inline JSON (REPRODUCING.md)
        params_json = json.loads(args.params)
    else:
        with open(args.params) as f:
            params_json = json.load(f)
    _validate_params_json(params_json)

    def load_head_weights():
        if not args.head_weights:
            return None
        with open(args.head_weights) as f:
            return np.asarray(json.load(f))

    cfg, params = _load_model(args)
    corpus = _load_corpus(args, cfg.vocab_size)
    if corpus.max() >= cfg.vocab_size or corpus.min() < 0:
        raise SystemExit(f"corpus token ids outside [0, {cfg.vocab_size}) — wrong tokenizer?")
    os.makedirs(args.output_dir, exist_ok=True)
    out = lambda name: os.path.join(args.output_dir, name)

    import contextlib

    from .obs.tracing import trace_capture

    profile_cm = (trace_capture(args.profile) if args.profile
                  else contextlib.nullcontext())

    # --metrics-out / --trace-out arm the obs subsystem; a params.json
    # "observability" object picks the pillars (flags force their own pillar
    # on — asking for an output file implies wanting its contents)
    from . import obs

    obs_params = params_json.get("observability")
    if (args.metrics_out or args.trace_out or args.trace_report
            or args.obs_port is not None or obs_params is not None):
        ob_cfg = obs.ObservabilityConfig(**(obs_params or {}))
        if args.metrics_out or args.trace_out or args.trace_report:
            ob_cfg = dataclasses.replace(
                ob_cfg,
                metrics=ob_cfg.metrics or bool(args.metrics_out),
                tracing=(ob_cfg.tracing or bool(args.trace_out)
                         or args.trace_report))
        if args.obs_port is not None:
            try:
                ob_cfg = dataclasses.replace(ob_cfg, obs_port=args.obs_port)
            except ValueError as e:
                raise SystemExit(f"--obs-port: {e}")
        if ob_cfg.flight_recorder is True:
            # unnamed recorder: keep the post-mortems with the run's other
            # artifacts instead of littering the cwd
            ob_cfg = dataclasses.replace(
                ob_cfg,
                flight_recorder=os.path.join(args.output_dir,
                                             "flight_recorder"))
        obs.enable(ob_cfg)
        if ob_cfg.obs_port is not None:
            from .obs.server import get_global

            srv = get_global()
            if srv is not None:
                print(f"obs endpoint -> {srv.url}  "
                      f"(/metrics /healthz /snapshot.json /trace)",
                      flush=True)

    def _export_observability() -> None:
        if args.metrics_out:
            reg = obs.get_registry()
            text = (reg.to_prometheus()
                    if args.metrics_out.endswith((".prom", ".txt"))
                    else reg.to_json(indent=1))
            with open(args.metrics_out, "w") as f:
                f.write(text)
            print(f"metrics snapshot -> {args.metrics_out}", flush=True)
        if args.trace_out:
            obs.get_tracer().export(args.trace_out)
            print(f"chrome trace -> {args.trace_out}", flush=True)

    def _dispatch() -> int:
        stamp = device_stamp()  # names the device on every result line
        experiment = params_json.get("experiment", "")
        methods = params_json.get("methods", [])
        max_length = params_json.get("max_length", cfg.max_position_embeddings)
        stride = params_json.get("stride", 32)
        common = dict(
            max_length=max_length, stride=stride,
            checkpoint_path=out("sweep_checkpoint.json"),
            checkpoint_every=args.checkpoint_every,
            metrics_path=out("metrics.jsonl"),
            max_chunks=args.max_chunks,
            window_batch=max(args.window_batch, 1),
        )

        if experiment == "relevance":
            try:
                from .importance.relevance import run_relevance_extraction
            except ImportError as e:
                raise SystemExit(f"relevance extraction unavailable: {e}") from e

            stats: dict = {}
            weights = run_relevance_extraction(
                cfg, params, corpus, max_length=max_length, stride=stride,
                max_chunks=args.max_chunks,
                window_batch=max(args.window_batch, 1),
                checkpoint_path=out("relevance_checkpoint.json"),
                checkpoint_every=args.checkpoint_every,
                metrics_path=out("relevance_metrics.jsonl"),
                stats=stats)
            with open(out("attention_head_weights.json"), "w") as f:
                json.dump(np.asarray(weights).tolist(), f)
            print(json.dumps({"artifact": out("attention_head_weights.json"),
                              "shape": list(np.asarray(weights).shape),
                              **stats}))
            return 0

        if experiment == "distances":
            from .analysis import (layer_importance_distributions,
                                   pairwise_layer_distances, save_heatmap)

            # per-sample forwards like the notebook's per-line loop: a multi-array
            # .npz is one sample per array; a flat corpus splits into
            # non-overlapping max_length windows
            if args.corpus and args.corpus.endswith(".npz"):
                data = np.load(args.corpus)
                samples = [np.asarray(data[f]).reshape(-1) for f in data.files]
                for i, s in enumerate(samples):  # _load_corpus only checked files[0]
                    if s.size and (s.max() >= cfg.vocab_size or s.min() < 0):
                        raise SystemExit(f"npz sample {i} has token ids outside "
                                         f"[0, {cfg.vocab_size}) — wrong tokenizer?")
            else:
                samples = [corpus[i:i + max_length]
                           for i in range(0, len(corpus), max_length)]
            samples = [s for s in samples if len(s) >= 2]
            if args.max_chunks:
                samples = samples[: args.max_chunks]
            # clipping to bucketed lengths is opt-in (params key "max_compiles"):
            # the notebook analyzes every sample at native length, and silent
            # clipping would change the JS values it claims to reproduce
            max_compiles = params_json.get("max_compiles")
            dists = layer_importance_distributions(
                cfg, params, samples, max_compiles=max_compiles)
            matrix = pairwise_layer_distances(dists)
            artifact = {"matrix": [[None if not np.isfinite(v) else float(v) for v in row]
                                   for row in matrix],
                        "n_samples": len(samples), "model": args.model,
                        "max_compiles": max_compiles,
                        "clipped": max_compiles is not None and
                        len({int(s.shape[0]) for s in samples}) > max_compiles}
            with open(out("layer_distances.json"), "w") as f:
                json.dump(artifact, f, indent=1)
            heatmap_path = out("layer_distances.png")
            save_heatmap(matrix, heatmap_path)
            print(json.dumps({"artifact": out("layer_distances.json"),
                              "heatmap": heatmap_path, "n_samples": len(samples),
                              "layers": matrix.shape[0]}))
            return 0

        if experiment == "serve":
            import jax
            import jax.numpy as jnp

            from .serve.decode import generate, generate_split
            from .serve.frontend import ServeFront
            from .serve.soak import SoakConfig, run_soak
            from .utils.clock import FakeClock

            sv = params_json["serving"]
            front_cfg = _serve_front_config(sv)
            soak = SoakConfig(**sv.get("soak", {}))
            clock = FakeClock()
            rt = None
            link_health = None
            if "cuts" in params_json:
                from .codecs.faults import FaultConfig, LinkPolicy
                from .codecs.fec import (FECConfig, HedgeConfig, LinkHealth,
                                         LinkHealthConfig)
                from .parallel import make_stage_mesh
                from .parallel.split import (PipelineConfig, SplitConfig,
                                             SplitRuntime)

                n_stages = len(params_json["cuts"]) + 1
                n_dev = len(jax.devices())
                if n_dev < n_stages:
                    raise SystemExit(
                        f"experiment 'serve' with {n_stages} pipeline stages "
                        f"needs >= {n_stages} devices, found {n_dev}")
                lp = params_json.get("link_policy")
                rt = SplitRuntime(
                    cfg,
                    SplitConfig(cuts=tuple(params_json["cuts"]),
                                hop_codecs=tuple(params_json["hop_codecs"])),
                    make_stage_mesh(n_stages),
                    faults=(FaultConfig(**params_json["faults"])
                            if "faults" in params_json else None),
                    policy=(LinkPolicy(**{**lp,
                                          "tiers": tuple(lp.get("tiers", ()))})
                            if lp else None),
                    fec=(FECConfig(**params_json["fec"])
                         if "fec" in params_json else None),
                    hedge=(HedgeConfig(**params_json["hedge"])
                           if "hedge" in params_json else None),
                    pipeline=(PipelineConfig(**params_json["pipeline"])
                              if "pipeline" in params_json else None))
                if "link_health" in params_json:
                    link_health = LinkHealth(
                        config=LinkHealthConfig(**params_json["link_health"]),
                        clock=clock)
            if "batching" in params_json:
                # continuous-batching path: the front routes every admitted
                # request through ONE paged batcher event loop instead of
                # serial per-request generate calls (REPRODUCING §13); with
                # "cuts" the ragged step runs through the split pipeline's
                # quantized boundary hops (SplitRuntime.decode_step_paged)
                from .serve.batching import BatchingConfig, ContinuousBatcher
                from .serve.frontend import Request

                prefix_kw = {}
                if "prefix_cache" in params_json:
                    from .models.paged_kv import PrefixCacheConfig

                    prefix_kw = dict(prefix_cache=PrefixCacheConfig(
                        **params_json["prefix_cache"]))
                batching_json = dict(params_json["batching"])
                if "kv_at_rest" in params_json:
                    # the at-rest tier rides the batcher pool; with
                    # "pool_bytes" the page count is re-derived from the
                    # byte budget — quantized rows are smaller, so the same
                    # HBM holds more pages (the capacity multiplier)
                    from .models.paged_kv import num_pages_for_bytes

                    kq = params_json["kv_at_rest"]
                    prefix_kw["kv_codec"] = kq["codec"]
                    if "pool_bytes" in kq:
                        batching_json["num_pages"] = num_pages_for_bytes(
                            cfg, kq["pool_bytes"],
                            batching_json.get("page_size", 16),
                            kv_codec=kq["codec"])
                bcfg = BatchingConfig(**batching_json, **prefix_kw)
                split_kw = {}
                if rt is not None:
                    split_kw = dict(split_runtime=rt,
                                    placed_params=rt.place_params(params))
                dcfg = (_disagg_config(params_json["disagg"])
                        if "disagg" in params_json else None)

                def make_batcher():
                    # the disaggregated front mirrors the batcher surface
                    # (submit/run/report/discard), so everything downstream —
                    # ServeFront.drain_batched, the cluster replica factory —
                    # is agnostic to which one it drives
                    if dcfg is not None:
                        from .serve.disagg import DisaggServer

                        return DisaggServer(cfg, params, bcfg, dcfg,
                                            clock=clock, **split_kw)
                    return ContinuousBatcher(cfg, params, bcfg, **split_kw)

                if "cluster" in params_json:
                    # replica-router path (REPRODUCING §20): N continuous-
                    # batching fronts behind prefix-affinity placement; every
                    # replica shares the (already-compiled) step plan, so one
                    # warm run heats the whole fleet's jit cache
                    from .obs.metrics import record_cluster_stats
                    from .serve.cluster import ClusterFront
                    from .serve.frontend import Request

                    ccfg = _cluster_config(params_json["cluster"])
                    if "gray" in params_json:
                        ccfg = dataclasses.replace(
                            ccfg,
                            gray=_gray_config(params_json["gray"]))

                    def replica_factory(replica_id, generation):
                        return ServeFront(cfg, params, config=front_cfg,
                                          clock=clock, batcher=make_batcher())

                    cluster = ClusterFront(replica_factory, ccfg,
                                           clock=clock)
                    _attach_front_obs(cluster)
                    warm = ContinuousBatcher(cfg, params, bcfg, **split_kw)
                    warm.submit(np.ones((soak.prompt_len,), np.int32), 2,
                                temperature=soak.temperature)
                    warm.run()
                    rng = np.random.default_rng(soak.seed)
                    gaps = rng.exponential(1.0 / soak.arrival_rate,
                                           size=soak.n_requests)
                    shared_pfx = (rng.integers(
                        1, cfg.vocab_size,
                        size=soak.shared_prefix_len).astype(np.int32)
                        if soak.shared_prefix_len else None)
                    records = []
                    for i in range(soak.n_requests):
                        clock.advance(float(gaps[i]))
                        pi = rng.integers(1, cfg.vocab_size,
                                          size=soak.prompt_len
                                          ).astype(np.int32)
                        if shared_pfx is not None:
                            pi[:soak.shared_prefix_len] = shared_pfx
                        cluster.submit(Request(
                            prompt_ids=pi,
                            max_new_tokens=soak.max_new_tokens,
                            temperature=soak.temperature,
                            deadline_s=soak.deadline_s, rng_seed=i))
                    while True:
                        recs = cluster.drain()
                        if not recs:
                            break
                        records.extend(recs)
                    rep = cluster.report()
                    record_cluster_stats(rep)
                    outcomes = {}
                    for rec in records:
                        outcomes[rec.outcome] = (
                            outcomes.get(rec.outcome, 0) + 1)
                    artifact = {
                        "requests": len(records), "outcomes": outcomes,
                        "mode": (("disagg_" if dcfg is not None else "")
                                 + ("cluster_batched_split" if rt is not None
                                    else "cluster_batched")),
                        **stamp,
                        "cluster": rep,
                        "records": [r.as_dict() for r in records]}
                    with open(out("cluster_report.json"), "w") as f:
                        json.dump(artifact, f, indent=1, default=float)
                    print(json.dumps({
                        "requests": len(records), "outcomes": outcomes,
                        "mode": artifact["mode"], **stamp,
                        "replicas": len(rep["replicas"]),
                        "placements": rep["totals"],
                        "artifact": out("cluster_report.json")},
                        default=float))
                    if cluster.pending:
                        raise SystemExit(
                            f"cluster drain left {cluster.pending} accepted "
                            f"request(s) unterminated — the router lost "
                            f"work: {rep}")
                    return _batcher_failure_rc(records)
                batcher = make_batcher()
                front = ServeFront(cfg, params, config=front_cfg,
                                   clock=clock, batcher=batcher)
                _attach_front_obs(front)
                # warm the ragged step, the soak's prefill shape and its
                # sampler (greedy and sampled token 0 are different eager
                # ops) so compile time never lands on a request's clock
                t_warm = time.monotonic()
                warm = ContinuousBatcher(cfg, params, bcfg, **split_kw)
                warm.submit(np.ones((soak.prompt_len,), np.int32), 2,
                                temperature=soak.temperature)
                warm.run()
                warmup_s = time.monotonic() - t_warm
                rng = np.random.default_rng(soak.seed)
                gaps = rng.exponential(1.0 / soak.arrival_rate,
                                       size=soak.n_requests)
                # with shared_prefix_len every request opens with the SAME
                # seeded token block (a system prompt) — the workload the
                # prefix index turns into mapped pages instead of prefill
                shared_pfx = (rng.integers(
                    1, cfg.vocab_size,
                    size=soak.shared_prefix_len).astype(np.int32)
                    if soak.shared_prefix_len else None)
                for i in range(soak.n_requests):
                    clock.advance(float(gaps[i]))
                    pi = rng.integers(1, cfg.vocab_size,
                                      size=soak.prompt_len).astype(np.int32)
                    if shared_pfx is not None:
                        pi[:soak.shared_prefix_len] = shared_pfx
                    front.submit(Request(
                        prompt_ids=pi,
                        max_new_tokens=soak.max_new_tokens,
                        temperature=soak.temperature,
                        deadline_s=soak.deadline_s, rng_seed=i))
                t_drain = time.monotonic()
                records = front.drain_batched()
                drain_s = time.monotonic() - t_drain
                rep = batcher.report()
                outcomes: dict = {}
                for rec in records:
                    outcomes[rec.outcome] = outcomes.get(rec.outcome, 0) + 1
                # wall clocks (the soak's own clock is virtual): warmup_s is
                # the first call of every executable, compiles included;
                # drain_s is the soak itself, each step ended by a host sync
                artifact = {"requests": len(records), "outcomes": outcomes,
                            "mode": (("disagg_" if dcfg is not None else "")
                                     + ("batched_split" if rt is not None
                                        else "batched")),
                            **stamp,
                            "warmup_s": warmup_s, "drain_s": drain_s,
                            "batcher": rep,
                            "records": [r.as_dict() for r in records],
                            "tokens": [None if r.tokens is None
                                       else np.asarray(r.tokens)[0].tolist()
                                       for r in records]}
                with open(out("serve_report.json"), "w") as f:
                    json.dump(artifact, f, indent=1, default=float)
                pf = rep.get("prefix")
                print(json.dumps({
                    "requests": len(records), "outcomes": outcomes,
                    "mode": artifact["mode"], **stamp,
                    "batched_steps": rep["steps"],
                    "jit_misses": rep["jit_misses"],
                    "occupancy_mean": round(rep["alloc_util_mean"], 4),
                    "decode_tokens_per_s": round(
                        rep["decode_tokens_per_s"], 3),
                    **({"prefix_hit_rate": round(pf["hit_rate"], 4),
                        "prefill_tokens_saved": pf["saved_tokens"]}
                       if pf else {}),
                    **({"disagg_migrations": rep["disagg"]["migrations"],
                        "disagg_degraded": rep["disagg"]["degraded"]}
                       if rep.get("disagg") else {}),
                    "artifact": out("serve_report.json")}))
                if args.serve_report:
                    _print_serve_report(front.report())
                if pf and soak.shared_prefix_len and not pf["hits"]:
                    # the config promised a shared system prompt: an index
                    # that never hit means the sharing plane is broken, not
                    # that the workload had nothing to share
                    raise SystemExit(
                        f"prefix cache enabled with shared_prefix_len="
                        f"{soak.shared_prefix_len} but the radix index "
                        f"never hit: {pf}")
                return _batcher_failure_rc(records, front.batcher_failure)
            spec = None
            if "speculative" in params_json:
                from .serve.speculative import SpecConfig

                spec = SpecConfig(**params_json["speculative"])
            front = ServeFront(cfg, params, split_runtime=rt,
                               config=front_cfg, link_health=link_health,
                               clock=clock, speculative=spec)
            _attach_front_obs(front)
            # pre-warm the jit caches for the soak's one (batch, capacity)
            # plan: the virtual clock advances by measured service time, and
            # folding tens of compile-seconds into the first request would
            # distort every arrival after it
            cr = front_cfg.capacity_round
            capacity = -(-(soak.prompt_len + soak.max_new_tokens) // cr) * cr
            warm_ids = jnp.zeros((1, soak.prompt_len), jnp.int32)
            warm_kw = dict(capacity=capacity, temperature=soak.temperature,
                           rng_key=jax.random.key(0))
            generate(cfg, params, warm_ids, soak.max_new_tokens, **warm_kw)
            if rt is not None:
                if spec is not None and spec.enabled:
                    # the front bumps capacity the same way for spec bursts
                    warm_kw["capacity"] = max(
                        capacity, soak.prompt_len + soak.max_new_tokens
                        + spec.k - 2)
                generate_split(rt, rt.place_params(params), warm_ids,
                               soak.max_new_tokens, speculative=spec,
                               raw_params=params, **warm_kw)
            artifact = {**run_soak(front, soak, clock=clock),
                        **stamp}
            with open(out("serve_report.json"), "w") as f:
                json.dump(artifact, f, indent=1, default=float)
            print(json.dumps({
                "requests": artifact["requests"],
                "outcomes": artifact["outcomes"], **stamp,
                "goodput_tokens_per_s": round(
                    artifact["goodput_tokens_per_s"], 3),
                "slo_attainment": artifact["slo_attainment"],
                "p99_ttft_s": artifact["p99_ttft_s"],
                "token_identity_ok": (artifact["token_identity"] or
                                      {}).get("ok"),
                "artifact": out("serve_report.json")}, default=float))
            if args.serve_report:
                _print_serve_report(artifact["report"])
            return 0

        from .eval import run_token_sweep, run_initial_sweep, run_channel_sweep

        if experiment == "split":
            from .eval import run_split_eval
            from .parallel import make_stage_mesh

            # optional extra mesh axes: "n_data" shards the window batch
            # (window_batch must be a multiple), "n_model" tensor-parallelizes
            # each stage; default is one device per pipeline stage
            mesh = None
            n_stages = len(params_json["cuts"]) + 1
            if params_json.get("n_seq", 1) > 1 and (
                    params_json.get("n_data", 1) > 1
                    or params_json.get("n_model", 1) > 1):
                raise SystemExit(
                    "n_seq composes the pipeline with sequence sharding only; "
                    "combining it with n_data/n_model is not supported")
            if args.distributed:
                # slice-aware layout: stage/seq/model within a slice, data across
                from .parallel import (make_multihost_sp_stage_mesh,
                                       make_multihost_stage_mesh)

                if params_json.get("n_seq", 1) > 1:
                    mesh = make_multihost_sp_stage_mesh(
                        n_stages, params_json["n_seq"])
                else:
                    mesh = make_multihost_stage_mesh(
                        n_stages, n_data=params_json.get("n_data"),
                        n_model=params_json.get("n_model", 1))
            elif (params_json.get("n_data", 1) > 1
                  or params_json.get("n_model", 1) > 1):
                mesh = make_stage_mesh(n_stages,
                                       n_data=params_json.get("n_data", 1),
                                       n_model=params_json.get("n_model", 1))
            result = run_split_eval(
                cfg, params, corpus,
                cuts=params_json["cuts"],
                hop_codecs=params_json["hop_codecs"],
                max_length=max_length, stride=stride,
                importance_method=params_json.get("importance_method"),
                head_weights=load_head_weights(),
                max_chunks=args.max_chunks,
                mesh=mesh,
                window_batch=max(args.window_batch, 1),
                n_seq=params_json.get("n_seq", 1),
                checkpoint_path=out("split_checkpoint.json"),
                checkpoint_every=args.checkpoint_every,
                metrics_path=out("split_metrics.jsonl"),
                faults=params_json.get("faults"),
                link_policy=params_json.get("link_policy"),
                fec=params_json.get("fec"),
                hedge=params_json.get("hedge"),
                link_health=params_json.get("link_health"),
                deadline_s=(args.deadline_s if args.deadline_s is not None
                            else params_json.get("deadline")),
                stage_failure=params_json.get("stage_failure"),
                recovery=params_json.get("recovery"),
                pipeline=_pipeline_config(params_json))
            with open(out("split_eval_results.json"), "w") as f:
                json.dump(result, f, indent=1)
            print(json.dumps(result))
            if args.fault_report:
                _print_fault_report(result)
            return 0

        if experiment == "initial":
            result = run_initial_sweep(
                cfg, params, corpus, layers_of_interest=params_json["layers_of_interest"],
                ratios=params_json["ratios"], **common)
        elif methods and "channel" in methods[0]:
            result = run_channel_sweep(
                cfg, params, corpus, methods=methods,
                layers_of_interest=params_json["layers_of_interest"], **common)
        else:
            head_weights = load_head_weights()
            if head_weights is None and "weighted_importance" in methods:
                raise SystemExit("weighted_importance requires --head-weights "
                                 "(produce it with experiment: \"relevance\")")
            import jax

            if jax.default_backend() == "tpu" and common["window_batch"] > 1:
                # pre-shrink the window batch by AOT memory analysis (no
                # allocation) so big real-corpus runs degrade instead of
                # dying on the first launch (bench.py does the same)
                from .tools.wb_preflight import preflight_token_sweep_batch

                wb = preflight_token_sweep_batch(
                    cfg, common["window_batch"], max_length=max_length,
                    stride=stride,
                    layers_of_interest=params_json["layers_of_interest"],
                    ratios=params_json["ratios"],
                    dtype=next(iter(jax.tree_util.tree_leaves(params))).dtype)
                if wb != common["window_batch"]:
                    print(f"window_batch {common['window_batch']} exceeds the "
                          f"memory budget; running at {wb}", flush=True)
                    common["window_batch"] = wb
            result = run_token_sweep(
                cfg, params, corpus, methods=methods or ["regular_importance"],
                layers_of_interest=params_json["layers_of_interest"],
                ratios=params_json["ratios"], head_weights=head_weights, **common)

        with open(out("avg_ppl_results.json"), "w") as f:
            json.dump(result.to_json(), f, indent=1)
        print(result.table())
        print(json.dumps({"chunks": result.chunks, "n_tokens": result.n_tokens,
                          **stamp,
                          "wall_s": round(result.wall_s, 3),
                          "ppl": np.round(result.ppl(), 4).tolist()}))
        return 0

    with profile_cm:
        try:
            return _dispatch()
        finally:
            # export even when the experiment dies: a partial trace/snapshot
            # is exactly what a post-mortem needs
            _export_observability()
            if args.trace_report:
                _print_trace_report(obs.get_tracer())


if __name__ == "__main__":
    sys.exit(main())
