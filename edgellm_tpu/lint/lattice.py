"""Layer 4: latticelint — the AOT config-lattice verifier.

The other three graphlint layers check code (AST footguns, thread/lock
discipline, traced graph contracts). This layer checks the CONFIG lattice:
every ``configs/*.json`` must (1) pass run.py's params validator, (2)
AOT-lower its serve/eval entry points at tiny geometry — reusing the
``analysis.aot`` lower/compile/``memory_analysis()`` driver the window-batch
preflight ships on — with the measured peak held against the config's own
``"budget"`` block, (3) keep its KV/pool buffers donated in the lowered
executables (a dropped ``donate_argnums`` is a finding here, not a silent
2x HBM cost in production), and (4) have a ``configs/README.md`` table row.

On top of the shipped configs, the layer fuzzes the feature lattice
pairwise: every two-block combination of the serve/split feature set must
either validate AND lower, or be refused with the exact typed error
:data:`PAIR_ORACLE` pins — so a validator rule nobody tests ("refuses
spec + batching") cannot silently drift from what the builders actually
accept, in either direction.

Everything is static: ``.lower()`` traces, ``.compile()`` builds the
executable, ``memory_analysis()`` is a read — no model math executes and
no device memory is allocated (the same property the window-batch
preflight relies on). The whole sweep shares one compile
cache keyed by plan geometry, so the 27 configs plus ~80 fuzzed combos
resolve to a couple dozen distinct compiles.

The machine-readable side product is ``capability_matrix.json``
(:data:`MATRIX_SCHEMA`): per-config features, lowered entry points with
argument/output/temp bytes, donation map, and refusal reasons — the input
ROADMAP's boundary auto-planner consumes instead of deployment-time
profiling (MCAP in PAPERS.md measures at runtime; this is a lint
artifact).

Findings use the shared :class:`~edgellm_tpu.lint.report.Finding` shape
(rules ``LL-*``) so they merge into the same JSON/SARIF reports as the
other layers.
"""
from __future__ import annotations

import itertools
import json
import re
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional, Tuple

from .report import Finding

#: schema tag stamped into capability_matrix.json — bump on layout change
MATRIX_SCHEMA = "edgellm.capability_matrix/v1"

RULE_VALIDATE = "LL-validate"   # configs/*.json fails run.py validation
RULE_README = "LL-readme"       # configs/README.md drift (row <-> file)
RULE_LOWER = "LL-lower"         # an entry point fails to lower/compile
RULE_BUDGET = "LL-budget"       # missing budget block or peak over budget
RULE_DONATE = "LL-donate"       # lowered executable dropped a donation
RULE_COMPAT = "LL-compat"       # pairwise fuzz: validator/builder drift

#: lint-scale geometry, identical to the graph layer's (entrypoints.py) so
#: the two layers compile against the same tiny model
BATCH, SEQ, CAPACITY = 1, 8, 16
SPEC_K = 4                      # speculative verify window
SWEEP_W, SWEEP_S, SWEEP_TAIL = 2, 32, 9  # eval-sweep window batch/len/tail
MAX_TINY_PAGES = 64             # pool-page cap at lint scale (note on clamp)

# ---------------------------------------------------------------------------
# pairwise feature-composition oracle
# ---------------------------------------------------------------------------

_MSG_SPEC_BATCH = (
    "speculative runs the one-stream spec loop; the batcher's ragged step "
    "verifies one token per slot — drop 'speculative' or 'batching'")
_MSG_PIPE_SPEC = (
    "pipeline + speculative: the spec loop verifies one stream at a time "
    "(B == 1), leaving nothing to micro-batch — drop one of the two blocks")
_MSG_KVQ_PIPE = (
    "kv_at_rest + pipeline: quantized paged decode composes with the "
    "unpipelined split runtime only — drop 'pipeline' or use codec 'fp'")

#: every pair of feature blocks run.py REFUSES, with the exact message its
#: validator claims (``params.json: `` prefix stripped). Absent pairs must
#: validate AND lower. A validator edit that changes either direction
#: without updating this table is an LL-compat finding — that is the point.
PAIR_ORACLE: Dict[Tuple[str, str], str] = {
    ("batching", "speculative"): _MSG_SPEC_BATCH,
    ("cluster", "speculative"): _MSG_SPEC_BATCH,
    ("disagg", "speculative"): _MSG_SPEC_BATCH,
    ("gray", "speculative"): _MSG_SPEC_BATCH,
    ("kv_at_rest", "speculative"): _MSG_SPEC_BATCH,
    ("prefix_cache", "speculative"): _MSG_SPEC_BATCH,
    ("pipeline", "speculative"): _MSG_PIPE_SPEC,
    ("kv_at_rest", "pipeline"): _MSG_KVQ_PIPE,
}

#: minimal valid params block per feature, composed onto a bare serve config
FUZZ_BLOCKS: Dict[str, dict] = {
    "cuts": {"cuts": [2], "hop_codecs": ["int8_per_token"]},
    "faults": {"faults": {"drop_rate": 0.05, "seed": 0}},
    "fec": {"fec": {"enabled": True}},
    "hedge": {"hedge": {"routes": 2}},
    "pipeline": {"pipeline": {"num_microbatches": 2}},
    "speculative": {"speculative": {"k": 4}},
    "batching": {"batching": {"page_size": 8, "num_pages": 10,
                              "max_slots": 2, "pages_per_slot": 2}},
    "prefix_cache": {"prefix_cache": {"enabled": True}},
    "kv_at_rest": {"kv_at_rest": {"codec": "int8_per_channel"}},
    "cluster": {"cluster": {"num_replicas": 2}},
    "disagg": {"disagg": {"num_prefill_workers": 1}},
    "gray": {"gray": {"p95_multiple": 3.0}},
}

#: structural prerequisites a feature block cannot validate without —
#: pulled in silently when composing a combo (they are scaffolding, not
#: part of the pair under test)
FUZZ_DEPS: Dict[str, Tuple[str, ...]] = {
    "fec": ("faults",), "hedge": ("faults",),
    "pipeline": ("cuts",), "speculative": ("cuts",),
    "prefix_cache": ("batching",), "kv_at_rest": ("batching",),
    "cluster": ("batching",), "disagg": ("batching",),
    # dep expansion is one level deep, so gray names cluster's own
    # scaffolding explicitly
    "gray": ("cluster", "batching"),
}

FUZZ_BASE = {"experiment": "serve", "serving": {}}

#: params keys that count as composable features in the matrix
FEATURE_KEYS = (
    "cuts", "faults", "link_policy", "fec", "hedge", "link_health",
    "pipeline", "speculative", "serving", "batching", "prefix_cache",
    "kv_at_rest", "cluster", "disagg", "gray", "deadline", "stage_failure",
    "recovery", "n_seq")


def compose_combo(names: Tuple[str, ...]) -> dict:
    """Minimal serve params exercising exactly the feature blocks in
    ``names`` (plus their :data:`FUZZ_DEPS` scaffolding)."""
    p = dict(FUZZ_BASE)
    want = list(names)
    for n in names:
        for d in FUZZ_DEPS.get(n, ()):
            if d not in want:
                want.append(d)
    for n in want:
        for k, v in FUZZ_BLOCKS[n].items():
            p.setdefault(k, v)
    return p


def default_configs_dir() -> Path:
    """``<repo>/configs`` next to the installed package."""
    return Path(__file__).resolve().parents[2] / "configs"


def config_features(p: dict) -> List[str]:
    """The feature blocks a params dict composes, for the matrix."""
    return sorted(k for k in FEATURE_KEYS if k in p)


def _validate(p: dict) -> Optional[str]:
    """run.py's params validation -> None (ok) or the refusal message with
    the ``params.json: `` prefix stripped."""
    from ..run import _validate_params_json

    try:
        _validate_params_json(p)
        return None
    except SystemExit as e:
        msg = str(e)
        return msg[len("params.json: "):] if msg.startswith(
            "params.json: ") else msg


# ---------------------------------------------------------------------------
# README parity
# ---------------------------------------------------------------------------

def readme_parity_findings(configs_dir: Path) -> List[Finding]:
    """Every ``configs/*.json`` needs a README table row and vice versa."""
    readme = configs_dir / "README.md"
    where = str(readme)
    if not readme.exists():
        return [Finding(layer="lattice", rule=RULE_README, where=where,
                        line=0, message="configs/README.md is missing")]
    text = readme.read_text(encoding="utf-8")
    # only the first column of a TABLE row registers a config — prose and
    # description cells may mention produced artifacts or upstream files
    # ("attention_head_weights.json", "params.json") that are not configs
    cells = [ln.split("|")[1] for ln in text.splitlines()
             if ln.lstrip().startswith("|") and ln.count("|") >= 2]
    mentioned = set(re.findall(r"`([\w.\-]+\.json)`", "\n".join(cells)))
    present = {f.name for f in configs_dir.glob("*.json")}
    findings = []
    for name in sorted(present - mentioned):
        findings.append(Finding(
            layer="lattice", rule=RULE_README, where=where, line=0,
            message=f"configs/{name} has no README table row"))
    for name in sorted(mentioned - present):
        findings.append(Finding(
            layer="lattice", rule=RULE_README, where=where, line=0,
            message=f"README mentions `{name}` but configs/{name} does not "
                    f"exist"))
    return findings


# ---------------------------------------------------------------------------
# donation
# ---------------------------------------------------------------------------

def donation_findings(jitted_fn: Callable, args: tuple, required: int,
                      where: str) -> List[Finding]:
    """LL-donate findings for one lowered entry point: the executable must
    declare at least ``required`` donated input buffers (its KV cache /
    page-pool arrays). Unit-tested directly against a donation-stripped jit
    twin — the seeded missing-donation fixture."""
    from .contracts import donated_input_count

    donated = donated_input_count(jitted_fn, *args)
    if donated >= required:
        return []
    return [Finding(
        layer="lattice", rule=RULE_DONATE, where=where, line=0,
        message=f"lowered executable donates {donated} input buffer(s), "
                f"needs >= {required} (KV/pool buffers must alias their "
                f"outputs — a dropped donate_argnums doubles HBM)")]


# ---------------------------------------------------------------------------
# entry-point planning + AOT evaluation
# ---------------------------------------------------------------------------

class _Entry:
    """One lowerable entry point of a config's plan."""

    def __init__(self, name: str, key: str, build: Callable[[], dict]):
        self.name = name
        self.key = key      # compile-cache key (plan geometry signature)
        self.build = build  # -> {"cost": AOTCost|None, "donated", "required"}


class _Lattice:
    """Shared tiny-geometry world + compile cache for the whole sweep."""

    def __init__(self) -> None:
        import jax
        import jax.numpy as jnp

        from ..models import transformer
        from ..models.configs import tiny_config

        self.jax, self.jnp = jax, jnp
        self.cfg = tiny_config("qwen2", num_layers=4, hidden_size=32,
                               num_heads=4, vocab_size=128)
        self.params = transformer.init_params(self.cfg, jax.random.key(0))
        self.cache: Dict[str, dict] = {}

    # -- cache -------------------------------------------------------------

    def evaluate(self, entry: _Entry) -> dict:
        """Build (or fetch) one entry's AOT result. Errors are captured per
        entry — one broken config must not abort the sweep."""
        if entry.key in self.cache:
            return self.cache[entry.key]
        try:
            res = entry.build()
        except Exception as e:  # noqa: BLE001 — surfaced as LL-lower
            res = {"error": f"{type(e).__name__}: {e}"}
        self.cache[entry.key] = res
        return res

    def _result(self, lowered: Any, jitted: Optional[Callable],
                args: tuple, required: int) -> dict:
        from ..analysis.aot import lowered_cost
        from .contracts import donated_input_count

        cost = lowered_cost(lowered)
        donated = (donated_input_count(jitted, *args)
                   if jitted is not None and required > 0 else None)
        return {"cost": cost, "donated": donated, "required": required}

    # -- tiny-geometry mapping ----------------------------------------------

    def tiny_cuts(self, n: int) -> Tuple[int, ...]:
        """Map a config's cut count onto the 4-layer lint model (valid cut
        positions 0..2): the stage COUNT is what shapes the lowered graph."""
        return {1: (2,), 2: (1, 2)}.get(min(n, 3), (0, 1, 2))

    def tiny_layers(self, layers: Any) -> Tuple[int, ...]:
        """Clamp real-model layer indices into the tiny model's range."""
        out = sorted({min(max(int(l), 0), self.cfg.num_layers - 1)
                      for l in layers
                      if isinstance(l, int) and not isinstance(l, bool)})
        return tuple(out) or (1,)

    # -- local (single-device) serve entries ---------------------------------

    def entry_decode(self) -> List[_Entry]:
        jax, jnp = self.jax, self.jnp
        cfg, params = self.cfg, self.params

        def build_prefill():
            from ..serve import decode as serve_decode

            ids = jnp.zeros((BATCH, SEQ), jnp.int32)
            args = (cfg, params, ids, CAPACITY, None)
            return self._result(serve_decode._prefill_jit.lower(*args),
                                None, args, 0)

        def build_step():
            from ..models import transformer
            from ..serve import decode as serve_decode

            cache = transformer.init_cache(cfg, BATCH, CAPACITY)
            tok = jnp.zeros((BATCH,), jnp.int32)
            args = (cfg, params, cache, tok, jax.random.key(0), 0.0, None)
            return self._result(serve_decode._step_jit.lower(*args),
                                serve_decode._step_jit, args, 2)

        return [_Entry("decode.prefill", "local:prefill", build_prefill),
                _Entry("decode.step", "local:step", build_step)]

    def entry_prefill_suffix(self) -> List[_Entry]:
        jnp = self.jnp
        cfg, params = self.cfg, self.params

        def build():
            from ..models import transformer
            from ..serve import decode as serve_decode

            cache = transformer.init_cache(cfg, BATCH, CAPACITY)
            suffix = jnp.zeros((BATCH, 4), jnp.int32)
            args = (cfg, params, suffix, cache, None)
            return self._result(serve_decode._prefill_suffix_jit.lower(*args),
                                serve_decode._prefill_suffix_jit, args, 2)

        return [_Entry("decode.prefill_suffix", "local:prefill_suffix",
                       build)]

    def _pool_geom(self, p: dict, notes: List[str]) -> Tuple[int, int, int,
                                                             int, str]:
        """(max_slots, pages_per_slot, page_size, num_pages, kv_codec) at
        lint scale, derived the way run.py derives them — including the
        ``kv_at_rest.pool_bytes`` -> page-count conversion — then clamped."""
        b = p.get("batching", {})
        ms = int(b.get("max_slots", 4))
        pps = int(b.get("pages_per_slot", 8))
        pgs = int(b.get("page_size", 16))
        npg = int(b.get("num_pages", 65))
        kq = p.get("kv_at_rest", {})
        codec = kq.get("codec", "fp")
        if "pool_bytes" in kq:
            from ..models.paged_kv import num_pages_for_bytes

            npg = num_pages_for_bytes(self.cfg, kq["pool_bytes"], pgs,
                                      kv_codec=codec)
        if npg > MAX_TINY_PAGES:
            notes.append(f"pool clamped to {MAX_TINY_PAGES} pages at lint "
                         f"geometry (config asks for {npg})")
            npg = MAX_TINY_PAGES
        ms, pps, pgs = min(ms, 8), min(pps, 8), min(pgs, 16)
        return ms, pps, pgs, max(npg, 2), codec

    def entry_batched(self, p: dict, notes: List[str]) -> List[_Entry]:
        jnp = self.jnp
        cfg, params = self.cfg, self.params
        ms, pps, pgs, npg, codec = self._pool_geom(p, notes)
        key = f"batched:{ms}:{pps}:{pgs}:{npg}:{codec}"

        def build():
            from ..models import paged_kv
            from ..serve import batching

            tab = jnp.zeros((ms, pps), jnp.int32)
            lens = jnp.zeros((ms,), jnp.int32)
            toks = jnp.zeros((ms,), jnp.int32)
            keys = jnp.tile(batching._key_data(0), (ms, 1))
            steps = jnp.zeros((ms,), jnp.int32)
            temps = jnp.zeros((ms,), jnp.float32)
            pool = (paged_kv.init_pool(cfg, npg, pgs) if codec == "fp"
                    else paged_kv.init_quant_pool(cfg, npg, pgs, codec))
            args = (cfg, params, pool, tab, lens, toks, keys, steps, temps,
                    None)
            return self._result(batching._batched_step_jit.lower(*args),
                                batching._batched_step_jit, args, len(pool))

        name = "batched.step" if codec == "fp" else "batched.step_quant"
        return [_Entry(name, key, build)]

    # -- split runtime entries ----------------------------------------------

    def _split_notes(self, p: dict, notes: List[str]) -> None:
        """Plan-time notes about how a split config maps to lint geometry
        (the builders run behind the compile cache, so notes cannot come
        from them)."""
        from ..eval.split_eval import parse_hop_codec

        cuts = self.tiny_cuts(len(p["cuts"]))
        for spec in list(p["hop_codecs"])[:len(cuts)]:
            try:
                parse_hop_codec(spec, 1)
            except (ValueError, KeyError):
                notes.append(f"hop codec {spec!r} has no n_seq=1 form at "
                             f"lint geometry; lowered as int8_per_token")
        if p.get("n_seq", 1) > 1:
            notes.append(f"stage x seq ring (n_seq={p['n_seq']}) lowered as "
                         f"its n_seq=1 twin")

    def _split_runtime(self, p: dict):
        """Tiny-geometry :class:`SplitRuntime` mirroring the config's plan:
        same stage count, codec family, link ladder and µ-batch schedule."""
        from ..codecs.faults import FaultConfig, LinkPolicy
        from ..eval.split_eval import parse_hop_codec
        from ..parallel.split import (PipelineConfig, SplitConfig,
                                      SplitRuntime, make_stage_mesh)

        cuts = self.tiny_cuts(len(p["cuts"]))
        codecs = []
        for spec in list(p["hop_codecs"])[:len(cuts)]:
            try:
                codecs.append(parse_hop_codec(spec, 1))
            except (ValueError, KeyError):
                codecs.append("int8_per_token")
        while len(codecs) < len(cuts):
            codecs.append(codecs[-1] if codecs else "int8_per_token")
        lp = p.get("link_policy")
        n_micro = 0
        if "pipeline" in p:
            n_micro = min(int(p["pipeline"].get("num_microbatches", 2)), 2)
        rt = SplitRuntime(
            self.cfg,
            SplitConfig(cuts=cuts, hop_codecs=tuple(codecs)),
            make_stage_mesh(len(cuts) + 1),
            faults=(FaultConfig(**p["faults"]) if "faults" in p else None),
            policy=(LinkPolicy(**{**lp, "tiers": tuple(lp.get("tiers", ()))})
                    if lp else None),
            fec=(self._fec(p) if "fec" in p else None),
            hedge=(self._hedge(p) if "hedge" in p else None),
            pipeline=(PipelineConfig(num_microbatches=n_micro)
                      if n_micro else None))
        return rt, n_micro

    def _fec(self, p: dict):
        from ..codecs.fec import FECConfig

        return FECConfig(**p["fec"])

    def _hedge(self, p: dict):
        from ..codecs.fec import HedgeConfig

        return HedgeConfig(**p["hedge"])

    def _split_key(self, p: dict, what: str) -> str:
        sig = {k: p[k] for k in ("cuts", "hop_codecs", "faults",
                                 "link_policy", "fec", "hedge", "pipeline",
                                 "n_seq", "batching",
                                 "kv_at_rest", "speculative") if k in p}
        return f"split:{what}:{json.dumps(sig, sort_keys=True)}"

    def entry_split_eval(self, p: dict, notes: List[str]) -> List[_Entry]:
        """experiment "split": the boundary-sweep forward."""
        jnp = self.jnp

        def build():
            rt, n_micro = self._split_runtime(p)
            bat = max(BATCH, n_micro)
            ids = jnp.zeros((bat, SEQ), jnp.int32)
            imps = jnp.zeros((len(rt.codecs), SEQ), jnp.float32)
            args = ((rt.place_params(self.params), ids, imps)
                    if rt._link is None else
                    (rt.place_params(self.params), ids, imps,
                     jnp.asarray(0, jnp.int32)))
            return self._result(rt._forward.lower(*args), None, args, 0)

        return [_Entry("split.forward", self._split_key(p, "forward"),
                       build)]

    def entry_split_decode(self, p: dict, notes: List[str],
                           speculative: bool) -> List[_Entry]:
        """Serve-path split pipeline: prefill + donated decode step, plus the
        k-token verify burst when the config speculates."""
        jnp = self.jnp
        entries = []

        def mk_state(rt, n_micro):
            bat = max(BATCH, n_micro)
            kv_shape = (rt.split.n_stages, rt.stage_size, bat, CAPACITY,
                        self.cfg.num_kv_heads, self.cfg.head_dim)
            placed = rt.place_params(self.params)
            return (placed, jnp.zeros(kv_shape, jnp.float32),
                    jnp.zeros(kv_shape, jnp.float32),
                    jnp.asarray(SEQ, jnp.int32),
                    jnp.zeros((bat,), jnp.int32))

        def build_prefill():
            rt, n_micro = self._split_runtime(p)
            prefill_fn, _ = rt._decode_fns(CAPACITY)
            ids = jnp.zeros((max(BATCH, n_micro), SEQ), jnp.int32)
            placed = rt.place_params(self.params)
            args = ((placed, ids) if rt._link is None
                    else (placed, ids, jnp.asarray(0, jnp.int32)))
            return self._result(prefill_fn.lower(*args), None, args, 0)

        def build_step():
            rt, n_micro = self._split_runtime(p)
            _, step_fn = rt._decode_fns(CAPACITY)
            args = mk_state(rt, n_micro)
            return self._result(step_fn.lower(*args), step_fn, args, 2)

        entries.append(_Entry("split.prefill",
                              self._split_key(p, "prefill"), build_prefill))
        entries.append(_Entry("split.decode_step",
                              self._split_key(p, "step"), build_step))
        if speculative:
            def build_verify():
                rt, n_micro = self._split_runtime(p)
                verify_fn = rt._verify_fns(CAPACITY, SPEC_K)
                placed, k_c, v_c, length, _ = mk_state(rt, 0)
                vtoks = jnp.zeros((BATCH, SPEC_K), jnp.int32)
                args = (placed, k_c, v_c, length, vtoks)
                return self._result(verify_fn.lower(*args), verify_fn,
                                    args, 2)

            entries.append(_Entry("split.verify_step",
                                  self._split_key(p, "verify"),
                                  build_verify))
        return entries

    def entry_split_paged(self, p: dict, notes: List[str]) -> List[_Entry]:
        """Serve-path split pipeline behind the continuous batcher: the
        ragged paged decode step over per-stage pools."""
        jnp = self.jnp
        ms, pps, pgs, npg, codec = self._pool_geom(p, notes)

        def build():
            rt, n_micro = self._split_runtime(p)
            pstep = rt._paged_decode_fns(npg, pgs, kv_codec=codec)
            pool = rt.init_paged_pool(npg, pgs, kv_codec=codec)
            placed = rt.place_params(self.params)
            tab = jnp.zeros((ms, pps), jnp.int32)
            lens = jnp.zeros((ms,), jnp.int32)
            toks = jnp.zeros((ms,), jnp.int32)
            args = (placed, pool, tab, lens, toks)
            return self._result(pstep.lower(*args), pstep, args, len(pool))

        return [_Entry("split.decode_step_paged",
                       self._split_key(p, f"paged:{ms}:{pps}:{pgs}:{npg}"),
                       build)]

    # -- eval-sweep entries ---------------------------------------------------

    def entry_sweep(self, p: dict, notes: List[str]) -> List[_Entry]:
        """Token/channel/initial/last_row sweeps: the stats forward + the
        ratio-vmapped suffix sweep — the same two executables the window-
        batch preflight sizes, at lint geometry."""
        jax, jnp = self.jax, self.jnp
        cfg = self.cfg
        layers = self.tiny_layers(p.get("layers_of_interest", (1,)))
        ratios = [r for r in p.get("ratios", []) or [0.25]]
        codec = "int4_token_select"
        key_base = f"sweep:{layers}:{len(ratios)}"

        def params_shape():
            from ..models import init_params

            return jax.eval_shape(
                lambda k: init_params(cfg, k, dtype=jnp.float32),
                jax.random.key(0))

        def build_stats():
            from ..eval.harness import DEDUP_ZERO_CODECS, _stats_forward

            ids = jax.ShapeDtypeStruct((SWEEP_W, SWEEP_S), jnp.int32)
            lowered = _stats_forward(
                cfg, layers,
                want_final=codec in DEDUP_ZERO_CODECS).lower(params_shape(),
                                                             ids)
            return self._result(lowered, None, (), 0)

        def build_suffix():
            from ..eval.harness import DEDUP_ZERO_CODECS, _suffix_sweep

            n_ratios = (max(1, sum(1 for r in ratios if float(r) != 0.0))
                        if codec in DEDUP_ZERO_CODECS
                        else max(1, len(ratios)))
            hidden = jax.ShapeDtypeStruct((SWEEP_W, SWEEP_S,
                                           cfg.hidden_size), jnp.float32)
            targets = jax.ShapeDtypeStruct((SWEEP_W, SWEEP_S), jnp.int32)
            imp = jax.ShapeDtypeStruct((SWEEP_W, SWEEP_S), jnp.float32)
            rr = jax.ShapeDtypeStruct((n_ratios,), jnp.float32)
            ks = jax.ShapeDtypeStruct((n_ratios,), jnp.int32)
            lowered = _suffix_sweep(cfg, min(layers), codec,
                                    SWEEP_TAIL).lower(
                params_shape(), hidden, targets, imp, rr, ks)
            return self._result(lowered, None, (), 0)

        return [_Entry("eval.stats_forward", key_base + ":stats",
                       build_stats),
                _Entry("eval.suffix_sweep", key_base + ":suffix",
                       build_suffix)]

    def entry_relevance(self) -> List[_Entry]:
        jax, jnp = self.jax, self.jnp
        cfg = self.cfg

        def build():
            from ..importance.relevance import _chunk_relevance
            from ..models import init_params

            ps = jax.eval_shape(
                lambda k: init_params(cfg, k, dtype=jnp.float32),
                jax.random.key(0))
            ids = jax.ShapeDtypeStruct((SWEEP_W, SWEEP_S), jnp.int32)
            return self._result(_chunk_relevance(cfg).lower(ps, ids),
                                None, (), 0)

        return [_Entry("eval.relevance", "relevance", build)]

    # -- the plan -------------------------------------------------------------

    def plan(self, p: dict) -> Tuple[List[_Entry], List[str]]:
        """Entry points a validated params dict would compile, at lint
        geometry. Mirrors run.py's serve/eval dispatch."""
        notes: List[str] = []
        exp = p.get("experiment", "")
        if exp == "serve":
            entries: List[_Entry] = []
            has_cuts, has_batch = "cuts" in p, "batching" in p
            spec = "speculative" in p and p["speculative"].get("enabled",
                                                               True)
            if "faults" in p and not has_cuts:
                notes.append("faults/link config without cuts: the local "
                             "decode path has no boundary link to fault")
            if has_cuts:
                self._split_notes(p, notes)
            if has_cuts and has_batch:
                entries += self.entry_split_paged(p, notes)
            elif has_cuts:
                entries += self.entry_split_decode(p, notes, spec)
            elif has_batch:
                entries += self.entry_batched(p, notes)
                entries += self.entry_decode()
                if "prefix_cache" in p:
                    entries += self.entry_prefill_suffix()
            else:
                entries += self.entry_decode()
            for host_side in ("cluster", "disagg", "gray"):
                if host_side in p:
                    notes.append(f"{host_side} is host-side orchestration: "
                                 f"its replicas/workers compile the entry "
                                 f"points above")
            return entries, notes
        if exp == "split":
            self._split_notes(p, notes)
            return self.entry_split_eval(p, notes), notes
        if exp == "relevance":
            return self.entry_relevance(), notes
        if exp == "distances":
            notes.append("distances sweeps compile per replan candidate; "
                         "no fixed entry point to pin at lint geometry")
            return [], notes
        # "", "initial", "last_row": the token/channel sweep family
        return self.entry_sweep(p, notes), notes


# ---------------------------------------------------------------------------
# the sweep
# ---------------------------------------------------------------------------

def _config_record(world: _Lattice, name: str, p: dict,
                   findings: List[Finding], where: str,
                   budget_required: bool) -> dict:
    """Verify one config (lower + budget + donation) and build its matrix
    row, appending findings in place."""
    record: Dict[str, Any] = {
        "features": config_features(p),
        "experiment": p.get("experiment", "") or "token_sweep",
        "valid": True, "refusal": None,
        "entrypoints": {}, "donation": {}, "notes": [],
        "peak_bytes": None, "budget_bytes": None,
    }
    entries, notes = world.plan(p)
    record["notes"] = notes
    peak = 0
    for entry in entries:
        res = world.evaluate(entry)
        if "error" in res:
            findings.append(Finding(
                layer="lattice", rule=RULE_LOWER, where=where, line=0,
                message=f"{entry.name}: failed to lower/compile at lint "
                        f"geometry: {res['error']}"))
            record["entrypoints"][entry.name] = {"error": res["error"]}
            continue
        cost = res["cost"]
        if cost is None:
            findings.append(Finding(
                layer="lattice", rule=RULE_LOWER, where=where, line=0,
                message=f"{entry.name}: compiler proved the program "
                        f"over-HBM at lint geometry"))
            record["entrypoints"][entry.name] = {"over_hbm": True}
            continue
        record["entrypoints"][entry.name] = cost.as_dict()
        peak = max(peak, cost.total)
        if res["required"]:
            record["donation"][entry.name] = {
                "donated": res["donated"], "required": res["required"]}
            if res["donated"] < res["required"]:
                findings.append(Finding(
                    layer="lattice", rule=RULE_DONATE, where=where, line=0,
                    message=f"{entry.name}: lowered executable donates "
                            f"{res['donated']} input buffer(s), needs >= "
                            f"{res['required']} (KV/pool buffers must alias "
                            f"their outputs)"))
    record["peak_bytes"] = peak if entries else None
    budget = p.get("budget")
    if budget is None:
        if budget_required:
            findings.append(Finding(
                layer="lattice", rule=RULE_BUDGET, where=where, line=0,
                message='missing "budget" block: every shipped config pins '
                        'its lint-geometry AOT peak ({"aot_peak_bytes": N})'))
    else:
        record["budget_bytes"] = budget["aot_peak_bytes"]
        if entries and peak > budget["aot_peak_bytes"]:
            findings.append(Finding(
                layer="lattice", rule=RULE_BUDGET, where=where, line=0,
                message=f"AOT peak {peak} bytes exceeds the config's budget "
                        f"of {budget['aot_peak_bytes']} bytes at lint "
                        f"geometry"))
    return record


def _pair_sweep(world: _Lattice, findings: List[Finding],
                pair_oracle: Dict[Tuple[str, str], str]) -> dict:
    """Pairwise feature-composition fuzz against :data:`PAIR_ORACLE`."""
    names = sorted(FUZZ_BLOCKS)
    combos = ([(n,) for n in names]
              + list(itertools.combinations(names, 2)))
    pairs: Dict[str, Any] = {}
    where = "lint/lattice.py:pairwise"
    for combo in combos:
        label = "+".join(combo)
        p = compose_combo(combo)
        got = _validate(p)
        want = pair_oracle.get(tuple(combo))
        pairs[label] = {"ok": got is None, "refusal": got}
        if got != want:
            if want is None:
                msg = (f"combo {label} should validate but run.py refuses "
                       f"it: {got}")
            elif got is None:
                msg = (f"combo {label} should be refused ({want!r}) but "
                       f"run.py accepts it")
            else:
                msg = (f"combo {label} is refused with a different message "
                       f"than the oracle pins: got {got!r}, want {want!r}")
            findings.append(Finding(layer="lattice", rule=RULE_COMPAT,
                                    where=where, line=0, message=msg))
            continue
        if got is not None:
            continue
        # accepted combos must also BUILD and LOWER — the builder half of
        # validator/builder drift (a validator that waves through what the
        # runtime constructors refuse)
        entries, _ = world.plan(p)
        for entry in entries:
            res = world.evaluate(entry)
            if "error" in res:
                findings.append(Finding(
                    layer="lattice", rule=RULE_COMPAT, where=where, line=0,
                    message=f"combo {label} validates but {entry.name} "
                            f"refuses to build/lower: {res['error']}"))
                pairs[label]["ok"] = False
                pairs[label]["build_error"] = res["error"]
                break
    return pairs


def run_lattice_checks(
        configs_dir: Optional[Path] = None,
        pair_oracle: Optional[Dict[Tuple[str, str], str]] = None,
        budget_required: bool = True,
        pairwise: bool = True,
) -> Tuple[List[Finding], List[str], List[str], dict]:
    """Run the whole lattice sweep.

    Returns ``(findings, checked, skipped, capability_matrix)`` — the first
    three in the shape the other layers use, the fourth the
    :data:`MATRIX_SCHEMA` document for ``capability_matrix.json``.

    ``configs_dir``/``pair_oracle``/``budget_required`` exist for the
    seeded-fixture tests; production callers take the defaults.
    """
    configs_dir = Path(configs_dir) if configs_dir else default_configs_dir()
    pair_oracle = PAIR_ORACLE if pair_oracle is None else pair_oracle
    findings: List[Finding] = []
    checked: List[str] = []
    skipped: List[str] = []

    world = _Lattice()
    if len(world.jax.devices()) < 4:
        skipped.append("lattice split-runtime entries: needs >= 4 devices "
                       "(set XLA_FLAGS=--xla_force_host_platform_device_"
                       "count=8)")

    readme = readme_parity_findings(configs_dir)
    findings.extend(readme)
    if not readme:
        checked.append("lattice.readme-parity")

    matrix: Dict[str, Any] = {
        "schema": MATRIX_SCHEMA,
        "tiny_geometry": {
            "model": "qwen2-tiny", "num_layers": world.cfg.num_layers,
            "hidden_size": world.cfg.hidden_size,
            "num_heads": world.cfg.num_heads,
            "num_kv_heads": world.cfg.num_kv_heads,
            "vocab_size": world.cfg.vocab_size,
            "batch": BATCH, "seq": SEQ, "capacity": CAPACITY,
            "sweep_window": [SWEEP_W, SWEEP_S],
        },
        "configs": {}, "pairs": {},
    }

    for path in sorted(configs_dir.glob("*.json")):
        where = str(path)
        try:
            p = json.loads(path.read_text(encoding="utf-8"))
        except (OSError, ValueError) as e:
            findings.append(Finding(
                layer="lattice", rule=RULE_VALIDATE, where=where, line=0,
                message=f"unreadable config: {e}"))
            continue
        refusal = _validate(p)
        if refusal is not None:
            findings.append(Finding(
                layer="lattice", rule=RULE_VALIDATE, where=where, line=0,
                message=f"run.py refuses this config: {refusal}"))
            matrix["configs"][path.stem] = {
                "features": config_features(p), "valid": False,
                "refusal": refusal, "entrypoints": {}, "donation": {},
                "notes": [], "peak_bytes": None, "budget_bytes": None,
                "experiment": p.get("experiment", "") or "token_sweep",
            }
            continue
        before = len(findings)
        matrix["configs"][path.stem] = _config_record(
            world, path.stem, p, findings, where, budget_required)
        if len(findings) == before:
            checked.append(f"lattice.config:{path.stem}")

    if pairwise:
        before = len(findings)
        matrix["pairs"] = _pair_sweep(world, findings, pair_oracle)
        if len(findings) == before:
            checked.append("lattice.pairwise-compat")

    return findings, checked, skipped, matrix


def write_matrix(matrix: dict, path: str) -> None:
    with open(path, "w", encoding="utf-8") as f:
        json.dump(matrix, f, indent=1, sort_keys=True)
        f.write("\n")
