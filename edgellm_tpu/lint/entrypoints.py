"""Layer 2 drivers: trace the REAL entry points and verify their declared
graph contracts.

Each driver builds test-scale example inputs (tiny qwen2 config, 2-stage
mesh on the spoofed CPU device grid), abstract-evals the production
function with ``jax.make_jaxpr``/``.lower()``, and hands the traced graph
to :mod:`edgellm_tpu.lint.contracts`. Nothing here executes model math —
tracing and lowering only, so the whole layer runs in seconds under
``JAX_PLATFORMS=cpu``.

The *declarations* live on the production code (``@graph_contract`` in
``models/transformer.py``, ``serve/decode.py``, ``parallel/split.py``,
``codecs/faults.py``); this module only knows how to build inputs and the
measured ``ctx`` facts (payload leaf counts, hop byte totals from the codec
registry) that parameterize them.
"""
from __future__ import annotations

from typing import Callable, List, Optional, Tuple

import numpy as np

from .contracts import GRAPH_CONTRACTS, check_identity, check_traced
from .report import Finding

#: example-input scale: big enough to exercise GQA + a real cut, small
#: enough that tracing every contract stays in seconds
BATCH, SEQ, CAPACITY = 1, 8, 16


def _missing(name: str) -> Finding:
    return Finding(layer="graph", rule="GC-missing", where=name, line=0,
                   message="entry point has no @graph_contract registration "
                           "(decorator removed or module not imported)")


def _driver_error(name: str, exc: Exception) -> Finding:
    return Finding(layer="graph", rule="GC-driver", where=name, line=0,
                   message=f"contract driver failed: "
                           f"{type(exc).__name__}: {exc}")


def _payload_info(codec, shape) -> Tuple[int, set, int]:
    """(leaf count, dtype names, total bytes) of one hop's wire payload,
    measured abstractly from the codec itself."""
    import jax
    import jax.numpy as jnp

    spec = jax.eval_shape(codec.encode, jax.ShapeDtypeStruct(shape,
                                                             jnp.float32))
    leaves = jax.tree_util.tree_leaves(spec)
    nbytes = sum(int(np.prod(a.shape)) * a.dtype.itemsize for a in leaves)
    return len(leaves), {a.dtype.name for a in leaves}, nbytes


def run_graph_checks() -> Tuple[List[Finding], List[str], List[str]]:
    """Run every registered graph contract against real traced graphs.

    Returns (findings, names of contracts verified clean, skip notes)."""
    import jax
    import jax.numpy as jnp

    # importing the production modules is what populates GRAPH_CONTRACTS
    from ..codecs.faults import COUNTER_KEYS, FaultConfig, LinkPolicy
    from ..models import transformer
    from ..models.configs import tiny_config
    from ..parallel.split import (PipelineConfig, SplitConfig, SplitRuntime,
                                  make_stage_mesh)
    from ..serve import decode as serve_decode
    from ..serve import recovery

    findings: List[Finding] = []
    checked: List[str] = []
    skipped: List[str] = []

    def run_one(name: str, traced: Callable, args: tuple,
                ctx: Optional[dict] = None, lowerable: Optional[Callable] = None,
                lower_args: Optional[tuple] = None) -> None:
        contract = GRAPH_CONTRACTS.get(name)
        if contract is None:
            findings.append(_missing(name))
            return
        try:
            found = check_traced(contract, traced, args, ctx,
                                 lowerable=lowerable, lower_args=lower_args)
        except Exception as e:  # noqa: BLE001 — a crashed driver must be loud
            findings.append(_driver_error(name, e))
            return
        if found:
            findings.extend(found)
        else:
            checked.append(name)

    cfg = tiny_config("qwen2", num_layers=4, hidden_size=32, num_heads=4,
                      vocab_size=128)
    params = transformer.init_params(cfg, jax.random.key(0))
    ids = jnp.zeros((BATCH, SEQ), jnp.int32)
    tok = jnp.zeros((BATCH,), jnp.int32)

    # ---- transformer core: prefill / decode_step (collective-free, no f64,
    # ---- no host callbacks) --------------------------------------------
    run_one("transformer.prefill",
            lambda p, i: transformer.prefill(cfg, p, i, CAPACITY),
            (params, ids))
    cache = transformer.init_cache(cfg, BATCH, CAPACITY)
    run_one("transformer.decode_step",
            lambda p, c, t: transformer.decode_step(cfg, p, c, t),
            (params, cache, tok))

    # ---- serve layer: the jitted generate() internals; the step contract
    # ---- also requires the KV cache to be donated in the lowered
    # ---- executable -----------------------------------------------------
    key = jax.random.key(0)
    run_one("decode.prefill",
            lambda p, i: serve_decode._prefill_impl(cfg, p, i, CAPACITY, None),
            (params, ids))
    run_one("decode.step",
            lambda p, c, t, k: serve_decode._step_impl(cfg, p, c, t, k, 0.0,
                                                       None),
            (params, cache, tok, key),
            ctx={"donate_min": 2},
            lowerable=serve_decode._step_jit,
            lower_args=(cfg, params, cache, tok, key, 0.0, None))

    # recovery must add NOTHING to the decode graph: the LocalRuntime step is
    # the raw transformer decode_step, bit-identical
    ident = check_identity(
        "decode.recovery-identity",
        lambda p, c, t: recovery._local_step.__wrapped__(cfg, p, c, t, None),
        (params, cache, tok),
        lambda p, c, t: transformer.decode_step(cfg, p, c, t,
                                                compute_dtype=None),
        (params, cache, tok),
        what="LocalRuntime (recovery failover) decode graph")
    (findings.extend(ident) if ident
     else checked.append("decode.recovery-identity"))

    # the serving front must add NOTHING either: a default-config ServeFront
    # routes admitted requests through the direct generate() loop, so the
    # decode step it traces — with the front's own bucketed capacity and
    # static args — is byte-identical to calling generate directly
    from ..serve.frontend import ServeFront

    front = ServeFront(cfg, params)
    spec = front.step_trace_spec(BATCH, SEQ, max_new_tokens=CAPACITY - SEQ)
    if spec["uses_survivable_loop"]:
        findings.append(Finding(
            layer="graph", rule="GC-identity",
            where="frontend.decode-step-identity", line=0,
            message="default-config ServeFront routes decode through the "
                    "survivable loop instead of the direct generate path"))
    front_cache = transformer.init_cache(cfg, BATCH, spec["capacity"])
    ident = check_identity(
        "frontend.decode-step-identity",
        lambda p, c, t, k: serve_decode._step_impl(
            cfg, p, c, t, k, spec["temperature"], spec["compute_dtype"]),
        (params, front_cache, tok, key),
        lambda p, c, t, k: serve_decode._step_impl(cfg, p, c, t, k, 0.0,
                                                   None),
        (params, front_cache, tok, key),
        what="default-config ServeFront decode-step graph")
    (findings.extend(ident) if ident
     else checked.append("frontend.decode-step-identity"))

    # ---- paged KV: the ragged continuous-batching step (collective-free;
    # ---- the pool buffers must stay donated in the lowered executable) --
    from ..models import paged_kv
    from ..serve import batching

    MS, PPS, PGS, NPG = 2, 2, 8, 5  # slots, pages/slot, page size, pages
    ppool = paged_kv.init_pool(cfg, NPG, PGS)
    ptab = jnp.zeros((MS, PPS), jnp.int32)
    plens = jnp.zeros((MS,), jnp.int32)
    ptoks = jnp.zeros((MS,), jnp.int32)
    pkeys = jnp.tile(batching._key_data(0), (MS, 1))
    psteps = jnp.zeros((MS,), jnp.int32)
    ptemps = jnp.zeros((MS,), jnp.float32)
    run_one("paged.decode_step",
            lambda p, pool, pt, ln, t: paged_kv.paged_decode_step(
                cfg, p, pool, pt, ln, t),
            (params, ppool, ptab, plens, ptoks),
            ctx={"donate_min": 1},
            lowerable=batching._batched_step_jit,
            lower_args=(cfg, params, ppool, ptab, plens, ptoks,
                        pkeys, psteps, ptemps, None))

    # ---- continuous batching: a single-request paged decode must emit
    # ---- token-for-token what direct generate() emits. This is the one
    # ---- driver that EXECUTES (tiny model, a handful of steps) — token
    # ---- identity is a value property no jaxpr hash can witness ---------
    try:
        bat = batching.ContinuousBatcher(
            cfg, params, batching.BatchingConfig(
                page_size=PGS, num_pages=NPG, max_slots=MS,
                pages_per_slot=PPS))
        bprompt = np.arange(1, 1 + SEQ, dtype=np.int32)
        sid = bat.submit(bprompt, 6, temperature=0.0, rng_seed=0)
        got = bat.run()[sid]
        ref = np.asarray(serve_decode.generate(
            cfg, params, bprompt[None], 6, capacity=CAPACITY,
            rng_key=jax.random.key(0)))[0]
        if not np.array_equal(got, ref):
            findings.append(Finding(
                layer="graph", rule="GC-identity",
                where="batching.decode-step-identity", line=0,
                message=f"single-request paged decode diverged from direct "
                        f"generate: {got.tolist()} != {ref.tolist()}"))
        else:
            checked.append("batching.decode-step-identity")
    except Exception as e:  # noqa: BLE001 — a crashed driver must be loud
        findings.append(_driver_error("batching.decode-step-identity", e))

    # ---- prefix-sharing paged KV: the suffix prefill that backfills only
    # ---- the unmatched prompt tail (collective-free, donated cache) -----
    suffix_cache = transformer.init_cache(cfg, BATCH, CAPACITY)
    suffix_ids = jnp.zeros((BATCH, 4), jnp.int32)
    run_one("decode.prefill_suffix",
            lambda p, i, c: serve_decode._prefill_suffix_impl(
                cfg, p, i, c, None),
            (params, suffix_ids, suffix_cache),
            ctx={"donate_min": 2},
            lowerable=serve_decode._prefill_suffix_jit,
            lower_args=(cfg, params, suffix_ids, suffix_cache, None))

    # prefix sharing is host-side bookkeeping ONLY: a prefix-enabled batcher
    # whose pool really holds shared (refcount > 1) pages must feed the
    # byte-identical ragged step graph as the zero-table trace — sharing may
    # change the table DATA, never the traced GRAPH
    try:
        pbat = batching.ContinuousBatcher(
            cfg, params, batching.BatchingConfig(
                page_size=PGS, num_pages=NPG, max_slots=MS,
                pages_per_slot=PPS,
                prefix_cache=paged_kv.PrefixCacheConfig(enabled=True)))
        pshared = np.arange(1, 1 + PGS, dtype=np.int32)  # one full page
        pbat.submit(np.concatenate([pshared, [99]]).astype(np.int32), 4,
                    temperature=0.0, rng_seed=0)
        pbat.submit(np.concatenate([pshared, [98]]).astype(np.int32), 4,
                    temperature=0.0, rng_seed=1)
        pbat.step()  # admit both: the shared page is live under two slots
        if pbat.pool.shared_pages < 1:
            raise AssertionError("driver bug: no page ended up shared")
        live_tab, live_lens = pbat.pool.device_tables()
        live_toks = jnp.zeros((MS,), jnp.int32)
        ident = check_identity(
            "batching.prefix-disabled-identity",
            lambda p, pool, pt, ln, t: paged_kv.paged_decode_step(
                cfg, p, pool, pt, ln, t),
            (params, pbat.pool.pool, live_tab,
             live_lens, live_toks),
            lambda p, pool, pt, ln, t: paged_kv.paged_decode_step(
                cfg, p, pool, pt, ln, t),
            (params, ppool, ptab, plens, ptoks),
            what="prefix-enabled batcher's ragged decode-step graph")
        (findings.extend(ident) if ident
         else checked.append("batching.prefix-disabled-identity"))
    except Exception as e:  # noqa: BLE001 — a crashed driver must be loud
        findings.append(_driver_error("batching.prefix-disabled-identity", e))

    # ---- prefix token identity: a mixed trace (two prompts sharing a
    # ---- prefix + one disjoint, mixed temperatures) must emit token-for-
    # ---- token what the prefix-DISABLED batcher emits — the EXECUTED half
    # ---- of the contract (suffix prefill + COW are value properties no
    # ---- jaxpr hash can witness) ----------------------------------------
    try:
        prng = np.random.default_rng(7)
        pfx = prng.integers(1, 128, size=PGS).astype(np.int32)
        pprompts = [
            np.concatenate([pfx, prng.integers(1, 128, size=3)]),
            np.concatenate([pfx, prng.integers(1, 128, size=2)]),
            prng.integers(1, 128, size=6).astype(np.int32),
        ]
        ptemps = [0.0, 0.8, 0.0]

        def _trace(prefix_cache):
            b = batching.ContinuousBatcher(
                cfg, params, batching.BatchingConfig(
                    page_size=PGS, num_pages=NPG, max_slots=MS,
                    pages_per_slot=PPS, prefix_cache=prefix_cache))
            sids = [b.submit(pp.astype(np.int32), 3, temperature=t,
                             rng_seed=i)
                    for i, (pp, t) in enumerate(zip(pprompts, ptemps))]
            out = b.run()
            b.pool.check_invariants()
            return [out[s].tolist() for s in sids], b.pool.prefix_counters

        base_toks, _ = _trace(None)
        got_toks, pc = _trace(paged_kv.PrefixCacheConfig(enabled=True))
        if got_toks != base_toks:
            findings.append(Finding(
                layer="graph", rule="GC-identity",
                where="batching.prefix-token-identity", line=0,
                message=f"prefix-enabled batched decode diverged from the "
                        f"non-shared path: {got_toks} != {base_toks}"))
        elif pc["hits"] < 1 or pc["saved_tokens"] < 1:
            findings.append(Finding(
                layer="graph", rule="GC-identity",
                where="batching.prefix-token-identity", line=0,
                message=f"prefix trace never hit the index (hits="
                        f"{pc['hits']}, saved={pc['saved_tokens']}): the "
                        f"parity check proved nothing"))
        else:
            checked.append("batching.prefix-token-identity")
    except Exception as e:  # noqa: BLE001 — a crashed driver must be loud
        findings.append(_driver_error("batching.prefix-token-identity", e))

    # ---- KV-at-rest quantization: the quant ragged step (collective-free;
    # ---- all FOUR pool buffers — codes AND scales — stay donated in the
    # ---- lowered executable) --------------------------------------------
    qpool = paged_kv.init_quant_pool(cfg, NPG, PGS, "int8_per_channel")
    qsteps = jnp.zeros((MS,), jnp.int32)
    qtemps = jnp.zeros((MS,), jnp.float32)
    run_one("paged.decode_step_quant",
            lambda p, pool, pt, ln, t: paged_kv.paged_decode_step(
                cfg, p, pool, pt, ln, t),
            (params, qpool, ptab, plens, ptoks),
            ctx={"donate_min": 4},
            lowerable=batching._batched_step_jit,
            lower_args=(cfg, params, qpool, ptab, plens, ptoks, pkeys,
                        qsteps, qtemps, None))

    # ---- a stack with recurrent state (granitemoehybrid): the hybrid
    # ---- ragged step — collective-free; the K/V pages, the per-slot state
    # ---- store (conv, ssm) and the expert counter, FOUR buffers, stay
    # ---- donated in the lowered executable ------------------------------
    from ..models import hybrid
    from ..models.configs import tiny_hybrid_config

    hcfg = tiny_hybrid_config()
    hparams = transformer.init_params(hcfg, jax.random.key(0))
    hpool = paged_kv.init_pool(hcfg, NPG, PGS)
    hstate = paged_kv.init_slot_state(hcfg, MS)
    hcount = jnp.zeros((hcfg.num_layers, hcfg.local_experts), jnp.int32)
    run_one("paged.decode_step_hybrid",
            lambda p, kv, st, ct, pt, ln, t:
                hybrid.paged_decode_step_hybrid(
                    hcfg, p, kv, st, ct, pt, ln, t),
            (hparams, hpool.kv, hstate, hcount, ptab, plens, ptoks),
            ctx={"donate_min": 4},
            lowerable=batching._batched_hybrid_step_jit,
            lower_args=(hcfg, hparams, hpool.kv, hstate, hcount,
                        ptab, plens, ptoks, pkeys, qsteps, qtemps, None))

    # ---- a stack with sliding-window layers (mellum): the same walk with
    # ---- the window group — collective-free; the full layers' pool, the
    # ---- window layers' pool of rings (one buffer each) and the expert
    # ---- counter, THREE buffers, stay donated in the lowered executable --
    from ..models.configs import tiny_mellum_config

    wcfg = tiny_mellum_config(sliding_window=2 * PGS + 2)
    wparams = transformer.init_params(wcfg, jax.random.key(0))
    wfull = paged_kv.init_pool(wcfg, NPG, PGS)
    wring = paged_kv.init_pool(wcfg, MS * wcfg.window_pages(PGS) + 1, PGS,
                               layers=wcfg.window_layers)
    wtab = jnp.zeros((MS, wcfg.window_pages(PGS)), jnp.int32)
    wcount = jnp.zeros((wcfg.num_layers, wcfg.local_experts), jnp.int32)
    run_one("paged.decode_step_window",
            lambda p, kv, win, ct, pt, wt, ln, t:
                hybrid.paged_decode_step_hybrid(
                    wcfg, p, kv, None, ct, pt, ln, t, window=(win, wt)),
            (wparams, wfull.kv, wring.kv, wcount, ptab, wtab, plens, ptoks),
            ctx={"donate_min": 3},
            lowerable=batching._batched_window_step_jit,
            lower_args=(wcfg, wparams, wfull, wring, wcount, ptab, wtab,
                        plens, ptoks, pkeys, qsteps, qtemps, None))

    # ---- a stack of latent-attention layers (mistral4): the same walk and
    # ---- the same step executable with the pool's ONE leaf where the K/V
    # ---- pages go and no state store — collective-free; the leaf and the
    # ---- expert counter, TWO buffers, stay donated ----------------------
    from ..models.configs import tiny_mistral4_config

    lcfg = tiny_mistral4_config()
    lparams = transformer.init_params(lcfg, jax.random.key(0))
    lpool = paged_kv.init_pool(lcfg, NPG, PGS)
    lcount = jnp.zeros((lcfg.num_layers, lcfg.local_experts), jnp.int32)
    run_one("paged.decode_step_latent",
            lambda p, rows, ct, pt, ln, t: hybrid.paged_decode_step_hybrid(
                lcfg, p, rows, None, ct, pt, ln, t),
            (lparams, lpool.rows, lcount, ptab, plens, ptoks),
            ctx={"donate_min": 2},
            lowerable=batching._batched_hybrid_step_jit,
            lower_args=(lcfg, lparams, lpool.rows, None, lcount,
                        ptab, plens, ptoks, pkeys, qsteps, qtemps, None))

    # ---- a stack of short convolutions beside rotated attention layers
    # ---- (lfm2_moe): the same executable with a state store of ONE leaf,
    # ---- the windows — collective-free; the K/V pages, the windows and the
    # ---- expert counter, THREE buffers, stay donated ---------------------
    from ..models.configs import tiny_lfm2_moe_config

    ccfg = tiny_lfm2_moe_config()
    cparams = transformer.init_params(ccfg, jax.random.key(0))
    cpool = paged_kv.init_pool(ccfg, NPG, PGS)
    cstate = paged_kv.init_slot_state(ccfg, MS)
    ccount = jnp.zeros((ccfg.expert_layers, ccfg.local_experts), jnp.int32)
    run_one("paged.decode_step_shortconv",
            lambda p, kv, st, ct, pt, ln, t:
                hybrid.paged_decode_step_hybrid(
                    ccfg, p, kv, st, ct, pt, ln, t),
            (cparams, cpool.kv, cstate, ccount, ptab, plens, ptoks),
            ctx={"donate_min": 3},
            lowerable=batching._batched_hybrid_step_jit,
            lower_args=(ccfg, cparams, cpool.kv, cstate, ccount,
                        ptab, plens, ptoks, pkeys, qsteps, qtemps, None))

    # ---- a stack of sparse-attention layers (keye_vl2): the same
    # ---- executable with a pool of TWO leaves handed over whole, K/V rows
    # ---- and index keys under one table (a span of 16 past the toy's topk
    # ---- of 8: the indexer, the selection and the row gather are in the
    # ---- graph) — collective-free; both leaves and the expert counter,
    # ---- THREE buffers, stay donated ------------------------------------
    from ..models.configs import (tiny_deepseek_v32_config,
                                  tiny_keye_vl2_config)

    kcfg = tiny_keye_vl2_config()
    kparams = transformer.init_params(kcfg, jax.random.key(0))
    kpool = paged_kv.init_pool(kcfg, NPG, PGS)
    kcount = jnp.zeros((kcfg.expert_layers, kcfg.local_experts), jnp.int32)
    run_one("paged.decode_step_sparse",
            lambda p, kv, ik, ct, pt, ln, t:
                hybrid.paged_decode_step_hybrid(
                    kcfg, p, paged_kv.IndexedPagePool(kv, ik), None, ct, pt,
                    ln, t),
            (kparams, kpool.kv, kpool.ik, kcount, ptab, plens, ptoks),
            ctx={"donate_min": 3},
            lowerable=batching._batched_hybrid_step_jit,
            lower_args=(kcfg, kparams, kpool, None, kcount,
                        ptab, plens, ptoks, pkeys, qsteps, qtemps, None))

    # the ragged step of a stack of sparse LATENT layers: its pool's two
    # leaves (latent rows, index keys) and the assignment counter donated
    dcfg = tiny_deepseek_v32_config()
    dparams = transformer.init_params(dcfg, jax.random.key(0))
    dpool = paged_kv.init_pool(dcfg, NPG, PGS)
    dcount = jnp.zeros((dcfg.expert_layers, dcfg.local_experts), jnp.int32)
    run_one("paged.decode_step_sparse_latent",
            lambda p, rows, ik, ct, pt, ln, t:
                hybrid.paged_decode_step_hybrid(
                    dcfg, p, paged_kv.IndexedLatentPool(rows, ik), None, ct,
                    pt, ln, t),
            (dparams, dpool.rows, dpool.ik, dcount, ptab, plens, ptoks),
            ctx={"donate_min": 3},
            lowerable=batching._batched_hybrid_step_jit,
            lower_args=(dcfg, dparams, dpool, None, dcount,
                        ptab, plens, ptoks, pkeys, qsteps, qtemps, None))

    # the ragged step of sparse latent layers beside WINDOW latent layers
    # (dots3_note): the window step's executable with an IndexedLatentPool
    # handed over whole and a ring group of latent rows: both leaves, the
    # ring's leaf and the assignment counter, FOUR buffers, stay donated
    from ..models.configs import tiny_dots3_note_config

    ncfg = tiny_dots3_note_config(sliding_window=2 * PGS + 2)
    nparams = transformer.init_params(ncfg, jax.random.key(0))
    npool = paged_kv.init_pool(ncfg, NPG, PGS)
    nring = paged_kv.init_pool(ncfg, MS * ncfg.window_pages(PGS) + 1, PGS,
                               layers=ncfg.window_layers,
                               lanes=ncfg.window_row_lanes)
    ntab = jnp.zeros((MS, ncfg.window_pages(PGS)), jnp.int32)
    ncount = jnp.zeros((ncfg.expert_layers, ncfg.local_experts), jnp.int32)
    run_one("paged.decode_step_window_latent",
            lambda p, rows, ik, win, ct, pt, wt, ln, t:
                hybrid.paged_decode_step_hybrid(
                    ncfg, p, paged_kv.IndexedLatentPool(rows, ik), None, ct,
                    pt, ln, t, window=(win, wt)),
            (nparams, npool.rows, npool.ik, nring.rows, ncount, ptab, ntab,
             plens, ptoks),
            ctx={"donate_min": 4},
            lowerable=batching._batched_window_step_jit,
            lower_args=(ncfg, nparams, npool, nring, ncount, ptab, ntab,
                        plens, ptoks, pkeys, qsteps, qtemps, None))

    # the fp tier must be a NO-OP: a kv_codec="fp" batcher with live state
    # feeds the byte-identical ragged step graph the pre-quantization
    # batcher traces — the disabled-build jaxpr fingerprint half of the
    # KV-at-rest contract
    try:
        fbat = batching.ContinuousBatcher(
            cfg, params, batching.BatchingConfig(
                page_size=PGS, num_pages=NPG, max_slots=MS,
                pages_per_slot=PPS, kv_codec="fp"))
        fbat.submit(np.arange(1, 1 + SEQ, dtype=np.int32), 4,
                    temperature=0.0, rng_seed=0)
        fbat.step()
        ftab, flens = fbat.pool.device_tables()
        ftoks = jnp.zeros((MS,), jnp.int32)
        ident = check_identity(
            "batching.kvq-disabled-identity",
            lambda p, pool, pt, ln, t: paged_kv.paged_decode_step(
                cfg, p, pool, pt, ln, t),
            (params, fbat.pool.pool, ftab, flens, ftoks),
            lambda p, pool, pt, ln, t: paged_kv.paged_decode_step(
                cfg, p, pool, pt, ln, t),
            (params, ppool, ptab, plens, ptoks),
            what="kv_codec=\"fp\" batcher's ragged decode-step graph")
        (findings.extend(ident) if ident
         else checked.append("batching.kvq-disabled-identity"))
    except Exception as e:  # noqa: BLE001 — a crashed driver must be loud
        findings.append(_driver_error("batching.kvq-disabled-identity", e))

    # ---- fp-tier token identity: an explicit kv_codec="fp" batcher must
    # ---- emit token-for-token what direct generate() emits — the EXECUTED
    # ---- half (quantize-on-append must never touch the fp path) ---------
    try:
        kbat = batching.ContinuousBatcher(
            cfg, params, batching.BatchingConfig(
                page_size=PGS, num_pages=NPG, max_slots=MS,
                pages_per_slot=PPS, kv_codec="fp"))
        kprompt = np.arange(1, 1 + SEQ, dtype=np.int32)
        ksid = kbat.submit(kprompt, 6, temperature=0.0, rng_seed=0)
        kgot = kbat.run()[ksid]
        kref = np.asarray(serve_decode.generate(
            cfg, params, kprompt[None], 6, capacity=CAPACITY,
            rng_key=jax.random.key(0)))[0]
        if not np.array_equal(kgot, kref):
            findings.append(Finding(
                layer="graph", rule="GC-identity",
                where="batching.kvq-fp-token-identity", line=0,
                message=f"fp-tier paged decode diverged from direct "
                        f"generate: {kgot.tolist()} != {kref.tolist()}"))
        else:
            checked.append("batching.kvq-fp-token-identity")
    except Exception as e:  # noqa: BLE001 — a crashed driver must be loud
        findings.append(_driver_error("batching.kvq-fp-token-identity", e))

    # ---- quant decode fallback: the XLA page-table-gather path must equal
    # ---- quantize->dequantize + plain decode attention EXACTLY (same op
    # ---- order, no extra rounding) — executed on random packed pools ----
    try:
        from ..models import flash_attention as fa

        eq_rng = np.random.default_rng(3)
        for tier in ("int8_per_channel", "int4_per_channel"):
            tpool = paged_kv.init_quant_pool(cfg, NPG, PGS, tier)
            kq, ks = fa.quantize_kv_rows(jnp.asarray(
                eq_rng.standard_normal((cfg.num_layers, NPG * PGS,
                                        cfg.num_kv_heads, cfg.head_dim),
                                       np.float32)), tier)
            vq, vs = fa.quantize_kv_rows(jnp.asarray(
                eq_rng.standard_normal((cfg.num_layers, NPG * PGS,
                                        cfg.num_kv_heads, cfg.head_dim),
                                       np.float32)), tier)
            # the stored form: (L, P, ps, KV*lanes) codes, (L, P, ps, KV)
            # scales; layer 0 is what the attend is asked for
            qpool_t = paged_kv.QuantPagePool(
                kq.reshape(tpool.k.shape), vq.reshape(tpool.k.shape),
                ks.reshape(tpool.k_scale.shape),
                vs.reshape(tpool.k_scale.shape))
            q = jnp.asarray(eq_rng.standard_normal(
                (MS, 1, cfg.num_heads, cfg.head_dim), np.float32))
            etab = jnp.asarray(
                eq_rng.permutation(np.arange(1, NPG))[:MS * PPS]
                .reshape(MS, PPS).astype(np.int32))
            elens = jnp.asarray([PGS + 3, PGS - 2], jnp.int32)
            got = paged_kv.paged_decode_attention(q, qpool_t, 0, etab, elens)
            # reference: dequantize the WHOLE pool, then the plain fp path
            fshape = tpool.k.shape[:-1] + (cfg.num_kv_heads * cfg.head_dim,)
            ref = paged_kv.paged_decode_attention(
                q, paged_kv.PagePool(paged_kv.join_kv(
                    fa.dequantize_kv_rows(kq, ks, tier).reshape(fshape),
                    fa.dequantize_kv_rows(vq, vs, tier).reshape(fshape))),
                0, etab, elens)
            if not np.array_equal(np.asarray(got), np.asarray(ref)):
                d = float(np.abs(np.asarray(got) - np.asarray(ref)).max())
                findings.append(Finding(
                    layer="graph", rule="GC-identity",
                    where="paged.quant-fallback-equivalence", line=0,
                    message=f"{tier} XLA fallback diverged from quantize->"
                            f"dequantize decode attention (max |d|={d:g})"))
                break
        else:
            checked.append("paged.quant-fallback-equivalence")
    except Exception as e:  # noqa: BLE001 — a crashed driver must be loud
        findings.append(_driver_error("paged.quant-fallback-equivalence", e))

    # ---- disaggregated prefill/decode: pure host-side orchestration.
    # ---- A DisaggServer's decode batcher — after a REAL migration landed
    # ---- (prefill on a staging worker, pages over the link, resume adopt)
    # ---- — must feed the byte-identical ragged step graph the pre-disagg
    # ---- batcher traces: migration moves page DATA, never the GRAPH ------
    try:
        from ..serve import disagg as serve_disagg

        dsrv = serve_disagg.DisaggServer(
            cfg, params, batching.BatchingConfig(
                page_size=PGS, num_pages=NPG, max_slots=MS,
                pages_per_slot=PPS),
            serve_disagg.DisaggConfig(num_prefill_workers=1,
                                      prefill_batch=1))
        dsid = dsrv.submit(np.arange(1, 1 + SEQ, dtype=np.int32), 4,
                           temperature=0.0, rng_seed=0)
        dsrv.step()  # prefill + migrate + adopt: decode holds migrated pages
        if dsrv.report()["disagg"]["migrations"] < 1:
            raise AssertionError("driver bug: no migration happened")
        dtab, dlens = dsrv.pool.device_tables()
        dtoks = jnp.zeros((MS,), jnp.int32)
        ident = check_identity(
            "disagg.disabled-identity",
            lambda p, pool, pt, ln, t: paged_kv.paged_decode_step(
                cfg, p, pool, pt, ln, t),
            (params, dsrv.pool.pool, dtab, dlens,
             dtoks),
            lambda p, pool, pt, ln, t: paged_kv.paged_decode_step(
                cfg, p, pool, pt, ln, t),
            (params, ppool, ptab, plens, ptoks),
            what="disagg decode batcher's ragged decode-step graph (with "
                 "migrated pages live)")
        (findings.extend(ident) if ident
         else checked.append("disagg.disabled-identity"))
        dsrv.run()
        dsrv.pop_result(dsid)
    except Exception as e:  # noqa: BLE001 — a crashed driver must be loud
        findings.append(_driver_error("disagg.disabled-identity", e))

    # ---- disagg migration wire bytes: every page transfer's built wire
    # ---- tree must measure exactly migration_wire_nbytes(payload) — the
    # ---- sealed form (payload + 8 B sidecar) and the FEC frame (parity
    # ---- chunks + per-chunk checksum words). A drifting frame layout is a
    # ---- silent protocol break between prefill and decode builds ---------
    try:
        from ..codecs import fec as codecs_fec
        from ..codecs import wire_format as codecs_wire

        wbat = batching.ContinuousBatcher(
            cfg, params, batching.BatchingConfig(
                page_size=PGS, num_pages=NPG, max_slots=MS,
                pages_per_slot=PPS))
        wsid = wbat.submit(np.arange(1, 1 + SEQ, dtype=np.int32), 2,
                           temperature=0.0, rng_seed=0)
        wst = wbat.prefill_hold(wsid)
        chunk = wbat.gather_rows(wst.slot, 0, PGS)
        payload = jax.tree_util.tree_map(jnp.asarray, chunk)
        sealed = codecs_wire.seal_payload(payload)
        bad = []
        measured = codecs_wire.tree_nbytes(sealed)
        declared = serve_disagg.migration_wire_nbytes(
            codecs_wire.tree_nbytes(payload), None)
        if measured != declared:
            bad.append(f"sealed frame measures {measured} B, "
                       f"declared {declared} B")
        fcfg = codecs_fec.FECConfig(enabled=True)
        fmeasured = codecs_wire.tree_nbytes(
            codecs_fec.fec_encode(sealed, fcfg))
        fdeclared = serve_disagg.migration_wire_nbytes(
            codecs_wire.tree_nbytes(payload), fcfg)
        if fmeasured != fdeclared:
            bad.append(f"FEC frame measures {fmeasured} B, "
                       f"declared {fdeclared} B")
        wbat.release_handoff(wsid)
        if bad:
            findings.append(Finding(
                layer="graph", rule="GC-identity",
                where="disagg.migration-wire-bytes", line=0,
                message="migration wire-byte contract violated: "
                        + "; ".join(bad)))
        else:
            checked.append("disagg.migration-wire-bytes")
    except Exception as e:  # noqa: BLE001 — a crashed driver must be loud
        findings.append(_driver_error("disagg.migration-wire-bytes", e))

    # ---- gray-failure hedging: pure host-side orchestration. A cluster
    # ---- whose gray plane REALLY fired (straggler samples observed, a
    # ---- hedge leg dispatched and settled first-finisher-wins) must leave
    # ---- every replica batcher feeding the byte-identical ragged step
    # ---- graph as the zero-table trace — hedging re-places REQUESTS,
    # ---- never touches the compiled decode graph ------------------------
    try:
        from ..serve.cluster import ClusterConfig, ClusterFront, GrayConfig
        from ..serve.frontend import Request, ServeFront
        from ..serve.overload import COMPLETED
        from ..utils.clock import FakeClock

        hck = FakeClock()
        hfronts = {}

        def _hedge_factory(rid, gen):
            f = ServeFront(cfg, params, clock=hck,
                           batcher=batching.ContinuousBatcher(
                               cfg, params, batching.BatchingConfig(
                                   page_size=PGS, num_pages=NPG,
                                   max_slots=MS, pages_per_slot=PPS)))
            hfronts[rid] = f
            return f

        hclu = ClusterFront(_hedge_factory, ClusterConfig(
            num_replicas=2, probe_prefix=False,
            gray=GrayConfig(enabled=True, p95_multiple=1.5,
                            hedge_delay_quantile=0.5, min_dwell_s=0.0,
                            max_hedge_fraction=1.0, min_samples=1)),
            clock=hck)
        hprompt = np.arange(1, 1 + SEQ, dtype=np.int32)
        # two seed requests give the detector per-replica latency samples
        # (FakeClock latencies are 0, so the hedge delay collapses to 0)
        for i in range(2):
            hclu.submit(Request(prompt_ids=hprompt, max_new_tokens=3,
                                temperature=0.0, rng_seed=i))
            while hclu.drain():
                pass
        hcrid = hclu.submit(Request(prompt_ids=hprompt, max_new_tokens=3,
                                    temperature=0.0, rng_seed=7))
        hck.advance(0.5)   # older than the 0-second hedge delay
        hrecs = []
        while True:
            got = hclu.drain()
            if not got:
                break
            hrecs.extend(got)
        if hclu.totals["hedges"] < 1:
            raise AssertionError("driver bug: no hedge leg fired")
        if hclu.pending:
            raise AssertionError(
                f"hedge settlement lost work: {hclu.pending} pending")
        hrec = next(r for r in hrecs if r.request_id == hcrid)
        href = np.asarray(serve_decode.generate(
            cfg, params, hprompt[None], 3, capacity=CAPACITY,
            rng_key=jax.random.key(7)))[0]
        htoks_got = (None if hrec.tokens is None
                     else np.asarray(hrec.tokens).reshape(-1))
        if hrec.outcome != COMPLETED or not np.array_equal(htoks_got, href):
            findings.append(Finding(
                layer="graph", rule="GC-identity",
                where="cluster.hedge-disabled-identity", line=0,
                message=f"hedged request diverged from direct generate: "
                        f"outcome={hrec.outcome} tokens={htoks_got} "
                        f"!= {href.tolist()}"))
        else:
            hpool = hfronts[0].batcher.pool
            htab, hlens = hpool.device_tables()
            htoks = jnp.zeros((MS,), jnp.int32)
            ident = check_identity(
                "cluster.hedge-disabled-identity",
                lambda p, pool, pt, ln, t: paged_kv.paged_decode_step(
                    cfg, p, pool, pt, ln, t),
                (params, hpool.pool, htab, hlens, htoks),
                lambda p, pool, pt, ln, t: paged_kv.paged_decode_step(
                    cfg, p, pool, pt, ln, t),
                (params, ppool, ptab, plens, ptoks),
                what="gray-hedged replica's ragged decode-step graph")
            (findings.extend(ident) if ident
             else checked.append("cluster.hedge-disabled-identity"))
    except Exception as e:  # noqa: BLE001 — a crashed driver must be loud
        findings.append(_driver_error("cluster.hedge-disabled-identity", e))

    # ---- split pipeline: boundary hops over a real 2-stage mesh ---------
    if len(jax.devices()) < 2:
        skipped.append("split/fault contracts: needs >= 2 devices "
                       "(set XLA_FLAGS=--xla_force_host_platform_device_count=8)")
        return findings, checked, skipped

    mesh = make_stage_mesh(2)
    split = SplitConfig(cuts=(2,), hop_codecs=("int8_per_token",))
    rt = SplitRuntime(cfg, split, mesh)
    placed = rt.place_params(params)
    n_hops = len(rt.codecs)

    fwd_shape = (BATCH, SEQ, cfg.hidden_size)
    leaves_f, dtypes_f, bytes_f = _payload_info(rt.codecs[0], fwd_shape)
    imps = jnp.zeros((n_hops, SEQ), jnp.float32)  # blank importance stack
    fwd_ctx = {
        "hop_eqns": n_hops * leaves_f,
        "wire_dtypes": frozenset(dtypes_f),
        "wire_bytes": sum(rt.hop_bytes(BATCH, SEQ)),
    }
    run_one("split.forward", rt._forward, (placed, ids, imps), fwd_ctx)

    step_shape = (BATCH, 1, cfg.hidden_size)
    leaves_s, dtypes_s, _ = _payload_info(rt.codecs[0], step_shape)
    prefill_fn, step_fn = rt._decode_fns(CAPACITY)
    kv_shape = (split.n_stages, rt.stage_size, BATCH, CAPACITY,
                cfg.num_kv_heads, cfg.head_dim)
    k_cache = jnp.zeros(kv_shape, jnp.float32)
    v_cache = jnp.zeros(kv_shape, jnp.float32)
    length = jnp.asarray(SEQ, jnp.int32)
    step_ctx = {
        "hop_eqns": n_hops * leaves_s,
        "wire_dtypes": frozenset(dtypes_s),
        "wire_bytes": sum(rt.decode_hop_bytes(BATCH)),
        "donate_min": 2,  # k_cache + v_cache buffers update in place
    }
    run_one("split.decode_step", step_fn,
            (placed, k_cache, v_cache, length, tok), step_ctx,
            lowerable=step_fn,
            lower_args=(placed, k_cache, v_cache, length, tok))

    # ---- paged split: the ragged twin of split.decode_step — every cut
    # ---- still quantizes a (max_slots, 1, D) boundary activation, the
    # ---- per-stage page pools stay donated ------------------------------
    spool = rt.init_paged_pool(NPG, PGS)
    paged_step_shape = (MS, 1, cfg.hidden_size)
    leaves_p, dtypes_p, _ = _payload_info(rt.codecs[0], paged_step_shape)
    pstep_fn = rt._paged_decode_fns(NPG, PGS)
    paged_ctx = {
        "hop_eqns": n_hops * leaves_p,
        "wire_dtypes": frozenset(dtypes_p),
        "wire_bytes": sum(rt.decode_hop_bytes(MS)),
        "donate_min": 1,  # the per-stage page pool updates in place
    }
    run_one("split.decode_step_paged", pstep_fn,
            (placed, spool, ptab, plens, ptoks), paged_ctx,
            lowerable=pstep_fn,
            lower_args=(placed, spool, ptab, plens, ptoks))

    # ---- k-token verify: the speculative burst's ONE boundary round-trip —
    # ---- every cut quantizes a single (B, K, D) activation block instead of
    # ---- K single-token payloads, KV donation discipline unchanged ---------
    K = 4  # verify window; any k traces the same contract shape
    verify_shape = (BATCH, K, cfg.hidden_size)
    leaves_v, dtypes_v, _ = _payload_info(rt.codecs[0], verify_shape)
    verify_fn = rt._verify_fns(CAPACITY, K)
    vtoks = jnp.zeros((BATCH, K), jnp.int32)
    verify_ctx = {
        "hop_eqns": n_hops * leaves_v,
        "wire_dtypes": frozenset(dtypes_v),
        "wire_bytes": sum(rt.verify_hop_bytes(BATCH, K)),
        "donate_min": 2,  # the burst updates both KV caches in place
    }
    run_one("split.verify_step", verify_fn,
            (placed, k_cache, v_cache, length, vtoks), verify_ctx,
            lowerable=verify_fn,
            lower_args=(placed, k_cache, v_cache, length, vtoks))

    # a disabled SpecConfig is pure host-side dispatch: a runtime whose
    # verify executables HAVE been built must still trace the byte-identical
    # vanilla decode step (the pre-spec graph) — this is the fingerprint
    # half of the ISSUE's disabled-spec contract; run.py's validator and the
    # serve loop's dispatch guard are the other half
    rt_prespec = SplitRuntime(cfg, split, mesh)
    _, step_fn_prespec = rt_prespec._decode_fns(CAPACITY)
    ident = check_identity(
        "split.decode_step.spec-disabled-identity",
        step_fn, (placed, k_cache, v_cache, length, tok),
        step_fn_prespec, (placed, k_cache, v_cache, length, tok),
        what="spec-aware build's vanilla decode-step graph")
    (findings.extend(ident) if ident
     else checked.append("split.decode_step.spec-disabled-identity"))

    # ---- micro-batch pipelined schedule: same wire protocol per hop, but
    # ---- every cut now moves M payloads of (B/M, ...) — hop_eqns and wire
    # ---- bytes scale by M, replication still collapses to ONE stacked psum,
    # ---- and the KV/pool donation discipline survives the schedule --------
    PBATCH, PM = 2, 2  # batch and µ-batch count; µ-batch rows = PBATCH // PM
    rt_pipe = SplitRuntime(cfg, split, mesh,
                           pipeline=PipelineConfig(num_microbatches=PM))
    pipe_ids = jnp.zeros((PBATCH, SEQ), jnp.int32)
    pipe_fwd_ctx = {
        "hop_eqns": PM * n_hops * leaves_f,
        "wire_dtypes": frozenset(dtypes_f),
        "wire_bytes": PM * sum(rt_pipe.hop_bytes(PBATCH // PM, SEQ)),
    }
    run_one("split.forward.pipelined", rt_pipe._forward,
            (placed, pipe_ids, imps), pipe_fwd_ctx)

    pipe_kv_shape = (split.n_stages, rt.stage_size, PBATCH, CAPACITY,
                     cfg.num_kv_heads, cfg.head_dim)
    pipe_k = jnp.zeros(pipe_kv_shape, jnp.float32)
    pipe_v = jnp.zeros(pipe_kv_shape, jnp.float32)
    pipe_tok = jnp.zeros((PBATCH,), jnp.int32)
    _, pipe_step_fn = rt_pipe._decode_fns(CAPACITY)
    pipe_step_ctx = {
        "hop_eqns": PM * n_hops * leaves_s,
        "wire_dtypes": frozenset(dtypes_s),
        "wire_bytes": sum(rt_pipe.pipelined_decode_hop_bytes(PBATCH)),
        "donate_min": 2,
    }
    run_one("split.decode_step.pipelined", pipe_step_fn,
            (placed, pipe_k, pipe_v, length, pipe_tok), pipe_step_ctx,
            lowerable=pipe_step_fn,
            lower_args=(placed, pipe_k, pipe_v, length, pipe_tok))

    # MS slots split into PM µ-batches of MS // PM ragged rows each
    pipe_pstep_fn = rt_pipe._paged_decode_fns(NPG, PGS)
    pipe_paged_ctx = {
        "hop_eqns": PM * n_hops * leaves_p,
        "wire_dtypes": frozenset(dtypes_p),
        "wire_bytes": sum(rt_pipe.pipelined_decode_hop_bytes(MS)),
        "donate_min": 1,
    }
    run_one("split.decode_step_paged.pipelined", pipe_pstep_fn,
            (placed, spool, ptab, plens, ptoks),
            pipe_paged_ctx,
            lowerable=pipe_pstep_fn,
            lower_args=(placed, spool, ptab, plens, ptoks))

    # num_microbatches=1 must trace the ORIGINAL sequential schedule byte for
    # byte — the fingerprint half of the ISSUE's disabled-pipeline contract
    # (run.py's validator and the runtime's n_micro dispatch are the other
    # half); pinned for forward AND decode so neither schedule can drift
    rt_m1 = SplitRuntime(cfg, split, mesh,
                         pipeline=PipelineConfig(num_microbatches=1))
    ident = check_identity(
        "split.forward.pipeline-disabled-identity",
        rt._forward, (placed, ids, imps),
        rt_m1._forward, (placed, ids, imps),
        what="num_microbatches=1 build's forward graph")
    (findings.extend(ident) if ident
     else checked.append("split.forward.pipeline-disabled-identity"))
    _, step_fn_m1 = rt_m1._decode_fns(CAPACITY)
    ident = check_identity(
        "split.decode_step.pipeline-disabled-identity",
        step_fn, (placed, k_cache, v_cache, length, tok),
        step_fn_m1, (placed, k_cache, v_cache, length, tok),
        what="num_microbatches=1 build's decode-step graph")
    (findings.extend(ident) if ident
     else checked.append("split.decode_step.pipeline-disabled-identity"))

    # ---- faulty link: sealed payloads, statically-unrolled retries ------
    attempts = 2  # 1 try + 1 retry, statically unrolled in the graph
    rt_fault = SplitRuntime(cfg, split, mesh,
                            faults=FaultConfig(bitflip_rate=0.01, seed=0),
                            policy=LinkPolicy(max_retries=attempts - 1))
    sealed_leaves = leaves_f + 2  # + canary + crc sidecars
    fault_ctx = {
        "hop_eqns": n_hops * sealed_leaves * attempts,
        "n_psum": 1 + len(COUNTER_KEYS),  # output + replicated counters
        "wire_dtypes": frozenset(dtypes_f) | {"uint32"},
        # every attempt retransmits payload + 8-byte integrity sidecar
        "wire_bytes": attempts * (bytes_f + 8) * n_hops,
    }
    fault_step = jnp.asarray(0, jnp.int32)
    run_one("faults.hop", rt_fault._forward,
            (placed, ids, imps, fault_step), fault_ctx)

    # ---- self-healing link: FEC parity + hedged routes ------------------
    from ..codecs.fec import FECConfig, HedgeConfig

    fec_cfg = FECConfig(group_size=4, n_groups=4)
    hedge_cfg = HedgeConfig(routes=2)
    rt_fec = SplitRuntime(cfg, split, mesh,
                          faults=FaultConfig(bitflip_rate=0.01, seed=0),
                          policy=LinkPolicy(max_retries=attempts - 1),
                          fec=fec_cfg, hedge=hedge_cfg)
    transmissions = attempts * hedge_cfg.routes  # retries x staggered routes
    fec_ctx = {
        # 2 wire leaves per transmission: the chunk matrix + the word vector
        "hop_eqns": n_hops * 2 * transmissions,
        "n_psum": 1 + len(rt_fec._link.counter_keys),
        "wire_dtypes": frozenset({"uint8", "uint32"}),
        # ppermute traffic = declared payload + parity overhead, per route
        "wire_bytes": transmissions * fec_cfg.wire_nbytes(bytes_f + 8)
        * n_hops,
    }
    run_one("fec.hop", rt_fec._forward,
            (placed, ids, imps, fault_step), fec_ctx)

    # a faulted build with FEC and hedging *disabled* must trace the exact
    # PR 2 hop — same fingerprint as a build that never heard of fec.py
    rt_fec_off = SplitRuntime(cfg, split, mesh,
                              faults=FaultConfig(bitflip_rate=0.01, seed=0),
                              policy=LinkPolicy(max_retries=attempts - 1),
                              fec=FECConfig(enabled=False),
                              hedge=HedgeConfig(enabled=False))
    ident = check_identity(
        "split.forward.fec-disabled-identity",
        rt_fault._forward, (placed, ids, imps, fault_step),
        rt_fec_off._forward, (placed, ids, imps, fault_step),
        what="disabled-FEC faulted forward graph")
    (findings.extend(ident) if ident
     else checked.append("split.forward.fec-disabled-identity"))

    # ---- disabled-config identity: a zero-rate fault config and an absent
    # ---- one must compile the SAME executable -----------------------------
    rt_zero = SplitRuntime(cfg, split, mesh, faults=FaultConfig())
    ident = check_identity(
        "split.forward.zero-fault-identity",
        rt._forward, (placed, ids, imps),
        rt_zero._forward, (placed, ids, imps),
        what="zero-rate FaultConfig forward graph")
    (findings.extend(ident) if ident
     else checked.append("split.forward.zero-fault-identity"))

    _, step_fn_zero = rt_zero._decode_fns(CAPACITY)
    ident = check_identity(
        "split.decode_step.zero-fault-identity",
        step_fn, (placed, k_cache, v_cache, length, tok),
        step_fn_zero, (placed, k_cache, v_cache, length, tok),
        what="zero-rate FaultConfig decode-step graph")
    (findings.extend(ident) if ident
     else checked.append("split.decode_step.zero-fault-identity"))

    # ---- observability identity: ARMING the obs stack (registry + tracer
    # ---- on, a span open on this thread) must not change a single jaxpr
    # ---- byte — every instrument is host-side, at sample boundaries, never
    # ---- inside the compiled graph ---------------------------------------
    from .. import obs

    def _armed(fn: Callable) -> Callable:
        """Trace ``fn`` with the full obs stack enabled and an open span, so
        any graph residue (a host callback, a metric op) flips the hash."""
        def traced(*args):
            obs.enable(obs.ObservabilityConfig())
            try:
                with obs.span("lint.obs-identity-probe"):
                    return fn(*args)
            finally:
                obs.disable()
        return traced

    ident = check_identity(
        "split.forward.obs-enabled-identity",
        rt._forward, (placed, ids, imps),
        _armed(rt._forward), (placed, ids, imps),
        what="obs-enabled forward graph")
    (findings.extend(ident) if ident
     else checked.append("split.forward.obs-enabled-identity"))

    ident = check_identity(
        "split.decode_step.obs-enabled-identity",
        step_fn, (placed, k_cache, v_cache, length, tok),
        _armed(step_fn), (placed, k_cache, v_cache, length, tok),
        what="obs-enabled decode-step graph")
    (findings.extend(ident) if ident
     else checked.append("split.decode_step.obs-enabled-identity"))

    return findings, checked, skipped
