"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main path once, through the entry point a user calls
(``edgellm_tpu.run.main``: ``experiment: "serve"`` -> ``ServeFront`` ->
``ContinuousBatcher`` -> paged pool -> model step), on Qwen2-0.5B at its
published widths and full depth with seeded-random weights and the default
dispatch, then the paper's eval sweep for a few chunks, then checks what came
out by the repo's own means: every Pallas kernel the run dispatched against
its XLA twin, and prefill-then-paged-decode logits against the dense fp32
forward. With four or more devices the same serve path runs split over a
4-stage mesh (``cuts``/``hop_codecs``: boundary hops over real ``ppermute``)
and the split forward is held to the single-device round-trip oracle.

    python3 chip_smoke.py          # on a machine with a TPU; one process

It refuses to run anywhere else: no accelerator -> exit 2, nothing printed on
stdout. Any failed phase raises, so the exit is non-zero and no result line
is printed. On success the LAST stdout line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``;
the full report (device stamp, versions, dispatch, per-phase first-call vs
steady wall times, compile-cache dir) is written to
``chiprun_out/chip_smoke/report.json``. Times in it are wall clocks around
work that ends in a host sync; it reports no rate and no utilization.
"""
from __future__ import annotations

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(ROOT, "chiprun_out", "chip_smoke")

MODEL = "qwen2-0.5b"
SEED = 0
#: pool geometry large enough to leave toy shapes: 8 slots x 1024-token span
BATCHING = {"page_size": 16, "pages_per_slot": 64, "max_slots": 8,
            "num_pages": 513}
N_REQUESTS, NEW_TOKENS = 8, 32
#: one prompt length that is no multiple of 8, one at the sweep's window
PROMPT_LENS = (100, 512)
SWEEP_PARAMS = os.path.join(ROOT, "configs", "qwen_baseline_table.json")
SWEEP_CHUNKS, SWEEP_WINDOW_BATCH = 4, 8
SPLIT = {"cuts": [5, 11, 17],
         "hop_codecs": ["int8_per_token", "int4_per_token", "int8_per_token"]}
#: |system - reference| bound on logits, both sides at
#: default_matmul_precision("highest"): fp32 rounding order through 24 layers
#: measured 1.7e-6 on logits of std 0.6 (first v5e run); a single-pass bf16
#: matmul anywhere (eps 2**-8) leaves >= 1e-2 — so this passes fp32 math and
#: fails anything computed in a lower precision than the configuration states
LOGIT_ATOL = 1e-4
#: split NLL vs the single-device round-trip oracle (__graft_entry__'s bound)
NLL_ATOL = 1e-3


def serve_params(prompt_len: int, *, batching: dict = BATCHING,
                 n_requests: int = N_REQUESTS, new_tokens: int = NEW_TOKENS,
                 split: dict | None = None) -> dict:
    """The inline params.json of one serve soak (``run.py`` accepts JSON)."""
    return {"experiment": "serve",
            "serving": {"admission": {"max_queue_depth": 64},
                        "capacity_round": 16,
                        "soak": {"n_requests": n_requests,
                                 "arrival_rate": 2.0,
                                 "prompt_len": prompt_len,
                                 "max_new_tokens": new_tokens,
                                 "deadline_s": 600.0}},
            "batching": batching, **(split or {})}


def env_phase() -> dict:
    """Versions, device stamp, cache locations — and the condition the rest
    of the smoke stands on: compiled (not interpreted) kernels."""
    from importlib import metadata

    import jax
    import jaxlib

    from edgellm_tpu.codecs import pallas_kernels
    from edgellm_tpu.models import flash_attention
    from edgellm_tpu.utils.startup import (configure_compile_cache,
                                           device_stamp)

    assert not flash_attention._use_interpret(), \
        "attention kernels would run in interpret mode"
    assert not pallas_kernels._use_interpret(), \
        "codec kernels would run in interpret mode"
    cache_dir = configure_compile_cache()
    return {**device_stamp(),
            "jax": jax.__version__, "jaxlib": jaxlib.__version__,
            "libtpu": metadata.version("libtpu"),
            "compile_cache_dir": cache_dir,
            "compile_cache_entries_at_start": (
                len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0),
            "interpret": False}


def dispatch_phase(cfg, param_dtype, *, prompt_lens=PROMPT_LENS,
                   batching: dict = BATCHING, sweep_len: int = 512,
                   split: dict | None = None) -> dict:
    """Which attention plan and which codec implementation each site of the
    run takes — the same gate functions the model code calls, at the same
    shapes."""
    import jax
    import jax.numpy as jnp

    from edgellm_tpu.models.flash_attention import kernel_plan
    from edgellm_tpu.models.paged_kv import decode_read_path, init_pool
    from edgellm_tpu.serve.batching import BatchingConfig

    h, kv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    itemsize = jnp.dtype(param_dtype).itemsize
    pages = (batching["pages_per_slot"], batching["page_size"])
    sites = [{"site": f"serve.prefill[s={s}]", "seq": s,
              "plan": kernel_plan(s, h, kv, hd, itemsize=itemsize)}
             for s in prompt_lens]
    sites.append({"site": "sweep.forward", "seq": sweep_len,
                  "plan": kernel_plan(sweep_len, h, kv, hd,
                                      itemsize=itemsize)})
    # the read paged_kv.decode_read_path picks for the pool the run serves
    # from: the page walk on a TPU where a page is whole tiles
    sites.append({"site": "serve.decode[paged]", "seq": pages[0] * pages[1],
                  "plan": decode_read_path(jax.eval_shape(
                      lambda: init_pool(cfg, 2, pages[1],
                                        BatchingConfig(**batching)
                                        .cache_dtype)))})
    out = {"param_dtype": jnp.dtype(param_dtype).name, "attention": sites}
    if split is not None:
        from edgellm_tpu.parallel.split import apply_default_codec_backend

        codecs = apply_default_codec_backend(list(split["hop_codecs"]))
        out["hop_codecs"] = [
            {"cut": cut, "asked": name, "dispatched": c.name}
            for cut, name, c in zip(split["cuts"], split["hop_codecs"],
                                    codecs)]
    return out


#: (batch, stats kernel?) the run puts through each prefill-kernel site:
#: serving prefills one request at a time; at 4 chunks the sweep's stats
#: forward sees window groups of 1 and 3, and its suffix sees (4 nonzero
#: ratios) x (group) = 4 and 12 rows through the plain kernel
SERVE_CALLS = ((1, False),)
SWEEP_CALLS = ((1, True), (3, True), (4, False), (12, False))


def kernels_phase(cfg, dispatch: dict, *, hop_shapes=()) -> dict:
    """Every Pallas kernel the dispatch selected, compiled on this backend
    at the run's shapes, against its jnp/XLA twin. Raises on a mismatch."""
    from edgellm_tpu.tools.attn_probe import parity_shape
    from edgellm_tpu.tools.pallas_probe import probe_all

    h, kv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    dtype = dispatch["param_dtype"]
    attention = []
    for site in dispatch["attention"]:
        if site["plan"] is None or site["plan"][0] not in ("whole",
                                                           "blocked"):
            continue
        calls = (SERVE_CALLS if site["site"].startswith("serve")
                 else SWEEP_CALLS)
        for b, stats in calls:
            attention.append({"site": site["site"], **parity_shape(
                b, h, kv, site["seq"], hd, dtype=dtype, stats=stats,
                plan=site["plan"])})
    codecs = [probe_all(dim=cfg.hidden_size)]
    codecs += [probe_all(batch=b, seq=s, dim=cfg.hidden_size)
               for b, s in hop_shapes]
    return {"attention": attention,
            "codecs": [{"shape": blk["shape"], "interpret": blk["interpret"],
                        "parity": blk["parity"],
                        "checked": [c["codec"] for c in blk["codecs"]
                                    if "excluded" not in c]}
                       for blk in codecs]}


def launch_ahead_share(report: dict) -> float:
    """Percent of a batcher's launched steps that found the step before them
    unread (``report()``'s ``steps_ahead`` over ``steps``)."""
    return 100.0 * report["steps_ahead"] / max(report["steps"], 1)


def attend_run_share(report: dict) -> float:
    """Percent of the pages a batcher's page walks fetched that went as part
    of a run of adjacent pages, one DMA a run (``report()``'s
    ``attend_pages_in_runs`` over ``attend_pages_walked``)."""
    return (100.0 * report["attend_pages_in_runs"]
            / max(report["attend_pages_walked"], 1))


class _old_order:
    """While entered, every ``ContinuousBatcher.step()`` reads its own step
    before it returns (launch, sync, commit: the order before the batcher ran
    a launch ahead), so a phase can serve the same requests both ways."""

    def __enter__(self):
        from edgellm_tpu.serve.batching import ContinuousBatcher

        self.cls, self.step = ContinuousBatcher, ContinuousBatcher.step

        def step(batcher):
            n = self.step(batcher)
            batcher._drain()
            return n

        ContinuousBatcher.step = step

    def __exit__(self, *exc):
        self.cls.step = self.step


def _soak(params: dict, phase_dir: str, model: str) -> dict:
    from edgellm_tpu import run

    rc = run.main(["--model", model, "--seed", str(SEED),
                   "--params", json.dumps(params),
                   "--output-dir", phase_dir])
    assert rc == 0, f"run.main serve returned {rc}"
    with open(os.path.join(phase_dir, "serve_report.json")) as f:
        return json.load(f)


def serve_phase(name: str, params: dict, vocab_size: int, *,
                model: str = MODEL, out_dir: str = OUT_DIR) -> dict:
    """One serve soak through ``run.main``. All requests must complete, with
    tokens in ``[0, vocab)`` and no step-cache miss inside the soak; and the
    same soak served in the old order (each step read before the next is
    launched) must give every request the same tokens."""
    rep = _soak(params, os.path.join(out_dir, name), model)
    with _old_order():
        old = _soak(params, os.path.join(out_dir, name + "_old_order"), model)
    assert old["batcher"]["steps_ahead"] == 0, old["batcher"]
    assert rep["tokens"] == old["tokens"], (rep["tokens"], old["tokens"])
    soak = params["serving"]["soak"]
    assert rep["outcomes"] == {"completed": soak["n_requests"]}, \
        rep["outcomes"]
    for toks in rep["tokens"]:
        assert (len(toks) == soak["max_new_tokens"]
                and all(0 <= t < vocab_size for t in toks)), toks
    bat = rep["batcher"]
    assert bat["jit_misses"] == 0, bat
    return {"mode": rep["mode"], "platform": rep["platform"],
            "outcomes": rep["outcomes"], "jit_misses": bat["jit_misses"],
            "batched_steps": bat["steps"], "evicted": bat["evicted"],
            "launch_ahead_share": launch_ahead_share(bat),
            "attend_run_share": attend_run_share(bat),
            "tokens_equal_old_order": True,
            "old_order_steady_s": old["drain_s"],
            # first call of every executable, compiles included
            "first_call_s": rep["warmup_s"],
            # the soak itself: prefill and decode steps each end in a sync
            "steady_s": rep["drain_s"],
            "steady_prefill_s": bat["prefill_s"],
            "steady_decode_s": bat["decode_s"]}


def sweep_phase(*, model: str = MODEL, params_path: str = SWEEP_PARAMS,
                out_dir: str = OUT_DIR) -> dict:
    """The paper's eval sweep for a few chunks, twice: the first pass compiles
    every executable, the second is steady. PPL must be finite."""
    import numpy as np

    from edgellm_tpu import run

    out = {}
    for leg in ("first_call", "steady"):
        leg_dir = os.path.join(out_dir, f"sweep_{leg}")
        ckpt = os.path.join(leg_dir, "sweep_checkpoint.json")
        if os.path.exists(ckpt):
            os.remove(ckpt)  # a finished sweep's checkpoint would resume to a no-op
        rc = run.main(["--model", model, "--seed", str(SEED),
                       "--params", params_path,
                       "--max-chunks", str(SWEEP_CHUNKS),
                       "--window-batch", str(SWEEP_WINDOW_BATCH),
                       "--output-dir", leg_dir])
        assert rc == 0, f"run.main sweep returned {rc}"
        with open(os.path.join(leg_dir, "avg_ppl_results.json")) as f:
            res = json.load(f)
        ppl = np.asarray(res["ppl"], np.float64)
        assert res["chunks"] == SWEEP_CHUNKS and np.isfinite(ppl).all(), res
        out[f"{leg}_s"] = res["wall_s"]
        out["ppl_shape"] = list(ppl.shape)
        out["ppl_min_max"] = [float(ppl.min()), float(ppl.max())]
    return out


def reference_phase(cfg, *, batching: dict = BATCHING, prompt_len: int = 100,
                    n_steps: int = 4) -> dict:
    """Prefill (kernel path) then teacher-forced decode through the paged
    cache, logits against the dense fp32 forward (``stats_block=0``: the
    full-probs formulation, no kernel, no cache) on a seeded sequence. Both
    sides at ``default_matmul_precision("highest")`` so the bound is fp32
    rounding, not the MXU's default single bf16 pass."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from edgellm_tpu.models import forward, init_params
    from edgellm_tpu.models.paged_kv import PagedKVCache, paged_decode_step
    from edgellm_tpu.serve.decode import _prefill_jit

    params = init_params(cfg, jax.random.key(SEED))
    ids = np.random.default_rng(SEED).integers(
        1, cfg.vocab_size, size=(1, prompt_len + n_steps)).astype(np.int32)
    span = batching["pages_per_slot"] * batching["page_size"]
    step = jax.jit(paged_decode_step, static_argnames=("cfg",),
                   donate_argnums=(2,))
    errs = []
    with jax.default_matmul_precision("highest"):
        ref, _ = jax.jit(lambda p, x: forward(
            cfg, p, x, capture_stats=True, stats_block=0))(params, ids)
        ref = np.asarray(ref[0])  # (S + n, V)
        assert np.isfinite(ref).all()
        last, cache = _prefill_jit(cfg, params,
                                   jnp.asarray(ids[:, :prompt_len]), span,
                                   None)
        errs.append(float(np.abs(np.asarray(last[0])
                                 - ref[prompt_len - 1]).max()))
        pool = PagedKVCache(cfg, num_pages=batching["num_pages"],
                            page_size=batching["page_size"],
                            max_slots=batching["max_slots"],
                            pages_per_slot=batching["pages_per_slot"])
        slot = pool.alloc_slot()
        pool.adopt(slot, cache.k[:, 0, :prompt_len],
                   cache.v[:, 0, :prompt_len], prompt_len)
        for t in range(n_steps):
            pos = prompt_len + t
            pool.ensure(slot, pos + 1)
            page_table, lengths = pool.device_tables()
            feed = np.zeros((batching["max_slots"],), np.int32)
            feed[slot] = ids[0, pos]
            logits, pool.pool = step(cfg, params, pool.pool, page_table,
                                     lengths, jnp.asarray(feed))
            # (the tables a step was handed are copies: the host may count on
            # while the step has yet to run, as the batcher does)
            got = np.asarray(logits[slot])
            pool.lengths[slot] = pos + 1
            assert np.isfinite(got).all()
            errs.append(float(np.abs(got - ref[pos]).max()))
    assert max(errs) <= LOGIT_ATOL, \
        f"prefill/paged-decode logits off the fp32 reference: {errs}"
    return {"prompt_len": prompt_len, "decode_steps": n_steps,
            "logit_std": float(ref.std()), "logit_max_abs_err": errs,
            "atol": LOGIT_ATOL}


def _device_bytes() -> list:
    import jax

    return [int(d.memory_stats()["peak_bytes_in_use"])
            for d in jax.devices()]


def split_phase(cfg, vocab_size: int, *, prompt_lens=PROMPT_LENS,
                batching: dict = BATCHING, split: dict = SPLIT,
                model: str = MODEL, out_dir: str = OUT_DIR) -> dict:
    """The serve path over a 4-stage mesh, then two checks a bare "all
    completed" would wave through: every stage device really holds its
    weights and pool, and the split forward matches the single-device
    forward that applies each hop codec's encode -> decode round trip at
    its cut."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from edgellm_tpu.models import forward, init_params
    from edgellm_tpu.models.transformer import nll_from_logits
    from edgellm_tpu.parallel import SplitConfig, SplitRuntime, make_stage_mesh

    n_stages = len(split["cuts"]) + 1
    before = _device_bytes()
    out = {"serve": [serve_phase(f"split_serve_{s}",
                                 serve_params(s, batching=batching,
                                              split=split),
                                 vocab_size, model=model, out_dir=out_dir)
                     for s in prompt_lens]}
    assert all(r["mode"] == "batched_split" for r in out["serve"])
    after = _device_bytes()

    params = init_params(cfg, jax.random.key(SEED))
    split_cfg = SplitConfig(cuts=tuple(split["cuts"]),
                            hop_codecs=tuple(split["hop_codecs"]))
    rt = SplitRuntime(cfg, split_cfg, make_stage_mesh(n_stages))
    placed = rt.place_params(params)
    pool = rt.init_paged_pool(batching["num_pages"], batching["page_size"])
    stage_devs = [d for d in np.asarray(rt.mesh.devices).reshape(-1)]
    # one stage's share: its layer group (every stage is padded to
    # stage_size layers) plus its slice of the paged K/V pool
    stage_bytes = sum(int(a.nbytes) // n_stages
                      for a in jax.tree_util.tree_leaves(
                          [placed["layers"], pool]))
    for tree in (placed["layers"], pool):
        for a in jax.tree_util.tree_leaves(tree):
            held = {s.device for s in a.addressable_shards}
            assert held == set(stage_devs), (a.shape, held)
    grown = [a - b for a, b in zip(after, before)]
    for d, g in zip(jax.devices(), grown):
        if d in stage_devs[1:]:
            # stages 1.. held nothing before the split serve ran
            assert g >= stage_bytes, \
                f"{d} grew {g} B < one stage's {stage_bytes} B"
    assert after[0] >= stage_bytes

    ids = jnp.asarray(np.random.default_rng(SEED + 1).integers(
        0, cfg.vocab_size, (1, 128)))
    # both sides at full matmul precision, like reference_phase: at the
    # default single bf16 pass the sharded and unsharded graphs already
    # round differently (3e-4 on the NLL, first four-chip run)
    with jax.default_matmul_precision("highest"):
        nll = float(nll_from_logits(rt.forward(placed, ids), ids))
        ref_logits, _ = jax.jit(lambda p, x: forward(
            cfg, p, x,
            boundary_fn=split_cfg.roundtrip_boundary_fn()))(params, ids)
        oracle = float(nll_from_logits(ref_logits, ids))
    assert np.isfinite(nll) and abs(nll - oracle) < NLL_ATOL, (nll, oracle)
    out.update({"stage_devices": [str(d) for d in stage_devs],
                "stage_bytes_expected": stage_bytes,
                "peak_bytes_growth_per_device": grown,
                "hop_codecs": [c.name for c in rt.codecs],
                "split_nll": nll, "oracle_nll": oracle,
                "nll_abs_diff": abs(nll - oracle), "nll_atol": NLL_ATOL})
    return out


def _evict_readmit(cfg, bcfg, prompt_len: int, n_new: int,
                   evict_after: int, tweak=lambda params: params) -> tuple:
    """One stream admitted, stepped, evicted and readmitted through
    ``ContinuousBatcher`` on the chip beside a short neighbour, float32 at
    ``highest``: its tokens equal an undisturbed stream's, served a launch
    ahead and in the old order, and ``forward`` over prompt + tokens puts
    each of them first. Returns (the batcher's report with its
    ``launch_ahead_share`` and the ``served`` tokens, the largest gap over
    the largest logit)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from edgellm_tpu.models import forward, init_params
    from edgellm_tpu.serve.batching import ContinuousBatcher

    params = tweak(init_params(cfg, jax.random.key(SEED)))
    prompt = np.random.default_rng(SEED).integers(
        1, cfg.vocab_size, size=prompt_len).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        calm = ContinuousBatcher(cfg, params, bcfg)
        sid = calm.submit(prompt, n_new, rng_seed=1)
        want = calm.run()[sid]
        with _old_order():
            old = ContinuousBatcher(cfg, params, bcfg)
            sid = old.submit(prompt, n_new, rng_seed=1)
            in_old_order = old.run()[sid]
        assert old.report()["steps_ahead"] == 0
        assert np.array_equal(want, in_old_order), (
            want.tolist(), in_old_order.tolist())
        b = ContinuousBatcher(cfg, params, bcfg)
        b.submit(prompt[:9], n_new, rng_seed=2)           # a neighbour
        sid = b.submit(prompt, n_new, rng_seed=1)
        for _ in range(evict_after):
            b.step()
        b.evict(sid)
        b.pool.check_invariants()
        got = b.run()[sid]
        report = b.report()
        seq = np.concatenate([prompt, got])[None]
        logits = np.asarray(jax.jit(
            lambda p, x: forward(cfg, p, x)[0])(params, jnp.asarray(seq))[0])
    assert np.array_equal(got, want), (got.tolist(), want.tolist())
    rows = logits[prompt_len - 1:-1]                      # predict got[i]
    gaps = rows.max(axis=-1) - rows[np.arange(n_new), got]
    scale = float(np.abs(rows).max())
    assert gaps.max() <= 1e-4 * scale, (gaps.tolist(), scale)
    assert report["evicted"] == 1
    # the eviction's drain and the launch after it are the old order; every
    # other launch found the step before it unread
    report["launch_ahead_share"] = launch_ahead_share(report)
    report["attend_run_share"] = attend_run_share(report)
    print(f"[chip_smoke] {cfg.family}: attend_run_share "
          f"{report['attend_run_share']:.1f}% of "
          f"{report['attend_pages_walked']} walked pages in "
          f"{report['attend_dmas']} DMAs", flush=True)
    report["served"] = got.tolist()
    assert report["steps_ahead"] >= report["steps"] - 3, report
    return report, float(gaps.max() / scale)


def _prefill_kernels(cfg, bcfg, prompt_len: int, tweak=lambda p: p) -> list:
    """The Pallas kernels of the prefill an admission of ``prompt_len``
    tokens runs: the names of the custom calls in ``_prefill_jit`` as
    ``ContinuousBatcher._admit_fill`` calls it, lowered for this backend
    (traced, not compiled: what ``report()`` says of a path is a rule's
    answer, this is the program's)."""
    import re

    import jax
    import jax.numpy as jnp

    from edgellm_tpu.models import init_params
    from edgellm_tpu.serve.decode import _prefill_jit

    params = tweak(init_params(cfg, jax.random.key(SEED)))
    text = _prefill_jit.lower(
        cfg, params, jnp.zeros((1, prompt_len), jnp.int32), bcfg.span,
        bcfg.compute_dtype).as_text()
    return sorted(set(re.findall(r'kernel_name\s*=\s*"(\w+)"', text)))


def hybrid_phase(*, prompt_len: int = 300, n_new: int = 24,
                 evict_after: int = 5) -> dict:
    """A tiny ``granitemoehybrid`` stream (Mamba-2 and NoPE attention layers,
    routed + shared experts, float32) admitted, stepped, evicted and
    readmitted through ``ContinuousBatcher`` on the chip: the recurrent state
    leaves the device with the K/V rows and comes back. The prompt is longer
    than ``moe.DENSE_MAX_TOKENS`` and the expert layers are whole lane tiles
    (D = F = 128), so the prefill takes the grouped expert products as the
    kernel (``models/grouped_matmul.py``: the phase asserts
    ``grouped_product``), whose rows past the last group hold anything, and
    many chunks of the scan; the same prefill through ``jax.lax.ragged_dot``
    gives the same logits."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from edgellm_tpu.models import grouped_matmul, init_params
    from edgellm_tpu.models.configs import tiny_hybrid_config
    from edgellm_tpu.models.hybrid import prefill_hybrid
    from edgellm_tpu.serve.batching import BatchingConfig

    # 4 of the router's 8 experts held: half the assignments are to absent
    # experts, the rows past the last group
    cfg = dataclasses.replace(tiny_hybrid_config(
        hidden_size=128, experts_held=4, expert_offset=2), expert_width=128)
    report, gap = _evict_readmit(
        cfg, BatchingConfig(page_size=16, num_pages=73, max_slots=3,
                            pages_per_slot=24), prompt_len, n_new, evict_after)
    assert report["state_bytes"] > 0
    assert report["grouped_product"] == grouped_matmul.PALLAS_GROUPED, \
        report["grouped_product"]
    params = init_params(cfg, jax.random.key(SEED))
    ids = jnp.asarray(np.random.default_rng(SEED).integers(
        1, cfg.vocab_size, size=(1, prompt_len)), jnp.int32)

    def logits():
        with jax.default_matmul_precision("highest"):
            return np.asarray(jax.jit(lambda p, x: prefill_hybrid(
                cfg, p, x, prompt_len)[0])(params, ids)[0])

    kernel, on_tpu = logits(), grouped_matmul._on_tpu
    grouped_matmul._on_tpu = lambda: False       # the oracle's path
    try:
        ragged = logits()
    finally:
        grouped_matmul._on_tpu = on_tpu
    paths_gap = float(np.abs(kernel - ragged).max() / np.abs(ragged).max())
    assert paths_gap <= 1e-4, paths_gap
    return {"tokens": int(n_new), "evicted": report["evicted"],
            "state_bytes": report["state_bytes"],
            "routed_local": report["routed_local"],
            "grouped_product": report["grouped_product"],
            "kernel_vs_ragged_dot_over_logit_max": paths_gap,
            "launch_ahead_share": report["launch_ahead_share"],
            "gap_max_over_logit_max": gap}


def _ring_stream(cfg, prompt_len: int, n_new: int, evict_after: int) -> tuple:
    """``_evict_readmit`` for a stack with window layers of 40 keys: a ring
    of 4 pages of 16 rows a slot in the window group's own pool, a prompt of
    4.7 ring turns adopted by its tail, 120 steps that turn the ring twice
    more, and the ring leaving the device and coming back in between (the
    benchmark's cells never evict). Rows of 2 KV heads of 64 are one whole
    lane tile, so both groups' reads are the page walk: the ring's rows are
    masked by the position each holds inside the kernel, beside a neighbour
    whose ring has not turned. Returns (the report, the phase's row)."""
    from edgellm_tpu.models.paged_kv import PAGE_WALK
    from edgellm_tpu.serve.batching import BatchingConfig

    bcfg = BatchingConfig(page_size=16, num_pages=73, max_slots=3,
                          pages_per_slot=28)
    report, gap = _evict_readmit(cfg, bcfg, prompt_len, n_new, evict_after)
    assert report["window_rows_capacity"] == 3 * 4 * 16
    assert report["decode_read"] == PAGE_WALK, report["decode_read"]
    assert report["attend_fetches_per_page"] == 1   # a page is one DMA
    assert report["window_read"] == PAGE_WALK, report["window_read"]
    assert 0 < report["window_pages_walked"] <= report["window_pages_spanned"]
    return report, {"tokens": int(n_new), "evicted": report["evicted"],
                    "window_pages": cfg.window_pages(16),
                    "decode_read": report["decode_read"],
                    "attend_fetches_per_page":
                        report["attend_fetches_per_page"],
                    "attend_run_share": report["attend_run_share"],
                    "window_read": report["window_read"],
                    "window_pages_walked": report["window_pages_walked"],
                    "window_pages_spanned": report["window_pages_spanned"],
                    "routed_local": report["routed_local"],
                    "launch_ahead_share": report["launch_ahead_share"],
                    "gap_max_over_logit_max": gap}


def window_phase(*, prompt_len: int = 300, n_new: int = 120,
                 evict_after: int = 70) -> dict:
    """A tiny ``mellum`` stream (sliding-window layers of 40 keys beside full
    ones, YaRN on the full layers only, routed experts with none shared, an
    untied head, float32) through the same admit / step / evict / readmit
    (:func:`_ring_stream`)."""
    from edgellm_tpu.models.configs import tiny_mellum_config

    return _ring_stream(tiny_mellum_config(sliding_window=40, head_dim=64),
                        prompt_len, n_new, evict_after)[1]


def afmoe_phase(*, prompt_len: int = 300, n_new: int = 120,
                evict_after: int = 70) -> dict:
    """A tiny ``afmoe`` stream (a leading dense layer and a period of expert
    layers; window layers of 40 keys that rotate beside a full one that takes
    no positions; q/k norms, the output gate, four norms a layer; sigmoid
    routing with a nonzero selection bias and a shared expert, float32) the
    same way: the ring group and the full group both leave the device and
    come back. The prompt is past ``moe.DENSE_MAX_TOKENS``: the prefill takes
    the grouped expert products."""
    from edgellm_tpu.models.configs import tiny_afmoe_config

    cfg = tiny_afmoe_config(sliding_window=40, head_dim=64)
    report, row = _ring_stream(cfg, prompt_len, n_new, evict_after)
    assert len(report["expert_tokens"]) == cfg.expert_layers == 4
    return {**row, "expert_layers": cfg.expert_layers}


def latent_phase(*, prompt_len: int = 300, n_new: int = 40,
                 evict_after: int = 20) -> dict:
    """A tiny ``mistral4`` stream (latent attention: one cached row a
    position for all heads, interleaved rotary pairs on the rope lanes, the
    query scale and YaRN stepping inside the prompt; routed experts, half of
    them absent, plus a shared one; float32) through the same admit / step /
    evict / readmit: the prefill attends EXPANDED, every step ABSORBED over
    the one-leaf pool across two page boundaries, the rows leave the device
    and come back in between, and ``forward`` (expanded at every position)
    over prompt + tokens puts each served token first. The step's read is
    the page walk (pages of 16 float32 rows of 128 lanes are whole tiles):
    the evicted stream's rows come back into other pages and its tokens
    reproduce through the kernel."""
    from edgellm_tpu.models.configs import tiny_mistral4_config
    from edgellm_tpu.models.paged_kv import PAGE_WALK
    from edgellm_tpu.serve.batching import BatchingConfig

    cfg = tiny_mistral4_config(experts_held=4, expert_offset=2)
    bcfg = BatchingConfig(page_size=16, num_pages=73, max_slots=3,
                          pages_per_slot=24)
    report, gap = _evict_readmit(cfg, bcfg, prompt_len, n_new, evict_after)
    assert report["latent_rows_capacity"] == 72 * 16
    assert report["kv_row_bytes"] == cfg.kv_row_lanes * 4
    assert report["decode_read"] == PAGE_WALK, report["decode_read"]
    assert report["attend_fetches_per_page"] == 1   # a page is one DMA
    assert 0 < report["attend_pages_walked"] < report["attend_pages_spanned"]
    return {"tokens": int(n_new), "evicted": report["evicted"],
            "decode_read": report["decode_read"],
            "attend_fetches_per_page": report["attend_fetches_per_page"],
            "attend_run_share": report["attend_run_share"],
            "attend_pages_walked": report["attend_pages_walked"],
            "attend_pages_spanned": report["attend_pages_spanned"],
            "kv_row_bytes": report["kv_row_bytes"],
            "routed_local": report["routed_local"],
            "launch_ahead_share": report["launch_ahead_share"],
            "gap_max_over_logit_max": gap}


def longcat_phase(*, prompt_len: int = 300, n_new: int = 40,
                  evict_after: int = 20) -> dict:
    """A tiny ``longcat_flash`` stream (two latent sublayers and two dense
    SwiGLUs a layer with the routed layer on a shortcut beside them; 8 routed
    experts, half of them absent, and 4 identity experts chosen top-5 of 12 by
    the whole softmax plus a selection bias; both rank scales; float32)
    through the same admit / step / evict / readmit: the rows of all FOUR
    sublayers go through the one-leaf pool and the page walk, leave the
    device and come back, and ``forward`` over prompt + tokens puts each
    served token first. The router is seeded 15x wider than ``init_params``
    leaves it, so that the chosen set varies with the token; the prompt is
    past ``moe.DENSE_MAX_TOKENS``: the prefill takes the grouped products
    with the identity assignments in no group."""
    from edgellm_tpu.models.configs import tiny_longcat_flash_config
    from edgellm_tpu.models.paged_kv import PAGE_WALK
    from edgellm_tpu.serve.batching import BatchingConfig

    def wider_router(params):
        return {**params, "moe": [
            {**mp, "shortcut": {**mp["shortcut"],
                                "router": mp["shortcut"]["router"] * 15.0}}
            if "shortcut" in mp else mp for mp in params["moe"]]}

    cfg = tiny_longcat_flash_config(experts_held=4, expert_offset=2)
    bcfg = BatchingConfig(page_size=16, num_pages=73, max_slots=3,
                          pages_per_slot=24)
    report, gap = _evict_readmit(cfg, bcfg, prompt_len, n_new, evict_after,
                                 wider_router)
    assert cfg.latent_layers == 4 and len(report["expert_tokens"]) == 2
    assert report["latent_rows_capacity"] == 72 * 16
    assert report["decode_read"] == PAGE_WALK, report["decode_read"]
    assert report["attend_fetches_per_page"] == 1   # a page is one DMA
    assert 0 < report["attend_pages_walked"] < report["attend_pages_spanned"]
    made = report["routed_assignments"]
    assert 0 < report["zero_assignments"] < made
    assert 0 < report["routed_local"] < made - report["zero_assignments"]
    return {"tokens": int(n_new), "evicted": report["evicted"],
            "decode_read": report["decode_read"],
            "attend_fetches_per_page": report["attend_fetches_per_page"],
            "attend_run_share": report["attend_run_share"],
            "attend_pages_walked": report["attend_pages_walked"],
            "attend_pages_spanned": report["attend_pages_spanned"],
            "kv_row_bytes": report["kv_row_bytes"],
            "routed_assignments": made,
            "routed_local": report["routed_local"],
            "zero_assignments": report["zero_assignments"],
            "launch_ahead_share": report["launch_ahead_share"],
            "gap_max_over_logit_max": gap}


def shortconv_phase(*, prompt_len: int = 300, n_new: int = 40,
                    evict_after: int = 20) -> dict:
    """A tiny ``lfm2_moe`` stream (gated short convolutions of 3 taps that
    keep a window of two rows a slot, 5:1 beside a rotated GQA layer with
    per-head q/k norms; two dense layers, then experts routed by sigmoid
    scores with a nonzero selection bias and none shared; a tied head,
    float32) through the same admit / step / evict / readmit: the windows,
    the state store's ONE leaf, leave the device with the K/V rows and come
    back, and ``forward`` over prompt + tokens puts each served token first.
    The attention layer's read is the page walk (rows of 2 KV heads x 64 =
    one lane tile), the prompt is past ``moe.DENSE_MAX_TOKENS`` and the
    expert layers are whole lane tiles, so the prefill takes the grouped
    products as the kernel. The matrices are seeded 3x wider than
    ``init_params`` leaves them (0.02 x sqrt(256) shrinks a projection's
    input threefold) and the router 15x: under a tied table a stack whose
    layers add next to nothing repeats its last token, and such a stream
    would pass with any window."""
    import dataclasses

    import jax
    import numpy as np

    from edgellm_tpu.models import grouped_matmul
    from edgellm_tpu.models.configs import tiny_lfm2_moe_config
    from edgellm_tpu.models.paged_kv import INDEX_WALK, PAGE_WALK
    from edgellm_tpu.serve.batching import BatchingConfig

    def wider(params):
        def scale(path, a):
            name = path[-1].key
            if name in ("router", "router_bias"):
                return a * 15.0
            return a * 3.0 if name.startswith("w") else a
        return jax.tree_util.tree_map_with_path(scale, params)

    cfg = dataclasses.replace(tiny_lfm2_moe_config(
        hidden_size=256, num_heads=4, num_kv_heads=2), expert_width=128)
    bcfg = BatchingConfig(page_size=16, num_pages=73, max_slots=3,
                          pages_per_slot=24)
    report, gap = _evict_readmit(cfg, bcfg, prompt_len, n_new, evict_after,
                                 wider)
    assert cfg.conv_layers == 5 and cfg.kv_layers == 1
    assert report["state_leaf_bytes"] == {"conv": 5 * 3 * 2 * 256 * 4}
    assert report["state_bytes"] == 5 * 3 * 2 * 256 * 4
    assert report["decode_read"] == PAGE_WALK, report["decode_read"]
    assert report["attend_fetches_per_page"] == 1   # a page is one DMA
    assert 0 < report["attend_pages_walked"] < report["attend_pages_spanned"]
    assert report["grouped_product"] == grouped_matmul.PALLAS_GROUPED, \
        report["grouped_product"]
    assert len(report["expert_tokens"]) == cfg.expert_layers == 4
    assert len(np.unique(report["served"])) > n_new // 4, report["served"]
    return {"tokens": int(n_new), "distinct_tokens":
            int(len(np.unique(report["served"]))),
            "evicted": report["evicted"],
            "state_leaf_bytes": report["state_leaf_bytes"],
            "decode_read": report["decode_read"],
            "attend_fetches_per_page": report["attend_fetches_per_page"],
            "attend_run_share": report["attend_run_share"],
            "grouped_product": report["grouped_product"],
            "routed_local": report["routed_local"],
            "launch_ahead_share": report["launch_ahead_share"],
            "gap_max_over_logit_max": gap}


def sparse_phase(*, prompt_len: int = 300, n_new: int = 40,
                 evict_after: int = 20) -> dict:
    """A tiny ``keye_vl2`` stream (sparse-attention layers: rotated,
    q/k-normed GQA whose query attends the 64 positions its indexer scores
    highest of the 300-340 it holds, an index key a position in the pool's
    second leaf; experts routed by the softmax over the chosen, none shared;
    an untied head, float32) through the same admit / step / evict /
    readmit: the index keys leave the device with the K/V rows and come
    back, and ``forward`` (the block-masked prefill form) over prompt +
    tokens puts each served token first, on the masked page walk a TPU's
    pool takes. ``wv`` is seeded 9x wider and the other matrices 3x, so that
    which rows are attended moves the logits. Then
    :func:`selection_on_the_chip`."""
    import dataclasses

    import jax
    import numpy as np

    from edgellm_tpu.models import grouped_matmul, sparse_attn
    from edgellm_tpu.models.configs import tiny_keye_vl2_config
    from edgellm_tpu.models.paged_kv import INDEX_WALK, PAGE_WALK
    from edgellm_tpu.serve.batching import BatchingConfig

    def wider(params):
        def scale(path, a):
            name = path[-1].key
            if name == "router":
                return a * 15.0
            return a * (9.0 if name == "wv" else 3.0) \
                if name.startswith("w") else a
        return jax.tree_util.tree_map_with_path(scale, params)

    cfg = dataclasses.replace(tiny_keye_vl2_config(
        hidden_size=256, num_heads=4, num_kv_heads=2, head_dim=64,
        index_heads=4, index_head_dim=64, index_topk=64), expert_width=128)
    bcfg = BatchingConfig(page_size=16, num_pages=73, max_slots=3,
                          pages_per_slot=24)
    report, gap = _evict_readmit(cfg, bcfg, prompt_len, n_new, evict_after,
                                 wider)
    # rows of 2 x 128 lanes on a TPU: the page walk of a GQA layer with the
    # selection as a mask (the row gather is every other backend's read, and
    # tests/test_keye_vl2.py holds it to the same reference on the CPU)
    assert report["sparse_read"] == sparse_attn.MASKED_WALK, report
    assert report["decode_read"] == PAGE_WALK
    assert 0 < report["attend_pages_walked"] < report["attend_pages_spanned"]
    # the prefill's blocks (heads of 64 lanes, 300 rows padded to 320) attend
    # in the masked kernel, and so does ``forward`` on the other side: what
    # the report names, and what the admission's program holds
    assert report["sparse_prefill"] == sparse_attn.MASKED_KERNEL, report
    kernels = _prefill_kernels(cfg, bcfg, prompt_len, wider)
    assert "masked_attention" in kernels, kernels
    # the index keys (pages of 16 x 128 float32 lanes, eight to a run) are
    # scored where they lie, the prompt's whole groups as runs
    assert report["index_read"] == INDEX_WALK, report
    assert 0 < report["index_pages_in_runs"] <= \
        report["index_pages_walked"] == report["attend_pages_walked"]
    assert report["kv_row_bytes"] == (2 * 128 + 128) * 4
    assert 0 < report["sparse_rows_attended"] < report["sparse_rows_live"]
    assert report["index_rows_scored"] == report["sparse_rows_live"]
    assert report["grouped_product"] == grouped_matmul.PALLAS_GROUPED, \
        report["grouped_product"]
    assert len(np.unique(report["served"])) > n_new // 4, report["served"]
    return {"tokens": int(n_new), "distinct_tokens":
            int(len(np.unique(report["served"]))),
            "evicted": report["evicted"],
            "sparse_read": report["sparse_read"],
            "index_read": report["index_read"],
            "sparse_prefill": report["sparse_prefill"],
            "prefill_kernels": kernels,
            "index_run_share": 100.0 * report["index_pages_in_runs"]
            / report["index_pages_walked"],
            "sparse_selected_share": 100.0 * report["sparse_rows_attended"]
            / report["sparse_rows_live"],
            "kv_row_bytes": report["kv_row_bytes"],
            "grouped_product": report["grouped_product"],
            "launch_ahead_share": report["launch_ahead_share"],
            "gap_max_over_logit_max": gap,
            "selection": selection_on_the_chip()}


def _selection_overlap(family: str, depths, k: int, idx, want, keep,
                       at_least: float) -> dict:
    """What both selection checks end on: the masked walk's mask is the row
    ids' set, and a slot a depth the share of the float32 ``top_k`` set the
    step's own ids hold (asserted above ``at_least``) beside what the newest
    ``k`` positions would share."""
    import numpy as np

    idx, want, keep = np.asarray(idx), np.asarray(want), np.asarray(keep)
    for slot in range(len(depths)):
        assert set(np.flatnonzero(keep[slot])) == set(idx[slot].tolist())
    out = {}
    for slot, depth in enumerate(depths):
        exact = set(want[slot].tolist())
        overlap = len(exact & set(idx[slot].tolist())) / k
        newest = len(exact & set(range(depth + 1 - k, depth + 1))) / k
        out[str(depth)] = {"overlap": overlap, "newest_2048": newest}
        print(f"[chip_smoke] {family} selection at depth {depth}: "
              f"{100 * overlap:.2f}% of the float32 top-{k} set, the "
              f"newest {k} positions would share {100 * newest:.1f}%",
              flush=True)
        assert overlap > at_least and newest < 0.5, out
    return out


def selection_on_the_chip(depths=(8192, 12288, 16384, 20479)) -> dict:
    """The selection at the benchmark cell's widths and depths: one
    published-width sparse layer in bfloat16, a slot a depth whose index keys
    lie in the pages of a pool of the cell's geometry; the row ids a decode
    step's own code chooses (index keys scored where they lie by the index
    walk, held here to the page gather's scores on every live row; bf16
    operands into float32 scores, ``select``, the flat ids) against
    ``jax.lax.top_k`` of float32 scores at ``highest`` from the SAME weights
    and cache. A row near the 2048th place may flip under bf16 operands; a
    wrong selection (the newest 2048 positions, the cheap answer) shares a
    fifth of the set. The benchmark cell's ``correct`` sees a wrong
    selection through the logits (the newest 2048 in place of the chosen
    reads ``gap_mean`` 0.31-0.34 against a limit of 0.029: PERF.md §6 "PR
    47"); this line says HOW MANY rows the stated precision itself flips,
    which is most of that cell's sound ``gap_mean``."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from edgellm_tpu.models import init_params, paged_kv, sparse_attn
    from edgellm_tpu.models.configs import KEYE_VL_2_0_30B_A3B

    cfg = dataclasses.replace(
        KEYE_VL_2_0_30B_A3B, num_layers=1,
        layer_types=("sparse_attention",), experts_held=1, vocab_size=256)
    k, ps, pps = cfg.index_topk, 16, 1280
    params = init_params(cfg, jax.random.key(SEED), dtype=jnp.bfloat16)
    lp = {name: a[0] for name, a in params["sparse"].items()}
    # the head weights at the benchmark's std (logits of std 1)
    lp["w_index"] = lp["w_index"] * (1.0 / (0.02 * cfg.hidden_size ** 0.5))
    span = ps * pps
    cache = paged_kv.PagedKVCache(
        cfg, num_pages=len(depths) * pps + 1, page_size=ps,
        max_slots=len(depths), pages_per_slot=pps, dtype=jnp.bfloat16)
    rope = sparse_attn.index_rope(cfg, span)

    @jax.jit
    def keys_of(x):           # a sequence's index keys, as cached
        return sparse_attn.project_index(
            cfg, lp, x, sparse_attn.rotate_rows(*rope))[1]

    xs = []
    for slot, depth in enumerate(depths):
        x = jax.random.normal(jax.random.key(SEED + slot),
                              (span, cfg.hidden_size), jnp.bfloat16)
        xs.append(x[depth])                    # the query's layer input
        assert cache.alloc_slot() == slot
        zeros = jnp.zeros((1, depth, cfg.num_kv_heads, cfg.head_dim),
                          jnp.bfloat16)
        cache.adopt(slot, zeros, zeros, depth,
                    index=keys_of(x)[None, :depth])
    table, lengths = cache.device_tables()
    x = jnp.stack(xs)
    at = (rope[0][lengths], rope[1][lengths])

    @jax.jit
    def chosen(pool, x):       # the step's own path, bf16 operands
        qi, ik, wi = sparse_attn.project_index(
            cfg, lp, x, sparse_attn.rotate_rows(*at))
        pool = paged_kv.write_rows(
            pool, 0, table, lengths,
            jnp.zeros((len(depths), 1, cfg.num_kv_heads, cfg.head_dim),
                      jnp.bfloat16),
            jnp.zeros((len(depths), 1, cfg.num_kv_heads, cfg.head_dim),
                      jnp.bfloat16), index=ik)
        scores = sparse_attn.index_scores_paged(qi, wi, pool, 0, table,
                                                lengths + 1)
        rows = paged_kv._gather_pages(pool.ik, 0, table)
        live = jnp.arange(span)[None, :] < (lengths + 1)[:, None]
        off = jnp.max(jnp.where(live, jnp.abs(
            scores - sparse_attn.index_scores(qi, wi, rows)), 0.0))
        # (the row gather's ids; the masked walk's mask is the same set:
        # asserted below)
        idx, _ = sparse_attn.select(scores, lengths + 1, k)
        keep = sparse_attn.selection_mask(scores, live, k)
        # the same weights and cache in float32 at ``highest``
        with jax.default_matmul_precision("highest"):
            f32 = {n: a.astype(jnp.float32) for n, a in lp.items()}
            lq, _, lw = sparse_attn.project_index(
                cfg, f32, x.astype(jnp.float32),
                sparse_attn.rotate_rows(*at))
            exact = sparse_attn.index_scores(lq, lw,
                                             rows.astype(jnp.float32))
        want, _ = sparse_attn.select(exact, lengths + 1, k)
        return idx, want, keep, pool, off, jnp.max(jnp.abs(exact))

    assert paged_kv.index_read_path(cache.pool) == paged_kv.INDEX_WALK
    idx, want, keep, cache.pool, off, largest = chosen(cache.pool, x)
    # float32 sums of the same bf16 products, in another order
    assert float(off) <= 1e-5 * float(largest), (float(off), float(largest))
    return _selection_overlap("keye_vl2", depths, k, idx, want, keep, 0.98)


def sparse_latent_phase(*, prompt_len: int = 300, n_new: int = 40,
                        evict_after: int = 20) -> dict:
    """A tiny ``deepseek_v32`` stream (sparse latent layers: latent attention
    whose query attends the 64 positions its indexer scores highest of the
    300-340 it holds, the indexer's query from the q latent and its first 32
    lanes rotated; a latent row and an index key a position in the two
    leaves of one pool; a leading dense layer, then sigmoid routing over 4
    groups of 4 of which 2 are kept, a shared expert; float32) through the
    same admit / step / evict / readmit: BOTH leaves leave the device and
    come back, and ``forward`` (the masked kernel's expanded form) over
    prompt + tokens puts each served token first, on the index walk and the
    masked walk of the latent rows a TPU's pool takes. ``wkv_b`` is seeded
    4.5x wider and the other matrices 3x, so that which rows are attended
    moves the logits and the stream is no repeat of one token (at 9x the
    values' 128 lanes drown the rest: token 70 thirty times of forty). Then
    :func:`latent_selection_on_the_chip`."""
    import dataclasses

    import jax
    import numpy as np

    from edgellm_tpu.models import grouped_matmul, sparse_attn
    from edgellm_tpu.models.configs import tiny_deepseek_v32_config
    from edgellm_tpu.models.paged_kv import INDEX_WALK, PAGE_WALK
    from edgellm_tpu.serve.batching import BatchingConfig

    def wider(params):
        def scale(path, a):
            name = path[-1].key
            if name in ("router", "router_bias"):
                return a * 15.0
            return a * (4.5 if name == "wkv_b" else 3.0) \
                if name.startswith("w") or name.startswith("shared") else a
        return jax.tree_util.tree_map_with_path(scale, params)

    # rows of [c 96 | k_rope 32] = 128 lanes and index keys of 128: whole
    # tiles, so that both walks are what the step is built on; heads of 160 +
    # 32 key lanes and 128 value lanes, a lane tile and a half and one as
    # the published 192 and 128 are, so that the prefill is the cell's: the
    # masked kernel over keys and values rebuilt a group of heads at a time
    cfg = dataclasses.replace(tiny_deepseek_v32_config(
        hidden_size=256, num_heads=4, index_heads=4, index_head_dim=128,
        index_topk=64), explicit_head_dim=192, qk_rope_head_dim=32,
        kv_lora_rank=96, v_head_dim=128, q_lora_rank=64, expert_width=128,
        shared_width=128)
    bcfg = BatchingConfig(page_size=16, num_pages=73, max_slots=3,
                          pages_per_slot=24)
    report, gap = _evict_readmit(cfg, bcfg, prompt_len, n_new, evict_after,
                                 wider)
    assert report["sparse_read"] == sparse_attn.MASKED_WALK, report
    assert report["decode_read"] == PAGE_WALK
    assert 0 < report["attend_pages_walked"] < report["attend_pages_spanned"]
    # (the body's 300 rows expanded, four heads a group: what the report
    # names, and what the admission's program holds)
    assert report["sparse_prefill"] == sparse_attn.MASKED_KERNEL, report
    kernels = _prefill_kernels(cfg, bcfg, prompt_len, wider)
    assert "masked_attention" in kernels, kernels
    assert report["index_read"] == INDEX_WALK, report
    assert 0 < report["index_pages_in_runs"] <= \
        report["index_pages_walked"] == report["attend_pages_walked"]
    assert report["kv_row_bytes"] == (128 + 128) * 4
    assert report["latent_rows_capacity"] == 72 * 16
    assert 0 < report["sparse_rows_attended"] < report["sparse_rows_live"]
    assert report["index_rows_scored"] == report["sparse_rows_live"]
    assert report["grouped_product"] == grouped_matmul.PALLAS_GROUPED, \
        report["grouped_product"]
    assert len(np.unique(report["served"])) > n_new // 4, report["served"]
    return {"tokens": int(n_new), "distinct_tokens":
            int(len(np.unique(report["served"]))),
            "evicted": report["evicted"],
            "sparse_read": report["sparse_read"],
            "index_read": report["index_read"],
            "sparse_prefill": report["sparse_prefill"],
            "prefill_kernels": kernels,
            "index_run_share": 100.0 * report["index_pages_in_runs"]
            / report["index_pages_walked"],
            "sparse_selected_share": 100.0 * report["sparse_rows_attended"]
            / report["sparse_rows_live"],
            "kv_row_bytes": report["kv_row_bytes"],
            "grouped_product": report["grouped_product"],
            "launch_ahead_share": report["launch_ahead_share"],
            "gap_max_over_logit_max": gap,
            "selection": latent_selection_on_the_chip()}


def latent_selection_on_the_chip(depths=(8192, 12288, 16384, 20479)) -> dict:
    """:func:`selection_on_the_chip` for the ``deepseek_v32`` cell's indexer:
    one published-width sparse latent layer in bfloat16 (64 index heads of
    128 lanes, the query from the q latent, the first 64 lanes rotated by
    the YaRN table), a slot a depth whose index keys lie in the second leaf
    of a pool of the cell's geometry; the row ids the step's own code
    chooses (the index walk's scores, held to the page gather's on every live
    row; ``select`` and the masked walk's mask, one set) against
    ``jax.lax.top_k`` of float32 scores at ``highest`` from the SAME weights
    and cache."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from edgellm_tpu.models import (init_params, mla, paged_kv, sparse_attn,
                                    sparse_mla)
    from edgellm_tpu.models.configs import DEEPSEEK_V3_2_EXP
    from edgellm_tpu.models.transformer import precompute_rope

    cfg = dataclasses.replace(
        DEEPSEEK_V3_2_EXP, num_layers=2, num_dense_layers=1,
        layer_types=("sparse_latent_attention",) * 2, experts_held=1,
        expert_width=128, shared_width=128, intermediate_size=128,
        vocab_size=256)
    k, ps, pps = cfg.index_topk, 16, 1280
    params = init_params(cfg, jax.random.key(SEED), dtype=jnp.bfloat16)
    lp = {name: a[0] for name, a in params["sparse_latent"].items()}
    # the head weights at the benchmark's std (logits of std 1)
    lp["w_index"] = lp["w_index"] * (1.0 / (0.02 * cfg.hidden_size ** 0.5))
    span = ps * pps
    cache = paged_kv.PagedKVCache(
        cfg, num_pages=len(depths) * pps + 1, page_size=ps,
        max_slots=len(depths), pages_per_slot=pps, dtype=jnp.bfloat16)
    cos, sin = precompute_rope(cfg, span)

    def project(weights, x, cos, sin):
        return sparse_attn.project_index(
            cfg, weights, x, sparse_mla.index_rotation_rows(cfg, cos, sin),
            query=mla.query_latent(cfg, weights, x))

    @jax.jit
    def keys_of(x):           # a sequence's index keys, as cached
        return project(lp, x, cos, sin)[1]

    xs = []
    for slot, depth in enumerate(depths):
        x = jax.random.normal(jax.random.key(SEED + slot),
                              (span, cfg.hidden_size), jnp.bfloat16)
        xs.append(x[depth])                    # the query's layer input
        assert cache.alloc_slot() == slot
        cache.adopt_latent(
            slot, jnp.zeros((2, depth, cfg.kv_row_lanes), jnp.bfloat16),
            depth, index=jnp.broadcast_to(keys_of(x)[None, :depth],
                                          (2, depth, cfg.index_row_lanes)))
    table, lengths = cache.device_tables()
    x = jnp.stack(xs)
    at = (cos[lengths], sin[lengths])

    @jax.jit
    def chosen(pool, x):       # the step's own path, bf16 operands
        qi, ik, wi = project(lp, x, *at)
        pool = paged_kv.write_rows(
            pool, 0, table, lengths,
            jnp.zeros((len(depths), 1, cfg.kv_row_lanes), jnp.bfloat16),
            None, index=ik)
        scores = sparse_attn.index_scores_paged(qi, wi, pool, 0, table,
                                                lengths + 1)
        rows = paged_kv._gather_pages(pool.ik, 0, table)
        live = jnp.arange(span)[None, :] < (lengths + 1)[:, None]
        off = jnp.max(jnp.where(live, jnp.abs(
            scores - sparse_attn.index_scores(qi, wi, rows)), 0.0))
        idx, _ = sparse_attn.select(scores, lengths + 1, k)
        keep = sparse_attn.selection_mask(scores, live, k)
        # the same weights and cache in float32 at ``highest``
        with jax.default_matmul_precision("highest"):
            f32 = {n: a.astype(jnp.float32) for n, a in lp.items()}
            lq, _, lw = project(f32, x.astype(jnp.float32), *at)
            exact = sparse_attn.index_scores(lq, lw,
                                             rows.astype(jnp.float32))
        want, _ = sparse_attn.select(exact, lengths + 1, k)
        return idx, want, keep, pool, off, jnp.max(jnp.abs(exact))

    assert paged_kv.index_read_path(cache.pool) == paged_kv.INDEX_WALK
    idx, want, keep, cache.pool, off, largest = chosen(cache.pool, x)
    # float32 sums of the same bf16 products, in another order
    assert float(off) <= 1e-5 * float(largest), (float(off), float(largest))
    return _selection_overlap("deepseek_v32", depths, k, idx, want, keep,
                              0.97)


def window_latent_phase(*, prompt_len: int = 300, n_new: int = 120,
                        evict_after: int = 70) -> dict:
    """A tiny ``dots3_note`` stream (a full sparse latent layer with a dense
    feed-forward, a second one and three WINDOW latent layers at sizes of
    their own: 2 heads of 96 + 32 key lanes over rows of [c 224 | k_rope 32]
    = 256 lanes, a band of 40 keys, a ring of 4 pages a slot turned six
    times; both rank factors, a gate lane a head on both kinds; float32)
    through the same admit / step / evict / readmit: ALL THREE leaves (the
    full layers' latent rows and index keys, the ring's latent rows) leave
    the device and come back, and ``forward`` over prompt + tokens puts each
    served token first, on the reads a TPU's pools take: the index walk, the
    masked walk of the full layers' rows, and the RING WALK of latent rows
    (a row key and value both, masked by position inside the kernel)."""
    import dataclasses

    import jax
    import numpy as np

    from edgellm_tpu.models import sparse_attn
    from edgellm_tpu.models.configs import (LatentGeometry,
                                            tiny_dots3_note_config)
    from edgellm_tpu.models.paged_kv import INDEX_WALK, PAGE_WALK
    from edgellm_tpu.serve.batching import BatchingConfig

    def wider(params):
        def scale(path, a):
            name = path[-1].key
            if name in ("router", "router_bias"):
                return a * 15.0
            if name == "wg":
                return a * 30.0
            return a * (4.5 if name == "wkv_b" else 3.0) \
                if name.startswith("w") or name.startswith("shared") else a
        return jax.tree_util.tree_map_with_path(scale, params)

    # the full kind as the sparse_latent phase's (rows and index keys of one
    # lane tile, heads of 160 + 32 and 128); the window kind's rows two lane
    # tiles, its heads 96 + 32 and 64
    cfg = dataclasses.replace(tiny_dots3_note_config(
        hidden_size=256, index_topk=64, sliding_window=40,
        window_latent=LatentGeometry(
            num_heads=2, q_lora_rank=64, kv_lora_rank=224,
            qk_nope_head_dim=96, qk_rope_head_dim=32, v_head_dim=64,
            rope_theta=500.0)),
        explicit_head_dim=192, qk_rope_head_dim=32, kv_lora_rank=96,
        v_head_dim=128, q_lora_rank=64, expert_width=128, shared_width=128,
        index_heads=4, index_head_dim=128)
    bcfg = BatchingConfig(page_size=16, num_pages=97, max_slots=3,
                          pages_per_slot=32)
    assert (cfg.kv_row_lanes, cfg.window_row_lanes,
            cfg.window_pages(16)) == (128, 256, 4)
    report, gap = _evict_readmit(cfg, bcfg, prompt_len, n_new, evict_after,
                                 wider)
    assert report["sparse_read"] == sparse_attn.MASKED_WALK, report
    assert report["decode_read"] == PAGE_WALK
    assert report["window_read"] == PAGE_WALK, report
    assert report["index_read"] == INDEX_WALK, report
    assert 0 < report["window_pages_walked"] <= \
        report["window_pages_spanned"]
    assert report["window_rows_capacity"] == 3 * 4 * 16
    kernels = _prefill_kernels(cfg, bcfg, prompt_len, wider)
    # (the full layers' blocks take the masked kernel; the band's are XLA)
    assert report["sparse_prefill"] == sparse_attn.MASKED_KERNEL, report
    assert "masked_attention" in kernels, kernels
    assert 0 < report["sparse_rows_attended"] < report["sparse_rows_live"]
    assert len(np.unique(report["served"])) > n_new // 6, report["served"]
    return {"tokens": int(n_new), "distinct_tokens":
            int(len(np.unique(report["served"]))),
            "evicted": report["evicted"],
            "sparse_read": report["sparse_read"],
            "window_read": report["window_read"],
            "sparse_prefill": report["sparse_prefill"],
            "prefill_kernels": kernels,
            "window_pages_walked": report["window_pages_walked"],
            "window_pages_spanned": report["window_pages_spanned"],
            "kv_row_bytes": report["kv_row_bytes"],
            "launch_ahead_share": report["launch_ahead_share"],
            "gap_max_over_logit_max": gap}


def smoke(report: dict, save) -> dict:
    """Every phase in order, at full width. ``save()`` persists ``report``
    after each phase so a failed run leaves what it learned."""
    import jax

    from edgellm_tpu.models import PRESETS, init_params

    cfg = PRESETS[MODEL]
    n_dev = len(jax.devices())
    split = SPLIT if n_dev >= len(SPLIT["cuts"]) + 1 else None
    param_dtype = jax.eval_shape(
        lambda k: init_params(cfg, k), jax.random.key(SEED))["embed"].dtype

    def phase(name, fn):
        t0 = time.monotonic()
        print(f"chip_smoke: {name} ...", file=sys.stderr, flush=True)
        report["phases"][name] = {**fn(),
                                  "phase_wall_s": time.monotonic() - t0}
        save()

    phase("dispatch", lambda: dispatch_phase(cfg, param_dtype, split=split))
    hop_shapes = ([(BATCHING["max_slots"], 1)] + [(1, s) for s in PROMPT_LENS]
                  if split else ())
    phase("kernels", lambda: kernels_phase(
        cfg, report["phases"]["dispatch"], hop_shapes=hop_shapes))
    for s in PROMPT_LENS:
        phase(f"serve_{s}", lambda s=s: serve_phase(
            f"serve_{s}", serve_params(s), cfg.vocab_size))
    phase("sweep", sweep_phase)
    phase("reference", lambda: reference_phase(cfg))
    phase("hybrid", hybrid_phase)
    phase("window", window_phase)
    phase("latent", latent_phase)
    phase("afmoe", afmoe_phase)
    phase("longcat", longcat_phase)
    phase("shortconv", shortconv_phase)
    phase("sparse", sparse_phase)
    phase("sparse_latent", sparse_latent_phase)
    phase("window_latent", window_latent_phase)
    if split is not None:
        phase("split", lambda: split_phase(cfg, cfg.vocab_size))
    else:
        report["phases"]["split"] = f"not run: {n_dev} devices"
    # built from committed files only: nothing on this path may load the
    # mtime-keyed native extension
    assert "edgellm_tpu.native" not in sys.modules
    return report


def main() -> int:
    if not __debug__:
        raise SystemExit("chip_smoke.py checks with assert; not under -O")
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found platform "
              f"{dev.platform!r} ({dev.device_kind}). Nothing was run and "
              f"there is no fallback.", file=sys.stderr)
        return 2
    t0 = time.monotonic()
    report: dict = {"ok": False, "env": env_phase(), "phases": {}}
    os.makedirs(OUT_DIR, exist_ok=True)

    def save():
        report["wall_s"] = time.monotonic() - t0
        with open(os.path.join(OUT_DIR, "report.json"), "w") as f:
            json.dump(report, f, indent=1, default=str)

    save()
    smoke(report, save)
    report["ok"] = True
    save()
    print(json.dumps(report, default=str))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
