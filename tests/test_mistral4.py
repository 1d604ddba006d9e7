"""The ``mistral4`` family (latent attention: one cached row a position for
all heads, absorbed decode beside an expanded prefill, interleaved rotary
pairs on the rope lanes, YaRN's ``mscale`` on the softmax and a per-position
query scale; routed experts plus a shared one; an untied head) against its
plain reference, on the CPU at toy widths with seeded float32 weights.

The reference is ``benchmark/reference_mistral4.py``: float32 at ``highest``,
whole sequences, the EXPANDED form at every position (keys and values rebuilt
per head from the latent), no cache, no pages, nothing imported from the
program. Both sides compute in float32 here, so they differ by summation
order alone — and, in every decode step, by the absorption itself.

TOL: logits are compared as ``max |system - reference| <= TOL * max
|reference|``. 2e-5 is ~100 float32 roundings of a three-layer stack whose
sums run over at most 100 terms; the readings are 2e-7 to 7e-7 (the forward,
the prefill by blocks, the contiguous decode and sixty absorbed paged steps
alike). Every named mistake below moves the logits by far more of their size
at some step of a 60-token answer that crosses three steps of the query
scale — the query scale left out 7.1e-3, the softmax's ``m^2`` left out and
``k_rope`` cached before its rotation 7.1e-2, half-split rotary pairs 0.10,
the absorbed scores without their rope term 0.12, a row not written at a
page's first position 0.18, the latent cached before its norm 0.87, V taken
from ``k_nope``'s lanes 1.3 — and ``test_a_named_mistake_fails`` holds each
to twenty tolerances (4e-4: under the least of them by 18x, so bfloat16 where
float32 is stated fails). "``kv_b`` applied per cached row in the step" gives
the same numbers and is held by shape instead: no tensor of the step has a
(slots, span, heads, ...) shape (here on the jaxpr, at the cell's shapes in
``tests/test_chip_compile.py``).
"""
import dataclasses
import json
import math
import os
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import reference_mistral4 as ref  # noqa: E402
from edgellm_tpu.models import (hybrid, mla, moe, paged_kv,  # noqa: E402
                                transformer)
from edgellm_tpu.models.configs import (MISTRAL_SMALL_4_119B,  # noqa: E402
                                        PRESETS, ModelConfig,
                                        tiny_hybrid_config,
                                        tiny_mellum_config,
                                        tiny_mistral4_config)
from edgellm_tpu.models.hybrid import (LatentRowsUnsupported,  # noqa: E402
                                       RecurrentStateUnsupported,
                                       WindowRingUnsupported)
from edgellm_tpu.models.paged_kv import (LatentPool, PagedKVCache,  # noqa: E402
                                         PrefixCacheConfig)
from edgellm_tpu.serve import batching  # noqa: E402
from edgellm_tpu.serve.batching import (BatchingConfig,  # noqa: E402
                                        ContinuousBatcher)
from edgellm_tpu.serve.decode import generate  # noqa: E402

TOL = 2e-5
#: original_max 16: the query scale steps at 16, 32, 48, ... and YaRN's band
#: (pairs 0-1 of 4) is crossed inside every test's positions
CFG = tiny_mistral4_config()
BCFG = BatchingConfig(page_size=4, num_pages=121, max_slots=3,
                      pages_per_slot=40)


def ref_config(cfg: ModelConfig) -> dict:
    """The published keys the reference reads, from a ModelConfig."""
    _, factor, orig, beta_fast, beta_slow, _ = cfg.rope_scaling
    return {
        "num_hidden_layers": cfg.num_layers,
        "num_attention_heads": cfg.num_heads,
        "qk_nope_head_dim": cfg.qk_nope_head_dim,
        "qk_rope_head_dim": cfg.qk_rope_head_dim,
        "v_head_dim": cfg.v_head_dim, "kv_lora_rank": cfg.kv_lora_rank,
        "rms_norm_eps": cfg.norm_eps, "n_routed_experts": cfg.num_experts,
        "num_experts_per_tok": cfg.experts_per_tok,
        "routed_scaling_factor": 1, "rope_interleave": True,
        "first_k_dense_replace": 0, "n_group": 1, "topk_group": 1,
        "norm_topk_prob": True,
        "share": {"experts_held": cfg.local_experts,
                  "expert_offset": cfg.expert_offset},
        "rope_parameters": {
            "rope_type": "yarn", "rope_theta": cfg.rope_theta,
            "factor": factor, "original_max_position_embeddings": orig,
            "beta_fast": beta_fast, "beta_slow": beta_slow, "mscale": 1,
            "mscale_all_dim": 1,
            "llama_4_scaling_beta": cfg.query_scale_beta}}


def make_params(cfg, seed=0):
    """Seeded weights, every matrix at std 0.04 instead of 0.02 and norm
    scales (the two latent norms among them) off one: at width 48 that makes
    attention, the experts and the untied head each a visible part of the
    logits."""
    params = transformer.init_params(cfg, jax.random.key(seed))
    keys = iter(jax.random.split(jax.random.key(seed + 1), 256))

    def shake(path, a):
        if path[-1].key.endswith(("_scale", "_norm")):
            return a + 0.1 * jax.random.normal(next(keys), a.shape)
        return a * 2.0

    return jax.tree_util.tree_map_with_path(shake, params)


@pytest.fixture(scope="module")
def params():
    return make_params(CFG)


def ref_logits(cfg, params, ids):
    return np.asarray(ref.logits(ref.model_key(ref_config(cfg)), params,
                                 jnp.asarray(ids)))


def rel_err(got, want):
    return float(np.max(np.abs(np.asarray(got) - want)) / np.max(np.abs(want)))


def _ids(n, seed=1, vocab=256):
    return np.random.default_rng(seed).integers(1, vocab, size=n).astype(
        np.int32)


def _forward(cfg, params, ids):
    with jax.default_matmul_precision("highest"):
        return jax.jit(lambda p, i: transformer.forward(cfg, p, i)[0])(
            params, jnp.asarray(ids)[None])[0]


# -- the configuration --------------------------------------------------------

def test_the_preset_holds_the_published_numbers():
    c = MISTRAL_SMALL_4_119B
    assert PRESETS["mistral-small-4-119b"] is c and c.is_hybrid
    assert (c.num_layers, c.hidden_size, c.num_heads, c.vocab_size) == (
        36, 4096, 32, 131072)
    assert (c.q_lora_rank, c.kv_lora_rank, c.qk_nope_head_dim,
            c.qk_rope_head_dim, c.v_head_dim, c.head_dim) == (
        1024, 256, 64, 64, 128, 128)
    assert (c.num_experts, c.experts_per_tok, c.expert_width,
            c.shared_width) == (128, 4, 2048, 2048)
    assert c.layer_types == ("latent_attention",) * 36
    assert c.latent_layers == c.kv_layers == 36 and not c.window_layers \
        and not c.recurrent_state and not c.tie_word_embeddings
    assert c.rotary_dim == 64          # the rope lanes only
    assert c.softmax_mscale == pytest.approx(1.48520, abs=1e-5)
    assert c.rope_scaling == ("yarn", 128.0, 8192, 32.0, 1.0, 1.0)


@pytest.mark.parametrize("cfg,lanes", [
    (MISTRAL_SMALL_4_119B, 384),       # 256 + 64 = 320 -> three lane tiles
    (CFG, 128),                        # 16 + 8 = 24 -> one
    (tiny_mellum_config(), 2 * 16), (tiny_hybrid_config(), 2 * 16),
    (PRESETS["tiny-qwen2"], 2 * 16), (PRESETS["qwen2-0.5b"], 2 * 64),
])
def test_the_pools_row_width_is_asked_of_the_config(cfg, lanes):
    """``kv_row_lanes`` is the one place: a latent row is ``[c | k_rope]``
    padded to whole 128-lane tiles, every other family's ``KV x hd``: of K,
    and as many of V behind them in the same row. Either pool is ONE leaf."""
    assert cfg.kv_row_lanes == lanes
    pool = jax.eval_shape(lambda: paged_kv.init_pool(cfg, 9, 4))
    (leaf,) = pool
    assert leaf.shape == (cfg.kv_layers, 9, 4,
                          lanes * (1 if cfg.latent_layers else 2))
    assert isinstance(pool, LatentPool) == bool(cfg.latent_layers)


def test_a_latent_row_is_a_twentieth_of_per_head_rows():
    c = MISTRAL_SMALL_4_119B
    per_head = c.num_heads * (c.head_dim + c.v_head_dim) * 2
    assert per_head == 16384 and c.kv_row_lanes * 2 == 768
    assert (c.kv_lora_rank + c.qk_rope_head_dim) * 2 == 640
    cut = dataclasses.replace(c, num_layers=4,
                              layer_types=("latent_attention",) * 4)
    assert paged_kv.kv_page_bytes(cut, 16, dtype=jnp.bfloat16) == \
        4 * 16 * 768
    assert paged_kv.num_pages_for_bytes(cut, 73729 * 4 * 16 * 768, 16,
                                        dtype=jnp.bfloat16) == 73729
    with pytest.raises(LatentRowsUnsupported, match="quantized KV tier"):
        paged_kv.kv_page_bytes(cut, 16, "int8_per_channel")


@pytest.mark.parametrize("bad", [
    dict(layer_types=("attention",) * 3),
    dict(layer_types=("latent_attention", "latent_attention")),
    dict(kv_lora_rank=0), dict(q_lora_rank=0), dict(v_head_dim=0),
    dict(qk_rope_head_dim=24), dict(qk_rope_head_dim=7),
    dict(experts_held=4, expert_offset=6),
])
def test_a_config_the_family_cannot_be_is_refused(bad):
    with pytest.raises(ValueError):
        dataclasses.replace(CFG, **bad)


def test_the_other_families_take_no_latent_fields():
    with pytest.raises(ValueError, match="latent"):
        dataclasses.replace(PRESETS["tiny-qwen2"], kv_lora_rank=16)
    with pytest.raises(ValueError, match="latent"):
        dataclasses.replace(tiny_mellum_config(), kv_lora_rank=16,
                            q_lora_rank=8, qk_rope_head_dim=8, v_head_dim=8)


# -- the scales and the rotation, by hand --------------------------------------

def test_yarn_band_and_the_two_scales_at_the_published_numbers():
    c = MISTRAL_SMALL_4_119B
    # dim(r) = 64 ln(8192 / (2 pi r)) / (2 ln 10000): 12.88 at 32, 24.92 at 1
    assert transformer.yarn_band(64, 10000.0, c.rope_scaling) == (12, 25)
    k = dict(ref.model_key(ref_config(c)))
    assert ref.yarn_band(k) == (12, 25)
    m = 0.1 * math.log(128.0) + 1.0
    assert ref.softmax_scale(k) == pytest.approx(128 ** -0.5 * m * m)
    pos = jnp.asarray([0, 8191, 8192, 16383, 16384, 131072])
    want = [1.0, 1.0, 1 + 0.1 * math.log(2), 1 + 0.1 * math.log(2),
            1 + 0.1 * math.log(3), 1 + 0.1 * math.log(17)]
    np.testing.assert_allclose(
        np.asarray(mla.query_scale(
            c, c.latent_geometry("latent_attention"), pos)),
        np.asarray(want) * m * m,
        rtol=1e-6)
    np.testing.assert_allclose(np.asarray(ref.query_scale(k, 16385))[
        [0, 8191, 8192, 16383, 16384]], want[:5], rtol=1e-6)
    cos, sin = transformer.precompute_rope(c, 4)
    assert cos.shape == (4, 64)        # the rope lanes only, unscaled
    assert float(cos[0].min()) == 1.0


def test_interleaved_pairs_rotate_by_hand():
    """Lanes (2i, 2i+1) turn by the angle of pair i. The program stores the
    result de-interleaved (evens, then odds); dot products of two rotated
    vectors are the pair rotation's either way."""
    cos, sin = transformer.precompute_rope(CFG, 50)         # (50, 8)
    k = dict(ref.model_key(ref_config(CFG)))
    rcos, rsin = ref.rope_table(k, 50)                      # (50, 4)
    np.testing.assert_allclose(np.asarray(cos[:, :4]), np.asarray(rcos),
                               atol=1e-6)
    x = jax.random.normal(jax.random.key(0), (1, 50, 2, 8))
    got = transformer.apply_rotary(transformer.deinterleave_pairs(x), cos,
                                   sin, 8)[0]
    want = ref._rotate_pairs(x[0], rcos, rsin)
    np.testing.assert_allclose(np.asarray(got[..., :4]),
                               np.asarray(want[..., 0::2]), atol=1e-6)
    np.testing.assert_allclose(np.asarray(got[..., 4:]),
                               np.asarray(want[..., 1::2]), atol=1e-6)
    a = np.asarray(x[0, 7, 0, :2])
    ang = 7 * float(ref.inv_freq(k)[0])
    np.testing.assert_allclose(
        np.asarray(want[7, 0, :2]),
        [a[0] * math.cos(ang) - a[1] * math.sin(ang),
         a[1] * math.cos(ang) + a[0] * math.sin(ang)], atol=1e-6)


# -- whole sequences: the expanded form -----------------------------------------

@pytest.mark.parametrize("cfg", [
    CFG, tiny_mistral4_config(experts_held=4, expert_offset=2),
    tiny_mistral4_config(num_layers=1),
], ids=["all-held", "4-of-8-held", "one-layer"])
@pytest.mark.parametrize("length", [24, 100])
def test_forward_matches_the_reference(cfg, length):
    p = make_params(cfg)
    ids = _ids(length, length)
    assert rel_err(_forward(cfg, p, ids), ref_logits(cfg, p, ids)) < TOL


def test_forward_takes_a_batch_and_refuses_a_hook(params):
    ids = np.stack([_ids(20, 1), _ids(20, 2)])
    with jax.default_matmul_precision("highest"):
        logits, _ = transformer.forward(CFG, params, jnp.asarray(ids))
    for row, seq in zip(logits, ids):
        assert rel_err(row, ref_logits(CFG, params, seq)) < TOL
    with pytest.raises(LatentRowsUnsupported, match="latent"):
        transformer.forward(CFG, params, jnp.asarray(ids),
                            boundary_fn=lambda l, h: h)


def test_prefill_by_query_blocks_matches_the_reference(monkeypatch, params):
    """Blocks of 16 query rows over 57 positions: four blocks, the last
    short; the cache comes back as the stored rows, padded to capacity."""
    monkeypatch.setattr(hybrid, "QBLOCK", 16)
    ids = _ids(57, 3)
    with jax.default_matmul_precision("highest"):
        logits, cache = hybrid.prefill_hybrid(CFG, params,
                                              jnp.asarray(ids)[None], 64)
    assert rel_err(logits[0], ref_logits(CFG, params, ids)) < TOL
    assert isinstance(cache, hybrid.LatentCache)
    assert cache.rows.shape == (3, 1, 64, 128) and int(cache.length) == 57
    rows = np.asarray(cache.rows)
    assert np.abs(rows[:, :, :57, :24]).min() > 0
    assert not rows[:, :, :, 24:].any() and not rows[:, :, 57:].any()


@pytest.mark.parametrize("plen", [6, 23])
def test_contiguous_decode_step_matches_the_reference(params, plen):
    ids = _ids(plen + 30, plen)
    want = ref_logits(CFG, params, ids)
    with jax.default_matmul_precision("highest"):
        _, cache = transformer.prefill(CFG, params,
                                       jnp.asarray(ids[:plen])[None], 64)
        step = jax.jit(lambda c, t: transformer.decode_step(CFG, params, c,
                                                            t))
        for pos in range(plen, plen + 30):
            logits, cache = step(cache, jnp.asarray(ids[pos:pos + 1]))
            assert rel_err(logits[0], want[pos]) < TOL, pos


# -- prefill, then the absorbed paged decode -------------------------------------

class LogitTap:
    """``ContinuousBatcher`` with its step executable replaced by one that
    also hands the logits out: the same ``paged_decode_step_hybrid``, the same
    sampler, the batcher's own admission, adoption and tables around it."""

    def __init__(self, monkeypatch, cfg):
        self.rows = []        # (lengths, logits) per step

        @jax.jit
        def step(params, rows, cnt, table, lengths, toks, key_data, steps,
                 temps):
            with jax.default_matmul_precision("highest"):
                logits, rows, _, cnt = hybrid.paged_decode_step_hybrid(
                    cfg, params, rows, None, cnt, table, lengths, toks)
            return (logits, batching._batched_sample(logits, key_data, steps,
                                                     temps), rows, cnt)

        def tapped(cfg_, params, rows, state, cnt, table, lengths,
                   toks, key_data, steps, temps, compute_dtype):
            assert state is None
            logits, toks, rows, cnt = step(params, rows, cnt, table, lengths,
                                           toks, key_data, steps, temps)
            self.rows.append((np.array(lengths), np.array(logits)))
            return toks, rows, None, cnt

        tapped._cache_size = lambda: 0
        monkeypatch.setattr(batching, "_batched_hybrid_step_jit", tapped)

    def of_slot(self, slot):
        """{cache length before the step: that slot's logits row}."""
        return {int(lengths[slot]): logits[slot]
                for lengths, logits in self.rows if lengths[slot] > 0}


def _worst(tap, slot, cfg, params, prompt, tokens):
    """The worst relative error of a stream's decode steps against the
    reference's full EXPANDED forward over prompt + served tokens."""
    seq = np.concatenate([prompt, tokens])
    want = ref_logits(cfg, params, seq)
    got = tap.of_slot(slot)
    assert len(got) >= len(tokens) - 1
    return max(rel_err(row, want[pos]) for pos, row in got.items()
               if pos < len(seq))


def _serve(monkeypatch, cfg, params, prompt, new, bcfg=BCFG, **submit):
    tap = LogitTap(monkeypatch, cfg)
    with jax.default_matmul_precision("highest"):
        b = ContinuousBatcher(cfg, params, bcfg)
        sid = b.submit(prompt, new, **submit)
        toks = b.run()[sid]
    b.pool.check_invariants()
    return tap, b, toks


@pytest.mark.parametrize("plen", [3, 16, 23, 41])
def test_prefill_then_absorbed_paged_decode_matches_the_full_expanded_forward(
        monkeypatch, params, plen):
    """Pages of 4 rows: 60 decode steps cross 15 page boundaries and three
    steps of the query scale (positions 16, 32, 48, 64, ...), each step's
    logits against the reference's expanded forward over the whole
    sequence."""
    prompt = _ids(plen, plen)
    tap, b, toks = _serve(monkeypatch, CFG, params, prompt, 61, rng_seed=0)
    assert isinstance(b.pool.pool, LatentPool)
    assert b.pool.pool.rows.shape == (3, 121, 4, 128)
    assert len(tap.of_slot(0)) == 60
    assert _worst(tap, 0, CFG, params, prompt, toks) < TOL
    # token 0 came from the prefill's last position
    want0 = ref_logits(CFG, params, prompt)[-1]
    assert want0[toks[0]] >= want0.max() - TOL * np.abs(want0).max()


def test_the_step_never_holds_a_per_head_key_or_value_of_the_span(params):
    """The absorption is real: in the traced step every gather takes whole
    pages of the one-leaf pool, and no value has a (slots, span, heads, ...)
    or (slots, heads, span, lanes) shape; the widest per-head tensors are the
    (slots, heads, span) scores."""
    b = ContinuousBatcher(CFG, params, BCFG)
    table, lengths = b.pool.device_tables()
    slots, span, heads = 3, 160, CFG.num_heads
    jaxpr = jax.make_jaxpr(lambda rows: hybrid.paged_decode_step_hybrid(
        CFG, params, rows, None, jnp.zeros((3, 8), jnp.int32),
        table, lengths, jnp.zeros((3,), jnp.int32)))(b.pool.pool.rows)
    gathers, shapes = set(), set()

    def walk(jp):
        for eqn in jp.eqns:
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)
            for v in eqn.outvars:
                shape = getattr(v.aval, "shape", ())
                shapes.add(shape)
                if eqn.primitive.name == "gather" and len(shape) == 4 \
                        and "attn.latent" in str(eqn.source_info.name_stack):
                    gathers.add(shape)

    walk(jaxpr.jaxpr)
    assert gathers == {(slots, 40, 4, 128)}, gathers
    assert (slots, heads, span) in shapes            # the scores
    per_head = [s for s in shapes if len(s) >= 4 and s[0] == slots
                and heads in s[1:3] and span in s[1:3]]
    assert not per_head, per_head


# -- the batcher around it -------------------------------------------------------

def test_evict_then_readmit_reproduces_the_undisturbed_stream(monkeypatch,
                                                              params):
    """The latent rows leave the device as stored and come back into other
    pages: the stream's remaining logits and tokens are bit-identical."""
    prompt = _ids(21, 9)
    calm, _, want = _serve(monkeypatch, CFG, params, prompt, 40, rng_seed=3)
    tap = LogitTap(monkeypatch, CFG)
    with jax.default_matmul_precision("highest"):
        b = ContinuousBatcher(CFG, params, BCFG)
        b.submit(_ids(7, 4), 50, rng_seed=1)              # a neighbour
        sid = b.submit(prompt, 40, rng_seed=3)
        for _ in range(17):
            b.step()
        slot = b._streams[sid].slot
        before = b.pool.gather_slot(slot)
        b.evict(sid)
        b.pool.check_invariants()
        payload = b._streams[sid].resume
        assert set(payload) == {"rows", "length"}
        assert payload["rows"].shape == (3, 21 + 17, 128)
        np.testing.assert_array_equal(payload["rows"], before["rows"])
        b.submit(_ids(5, 6), 30, rng_seed=2)              # takes the slot
        got = b.run()[sid]
    np.testing.assert_array_equal(got, want)
    assert b.report()["evicted"] == 1
    new_slot = [s for s in range(3) if s != slot]
    mine = {}
    for s in new_slot + [slot]:
        mine.update({pos: row for pos, row in tap.of_slot(s).items()
                     if pos >= 21 + 17 and pos not in mine})
    for pos, row in calm.of_slot(0).items():
        if pos >= 21 + 17:
            assert any(np.array_equal(row, logits[s]) for lengths, logits in
                       tap.rows for s in range(3) if lengths[s] == pos), pos


def test_defrag_and_the_invariants_hold_over_latent_pages(params):
    """Three streams admitted, the middle one freed, the pool compacted
    mid-flight (one donated gather of whole pages of the one leaf): every
    stream's tokens equal the undisturbed run's."""
    prompts = [_ids(n, n) for n in (9, 14, 6)]

    def run(disturb):
        with jax.default_matmul_precision("highest"):
            b = ContinuousBatcher(CFG, params, BCFG)
            sids = [b.submit(p, 30 if i != 1 else 4, rng_seed=i)
                    for i, p in enumerate(prompts)]
            for _ in range(8):
                b.step()
                b.pool.check_invariants()
            moved = b.pool.defrag() if disturb else 0
            b.pool.check_invariants()
            return b.run(), moved, sids

    calm, _, sids = run(False)
    got, moved, _ = run(True)
    assert moved > 0
    for sid in sids:
        np.testing.assert_array_equal(got[sid], calm[sid])


def test_out_of_pages_evicts_and_readmits_inside_the_batcher(params):
    tight = BatchingConfig(page_size=4, num_pages=19, max_slots=3,
                           pages_per_slot=16)
    with jax.default_matmul_precision("highest"):
        roomy = ContinuousBatcher(CFG, params, BCFG)
        b = ContinuousBatcher(CFG, params, tight)
        for batcher in (roomy, b):
            sids = [batcher.submit(_ids(n, n), 30, rng_seed=n)
                    for n in (10, 12, 8)]
        want, got = roomy.run(), b.run()
    assert b.report()["evicted"] >= 1 and roomy.report()["evicted"] == 0
    for sid in sids:
        np.testing.assert_array_equal(got[sid], want[sid])
    b.pool.check_invariants()


def test_batcher_tokens_equal_generate(params):
    """The paged absorbed step, the contiguous absorbed step and the sampler
    agree token for token, greedy and sampled."""
    prompts = [_ids(11, 1), _ids(19, 2), _ids(4, 3)]
    temps = [0.0, 0.7, 0.0]
    b = ContinuousBatcher(CFG, params, BCFG)
    sids = [b.submit(p, 20, temperature=t, rng_seed=i)
            for i, (p, t) in enumerate(zip(prompts, temps))]
    res = b.run()
    for i, (sid, p, t) in enumerate(zip(sids, prompts, temps)):
        want = np.asarray(generate(CFG, params, p[None], 20, temperature=t,
                                   rng_key=jax.random.key(i)))[0]
        np.testing.assert_array_equal(res[sid], want)
    rep = b.report()
    assert rep["state_bytes"] == 0 and rep["window_rows_capacity"] == 0
    assert rep["routed_local"] == rep["routed_assignments"] > 0
    assert np.asarray(rep["expert_tokens"]).shape == (3, 8)


@pytest.mark.parametrize("cfg,live,capacity,row_bytes", [
    (CFG, 7 + 31, 120 * 4, 128 * 4),
    (tiny_mellum_config(), 0, 0, 2 * 32 * 4),
    (tiny_hybrid_config(), 0, 0, 2 * 32 * 4),
], ids=["mistral4", "mellum", "granite"])
def test_the_latent_counters(cfg, live, capacity, row_bytes):
    b = ContinuousBatcher(cfg, transformer.init_params(
        cfg, jax.random.key(0)), BCFG)
    b.submit(_ids(6, 1), 40, rng_seed=0)
    b.submit(_ids(30, 2), 40, rng_seed=1)
    b.step()
    rep = b.report()
    assert rep["latent_rows_live"] == live
    assert rep["latent_rows_capacity"] == capacity
    assert rep["kv_row_bytes"] == row_bytes


def test_a_latent_batcher_whose_read_is_the_walk_counts_its_pages(
        monkeypatch, params):
    """``decode_read`` is read off the pool the batcher serves, a one-leaf
    :class:`LatentPool` here: the page walk where a page is whole tiles and
    the backend a TPU (the one question, answered by the test), the gather
    for this file's pages of 4 rows. With it ``report()`` counts, before
    every step, the pages under each slot's length with the row the step
    writes (an idle slot's one trash page) against slots x table entries, by
    the code that counts a K/V walk's. The counting is the host's: on the
    CPU the step itself still gathers."""
    bcfg = BatchingConfig(page_size=8, num_pages=61, max_slots=3,
                          pages_per_slot=20)
    with monkeypatch.context() as m:
        m.setattr(paged_kv, "_on_tpu", lambda: True)
        b = ContinuousBatcher(CFG, params, bcfg)
        assert ContinuousBatcher(CFG, params, BCFG).decode_read \
            == paged_kv.PAGE_GATHER
    assert isinstance(b.pool.pool, LatentPool)
    assert b.decode_read == paged_kv.PAGE_WALK
    assert ContinuousBatcher(CFG, params, bcfg).decode_read \
        == paged_kv.PAGE_GATHER                        # a cpu: the oracle
    b.submit(_ids(6, 1), 12, rng_seed=0)      # decodes at cache lengths 6..16
    b.submit(_ids(30, 2), 4, rng_seed=1)      # 30..32, then its slot idles
    b.run()
    rep = b.report()
    assert rep["decode_read"] == paged_kv.PAGE_WALK and rep["steps"] == 11
    lens = [list(range(6, 17)), [30, 31, 32] + [0] * 8, [0] * 11]
    assert rep["attend_pages_walked"] == sum(
        n // 8 + 1 for slot in lens for n in slot)
    assert rep["attend_pages_spanned"] == 11 * 3 * 20
    assert rep["latent_rows_capacity"] == 60 * 8


# -- the named mistakes ---------------------------------------------------------

def _project_with(change):
    """``mla.project`` whose cached row is altered by ``change(cfg, lp, x,
    rotate, row)``."""
    def make(monkeypatch):
        real = mla.project

        def project(cfg, geo, lp, x, rotate, scale):
            q_nope, q_rope, row = real(cfg, geo, lp, x, rotate, scale)
            return q_nope, q_rope, change(cfg, lp, x, rotate, row)

        monkeypatch.setattr(mla, "project", project)
        return CFG
    return make


def _unnormed_latent(cfg, lp, x, rotate, row):
    return row.at[..., :cfg.kv_lora_rank].set(
        (x @ lp["wkv_a"])[..., :cfg.kv_lora_rank])


def _unrotated_k_rope(cfg, lp, x, rotate, row):
    rank, rope = cfg.kv_lora_rank, cfg.qk_rope_head_dim
    return row.at[..., rank:rank + rope].set(transformer.deinterleave_pairs(
        (x @ lp["wkv_a"])[..., rank:]))


def _half_split_pairs(monkeypatch):
    monkeypatch.setattr(mla, "deinterleave_pairs", lambda x: x)
    return CFG


def _v_from_k_nope_lanes(monkeypatch):
    def unabsorb(cfg, lp, ctx):
        wv = mla._kvb(cfg, lp)[..., :cfg.v_head_dim]
        out = jnp.einsum("bhc,chv->bhv", ctx[..., :cfg.kv_lora_rank], wv)
        return out.reshape(ctx.shape[0], -1) @ lp["wo"]

    monkeypatch.setattr(mla, "unabsorb", unabsorb)
    return CFG


def _scores_without_the_rope_term(monkeypatch):
    real = mla.absorb_query
    monkeypatch.setattr(mla, "absorb_query", lambda cfg, lp, q_nope, q_rope:
                        real(cfg, lp, q_nope, jnp.zeros_like(q_rope)))
    return CFG


def _no_row_at_a_pages_first_position(monkeypatch):
    """A stale row read after a page boundary: the step's write skips the
    first row of every page (it lands in the trash page)."""
    real = paged_kv.write_rows

    def write(pool, layer, table, lengths, k, v, ring=False):
        keep = (lengths % pool.page_size != 0)[:, None]
        return real(pool, layer, jnp.where(keep, table, 0), lengths, k, v,
                    ring)

    monkeypatch.setattr(paged_kv, "write_rows", write)
    return CFG


MISTAKES = {
    "latent-cached-before-its-norm": _project_with(_unnormed_latent),
    "k-rope-cached-before-its-rotation": _project_with(_unrotated_k_rope),
    "half-split-instead-of-interleaved-pairs": _half_split_pairs,
    "softmax-mscale-squared-left-out":
        lambda mp: dataclasses.replace(CFG, softmax_mscale=1.0),
    "query-scale-left-out":
        lambda mp: dataclasses.replace(CFG, query_scale_beta=0.0),
    "v-taken-from-k-nope-lanes": _v_from_k_nope_lanes,
    "absorbed-scores-without-the-rope-term": _scores_without_the_rope_term,
    "no-row-written-at-a-pages-first-position":
        _no_row_at_a_pages_first_position,
}


@pytest.mark.parametrize("name", sorted(MISTAKES))
def test_a_named_mistake_fails(monkeypatch, params, name):
    """The comparison above is tight enough: the same prefill-then-decode
    through the batcher, with one thing wrong, misses the reference by at
    least twenty tolerances at some step."""
    cfg = MISTAKES[name](monkeypatch)
    prompt = _ids(23, 5)
    tap, _, toks = _serve(monkeypatch, cfg, params, prompt, 61, rng_seed=0)
    worst = _worst(tap, 0, CFG, params, prompt, toks)
    assert worst > 20 * TOL, worst


# -- the share ----------------------------------------------------------------

def test_the_four_shares_of_a_layer_add_up_to_the_uncut_layer(params):
    """Four chips hold two of the eight experts each and the shared expert
    whole: their parts, the shared expert counted once, are the uncut
    layer's result, which is the reference's."""
    mp = params["moe"][0]
    u = jax.random.normal(jax.random.key(6), (37, CFG.hidden_size))
    routed = ("w_gate", "w_up", "w_down")
    with jax.default_matmul_precision("highest"):
        whole, counts = moe.moe_layer(CFG, mp, u)
        shared = (jax.nn.silu(u @ mp["shared_gate"])
                  * (u @ mp["shared_up"])) @ mp["shared_down"]
        parts, held = [], []
        for chip in range(4):
            cfg = tiny_mistral4_config(experts_held=2, expert_offset=2 * chip)
            mine = {**mp, **{k: mp[k][2 * chip:2 * chip + 2] for k in routed}}
            out, c = moe.moe_layer(cfg, mine, u)
            parts.append(out - shared)
            held.append(np.asarray(c))
        k = dict(ref.model_key(ref_config(CFG)))
        want = ref._moe(k, mp, u, False)
    assert rel_err(sum(parts) + shared, np.asarray(whole)) < TOL
    assert rel_err(whole, np.asarray(want)) < TOL
    np.testing.assert_array_equal(np.concatenate(held), np.asarray(counts))
    assert int(counts.sum()) == 37 * 3       # every assignment held once


@pytest.mark.parametrize("tokens", [7, 300, 301])
def test_dense_and_grouped_paths_agree_beside_a_shared_expert(tokens):
    cfg = tiny_mistral4_config(experts_held=4, expert_offset=2)
    mp = make_params(CFG)["moe"][0]
    mp = {**mp, **{k: mp[k][2:6] for k in ("w_gate", "w_up", "w_down")}}
    u = jax.random.normal(jax.random.key(6), (tokens, cfg.hidden_size))
    with jax.default_matmul_precision("highest"):
        idx, w = moe.route(cfg, mp["router"], u)
        dense = moe._experts_dense(cfg, mp, u, idx, w)
        grouped = moe._experts_grouped(cfg, mp, u, idx, w)
    assert rel_err(grouped, np.asarray(dense)) < TOL


# -- what refuses the family, by name --------------------------------------------

def _refusals():
    from edgellm_tpu.parallel import SplitConfig, SplitRuntime, \
        make_stage_mesh
    from edgellm_tpu.serve import recovery, speculative

    p = None     # no refusal below gets as far as touching parameters
    geometry = dict(num_pages=9, page_size=4, max_slots=2, pages_per_slot=4)
    yield "prefix-sharing", lambda: PagedKVCache(
        CFG, **geometry, prefix_cache=PrefixCacheConfig())
    yield "quantized-kv-tier", lambda: PagedKVCache(
        CFG, **geometry, kv_codec="int8_per_channel")
    yield "bookkeeping-only-allocator", lambda: PagedKVCache(
        CFG, **geometry, materialize=False)
    yield "checkpoint-dir", lambda: ContinuousBatcher(
        CFG, p, dataclasses.replace(BCFG, checkpoint_dir="/nonexistent"))
    yield "split-runtime-batcher", lambda: ContinuousBatcher(
        CFG, p, BCFG, split_runtime=object(), placed_params=object())
    yield "split-runtime", lambda: SplitRuntime(
        CFG, SplitConfig(cuts=(1,), hop_codecs=("fp16",)),
        make_stage_mesh(2))
    yield "checkpoint-stream", lambda: ContinuousBatcher(
        CFG, p, BCFG).checkpoint_stream(0, "/nonexistent")
    yield "restore-stream", lambda: ContinuousBatcher(
        CFG, p, BCFG).restore_stream("/nonexistent")
    yield "prefill-hold", lambda: ContinuousBatcher(
        CFG, p, BCFG).prefill_hold(0)
    yield "speculation", lambda: speculative.draft_from_params(
        CFG, p, speculative.SpecConfig())
    yield "recovery-runtime", lambda: recovery.LocalRuntime(CFG, None)
    yield "whole-cache-snapshot", lambda: PagedKVCache(
        CFG, **geometry).state_dict()
    yield "whole-cache-restore", lambda: PagedKVCache(
        CFG, **geometry).load_state_dict({})
    yield "survivable-generate", lambda: generate(
        CFG, p, _ids(4)[None], 2, recovery=types.SimpleNamespace())
    yield "decode-step-hook", lambda: transformer.decode_step(
        CFG, p, None, None, boundary_fn=lambda l, h: h)


REFUSALS = dict(_refusals())      # the calls are lambdas: nothing runs yet


@pytest.mark.parametrize("name", sorted(REFUSALS))
def test_a_mechanism_that_moves_k_and_v_rows_refuses_the_family_by_name(name):
    """One worded refusal (``hybrid.refuse_latent_rows``), raised where the
    mechanism is built or entered, before any weights are touched."""
    with pytest.raises(LatentRowsUnsupported) as e:
        REFUSALS[name]()
    msg = str(e.value)
    assert "'mistral4'" in msg and "cache ONE row a position" in msg
    assert "no fallback" in msg and not isinstance(
        e.value, (RecurrentStateUnsupported, WindowRingUnsupported))


def test_the_three_refusals_refuse_their_own_family_only():
    granite, mellum = tiny_hybrid_config(), tiny_mellum_config()
    for cfg in (granite, mellum) + tuple(
            PRESETS[n] for n in ("tiny-qwen2", "tiny-neox", "tiny-llama")):
        hybrid.refuse_latent_rows(cfg, "x")
    hybrid.refuse_recurrent_state(CFG, "x")
    hybrid.refuse_window_ring(CFG, "x")
    with pytest.raises(RecurrentStateUnsupported, match="Mamba-2"):
        hybrid.refuse_beyond_kv_rows(granite, "x")
    with pytest.raises(WindowRingUnsupported, match="ring"):
        hybrid.refuse_beyond_kv_rows(mellum, "x")
    with pytest.raises(LatentRowsUnsupported, match="latent"):
        hybrid.refuse_beyond_kv_rows(CFG, "x")
    assert CFG.is_hybrid and CFG.latent_layers == 3 \
        and not CFG.recurrent_state and not CFG.window_layers


# -- hf_loader -------------------------------------------------------------------

def _hf(**over):
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "mistral-small-4-119b-ep4.json")) as f:
        published = json.load(f)
    return types.SimpleNamespace(**{**published, **over})


def test_hf_loader_maps_the_published_mistral4_config():
    from edgellm_tpu.models.hf_loader import config_from_hf

    full = _hf(num_hidden_layers=36, n_routed_experts=128, vocab_size=131072)
    assert config_from_hf(full) == MISTRAL_SMALL_4_119B
    cut = config_from_hf(_hf())
    assert (cut.num_layers, cut.num_experts, cut.vocab_size,
            cut.kv_row_lanes) == (4, 32, 32768, 384)
    assert cut.shared_width == 2048 and cut.rotary_dim == 64


@pytest.mark.parametrize("over,match", [
    (dict(first_k_dense_replace=1), "first_k_dense_replace"),
    (dict(norm_topk_prob=False), "norm_topk_prob"),
    (dict(routed_scaling_factor=2.5), "routed_scaling_factor"),
    (dict(rope_interleave=False), "rope_interleave"),
    (dict(n_group=8, topk_group=4), "n_group"),
    (dict(qk_head_dim=192), "qk_head_dim"),
    (dict(rope_parameters={"rope_type": "default", "rope_theta": 1e4}),
     "yarn"),
    (dict(model_type="mistral5"), "unsupported model_type: mistral5"),
])
def test_hf_loader_refuses_a_mistral4_it_does_not_know(over, match):
    from edgellm_tpu.models.hf_loader import config_from_hf

    with pytest.raises(ValueError, match=match):
        config_from_hf(_hf(**over))


# -- the normal path ------------------------------------------------------------

def test_run_py_serves_the_family_through_the_front_and_the_batcher(tmp_path,
                                                                    capsys):
    """``run.py`` serve -> ``ServeFront`` -> ``ContinuousBatcher`` -> the
    one-leaf pool -> the absorbed paged step, by the preset's name and
    nothing else: prompts of 30 tokens are past the toy's original length."""
    from edgellm_tpu.run import main

    params = {"experiment": "serve",
              "serving": {"admission": {"max_queue_depth": 8},
                          "capacity_round": 16,
                          "soak": {"n_requests": 3, "arrival_rate": 2.0,
                                   "prompt_len": 30, "max_new_tokens": 12}},
              "batching": {"page_size": 4, "num_pages": 41, "max_slots": 2,
                           "pages_per_slot": 12}}
    assert main(["--params", json.dumps(params), "--model", "tiny-mistral4",
                 "--output-dir", str(tmp_path / "out")]) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["outcomes"] == {"completed": 3} and line["mode"] == "batched"
    rep = json.load(open(tmp_path / "out" / "serve_report.json"))
    assert [len(t) for t in rep["tokens"]] == [12, 12, 12]
