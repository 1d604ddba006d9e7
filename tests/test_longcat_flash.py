"""The ``longcat_flash`` family (a layer of two latent-attention sublayers and
two dense SwiGLUs with ONE routed layer on a shortcut beside them; routed +
identity experts chosen by the whole softmax's scores plus a selection bias,
weights unnormalised times a factor; both latent rank scales; plain RoPE; an
untied head) against its plain reference, on the CPU at toy widths with
seeded float32 weights.

The reference is ``benchmark/reference_longcat_flash.py``: float32 at
``highest``, whole sequences, the layer's seven lines literally, the EXPANDED
attention at every position, ``E_e(u) = u`` written as such, no cache, no
pages, nothing imported from the program. Both sides compute in float32
here, so they differ by summation order alone, and in every decode step by
the absorption.

TOL: logits are compared as ``max |system - reference| <= TOL * max
|reference|``, 1e-5 (the readings are 2e-7 to 9e-7: the forward, the prefill
by blocks, the contiguous decode, sixty absorbed paged steps and the batcher
with an eviction alike). Every named mistake below moves the logits by far
more at some step (``test_a_named_mistake_fails`` holds each to twenty
tolerances).
"""
import dataclasses
import json
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

from benchmark import reference_longcat_flash as ref  # noqa: E402
from edgellm_tpu.models import hybrid, mla, moe, transformer  # noqa: E402
from edgellm_tpu.models.configs import (LONGCAT_FLASH_CHAT,  # noqa: E402
                                        PRESETS, ModelConfig,
                                        tiny_longcat_flash_config,
                                        tiny_mistral4_config)
from edgellm_tpu.models.hybrid import LatentRowsUnsupported  # noqa: E402
from edgellm_tpu.models.paged_kv import (LatentPool, PagedKVCache,  # noqa: E402
                                         PrefixCacheConfig)
from edgellm_tpu.serve import batching  # noqa: E402
from edgellm_tpu.serve.batching import (BatchingConfig,  # noqa: E402
                                        ContinuousBatcher)
from edgellm_tpu.serve.decode import generate  # noqa: E402

TOL = 1e-5
CFG = tiny_longcat_flash_config()
BCFG = BatchingConfig(page_size=4, num_pages=121, max_slots=3,
                      pages_per_slot=40)


def ref_config(cfg: ModelConfig) -> dict:
    """The published keys the reference reads, from a ModelConfig."""
    return {
        "num_layers": cfg.num_layers, "hidden_size": cfg.hidden_size,
        "num_attention_heads": cfg.num_heads,
        "attention_method": "MLA", "zero_expert_type": "identity",
        "qk_nope_head_dim": cfg.qk_nope_head_dim,
        "qk_rope_head_dim": cfg.qk_rope_head_dim,
        "v_head_dim": cfg.v_head_dim, "kv_lora_rank": cfg.kv_lora_rank,
        "q_lora_rank": cfg.q_lora_rank,
        "mla_scale_q_lora": cfg.rank_scales,
        "mla_scale_kv_lora": cfg.rank_scales,
        "rms_norm_eps": cfg.norm_eps, "rope_theta": cfg.rope_theta,
        "n_routed_experts": cfg.local_experts,
        "zero_expert_num": cfg.zero_experts, "moe_topk": cfg.experts_per_tok,
        "routed_scaling_factor": cfg.route_scale,
        "share": {"router_experts": cfg.num_experts,
                  "experts_held": cfg.local_experts,
                  "expert_offset": cfg.expert_offset}}


def make_params(cfg, seed=0):
    """Seeded weights, every matrix at std 0.04 instead of 0.02 and norm
    scales (the two latent norms among them) off one; the router at 0.3 so
    that the 12 scores are no near-ties (a flip swaps an identity expert for
    a held one), its selection bias 0.02 in score units (scores are ~1/12
    each): it changes the chosen set at most positions."""
    params = transformer.init_params(cfg, jax.random.key(seed))
    keys = iter(jax.random.split(jax.random.key(seed + 1), 256))

    def shake(path, a):
        name = path[-1].key
        if name.endswith(("_scale", "_norm")):
            return a + 0.1 * jax.random.normal(next(keys), a.shape)
        return a * {"router": 15.0, "router_bias": 1.0}.get(name, 2.0)

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


def _share(mp, lo, hi):
    """A ``moe`` entry whose shortcut holds experts [lo, hi)."""
    sc = mp["shortcut"]
    return {**mp, "shortcut": {**sc, **{
        k: sc[k][lo:hi] for k in ("w_gate", "w_up", "w_down")}}}


# -- the configuration --------------------------------------------------------

def test_the_preset_holds_the_published_numbers():
    c = LONGCAT_FLASH_CHAT
    assert PRESETS["longcat-flash-chat"] is c and c.is_hybrid
    assert (c.num_layers, c.sublayers, len(c.layer_types)) == (28, 2, 56)
    assert set(c.layer_types) == {"latent_attention"}
    assert (c.latent_layers, c.kv_layers, c.expert_layers) == (56, 56, 28)
    assert (c.hidden_size, c.num_heads, c.head_dim, c.qk_nope_head_dim,
            c.qk_rope_head_dim, c.v_head_dim) == (6144, 64, 192, 128, 64, 128)
    assert (c.q_lora_rank, c.kv_lora_rank) == (1536, 512)
    assert c.q_rank_scale == 2.0 and c.kv_rank_scale == pytest.approx(
        12 ** 0.5)
    assert (c.num_experts, c.zero_experts, c.router_width,
            c.experts_per_tok) == (512, 256, 768, 12)
    assert (c.score_func, c.route_scale) == ("softmax_all", 6.0)
    assert (c.intermediate_size, c.expert_width, c.shared_width) == (
        12288, 2048, 0)
    assert c.rope_scaling is None and c.rope_theta == 1e7
    # 512 + 64 lanes stored in five lane tiles: 1280 B a position a sublayer
    assert c.kv_row_lanes == 640 and c.rotary_dim == 64
    assert c.counted_experts == 513
    held = dataclasses.replace(c, experts_held=16)
    assert (held.local_experts, held.counted_experts) == (16, 17)
    t = PRESETS["tiny-longcat-flash"]
    assert (t.kv_row_lanes, t.kv_lora_rank + t.qk_rope_head_dim) == (128, 24)
    assert t.zero_experts * 2 == t.num_experts and t.sublayers == 2
    assert tiny_mistral4_config().q_rank_scale == 1.0


@pytest.mark.parametrize("bad", [
    dict(layer_types=("latent_attention",) * 2), dict(num_dense_layers=1),
    dict(shared_width=32), dict(score_func="sigmoid"),
    dict(experts_per_tok=13), dict(zero_experts=-1),
    dict(expert_offset=7, experts_held=2),
], ids=lambda d: "-".join(d))
def test_a_config_the_family_cannot_be_is_refused(bad):
    with pytest.raises(ValueError):
        dataclasses.replace(CFG, **bad)


@pytest.mark.parametrize("fields", [
    dict(zero_experts=4), dict(rank_scales=True),
    dict(score_func="softmax_all"),
], ids=lambda d: "-".join(d))
def test_the_other_families_take_none_of_its_fields(fields):
    with pytest.raises(ValueError):
        dataclasses.replace(PRESETS["tiny-qwen2"], **fields)
    if "score_func" not in fields:
        with pytest.raises(ValueError):
            dataclasses.replace(tiny_mistral4_config(), **{
                **fields, **({"kv_lora_rank": 0, "layer_types": ()}
                             if "rank_scales" in fields else {})})


# -- the router by hand ---------------------------------------------------------

def test_the_route_is_the_whole_softmax_of_the_issue_by_hand():
    """Scores over all 12 outputs, the 5 chosen by score + bias, weights the
    scores as they are times 6: not renormalised, the bias in none of
    them."""
    rng = np.random.default_rng(0)
    u = rng.normal(size=(9, 48)).astype(np.float32)
    w = (rng.normal(size=(48, 12)) * 0.3).astype(np.float32)
    b = (rng.normal(size=(12,)) * 0.05).astype(np.float32)
    with jax.default_matmul_precision("highest"):
        idx, weights = moe.route(CFG, jnp.asarray(w), jnp.asarray(u),
                                 jnp.asarray(b))
    logits = u.astype(np.float64) @ w
    p = np.exp(logits - logits.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    want = np.argsort(-(p + b), axis=-1)[:, :5]
    np.testing.assert_array_equal(np.sort(np.asarray(idx)), np.sort(want))
    np.testing.assert_allclose(
        np.asarray(weights), 6.0 * np.take_along_axis(p, np.asarray(idx), -1),
        rtol=1e-5)
    assert not np.allclose(np.asarray(weights).sum(-1), 6.0)
    # the bias decides: without it another set is chosen somewhere
    plain = np.argsort(-p, axis=-1)[:, :5]
    assert (np.sort(plain) != np.sort(want)).any()


@pytest.mark.parametrize("tokens", [7, 300, 301])
def test_dense_and_grouped_paths_agree_with_identities_and_absent_experts(
        tokens):
    """Held experts 2..5 of 8, so a token's five choices fall among the
    held, the absent and the four identities: both paths give the reference's
    routed layer, the identity part among it, and count alike."""
    cfg = tiny_longcat_flash_config(experts_held=4, expert_offset=2)
    mp = _share(make_params(CFG)["moe"][0], 2, 6)["shortcut"]
    u = jax.random.normal(jax.random.key(6), (tokens, cfg.hidden_size))
    with jax.default_matmul_precision("highest"):
        idx, w = moe.route(cfg, mp["router"], u, mp["router_bias"])
        dense = moe._experts_dense(cfg, mp, u, idx, w)
        grouped = moe._experts_grouped(cfg, mp, u, idx, w)
        out, counts = moe.moe_layer(cfg, mp, u)
        want = ref.routed(dict(ref.model_key(ref_config(cfg))), mp, u)
    assert rel_err(grouped, np.asarray(dense)) < TOL
    assert rel_err(out, np.asarray(want)) < TOL
    idx = np.asarray(idx)
    assert counts.shape == (5,)
    assert int(counts[-1]) == int((idx >= 8).sum()) > 0
    np.testing.assert_array_equal(
        counts[:4], [(idx == e).sum() for e in range(2, 6)])
    assert ((idx < 2) | ((idx >= 6) & (idx < 8))).any()     # some absent


def test_the_four_shares_with_the_identities_once_add_up_to_the_layer():
    """Four chips hold two of the eight routed experts each; the identity
    experts belong to none and are computed with every share, so the parts
    sum to the uncut layer's ``R(u0)`` with the identity part counted ONCE."""
    mp = make_params(CFG)["moe"][0]
    u = jax.random.normal(jax.random.key(6), (37, CFG.hidden_size))
    with jax.default_matmul_precision("highest"):
        whole, counts = moe.moe_layer(CFG, mp["shortcut"], u)
        idx, w = moe.route(CFG, mp["shortcut"]["router"], u,
                           mp["shortcut"]["router_bias"])
        identity = u * jnp.sum(jnp.where(idx >= 8, w, 0.0), -1)[:, None]
        parts, held = [], []
        for chip in range(4):
            cfg = tiny_longcat_flash_config(experts_held=2,
                                            expert_offset=2 * chip)
            out, c = moe.moe_layer(
                cfg, _share(mp, 2 * chip, 2 * chip + 2)["shortcut"], u)
            parts.append(out - identity)
            held.append(np.asarray(c))
            np.testing.assert_array_equal(c[-1], counts[-1])
        want = ref.routed(dict(ref.model_key(ref_config(CFG))),
                          mp["shortcut"], u)
    assert float(jnp.abs(identity).max()) > 0.1 * float(jnp.abs(whole).max())
    assert rel_err(sum(parts) + identity, np.asarray(whole)) < TOL
    assert rel_err(whole, np.asarray(want)) < TOL
    np.testing.assert_array_equal(
        np.concatenate([h[:-1] for h in held]), np.asarray(counts[:-1]))
    assert int(counts.sum()) == 37 * 5       # every assignment counted once


# -- whole sequences --------------------------------------------------------------

@pytest.mark.parametrize("cfg", [
    CFG, tiny_longcat_flash_config(experts_held=4, expert_offset=2),
    tiny_longcat_flash_config(num_layers=1),
], ids=["all-held", "4-of-8-held", "one-layer"])
@pytest.mark.parametrize("length", [24, 100])
def test_forward_matches_the_reference(cfg, length):
    p = make_params(CFG if cfg.num_layers == 2 else cfg)
    if cfg.experts_held:
        p["moe"] = [_share(m, 2, 6) if "shortcut" in m else m
                    for m in p["moe"]]
    ids = _ids(length, length)
    assert rel_err(_forward(cfg, p, ids), ref_logits(cfg, p, ids)) < TOL


def test_a_prefill_past_the_dense_path_takes_the_grouped_products(params):
    """300 tokens: every routed layer sorts its assignments and runs the
    grouped products, identity and absent assignments in no group."""
    ids = _ids(300, 8)
    assert 300 > moe.DENSE_MAX_TOKENS
    assert rel_err(_forward(CFG, params, ids),
                   ref_logits(CFG, params, np.pad(ids, (0, 212)))[:300]) < TOL


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
    """Blocks of 16 query rows over 57 positions; the cache comes back as the
    stored rows of all FOUR sublayers, padded to capacity, the latent lanes
    carrying their rank scale."""
    monkeypatch.setattr(hybrid, "QBLOCK", 16)
    ids = _ids(57, 3)
    with jax.default_matmul_precision("highest"):
        logits, cache = hybrid.prefill_hybrid(CFG, params,
                                              jnp.asarray(ids)[None], 64)
    assert rel_err(logits[0], ref_logits(CFG, params, ids)) < TOL
    assert isinstance(cache, hybrid.LatentCache)
    assert cache.rows.shape == (4, 1, 64, 128) and int(cache.length) == 57
    rows = np.asarray(cache.rows)
    assert np.abs(rows[:, :, :57, :24]).min() > 0
    assert not rows[:, :, :, 24:].any() and not rows[:, :, 57:].any()
    # an RMS-normed latent has mean square ~1 x its scale^2; times 48 / 16
    ms = (rows[:, 0, :57, :16] ** 2).mean()
    assert 2.0 < ms < 4.5, ms


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
    seq = np.pad(seq, (0, -len(seq) % 4))
    want = ref_logits(cfg, params, seq)
    got = tap.of_slot(slot)
    assert len(got) >= len(tokens) - 1
    return max(rel_err(row, want[pos]) for pos, row in got.items()
               if pos < len(prompt) + len(tokens))


def _serve(monkeypatch, cfg, params, prompt, new, bcfg=BCFG, **submit):
    tap = LogitTap(monkeypatch, cfg)
    with jax.default_matmul_precision("highest"):
        b = ContinuousBatcher(cfg, params, bcfg)
        sid = b.submit(prompt, new, **submit)
        toks = b.run()[sid]
    b.pool.check_invariants()
    return tap, b, toks


@pytest.mark.parametrize("plen", [3, 16, 41])
def test_prefill_then_absorbed_paged_decode_matches_the_full_expanded_forward(
        monkeypatch, params, plen):
    """Pages of 4 rows: 60 decode steps cross 15 page boundaries in each of
    the four sublayers' rows, each step's logits against the reference's
    expanded forward over the whole sequence."""
    prompt = _ids(plen, plen)
    tap, b, toks = _serve(monkeypatch, CFG, params, prompt, 61, rng_seed=0)
    assert isinstance(b.pool.pool, LatentPool)
    assert b.pool.pool.rows.shape == (4, 121, 4, 128)
    assert len(tap.of_slot(0)) == 60
    assert _worst(tap, 0, CFG, params, prompt, toks) < TOL
    want0 = ref_logits(CFG, params, prompt)[-1]
    assert want0[toks[0]] >= want0.max() - TOL * np.abs(want0).max()


def test_the_batcher_serves_the_references_firsts_through_an_eviction(
        monkeypatch, params):
    """Every expert held, float32: admitted, stepped, evicted (the rows of
    all four sublayers leave as stored), readmitted into other pages, run
    out: every step's logits are the reference's to TOL and the served
    tokens its firsts; the counters count a PUBLISHED layer's routing."""
    prompt = _ids(21, 9)
    tap = LogitTap(monkeypatch, CFG)
    with jax.default_matmul_precision("highest"):
        b = ContinuousBatcher(CFG, params, BCFG)
        b.submit(_ids(7, 4), 50, rng_seed=1)              # a neighbour
        sid = b.submit(prompt, 40, rng_seed=3)
        for _ in range(17):
            b.step()
        slot = b._streams[sid].slot
        b.evict(sid)
        b.pool.check_invariants()
        payload = b._streams[sid].resume
        assert set(payload) == {"rows", "length"}
        assert payload["rows"].shape == (4, 21 + 17, 128)
        b.submit(_ids(5, 6), 30, rng_seed=2)              # takes the slot
        got = b.run()[sid]
    seq = np.concatenate([prompt, got])
    want = ref_logits(CFG, params, np.pad(seq, (0, -len(seq) % 4)))
    np.testing.assert_array_equal(
        got, want[len(prompt) - 1:len(seq) - 1].argmax(-1))
    mine = {}
    for s in [s for s in range(3) if s != slot] + [slot]:
        for lengths, logits in tap.rows:
            pos = int(lengths[s])
            if pos >= 21 and pos not in mine and pos < len(seq) and \
                    rel_err(logits[s], want[pos]) < TOL:
                mine[pos] = True
    assert len(mine) == 39                   # every decode step of the stream
    rep = b.report()
    assert rep["evicted"] == 1
    assert np.asarray(rep["expert_tokens"]).shape == (2, 8)
    # 5 choices a running slot a published layer (2), not a sublayer (4)
    assert rep["routed_assignments"] == rep["routed_local"] \
        + rep["zero_assignments"]
    assert 0 < rep["zero_assignments"] < rep["routed_assignments"]
    assert rep["kv_row_bytes"] == 128 * 4
    assert rep["latent_rows_capacity"] == 120 * 4


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


def test_a_free_slots_identity_choices_are_not_counted(params):
    """One running slot of three: the step's counter gains 5 assignments a
    published layer, the identity column among them, and nothing for the two
    free slots' token-0 math."""
    b = ContinuousBatcher(CFG, params, BCFG)
    b.submit(_ids(6, 1), 9, rng_seed=0)
    b.run()
    rep = b.report()
    assert rep["steps"] == 8
    assert rep["routed_assignments"] == 8 * 5 * 2
    assert rep["routed_local"] + rep["zero_assignments"] == 8 * 5 * 2


# -- the named mistakes ---------------------------------------------------------

def _moe_with(change):
    """``moe.moe_layer`` replaced by ``change(real, cfg, mp, u, active)``."""
    def make(monkeypatch):
        real = moe.moe_layer
        monkeypatch.setattr(hybrid, "moe_layer",
                            lambda cfg, mp, u, active=None: change(
                                real, cfg, mp, u, active))
        return CFG
    return make


def _identity_dropped(real, cfg, mp, u, active):
    plain = dataclasses.replace(cfg, zero_experts=0)
    out, counts = real(plain, mp, u, active)
    return out, jnp.concatenate([counts, jnp.zeros((1,), jnp.int32)])


def _identity_of_twice_the_input(real, cfg, mp, u, active):
    """The identity applied to another tensor than ``u0`` (here 2 u0: the
    walk hands the routed layer nothing else to mistake it for)."""
    out, counts = real(cfg, mp, u, active)
    none, _ = _identity_dropped(real, cfg, mp, u, active)
    return out + (out - none), counts


def _shortcut_joined_before_the_second_attention(monkeypatch):
    real = hybrid._shortcut

    def shortcut(cfg, mp, h, g, term, counts=None, active=None):
        g, term, counts = real(cfg, mp, h, g, term, counts, active)
        return (g, None, counts) if term is None else (g + term, 0.0 * term,
                                                       counts)

    monkeypatch.setattr(hybrid, "_shortcut", shortcut)
    return CFG


def _route_with(change):
    def make(monkeypatch):
        real = moe.route

        def route(cfg, router_w, u, bias=None):
            return change(real, cfg, router_w, u, bias)

        monkeypatch.setattr(moe, "route", route)
        return CFG
    return make


def _weights_renormalised(real, cfg, router_w, u, bias):
    idx, w = real(cfg, router_w, u, bias)
    return idx, w / jnp.sum(w, -1, keepdims=True) * cfg.route_scale


def _bias_in_the_weights(real, cfg, router_w, u, bias):
    idx, w = real(cfg, router_w, u, bias)
    return idx, w + cfg.route_scale * bias[idx]


def _bias_dropped(real, cfg, router_w, u, bias):
    return real(cfg, router_w, u, jnp.zeros_like(bias))


def _latent_cached_without_its_rank_scale(monkeypatch):
    real = mla.project

    def project(cfg, geo, lp, x, rotate, scale):
        q_nope, q_rope, row = real(cfg, geo, lp, x, rotate, scale)
        rank = cfg.kv_lora_rank
        return q_nope, q_rope, row.at[..., :rank].divide(cfg.kv_rank_scale)

    monkeypatch.setattr(mla, "project", project)
    return CFG


def _query_rank_scale_left_out(monkeypatch):
    monkeypatch.setattr(
        mla, "query_scale",
        lambda cfg, geo, positions: jnp.ones(positions.shape, jnp.float32))
    return CFG


MISTAKES = {
    "identity-part-dropped": _moe_with(_identity_dropped),
    "identity-of-another-tensor-than-u0": _moe_with(
        _identity_of_twice_the_input),
    "shortcut-joined-before-the-second-attention":
        _shortcut_joined_before_the_second_attention,
    "weights-renormalised-over-the-chosen": _route_with(
        _weights_renormalised),
    "selection-bias-let-into-the-weights": _route_with(_bias_in_the_weights),
    "selection-bias-dropped": _route_with(_bias_dropped),
    "latent-cached-without-its-rank-scale":
        _latent_cached_without_its_rank_scale,
    "query-rank-scale-left-out": _query_rank_scale_left_out,
}


@pytest.mark.parametrize("name", sorted(MISTAKES))
def test_a_named_mistake_fails(monkeypatch, name):
    """The comparison above is tight enough: the same prefill-then-decode
    through the batcher, with one thing wrong, misses the reference by at
    least twenty tolerances at some step."""
    params = make_params(CFG)
    cfg = MISTAKES[name](monkeypatch)
    prompt = _ids(23, 5)
    tap, _, toks = _serve(monkeypatch, cfg, params, prompt, 41, rng_seed=0)
    worst = _worst(tap, 0, CFG, params, prompt, toks)
    assert worst > 20 * TOL, worst


# -- what refuses the family, by name --------------------------------------------

def _refusals():
    from edgellm_tpu.parallel import SplitConfig, SplitRuntime, \
        make_stage_mesh
    from edgellm_tpu.serve import speculative

    p = None     # no refusal below gets as far as touching parameters
    geometry = dict(num_pages=9, page_size=4, max_slots=2, pages_per_slot=4)
    yield "prefix-sharing", lambda: PagedKVCache(
        CFG, **geometry, prefix_cache=PrefixCacheConfig())
    yield "quantized-kv-tier", lambda: PagedKVCache(
        CFG, **geometry, kv_codec="int8_per_channel")
    yield "checkpoint-dir", lambda: ContinuousBatcher(
        CFG, p, dataclasses.replace(BCFG, checkpoint_dir="/nonexistent"))
    yield "split-runtime", lambda: SplitRuntime(
        CFG, SplitConfig(cuts=(1,), hop_codecs=("fp16",)),
        make_stage_mesh(2))
    yield "prefill-hold", lambda: ContinuousBatcher(
        CFG, p, BCFG).prefill_hold(0)
    yield "speculation", lambda: speculative.draft_from_params(
        CFG, p, speculative.SpecConfig())


REFUSALS = dict(_refusals())      # the calls are lambdas: nothing runs yet


@pytest.mark.parametrize("name", sorted(REFUSALS))
def test_a_mechanism_that_moves_k_and_v_rows_refuses_the_family_by_name(name):
    with pytest.raises(LatentRowsUnsupported) as e:
        REFUSALS[name]()
    msg = str(e.value)
    assert "'longcat_flash'" in msg and "cache ONE row a position" in msg
    assert "4 latent-attention layers" in msg and "no fallback" in msg


# -- hf_loader -------------------------------------------------------------------

def _hf(**over):
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "longcat-flash-chat-ep32.json")) as f:
        published = json.load(f)
    return types.SimpleNamespace(**{**published, **over})


def test_hf_loader_maps_the_published_config():
    from edgellm_tpu.models.hf_loader import config_from_hf

    full = _hf(num_layers=28, num_hidden_layers=28, n_routed_experts=512,
               vocab_size=131072)
    assert config_from_hf(full) == LONGCAT_FLASH_CHAT
    cut = config_from_hf(_hf())
    assert (cut.num_layers, len(cut.layer_types), cut.num_experts,
            cut.vocab_size, cut.kv_row_lanes) == (4, 8, 16, 16384, 640)
    assert cut.intermediate_size == 12288 and cut.zero_experts == 256


@pytest.mark.parametrize("over,match", [
    (dict(zero_expert_type="zero"), "zero_expert_type"),
    (dict(attention_method="GQA"), "attention_method"),
    (dict(attention_bias=True), "attention_bias"),
    (dict(router_bias=True), "router_bias"),
    (dict(rope_scaling={"type": "yarn"}), "rope_scaling"),
    (dict(norm_topk_prob=True), "norm_topk_prob"),
    (dict(mla_scale_kv_lora=False), "mla_scale_kv_lora"),
    (dict(num_hidden_layers=8), "num_hidden_layers"),
    (dict(intermediate_size=2048), "intermediate_size"),
    (dict(num_key_value_heads=8), "num_key_value_heads"),
    (dict(model_type="longcat_flash_2"), "unsupported model_type"),
])
def test_hf_loader_refuses_a_longcat_flash_it_does_not_know(over, match):
    from edgellm_tpu.models.hf_loader import config_from_hf

    with pytest.raises(ValueError, match=match):
        config_from_hf(_hf(**over))


# -- the normal path ------------------------------------------------------------

def test_run_py_serves_the_family_through_the_front_and_the_batcher(tmp_path,
                                                                    capsys):
    from edgellm_tpu.run import main

    params = {"experiment": "serve",
              "serving": {"admission": {"max_queue_depth": 8},
                          "capacity_round": 16,
                          "soak": {"n_requests": 3, "arrival_rate": 2.0,
                                   "prompt_len": 30, "max_new_tokens": 12}},
              "batching": {"page_size": 4, "num_pages": 41, "max_slots": 2,
                           "pages_per_slot": 12}}
    assert main(["--params", json.dumps(params), "--model",
                 "tiny-longcat-flash", "--output-dir",
                 str(tmp_path / "out")]) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["outcomes"] == {"completed": 3} and line["mode"] == "batched"
    rep = json.load(open(tmp_path / "out" / "serve_report.json"))
    assert [len(t) for t in rep["tokens"]] == [12, 12, 12]
