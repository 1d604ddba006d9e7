"""The ``keye_vl2`` family (Kwai Keye-VL 2.0's language model: rotated,
q/k-normed GQA layers whose query attends the ``index_topk`` positions a
learned indexer scores highest, an index key a position cached in the page
pool's second leaf; every feed-forward routed experts by the softmax over
the chosen, none shared; an untied head) against its plain reference, on the
CPU at toy widths with seeded float32 weights.

The reference is ``benchmark/reference_keye_vl2.py``: float32 at ``highest``,
whole sequences, no cache, no pages, the selection by ``jax.lax.top_k`` over
each row of the index scores literally, the rotation from three position
streams, nothing imported from the program. Both sides compute in float32
here, so they differ by summation order alone.

TOL: logits are compared as ``max |system - reference| <= TOL * max
|reference|``. 2e-5 is ~100 float32 roundings of a two-layer stack whose sums
run over at most 96 terms; the readings are 2e-7 to 1e-6. A top-k is a
discrete choice: the toy's weights are seeded wide (``make_params``) so that
no test position has its k-th and (k+1)-th index score, or router logit,
within a rounding of each other, and so that WHICH rows are attended moves
the logits by 1e-2 and more (``test_a_named_mistake_fails`` holds each wrong
selection, and bfloat16 where float32 is stated, to twenty tolerances).
"""
import dataclasses
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

from benchmark import reference_keye_vl2 as ref  # noqa: E402
from edgellm_tpu.models import (hybrid, moe, paged_kv,  # noqa: E402
                                sparse_attn, transformer)
from edgellm_tpu.models.configs import (KEYE_VL_2_0_30B_A3B,  # noqa: E402
                                        PRESETS, ModelConfig, tiny_config,
                                        tiny_keye_vl2_config)
from edgellm_tpu.models.hf_loader import (config_from_hf,  # noqa: E402
                                          params_from_state_dict)
from edgellm_tpu.models.hybrid import IndexKeysUnsupported  # noqa: E402
from edgellm_tpu.serve import batching  # noqa: E402
from edgellm_tpu.serve.batching import (BatchingConfig,  # noqa: E402
                                        ContinuousBatcher)
from edgellm_tpu.serve.decode import generate  # noqa: E402
from test_hybrid import LogitTap, _ids, rel_err  # noqa: E402

TOL = 2e-5
TOPK = 8
CFG = tiny_keye_vl2_config()          # two sparse layers, topk 8
BCFG = BatchingConfig(page_size=4, num_pages=121, max_slots=3,
                      pages_per_slot=40)


def ref_config(cfg: ModelConfig) -> dict:
    """The published keys the reference reads, from a ModelConfig."""
    return {
        "hidden_size": cfg.hidden_size, "head_dim": cfg.head_dim,
        "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads,
        "num_hidden_layers": cfg.num_layers,
        "rms_norm_eps": cfg.norm_eps, "rope_theta": cfg.rope_theta,
        "rope_scaling": {"mrope_section": list(cfg.mrope_section),
                         "rope_type": "default"},
        "sa_config": {"indexer_head_dim": cfg.index_head_dim,
                      "indexer_num_heads": cfg.index_heads,
                      "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                      "q_chunk_size": 512, "topk": cfg.index_topk},
        "num_experts": cfg.local_experts,
        "num_experts_per_tok": cfg.experts_per_tok,
        "norm_topk_prob": True, "tie_word_embeddings": False,
        "share": {"router_experts": cfg.num_experts,
                  "experts_held": cfg.local_experts,
                  "expert_offset": cfg.expert_offset}}


def make_params(cfg, seed=0):
    """Seeded weights, every matrix at std 0.06 instead of 0.02 and norm
    scales off one (at width 48 that makes attention and the experts each a
    visible part of the logits), the router at std 0.2, the index key's
    LayerNorm bias off zero (std 0.1: a path that drops it would pass at
    zero), and ``wv`` three times wider again, so that WHICH rows a query
    attends moves the logits."""
    params = transformer.init_params(cfg, jax.random.key(seed))
    keys = iter(jax.random.split(jax.random.key(seed + 1), 256))

    def shake(path, a):
        name = path[-1].key
        if name.endswith("_scale") or name in ("q_norm", "k_norm"):
            return a + 0.1 * jax.random.normal(next(keys), a.shape)
        if name == "index_norm_bias":
            return 0.1 * jax.random.normal(next(keys), a.shape)
        if name == "router":
            return a * 10.0
        return a * (9.0 if name == "wv" else 3.0)

    return jax.tree_util.tree_map_with_path(shake, params)


@pytest.fixture(scope="module")
def params():
    return make_params(CFG)


def ref_logits(cfg, params, ids):
    return np.asarray(ref.logits(ref.model_key(ref_config(cfg)), params,
                                 jnp.asarray(ids)))


def _forward(cfg, params, ids):
    with jax.default_matmul_precision("highest"):
        return jax.jit(lambda p, i: transformer.forward(cfg, p, i)[0])(
            params, jnp.asarray(ids)[None])[0]


def _pad(ids, multiple=ref.QUERY_BLOCK):
    """The reference attends whole blocks of query rows: ids padded at the
    end (causal: a position's logits do not see what follows it)."""
    ids = np.asarray(ids)
    if len(ids) <= multiple:
        return ids
    return np.concatenate([ids, np.zeros(-len(ids) % multiple, ids.dtype)])


# -- the configuration --------------------------------------------------------

def test_the_preset_holds_the_published_numbers():
    c = PRESETS["keye-vl-2.0-30b-a3b"]
    assert c is KEYE_VL_2_0_30B_A3B and c.family == "keye_vl2"
    assert (c.num_layers, c.hidden_size, c.num_heads, c.num_kv_heads,
            c.head_dim, c.vocab_size) == (48, 2048, 32, 4, 128, 151936)
    assert set(c.layer_types) == {"sparse_attention"}
    assert (c.sparse_layers, c.kv_layers) == (48, 48)
    assert (c.index_heads, c.index_head_dim, c.index_topk) == (16, 64, 2048)
    assert c.mrope_section == (16, 24, 24) and c.rotary_dim == 128
    assert (c.num_experts, c.experts_per_tok, c.expert_width,
            c.shared_width, c.num_dense_layers) == (128, 8, 768, 0, 0)
    assert c.rope_theta == 1e7 and c.norm_eps == 1e-6
    assert not c.tie_word_embeddings and not c.recurrent_state
    # a position's rows: K and V of 4 x 128 lanes, the index key stored in
    # one whole lane tile
    assert (c.kv_row_lanes, c.index_row_lanes) == (512, 128)
    assert PRESETS["tiny-keye-vl2"] == tiny_config("keye_vl2") == CFG
    # a family without an indexer has no such leaf
    assert PRESETS["trinity-mini"].index_row_lanes == 0
    assert PRESETS["qwen2-0.5b"].sparse_layers == 0


@pytest.mark.parametrize("bad", [
    dict(index_topk=0), dict(index_heads=0), dict(index_head_dim=7),
    dict(mrope_section=(2, 3, 4)), dict(layer_types=("attention",) * 2),
    dict(family="mellum", layer_types=("attention",) * 2)])
def test_a_config_the_family_cannot_be_is_refused(bad):
    with pytest.raises(ValueError):
        dataclasses.replace(CFG, **bad)


def test_indexer_fields_belong_to_the_family():
    with pytest.raises(ValueError, match="indexer"):
        dataclasses.replace(PRESETS["tiny-qwen2"], index_topk=4)


# -- hf_loader ------------------------------------------------------------------

PUBLISHED = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 262144, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "KeyeVL2",
    "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 48, "num_key_value_heads": 4,
    "num_local_experts": 128, "rms_norm_eps": 1e-06,
    "rope_scaling": {"mrope_section": [16, 24, 24], "rope_type": "default",
                     "type": "default"},
    "rope_theta": 10000000,
    "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16,
                  "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                  "q_chunk_size": 512, "topk": 2048},
    "sliding_window": None, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936}


def test_hf_loader_maps_the_published_config():
    """The six ``sa_config`` keys and the shared ones: the published file
    read as attributes gives the preset."""
    assert config_from_hf(
        types.SimpleNamespace(**PUBLISHED)) == KEYE_VL_2_0_30B_A3B


@pytest.mark.parametrize("over, match", [
    (dict(use_sliding_window=True), "use_sliding_window=True"),
    (dict(decoder_sparse_step=2), "decoder_sparse_step=2"),
    (dict(mlp_only_layers=[0, 1]), r"mlp_only_layers=\[0, 1\]"),
    (dict(norm_topk_prob=False), "norm_topk_prob=False"),
    (dict(attention_bias=True), "attention_bias=True"),
    (dict(tie_word_embeddings=True), "tie_word_embeddings=True"),
    (dict(rope_scaling={"mrope_section": [16, 24, 20],
                        "rope_type": "default"}),
     r"mrope_section \[16, 24, 20\] sums to 60, not to head_dim / 2 = 64"),
    (dict(rope_scaling={"mrope_section": [16, 24, 24], "rope_type": "yarn"}),
     "rope_scaling="),
    (dict(sa_config={**PUBLISHED["sa_config"], "indexer_num_kv_heads": 2}),
     "indexer_num_kv_heads=2"),
    (dict(sa_config={**PUBLISHED["sa_config"], "q_chunk_size": 0}),
     "q_chunk_size must be >= 1"),
])
def test_hf_loader_refuses_a_keye_it_does_not_know(over, match):
    with pytest.raises(ValueError, match=match):
        config_from_hf(types.SimpleNamespace(**{**PUBLISHED, **over}))


def _state_dict(cfg, params):
    """A state_dict under the names ``hf_loader`` assumes (torch's (out, in)
    orientation), from the per-kind tree."""
    sd = {"model.embed_tokens.weight": params["embed"],
          "model.norm.weight": params["final_norm_scale"],
          "lm_head.weight": params["lm_head"].T}
    names = {"ln1_scale": ("input_layernorm.weight", False),
             "wq": ("self_attn.q_proj.weight", True),
             "wk": ("self_attn.k_proj.weight", True),
             "wv": ("self_attn.v_proj.weight", True),
             "wo": ("self_attn.o_proj.weight", True),
             "q_norm": ("self_attn.q_norm.weight", False),
             "k_norm": ("self_attn.k_norm.weight", False),
             "wq_index": ("self_attn.indexer.wq.weight", True),
             "wk_index": ("self_attn.indexer.wk.weight", True),
             "index_norm_scale": ("self_attn.indexer.k_norm.weight", False),
             "index_norm_bias": ("self_attn.indexer.k_norm.bias", False),
             "w_index": ("self_attn.indexer.weights_proj.weight", True)}
    for i in range(cfg.num_layers):
        pre = f"model.layers.{i}."
        for leaf, (name, turned) in names.items():
            a = params["sparse"][leaf][i]
            sd[pre + name] = a.T if turned else a
        mp = params["moe"][i]
        sd[pre + "post_attention_layernorm.weight"] = mp["ln2_scale"]
        sd[pre + "mlp.gate.weight"] = mp["router"].T
        for e in range(cfg.num_experts):
            for leaf, name in (("w_gate", "gate_proj"), ("w_up", "up_proj"),
                               ("w_down", "down_proj")):
                sd[f"{pre}mlp.experts.{e}.{name}.weight"] = mp[leaf][e].T
    return {k: np.asarray(v) for k, v in sd.items()}


def test_hf_loader_maps_a_state_dict_to_the_per_kind_tree(params):
    """Every tensor name listed under the configuration file's ``assumed``
    lands in its leaf, and the tree serves the same logits."""
    sd = _state_dict(CFG, params)
    got = params_from_state_dict(CFG, sd)
    assert sorted(got) == sorted(params)
    assert sorted(got["sparse"]) == sorted(params["sparse"])
    jax.tree_util.tree_map(np.testing.assert_array_equal, got, params)
    with pytest.raises(ValueError, match="projection bias"):
        params_from_state_dict(CFG, {
            **sd, "model.layers.0.self_attn.q_proj.bias": np.zeros(64)})
    with pytest.raises(ValueError, match="holds every expert"):
        params_from_state_dict(dataclasses.replace(CFG, experts_held=4), sd)


# -- the rotation -----------------------------------------------------------------

def test_three_equal_streams_rotate_as_the_plain_table():
    """(vi) ``mrope_section`` cuts the head's frequencies among three
    position streams; on text the three are equal and the reference's
    table, built from the streams literally, IS ``apply_rotary``'s plain
    one; with unequal streams it is not (the test can fail)."""
    for cfg in (CFG, KEYE_VL_2_0_30B_A3B):
        k = dict(ref.model_key(ref_config(dataclasses.replace(
            cfg, num_layers=2, layer_types=("sparse_attention",) * 2))))
        s = 50
        cos, sin = ref.mrope_table(k, ref.text_positions(s))
        pcos, psin = transformer.precompute_rope(cfg, s)
        # 5e-6: a float32 angle of up to 49 rad carries 4e-6 of rounding,
        # and the two sides form pos * inv_freq in different orders
        np.testing.assert_allclose(cos, pcos, rtol=0, atol=5e-6)
        np.testing.assert_allclose(sin, psin, rtol=0, atol=5e-6)
        apart = jnp.stack([jnp.arange(s), jnp.arange(s) // 2,
                           jnp.arange(s) // 3])
        assert float(jnp.abs(ref.mrope_table(k, apart)[0] - pcos).max()) > .1
    x = jax.random.normal(jax.random.key(0), (1, 50, 4, 16))
    cos, sin = ref.mrope_table(dict(ref.model_key(ref_config(CFG))),
                               ref.text_positions(50))
    np.testing.assert_allclose(
        transformer.apply_rotary(x, *transformer.precompute_rope(CFG, 50),
                                 16)[0],
        ref._rotate(x[0], cos, sin), rtol=0, atol=2e-5)


# -- forward, prefill, decode ----------------------------------------------------

@pytest.mark.parametrize("length", [TOPK - 1, TOPK, TOPK + 1, 5 * TOPK])
def test_forward_matches_the_reference(params, length):
    ids = _ids(length, length)
    assert rel_err(_forward(CFG, params, ids), ref_logits(CFG, params,
                                                          ids)) < TOL


def test_forward_spans_prefill_blocks(monkeypatch, params):
    """A prompt of several query blocks (QBLOCK cut to 16 so that the toy
    crosses block edges inside and past ``topk``) against the reference,
    whose own blocks are 128 rows."""
    monkeypatch.setattr(sparse_attn, "QBLOCK", 16)
    ids = _ids(150, 3)
    want = ref_logits(CFG, params, _pad(ids))[:150]
    assert rel_err(_forward(CFG, params, ids), want) < TOL


def test_forward_takes_a_batch_and_refuses_a_hook(params):
    ids = np.stack([_ids(24, 1), _ids(24, 2)])
    with jax.default_matmul_precision("highest"):
        got, _ = transformer.forward(CFG, params, jnp.asarray(ids))
    for row, want in zip(got, ids):
        assert rel_err(row, ref_logits(CFG, params, want)) < TOL
    with pytest.raises(IndexKeysUnsupported, match="boundary hook"):
        transformer.forward(CFG, params, jnp.asarray(ids),
                            boundary_fn=lambda h, i: h)


def test_a_short_context_is_plain_attention(params):
    """(ii) At a context of at most ``topk`` a sparse layer equals an
    ``attention`` layer on the same weights: a mellum stack (full layers,
    q/k norms aside the family differs only by the rope's scaling, so the
    comparison is of the attention sublayer itself)."""
    lp = {k: v[0] for k, v in params["sparse"].items()}
    x = jax.random.normal(jax.random.key(3), (1, TOPK, CFG.hidden_size))
    rope = transformer.precompute_rope(CFG, TOPK)
    with jax.default_matmul_precision("highest"):
        got, k, v, _ = sparse_attn.attention_full(
            CFG, lp, x, rope, sparse_attn.index_rope(CFG, TOPK))
        # the same leaves through hybrid's plain attention sublayer (q/k
        # norms applied where the layer holds them)
        want, k2, v2 = hybrid._attention_full(CFG, lp, x, rope)
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-6)
    np.testing.assert_array_equal(k, k2)
    # one position more and the two part: the 9th query leaves a row out
    x = jax.random.normal(jax.random.key(3), (1, TOPK + 1, CFG.hidden_size))
    rope = transformer.precompute_rope(CFG, TOPK + 1)
    with jax.default_matmul_precision("highest"):
        got = sparse_attn.attention_full(
            CFG, lp, x, rope, sparse_attn.index_rope(CFG, TOPK + 1))[0]
        want = hybrid._attention_full(CFG, lp, x, rope)[0]
    np.testing.assert_allclose(got[:, :TOPK], want[:, :TOPK], rtol=0,
                               atol=1e-6)
    assert float(jnp.abs(got[:, TOPK] - want[:, TOPK]).max()) > 1e-3


@pytest.mark.parametrize("plen", [1, TOPK - 1, TOPK, 41])
def test_contiguous_decode_step_matches_the_reference(params, plen):
    """(iv) the block-masked prefill and the one-query decode give the same
    output position by position under teacher forcing: both against the
    reference's full forward."""
    ids = _ids(plen + 30, plen)
    want = ref_logits(CFG, params, ids)
    with jax.default_matmul_precision("highest"):
        logits, cache = hybrid.prefill_hybrid(CFG, params,
                                              jnp.asarray(ids[None, :plen]),
                                              80)
        assert isinstance(cache, hybrid.SparseCache)
        assert cache.index.shape == (2, 1, 80, 128)
        assert rel_err(logits[0], want[:plen]) < TOL
        step = jax.jit(lambda c, t: hybrid.decode_step_hybrid(CFG, params, c,
                                                              t))
        for t in range(plen, plen + 30):
            lg, cache = step(cache, jnp.asarray(ids[t:t + 1]))
            assert rel_err(lg[0], want[t]) < TOL, t


def _worst(tap, slot, cfg, params, prompt, tokens):
    seq = np.concatenate([prompt, tokens])
    want = ref_logits(cfg, params, _pad(seq))
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


@pytest.mark.parametrize("page_size", [4, 3])     # 8 = 2 pages; 8 = 2.67
@pytest.mark.parametrize("plen", [TOPK - 1, TOPK, TOPK + 1, 5 * TOPK])
def test_prefill_then_paged_decode_through_the_batcher_matches_the_full_forward(
        monkeypatch, params, plen, page_size):
    """(i) The batcher's own admission (the prefill's K/V rows AND index keys
    adopted into the slot's pages) and 30 paged steps, each step's logits
    against the reference's full forward over the whole sequence, at
    contexts around ``topk`` and far past it, the page size dividing
    ``topk`` and not."""
    bcfg = BatchingConfig(page_size=page_size, num_pages=161, max_slots=3,
                          pages_per_slot=40)
    prompt = _ids(plen, plen)
    tap, b, toks = _serve(monkeypatch, CFG, params, prompt, 31, bcfg=bcfg,
                          rng_seed=0)
    assert isinstance(b.pool.pool, paged_kv.IndexedPagePool)
    assert len(tap.of_slot(0)) == 30
    assert _worst(tap, 0, CFG, params, prompt, toks) < TOL
    want0 = ref_logits(CFG, params, prompt)[-1]
    assert want0[toks[0]] >= want0.max() - TOL * np.abs(want0).max()
    rep = b.report()
    assert rep["sparse_read"] == sparse_attn.ROW_GATHER
    assert rep["sparse_prefill"] == sparse_attn.XLA_BLOCKS
    # this backend is no TPU: the index keys come by the page gather
    assert rep["index_read"] == paged_kv.PAGE_GATHER
    assert rep["index_pages_walked"] == rep["index_pages_in_runs"] == 0
    live = sum(range(plen + 1, plen + 31))
    assert rep["sparse_rows_live"] == rep["index_rows_scored"] == live
    assert rep["sparse_rows_attended"] == sum(
        min(n, TOPK) for n in range(plen + 1, plen + 31))
    assert rep["routed_assignments"] == 30 * 3 * 2 == rep["routed_local"]


def test_a_pool_no_slot_of_which_can_pass_topk_skips_the_selection(
        monkeypatch, params):
    """A slot of at most ``topk`` positions attends them all: a pool whose
    span is ``topk`` builds the step without the indexer's score pass, still
    writes its index keys, and serves the reference's logits."""
    bcfg = BatchingConfig(page_size=4, num_pages=9, max_slots=2,
                          pages_per_slot=2)
    prompt = _ids(3, 3)
    tap, b, toks = _serve(monkeypatch, CFG, params, prompt, 6, bcfg=bcfg)
    assert _worst(tap, 0, CFG, params, prompt, toks) < TOL
    rep = b.report()
    assert rep["sparse_read"] == sparse_attn.EVERY_ROW
    assert rep["index_read"] is None and rep["index_rows_scored"] == 0
    assert rep["sparse_rows_attended"] == rep["sparse_rows_live"] > 0
    assert float(jnp.abs(b.pool.pool.ik).max()) > 0


def test_batcher_tokens_equal_generate_and_survive_an_eviction(params):
    prompts = [_ids(n, n) for n in (1, 13, 36)]
    temps = [0.0, 0.7, 0.0]

    def serve(evict):
        b = ContinuousBatcher(CFG, params, BCFG)
        sids = [b.submit(p, 20, temperature=t, rng_seed=i)
                for i, (p, t) in enumerate(zip(prompts, temps))]
        if evict:
            for _ in range(6):
                b.step()
            b.evict(sids[2])
        res = b.run()
        b.pool.check_invariants()
        assert b.report()["evicted"] == int(evict)
        return [res[s] for s in sids]

    plain, evicted = serve(False), serve(True)
    for i, (p, t) in enumerate(zip(prompts, temps)):
        want = np.asarray(generate(CFG, params, p[None], 20, temperature=t,
                                   rng_key=jax.random.key(i)))[0]
        np.testing.assert_array_equal(plain[i], want)
        np.testing.assert_array_equal(evicted[i], want)


# -- the selection ------------------------------------------------------------------

def _reference_set(scores, t, k):
    """The reference's selection for the query at position t."""
    row = np.asarray(ref.selected({"topk": k}, jnp.asarray(scores)[None],
                                  jnp.asarray([[t]]))[0])
    return set(np.flatnonzero(row))


@pytest.mark.parametrize("case", ["random", "tie", "zeros", "short"])
def test_the_rows_a_step_attends_are_the_references_top_k_set(case):
    """(iii) ``select`` (decode: row ids) and ``selection_mask`` (prefill: a
    mask) against the reference's literal ``top_k``: on random scores, with a
    constructed tie across the k-th place (the earlier position wins), on an
    all-zero row (the first k positions), and where fewer than k are
    live."""
    k, c = 8, 40
    scores = np.array(jax.random.normal(jax.random.key(7), (c,)))
    t = c - 1
    if case == "tie":
        scores[[3, 17, 30, 31]] = 0.25          # four equal ...
        order = np.argsort(-scores, kind="stable")
        above = [i for i in order if scores[i] > 0.25][:6]
        scores[[i for i in range(c) if scores[i] > 0.25
                and i not in above]] = -1.0     # ... two places left
        want = set(above) | {3, 17}
    elif case == "zeros":
        scores[:] = 0.0
        want = set(range(k))
    elif case == "short":
        t = 4
        want = set(range(5))
    else:
        want = set(np.argsort(-scores)[:k])
    assert _reference_set(scores, t, k) == want
    idx, count = sparse_attn.select(jnp.asarray(scores)[None],
                                    jnp.asarray([t + 1]), k)
    assert int(count[0]) == len(want)
    assert set(np.asarray(idx[0, :int(count[0])]).tolist()) == want
    visible = (jnp.arange(c)[None, :] <= jnp.asarray([[t]]))
    mask = sparse_attn.selection_mask(jnp.asarray(scores)[None, None],
                                      visible, k)
    assert set(np.flatnonzero(np.asarray(mask[0, 0]))) == want


def test_a_score_of_minus_zero_ties_with_zero():
    """relu(dot) * a negative weight is -0.0: the scores are made +0.0 so
    that an all-zero row's tie is decided by position, bit for bit."""
    qi = jnp.zeros((1, 3, 8)).at[0, 0, 0].set(1.0)
    rows = jnp.zeros((1, 20, 128)).at[0, :, 0].set(-1.0)   # every dot < 0
    wi = jnp.asarray([[-1.0, 1.0, -2.0]])
    scores = sparse_attn.index_scores(qi, wi, rows)
    assert not np.signbit(np.asarray(scores)).any()
    idx, _ = sparse_attn.select(scores, jnp.asarray([20]), 8)
    assert sorted(np.asarray(idx[0]).tolist()) == list(range(8))


def test_the_paged_steps_row_ids_are_the_references_set(params):
    """The flat row ids a paged decode step gathers name exactly the
    positions the reference's indexer selects for that query, through a
    page table whose pages are out of order."""
    cfg, plen = CFG, 30
    ids = _ids(plen + 1, 5)
    lp = {k: v[0] for k, v in params["sparse"].items()}
    k = dict(ref.model_key(ref_config(cfg)))
    with jax.default_matmul_precision("highest"):
        h = hybrid.embed_hybrid(cfg, params, jnp.asarray(ids))
        u = hybrid._rms(cfg, h, lp["ln1_scale"])
        qi, ki, wi = ref.index_scores(k, lp, u, False)
        dots = jnp.einsum("jd,td->jt", qi[plen], ki)
        want = _reference_set(np.asarray(jnp.sum(
            jax.nn.relu(dots) * wi[plen][:, None], axis=0)), plen, TOPK)
        # the program's: index keys of the prompt adopted, the query's own
        # written by the step
        _, kk, vv, ik = sparse_attn.attention_full(
            cfg, lp, u[None], transformer.precompute_rope(cfg, plen + 1),
            sparse_attn.index_rope(cfg, plen + 1))
        cache = paged_kv.PagedKVCache(cfg, num_pages=41, page_size=4,
                                      max_slots=2, pages_per_slot=10)
        slot = cache.alloc_slot()
        cache.ensure(slot, 3)          # churn: the slot's pages out of order
        other = cache.alloc_slot()
        cache.ensure(other, 9)
        two = lambda a: jnp.stack([a[0, :plen]] * 2)  # noqa: E731
        cache.adopt(slot, two(kk), two(vv), plen, index=two(ik))
        table, lengths = cache.device_tables()
        qi_p, _, wi_p = sparse_attn.project_index(
            cfg, lp, u[plen][None], sparse_attn.rotate_rows(
                *(t[plen:plen + 1] for t in sparse_attn.index_rope(
                    cfg, plen + 1))))
        pool = paged_kv.write_rows(
            cache.pool, 0, table[:1], lengths[:1], kk[:, plen:plen + 1],
            vv[:, plen:plen + 1], index=ik[:, plen])
        scores = sparse_attn.index_scores(
            qi_p, wi_p, paged_kv._gather_pages(pool.ik, 0, table[:1]))
        count, rows = sparse_attn.selected_rows(
            scores, lengths[:1] + 1, TOPK, pool, 0, table[:1])
    flat = cache._flat_indices(slot, plen + 1)
    assert int(count[0]) == TOPK
    got = {int(np.flatnonzero(flat == r)[0]) for r in np.asarray(rows[0])}
    assert got == want and len(want) == TOPK
    assert want != set(range(plen + 1 - TOPK, plen + 1))   # not the newest


# -- the masked walk: the read a TPU takes at the cell's depths ---------------------

WIDE = tiny_keye_vl2_config(num_kv_heads=2, head_dim=64)   # rows of 2 x 128


def _interpreted(*args, kernel=None, **kwargs):
    """The page-walk kernel (or ``kernel``) under the TPU interpreter, WAITED
    FOR (its host callbacks deadlock against a main thread that keeps
    dispatching)."""
    from jax.experimental.pallas import tpu as pltpu

    return jax.block_until_ready((kernel or _KERNEL)(
        *args, **kwargs, interpret=pltpu.InterpretParams()))


from edgellm_tpu.models import flash_attention  # noqa: E402

_KERNEL = flash_attention.paged_decode_walk      # before any test patches it
_INDEX_KERNEL = flash_attention.paged_index_walk


def _a_tpus_reads(monkeypatch):
    """The choices forced as a TPU would make them, both kernels interpreted."""
    import functools

    monkeypatch.setattr(paged_kv, "_on_tpu", lambda: True)
    monkeypatch.setattr(flash_attention, "paged_decode_walk", _interpreted)
    monkeypatch.setattr(flash_attention, "paged_index_walk",
                        functools.partial(_interpreted, kernel=_INDEX_KERNEL))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_the_walk_under_a_mask_attends_the_marked_rows_alone(dtype):
    """``paged_decode_walk(keep=)`` against ``attend_rows`` over the gathered
    span with the same rows valid: slots of several blocks, one whose
    selection leaves whole blocks out (its first and its last), one of a
    single row; every page no table names holds NaN."""
    page, pps, kvh, hd, h = 16, 12, 1, 128, 4
    rng = np.random.default_rng(3)
    lens = np.asarray([150, 1, 77, 192], np.int32)
    pool = rng.standard_normal((40, page, 2 * kvh * hd)).astype(np.float32)
    table = np.zeros((4, pps), np.int32)
    order, at = rng.permutation(39) + 1, 0
    held = np.zeros((40,), bool)
    held[0] = True
    for i, n in enumerate(lens):
        for j in range(-(-n // page)):
            table[i, j] = order[at]
            held[order[at]] = True
            at += 1
    keep = rng.random((4, pps * page)) < 0.2
    keep[0, :64] = False                      # a block (4 pages) of nothing
    keep[0, 128:] = False
    keep[1] = True
    keep &= np.arange(pps * page)[None, :] < lens[:, None]
    keep[3, 5] = True
    assert keep.sum(1).min() >= 1
    q = jnp.asarray(rng.standard_normal((4, 1, h, hd)), dtype)
    clean = jnp.asarray(pool, dtype)
    dirty = jnp.where(jnp.asarray(held)[:, None, None], clean, jnp.nan)
    own, qz = paged_kv._group_lanes(q, kvh)
    got = paged_kv._own_lanes(_interpreted(
        qz, dirty, jnp.asarray(table), jnp.asarray(lens),
        scale=float(hd ** -0.5), pages_per_block=4,
        keep=jnp.asarray(keep)), own)
    rows = clean[jnp.asarray(table)].reshape(4, pps * page, -1)
    want = paged_kv.attend_rows(q, *paged_kv.split_kv(rows), None,
                                jnp.asarray(keep))
    assert bool(jnp.isfinite(got).all())
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol)
    # no mask: the kernel as every other family calls it, the same rows as
    # a mask of every live row
    live = np.arange(pps * page)[None, :] < lens[:, None]
    plain = _interpreted(qz, dirty, jnp.asarray(table), jnp.asarray(lens),
                         scale=float(hd ** -0.5), pages_per_block=4)
    masked = _interpreted(qz, dirty, jnp.asarray(table), jnp.asarray(lens),
                          scale=float(hd ** -0.5), pages_per_block=4,
                          keep=jnp.asarray(live))
    np.testing.assert_allclose(np.asarray(plain, np.float32),
                               np.asarray(masked, np.float32), atol=tol)
    with pytest.raises(ValueError, match="must mark every position"):
        _KERNEL(qz, dirty, jnp.asarray(table), jnp.asarray(lens), scale=1.0,
                keep=jnp.asarray(keep[:, :-1]))


def test_the_read_is_read_off_the_pool_and_the_span(monkeypatch):
    pool = paged_kv.init_pool(WIDE, 9, 8)
    narrow = paged_kv.init_pool(CFG, 9, 8)
    assert sparse_attn.sparse_read_path(WIDE, 8, pool) == \
        sparse_attn.EVERY_ROW
    # this backend is no TPU: the gather, whatever the pool
    assert sparse_attn.sparse_read_path(WIDE, 64, pool) == \
        sparse_attn.ROW_GATHER
    monkeypatch.setattr(paged_kv, "_on_tpu", lambda: True)
    assert sparse_attn.sparse_read_path(WIDE, 64, pool) == \
        sparse_attn.MASKED_WALK
    # however deep the slots: a TPU has the one read
    assert sparse_attn.sparse_read_path(WIDE, 8192, pool) == \
        sparse_attn.MASKED_WALK
    # rows of part tiles take no walk
    assert sparse_attn.sparse_read_path(CFG, 64, narrow) == \
        sparse_attn.ROW_GATHER
    assert sparse_attn.sparse_read_path(WIDE, 64) == sparse_attn.ROW_GATHER
    # the cell
    big = paged_kv.init_pool(dataclasses.replace(
        KEYE_VL_2_0_30B_A3B, num_layers=1,
        layer_types=("sparse_attention",)), 3, 16, jnp.bfloat16)
    assert sparse_attn.sparse_read_path(KEYE_VL_2_0_30B_A3B, 20480, big) == \
        sparse_attn.MASKED_WALK
    # the index keys' read, off the OTHER leaf: an index key is 128 lanes
    # whatever the K/V rows are, so a page of whole sublane tiles walks
    assert paged_kv.index_read_path(pool) == paged_kv.index_read_path(
        narrow) == paged_kv.index_read_path(big) == paged_kv.INDEX_WALK
    assert paged_kv.index_read_path(paged_kv.init_pool(WIDE, 9, 4)) == \
        paged_kv.index_read_path(paged_kv.PagePool(pool.kv)) == \
        paged_kv.PAGE_GATHER
    assert paged_kv.index_walk_geometry(big, 1280) == (128, 8)
    assert paged_kv.walk_geometry(paged_kv.PagePool(big.kv), 1280) == (32, 1)
    monkeypatch.undo()
    assert paged_kv.index_read_path(big) == paged_kv.PAGE_GATHER


def test_the_step_on_the_masked_walk_equals_the_step_on_the_row_gather(
        monkeypatch):
    """``paged_decode_step_hybrid`` of a sparse stack built on the index
    walk and the masked walk (the choices forced as a TPU would make them,
    the kernels interpreted) against the step on the page gather and the row
    gather: logits, both written leaves, the counter. Slot 1 is idle; every
    page no table names holds NaN in BOTH leaves under the walks."""
    import functools

    cfg = WIDE
    params = make_params(cfg)
    page = 8
    table = np.asarray([[1, 2, 3, 6, 0, 0, 0, 0], [0] * 8,
                        [7, 5, 0, 0, 0, 0, 0, 0]], np.int32)
    lens = np.asarray([3 * page + 4, 0, page], np.int32)
    rng = np.random.default_rng(13)
    pool = paged_kv.init_pool(cfg, 9, page)
    assert pool.kv.shape == (2, 9, page, 256)
    clean = paged_kv.IndexedPagePool(*(
        jnp.asarray(rng.standard_normal(a.shape), jnp.float32) for a in pool))
    held = np.zeros((9,), bool)
    held[np.unique(table)] = True
    dead = jnp.asarray(~held)[None, :, None, None]
    step = functools.partial(
        hybrid.paged_decode_step_hybrid, cfg, params, state=None,
        expert_tokens=jnp.zeros((cfg.expert_layers, cfg.local_experts),
                                jnp.int32),
        page_table=jnp.asarray(table), lengths=jnp.asarray(lens),
        token_ids=jnp.asarray([3, 0, 5], jnp.int32))
    with jax.default_matmul_precision("highest"):
        want, want_pool, _, want_cnt = step(pool=clean)
        _a_tpus_reads(monkeypatch)
        assert sparse_attn.sparse_read_path(cfg, 64, pool) == \
            sparse_attn.MASKED_WALK
        assert paged_kv.index_read_path(pool) == paged_kv.INDEX_WALK
        # both leaves are read where they lie: the dead pages of both hold NaN
        got, got_pool, _, got_cnt = jax.block_until_ready(step(
            pool=paged_kv.IndexedPagePool(
                *(jnp.where(dead, jnp.nan, a) for a in clean))))
    assert bool(jnp.isfinite(got).all())
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    np.testing.assert_array_equal(np.asarray(got_cnt), np.asarray(want_cnt))
    for a, b in zip(got_pool, want_pool):
        np.testing.assert_allclose(np.asarray(jnp.where(dead, 0, a)),
                                   np.asarray(jnp.where(dead, 0, b)),
                                   atol=1e-5)


def test_the_batcher_on_a_tpus_reads_serves_the_contiguous_references_tokens(
        monkeypatch):
    """Through the batcher, built on the index walk and the masked walk (the
    choices forced, the kernels interpreted): two streams' tokens equal
    ``generate``'s over a contiguous cache and the same service's on this
    backend's reads; ``report()`` names the index read and counts its pages
    (every live page a layer a step, those of whole groups of a slot's
    table in runs: the pool deals runs of eight 4 KB index pages), and the
    counters of rows a step are what they were."""
    cfg = WIDE
    params = make_params(cfg)
    bcfg = BatchingConfig(page_size=8, num_pages=41, max_slots=2,
                          pages_per_slot=20)
    prompts, new = [_ids(70, 1), _ids(13, 2)], 12
    step_jit = batching._batched_hybrid_step_jit

    def serve():
        step_jit.clear_cache()
        b = ContinuousBatcher(cfg, params, bcfg)
        sids = [b.submit(p, new, rng_seed=i) for i, p in enumerate(prompts)]
        res = b.run()
        b.pool.check_invariants()
        return b.report(), [res[s] for s in sids]

    with jax.default_matmul_precision("highest"):
        oracle, want = serve()
        _a_tpus_reads(monkeypatch)

        def waited(*args):      # the interpreter's callbacks dispatch too
            return jax.block_until_ready(step_jit(*args))

        waited._cache_size = step_jit._cache_size
        monkeypatch.setattr(batching, "_batched_hybrid_step_jit", waited)
        rep, got = serve()
        for i, p in enumerate(prompts):
            np.testing.assert_array_equal(want[i], np.asarray(generate(
                cfg, params, p[None], new, rng_key=jax.random.key(i)))[0])
    step_jit.clear_cache()
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)
    assert (oracle["sparse_read"], oracle["index_read"]) == (
        sparse_attn.ROW_GATHER, paged_kv.PAGE_GATHER)
    assert (rep["sparse_read"], rep["index_read"], rep["decode_read"]) == (
        sparse_attn.MASKED_WALK, paged_kv.INDEX_WALK, paged_kv.PAGE_WALK)
    for name in ("sparse_rows_live", "sparse_rows_attended",
                 "index_rows_scored", "steps"):
        assert rep[name] == oracle[name] > 0, name
    # the same pages as the K/V walk's, of the other leaf; runs of eight
    assert rep["index_pages_walked"] == rep["attend_pages_walked"] > 0
    assert 0 < rep["index_pages_in_runs"] < rep["index_pages_walked"]
    assert rep["index_pages_in_runs"] % 8 == 0
    assert oracle["index_pages_walked"] == oracle["index_pages_in_runs"] == 0


_MASKED = flash_attention.masked_attention


def _a_tpus_prefill(monkeypatch):
    """The prefill's attend chosen as a TPU would choose it, the kernel
    interpreted (traced into a body's ``lax.map``: the caller jits the whole
    prefill and waits for what it hands back)."""
    import functools

    from jax.experimental.pallas import tpu as pltpu

    monkeypatch.setattr(sparse_attn, "_on_tpu", lambda: True)
    monkeypatch.setattr(flash_attention, "masked_attention", functools.partial(
        _MASKED, interpret=pltpu.InterpretParams()))


def test_the_prefill_attend_is_read_off_the_backend_and_the_lanes(
        monkeypatch):
    path = sparse_attn.sparse_prefill_path
    assert path(WIDE, jnp.float32) == path(
        KEYE_VL_2_0_30B_A3B, jnp.bfloat16) == sparse_attn.XLA_BLOCKS
    monkeypatch.setattr(sparse_attn, "_on_tpu", lambda: True)
    assert path(WIDE, jnp.float32) == path(
        KEYE_VL_2_0_30B_A3B, jnp.bfloat16) == sparse_attn.MASKED_KERNEL
    # heads of 16 lanes are no half of a lane tile; float16 is no operand
    # the kernel was built for
    assert path(CFG, jnp.float32) == path(WIDE, jnp.float16) == \
        sparse_attn.XLA_BLOCKS


def test_the_prefill_on_the_masked_kernel_equals_the_xla_blocks(monkeypatch):
    """``attention_full`` built on the masked kernel (the choice forced as a
    TPU makes it, the kernel interpreted) against the XLA blocks, two
    sequences of 150 positions past ``index_topk`` in blocks of 16 rows
    (a body of 128 and 22 rows of a second, six of them left over): K, V
    and the index keys a cache is filled from bit for bit, the outputs to a
    float32 running softmax's reordering; then the whole forward on the
    kernel against the reference."""
    cfg, params = WIDE, make_params(WIDE)
    monkeypatch.setattr(sparse_attn, "QBLOCK", 16)
    lp = hybrid._row(params["sparse"], 1)
    x = jax.random.normal(jax.random.key(5), (2, 150, cfg.hidden_size))
    tables = hybrid._rope_tables(cfg, 150)

    def full(x):
        return sparse_attn.attention_full(
            cfg, lp, x, tables["sparse_attention"], tables["sparse_index"])

    ids = _ids(150, 3)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(full)(x)
        _a_tpus_prefill(monkeypatch)
        jax.clear_caches()
        assert sparse_attn.sparse_prefill_path(cfg, x.dtype) == \
            sparse_attn.MASKED_KERNEL
        got = jax.block_until_ready(jax.jit(full)(x))
        logits = jax.block_until_ready(_forward(cfg, params, ids))
    jax.clear_caches()
    for a, b in zip(got[1:], want[1:]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert rel_err(got[0], want[0]) < 2e-6
    assert rel_err(logits, ref_logits(cfg, params, _pad(ids))[:150]) < TOL


def test_only_a_sparse_stack_reports_a_sparse_prefill():
    """``report()["sparse_prefill"]`` stands beside ``sparse_read`` for a
    stack of sparse layers (the XLA blocks on this backend) and for no
    other."""
    b = ContinuousBatcher(CFG, make_params(CFG), BCFG)
    assert b.report()["sparse_prefill"] == sparse_attn.XLA_BLOCKS
    plain = tiny_config("qwen2", num_layers=1)
    rep = ContinuousBatcher(plain, transformer.init_params(
        plain, jax.random.key(0)), BCFG).report()
    assert "sparse_prefill" not in rep and "sparse_read" not in rep


def test_the_mask_is_the_row_ids_set():
    """``selection_mask`` (the masked walk's, by :func:`kth_largest`) and
    ``select`` (the row gather's, by ``jax.lax.top_k``) choose one set, on
    scores with many exact ties and rows of fewer than k live."""
    k, c = 8, 70
    scores = jnp.round(jax.random.normal(jax.random.key(2), (6, c)) * 2) / 2
    lengths = jnp.asarray([70, 40, 9, 8, 3, 1])
    idx, count = sparse_attn.select(scores, lengths, k)
    live = jnp.arange(c)[None, :] < lengths[:, None]
    mask = np.asarray(sparse_attn.selection_mask(scores, live, k))
    for row in range(6):
        n = int(count[row])
        assert set(np.flatnonzero(mask[row])) == \
            set(np.asarray(idx[row, :n]).tolist()), row
    u, kth = sparse_attn.kth_largest(scores, k)
    want = sparse_attn._ordered(jax.lax.top_k(scores, k)[0][:, -1:])
    np.testing.assert_array_equal(np.asarray(kth), np.asarray(want))


# -- named mistakes -----------------------------------------------------------------

def _newest(scores, lengths, k):
    idx = lengths[:, None] - 1 - jnp.arange(k)[None, :]
    return jnp.maximum(idx, 0).astype(jnp.int32), jnp.minimum(lengths, k)


MISTAKES = {
    "the newest topk rows instead of the selected":
        lambda mp: mp.setattr(sparse_attn, "select", _newest),
    "the index key's LayerNorm bias dropped":
        lambda mp: mp.setattr(
            sparse_attn, "_layernorm",
            lambda x, s, b, eps: transformer._layernorm(x, s, 0 * b, eps)),
    "the indexer left unrotated":
        lambda mp: mp.setattr(sparse_attn, "rotate_rows",
                              lambda cos, sin: (lambda t: t)),
    "relu dropped from the index scores":
        lambda mp: mp.setattr(sparse_attn, "_weighted",
                              lambda dots, wi: jnp.sum(dots * wi, axis=1)),
    "the index key not written by the step":
        lambda mp: mp.setattr(
            sparse_attn, "write_rows",
            lambda pool, *a, index=None, **kw: paged_kv.write_rows(
                pool, *a, index=0 * index, **kw)),
    "bfloat16 index scores (vii)":
        lambda mp: mp.setattr(
            sparse_attn, "index_scores",
            (lambda f: lambda qi, wi, rows: f(
                qi.astype(jnp.bfloat16), wi, rows.astype(jnp.bfloat16)
            ))(sparse_attn.index_scores)),
    "bfloat16 attend over the selected rows (vii)":
        lambda mp: mp.setattr(
            sparse_attn, "attend_rows",
            lambda q, k, v, n: paged_kv.attend_rows(
                q.astype(jnp.bfloat16), k.astype(jnp.bfloat16),
                v.astype(jnp.bfloat16), n).astype(q.dtype)),
}


@pytest.mark.parametrize("name", sorted(MISTAKES))
def test_a_named_mistake_fails(monkeypatch, params, name):
    """Each wrong decode path moves some step's logits by more than twenty
    tolerances: the sound path's margin is not slack. (vii): computing the
    indexer or the attend in bfloat16 where float32 is stated is among
    them: a bf16 score flips selections near the k-th place, a bf16 attend
    rounds every probability."""
    MISTAKES[name](monkeypatch)
    jax.clear_caches()      # a prefill compiled by an earlier test is sound
    prompt = _ids(41, 41)
    try:
        tap, _, toks = _serve(monkeypatch, CFG, params, prompt, 31,
                              rng_seed=0)
        worst = _worst(tap, 0, CFG, params, prompt, toks)
    finally:
        monkeypatch.undo()
        jax.clear_caches()  # ... and this one is not: leave none behind
    assert worst > 20 * TOL, name


# -- the expert layer ---------------------------------------------------------------

def test_the_four_shares_of_a_layer_add_up_to_the_uncut_layer(params):
    """(v) Four chips hold two of the eight experts each: their parts are
    the uncut layer's result, which is the reference's."""
    mp = params["moe"][0]
    u = jax.random.normal(jax.random.key(6), (37, CFG.hidden_size))
    routed = ("w_gate", "w_up", "w_down")
    with jax.default_matmul_precision("highest"):
        whole, counts = moe.moe_layer(CFG, mp, u)
        parts, held = [], []
        for chip in range(4):
            cfg = tiny_keye_vl2_config(experts_held=2, expert_offset=2 * chip)
            mine = {**mp, **{k: mp[k][2 * chip:2 * chip + 2] for k in routed}}
            out, c = moe.moe_layer(cfg, mine, u)
            parts.append(out)
            held.append(np.asarray(c))
        want = ref._moe(dict(ref.model_key(ref_config(CFG))), mp, u, False)
    assert rel_err(sum(parts), np.asarray(whole)) < TOL
    assert rel_err(whole, np.asarray(want)) < TOL
    np.testing.assert_array_equal(np.concatenate(held), np.asarray(counts))
    assert int(counts.sum()) == 37 * 3       # every assignment held once


def test_a_share_of_the_experts_matches_the_reference_given_the_same_share():
    cfg = tiny_keye_vl2_config(experts_held=4, expert_offset=2)
    p = make_params(cfg)
    ids = _ids(5 * TOPK, 9)
    assert p["moe"][0]["w_gate"].shape[0] == 4
    assert p["moe"][0]["router"].shape[1] == 8
    assert rel_err(_forward(cfg, p, ids), ref_logits(cfg, p, ids)) < TOL


# -- the cache: index keys ride the page pool's surgery --------------------------------

def _cache(cfg=CFG, **kw):
    return paged_kv.PagedKVCache(cfg, **{"num_pages": 33, "page_size": 4,
                                         "max_slots": 3, "pages_per_slot": 8,
                                         **kw})


def _rows(cfg, n, seed):
    keys = jax.random.split(jax.random.key(seed), 3)
    shape = (cfg.kv_layers, n, cfg.num_kv_heads, cfg.head_dim)
    return (jax.random.normal(keys[0], shape),
            jax.random.normal(keys[1], shape),
            jax.random.normal(keys[2], (cfg.kv_layers, n,
                                        cfg.index_row_lanes)))


def _same(got, k, v, index):
    np.testing.assert_array_equal(got["k"], k)
    np.testing.assert_array_equal(got["v"], v)
    np.testing.assert_array_equal(got["index"], index)


def test_the_pool_is_two_leaves_under_one_table_and_counts_both():
    cache = _cache()
    pool = cache.pool
    assert isinstance(pool, paged_kv.IndexedPagePool)
    assert pool.kv.shape == (2, 33, 4, 2 * 32)
    assert pool.ik.shape == (2, 33, 4, 128)
    assert (pool.num_pages, pool.page_size, pool.k_lanes) == (33, 4, 32)
    assert paged_kv.pool_tier(pool) == "fp"
    # a page across the layers: K and V rows and the index keys
    page = 2 * 4 * (64 + 128) * 4
    assert paged_kv.kv_page_bytes(CFG, 4) == page
    assert cache.kv_row_bytes == (64 + 128) * 4
    assert paged_kv.num_pages_for_bytes(CFG, 10 * page + 5, 4) == 10
    # the smallest page a walk would fetch: here the K/V leaf's (64 lanes
    # beside the index keys' 128)
    assert paged_kv.page_leaf_bytes(CFG, 4) == 4 * 64 * 4
    assert cache.token_capacity == 32 * 4
    # at the cell's sizes: 216 KiB a page, 9.06 GB
    big = dataclasses.replace(KEYE_VL_2_0_30B_A3B, num_layers=6,
                              layer_types=("sparse_attention",) * 6)
    assert paged_kv.kv_page_bytes(big, 16, dtype=jnp.bfloat16) == 216 * 1024
    # (the K/V leaf's page 32 KB, a fetch by itself; the index keys' 4 KB,
    # which is what the pool's runs are read off: eight to a run)
    assert paged_kv.page_leaf_bytes(big, 16, dtype=jnp.bfloat16) == 4 * 1024


def test_a_family_without_an_indexer_builds_the_one_leaf_pool_it_built():
    for name in ("tiny-qwen2", "tiny-mellum", "tiny-lfm2-moe",
                 "tiny-granite-hybrid"):
        cache = _cache(PRESETS[name])
        assert type(cache.pool) is paged_kv.PagePool and len(cache.pool) == 1
    assert type(_cache(PRESETS["tiny-mistral4"]).pool) is paged_kv.LatentPool
    with pytest.raises(ValueError, match="without index keys"):
        paged_kv.adopt_at(_cache(PRESETS["tiny-qwen2"]).pool,
                          jnp.zeros((6, 2, 2, 16)), jnp.zeros((6, 2, 2, 16)),
                          jnp.arange(2), 1, index=jnp.zeros((6, 2, 128)))
    with pytest.raises(ValueError, match="WITH index keys"):
        paged_kv.adopt_at(_cache().pool, jnp.zeros((2, 2, 2, 16)),
                          jnp.zeros((2, 2, 2, 16)), jnp.arange(2), 1)


def test_index_keys_follow_their_page_through_the_surgery():
    """An adopt (whole pages and a ragged tail), a gather, an eviction and
    re-admission elsewhere, a defrag (``_permute_impl``), a fork
    (``_copy_pages_impl``) and an adopt with a page head: a position's index
    key stays with its K/V row."""
    cache = _cache()
    a, b = cache.alloc_slot(), cache.alloc_slot()
    ka, va, ia = _rows(CFG, 14, 1)
    kb, vb, ib = _rows(CFG, 7, 2)
    cache.adopt(a, ka, va, 14, index=ia)
    cache.adopt(b, kb, vb, 7, index=ib)
    _same(cache.gather_slot(a), ka, va, ia)
    _same(cache.gather_slot(b), kb, vb, ib)
    assert cache.live_tokens == 21
    # evicted, its pages freed, and re-admitted into other pages
    payload = cache.gather_slot(a)
    cache.free_slot(a)
    c = cache.alloc_slot()
    kc, vc, ic = _rows(CFG, 5, 3)
    cache.adopt(c, kc, vc, 5, index=ic)       # takes a's first pages
    a2 = cache.alloc_slot()
    cache.adopt(a2, payload["k"], payload["v"], 14, index=payload["index"])
    _same(cache.gather_slot(a2), ka, va, ia)
    # a defrag moves pages: both leaves by one permutation
    cache.free_slot(c)
    assert cache.defrag() > 0
    cache.check_invariants()
    _same(cache.gather_slot(a2), ka, va, ia)
    _same(cache.gather_slot(b), kb, vb, ib)
    # a fork copies whole pages of both leaves
    src = jnp.asarray(cache._slot_pages[b][:1], jnp.int32)
    dst = jnp.asarray([30], jnp.int32)
    cache.pool = paged_kv._copy_pages_impl(cache.pool, src, dst)
    for leaf in cache.pool:
        np.testing.assert_array_equal(leaf[:, 30], leaf[:, int(src[0])])
    _same(cache.gather_slot(b), kb, vb, ib)
    # an adopt that starts inside a page (``head``): rows 7.. of slot b
    more = _rows(CFG, 6, 4)
    cache.ensure(b, 13)
    dest = cache._flat_indices(b, 13)[7:]
    cache.pool = paged_kv._adopt_impl(
        cache.pool, more[0], more[1], jnp.asarray(dest),
        head=paged_kv.page_head(dest, 4), index=more[2])
    cache.lengths[b] = 13
    _same(cache.gather_slot(b), np.concatenate([kb, more[0]], 1),
          np.concatenate([vb, more[1]], 1), np.concatenate([ib, more[2]], 1))


def test_a_steps_row_write_puts_both_leaves_at_one_place():
    cache = _cache()
    s = cache.alloc_slot()
    k, v, index = _rows(CFG, 6, 5)
    cache.adopt(s, k[:, :5], v[:, :5], 5, index=index[:, :5])
    table, lengths = cache.device_tables()
    pool = cache.pool
    for layer in range(CFG.kv_layers):
        row = lambda a: jnp.stack([a[layer, 5]] * 3)[:, None]  # noqa: E731
        pool = paged_kv.write_rows(pool, layer, table, lengths, row(k),
                                   row(v), index=index[layer, 5][None]
                                   .repeat(3, 0))
    cache.pool = pool
    cache.ensure(s, 6)
    cache.lengths[s] = 6
    _same(cache.gather_slot(s), k, v, index)


# -- what refuses the family, by name ----------------------------------------------

def test_what_reads_a_cache_of_kv_rows_alone_refuses_the_family_by_name(
        params):
    from edgellm_tpu.models.paged_kv import PrefixCacheConfig
    from edgellm_tpu.serve import speculative

    cache = _cache()
    s = cache.alloc_slot()
    for make in (
            lambda: _cache(kv_codec="int8_per_channel"),
            lambda: _cache(prefix_cache=PrefixCacheConfig(enabled=True)),
            lambda: _cache(materialize=False),
            lambda: paged_kv.kv_page_bytes(CFG, 4, "int4_per_channel"),
            lambda: cache.state_dict(),
            lambda: cache.load_state_dict({}),
            lambda: cache.gather_slot_rows(s, 0, 1),
            lambda: cache.adopt_rows(s, None, None, 0, 1),
            lambda: ContinuousBatcher(CFG, None, dataclasses.replace(
                BCFG, checkpoint_dir="/nonexistent")),
            lambda: ContinuousBatcher(CFG, None, BCFG, split_runtime=object(),
                                      placed_params=object()),
            lambda: ContinuousBatcher(CFG, None, BCFG).prefill_hold(0),
            lambda: ContinuousBatcher(CFG, None, BCFG).checkpoint_stream(
                0, "/nonexistent"),
            lambda: ContinuousBatcher(CFG, None, BCFG).restore_stream(
                "/nonexistent"),
            lambda: speculative.draft_from_params(
                CFG, params, speculative.SpecConfig()),
            lambda: generate(CFG, params, _ids(4)[None], 2,
                             recovery=object()),
            lambda: transformer.prefill(CFG, params, jnp.zeros((1, 4),
                                                               jnp.int32), 8,
                                        boundary_fn=lambda h, i: h)):
        with pytest.raises(IndexKeysUnsupported,
                           match="'keye_vl2'.*sparse-attention layers keep "
                                 "an index key"):
            make()


# -- scopes and donation ----------------------------------------------------------------

def test_the_step_carries_the_new_scopes_and_donates_both_leaves(params):
    from edgellm_tpu.obs.names import SCOPE_NAMES

    new = {"attn.sparse", "attn.sparse.index", "attn.sparse.select",
           "attn.sparse.prefill"}
    assert new <= SCOPE_NAMES
    b = ContinuousBatcher(CFG, params, BCFG)
    table, lengths = b.pool.device_tables()
    ints = jnp.zeros((3,), jnp.int32)
    low = batching._batched_hybrid_step_jit.lower(
        CFG, params, b.pool.pool, None, b._expert_tokens, table, lengths,
        ints, jnp.zeros((3, 2), jnp.uint32), ints, jnp.zeros((3,)), None)
    text = low.as_text(debug_info=True)
    for scope in new - {"attn.sparse.prefill"}:
        assert f"{scope}/" in text or f"{scope}\"" in text, scope
    hlo = low.compile().as_text()
    # both leaves and the counter: three aliased buffers
    assert hlo.count("may-alias") + hlo.count("must-alias") >= 3
    pre = jax.jit(lambda p, i: hybrid.prefill_hybrid(CFG, p, i, 64)).lower(
        params, jnp.zeros((1, 20), jnp.int32)).as_text(debug_info=True)
    assert "attn.sparse.prefill" in pre and "attn.sparse.select" in pre
