"""The ``deepseek_v32`` family (DeepSeek-V3.2-Exp: latent attention whose
query attends the ``index_topk`` positions an indexer selects, a latent row
and an index key a position in the two leaves of one page pool; a leading
dense layer, then experts routed by sigmoid scores within the best expert
groups plus a shared one; an untied head) against its plain reference, on the
CPU at toy widths with seeded float32 weights.

The reference is ``benchmark/reference_deepseek_v32.py``: float32 at
``highest``, whole sequences, the EXPANDED form (keys and values rebuilt per
head) under an explicit ``jax.lax.top_k`` mask, no cache, no pages, nothing
imported from the program. Both sides compute in float32 here, so they differ
by summation order and by the absorption's reassociation alone.

TOL: logits are compared as ``max |system - reference| <= TOL * max
|reference|``. 2e-5 is ~100 float32 roundings of a three-layer stack; the
readings are 2e-7 to 2e-6. A top-k is a discrete choice: the toy's weights
are seeded wide (``make_params``) so that no test position has its k-th and
(k+1)-th index score, or two biased router scores, within a rounding of each
other, and so that WHICH rows are attended and WHICH experts are chosen move
the logits by 1e-3 and more (``test_a_named_mistake_fails`` holds each wrong
path to twenty tolerances).
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

from benchmark import reference_deepseek_v32 as ref  # noqa: E402
from edgellm_tpu.models import (flash_attention, hybrid, mla,  # noqa: E402
                                moe, paged_kv, sparse_attn, sparse_mla,
                                transformer)
from edgellm_tpu.models.configs import (DEEPSEEK_V3_2_EXP,  # noqa: E402
                                        PRESETS, ModelConfig, tiny_config,
                                        tiny_deepseek_v32_config)
from edgellm_tpu.models.hf_loader import (config_from_hf,  # noqa: E402
                                          params_from_state_dict)
from edgellm_tpu.models.hybrid import (IndexKeysUnsupported,  # noqa: E402
                                       LatentRowsUnsupported)
from edgellm_tpu.serve import batching  # noqa: E402
from edgellm_tpu.serve.batching import (BatchingConfig,  # noqa: E402
                                        ContinuousBatcher)
from edgellm_tpu.serve.decode import generate  # noqa: E402
from test_hybrid import LogitTap, _ids, rel_err  # noqa: E402

TOL = 2e-5
TOPK = 8
CFG = tiny_deepseek_v32_config()      # a dense layer, two expert layers
BCFG = BatchingConfig(page_size=4, num_pages=121, max_slots=3,
                      pages_per_slot=40)
KIND = "sparse_latent"


def ref_config(cfg: ModelConfig) -> dict:
    """The published keys the reference reads, from a ModelConfig."""
    _, factor, orig, fast, slow, _ = cfg.rope_scaling
    return {
        "hidden_size": cfg.hidden_size,
        "num_attention_heads": cfg.num_heads,
        "num_hidden_layers": cfg.num_layers,
        "first_k_dense_replace": cfg.num_dense_layers,
        "qk_nope_head_dim": cfg.qk_nope_head_dim,
        "qk_rope_head_dim": cfg.qk_rope_head_dim,
        "v_head_dim": cfg.v_head_dim, "kv_lora_rank": cfg.kv_lora_rank,
        "rms_norm_eps": cfg.norm_eps, "rope_theta": cfg.rope_theta,
        "rope_scaling": {"type": "yarn", "factor": factor,
                         "original_max_position_embeddings": orig,
                         "beta_fast": fast, "beta_slow": slow, "mscale": 1,
                         "mscale_all_dim": 1},
        "index_n_heads": cfg.index_heads,
        "index_head_dim": cfg.index_head_dim, "index_topk": cfg.index_topk,
        "n_routed_experts": cfg.local_experts,
        "num_experts_per_tok": cfg.experts_per_tok,
        "n_group": cfg.route_groups, "topk_group": cfg.route_groups_kept,
        "routed_scaling_factor": cfg.route_scale,
        "share": {"router_experts": cfg.num_experts,
                  "experts_held": cfg.local_experts,
                  "expert_offset": cfg.expert_offset}}


def make_params(cfg, seed=0):
    """Seeded weights, every matrix at std 0.06 instead of 0.02 and norm
    scales off one (at width 48 that makes attention and the experts each a
    visible part of the logits), the router at std 0.2, the index key's
    LayerNorm bias off zero (std 0.1: a path that drops it would pass at
    zero), the selection bias at std 0.2 (so that ``p + b`` and ``p`` choose
    differently), and ``wkv_b`` three times wider again, so that WHICH rows a
    query attends moves the logits."""
    params = transformer.init_params(cfg, jax.random.key(seed))
    keys = iter(jax.random.split(jax.random.key(seed + 1), 256))

    def shake(path, a):
        name = path[-1].key
        if name.endswith("_scale") or name in ("q_norm", "kv_norm"):
            return a + 0.1 * jax.random.normal(next(keys), a.shape)
        if name == "index_norm_bias":
            return 0.1 * jax.random.normal(next(keys), a.shape)
        if name == "router_bias":
            return a * 10.0
        if name == "router":
            return a * 10.0
        return a * (9.0 if name == "wkv_b" else 3.0)

    return jax.tree_util.tree_map_with_path(shake, params)


@pytest.fixture(scope="module")
def params():
    return make_params(CFG)


def ref_logits(cfg, params, ids, **broken):
    return np.asarray(ref.logits(ref.model_key(ref_config(cfg)), params,
                                 jnp.asarray(ids), **broken))


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
    c = PRESETS["deepseek-v3.2-exp"]
    assert c is DEEPSEEK_V3_2_EXP and c.family == "deepseek_v32"
    assert (c.num_layers, c.hidden_size, c.num_heads, c.head_dim,
            c.vocab_size, c.intermediate_size) == (61, 7168, 128, 192,
                                                   129280, 18432)
    assert set(c.layer_types) == {"sparse_latent_attention"}
    # the one kind is BOTH: a latent row and an index key a position
    assert (c.latent_layers, c.sparse_layers, c.kv_layers) == (61, 61, 61)
    assert (c.q_lora_rank, c.kv_lora_rank, c.qk_nope_head_dim,
            c.qk_rope_head_dim, c.v_head_dim) == (1536, 512, 128, 64, 128)
    assert (c.index_heads, c.index_head_dim, c.index_topk,
            c.index_rope_lanes) == (64, 128, 2048, 64)
    assert (c.num_experts, c.experts_per_tok, c.expert_width,
            c.shared_width, c.num_dense_layers, c.expert_layers) == (
        256, 8, 2048, 2048, 3, 58)
    assert (c.score_func, c.route_scale, c.route_groups,
            c.route_groups_kept, c.route_norm_eps) == ("sigmoid", 2.5, 8, 4,
                                                       1e-20)
    assert c.rope_scaling == ("yarn", 40.0, 4096, 32.0, 1.0, 1.0)
    assert abs(c.softmax_mscale - 1.3688879) < 1e-6
    assert not c.query_scale_beta and not c.rank_scales
    # a position's rows: [c 512 | k_rope 64] stored 640 wide, the index key a
    # whole lane tile
    assert (c.kv_row_lanes, c.index_row_lanes) == (640, 128)
    assert PRESETS["tiny-deepseek-v32"] == tiny_config("deepseek_v32") == CFG
    # keye's indexer rotates every lane by a table of its own
    assert PRESETS["keye-vl-2.0-30b-a3b"].index_rope_lanes == 64
    assert PRESETS["tiny-keye-vl2"].index_rope_lanes == 8
    assert PRESETS["trinity-mini"].route_groups == 1


@pytest.mark.parametrize("bad", [
    dict(index_topk=0), dict(index_head_dim=8),     # no wider than the rope
    dict(route_groups=3), dict(route_groups_kept=5),
    dict(route_groups=16),                          # groups of one
    dict(route_groups=8, route_groups_kept=1),      # 2 kept outputs < top-3
    dict(layer_types=("latent_attention",) * 3),
    dict(kv_lora_rank=0),
    dict(family="mistral4", layer_types=("latent_attention",) * 3)])
def test_a_config_the_family_cannot_be_is_refused(bad):
    with pytest.raises(ValueError):
        dataclasses.replace(CFG, **bad)


def test_group_fields_belong_to_the_walked_families():
    with pytest.raises(ValueError, match="routing"):
        dataclasses.replace(PRESETS["tiny-qwen2"], route_groups=2)
    with pytest.raises(ValueError, match="route_groups"):
        dataclasses.replace(PRESETS["tiny-mistral4"], route_groups=2,
                            route_groups_kept=2)    # softmax over the chosen


# -- hf_loader ------------------------------------------------------------------

PUBLISHED = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 3,
    "hidden_act": "silu", "hidden_size": 7168, "index_head_dim": 128,
    "index_n_heads": 64, "index_topk": 2048, "intermediate_size": 18432,
    "kv_lora_rank": 512, "max_position_embeddings": 163840,
    "model_type": "deepseek_v32", "moe_intermediate_size": 2048,
    "moe_layer_freq": 1, "n_group": 8, "n_routed_experts": 256,
    "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 61, "num_key_value_heads": 128,
    "num_nextn_predict_layers": 1, "q_lora_rank": 1536,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "rope_theta": 10000, "routed_scaling_factor": 2.5,
    "scoring_func": "sigmoid", "tie_word_embeddings": False,
    "topk_group": 4, "topk_method": "noaux_tc", "v_head_dim": 128,
    "vocab_size": 129280}


def test_hf_loader_maps_the_published_config(caplog):
    """The published file read as attributes gives the preset; the
    multi-token-prediction key is accepted and said to be not built."""
    with caplog.at_level("INFO"):
        assert config_from_hf(
            types.SimpleNamespace(**PUBLISHED)) == DEEPSEEK_V3_2_EXP
    assert "multi-token-prediction module is not built" in caplog.text


@pytest.mark.parametrize("over, match", [
    (dict(quantization_config={"quant_method": "fp8"}),
     "quantization_config"),
    (dict(rope_scaling={**PUBLISHED["rope_scaling"], "type": "linear"}),
     "rope_scaling must be yarn"),
    (dict(rope_scaling=None), "rope_scaling must be yarn"),
    (dict(rope_scaling={**PUBLISHED["rope_scaling"], "mscale": 0.707}),
     "mscale=0.707 != mscale_all_dim=1"),
    (dict(attention_bias=True), "attention_bias=True"),
    (dict(n_shared_experts=2), "n_shared_experts=2"),
    (dict(scoring_func="softmax"), "scoring_func='softmax'"),
    (dict(topk_method="greedy"), "topk_method='greedy'"),
    (dict(moe_layer_freq=2), "moe_layer_freq=2"),
    (dict(norm_topk_prob=False), "norm_topk_prob=False"),
    (dict(tie_word_embeddings=True), "tie_word_embeddings=True"),
])
def test_hf_loader_refuses_a_deepseek_it_does_not_know(over, match):
    with pytest.raises(ValueError, match=match):
        config_from_hf(types.SimpleNamespace(**{**PUBLISHED, **over}))


@pytest.mark.parametrize("family, extra", [
    ("mistral4", {"rope_parameters": {"rope_type": "yarn"}}), ("afmoe", {})])
@pytest.mark.parametrize("key", ["n_group", "topk_group"])
def test_the_other_families_still_refuse_expert_groups(family, extra, key):
    """mistral4 and afmoe hold ``n_group`` / ``topk_group`` in their configs
    and map no group-limited routing: refused by name, as before."""
    with pytest.raises(ValueError, match=f"{family} with {key}=2"):
        config_from_hf(types.SimpleNamespace(model_type=family, **extra,
                                             **{key: 2}))


def _state_dict(cfg, params):
    """A state_dict under the names ``hf_loader`` assumes (torch's (out, in)
    orientation), from the per-kind tree."""
    sd = {"model.embed_tokens.weight": params["embed"],
          "model.norm.weight": params["final_norm_scale"],
          "lm_head.weight": params["lm_head"].T}
    at = "self_attn."
    names = {"ln1_scale": ("input_layernorm.weight", False),
             "wq_a": (at + "q_a_proj.weight", True),
             "q_norm": (at + "q_a_layernorm.weight", False),
             "wq_b": (at + "q_b_proj.weight", True),
             "wkv_a": (at + "kv_a_proj_with_mqa.weight", True),
             "kv_norm": (at + "kv_a_layernorm.weight", False),
             "wkv_b": (at + "kv_b_proj.weight", True),
             "wo": (at + "o_proj.weight", True),
             "wq_index": (at + "indexer.wq_b.weight", True),
             "wk_index": (at + "indexer.wk.weight", True),
             "index_norm_scale": (at + "indexer.k_norm.weight", False),
             "index_norm_bias": (at + "indexer.k_norm.bias", False),
             "w_index": (at + "indexer.weights_proj.weight", True)}
    swiglu = (("gate", "gate_proj"), ("up", "up_proj"), ("down", "down_proj"))
    for i in range(cfg.num_layers):
        pre = f"model.layers.{i}."
        for leaf, (name, turned) in names.items():
            a = params[KIND][leaf][i]
            sd[pre + name] = a.T if turned else a
        mp = params["moe"][i]
        sd[pre + "post_attention_layernorm.weight"] = mp["ln2_scale"]
        if i < cfg.num_dense_layers:
            for leaf, name in swiglu:
                sd[f"{pre}mlp.{name}.weight"] = mp["w_" + leaf].T
            continue
        sd[pre + "mlp.gate.weight"] = mp["router"].T
        sd[pre + "mlp.gate.e_score_correction_bias"] = mp["router_bias"]
        for leaf, name in swiglu:
            sd[f"{pre}mlp.shared_experts.{name}.weight"] = \
                mp["shared_" + leaf].T
            for e in range(cfg.num_experts):
                sd[f"{pre}mlp.experts.{e}.{name}.weight"] = \
                    mp["w_" + leaf][e].T
    # the multi-token-prediction module's layer: present, and not read
    sd[f"model.layers.{cfg.num_layers}.eh_proj.weight"] = np.zeros((2, 2))
    return {k: np.asarray(v) for k, v in sd.items()}


def test_hf_loader_maps_a_state_dict_to_the_per_kind_tree(params):
    """Every tensor name listed under the configuration file's ``assumed``
    lands in its leaf; the extra layer of the draft module is left alone."""
    sd = _state_dict(CFG, params)
    got = params_from_state_dict(CFG, sd)
    assert sorted(got) == sorted(params)
    assert sorted(got[KIND]) == sorted(params[KIND])
    assert [sorted(m) for m in got["moe"]] == [sorted(m)
                                               for m in params["moe"]]
    jax.tree_util.tree_map(np.testing.assert_array_equal, got, params)
    assert got["moe"][1]["router_bias"].dtype == jnp.float32
    with pytest.raises(ValueError, match="holds every expert"):
        params_from_state_dict(dataclasses.replace(CFG, experts_held=4), sd)


# -- the rotations --------------------------------------------------------------

def test_the_tables_and_the_two_rotations_are_the_references():
    """The YaRN table the program rotates by is the reference's angles; the
    heads' rope lanes rotate in interleaved pairs (stored de-interleaved),
    the indexer's FIRST rope lanes in half-split pairs, the rest left."""
    for cfg in (CFG, DEEPSEEK_V3_2_EXP):
        k = dict(ref.model_key(ref_config(dataclasses.replace(
            cfg, num_layers=2, num_dense_layers=1,
            layer_types=("sparse_latent_attention",) * 2))))
        s = 50
        cos, sin = ref.rope_table(k, s)
        pcos, psin = transformer.precompute_rope(cfg, s)
        assert pcos.shape == (s, cfg.qk_rope_head_dim)
        half = cfg.qk_rope_head_dim // 2
        np.testing.assert_allclose(pcos[:, :half], cos, rtol=0, atol=5e-6)
        np.testing.assert_allclose(psin[:, half:], sin, rtol=0, atol=5e-6)
    s, hi, di, rot = 20, CFG.index_heads, CFG.index_head_dim, 8
    x = jax.random.normal(jax.random.key(0), (s, hi, di))
    table = transformer.precompute_rope(CFG, s)
    want = ref._rotate_first_half_split(
        x, *ref.rope_table(dict(ref.model_key(ref_config(CFG))), s))
    got = transformer.apply_rotary(x[None], *table, CFG.index_rope_lanes)[0]
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-5)
    np.testing.assert_array_equal(got[..., rot:], x[..., rot:])
    # one row a sequence: the decode's rotation of (B, heads, di)
    rows = sparse_mla.index_rotation_rows(CFG, table[0][7:8], table[1][7:8])
    np.testing.assert_allclose(rows(x[7:8]), want[7:8], rtol=0, atol=2e-5)


# -- forward, prefill, decode ----------------------------------------------------

@pytest.mark.parametrize("length", [TOPK - 1, TOPK, TOPK + 1, 5 * TOPK])
def test_forward_matches_the_reference(params, length):
    ids = _ids(length, length)
    assert rel_err(_forward(CFG, params, ids), ref_logits(CFG, params,
                                                          ids)) < TOL


def test_forward_spans_prefill_blocks_and_chunks_its_feed_forwards(
        monkeypatch, params):
    """A prompt of several bodies and blocks (QBLOCK cut to 8 and QUERY_ROWS
    to 4, so that the toy crosses block and body edges inside and past
    ``topk``, with rows left over) whose feed-forwards go in chunks
    (``hybrid.FFN_ROWS_MAX`` cut so that 150 tokens pass it), against the
    reference, whose own blocks are 128 rows."""
    monkeypatch.setattr(sparse_mla, "QBLOCK", 8)
    monkeypatch.setattr(sparse_mla, "QUERY_ROWS", 4)
    row = CFG.experts_per_tok * CFG.hidden_size * 4
    monkeypatch.setattr(hybrid, "FFN_ROWS_MAX", 100 * row)
    monkeypatch.setattr(hybrid, "FFN_CHUNK_ROWS", 40 * row)
    u = jnp.zeros((150, CFG.hidden_size))
    assert hybrid._ffn_chunk(CFG, u) == 32 and not hybrid._ffn_chunk(
        CFG, u[:100])
    ids = _ids(150, 3)
    want = ref_logits(CFG, params, _pad(ids))[:150]
    assert rel_err(_forward(CFG, params, ids), want) < TOL


def test_no_other_cell_chunks_its_feed_forwards():
    """The widest prefill before this family (keye: 16384 tokens x 8 x 2048
    in bf16, 512 MiB of gathered rows) is under the rule; this family's 8192
    and 16384 are over it and go 2048 tokens at a time."""
    keye = PRESETS["keye-vl-2.0-30b-a3b"]
    wide = jnp.zeros((16384, keye.hidden_size), jnp.bfloat16)
    assert hybrid._ffn_chunk(keye, wide) == 0
    for cfg, tokens in ((PRESETS["trinity-mini"], 8192),
                        (PRESETS["mistral-small-4-119b"], 8192),
                        (PRESETS["longcat-flash-chat"], 1024)):
        assert hybrid._ffn_chunk(cfg, jnp.zeros(
            (tokens, cfg.hidden_size), jnp.bfloat16)) == 0
    for tokens in (8192, 16384):
        assert hybrid._ffn_chunk(DEEPSEEK_V3_2_EXP, jnp.zeros(
            (tokens, 7168), jnp.bfloat16)) == 2048


def test_forward_takes_a_batch_and_refuses_a_hook(params):
    ids = np.stack([_ids(24, 1), _ids(24, 2)])
    with jax.default_matmul_precision("highest"):
        got, _ = transformer.forward(CFG, params, jnp.asarray(ids))
    for row, want in zip(got, ids):
        assert rel_err(row, ref_logits(CFG, params, want)) < TOL
    with pytest.raises(LatentRowsUnsupported, match="boundary hook"):
        transformer.forward(CFG, params, jnp.asarray(ids),
                            boundary_fn=lambda h, i: h)


def test_a_short_context_is_plain_latent_attention(params):
    """At a context of at most ``topk`` a sparse latent layer equals a
    ``latent_attention`` layer on the same weights (mistral4's expanded
    prefill: the comparison also holds the absorbed block form to the
    expanded one); one position more and the two part."""
    lp = {k: v[0] for k, v in params[KIND].items()}
    plain = dataclasses.replace(
        CFG, family="mistral4", layer_types=("latent_attention",) * 3,
        index_heads=0, index_head_dim=0, index_topk=0, num_dense_layers=0,
        score_func="softmax", route_groups=1, route_groups_kept=1,
        route_scale=1.0)
    for n in (TOPK, TOPK + 1):
        x = jax.random.normal(jax.random.key(3), (1, n, CFG.hidden_size))
        rope = transformer.precompute_rope(CFG, n)
        with jax.default_matmul_precision("highest"):
            got, rows, _ = sparse_mla.attention_full(CFG, lp, x, rope)
            want, rows2 = hybrid._attention_latent_full(plain, lp, x, rope)
        np.testing.assert_array_equal(rows, rows2)
        np.testing.assert_allclose(got[:, :TOPK], want[:, :TOPK], rtol=0,
                                   atol=2e-6)
    assert float(jnp.abs(got[:, TOPK] - want[:, TOPK]).max()) > 1e-3


@pytest.mark.parametrize("plen", [1, TOPK - 1, TOPK, 41])
def test_contiguous_decode_step_matches_the_reference(params, plen):
    """The block-masked prefill and the one-query decode give the same
    output position by position under teacher forcing: both against the
    reference's full forward."""
    ids = _ids(plen + 30, plen)
    want = ref_logits(CFG, params, ids)
    with jax.default_matmul_precision("highest"):
        logits, cache = hybrid.prefill_hybrid(CFG, params,
                                              jnp.asarray(ids[None, :plen]),
                                              80)
        assert isinstance(cache, hybrid.SparseLatentCache)
        assert cache.rows.shape == (3, 1, 80, 128)
        assert cache.index.shape == (3, 1, 80, 128)
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
    """The batcher's own admission (the prefill's latent rows AND index keys
    adopted into the slot's pages) and 30 paged steps, each step's logits
    against the reference's full forward over the whole sequence, at
    contexts around ``topk`` and far past it, the page size dividing
    ``topk`` and not."""
    bcfg = BatchingConfig(page_size=page_size, num_pages=161, max_slots=3,
                          pages_per_slot=40)
    prompt = _ids(plen, plen)
    tap, b, toks = _serve(monkeypatch, CFG, params, prompt, 31, bcfg=bcfg,
                          rng_seed=0)
    assert isinstance(b.pool.pool, paged_kv.IndexedLatentPool)
    assert len(tap.of_slot(0)) == 30
    assert _worst(tap, 0, CFG, params, prompt, toks) < TOL
    want0 = ref_logits(CFG, params, prompt)[-1]
    assert want0[toks[0]] >= want0.max() - TOL * np.abs(want0).max()
    rep = b.report()
    assert rep["sparse_read"] == sparse_attn.ROW_GATHER
    assert rep["sparse_prefill"] == sparse_attn.XLA_BLOCKS
    # this backend is no TPU: every read is a gather
    assert rep["index_read"] == rep["decode_read"] == paged_kv.PAGE_GATHER
    assert rep["index_pages_walked"] == rep["index_pages_in_runs"] == 0
    live = sum(range(plen + 1, plen + 31))
    assert rep["sparse_rows_live"] == rep["index_rows_scored"] == live
    assert rep["sparse_rows_attended"] == sum(
        min(n, TOPK) for n in range(plen + 1, plen + 31))
    # top-3 over the two expert layers; the dense layer routes nothing
    assert rep["routed_assignments"] == 30 * 3 * 2 == rep["routed_local"]
    assert len(rep["expert_tokens"]) == 2
    assert rep["latent_rows_live"] == 0 and rep["latent_rows_capacity"] == \
        160 * page_size
    assert rep["kv_row_bytes"] == (128 + 128) * 4


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
    """Evict -> readmit moves BOTH leaves: the stream goes on as if nothing
    had happened, greedy and sampled."""
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

@pytest.mark.parametrize("case", ["random", "tie", "zeros", "short"])
def test_the_rows_a_step_attends_are_the_references_top_k_set(case):
    """``select`` (the row gather's) and ``selection_mask`` (the masked
    walk's and the prefill's) against the reference's ``selected`` +
    ``chosen_mask``: exact ties go to the earlier position, rows of fewer
    than k live positions take them all."""
    k, c = 8, 70
    scores = jax.random.normal(jax.random.key(4), (5, c))
    lengths = jnp.asarray([70, 33, 9, 8, 3])
    if case == "tie":
        scores = jnp.round(scores * 2) / 2
    elif case == "zeros":
        scores = jnp.zeros_like(scores)
    elif case == "short":
        lengths = jnp.asarray([7, 5, 2, 1, 8])
    idx, count = sparse_attn.select(scores, lengths, k)
    live = jnp.arange(c)[None, :] < lengths[:, None]
    mask = np.asarray(sparse_attn.selection_mask(scores, live, k))
    rows = (lengths - 1)[:, None]
    want = np.asarray(ref.chosen_mask(ref.selected({"topk": k}, scores, rows),
                                      rows, c))
    np.testing.assert_array_equal(mask, want)
    for row in range(5):
        assert set(np.asarray(idx[row, :int(count[row])]).tolist()) == set(
            np.flatnonzero(want[row])), row


# -- the walks a TPU takes, interpreted ------------------------------------------------

WIDE = dataclasses.replace(CFG, kv_lora_rank=120, index_head_dim=128,
                           index_heads=2)     # rows of 128 lanes: whole tiles


def _interpreted(*args, kernel=None, **kwargs):
    from jax.experimental.pallas import tpu as pltpu

    return jax.block_until_ready((kernel or _KERNEL)(
        *args, **kwargs, interpret=pltpu.InterpretParams()))


_KERNEL = flash_attention.paged_decode_walk
_INDEX_KERNEL = flash_attention.paged_index_walk


def _a_tpus_reads(monkeypatch):
    import functools

    monkeypatch.setattr(paged_kv, "_on_tpu", lambda: True)
    monkeypatch.setattr(flash_attention, "paged_decode_walk", _interpreted)
    monkeypatch.setattr(flash_attention, "paged_index_walk",
                        functools.partial(_interpreted, kernel=_INDEX_KERNEL))


def test_the_read_is_read_off_the_pool_and_the_span(monkeypatch):
    pool = paged_kv.init_pool(WIDE, 9, 8)
    narrow = paged_kv.init_pool(CFG, 9, 3)
    assert type(pool) is type(narrow) is paged_kv.IndexedLatentPool
    assert sparse_attn.sparse_read_path(WIDE, 8, pool) == \
        sparse_attn.EVERY_ROW
    assert sparse_attn.sparse_read_path(WIDE, 64, pool) == \
        sparse_attn.ROW_GATHER                       # this backend is no TPU
    assert paged_kv.decode_read_path(pool) == paged_kv.index_read_path(
        pool) == paged_kv.PAGE_GATHER
    monkeypatch.setattr(paged_kv, "_on_tpu", lambda: True)
    assert sparse_attn.sparse_read_path(WIDE, 64, pool) == \
        sparse_attn.MASKED_WALK
    assert paged_kv.decode_read_path(pool) == paged_kv.PAGE_WALK
    assert paged_kv.index_read_path(pool) == paged_kv.INDEX_WALK
    # a page of 3 rows is no whole sublane tile: neither leaf walks
    assert sparse_attn.sparse_read_path(CFG, 64, narrow) == \
        sparse_attn.ROW_GATHER
    assert paged_kv.index_read_path(narrow) == paged_kv.PAGE_GATHER
    # the cell: a block of 64 pages (twice a K/V walk's), runs of 4 off the
    # 20 KB latent page; the index walk 128 pages, runs of 8 off its 4 KB
    # page, which is what the pool hands out
    big = paged_kv.init_pool(dataclasses.replace(
        DEEPSEEK_V3_2_EXP, num_layers=2, num_dense_layers=1,
        layer_types=("sparse_latent_attention",) * 2), 3, 16, jnp.bfloat16)
    assert sparse_attn.sparse_read_path(DEEPSEEK_V3_2_EXP, 20480, big) == \
        sparse_attn.MASKED_WALK
    assert paged_kv.walk_geometry(big, 1280) == (64, 4)
    assert paged_kv.index_walk_geometry(big, 1280) == (128, 8)
    assert paged_kv.pool_run_pages(big, 1280) == 8


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


#: the toy's heads widened to half a lane tile (56 + 8 key lanes, 64 value
#: lanes): what the masked kernel takes, as the published 192 and 128 are
WIDE = dataclasses.replace(CFG, explicit_head_dim=64, v_head_dim=64)


def test_the_prefill_attend_is_read_off_the_backend_and_the_lanes(
        monkeypatch):
    path = sparse_attn.sparse_prefill_path
    assert path(WIDE, jnp.float32) == path(
        DEEPSEEK_V3_2_EXP, jnp.bfloat16) == sparse_attn.XLA_BLOCKS
    monkeypatch.setattr(sparse_attn, "_on_tpu", lambda: True)  # (no TPU here)
    # a head's 64 + 64 (192 + 128) key and value lanes, as ``mla.expand``
    # rebuilds them: the cached row's lanes are not what the kernel reads
    assert path(WIDE, jnp.float32) == path(
        DEEPSEEK_V3_2_EXP, jnp.bfloat16) == sparse_attn.MASKED_KERNEL
    # heads of 24 + 16 lanes are no half of a lane tile; float16 and int8
    # are no operands the kernel was built for
    assert path(CFG, jnp.float32) == path(WIDE, jnp.float16) == \
        path(WIDE, jnp.int8) == sparse_attn.XLA_BLOCKS


@pytest.mark.parametrize("heads", [2, 4])
def test_the_prefill_on_the_masked_kernel_equals_the_xla_blocks(
        monkeypatch, heads):
    """``attention_full`` built on the masked kernel (the choice forced as a
    TPU makes it, the kernel interpreted: EXPANDED, a body at a time under
    the blocks' masks, ``heads`` of the four heads' keys and values rebuilt
    at once) against the XLA blocks (ABSORBED, a block at a time), two
    sequences of 150 positions past ``index_topk`` in bodies of 64 rows and
    blocks of 4 with two rows left over: the rows and the index keys a cache
    is filled from bit for bit (nothing of the kernel reaches them), the
    outputs to a float32 running softmax's reordering and the absorption's
    reassociation; then the whole forward on the kernel against the
    reference."""
    cfg = WIDE
    monkeypatch.setattr(sparse_mla, "QBLOCK", 8)
    monkeypatch.setattr(sparse_mla, "QUERY_ROWS", 4)
    monkeypatch.setattr(sparse_mla, "EXPANDED_HEADS", heads)
    params = make_params(cfg)
    lp = hybrid._row(params["sparse_latent"], 1)
    x = jax.random.normal(jax.random.key(5), (2, 150, cfg.hidden_size))
    rope = hybrid._rope_tables(cfg, 150)["sparse_latent_attention"]
    full = jax.jit(lambda x: sparse_mla.attention_full(cfg, lp, x, rope))
    ids = _ids(150, 3)
    with jax.default_matmul_precision("highest"):
        want = full(x)
        _a_tpus_prefill(monkeypatch)
        jax.clear_caches()
        assert sparse_attn.sparse_prefill_path(cfg, x.dtype) == \
            sparse_attn.MASKED_KERNEL
        on_kernel = jax.jit(
            lambda x: sparse_mla.attention_full(cfg, lp, x, rope))
        assert "masked_attention" in str(jax.make_jaxpr(on_kernel)(x))
        got = jax.block_until_ready(on_kernel(x))
        logits = jax.block_until_ready(_forward(cfg, params, ids))
    jax.clear_caches()
    for a, b in zip(got[1:], want[1:]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert rel_err(got[0], want[0]) < 2e-6
    assert rel_err(logits, ref_logits(cfg, params, _pad(ids))[:150]) < TOL


def test_a_head_slice_of_the_latent_projections_is_those_heads_alone():
    """``mla.head_queries`` and ``mla.expand`` of heads (first, count), the
    first traced as a prefill's ``lax.map`` over groups hands it over,
    against the same heads cut from the whole layer's, bit for bit."""
    cfg = WIDE
    lp = hybrid._row(make_params(cfg)["sparse_latent"], 1)
    c_q = jax.random.normal(jax.random.key(1), (2, 9, cfg.q_lora_rank))
    rows = jax.random.normal(jax.random.key(2), (2, 9, cfg.kv_row_lanes))
    scale = jnp.full((2, 9), 0.7, jnp.float32)
    q = mla.head_queries(cfg, lp, c_q, scale)
    k, v = mla.expand(cfg, lp, rows)
    assert q.shape[2] == k.shape[2] == v.shape[2] == cfg.num_heads

    @jax.jit
    def some(first):
        return (mla.head_queries(cfg, lp, c_q, scale, (first, 2)),
                *mla.expand(cfg, lp, rows, (first, 2)))

    for first in (0, 2):
        for part, whole in zip(some(first), (q, k, v)):
            np.testing.assert_array_equal(
                np.asarray(part), np.asarray(whole[:, :, first:first + 2]))


def test_the_step_on_the_walks_equals_the_step_on_the_gathers(monkeypatch):
    """``paged_decode_step_hybrid`` of a sparse latent stack built on the
    index walk and the masked walk of the latent rows (the choices forced as
    a TPU would make them, the kernels interpreted) against the step on the
    page gather and the row gather: logits, both written leaves, the
    counter. Slot 1 is idle; every page no table names holds NaN in BOTH
    leaves under the walks."""
    cfg = WIDE
    p = make_params(cfg, seed=3)
    ps, pps, slots = 8, 6, 3
    lens = np.asarray([37, 0, 20], np.int32)
    rng = np.random.default_rng(0)
    pages = slots * pps + 1
    pool = paged_kv.IndexedLatentPool(*(jnp.asarray(rng.standard_normal(
        a.shape), jnp.float32) * 0.3 for a in paged_kv.init_pool(cfg, pages,
                                                                 ps)))
    table = np.zeros((slots, pps), np.int32)
    order, at = rng.permutation(pages - 1) + 1, 0
    held = np.zeros((pages,), bool)
    held[0] = True
    for i, n in enumerate(lens):
        for j in range(-(-(n + 1) // ps) if n else 0):
            table[i, j] = order[at]
            held[order[at]] = True
            at += 1
    dirty = paged_kv.IndexedLatentPool(*(jnp.where(
        jnp.asarray(held)[None, :, None, None], a, jnp.nan) for a in pool))
    dirty = paged_kv.IndexedLatentPool(*(a.at[:, 0].set(0.0) for a in dirty))
    counts = jnp.zeros((cfg.expert_layers, cfg.local_experts), jnp.int32)
    toks = jnp.asarray([5, 0, 9], jnp.int32)

    def step(which):
        with jax.default_matmul_precision("highest"):
            return hybrid.paged_decode_step_hybrid(
                cfg, p, which, None, counts, jnp.asarray(table),
                jnp.asarray(lens), toks)

    want = step(pool)
    _a_tpus_reads(monkeypatch)
    assert sparse_attn.sparse_read_path(cfg, ps * pps, dirty) == \
        sparse_attn.MASKED_WALK
    got = step(dirty)
    live = [0, 2]
    np.testing.assert_allclose(np.asarray(got[0])[live],
                               np.asarray(want[0])[live], rtol=0,
                               atol=2e-5 * float(jnp.abs(want[0]).max()))
    for a, b in zip(got[1], want[1]):
        a, b = np.asarray(a)[:, held], np.asarray(b)[:, held]
        np.testing.assert_allclose(a[:, 1:], b[:, 1:], rtol=0, atol=2e-5)
    np.testing.assert_array_equal(got[3], want[3])


# -- named mistakes -----------------------------------------------------------------

def _newest(scores, lengths, k):
    idx = lengths[:, None] - 1 - jnp.arange(k)[None, :]
    return jnp.maximum(idx, 0).astype(jnp.int32), jnp.minimum(lengths, k)


def _project_index_from_x(cfg, lp, x, rotate, query=None):
    """The indexer's query made from the layer's input (keye's), through the
    first ``hidden`` rows of a ``W_qI`` made as tall."""
    tall = {**lp, "wq_index": jnp.resize(lp["wq_index"], (
        x.shape[-1], lp["wq_index"].shape[-1]))}
    return _PROJECT_INDEX(cfg, tall, x, rotate)


_PROJECT_INDEX = sparse_attn.project_index

MISTAKES = {
    "the newest topk rows instead of the selected":
        lambda mp: mp.setattr(sparse_attn, "select", _newest),
    "the indexer's query from x, not from c_q":
        lambda mp: mp.setattr(sparse_mla, "project_index",
                              _project_index_from_x),
    "the indexer left unrotated":
        lambda mp: mp.setattr(sparse_mla, "index_rotation_rows",
                              lambda cfg, cos, sin: (lambda t: t)),
    "the indexer's rope lanes in interleaved pairs":
        lambda mp: mp.setattr(
            sparse_mla, "index_rotation_rows",
            (lambda f: lambda cfg, cos, sin: (
                lambda t: f(cfg, cos, sin)(jnp.concatenate(
                    [transformer.deinterleave_pairs(t[..., :8]),
                     t[..., 8:]], -1))))(sparse_mla.index_rotation_rows)),
    "the index key's LayerNorm bias dropped":
        lambda mp: mp.setattr(
            sparse_attn, "_layernorm",
            lambda x, s, b, eps: transformer._layernorm(x, s, 0 * b, eps)),
    "the index key not written by the step":
        lambda mp: mp.setattr(
            sparse_mla, "write_rows",
            lambda pool, *a, index=None, **kw: paged_kv.write_rows(
                pool, *a, index=0 * index, **kw)),
    "ungrouped top-k routing":
        lambda mp: mp.setattr(moe, "_group_limited", lambda cfg, b: b),
    "the selection bias dropped":
        lambda mp: mp.setattr(
            moe, "_group_limited",
            (lambda f: lambda cfg, b: f(cfg, b * 0 + jax.nn.sigmoid(
                jnp.zeros(()))))(moe._group_limited)),
    "the softmax mscale dropped":
        lambda mp: mp.setattr(
            mla, "query_scale",
            lambda cfg, geo, pos: jnp.ones(pos.shape, jnp.float32)),
}


@pytest.mark.parametrize("name", sorted(MISTAKES))
def test_a_named_mistake_fails(monkeypatch, params, name):
    """Each wrong decode path moves some step's logits by more than twenty
    tolerances: the sound path's margin is not slack."""
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


def test_the_references_broken_paths_read_far_off(params):
    """The reference's own two broken paths (what the benchmark's limits
    are held against): the newest ``topk`` rows in place of the chosen, and
    ungrouped top-k routing."""
    ids = _ids(5 * TOPK, 7)
    want = ref_logits(CFG, params, ids)
    for broken in ({"newest": True}, {"ungrouped": True}):
        got = ref_logits(CFG, params, ids, **broken)
        assert rel_err(got, want) > 50 * TOL, broken
    assert rel_err(ref_logits(CFG, params, ids)[:TOPK],
                   ref_logits(CFG, params, ids, newest=True)[:TOPK]) == 0


# -- the expert layer ---------------------------------------------------------------

def _route_by_loops(cfg, mp, u):
    """Group-limited routing, literally: a Python loop a token."""
    logits = np.asarray(u, np.float64) @ np.asarray(mp["router"], np.float64)
    p = 1.0 / (1.0 + np.exp(-logits))
    biased = p + np.asarray(mp["router_bias"], np.float64)
    g, kept, k = cfg.route_groups, cfg.route_groups_kept, cfg.experts_per_tok
    size = p.shape[1] // g
    ids, weights, plain = [], [], []
    for t in range(p.shape[0]):
        rank = [np.sort(biased[t, j * size:(j + 1) * size])[-2:].sum()
                for j in range(g)]
        groups = sorted(range(g), key=lambda j: (-rank[j], j))[:kept]
        allowed = [e for j in groups for e in range(j * size, (j + 1) * size)]
        chosen = sorted(allowed, key=lambda e: (-biased[t, e], e))[:k]
        ids.append(chosen)
        weights.append(cfg.route_scale * p[t, chosen] / p[t, chosen].sum())
        plain.append(sorted(range(p.shape[1]),
                            key=lambda e: (-biased[t, e], e))[:k])
    return np.asarray(ids), np.asarray(weights), np.asarray(plain)


def test_group_limited_routing_is_the_literal_loops(params):
    """``moe.route`` against a loop a token, in the program, in the
    reference, and for a token whose ungrouped top-k differs (the toy's 64
    tokens hold a dozen); the bias moves choices and no weight."""
    mp = params["moe"][1]
    u = jax.random.normal(jax.random.key(8), (64, CFG.hidden_size))
    with jax.default_matmul_precision("highest"):
        idx, w = moe.route(CFG, mp["router"], u, mp["router_bias"])
        ridx, rw = ref.route(dict(ref.model_key(ref_config(CFG))), mp, u,
                             False)
    ids, weights, plain = _route_by_loops(CFG, mp, u)
    np.testing.assert_array_equal(np.asarray(idx), ids)
    np.testing.assert_array_equal(np.asarray(ridx), ids)
    np.testing.assert_allclose(w, weights, rtol=2e-6)
    np.testing.assert_allclose(rw, weights, rtol=2e-6)
    differ = [t for t in range(64) if set(ids[t]) != set(plain[t])]
    assert len(differ) >= 5
    # every chosen expert lies in one of the two kept groups of four
    assert all(len({e // 4 for e in row}) <= 2 for row in ids)
    np.testing.assert_allclose(np.asarray(w).sum(-1), CFG.route_scale,
                               rtol=1e-6)
    # one group: the trace the other sigmoid families have
    flat = dataclasses.replace(CFG, route_groups=1, route_groups_kept=1)
    with jax.default_matmul_precision("highest"):
        fidx, _ = moe.route(flat, mp["router"], u, mp["router_bias"])
    np.testing.assert_array_equal(np.asarray(fidx), plain)
    text = str(jax.make_jaxpr(lambda a: moe.route(
        flat, mp["router"], a, mp["router_bias"]))(u))
    assert text.count("top_k") == 1


def test_the_sixteen_shares_of_a_layer_add_up_to_the_uncut_layer(params):
    """Sixteen chips hold one of the sixteen experts each: their routed
    parts, with the shared expert every chip computes alike counted ONCE,
    are the uncut layer's result, which is the reference's."""
    mp = params["moe"][1]
    u = jax.random.normal(jax.random.key(6), (37, CFG.hidden_size))
    routed = ("w_gate", "w_up", "w_down")
    alone = dataclasses.replace(CFG, shared_width=0)
    with jax.default_matmul_precision("highest"):
        whole, counts = moe.moe_layer(CFG, mp, u)
        shared = (jax.nn.silu(u @ mp["shared_gate"])
                  * (u @ mp["shared_up"])) @ mp["shared_down"]
        parts, held = [], []
        for chip in range(16):
            cfg = dataclasses.replace(alone, experts_held=1,
                                      expert_offset=chip)
            mine = {**mp, **{k: mp[k][chip:chip + 1] for k in routed}}
            out, c = moe.moe_layer(cfg, mine, u)
            parts.append(out)
            held.append(np.asarray(c))
        want = ref._moe(dict(ref.model_key(ref_config(CFG))), mp, u, False)
    assert rel_err(sum(parts) + shared, np.asarray(whole)) < TOL
    assert rel_err(whole, np.asarray(want)) < TOL
    np.testing.assert_array_equal(np.concatenate(held), np.asarray(counts))
    assert int(counts.sum()) == 37 * 3       # every assignment held once


def test_a_share_of_the_experts_matches_the_reference_given_the_same_share():
    cfg = tiny_deepseek_v32_config(experts_held=4, expert_offset=2)
    p = make_params(cfg)
    ids = _ids(5 * TOPK, 9)
    assert p["moe"][1]["w_gate"].shape[0] == 4
    assert p["moe"][1]["router"].shape[1] == 16
    assert "router" not in p["moe"][0] and p["moe"][0]["w_gate"].shape == (
        48, 96)
    assert rel_err(_forward(cfg, p, ids), ref_logits(cfg, p, ids)) < TOL


# -- the cache: both leaves ride the page pool's surgery -------------------------------

def _cache(cfg=CFG, **kw):
    return paged_kv.PagedKVCache(cfg, **{"num_pages": 33, "page_size": 4,
                                         "max_slots": 3, "pages_per_slot": 8,
                                         **kw})


def _rows(cfg, n, seed):
    keys = jax.random.split(jax.random.key(seed), 2)
    return (jax.random.normal(keys[0], (cfg.kv_layers, n, cfg.kv_row_lanes)),
            jax.random.normal(keys[1], (cfg.kv_layers, n,
                                        cfg.index_row_lanes)))


def _same(got, rows, index):
    np.testing.assert_array_equal(got["rows"], rows)
    np.testing.assert_array_equal(got["index"], index)
    assert "k" not in got


def test_the_pool_is_a_latent_leaf_and_an_index_leaf_under_one_table():
    cache = _cache()
    pool = cache.pool
    assert isinstance(pool, paged_kv.IndexedLatentPool)
    assert pool.rows.shape == pool.ik.shape == (3, 33, 4, 128)
    assert (pool.num_pages, pool.page_size) == (33, 4)
    assert paged_kv.pool_tier(pool) == "fp"
    page = 3 * 4 * (128 + 128) * 4
    assert paged_kv.kv_page_bytes(CFG, 4) == page
    assert cache.kv_row_bytes == (128 + 128) * 4
    assert paged_kv.page_leaf_bytes(CFG, 4) == 4 * 128 * 4
    # at the cell's sizes: 120 KiB a page over 5 layers, 2.52 GB; the index
    # keys' 4 KB page is the smaller and sets the runs
    big = dataclasses.replace(DEEPSEEK_V3_2_EXP, num_layers=5,
                              num_dense_layers=1,
                              layer_types=("sparse_latent_attention",) * 5)
    assert paged_kv.kv_page_bytes(big, 16, dtype=jnp.bfloat16) == 120 * 1024
    assert 20481 * 120 * 1024 == 2_516_705_280
    assert paged_kv.page_leaf_bytes(big, 16, dtype=jnp.bfloat16) == 4 * 1024
    # the two older pools are what they were
    assert type(_cache(PRESETS["tiny-mistral4"]).pool) is paged_kv.LatentPool
    assert type(_cache(PRESETS["tiny-keye-vl2"]).pool) is \
        paged_kv.IndexedPagePool
    with pytest.raises(ValueError, match="WITH index keys"):
        cache.adopt_latent(cache.alloc_slot(), jnp.zeros((3, 2, 128)), 2)
    with pytest.raises(ValueError, match="without index keys"):
        lat = _cache(PRESETS["tiny-mistral4"])
        lat.adopt_latent(lat.alloc_slot(), jnp.zeros((3, 2, 128)), 2,
                         index=jnp.zeros((3, 2, 128)))


def test_both_leaves_follow_their_page_through_the_surgery():
    """An adopt (whole pages and a ragged tail), a gather, an eviction and
    re-admission elsewhere, a defrag, a fork: a position's index key stays
    with its latent row."""
    cache = _cache()
    a, b = cache.alloc_slot(), cache.alloc_slot()
    ra, ia = _rows(CFG, 14, 1)
    rb, ib = _rows(CFG, 7, 2)
    cache.adopt_latent(a, ra, 14, index=ia)
    cache.adopt_latent(b, rb, 7, index=ib)
    _same(cache.gather_slot(a), ra, ia)
    _same(cache.gather_slot(b), rb, ib)
    assert cache.live_tokens == 21 == cache.latent_rows_live
    payload = cache.gather_slot(a)
    cache.free_slot(a)
    c = cache.alloc_slot()
    rc, ic = _rows(CFG, 5, 3)
    cache.adopt_latent(c, rc, 5, index=ic)        # takes a's first pages
    a2 = cache.alloc_slot()
    cache.adopt_latent(a2, payload["rows"], 14, index=payload["index"])
    _same(cache.gather_slot(a2), ra, ia)
    cache.free_slot(c)
    assert cache.defrag() > 0
    cache.check_invariants()
    _same(cache.gather_slot(a2), ra, ia)
    _same(cache.gather_slot(b), rb, ib)
    src = jnp.asarray(cache._slot_pages[b][:1], jnp.int32)
    dst = jnp.asarray([30], jnp.int32)
    cache.pool = paged_kv._copy_pages_impl(cache.pool, src, dst)
    for leaf in cache.pool:
        np.testing.assert_array_equal(leaf[:, 30], leaf[:, int(src[0])])
    _same(cache.gather_slot(b), rb, ib)


def test_a_steps_row_write_puts_both_leaves_at_one_place():
    cache = _cache()
    s = cache.alloc_slot()
    rows, index = _rows(CFG, 6, 5)
    cache.adopt_latent(s, rows[:, :5], 5, index=index[:, :5])
    table, lengths = cache.device_tables()
    pool = cache.pool
    for layer in range(CFG.kv_layers):
        pool = paged_kv.write_rows(
            pool, layer, table, lengths,
            jnp.stack([rows[layer, 5]] * 3)[:, None], None,
            index=index[layer, 5][None].repeat(3, 0))
    cache.pool = pool
    cache.ensure(s, 6)
    cache.lengths[s] = 6
    _same(cache.gather_slot(s), rows, index)


# -- what refuses the family, by name ----------------------------------------------

def test_what_reads_a_cache_of_kv_rows_alone_refuses_the_family_by_name(
        params):
    """Everything ``refuse_latent_rows`` and ``refuse_index_keys`` refuse
    stays refused for the family that is both, whichever speaks first."""
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
        with pytest.raises((LatentRowsUnsupported, IndexKeysUnsupported),
                           match="'deepseek_v32'.*(latent-attention layers "
                                 "cache ONE row|sparse-attention layers keep "
                                 "an index key)"):
            make()


# -- scopes and donation ----------------------------------------------------------------

def test_the_step_carries_the_new_scopes_and_donates_both_leaves(params):
    from edgellm_tpu.obs.names import SCOPE_NAMES

    new = {"attn.sparse_latent", "attn.sparse_latent.prefill"}
    shared = {"attn.sparse.index", "attn.sparse.select"}
    assert new | shared <= SCOPE_NAMES
    b = ContinuousBatcher(CFG, params, BCFG)
    table, lengths = b.pool.device_tables()
    ints = jnp.zeros((3,), jnp.int32)
    low = batching._batched_hybrid_step_jit.lower(
        CFG, params, b.pool.pool, None, b._expert_tokens, table, lengths,
        ints, jnp.zeros((3, 2), jnp.uint32), ints, jnp.zeros((3,)), None)
    text = low.as_text(debug_info=True)
    for scope in shared | {"attn.sparse_latent", "moe.route", "moe.shared",
                           "mlp"}:
        assert f"{scope}/" in text or f"{scope}\"" in text, scope
    # a reader tells the three kinds apart: neither older outer scope
    assert "attn.sparse/" not in text and "attn.latent/" not in text
    hlo = low.compile().as_text()
    # both leaves and the counter: three aliased buffers
    assert hlo.count("may-alias") + hlo.count("must-alias") >= 3
    pre = jax.jit(lambda p, i: hybrid.prefill_hybrid(CFG, p, i, 64)).lower(
        params, jnp.zeros((1, 20), jnp.int32)).as_text(debug_info=True)
    assert "attn.sparse_latent.prefill" in pre
    assert "attn.sparse.select" in pre and "attn.latent.expand" not in pre
