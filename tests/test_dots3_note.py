"""The ``dots3_note`` family (dots3-note-prev's language model: sparse latent
layers beside WINDOW latent layers of their own sizes, whose rows live in a
ring; both rank factors, a gate lane a head on both kinds; a leading dense
layer, then sigmoid routing in one group plus a shared expert; an untied
head) against its plain reference, on the CPU at toy widths with seeded
float32 weights: LOGITS, not tokens.

The reference is ``benchmark/reference_dots3_note.py``: float32 at
``highest``, whole sequences, both kinds EXPANDED, an explicit ``top_k`` mask
on the full layers and an explicit band on the window layers, no cache, no
pages, no ring, nothing imported from the program. Both sides compute in
float32 here, so they differ by summation order and by the absorption's
reassociation alone.

TOL: logits are compared as ``max |system - reference| <= TOL * max
|reference|``; 2e-5 is ~100 float32 roundings of a five-layer stack, the
readings are under 2e-6. The toy's weights are seeded wide (``make_params``)
so that WHICH rows are attended, either gate, either rank factor and the
window kind's own theta each move the logits by 6% and more
(``test_a_broken_path_reads_far_off``).
"""
import dataclasses
import logging
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

from benchmark import reference_dots3_note as ref  # noqa: E402
from edgellm_tpu.models import (flash_attention, hybrid, mla,  # noqa: E402
                                moe, paged_kv, transformer)
from edgellm_tpu.models.configs import (DOTS3_NOTE_PREV,  # noqa: E402
                                        PRESETS, LatentGeometry, ModelConfig,
                                        tiny_config, tiny_dots3_note_config)
from edgellm_tpu.models.hf_loader import (config_from_hf,  # noqa: E402
                                          params_from_state_dict)
from edgellm_tpu.models.hybrid import (IndexKeysUnsupported,  # noqa: E402
                                       LatentRowsUnsupported,
                                       WindowRingUnsupported)
from edgellm_tpu.serve import batching  # noqa: E402
from edgellm_tpu.serve.batching import (BatchingConfig,  # noqa: E402
                                        ContinuousBatcher)
from edgellm_tpu.serve.decode import generate  # noqa: E402
from test_hybrid import _ids, rel_err  # noqa: E402

TOL = 2e-5
TOPK, BAND = 8, 21
CFG = tiny_dots3_note_config()     # [full (dense), full, window x 3]
BCFG = BatchingConfig(page_size=4, num_pages=121, max_slots=3,
                      pages_per_slot=40)
HF_KINDS = {"sparse_latent_attention": "full_attention",
            "sliding_latent_attention": "sliding_attention"}


def ref_config(cfg: ModelConfig) -> dict:
    """The published keys the reference reads, from a ModelConfig."""
    w = cfg.window_latent
    return {
        "hidden_size": cfg.hidden_size, "num_hidden_layers": cfg.num_layers,
        "layer_types": [HF_KINDS[t] for t in cfg.layer_types],
        "first_k_dense_replace": cfg.num_dense_layers,
        "num_attention_heads": cfg.num_heads,
        "q_lora_rank": cfg.q_lora_rank, "kv_lora_rank": cfg.kv_lora_rank,
        "qk_nope_head_dim": cfg.qk_nope_head_dim,
        "qk_rope_head_dim": cfg.qk_rope_head_dim,
        "v_head_dim": cfg.v_head_dim, "rope_theta": cfg.rope_theta,
        "swa_num_attention_heads": w.num_heads,
        "swa_q_lora_rank": w.q_lora_rank, "swa_kv_lora_rank": w.kv_lora_rank,
        "swa_qk_nope_head_dim": w.qk_nope_head_dim,
        "swa_qk_rope_head_dim": w.qk_rope_head_dim,
        "swa_v_head_dim": w.v_head_dim, "swa_rope_theta": w.rope_theta,
        "sliding_window_size": cfg.sliding_window,
        "apply_mla_qkv_lora_rescale": cfg.rank_scales,
        "rms_norm_eps": cfg.norm_eps, "rope_scaling": None,
        "index_n_heads": cfg.index_heads,
        "index_head_dim": cfg.index_head_dim, "index_topk": cfg.index_topk,
        "n_routed_experts": cfg.local_experts,
        "num_experts_per_tok": cfg.experts_per_tok,
        "routed_scaling_factor": cfg.route_scale,
        "share": {"router_experts": cfg.num_experts,
                  "experts_held": cfg.local_experts,
                  "expert_offset": cfg.expert_offset}}


def make_params(cfg, seed=0):
    """Seeded weights, every matrix at std 0.06 instead of 0.02 and norm
    scales off one, the router and its selection bias at std 0.2, the index
    key's LayerNorm bias off zero, ``wkv_b`` three times wider again (WHICH
    rows a query attends moves the logits) and the gates' ``wg`` at std 0.6
    (a gate of 0.5 everywhere would let a path that dropped it pass with
    half the residual)."""
    params = transformer.init_params(cfg, jax.random.key(seed))
    keys = iter(jax.random.split(jax.random.key(seed + 1), 256))

    def shake(path, a):
        name = path[-1].key
        if name.endswith("_scale") or name in ("q_norm", "kv_norm"):
            return a + 0.1 * jax.random.normal(next(keys), a.shape)
        if name == "index_norm_bias":
            return 0.1 * jax.random.normal(next(keys), a.shape)
        if name in ("router_bias", "router"):
            return a * 10.0
        return a * {"wkv_b": 9.0, "wg": 30.0}.get(name, 3.0)

    return jax.tree_util.tree_map_with_path(shake, params)


@pytest.fixture(scope="module")
def params():
    return make_params(CFG)


def _pad(ids, multiple=ref.QUERY_BLOCK):
    ids = np.asarray(ids)
    if len(ids) <= multiple:
        return ids
    return np.concatenate([ids, np.zeros(-len(ids) % multiple, ids.dtype)])


def ref_logits(cfg, params, ids, **kw):
    n = len(ids)
    return np.asarray(ref.logits(ref.model_key(ref_config(cfg)), params,
                                 jnp.asarray(_pad(ids)), **kw))[:n]


def _forward(cfg, params, ids):
    with jax.default_matmul_precision("highest"):
        return jax.jit(lambda p, i: transformer.forward(cfg, p, i)[0])(
            params, jnp.asarray(ids)[None])[0]


# -- the configuration --------------------------------------------------------

def test_the_preset_holds_the_published_numbers():
    c = PRESETS["dots3-note-prev"]
    assert c is DOTS3_NOTE_PREV and c.family == "dots3_note"
    assert (c.num_layers, c.hidden_size, c.vocab_size) == (46, 5120, 152064)
    assert c.layer_types[:6] == ("sparse_latent_attention",) * 2 + (
        "sliding_latent_attention",) * 3 + ("sparse_latent_attention",)
    assert (c.latent_layers, c.window_layers, c.window_latent_layers,
            c.kv_layers, c.sparse_layers) == (13, 33, 33, 13, 13)
    full, win = (c.latent_geometry("sparse_latent_attention"),
                 c.latent_geometry("sliding_latent_attention"))
    assert full == LatentGeometry(128, 1024, 512, 128, 64, 128, 8e7)
    assert win == LatentGeometry(64, 1024, 1024, 192, 64, 128, 5e4)
    assert (full.head_dim, win.head_dim) == (192, 256)
    assert (c.kv_row_lanes, c.window_row_lanes, c.index_row_lanes) == (
        640, 1152, 128)
    assert c.sliding_window == 513 and c.window_pages(16) == 33
    assert c.rank_scales and c.head_gate and c.rope_scaling is None
    assert c.rank_scale(1024) == pytest.approx(5 ** 0.5)
    assert c.rank_scale(512) == pytest.approx(10 ** 0.5)
    assert (c.num_experts, c.experts_per_tok, c.expert_width, c.shared_width,
            c.num_dense_layers, c.intermediate_size, c.score_func,
            c.route_scale, c.route_groups) == (
        256, 8, 1536, 1536, 1, 13824, "sigmoid", 1.0, 1)
    assert (c.index_heads, c.index_head_dim, c.index_topk) == (64, 128, 2048)
    assert tiny_config("dots3_note") == PRESETS["tiny-dots3-note"] == CFG
    assert CFG.kv_row_lanes != CFG.window_row_lanes


@pytest.mark.parametrize("bad", [
    dict(window_latent=None),
    dict(layer_types=("sparse_latent_attention", "latent_attention",
                      "sliding_latent_attention", "sliding_latent_attention",
                      "sliding_latent_attention")),
    dict(sliding_window=0),
    dict(window_latent=LatentGeometry(2, 20, 24, 24, 7, 16, 500.0)),
])
def test_a_config_the_family_cannot_be_is_refused(bad):
    with pytest.raises(ValueError):
        dataclasses.replace(CFG, **bad)


def test_the_new_fields_belong_to_the_walked_families():
    with pytest.raises(ValueError, match="head-gate"):
        dataclasses.replace(PRESETS["tiny-qwen2"], head_gate=True)
    with pytest.raises(ValueError, match="window_latent"):
        dataclasses.replace(PRESETS["tiny-mistral4"],
                            window_latent=CFG.window_latent)


PUBLISHED = {
    "model_type": "dots3_note", "apply_mla_qkv_lora_rescale": True,
    "attention_bias": False, "attention_gate_type": "headwise",
    "first_k_dense_replace": 1, "hidden_act": "silu", "hidden_size": 5120,
    "index_head_dim": 128, "index_n_heads": 64, "index_topk": 2048,
    "intermediate_size": 13824, "kv_lora_rank": 512,
    "layer_types": ["full_attention"] * 2 + (
        ["sliding_attention"] * 3 + ["full_attention"]) * 11,
    "max_position_embeddings": 524288, "moe_intermediate_size": 1536,
    "moe_layer_freq": 1, "n_routed_experts": 256, "n_shared_experts": 1,
    "norm_topk_prob": True, "num_attention_heads": 128,
    "num_experts_per_tok": 8, "num_hidden_layers": 46,
    "num_key_value_heads": 128, "q_lora_rank": 1024, "qk_nope_head_dim": 128,
    "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 80000000, "routed_scaling_factor": 1,
    "scoring_func": "sigmoid", "sliding_window_size": 513,
    "swa_attention_gate_type": "headwise", "swa_kv_lora_rank": 1024,
    "swa_num_attention_heads": 64, "swa_num_key_value_heads": 64,
    "swa_q_lora_rank": 1024, "swa_qk_nope_head_dim": 192,
    "swa_qk_rope_head_dim": 64, "swa_rope_theta": 50000,
    "swa_v_head_dim": 128, "tie_word_embeddings": False,
    "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 152064,
}


def test_hf_loader_maps_the_published_config():
    assert config_from_hf(types.SimpleNamespace(**PUBLISHED)) == \
        DOTS3_NOTE_PREV
    off = config_from_hf(types.SimpleNamespace(
        **{**PUBLISHED, "apply_mla_qkv_lora_rescale": False}))
    assert not off.rank_scales and off.q_rank_scale == 1.0


@pytest.mark.parametrize("over, match", [
    ({"quantization_config": {"quant_method": "fp8"}}, "quantization_config"),
    ({"rope_scaling": {"type": "yarn", "factor": 4}}, "rope_scaling"),
    ({"attention_gate_type": "elementwise"}, "attention_gate_type"),
    ({"swa_attention_gate_type": "none"}, "swa_attention_gate_type"),
    ({"attention_bias": True}, "attention_bias"),
    ({"n_shared_experts": 2}, "n_shared_experts"),
    ({"scoring_func": "softmax"}, "scoring_func"),
    ({"topk_method": "greedy"}, "topk_method"),
    ({"moe_layer_freq": 2}, "moe_layer_freq"),
    ({"norm_topk_prob": False}, "norm_topk_prob"),
    ({"layer_types": ["full_attention"] * 45 + ["linear_attention"]},
     "layer_types"),
    ({"tie_word_embeddings": True}, "tie_word_embeddings"),
])
def test_hf_loader_refuses_a_dots3_it_does_not_know(over, match):
    with pytest.raises(ValueError, match=match):
        config_from_hf(types.SimpleNamespace(**{**PUBLISHED, **over}))


def _state_dict(cfg, params):
    """A state dict under the assumed names from the per-kind tree."""
    sd = {"model.embed_tokens.weight": params["embed"],
          "model.norm.weight": params["final_norm_scale"],
          "lm_head.weight": params["lm_head"].T,
          "vision_tower.blocks.0.weight": np.zeros((2, 2), np.float32)}
    names = {"ln1_scale": ("input_layernorm.weight", False),
             "wq_a": ("self_attn.q_a_proj.weight", True),
             "q_norm": ("self_attn.q_a_layernorm.weight", False),
             "wq_b": ("self_attn.q_b_proj.weight", True),
             "wkv_a": ("self_attn.kv_a_proj_with_mqa.weight", True),
             "kv_norm": ("self_attn.kv_a_layernorm.weight", False),
             "wkv_b": ("self_attn.kv_b_proj.weight", True),
             "wo": ("self_attn.o_proj.weight", True),
             "wg": ("self_attn.gate_proj.weight", True),
             "wq_index": ("self_attn.indexer.wq_b.weight", True),
             "wk_index": ("self_attn.indexer.wk.weight", True),
             "index_norm_scale": ("self_attn.indexer.k_norm.weight", False),
             "index_norm_bias": ("self_attn.indexer.k_norm.bias", False),
             "w_index": ("self_attn.indexer.weights_proj.weight", True)}
    seen = {"sparse_latent": 0, "window_latent": 0}
    for i, kind in enumerate(cfg.layer_types):
        at = ("sparse_latent" if kind == "sparse_latent_attention"
              else "window_latent")
        for leaf, a in params[at].items():
            name, t = names[leaf]
            row = np.asarray(a[seen[at]])
            sd[f"model.layers.{i}.{name}"] = row.T if t else row
        seen[at] += 1
        mp, ff = params["moe"][i], f"model.layers.{i}.mlp."
        sd[f"model.layers.{i}.post_attention_layernorm.weight"] = \
            mp["ln2_scale"]
        proj = (("w_gate", "gate_proj"), ("w_up", "up_proj"),
                ("w_down", "down_proj"))
        if "router" not in mp:
            for k, n in proj:
                sd[f"{ff}{n}.weight"] = np.asarray(mp[k]).T
            continue
        sd[ff + "gate.weight"] = np.asarray(mp["router"]).T
        sd[ff + "gate.e_score_correction_bias"] = mp["router_bias"]
        for k, n in proj:
            for e in range(cfg.num_experts):
                sd[f"{ff}experts.{e}.{n}.weight"] = np.asarray(mp[k][e]).T
            sd[f"{ff}shared_experts.{n}.weight"] = np.asarray(
                mp["shared_" + k[2:]]).T
    return sd


def test_hf_loader_maps_a_state_dict_to_the_per_kind_tree(params, caplog):
    with caplog.at_level(logging.INFO):
        got = params_from_state_dict(CFG, _state_dict(CFG, params))
    assert "vision_tower" in caplog.text and "not loaded" in caplog.text
    flat = jax.tree_util.tree_leaves_with_path(params)
    assert jax.tree_util.tree_structure(got) == \
        jax.tree_util.tree_structure(params)
    for (path, a), b in zip(flat, jax.tree_util.tree_leaves(got)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=str(path))


# -- the forward ---------------------------------------------------------------

@pytest.mark.parametrize("length", [TOPK, BAND + 1, 5 * BAND])
def test_forward_matches_the_reference(params, length):
    """Around ``index_topk`` and the band, and far past both (105 positions:
    the selection leaves out 97 rows and the band 84)."""
    ids = _ids(length, length)
    assert rel_err(_forward(CFG, params, ids),
                   ref_logits(CFG, params, ids)) < TOL


@pytest.mark.parametrize("broken", ref.BROKEN)
def test_a_broken_path_reads_far_off(params, broken):
    """Each wrong path of the reference (a window layer that attends every
    row, either gate dropped, the rescale dropped, a window layer rotated by
    the full layers' theta, the newest ``topk`` in place of the chosen) lies
    thousands of tolerances from the program, which is the sound
    reference's."""
    ids = _ids(100, 7)
    got = _forward(CFG, params, ids)
    assert rel_err(got, ref_logits(CFG, params, ids,
                                   broken=(broken,))) > 2e3 * TOL


def test_a_layer_built_with_the_other_kinds_sizes_fails(params):
    """The two geometries are two: a window layer's leaves do not fit the
    full kind's sizes (nor the other way round), and a stack whose window
    kind were given the full kind's sizes is another model."""
    x = jax.random.normal(jax.random.key(0), (1, 30, CFG.hidden_size))
    full, win = (CFG.latent_geometry("sparse_latent_attention"),
                 CFG.latent_geometry("sliding_latent_attention"))
    assert full != win
    lpw = hybrid._row(params["window_latent"], 0)
    lpf = hybrid._row(params["sparse_latent"], 0)
    c_q = mla.query_latent(CFG, lpw, x)
    scale = jnp.ones((1, 30), jnp.float32)
    good = mla.head_queries(win, lpw, c_q, scale)
    assert good.shape == (1, 30, win.num_heads, win.head_dim)
    with pytest.raises((TypeError, ValueError)):
        mla.head_queries(full, lpw, c_q, scale)
    with pytest.raises((TypeError, ValueError)):
        mla.expand(win, lpf, jnp.zeros((1, 30, win.kv_row_lanes)))
    rows = mla.latent_row(CFG, win, lpw, x, lambda t: t)
    assert rows.shape[-1] == CFG.window_row_lanes == win.kv_row_lanes
    same = dataclasses.replace(CFG, window_latent=full)
    assert same.window_row_lanes == CFG.kv_row_lanes
    with pytest.raises((TypeError, ValueError)):
        _forward(same, params, _ids(30, 3))


def test_the_gate_is_a_head_in_the_absorbed_and_the_expanded_form_alike(
        params):
    """``mla.unabsorb(gate=)`` (a head's 16 value lanes times its gate) and
    ``paged_kv.gated`` with a gate of H lanes (broadcast over the head's
    lanes of the flat context) are one gate, the reference's
    ``sigmoid(x W_g)_h``; neither is the element-wise gate of (D, H x hd)."""
    geo = CFG.latent_geometry("sliding_latent_attention")
    lp = hybrid._row(params["window_latent"], 1)
    x = jax.random.normal(jax.random.key(1), (5, CFG.hidden_size))
    ctx = jax.random.normal(jax.random.key(2),
                            (5, geo.num_heads, geo.kv_row_lanes))
    gate = mla.head_gate(lp, x)
    assert gate.shape == (5, geo.num_heads)
    np.testing.assert_allclose(
        np.asarray(gate), np.asarray(jax.nn.sigmoid(x @ lp["wg"])))
    with jax.default_matmul_precision("highest"):
        absorbed = mla.unabsorb(geo, lp, ctx, gate)
        wv = mla._kvb(geo, lp)[..., geo.qk_nope_head_dim:]
        heads = jnp.einsum("bhc,chv->bhv", ctx[..., :geo.kv_lora_rank], wv)
        flat = heads.reshape(5, -1)
        expanded = paged_kv.gated(lp, x, flat) @ lp["wo"]
        want = (heads * gate[..., None]).reshape(5, -1) @ lp["wo"]
        ungated = mla.unabsorb(geo, lp, ctx)
    np.testing.assert_allclose(np.asarray(absorbed), np.asarray(want),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(expanded), np.asarray(want),
                               rtol=1e-6, atol=1e-6)
    assert rel_err(ungated, np.asarray(want)) > 0.1
    assert mla.head_gate({}, x) is None
    # an element-wise gate (afmoe's) goes through ``gated`` as it did
    wide = {"wg": jax.random.normal(jax.random.key(3),
                                    (CFG.hidden_size, flat.shape[-1]))}
    np.testing.assert_array_equal(
        np.asarray(paged_kv.gated(wide, x, flat)),
        np.asarray(flat * jax.nn.sigmoid(x @ wide["wg"])))


def test_the_two_kinds_rotate_by_a_table_each():
    tables = hybrid._rope_tables(CFG, 50)
    assert set(tables) == {"sparse_latent_attention",
                           "sliding_latent_attention"}
    for kind, table in tables.items():
        geo = CFG.latent_geometry(kind)
        cos, sin = ref.rope_table(
            {"rope": geo.qk_rope_head_dim, "theta": geo.rope_theta}, 50)
        half = geo.qk_rope_head_dim // 2
        np.testing.assert_allclose(np.asarray(table[0][:, :half]),
                                   np.asarray(cos), atol=1e-6)
        np.testing.assert_allclose(np.asarray(table[1][:, half:]),
                                   np.asarray(sin), atol=1e-6)
    assert not np.allclose(*(np.asarray(t[0]) for t in tables.values()))


@pytest.mark.parametrize("plen", [1, TOPK, 41])
def test_contiguous_decode_step_matches_the_reference(params, plen):
    ids = _ids(plen + 12, plen)
    want = ref_logits(CFG, params, ids)
    with jax.default_matmul_precision("highest"):
        logits, cache = transformer.prefill(CFG, params,
                                            jnp.asarray(ids[None, :plen]), 64)
        assert isinstance(cache, hybrid.SparseLatentWindowCache)
        assert rel_err(logits[0], want[:plen]) < TOL
        step = jax.jit(lambda c, t: transformer.decode_step(CFG, params, c,
                                                            t))
        for t in range(plen, plen + 12):
            logits, cache = step(cache, jnp.asarray(ids[None, t]))
            assert rel_err(logits[0], want[t]) < TOL


# -- the served path: both page groups ---------------------------------------

class LogitTap:
    """``ContinuousBatcher`` with its window step executable replaced by one
    that also hands the logits out: the same ``paged_decode_step_hybrid``,
    the same sampler, the batcher's own admission, adoption and tables."""

    def __init__(self, monkeypatch, cfg):
        self.rows = []

        @jax.jit
        def step(params, pool, wpool, cnt, table, wtable, lengths, toks,
                 key_data, steps, temps):
            with jax.default_matmul_precision("highest"):
                logits, pool, _, cnt, win = hybrid.paged_decode_step_hybrid(
                    cfg, params, pool, None, cnt, table, lengths, toks,
                    window=(wpool.rows, wtable))
            return (logits, batching._batched_sample(logits, key_data, steps,
                                                     temps),
                    pool, type(wpool)(win), cnt)

        def tapped(cfg_, params, pool, wpool, cnt, table, wtable, lengths,
                   toks, key_data, steps, temps, compute_dtype):
            logits, *rest = step(params, pool, wpool, cnt, table, wtable,
                                 lengths, toks, key_data, steps, temps)
            self.rows.append((np.array(lengths), np.array(logits)))
            return tuple(rest)

        tapped._cache_size = lambda: 0
        monkeypatch.setattr(batching, "_batched_window_step_jit", tapped)

    def of_slot(self, slot):
        return {int(lengths[slot]): logits[slot]
                for lengths, logits in self.rows if lengths[slot] > 0}


def _worst(tap, slot, cfg, params, prompt, tokens):
    seq = np.concatenate([prompt, tokens])
    want = ref_logits(cfg, params, seq)
    got = tap.of_slot(slot)
    assert len(got) >= len(tokens) - 1
    return max(rel_err(row, want[pos]) for pos, row in got.items()
               if pos < len(seq))


@pytest.mark.parametrize("plen, page_size", [(TOPK - 1, 4), (BAND, 3),
                                             (3 * BAND, 4)])
def test_prefill_then_paged_decode_through_both_page_groups_matches_the_reference(
        monkeypatch, params, plen, page_size):
    """The batcher's own admission (the full layers' latent rows and index
    keys adopted into the slot's pages, the window layers' latent rows into
    its ring) and 40 paged steps, each step's logits against the reference's
    full forward: from under ``index_topk`` and the band to past both, the
    ring (7 or 8 pages) turned more than once."""
    bcfg = BatchingConfig(page_size=page_size, num_pages=161, max_slots=3,
                          pages_per_slot=40)
    prompt = _ids(plen, plen)
    tap = LogitTap(monkeypatch, CFG)
    with jax.default_matmul_precision("highest"):
        b = ContinuousBatcher(CFG, params, bcfg)
        sid = b.submit(prompt, 41, rng_seed=0)
        toks = b.run()[sid]
    b.pool.check_invariants()
    assert isinstance(b.pool.pool, paged_kv.IndexedLatentPool)
    assert isinstance(b.pool.window_pool, paged_kv.LatentPool)
    assert b.pool.pool.rows.shape[-1] == CFG.kv_row_lanes
    assert b.pool.window_pool.rows.shape[-1] == CFG.window_row_lanes
    ring = CFG.window_pages(page_size) * page_size
    assert plen + 41 > ring + page_size        # the ring has turned
    assert len(tap.of_slot(0)) == 40
    assert _worst(tap, 0, CFG, params, prompt, toks) < TOL
    want0 = ref_logits(CFG, params, prompt)[-1]
    assert want0[toks[0]] >= want0.max() - TOL * np.abs(want0).max()
    rep = b.report()
    # this backend is no TPU: every read is a gather
    assert rep["decode_read"] == rep["window_read"] == paged_kv.PAGE_GATHER
    assert rep["window_pages_walked"] == 0
    assert rep["window_rows_capacity"] == 3 * ring
    assert rep["sparse_rows_attended"] == sum(
        min(n, TOPK) for n in range(plen + 1, plen + 41))
    # top-3 over the four expert layers; the dense layer routes nothing
    assert rep["routed_assignments"] == 40 * 3 * 4 == rep["routed_local"]


def test_batcher_tokens_equal_generate_and_survive_an_eviction(params):
    """Evict -> readmit moves ALL THREE leaves (latent rows, index keys, the
    ring's latent rows): the streams go on as if nothing had happened."""
    prompts = [_ids(n, n) for n in (1, 13, 47)]
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


def _cache(**kw):
    return paged_kv.PagedKVCache(CFG, **{
        "num_pages": 41, "page_size": 4, "max_slots": 3,
        "pages_per_slot": 12, **kw})


def _payload(n, seed):
    k = jax.random.split(jax.random.key(seed), 3)
    return (np.asarray(jax.random.normal(
                k[0], (CFG.kv_layers, n, CFG.kv_row_lanes))),
            np.asarray(jax.random.normal(
                k[1], (CFG.kv_layers, n, CFG.index_row_lanes))),
            np.asarray(jax.random.normal(
                k[2], (CFG.window_layers, n, CFG.window_row_lanes))))


def _adopt(cache, slot, rows, index, wrows, n):
    cache.adopt_latent(slot, rows, n, index=index)
    r0 = cache.window_ring_start(n)
    cache.adopt_window(slot, wrows[:, r0:n], None, n)


def _same(cache, slot, rows, index, wrows):
    n = len(rows[0])
    got = {**cache.gather_slot(slot), **cache.gather_window(slot)}
    assert set(got) == {"rows", "index", "wrows", "length"}
    np.testing.assert_array_equal(got["rows"], rows)
    np.testing.assert_array_equal(got["index"], index)
    np.testing.assert_array_equal(got["wrows"],
                                  wrows[:, cache.window_ring_start(n):])


def test_the_three_leaves_follow_their_slot_through_the_surgery():
    """Two page tables, three leaves at two row widths, one allocator: an
    adopt (whole pages and a ragged tail; a ring shorter than the stream), a
    gather, an eviction and re-admission elsewhere, a defrag."""
    cache = _cache()
    assert cache.window_pages == CFG.window_pages(4) == 6
    assert [a.shape[-1] for a in cache.pool] == [128, 128]
    assert cache.window_pool.rows.shape == (3, 3 * 6 + 1, 4, 256)
    a, b = cache.alloc_slot(), cache.alloc_slot()
    pa, pb = _payload(38, 1), _payload(7, 2)     # 38 > the ring's 24 rows
    _adopt(cache, a, *pa, 38)
    _adopt(cache, b, *pb, 7)
    cache.check_invariants()
    _same(cache, a, *pa)
    _same(cache, b, *pb)
    assert cache.window_rows_live == min(38, BAND) + 7
    kept = {**cache.gather_slot(a), **cache.gather_window(a)}
    cache.free_slot(a)
    c = cache.alloc_slot()                       # takes a's slot and pages
    pc = _payload(5, 3)
    _adopt(cache, c, *pc, 5)
    a2 = cache.alloc_slot()
    cache.adopt_latent(a2, kept["rows"], 38, index=kept["index"])
    cache.adopt_window(a2, kept["wrows"], None, 38)
    _same(cache, a2, *pa)
    cache.free_slot(c)
    assert cache.defrag() > 0
    cache.check_invariants()
    _same(cache, a2, *pa)
    _same(cache, b, *pb)
    with pytest.raises(ValueError, match="latent rows alone"):
        cache.adopt_window(b, pb[2], pb[2], 7)
    with pytest.raises(ValueError, match="positions"):
        cache.adopt_window(b, pb[2][:, :3], None, 7)


def test_a_steps_ring_write_lands_at_the_positions_ring_place():
    cache = _cache()
    s = cache.alloc_slot()
    p = _payload(30, 4)
    _adopt(cache, s, *p, 30)
    row = jnp.full((3, 1, CFG.window_row_lanes), 7.0)
    table = cache.device_window_table()
    lengths = jnp.asarray(cache.lengths, jnp.int32)
    pool = paged_kv.write_rows(cache.window_pool, 1, table, lengths, row,
                               None, ring=True)
    at = int(cache._ring_indices(s, 30, 31)[0])
    flat = np.array(pool.rows).reshape(3, -1, CFG.window_row_lanes)
    np.testing.assert_array_equal(flat[1, at], 7.0)
    before = np.asarray(cache.window_pool.rows).reshape(flat.shape)
    flat[1, at] = before[1, at]
    flat[:, 0:4] = before[:, 0:4]               # the idle slots' trash page
    np.testing.assert_array_equal(flat, before)


# -- the TPU's reads, interpreted ---------------------------------------------

WIDE = tiny_dots3_note_config(
    sliding_window=40, index_topk=8,
    window_latent=LatentGeometry(2, 20, 120, 56, 8, 64, 500.0))
_KERNEL = flash_attention.paged_decode_walk


def test_the_ring_walk_equals_the_gather(monkeypatch):
    """What a TPU runs for the window kind's decode (rows of whole lane
    tiles, pages of 16 rows): the ring's page walk under ``window=`` over
    rows that are key and value both, against the page gather it replaces
    there, in interpret mode: a ring not yet turned, turned, turned twice."""
    cfg = WIDE
    # the ring: 3 slots, pages of 16 rows, a band of 40 -> 4 pages a ring
    geo = cfg.window_latent
    ps, slots = 16, 3
    wp = cfg.window_pages(ps)
    pool = paged_kv.LatentPool(jax.random.normal(
        jax.random.key(6), (2, slots * wp + 1, ps, cfg.window_row_lanes),
        jnp.float32))
    table = jnp.asarray(1 + np.arange(slots * wp).reshape(slots, wp),
                        jnp.int32)
    lengths = jnp.asarray([5, 64, 150], jnp.int32)   # not turned .. twice
    q = jax.random.normal(jax.random.key(7),
                          (slots, geo.num_heads, cfg.window_row_lanes))
    with jax.default_matmul_precision("highest"):
        want = paged_kv.latent_ring_attention(
            q, pool, 1, table, lengths, geo.head_dim, cfg.sliding_window)
        monkeypatch.setattr(paged_kv, "_on_tpu", lambda: True)
        monkeypatch.setattr(
            flash_attention, "paged_decode_walk",
            lambda *a, **k: _KERNEL(*a, **{**k, "interpret": True}))
        assert paged_kv.decode_read_path(pool) == paged_kv.PAGE_WALK
        walk = jax.jit(lambda q: paged_kv.latent_ring_attention(
            q, pool, 1, table, lengths, geo.head_dim, cfg.sliding_window))
        assert "paged_decode_walk" in str(jax.make_jaxpr(walk)(q))
        got = walk(q)
    assert rel_err(got, np.asarray(want)) < 2e-6


# -- the shares ------------------------------------------------------------------

def test_the_eight_shares_of_a_layer_add_up_to_the_uncut_layer(params):
    """Eight chips hold two of the sixteen experts each: their routed parts,
    with the shared expert every chip computes alike counted ONCE, are the
    uncut layer's result, which is the reference's."""
    mp = params["moe"][2]
    u = jax.random.normal(jax.random.key(6), (37, CFG.hidden_size))
    routed = ("w_gate", "w_up", "w_down")
    alone = dataclasses.replace(CFG, shared_width=0)
    with jax.default_matmul_precision("highest"):
        whole, counts = moe.moe_layer(CFG, mp, u)
        shared = (jax.nn.silu(u @ mp["shared_gate"])
                  * (u @ mp["shared_up"])) @ mp["shared_down"]
        parts, held = [], []
        for chip in range(8):
            cfg = dataclasses.replace(alone, experts_held=2,
                                      expert_offset=2 * chip)
            mine = {**mp, **{k: mp[k][2 * chip:2 * chip + 2]
                             for k in routed}}
            out, c = moe.moe_layer(cfg, mine, u)
            parts.append(out)
            held.append(np.asarray(c))
        want = ref._moe(dict(ref.model_key(ref_config(CFG))), mp, u, False)
    assert rel_err(sum(parts) + shared, np.asarray(whole)) < 2e-6
    assert rel_err(whole, np.asarray(want)) < 2e-6
    np.testing.assert_array_equal(np.concatenate(held), np.asarray(counts))
    assert int(np.asarray(counts).sum()) == 37 * CFG.experts_per_tok


def test_a_share_of_the_experts_matches_the_reference_given_the_same_share():
    cfg = tiny_dots3_note_config(experts_held=4, expert_offset=8)
    params = make_params(cfg, seed=3)
    assert params["moe"][1]["w_gate"].shape[0] == 4
    assert params["moe"][1]["router"].shape[1] == 16
    ids = _ids(60, 9)
    assert rel_err(_forward(cfg, params, ids),
                   ref_logits(cfg, params, ids)) < TOL


# -- refusals, scopes, donation ------------------------------------------------

@pytest.mark.parametrize("refuse, error, says", [
    (hybrid.refuse_window_ring, WindowRingUnsupported, "ring of the newest"),
    (hybrid.refuse_latent_rows, LatentRowsUnsupported, "5 latent-attention"),
    (hybrid.refuse_index_keys, IndexKeysUnsupported, "index key"),
])
def test_each_of_the_three_refusals_speaks_for_the_family(refuse, error,
                                                          says):
    with pytest.raises(error, match=says) as e:
        refuse(CFG, "the mechanism")
    assert "dots3_note" in str(e.value) and "the mechanism" in str(e.value)


@pytest.mark.parametrize("build, what", [
    (lambda: _cache(prefix_cache=paged_kv.PrefixCacheConfig()),
     "prefix sharing"),
    (lambda: _cache(kv_codec="int8_per_channel"), "quantized KV tier"),
    (lambda: _cache(materialize=False), "bookkeeping-only"),
    (lambda: _cache().state_dict(), "state_dict"),
    (lambda: paged_kv.kv_page_bytes(CFG, 4, "int4_per_channel"),
     "quantized KV tier"),
])
def test_what_reads_a_cache_of_kv_rows_alone_refuses_the_family_by_name(
        build, what):
    """Whichever of the three refusals speaks first, the mechanism and the
    family are named."""
    with pytest.raises((WindowRingUnsupported, LatentRowsUnsupported,
                        IndexKeysUnsupported), match=what) as e:
        build()
    assert "dots3_note" in str(e.value)


def test_the_step_carries_the_new_scopes_and_donates_four_buffers(params):
    from edgellm_tpu.lint import GRAPH_CONTRACTS
    from edgellm_tpu.obs.names import SCOPE_NAMES

    assert "paged.decode_step_window_latent" in GRAPH_CONTRACTS
    new = {"attn.window_latent", "attn.window_latent.write",
           "attn.window_latent.prefill"}
    assert new <= SCOPE_NAMES
    b = ContinuousBatcher(CFG, params, BCFG)
    table, lengths = b.pool.device_tables()
    ints = jnp.zeros((3,), jnp.int32)
    low = batching._batched_window_step_jit.lower(
        CFG, params, b.pool.pool, b.pool.window_pool, b._expert_tokens,
        table, b.pool.device_window_table(), lengths, ints,
        jnp.zeros((3, 2), jnp.uint32), ints, jnp.zeros((3,)), None)
    text = low.as_text(debug_info=True)
    for scope in ("attn.window_latent", "attn.window_latent.write",
                  "attn.sparse_latent", "attn.sparse.index",
                  "attn.sparse.select", "paged_kv.write", "moe.route", "mlp"):
        assert f"{scope}/" in text or f"{scope}\"" in text, scope
    # the ring's write stands under its own scope, never the growing pool's:
    # a reader of the full layers' scopes reads the full layers alone
    assert "attn.window_latent/paged_kv.write" not in text
    assert "attn.window/" not in text and "attn.latent/" not in text
    hlo = low.compile().as_text()
    # both leaves, the ring's leaf and the counter: four aliased buffers
    assert hlo.count("may-alias") + hlo.count("must-alias") >= 4
    pre = jax.jit(lambda p, i: hybrid.prefill_hybrid(CFG, p, i, 64)).lower(
        params, jnp.zeros((1, 30), jnp.int32)).as_text(debug_info=True)
    assert "attn.window_latent.prefill" in pre
    assert "attn.sparse_latent.prefill" in pre
