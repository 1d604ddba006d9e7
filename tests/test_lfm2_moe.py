"""The ``lfm2_moe`` family (LiquidAI LFM2: gated short convolutions that keep
a window of ``taps - 1`` rows a sequence, 3:1 beside rotated GQA layers with
per-head q/k norms, two leading dense layers, then experts routed by sigmoid
scores with a selection bias and none shared, a tied head) against its plain
reference, on the CPU at toy widths with seeded float32 weights.

The reference is ``benchmark/reference_lfm2_moe.py``: float32 at ``highest``,
whole sequences, no cache, no pages, no window carried, nothing imported from
the program. Both sides compute in float32 here, so they differ by summation
order alone.

TOL: logits are compared as ``max |system - reference| <= TOL * max
|reference|``. 2e-5 is ~100 float32 roundings of a six-layer stack whose sums
run over at most 96 terms; the readings are 2e-7 to 9e-7 (the forward, the
contiguous decode and seventy paged steps through the state store alike). A
sigmoid top-k is a discrete choice: the toy router is seeded wide so that no
test position has its k-th and (k+1)-th of ``p + b`` within a rounding of
each other. Every named mistake below (bfloat16 where float32 is stated
among them) moves the logits by far more at some step of a 40-token answer,
and ``test_a_named_mistake_fails`` holds each to twenty tolerances.
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

from benchmark import reference_lfm2_moe as ref  # noqa: E402
from edgellm_tpu.models import (hybrid, moe, paged_kv,  # noqa: E402
                                shortconv, transformer)
from edgellm_tpu.models.configs import (LFM2_8B_A1B, PRESETS,  # noqa: E402
                                        ModelConfig, tiny_config,
                                        tiny_lfm2_moe_config)
from edgellm_tpu.models.hf_loader import (config_from_hf,  # noqa: E402
                                          params_from_state_dict)
from edgellm_tpu.models.hybrid import RecurrentStateUnsupported  # noqa: E402
from edgellm_tpu.serve import batching  # noqa: E402
from edgellm_tpu.serve.batching import (BatchingConfig,  # noqa: E402
                                        ContinuousBatcher)
from edgellm_tpu.serve.decode import generate  # noqa: E402
from test_hybrid import LogitTap, _ids, rel_err  # noqa: E402

TOL = 2e-5
CFG = tiny_lfm2_moe_config()          # C C A C C C: two dense, four routed
BCFG = BatchingConfig(page_size=4, num_pages=121, max_slots=3,
                      pages_per_slot=40)
KINDS = {"conv": "conv", "attention": "full_attention"}


def ref_config(cfg: ModelConfig) -> dict:
    """The published keys the reference reads, from a ModelConfig."""
    return {
        "hidden_size": cfg.hidden_size,
        "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads,
        "norm_eps": cfg.norm_eps, "rope_theta": cfg.rope_theta,
        "layer_types": [KINDS[t] for t in cfg.layer_types],
        "conv_L_cache": cfg.conv_window, "conv_bias": False,
        "num_dense_layers": cfg.num_dense_layers,
        "num_experts": cfg.num_experts,
        "num_experts_per_tok": cfg.experts_per_tok,
        "norm_topk_prob": True, "use_expert_bias": True,
        "routed_scaling_factor": cfg.route_scale,
        "share": {"experts_held": cfg.local_experts,
                  "expert_offset": cfg.expert_offset}}


def make_params(cfg, seed=0):
    """Seeded weights, every matrix at std 0.06 instead of 0.02 and norm
    scales off one (at width 48 that makes the short convolutions, attention
    and the experts each a visible part of the logits), the router at std 0.2
    (sigmoid scores spread over 0.1 .. 0.9) and the selection bias at std
    0.2: it changes the chosen set at most positions. The taps stay as
    drawn, uniform in +-1/sqrt(taps)."""
    params = transformer.init_params(cfg, jax.random.key(seed))
    keys = iter(jax.random.split(jax.random.key(seed + 1), 256))

    def shake(path, a):
        name = path[-1].key
        if name.endswith("_scale") or name in ("q_norm", "k_norm"):
            return a + 0.1 * jax.random.normal(next(keys), a.shape)
        if name in ("router", "router_bias"):
            return a * 10.0
        if name == "conv_w":
            return a
        return a * 3.0

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


# -- the configuration --------------------------------------------------------

def test_the_preset_holds_the_published_numbers():
    c = PRESETS["lfm2-8b-a1b"]
    assert c is LFM2_8B_A1B and c.is_hybrid and c.recurrent_state
    assert (c.hidden_size, c.num_heads, c.num_kv_heads, c.head_dim) == (
        2048, 32, 8, 64)
    assert (c.num_layers, c.conv_layers, c.kv_layers) == (24, 18, 6)
    assert [i for i, t in enumerate(c.layer_types) if t == "attention"] == [
        2, 6, 10, 14, 18, 21]
    assert (c.conv_window, c.mamba_layers, c.window_layers) == (3, 0, 0)
    assert (c.num_dense_layers, c.expert_layers, c.intermediate_size) == (
        2, 22, 7168)
    assert (c.num_experts, c.experts_per_tok, c.expert_width,
            c.shared_width) == (32, 4, 1792, 0)
    assert (c.score_func, c.route_scale, c.route_norm_eps) == (
        "sigmoid", 1.0, 1e-6)
    assert c.position_free == () and c.rope_scaling is None
    assert (c.rope_theta, c.rotary_dim, c.vocab_size, c.norm_eps) == (
        1e6, 64, 65536, 1e-5)
    assert c.tie_word_embeddings and c.kv_row_lanes == 512
    assert hybrid.state_shapes(c, 96) == {"conv": (18, 96, 2, 2048)}
    # the constant is the family's own; the other sigmoid family keeps its
    assert PRESETS["trinity-mini"].route_norm_eps == 1e-20
    assert tiny_config("lfm2_moe") == PRESETS["tiny-lfm2-moe"] == CFG


def test_recurrent_state_is_a_property_of_the_kinds():
    """Asked of ``layer_types``, not of the family's name: the leaves the
    store holds are what the stack's recurrent kinds keep."""
    granite = PRESETS["tiny-granite-hybrid"]
    assert granite.recurrent_state and sorted(
        hybrid.state_shapes(granite, 2)) == ["conv", "ssm"]
    assert CFG.recurrent_state and hybrid.state_shapes(CFG, 2) == {
        "conv": (5, 2, 2, 48)}
    for name in ("tiny-mellum", "tiny-mistral4", "tiny-afmoe",
                 "tiny-longcat-flash", "tiny-qwen2"):
        assert not PRESETS[name].recurrent_state
        assert hybrid.state_shapes(PRESETS[name], 2) == {}
    with pytest.raises(RecurrentStateUnsupported,
                       match="'lfm2_moe'.*short-convolution layers keep "
                             "recurrent state .a window of the last 2 rows"):
        hybrid.refuse_recurrent_state(CFG, "a snapshot")
    with pytest.raises(RecurrentStateUnsupported,
                       match="Mamba-2 layers keep recurrent state .a "
                             "convolution window and an SSM state"):
        hybrid.refuse_recurrent_state(granite, "a snapshot")


@pytest.mark.parametrize("bad", [
    dict(conv_window=0), dict(conv_window=1), dict(num_dense_layers=6),
    dict(layer_types=("mamba",) * 6),
    dict(layer_types=("sliding_attention",) * 6)],
    ids=["no-taps", "one-tap", "no-expert-layer", "mamba", "sliding"])
def test_a_config_the_family_cannot_be_is_refused(bad):
    with pytest.raises(ValueError):
        dataclasses.replace(CFG, **bad)
    with pytest.raises(ValueError, match="lfm2_moe"):
        dataclasses.replace(PRESETS["tiny-qwen2"], conv_window=3)
    with pytest.raises(ValueError, match="conv_window"):
        dataclasses.replace(PRESETS["tiny-granite-hybrid"], conv_window=3)


def _published() -> dict:
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "lfm2-8b-a1b-pp2.json")) as f:
        return json.load(f)


def _hf(**over):
    return types.SimpleNamespace(**{**_published(), **over})


def test_hf_loader_maps_the_published_config():
    c = _published()
    kinds = c["layer_types"] + [
        "conv", "conv", "full_attention", "conv", "conv", "conv",
        "full_attention", "conv", "conv", "full_attention", "conv", "conv"]
    assert config_from_hf(_hf(num_hidden_layers=24,
                              layer_types=kinds)) == LFM2_8B_A1B
    cut = config_from_hf(_hf())
    assert (cut.num_layers, cut.num_dense_layers, cut.expert_layers,
            cut.conv_layers, cut.kv_layers) == (12, 2, 10, 9, 3)
    assert cut.layer_types == LFM2_8B_A1B.layer_types[:12]
    # the catalog's row drops the key: tied is the family's default
    bare = {k: v for k, v in c.items() if k != "tie_word_embeddings"}
    assert config_from_hf(types.SimpleNamespace(**bare)).tie_word_embeddings
    assert config_from_hf(_hf(conv_L_cache=4)).conv_window == 4


@pytest.mark.parametrize("over,match", [
    (dict(conv_bias=True), "conv_bias"),
    (dict(use_expert_bias=False), "use_expert_bias"),
    (dict(norm_topk_prob=False), "norm_topk_prob"),
    (dict(rope_scaling={"rope_type": "yarn", "factor": 4.0}), "rope_scaling"),
])
def test_hf_loader_refuses_an_lfm2_it_does_not_know(over, match):
    with pytest.raises(ValueError, match=match):
        config_from_hf(_hf(**over))


def _state_dict(cfg, params) -> dict:
    """``params`` under the names and layouts the checkpoint publishes:
    ``nn.Linear`` (out, in), the depthwise ``Conv1d`` (D, 1, L)."""
    sd = {"model.embed_tokens.weight": params["embed"],
          "model.embedding_norm.weight": params["final_norm_scale"]}
    seen = {"conv": 0, "attention": 0}
    lin = {"conv": {"conv.in_proj": "w_in", "conv.out_proj": "w_out"},
           "attention": {"self_attn.q_proj": "wq", "self_attn.k_proj": "wk",
                         "self_attn.v_proj": "wv",
                         "self_attn.out_proj": "wo"}}
    for i, kind in enumerate(cfg.layer_types):
        pre, j = f"model.layers.{i}.", seen[kind]
        lp = params["conv" if kind == "conv" else "attn"]
        seen[kind] += 1
        sd[pre + "operator_norm.weight"] = lp["ln1_scale"][j]
        for name, leaf in lin[kind].items():
            sd[pre + name + ".weight"] = lp[leaf][j].T
        if kind == "conv":
            sd[pre + "conv.conv.weight"] = lp["conv_w"][j][:, None, :]
        else:
            sd[pre + "self_attn.q_layernorm.weight"] = lp["q_norm"][j]
            sd[pre + "self_attn.k_layernorm.weight"] = lp["k_norm"][j]
        mp, ff = params["moe"][i], pre + "feed_forward."
        sd[pre + "ffn_norm.weight"] = mp["ln2_scale"]
        names = (("w1", "w_gate"), ("w3", "w_up"), ("w2", "w_down"))
        if "router" not in mp:
            for n, leaf in names:
                sd[ff + n + ".weight"] = mp[leaf].T
            continue
        sd[ff + "gate.weight"] = mp["router"].T
        sd[ff + "expert_bias"] = mp["router_bias"]
        for e in range(cfg.num_experts):
            for n, leaf in names:
                sd[f"{ff}experts.{e}.{n}.weight"] = mp[leaf][e].T
    return {k: np.asarray(v) for k, v in sd.items()}


def test_hf_loader_maps_a_state_dict_to_the_per_kind_tree(params):
    sd = _state_dict(CFG, params)
    got = params_from_state_dict(CFG, sd)
    want_leaves, want_tree = jax.tree_util.tree_flatten(params)
    got_leaves, got_tree = jax.tree_util.tree_flatten(got)
    assert got_tree == want_tree
    for a, b in zip(got_leaves, want_leaves):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    ids = _ids(19, 2)
    assert rel_err(_forward(CFG, got, ids), ref_logits(CFG, params, ids)) < TOL
    with pytest.raises(ValueError, match="conv.conv.bias"):
        params_from_state_dict(CFG, {
            **sd, "model.layers.0.conv.conv.bias": np.zeros((48,))})
    with pytest.raises(ValueError, match="every expert"):
        params_from_state_dict(dataclasses.replace(CFG, experts_held=4), sd)


# -- whole sequences ------------------------------------------------------------

@pytest.mark.parametrize("cfg", [
    CFG,
    tiny_lfm2_moe_config(conv_window=4),
    tiny_lfm2_moe_config(conv_window=2),
    tiny_lfm2_moe_config(experts_held=4, expert_offset=4),
    tiny_lfm2_moe_config(num_dense_layers=1, layer_types=(
        "attention", "conv", "conv", "attention", "conv")),
], ids=["three-taps", "four-taps", "two-taps", "share-upper-half",
        "attention-first-one-dense"])
@pytest.mark.parametrize("length", [24, 57, 1])
def test_forward_matches_the_reference(cfg, length):
    p = make_params(cfg)
    ids = _ids(length)
    assert rel_err(_forward(cfg, p, ids), ref_logits(cfg, p, ids)) < TOL


def test_forward_takes_a_batch_and_refuses_a_hook(params):
    ids = np.stack([_ids(17, 3), _ids(17, 4)])
    with jax.default_matmul_precision("highest"):
        logits, aux = transformer.forward(CFG, params, jnp.asarray(ids))
    assert aux == {} and logits.shape == (2, 17, CFG.vocab_size)
    for b in range(2):
        assert rel_err(logits[b], ref_logits(CFG, params, ids[b])) < TOL
    with pytest.raises(RecurrentStateUnsupported, match="boundary hook"):
        transformer.forward(CFG, params, jnp.asarray(ids),
                            boundary_fn=lambda l, h: h)


def test_the_short_convolution_by_hand():
    """One layer against the equations written out position by position, and
    the window a prefill hands on: the last ``taps - 1`` rows of ``B * x``,
    zero rows AHEAD of a prompt shorter than that."""
    cfg = tiny_lfm2_moe_config(conv_window=4)
    lp = {k: v[1] for k, v in make_params(cfg)["conv"].items()}
    u = jax.random.normal(jax.random.key(2), (1, 7, cfg.hidden_size))
    with jax.default_matmul_precision("highest"):
        out, window = shortconv.shortconv_prefill(cfg, lp, u)
        bcx = np.asarray(u[0] @ lp["w_in"])
        z = bcx[:, :48] * bcx[:, 96:]
        w = np.asarray(lp["conv_w"])
        conv = np.stack([sum(w[:, j] * (z[t - 3 + j] if t - 3 + j >= 0 else 0)
                             for j in range(4)) for t in range(7)])
        want = (bcx[:, 48:96] * conv) @ np.asarray(lp["w_out"])
        np.testing.assert_allclose(np.asarray(out[0]), want, atol=1e-6)
        np.testing.assert_allclose(np.asarray(window[0]), z[4:], atol=1e-7)
        assert window.dtype == jnp.float32 and window.shape == (1, 3, 48)
        _, short = shortconv.shortconv_prefill(cfg, lp, u[:, :2])
        np.testing.assert_allclose(np.asarray(short[0, 1:]), z[:2], atol=1e-7)
        assert float(jnp.abs(short[0, 0]).max()) == 0.0
        # one step against the window of the first six positions
        _, w6 = shortconv.shortconv_prefill(cfg, lp, u[:, :6])
        step, w7 = shortconv.shortconv_step(cfg, lp, u[:, 6], w6)
    np.testing.assert_allclose(np.asarray(step[0]), want[6], atol=1e-6)
    np.testing.assert_allclose(np.asarray(w7), np.asarray(window), atol=1e-7)


@pytest.mark.parametrize("taps", [3, 4])
@pytest.mark.parametrize("plen", [1, 2, 6, 23])
def test_contiguous_decode_step_matches_the_reference(taps, plen):
    """Prompts shorter than the window (1 token against 2 or 3 rows), as
    long, and longer; then 30 steps, each against the reference's full
    forward."""
    cfg = tiny_lfm2_moe_config(conv_window=taps)
    p = make_params(cfg)
    ids = _ids(plen + 30, plen)
    want = ref_logits(cfg, p, ids)
    with jax.default_matmul_precision("highest"):
        logits, cache = transformer.prefill(
            cfg, p, jnp.asarray(ids[:plen])[None], 64)
        assert rel_err(logits[0], want[:plen]) < TOL
        assert isinstance(cache, hybrid.HybridCache)
        assert {k: v.shape for k, v in cache.state.items()} == {
            "conv": (5, 1, taps - 1, 48)}
        step = jax.jit(lambda c, t: transformer.decode_step(cfg, p, c, t))
        for pos in range(plen, plen + 30):
            row, cache = step(cache, jnp.asarray(ids[pos:pos + 1]))
            assert rel_err(row[0], want[pos]) < TOL, pos


# -- prefill, then paged decode through the state store -----------------------

def _worst(tap, slot, cfg, params, prompt, tokens):
    """The worst relative error of a stream's decode steps against the
    reference's full forward over prompt + served tokens."""
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


@pytest.mark.parametrize("taps", [3, 4])
@pytest.mark.parametrize("plen", [1, 2, 16, 41])
def test_prefill_then_paged_decode_through_the_batcher_matches_the_full_forward(
        monkeypatch, taps, plen):
    """The batcher's own admission (the prefill's windows adopted into the
    state store's one leaf, its K/V rows into the pages) and 70 paged steps,
    each step's logits against the reference's full forward over the whole
    sequence: a wrong hand-off of the window shows at the first step, a
    window not advanced at the second."""
    cfg = tiny_lfm2_moe_config(conv_window=taps)
    p = make_params(cfg)
    prompt = _ids(plen, plen)
    tap, b, toks = _serve(monkeypatch, cfg, p, prompt, 71, rng_seed=0)
    assert sorted(b.pool.state) == ["conv"]
    assert b.pool.state["conv"].shape == (5, 3, taps - 1, 48)
    assert len(tap.of_slot(0)) == 70
    assert _worst(tap, 0, cfg, p, prompt, toks) < TOL
    want0 = ref_logits(cfg, p, prompt)[-1]
    assert want0[toks[0]] >= want0.max() - TOL * np.abs(want0).max()
    rep = b.report()
    # a row an EXPERT layer; the two dense layers route nothing
    assert np.asarray(rep["expert_tokens"]).shape == (4, 8)
    assert rep["routed_assignments"] == 70 * 3 * 4 == rep["routed_local"]
    assert rep["state_leaf_bytes"] == {"conv": 5 * 3 * (taps - 1) * 48 * 4}
    assert rep["state_bytes"] == sum(rep["state_leaf_bytes"].values())


def test_batcher_tokens_equal_generate(params):
    b = ContinuousBatcher(CFG, params, BCFG)
    prompts = [_ids(n, n) for n in (1, 13, 26)]
    temps = [0.0, 0.7, 0.0]
    sids = [b.submit(p, 20, temperature=t, rng_seed=i)
            for i, (p, t) in enumerate(zip(prompts, temps))]
    res = b.run()
    for i, (sid, p, t) in enumerate(zip(sids, prompts, temps)):
        want = np.asarray(generate(CFG, params, p[None], 20, temperature=t,
                                   rng_key=jax.random.key(i)))[0]
        np.testing.assert_array_equal(res[sid], want)


def test_what_needs_a_snapshot_refuses_the_family_in_the_windows_words():
    for make in (
            lambda: paged_kv.PagedKVCache(
                CFG, num_pages=9, page_size=4, max_slots=2, pages_per_slot=4,
                kv_codec="int8_per_channel"),
            lambda: ContinuousBatcher(CFG, None, dataclasses.replace(
                BCFG, checkpoint_dir="/nonexistent")),
            lambda: ContinuousBatcher(CFG, None, BCFG).prefill_hold(0)):
        with pytest.raises(RecurrentStateUnsupported,
                           match="'lfm2_moe'.*short-convolution layers keep"):
            make()


def test_the_step_carries_the_new_scopes_and_donates_three_buffers(params):
    b = ContinuousBatcher(CFG, params, BCFG)
    table, lengths = b.pool.device_tables()
    n = BCFG.max_slots
    args = (CFG, params, b.pool.pool.kv, b.pool.state,
            b._expert_tokens, table, lengths,
            jnp.zeros((n,), jnp.int32), jnp.asarray(b._free_key_rows),
            jnp.zeros((n,), jnp.int32), jnp.zeros((n,), jnp.float32), None)
    text = batching._batched_hybrid_step_jit.lower(*args).as_text(
        debug_info=True)
    for scope in ("shortconv.proj", "shortconv.conv", "moe.route",
                  "moe.experts", "mlp", "attn.decode", "paged_kv.write",
                  "unembed_sample"):
        assert scope in text, scope
    assert "ssm." not in text and "moe.shared" not in text
    # the pages' one leaf, the windows, the expert counter
    assert text.count("tf.aliasing_output") == 3
    from edgellm_tpu.lint.contracts import GRAPH_CONTRACTS
    from edgellm_tpu.obs.names import SCOPE_NAMES
    from edgellm_tpu.serve.decode import _prefill_jit

    pre = _prefill_jit.lower(CFG, params, jnp.zeros((1, 16), jnp.int32),
                             BCFG.span, None).as_text(debug_info=True)
    assert "shortconv.conv" in pre and "shortconv.proj" in pre
    assert {"shortconv.proj", "shortconv.conv"} <= set(SCOPE_NAMES)
    assert "paged.decode_step_shortconv" in GRAPH_CONTRACTS


# -- the named mistakes ---------------------------------------------------------

def _taps_reversed(monkeypatch, params):
    conv = {**params["conv"], "conv_w": params["conv"]["conv_w"][..., ::-1]}
    return CFG, {**params, "conv": conv}


def _qk_norm_dropped(monkeypatch, params):
    attn = {k: v for k, v in params["attn"].items()
            if k not in ("q_norm", "k_norm")}
    return CFG, {**params, "attn": attn}


def _bias_dropped(monkeypatch, params):
    moe_ = [{**mp, "router_bias": jnp.zeros_like(mp["router_bias"])}
            if "router_bias" in mp else mp for mp in params["moe"]]
    return CFG, {**params, "moe": moe_}


def _in_bfloat16(monkeypatch, params):
    """bfloat16 where float32 is stated: the weights, and so the step's
    activations and the rows it caches."""
    return CFG, jax.tree_util.tree_map(
        lambda a: a.astype(jnp.bfloat16).astype(jnp.float32), params)


def _window_in_bfloat16(monkeypatch, params):
    """The state store's rows rounded to bfloat16 at every write."""
    real = shortconv.shortconv_step

    def rounded(cfg, lp, u, window):
        out, window = real(cfg, lp, u, window)
        return out, window.astype(jnp.bfloat16).astype(jnp.float32)

    monkeypatch.setattr(hybrid, "shortconv_step", rounded)
    return CFG, params


def _window_not_advanced(monkeypatch, params):
    real = shortconv.shortconv_step
    monkeypatch.setattr(
        hybrid, "shortconv_step",
        lambda cfg, lp, u, window: (real(cfg, lp, u, window)[0], window))
    return CFG, params


def _window_dropped_at_admission(monkeypatch, params):
    real = paged_kv.PagedKVCache.adopt_state
    monkeypatch.setattr(
        paged_kv.PagedKVCache, "adopt_state",
        lambda self, slot, window: real(self, slot, 0.0))
    return CFG, params


def _gates_swapped(monkeypatch, params):
    """``[C | B | x]`` read for ``[B | C | x]``."""
    d = CFG.hidden_size
    w_in = params["conv"]["w_in"]
    swapped = jnp.concatenate([w_in[..., d:2 * d], w_in[..., :d],
                               w_in[..., 2 * d:]], axis=-1)
    return CFG, {**params, "conv": {**params["conv"], "w_in": swapped}}


def _position_free(monkeypatch, params):
    monkeypatch.setattr(ModelConfig, "position_free",
                        property(lambda self: ("attention",)))
    return CFG, params


def _dense_served_as_experts(monkeypatch, params):
    cfg = dataclasses.replace(CFG, num_dense_layers=1)
    moe_ = list(params["moe"])
    moe_[1] = {**moe_[2], "ln2_scale": moe_[1]["ln2_scale"]}
    return cfg, {**params, "moe": moe_}


def _replaced(**change):
    return lambda monkeypatch, params: (dataclasses.replace(CFG, **change),
                                        params)


MISTAKES = {
    "the-taps-reversed": _taps_reversed,
    "the-qk-norm-dropped": _qk_norm_dropped,
    "the-bias-dropped-from-the-choice": _bias_dropped,
    "bfloat16-where-float32-is-stated": _in_bfloat16,
    "the-window-kept-in-bfloat16": _window_in_bfloat16,
    "the-window-not-advanced": _window_not_advanced,
    "the-window-dropped-at-admission": _window_dropped_at_admission,
    "the-gates-swapped": _gates_swapped,
    "the-attention-not-rotated": _position_free,
    "softmax-for-sigmoid": _replaced(score_func="softmax"),
    "a-dense-layer-served-as-an-expert-layer": _dense_served_as_experts,
}


@pytest.mark.parametrize("name", sorted(MISTAKES))
def test_a_named_mistake_fails(monkeypatch, params, name):
    """The comparison above is tight enough: the same prefill-then-decode
    through the batcher, with one thing wrong on the served side, misses the
    reference by at least twenty tolerances at some step."""
    jax.clear_caches()      # a prefill traced by an earlier test is sound
    cfg, p = MISTAKES[name](monkeypatch, params)
    prompt = _ids(23, 5)
    try:
        tap, _, toks = _serve(monkeypatch, cfg, p, prompt, 40, rng_seed=0)
        assert _worst(tap, 0, CFG, params, prompt, toks) > 20 * TOL
    finally:
        monkeypatch.undo()
        jax.clear_caches()  # ... and this one is not: leave none behind


# -- the expert layer and the share -------------------------------------------

def test_the_bias_changes_the_chosen_set_and_leaves_the_weights():
    """The choice is the top-k of p + b; the weights come from p alone, sum
    to ``route_scale`` less the family's 1e-6, and all of it is float32."""
    u = jax.random.normal(jax.random.key(3), (29, CFG.hidden_size))
    w = jax.random.normal(jax.random.key(4), (CFG.hidden_size, 8)) * 0.5
    b = jnp.asarray([3.0, -3.0, 0, 0, 0, 0, 0, 0], jnp.float32)
    idx, got = moe.route(CFG, w.astype(jnp.bfloat16),
                         u.astype(jnp.bfloat16), b)
    idx0, _ = moe.route(CFG, w.astype(jnp.bfloat16), u.astype(jnp.bfloat16),
                        jnp.zeros_like(b))
    assert got.dtype == jnp.float32
    p = np.asarray(jax.nn.sigmoid(jnp.einsum(
        "td,de->te", u.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
        preferred_element_type=jnp.float32)))
    idx = np.asarray(idx)
    # a bias of 3 always wins a seat, one of -3 never does
    assert (idx == 0).any(axis=1).all() and not (idx == 1).any()
    assert (np.sort(idx, axis=1) != np.sort(np.asarray(idx0), axis=1)).any()
    np.testing.assert_array_equal(np.sort(idx, axis=1), np.sort(
        np.argsort(-(p + np.asarray(b)), axis=1)[:, :3], axis=1))
    chosen = np.take_along_axis(p, idx, axis=1)
    np.testing.assert_allclose(
        np.asarray(got), chosen / (chosen.sum(1, keepdims=True) + 1e-6),
        rtol=2e-6)


def test_the_weights_are_normalised_over_the_familys_own_constant():
    """Scores near 1e-7 (router logits of -16): a sum of 3e-7 + 1e-6 where
    the other sigmoid family adds 1e-20."""
    u = jnp.ones((2, CFG.hidden_size))
    w = jnp.full((CFG.hidden_size, 8), -16.0 / CFG.hidden_size)
    _, got = moe.route(CFG, w, u, jnp.zeros((8,)))
    p = float(jax.nn.sigmoid(-16.0))
    np.testing.assert_allclose(np.asarray(got), p / (3 * p + 1e-6), rtol=1e-4)
    afmoe = PRESETS["tiny-afmoe"]
    _, other = moe.route(dataclasses.replace(afmoe, route_scale=1.0), w, u,
                         jnp.zeros((8,)))
    np.testing.assert_allclose(np.asarray(other), 1 / 3, rtol=1e-5)


@pytest.mark.parametrize("tokens", [7, 300])
def test_dense_and_grouped_paths_agree_with_no_shared_expert(tokens):
    cfg = tiny_lfm2_moe_config(experts_held=4, expert_offset=2)
    mp = make_params(CFG)["moe"][2]
    assert "shared_gate" not in mp
    assert float(jnp.abs(mp["router_bias"]).min()) > 0
    mp = {**mp, **{k: mp[k][2:6] for k in ("w_gate", "w_up", "w_down")}}
    u = jax.random.normal(jax.random.key(6), (tokens, cfg.hidden_size))
    with jax.default_matmul_precision("highest"):
        idx, w = moe.route(cfg, mp["router"], u, mp["router_bias"])
        dense = moe._experts_dense(cfg, mp, u, idx, w)
        grouped = moe._experts_grouped(cfg, mp, u, idx, w)
        out, counts = moe.moe_layer(cfg, mp, u)
    assert rel_err(grouped, np.asarray(dense)) < TOL
    routed = dense if tokens <= moe.DENSE_MAX_TOKENS else grouped
    assert rel_err(out, np.asarray(routed)) < TOL
    local = np.asarray(idx) - 2
    want = np.bincount(local[(local >= 0) & (local < 4)], minlength=4)
    np.testing.assert_array_equal(np.asarray(counts), want)


def test_the_shares_add_up_to_the_layer():
    """``experts_held`` 2 of 8 at the four offsets: the four routed parts are
    the uncut layer's output (no shared expert to count once)."""
    mp = make_params(CFG)["moe"][3]
    u = jax.random.normal(jax.random.key(9), (40, CFG.hidden_size))
    with jax.default_matmul_precision("highest"):
        want, counts = moe.moe_layer(CFG, mp, u)
        ref_out = ref._moe(dict(ref.model_key(ref_config(CFG))), mp, u, False)
        total, seen = 0.0, 0
        for offset in range(0, 8, 2):
            cfg = dataclasses.replace(CFG, experts_held=2,
                                      expert_offset=offset)
            part = {**mp, **{k: mp[k][offset:offset + 2]
                             for k in ("w_gate", "w_up", "w_down")}}
            out, c = moe.moe_layer(cfg, part, u)
            np.testing.assert_array_equal(np.asarray(c), np.asarray(
                counts[offset:offset + 2]))
            total, seen = total + out, seen + int(c.sum())
    assert seen == 40 * 3
    assert rel_err(total, np.asarray(want)) < TOL
    assert rel_err(want, np.asarray(ref_out)) < TOL


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
    assert main(["--params", json.dumps(params), "--model", "tiny-lfm2-moe",
                 "--output-dir", str(tmp_path / "out")]) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["outcomes"] == {"completed": 3} and line["mode"] == "batched"
