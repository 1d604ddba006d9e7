"""The ``afmoe`` family (Arcee Trinity: sigmoid routing with a selection bias
and a shared expert, a leading dense layer, per-head q/k norms, a gated
attention rotated on the window layers only, four norms a layer, the embedding
times sqrt(d)) against its plain reference, on the CPU at toy widths with
seeded float32 weights.

The reference is ``benchmark/reference_afmoe.py``: float32 at ``highest``,
whole sequences, no cache, no pages, no ring, nothing imported from the
program. Both sides compute in float32 here, so they differ by summation
order alone.

TOL: logits are compared as ``max |system - reference| <= TOL * max
|reference|``. 2e-5 is ~100 float32 roundings of a five-layer stack whose
sums run over at most 96 terms; the readings are 3e-7 to 9e-7 (the forward,
the prefill by blocks, the contiguous decode and seventy paged steps through
both page groups alike). A sigmoid top-k is a discrete choice: where the k-th
and (k+1)-th of ``p + b`` lie within a rounding of each other the two sides
may pick differently, and the toy router is seeded wide (logits of std ~1.5)
so that no test position does. Every named mistake below moves the logits by
far more at some step of a 40-token answer, and ``test_a_named_mistake_fails``
holds each to twenty tolerances.
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

from benchmark import reference_afmoe as ref  # noqa: E402
from edgellm_tpu.models import hybrid, moe, paged_kv, transformer  # noqa: E402
from edgellm_tpu.models.configs import (PRESETS, TRINITY_MINI,  # noqa: E402
                                        ModelConfig, tiny_afmoe_config,
                                        tiny_mellum_config)
from edgellm_tpu.models.hf_loader import config_from_hf  # noqa: E402
from edgellm_tpu.models.hybrid import WindowRingUnsupported  # noqa: E402
from edgellm_tpu.serve import batching  # noqa: E402
from edgellm_tpu.serve.batching import (BatchingConfig,  # noqa: E402
                                        ContinuousBatcher)
from edgellm_tpu.serve.decode import generate  # noqa: E402

TOL = 2e-5
#: window 10 over pages of 4: a ring of ceil(9 / 4) + 1 = 4 pages
CFG = tiny_afmoe_config(sliding_window=10)
BCFG = BatchingConfig(page_size=4, num_pages=121, max_slots=3,
                      pages_per_slot=40)
KINDS = {"attention": "full_attention",
         "sliding_attention": "sliding_attention"}


def ref_config(cfg: ModelConfig) -> dict:
    """The published keys the reference reads, from a ModelConfig."""
    return {
        "hidden_size": cfg.hidden_size,
        "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads, "head_dim": cfg.head_dim,
        "rms_norm_eps": cfg.norm_eps, "rope_theta": cfg.rope_theta,
        "rope_scaling": None,
        "layer_types": [KINDS[t] for t in cfg.layer_types],
        "sliding_window": cfg.sliding_window,
        "num_dense_layers": cfg.num_dense_layers,
        "num_experts": cfg.num_experts,
        "num_experts_per_tok": cfg.experts_per_tok,
        "score_func": cfg.score_func, "route_norm": True,
        "route_scale": cfg.route_scale,
        "mup_enabled": cfg.embedding_multiplier != 1.0,
        "share": {"experts_held": cfg.local_experts,
                  "expert_offset": cfg.expert_offset}}


def make_params(cfg, seed=0):
    """Seeded weights, every matrix at std 0.04 instead of 0.02 and norm
    scales off one (at width 48 that makes attention, the experts and the
    untied head each a visible part of the logits), the router at std 0.2
    (sigmoid scores spread over 0.1 .. 0.9) and the selection bias at std
    0.2: it changes the chosen set at most positions."""
    params = transformer.init_params(cfg, jax.random.key(seed))
    keys = iter(jax.random.split(jax.random.key(seed + 1), 256))

    def shake(path, a):
        name = path[-1].key
        if name.endswith("_scale") or name in ("q_norm", "k_norm"):
            return a + 0.1 * jax.random.normal(next(keys), a.shape)
        if name in ("router", "router_bias"):
            return a * 10.0
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
    c = PRESETS["trinity-mini"]
    assert c is TRINITY_MINI and c.is_hybrid and not c.recurrent_state
    assert (c.hidden_size, c.num_heads, c.num_kv_heads, c.head_dim) == (
        2048, 32, 4, 128)
    assert c.num_layers == 32 and c.window_layers == 24 and c.kv_layers == 8
    assert c.layer_types[:4] == ("sliding_attention",) * 3 + ("attention",)
    assert (c.sliding_window, c.window_pages(16)) == (2048, 129)
    assert (c.num_dense_layers, c.expert_layers, c.intermediate_size) == (
        2, 30, 6144)
    assert (c.num_experts, c.experts_per_tok, c.expert_width,
            c.shared_width) == (128, 8, 1024, 1024)
    assert (c.score_func, c.route_scale) == ("sigmoid", 2.826)
    assert c.embedding_multiplier == 2048 ** 0.5
    assert c.position_free == ("attention",) and c.rope_scaling is None
    assert (c.rope_theta, c.rotary_dim, c.vocab_size, c.norm_eps) == (
        10000.0, 128, 200192, 1e-5)
    assert not c.tie_word_embeddings and c.q_prescale == 1.0
    # granite's stack-wide flag is the case where every kind has none
    assert PRESETS["granite-4.0-h-small"].position_free == (
        "attention", "sliding_attention")
    assert PRESETS["mellum2-12b-a2.5b"].position_free == ()
    assert PRESETS["tiny-afmoe"].family == "afmoe"


@pytest.mark.parametrize("bad", [
    dict(num_dense_layers=5), dict(score_func="tanh"),
    dict(layer_types=("mamba",) * 5), dict(sliding_window=0)])
def test_a_config_the_family_cannot_be_is_refused(bad):
    with pytest.raises(ValueError):
        dataclasses.replace(CFG, **bad)
    with pytest.raises(ValueError, match="afmoe"):
        dataclasses.replace(PRESETS["tiny-qwen2"], num_dense_layers=1)


def _hf(**over):
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "trinity-mini-pp8.json")) as f:
        c = json.load(f)
    return types.SimpleNamespace(**{**c, **over})


def test_hf_loader_maps_the_published_config():
    full = _hf(num_hidden_layers=32, num_dense_layers=2, layer_types=(
        ["sliding_attention"] * 3 + ["full_attention"]) * 8)
    assert config_from_hf(full) == TRINITY_MINI
    cut = config_from_hf(_hf())
    assert (cut.num_layers, cut.num_dense_layers, cut.expert_layers,
            cut.window_layers, cut.kv_layers) == (5, 1, 4, 4, 1)
    assert config_from_hf(_hf(num_shared_experts=0)).shared_width == 0
    assert config_from_hf(_hf(mup_enabled=False)).embedding_multiplier == 1.0


@pytest.mark.parametrize("over,match", [
    (dict(n_group=2), "n_group"), (dict(topk_group=2), "topk_group"),
    (dict(num_expert_groups=4), "num_expert_groups"),
    (dict(num_limited_groups=2), "num_limited_groups"),
    (dict(rope_scaling={"rope_type": "yarn", "factor": 4.0}), "rope_scaling"),
    (dict(score_func="softmax"), "score_func"),
    (dict(route_norm=False), "route_norm"),
])
def test_hf_loader_refuses_an_afmoe_it_does_not_know(over, match):
    with pytest.raises(ValueError, match=match):
        config_from_hf(_hf(**over))


# -- whole sequences ------------------------------------------------------------

@pytest.mark.parametrize("cfg", [
    CFG,
    tiny_afmoe_config(sliding_window=12),
    tiny_afmoe_config(sliding_window=10, experts_held=4, expert_offset=4),
    tiny_afmoe_config(sliding_window=200),
    tiny_afmoe_config(sliding_window=7, num_dense_layers=2, layer_types=(
        "attention", "sliding_attention", "sliding_attention",
        "sliding_attention", "attention", "sliding_attention")),
], ids=["window10", "window12", "share-upper-half", "window-past-the-prompt",
        "two-dense-full-first"])
@pytest.mark.parametrize("length", [24, 57])
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
    with pytest.raises(WindowRingUnsupported, match="boundary hook"):
        transformer.forward(CFG, params, jnp.asarray(ids),
                            boundary_fn=lambda l, h: h)


def test_prefill_by_query_blocks_matches_the_reference(monkeypatch, params):
    monkeypatch.setattr(hybrid, "QBLOCK", 16)
    ids = _ids(40, 11)
    with jax.default_matmul_precision("highest"):
        logits, cache = transformer.prefill(CFG, params,
                                            jnp.asarray(ids)[None], 48)
        last, _ = hybrid.prefill_hybrid(CFG, params, jnp.asarray(ids)[None],
                                        48, last_only=True)
    want = ref_logits(CFG, params, ids)
    assert rel_err(logits[0], want) < TOL
    assert rel_err(last[0], want[-1]) < TOL
    assert cache.k.shape == (1, 1, 48, 2, 16)
    assert cache.wk.shape == (4, 1, 48, 2, 16)
    assert isinstance(cache, hybrid.WindowCache)


@pytest.mark.parametrize("plen", [6, 23])
def test_contiguous_decode_step_matches_the_reference(params, plen):
    ids = _ids(plen + 30, plen)
    want = ref_logits(CFG, params, ids)
    with jax.default_matmul_precision("highest"):
        logits, cache = transformer.prefill(
            CFG, params, jnp.asarray(ids[:plen])[None], 64)
        assert rel_err(logits[0], want[:plen]) < TOL
        step = jax.jit(lambda c, t: transformer.decode_step(CFG, params, c,
                                                            t))
        for pos in range(plen, plen + 30):
            row, cache = step(cache, jnp.asarray(ids[pos:pos + 1]))
            assert rel_err(row[0], want[pos]) < TOL, pos


# -- prefill, then paged decode through both page groups ----------------------

class LogitTap:
    """``ContinuousBatcher`` with its step executable replaced by one that
    also hands the logits out: the same ``paged_decode_step_hybrid``, the same
    sampler, the batcher's own admission, adoption and tables around it."""

    def __init__(self, monkeypatch, cfg):
        self.rows = []        # (lengths, logits) per step

        @jax.jit
        def step(params, pool, wpool, cnt, table, wtable, lengths, toks,
                 key_data, steps, temps):
            with jax.default_matmul_precision("highest"):
                logits, kv, _, cnt, win = (
                    hybrid.paged_decode_step_hybrid(
                        cfg, params, pool.kv, None, cnt, table,
                        lengths, toks, window=(wpool.kv, wtable)))
            return (logits, batching._batched_sample(logits, key_data, steps,
                                                     temps),
                    type(pool)(kv), type(wpool)(win), cnt)

        def tapped(cfg_, params, pool, wpool, cnt, table, wtable, lengths,
                   toks, key_data, steps, temps, compute_dtype):
            logits, *rest = step(params, pool, wpool, cnt, table, wtable,
                                 lengths, toks, key_data, steps, temps)
            self.rows.append((np.array(lengths), np.array(logits)))
            return tuple(rest)

        tapped._cache_size = lambda: 0
        monkeypatch.setattr(batching, "_batched_window_step_jit", tapped)

    def of_slot(self, slot):
        """{cache length before the step: that slot's logits row}."""
        return {int(lengths[slot]): logits[slot]
                for lengths, logits in self.rows if lengths[slot] > 0}


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


@pytest.mark.parametrize("window", [10, 12])
@pytest.mark.parametrize("plen", [3, 16, 41])
def test_prefill_then_paged_decode_through_both_groups_matches_the_full_forward(
        monkeypatch, window, plen):
    """A ring of 4 pages of 4 rows beside a full layer's growing pages:
    prompts shorter than the ring, as long, and longer than two turns of it;
    then 70 decode steps (seven windows), each step's logits against the
    reference's full forward over the whole sequence."""
    cfg = tiny_afmoe_config(sliding_window=window)
    p = make_params(cfg)
    prompt = _ids(plen, plen)
    tap, b, toks = _serve(monkeypatch, cfg, p, prompt, 71, rng_seed=0)
    assert b.pool.window_pages == 4
    assert len(tap.of_slot(0)) == 70
    assert _worst(tap, 0, cfg, p, prompt, toks) < TOL
    want0 = ref_logits(cfg, p, prompt)[-1]
    assert want0[toks[0]] >= want0.max() - TOL * np.abs(want0).max()
    rep = b.report()
    # a row an EXPERT layer; the dense layer routes nothing
    assert np.asarray(rep["expert_tokens"]).shape == (4, 8)
    assert rep["routed_assignments"] == 70 * 3 * 4 == rep["routed_local"]


def test_evict_then_readmit_reproduces_the_undisturbed_stream(monkeypatch,
                                                             params):
    prompt = _ids(21, 7)
    with jax.default_matmul_precision("highest"):
        tap0 = LogitTap(monkeypatch, CFG)
        calm = ContinuousBatcher(CFG, params, BCFG)
        sid = calm.submit(prompt, 40, rng_seed=3, temperature=0.7)
        want = calm.run()[sid]
        tap1 = LogitTap(monkeypatch, CFG)
        b = ContinuousBatcher(CFG, params, BCFG)
        other = b.submit(_ids(6, 8), 50, rng_seed=4)   # takes slot 0
        sid = b.submit(prompt, 40, rng_seed=3, temperature=0.7)
        for _ in range(14):
            b.step()
        st = b._streams[sid]
        assert st.status == "running" and st.slot == 1
        b.evict(sid)
        assert set(st.resume) == {"k", "v", "length", "wk", "wv"}
        n = int(st.resume["length"])
        assert n == 21 + 14 and st.resume["k"].shape[:2] == (1, n)
        assert st.resume["wk"].shape[:2] == (4, n - 20)
        b.pool.check_invariants()
        got = b.run()[sid]
        assert b.report()["evicted"] == 1 and other in b.results
    np.testing.assert_array_equal(got, want)
    a, c = tap0.of_slot(0), tap1.of_slot(1)
    assert len(c) == len(a) == 39
    for pos, row in a.items():        # byte copies out and back: the same
        np.testing.assert_allclose(c[pos], row, rtol=0, atol=1e-7)
    assert _worst(tap1, 1, CFG, params, prompt, got) < TOL


def test_adjacent_slots_do_not_read_each_others_ring_and_a_reused_slot_is_clean(
        monkeypatch, params):
    prompt = _ids(9, 21)
    with jax.default_matmul_precision("highest"):
        alone = ContinuousBatcher(CFG, params, BCFG)
        sid = alone.submit(prompt, 30, rng_seed=1)
        want = alone.run()[sid]
        tap = LogitTap(monkeypatch, CFG)
        b = ContinuousBatcher(CFG, params, BCFG)
        first = b.submit(_ids(25, 22), 3, rng_seed=2)     # slot 0, ends early
        sid = b.submit(prompt, 30, rng_seed=1)            # slot 1
        third = b.submit(_ids(7, 23), 35, rng_seed=5)     # slot 2
        for _ in range(3):
            b.step()
        assert first in b.results and not b.pool.active[0]
        again = b.submit(prompt, 30, rng_seed=1)   # reuses slot 0's stale ring
        res = b.run()
    np.testing.assert_array_equal(res[sid], want)
    np.testing.assert_array_equal(res[again], want)
    assert _worst(tap, 1, CFG, params, prompt, res[sid]) < TOL
    assert third in res


def test_batcher_tokens_equal_generate(params):
    b = ContinuousBatcher(CFG, params, BCFG)
    prompts = [_ids(n, n) for n in (5, 13, 26)]
    temps = [0.0, 0.7, 0.0]
    sids = [b.submit(p, 20, temperature=t, rng_seed=i)
            for i, (p, t) in enumerate(zip(prompts, temps))]
    res = b.run()
    for i, (sid, p, t) in enumerate(zip(sids, prompts, temps)):
        want = np.asarray(generate(CFG, params, p[None], 20, temperature=t,
                                   rng_key=jax.random.key(i)))[0]
        np.testing.assert_array_equal(res[sid], want)


def test_what_reads_full_history_refuses_the_family_in_the_rings_words():
    for make in (
            lambda: paged_kv.PagedKVCache(
                CFG, num_pages=9, page_size=4, max_slots=2, pages_per_slot=4,
                kv_codec="int8_per_channel"),
            lambda: ContinuousBatcher(CFG, None, dataclasses.replace(
                BCFG, checkpoint_dir="/nonexistent")),
            lambda: ContinuousBatcher(CFG, None, BCFG).prefill_hold(0)):
        with pytest.raises(WindowRingUnsupported,
                           match="'afmoe'.*4 sliding-window layers keep a"):
            make()


# -- the named mistakes ---------------------------------------------------------

def _both(monkeypatch, name, fn):
    """A helper ``hybrid`` imported by name from ``paged_kv``, replaced in
    both."""
    monkeypatch.setattr(paged_kv, name, fn)
    monkeypatch.setattr(hybrid, name, fn)


def _route_with(change):
    """``moe.route`` with the sigmoid branch's weights or choice changed."""
    def make(monkeypatch, params):
        def route(cfg, router_w, u, bias=None):
            logits = jnp.einsum("td,de->te", u, router_w,
                                preferred_element_type=jnp.float32)
            p = jax.nn.sigmoid(logits)
            _, idx = jax.lax.top_k(p + bias, cfg.experts_per_tok)
            chosen = jnp.take_along_axis(
                p + bias if change == "bias-in-weights" else p, idx, axis=-1)
            if change != "no-route-norm":
                chosen = chosen / (chosen.sum(-1, keepdims=True) + 1e-20)
            return idx.astype(jnp.int32), chosen * cfg.route_scale

        monkeypatch.setattr(moe, "route", route)
        return CFG, params
    return make


def _without(*path):
    """The same weights with one leaf dropped (a list index walks ``moe``)."""
    def make(monkeypatch, params):
        out = jax.tree_util.tree_map(lambda a: a, params)
        node = out
        for part in path[:-1]:
            node = node[part]
        del node[path[-1]]
        return CFG, out
    return make


def _replaced(**change):
    return lambda monkeypatch, params: (dataclasses.replace(CFG, **change),
                                        params)


def _bias_dropped(monkeypatch, params):
    moe_ = [{**mp, "router_bias": jnp.zeros_like(mp["router_bias"])}
            if "router_bias" in mp else mp for mp in params["moe"]]
    return CFG, {**params, "moe": moe_}


def _gate_after_wo(monkeypatch, params):
    """sigmoid(x W_g) times the output of W_o instead of its input: at a
    width where both are 64 lanes (the same weights, a wider stream)."""
    cfg = tiny_afmoe_config(sliding_window=10, hidden_size=64)
    held = []
    real = paged_kv.post_norm

    def gated(lp, x, ctx):
        held.append(jax.nn.sigmoid(x @ lp["wg"]))
        return ctx

    def post_norm(cfg_, lp, out):
        return real(cfg_, lp, out * held.pop() if held else out)

    _both(monkeypatch, "gated", gated)
    _both(monkeypatch, "post_norm", post_norm)
    return cfg, make_params(cfg)


def _norm_after_rotation(monkeypatch, params):
    """q and k normed per head AFTER the rotation. A rotation keeps a head's
    mean square, so that is ``R(x / rms) * g`` where the right order gives
    ``R(x / rms * g)``; the scales ``g`` are off one, so the two differ."""
    scales = []
    real_norms = paged_kv.head_norms
    real_rot, real_rows = hybrid.apply_rotary, paged_kv._apply_rotary_rows

    def head_norms(cfg, lp, q, k):
        scales[:] = [lp["q_norm"], lp["k_norm"]]
        return real_norms(cfg, lp, q, k)

    def after(rotate):
        def wrong(x, *a):
            g = scales.pop(0)
            return rotate(x / g, *a) * g
        return wrong

    _both(monkeypatch, "head_norms", head_norms)
    monkeypatch.setattr(hybrid, "apply_rotary", after(real_rot))
    monkeypatch.setattr(paged_kv, "_apply_rotary_rows", after(real_rows))
    return CFG, params


def _position_free(kinds):
    def make(monkeypatch, params):
        monkeypatch.setattr(ModelConfig, "position_free",
                            property(lambda self: kinds))
        return CFG, params
    return make


def _dense_served_as_experts(monkeypatch, params):
    cfg = dataclasses.replace(CFG, num_dense_layers=0)
    moe_ = list(params["moe"])
    moe_[0] = {**moe_[1], "ln2_scale": moe_[0]["ln2_scale"],
               "post_scale": moe_[0]["post_scale"]}
    return cfg, {**params, "moe": moe_}


MISTAKES = {
    "the-bias-added-to-the-weights": _route_with("bias-in-weights"),
    "the-bias-dropped-from-the-choice": _bias_dropped,
    "softmax-for-sigmoid": _replaced(score_func="softmax"),
    "route-scale-left-out": _replaced(route_scale=1.0),
    "no-route-norm": _route_with("no-route-norm"),
    "the-gate-left-out": _without("window", "wg"),
    "the-gate-after-wo": _gate_after_wo,
    "qk-norm-after-the-rotation": _norm_after_rotation,
    "the-full-layer-rotated": _position_free(()),
    "the-window-layers-not-rotated": _position_free(
        ("attention", "sliding_attention")),
    "a-post-norm-left-out": _without("moe", 2, "post_scale"),
    "the-embedding-unscaled": _replaced(embedding_multiplier=1.0),
    "the-dense-layer-served-as-an-expert-layer": _dense_served_as_experts,
}


@pytest.mark.parametrize("name", sorted(MISTAKES))
def test_a_named_mistake_fails(monkeypatch, params, name):
    """The comparison above is tight enough: the same prefill-then-decode
    through the batcher, with one thing wrong on the served side, misses the
    reference by at least twenty tolerances at some step."""
    jax.clear_caches()      # a prefill traced by an earlier test is sound
    cfg, p = MISTAKES[name](monkeypatch, params)
    right = dataclasses.replace(CFG, hidden_size=cfg.hidden_size,
                                embedding_multiplier=float(
                                    cfg.hidden_size) ** 0.5)
    prompt = _ids(23, 5)
    try:
        tap, _, toks = _serve(monkeypatch, cfg, p, prompt, 40, rng_seed=0)
        ref_p = p if right.hidden_size != CFG.hidden_size else params
        assert _worst(tap, 0, right, ref_p, prompt, toks) > 20 * TOL
    finally:
        monkeypatch.undo()
        jax.clear_caches()  # ... and this one is not: leave none behind


# -- the expert layer and the share -------------------------------------------

@pytest.mark.parametrize("tokens", [7, 300, 304])
def test_dense_and_grouped_paths_agree_with_the_shared_expert_and_the_bias(
        tokens):
    cfg = tiny_afmoe_config(experts_held=4, expert_offset=2)
    mp = make_params(tiny_afmoe_config())["moe"][1]
    assert "shared_gate" in mp and float(jnp.abs(mp["router_bias"]).min()) > 0
    mp = {**mp, **{k: mp[k][2:6] for k in ("w_gate", "w_up", "w_down")}}
    u = jax.random.normal(jax.random.key(6), (tokens, cfg.hidden_size))
    with jax.default_matmul_precision("highest"):
        idx, w = moe.route(cfg, mp["router"], u, mp["router_bias"])
        dense = moe._experts_dense(cfg, mp, u, idx, w)
        grouped = moe._experts_grouped(cfg, mp, u, idx, w)
        out, counts = moe.moe_layer(cfg, mp, u)
        shared = (jax.nn.silu(u @ mp["shared_gate"])
                  * (u @ mp["shared_up"])) @ mp["shared_down"]
    assert rel_err(grouped, np.asarray(dense)) < TOL
    routed = dense if tokens <= moe.DENSE_MAX_TOKENS else grouped
    assert rel_err(out, np.asarray(routed + shared)) < TOL
    local = np.asarray(idx) - 2
    want = np.bincount(local[(local >= 0) & (local < 4)], minlength=4)
    np.testing.assert_array_equal(np.asarray(counts), want)


def test_the_route_is_the_sigmoid_of_the_issue_by_hand():
    """The choice is the top-k of p + b, the weights come from p alone, sum
    to ``route_scale``, and all of it is float32 whatever u is."""
    cfg = tiny_afmoe_config()
    u = jax.random.normal(jax.random.key(3), (29, cfg.hidden_size))
    w = jax.random.normal(jax.random.key(4), (cfg.hidden_size, 8)) * 0.5
    b = jnp.asarray([3.0, -3.0, 0, 0, 0, 0, 0, 0], jnp.float32)
    idx, got = moe.route(cfg, w.astype(jnp.bfloat16),
                         u.astype(jnp.bfloat16), b)
    assert got.dtype == jnp.float32
    p = np.asarray(jax.nn.sigmoid(jnp.einsum(
        "td,de->te", u.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
        preferred_element_type=jnp.float32)))
    idx = np.asarray(idx)
    # a bias of 3 always wins a seat, one of -3 never does
    assert (idx == 0).any(axis=1).all() and not (idx == 1).any()
    np.testing.assert_array_equal(np.sort(idx, axis=1), np.sort(
        np.argsort(-(p + np.asarray(b)), axis=1)[:, :3], axis=1))
    chosen = np.take_along_axis(p, idx, axis=1)
    np.testing.assert_allclose(np.asarray(got), cfg.route_scale * chosen
                               / chosen.sum(1, keepdims=True), rtol=2e-6)
    # softmax families never read a bias
    mellum = tiny_mellum_config()
    i0, w0 = moe.route(mellum, w, u)
    i1, w1 = moe.route(mellum, w, u, b)
    np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
    np.testing.assert_array_equal(np.asarray(w0), np.asarray(w1))


def test_the_shares_and_the_shared_expert_counted_once_add_up_to_the_layer():
    """``experts_held`` 2 of 8 at the four offsets (the published model: 32
    of 128): the four routed parts plus the shared expert ONCE are the uncut
    layer's output."""
    whole = tiny_afmoe_config()
    mp = make_params(whole)["moe"][2]
    u = jax.random.normal(jax.random.key(9), (40, whole.hidden_size))
    no_shared = {k: v for k, v in mp.items() if not k.startswith("shared_")}
    with jax.default_matmul_precision("highest"):
        want, counts = moe.moe_layer(whole, mp, u)
        shared = (jax.nn.silu(u @ mp["shared_gate"])
                  * (u @ mp["shared_up"])) @ mp["shared_down"]
        total, seen = shared, 0
        for offset in range(0, 8, 2):
            cfg = dataclasses.replace(whole, experts_held=2,
                                      expert_offset=offset, shared_width=0)
            part = {**no_shared, **{k: mp[k][offset:offset + 2]
                                    for k in ("w_gate", "w_up", "w_down")}}
            out, c = moe.moe_layer(cfg, part, u)
            np.testing.assert_array_equal(np.asarray(c), np.asarray(
                counts[offset:offset + 2]))
            total, seen = total + out, seen + int(c.sum())
    assert seen == 40 * 3
    assert rel_err(total, np.asarray(want)) < TOL


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
    assert main(["--params", json.dumps(params), "--model", "tiny-afmoe",
                 "--output-dir", str(tmp_path / "out")]) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["outcomes"] == {"completed": 3} and line["mode"] == "batched"
