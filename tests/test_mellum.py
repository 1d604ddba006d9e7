"""The ``mellum`` family (sliding-window layers beside full ones, YaRN on the
full layers only, an explicit head width, routed experts with none shared, an
untied head) against its plain reference, on the CPU at toy widths with
seeded float32 weights.

The reference is ``benchmark/reference_mellum.py``: float32 at ``highest``,
whole sequences, no cache, no pages, no ring, nothing imported from the
program. Both sides compute in float32 here, so they differ by summation
order alone.

TOL: logits are compared as ``max |system - reference| <= TOL * max
|reference|``. 2e-5 is ~100 float32 roundings of an eight-layer stack whose
sums run over at most 64 terms; the readings are 3e-7 to 7e-7 (the forward,
the prefill by blocks, the contiguous decode and seventy paged steps through
the ring alike). Every named mistake below moves the logits by far more of
their size at some step of a 40-token answer — bfloat16 keys in the ring
2.3e-3, no YaRN attention factor 0.011, YaRN on no layer 0.021, YaRN on the
sliding layers too 0.051, a window one key short 0.32, a full layer served
as a window layer 0.85, a ring row masked by its place 1.0, window layers
served as full ones 1.5, a tied head 1.8 — and
``test_a_named_mistake_fails`` holds each to twenty tolerances (4e-4: under
the least of them by 5x, so bfloat16 where float32 is stated fails).
"""
import dataclasses
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

from benchmark import reference_mellum as ref  # noqa: E402
from edgellm_tpu.models import hybrid, moe, paged_kv, transformer  # noqa: E402
from edgellm_tpu.models.configs import (MELLUM2_12B_A2_5B,  # noqa: E402
                                        PRESETS, ModelConfig,
                                        tiny_hybrid_config,
                                        tiny_mellum_config)
from edgellm_tpu.models.hybrid import (RecurrentStateUnsupported,  # noqa: E402
                                       WindowRingUnsupported)
from edgellm_tpu.models.paged_kv import (PagedKVCache,  # noqa: E402
                                         PrefixCacheConfig)
from edgellm_tpu.serve import batching  # noqa: E402
from edgellm_tpu.serve.batching import (BatchingConfig,  # noqa: E402
                                        ContinuousBatcher)
from edgellm_tpu.serve.decode import generate  # noqa: E402

TOL = 2e-5
#: window 10 over pages of 4: a ring of ceil(9 / 4) + 1 = 4 pages, 16 rows,
#: NOT a multiple of the page; 12 is the case that is (12 // 4 + 1 = 4)
CFG = tiny_mellum_config(sliding_window=10)
BCFG = BatchingConfig(page_size=4, num_pages=121, max_slots=3,
                      pages_per_slot=40)
KINDS = {"attention": "full_attention",
         "sliding_attention": "sliding_attention"}


def ref_config(cfg: ModelConfig) -> dict:
    """The published keys the reference reads, from a ModelConfig."""
    _, factor, orig, beta_fast, beta_slow, attention_factor = cfg.rope_scaling
    return {
        "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads, "head_dim": cfg.head_dim,
        "rms_norm_eps": cfg.norm_eps,
        "layer_types": [KINDS[t] for t in cfg.layer_types],
        "sliding_window": cfg.sliding_window,
        "num_experts": cfg.num_experts,
        "num_experts_per_tok": cfg.experts_per_tok,
        "share": {"experts_held": cfg.local_experts,
                  "expert_offset": cfg.expert_offset},
        "rope_parameters": {
            "full_attention": {
                "rope_type": "yarn", "rope_theta": cfg.rope_theta,
                "factor": factor, "original_max_position_embeddings": orig,
                "beta_fast": beta_fast, "beta_slow": beta_slow,
                "attention_factor": attention_factor},
            "sliding_attention": {"rope_type": "default",
                                  "rope_theta": cfg.rope_theta}}}


def make_params(cfg, seed=0):
    """Seeded weights, every matrix at std 0.04 instead of 0.02 and norm
    scales off one: at width 48 that makes attention, the experts and the
    untied head each a visible part of the logits."""
    params = transformer.init_params(cfg, jax.random.key(seed))
    keys = iter(jax.random.split(jax.random.key(seed + 1), 256))

    def shake(path, a):
        if path[-1].key.endswith("_scale"):
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
    c = PRESETS["mellum2-12b-a2.5b"]
    assert c is MELLUM2_12B_A2_5B and c.family == "mellum"
    assert c.head_dim == 128 and c.num_heads * c.head_dim == 4096 \
        != c.hidden_size == 2304
    assert (c.kv_layers, c.window_layers, c.mamba_layers) == (7, 21, 0)
    assert c.layer_types[:4] == ("sliding_attention",) * 3 + ("attention",)
    assert c.window_pages(16) == 1024 // 16 + 1 == 65
    assert (c.num_experts, c.experts_per_tok, c.expert_width,
            c.shared_width, c.local_experts) == (64, 8, 896, 0, 64)
    assert not c.tie_word_embeddings and c.is_hybrid \
        and not c.recurrent_state


@pytest.mark.parametrize("window,page,pages", [
    (1024, 16, 65), (10, 4, 4), (12, 4, 4), (20, 8, 4), (16, 8, 3),
    (1, 4, 1), (2, 4, 2), (9, 4, 3)])
def test_a_ring_holds_a_whole_window_wherever_it_starts_in_a_page(
        window, page, pages):
    """``ceil((window - 1) / page) + 1``: ``window // page + 1`` where the
    page divides the window; one more where the remainder is two or more,
    because a window that starts on a page's last row touches that many."""
    cfg = tiny_mellum_config(sliding_window=window)
    assert cfg.window_pages(page) == pages
    # brute force: the most pages any `window` consecutive positions touch
    most = max(len({p // page for p in range(t - window + 1, t + 1)})
               for t in range(window, window + 3 * page))
    assert pages == most
    if window % page == 0 and page > 1:
        assert pages == window // page + 1


@pytest.mark.parametrize("bad", [
    dict(layer_types=("mamba",) * 8), dict(sliding_window=0),
    dict(experts_per_tok=9)])
def test_a_config_the_family_cannot_be_is_refused(bad):
    with pytest.raises(ValueError):
        tiny_mellum_config(**bad)


def test_the_one_block_families_take_no_head_width_or_window():
    with pytest.raises(ValueError, match="mellum"):
        dataclasses.replace(PRESETS["tiny-qwen2"], sliding_window=8)
    with pytest.raises(ValueError, match="mellum"):
        dataclasses.replace(PRESETS["tiny-llama"], explicit_head_dim=32)
    with pytest.raises(ValueError, match="granitemoehybrid"):
        tiny_hybrid_config(layer_types=("sliding_attention", "mamba",
                                        "attention", "mamba"))


# -- the YaRN table at the published numbers -----------------------------------

def _published_inv_freq(d):
    """The issue's formulas by hand, in Python floats."""
    base = 500000.0 ** (-2.0 * d / 128)
    ramp = min(max((d - 18) / (35 - 18), 0.0), 1.0)
    return base / 16 * ramp + base * (1 - ramp)


def test_yarn_band_at_the_published_numbers():
    sc = MELLUM2_12B_A2_5B.rope_scaling

    def dim(r):
        return 128 * math.log(8192 / (2 * math.pi * r)) / (
            2 * math.log(500000))

    assert abs(dim(32) - 18.08) < 0.01 and abs(dim(1) - 34.98) < 0.01
    assert transformer.yarn_band(128, 500000.0, sc) == (18, 35)
    assert ref.yarn_band(dict(ref.model_key(ref_config(
        MELLUM2_12B_A2_5B)))) == (18, 35)
    assert abs(sc[5] - (0.1 * math.log(16) + 1)) < 1e-12


@pytest.mark.parametrize("d,want", [
    (0, 1.0),                                  # first: kept, theta^0
    (17, 500000.0 ** (-34 / 128)),             # below low: kept
    (18, 500000.0 ** (-36 / 128)),             # low itself: ramp 0
    (26, None),                                # inside the ramp: 8/17 blended
    (35, 500000.0 ** (-70 / 128) / 16),        # high: interpolated
    (63, 500000.0 ** (-126 / 128) / 16),       # last
])
def test_yarn_table_entries_at_the_published_numbers(d, want):
    """cos/sin of the full layers' table = attention_factor x cos/sin(p x
    inv_freq'), entry by entry; the sliding layers' table is the plain one."""
    cfg = MELLUM2_12B_A2_5B
    if want is None:
        base = 500000.0 ** (-52 / 128)
        want = base / 16 * (8 / 17) + base * (9 / 17)
    assert abs(_published_inv_freq(d) - want) <= 1e-12 * want
    af = 1.2772588722239782
    cos, sin = transformer.precompute_rope(cfg, 4097)
    pcos, psin = transformer.precompute_rope(cfg, 4097, scaled=False)
    for p in (1, 17, 4096):
        # float32 angles: p * inv_freq carries ~p * inv_freq * 6e-8 of error
        tol = 2e-6 + p * want * 2.5e-7
        assert abs(float(cos[p, d]) - af * math.cos(p * want)) < tol
        assert abs(float(sin[p, d + 64]) - af * math.sin(p * want)) < tol
        plain = 500000.0 ** (-2.0 * d / 128)
        tol = 2e-6 + p * plain * 2.5e-7
        assert abs(float(pcos[p, d]) - math.cos(p * plain)) < tol
        assert abs(float(psin[p, d]) - math.sin(p * plain)) < tol
    k = dict(ref.model_key(ref_config(cfg)))
    np.testing.assert_allclose(float(ref.inv_freq(k, "full_attention")[d]),
                               want, rtol=2e-6)
    np.testing.assert_allclose(
        float(ref.inv_freq(k, "sliding_attention")[d]),
        500000.0 ** (-2.0 * d / 128), rtol=2e-6)


def test_the_toy_yarn_band_has_kept_blended_and_interpolated_pairs():
    low, high = transformer.yarn_band(CFG.rotary_dim, CFG.rope_theta,
                                      CFG.rope_scaling)
    assert 0 <= low < high < CFG.rotary_dim // 2 - 1
    assert CFG.rope_scaling[2] == 32 < 40      # the tests run past `orig`


# -- forward, prefill, contiguous decode ----------------------------------------

@pytest.mark.parametrize("cfg", [
    CFG,
    tiny_mellum_config(sliding_window=12),
    tiny_mellum_config(sliding_window=10, experts_held=4, expert_offset=4),
    tiny_mellum_config(sliding_window=200),
    tiny_mellum_config(sliding_window=7, layer_types=(
        "attention", "sliding_attention", "sliding_attention",
        "sliding_attention", "attention")),
], ids=["window10", "window12", "share-upper-half", "window-past-the-prompt",
        "full-first"])
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
    """Three blocks of 16 query rows over 40 positions: a sliding block reads
    at most 16 + 9 keys, a full block the keys up to its end."""
    monkeypatch.setattr(hybrid, "QBLOCK", 16)
    ids = _ids(40, 11)
    with jax.default_matmul_precision("highest"):
        logits, cache = transformer.prefill(CFG, params,
                                            jnp.asarray(ids)[None], 48)
    assert rel_err(logits[0], ref_logits(CFG, params, ids)) < TOL
    assert cache.k.shape == (2, 1, 48, 2, 16)
    assert cache.wk.shape == (6, 1, 48, 2, 16)
    assert isinstance(cache, hybrid.WindowCache)


def test_a_block_of_queries_never_sees_a_whole_score_matrix(monkeypatch):
    """No (H, S, S): the widest score tensor of a blocked prefill is (H,
    QBLOCK, S) on a full layer and (H, QBLOCK, QBLOCK + window - 1) on a
    sliding one."""
    monkeypatch.setattr(hybrid, "QBLOCK", 16)
    p = make_params(CFG)
    jaxpr = jax.make_jaxpr(
        lambda i: hybrid.prefill_hybrid(CFG, p, i, 64))(
            jnp.zeros((1, 64), jnp.int32))
    widest = {"full": 0, "sliding": 0}

    def walk(jp):
        for eqn in jp.eqns:
            for v in eqn.outvars:
                sh = getattr(v.aval, "shape", ())
                if len(sh) == 5 and sh[-2] == 16:        # (B, KV, rep, q, c)
                    kind = "sliding" if sh[-1] <= 16 + 9 else "full"
                    widest[kind] = max(widest[kind], sh[-1])
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jaxpr.jaxpr)
    assert widest == {"full": 64, "sliding": 25}


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


# -- prefill, then paged decode through the ring --------------------------------

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


def _check_stream(tap, slot, cfg, params, prompt, tokens, tol=TOL):
    """Every decode step's logits of a stream against the reference's full
    forward over prompt + served tokens. Returns the worst relative error."""
    seq = np.concatenate([prompt, tokens])
    want = ref_logits(cfg, params, seq)
    got = tap.of_slot(slot)
    assert len(got) >= len(tokens) - 1
    worst = 0.0
    for pos, row in got.items():
        if pos < len(seq):
            err = rel_err(row, want[pos])
            assert err < tol, (pos, err)
            worst = max(worst, err)
    return worst


def _serve(monkeypatch, cfg, params, prompt, new, bcfg=BCFG, **submit):
    tap = LogitTap(monkeypatch, cfg)
    with jax.default_matmul_precision("highest"):
        b = ContinuousBatcher(cfg, params, bcfg)
        sid = b.submit(prompt, new, **submit)
        toks = b.run()[sid]
    b.pool.check_invariants()
    return tap, b, toks


@pytest.mark.parametrize("window", [10, 12])
@pytest.mark.parametrize("plen", [3, 16, 23, 41])
def test_prefill_then_paged_decode_through_the_ring_matches_the_full_forward(
        monkeypatch, window, plen):
    """A ring of 4 pages of 4 rows: prompts shorter than it, as long as it,
    longer, and longer than two turns of it; then 70 decode steps, four more
    turns of the ring and 17 page boundaries, each step's logits against the
    reference's full forward over the whole sequence."""
    cfg = tiny_mellum_config(sliding_window=window)
    p = make_params(cfg)
    prompt = _ids(plen, plen)
    tap, b, toks = _serve(monkeypatch, cfg, p, prompt, 71, rng_seed=0)
    assert b.pool.window_pages == 4
    assert len(tap.of_slot(0)) == 70
    assert _check_stream(tap, 0, cfg, p, prompt, toks) < TOL
    # token 0 came from the prefill's last position
    want0 = ref_logits(cfg, p, prompt)[-1]
    assert want0[toks[0]] >= want0.max() - TOL * np.abs(want0).max()


def test_window_layers_hold_a_ring_however_long_the_stream_grows(
        monkeypatch, params):
    """The full group grows a page every four positions; the window group's
    pool, table and pages a slot never change, and no gather on a sliding
    layer reads more than the ring."""
    tap = LogitTap(monkeypatch, CFG)
    with jax.default_matmul_precision("highest"):
        b = ContinuousBatcher(CFG, params, BCFG)
        pool = b.pool
        assert pool.window_pool.kv.shape == (6, 3 * 4 + 1, 4, 2 * 32)
        assert pool.pool.kv.shape == (2, 121, 4, 2 * 32)
        sid = b.submit(_ids(9, 2), 100, rng_seed=1)
        seen = []
        for _ in range(100):  # 99 launches, and the read of the last one
            b.step()
            pool.check_invariants()
            seen.append((len(pool._slot_pages[0]),
                         tuple(pool.window_table[0])))
    assert sid in b.results
    assert seen[0][0] == 3 and seen[-2][0] == 27       # full pages grew
    assert {ring for _, ring in seen[:-1]} == {(1, 2, 3, 4)}
    assert seen[-1] == (0, (0, 0, 0, 0))               # freed with the slot
    rep = b.report()
    assert rep["window_rows_capacity"] == 3 * 4 * 4
    # the step's jaxpr: every gather under attn.window takes whole pages out
    # of the window pool through the (slots, 4) ring table
    cfg = CFG
    table, lengths = pool.device_tables()
    jaxpr = jax.make_jaxpr(lambda *a: hybrid.paged_decode_step_hybrid(
        cfg, params, a[0], None, jnp.zeros((8, 8), jnp.int32),
        table, lengths, jnp.zeros((3,), jnp.int32),
        window=(a[1], pool.device_window_table())))(
            pool.pool.kv, pool.window_pool.kv)
    spans = set()
    for eqn in jaxpr.jaxpr.eqns:
        if eqn.primitive.name == "gather" and \
                "attn.window" in str(eqn.source_info.name_stack) and \
                eqn.outvars[0].aval.ndim == 4:
            spans.add(eqn.outvars[0].aval.shape[:2])
    assert spans == {(3, 4)}, spans


def test_ring_positions_by_hand():
    """4 entries of 4 rows. 23 positions written (t = 22, page 5 = entry 1):
    entry 0 holds page 4, entry 1 page 5, entry 2 page 2, entry 3 page 3."""
    pos = np.asarray(paged_kv.ring_positions(jnp.asarray([23, 1, 16, 17]), 4,
                                             4))
    assert pos[0].tolist() == [16, 17, 18, 19, 20, 21, 22, 23,
                               8, 9, 10, 11, 12, 13, 14, 15]
    # one position: only row 0 of entry 0 is reached; the rest lie before 0
    assert pos[1].tolist()[:4] == [0, 1, 2, 3] and (pos[1][4:] < 0).all()
    assert pos[2].tolist() == list(range(16))
    assert pos[3].tolist()[:4] == [16, 17, 18, 19]
    valid = np.asarray(paged_kv.window_valid(
        jnp.asarray(pos), jnp.asarray([23, 1, 16, 17]), 10))
    # t = 22 attends 13 .. 22: by position, wherever in the ring they lie
    assert sorted(pos[0][valid[0]].tolist()) == list(range(13, 23))
    assert pos[1][valid[1]].tolist() == [0]
    assert sorted(pos[2][valid[2]].tolist()) == list(range(6, 16))
    assert sorted(pos[3][valid[3]].tolist()) == list(range(7, 17))


def test_evict_then_readmit_reproduces_the_undisturbed_stream(monkeypatch,
                                                             params):
    """Past a ring turn: the payload is the full layers' rows and the ring's,
    in position order, and readmission puts both back where they were."""
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
        assert n == 21 + 14 and st.resume["k"].shape[:2] == (2, n)
        # the ring holds whole pages up to the newest: positions 20 .. 34
        assert b.pool.window_ring_start(n) == 20
        assert st.resume["wk"].shape[:2] == (6, n - 20)
        b.pool.check_invariants()
        got = b.run()[sid]
        assert b.report()["evicted"] == 1 and other in b.results
    np.testing.assert_array_equal(got, want)
    a, c = tap0.of_slot(0), tap1.of_slot(1)
    assert len(c) == len(a) == 39
    for pos, row in a.items():        # byte copies out and back: the same
        np.testing.assert_allclose(c[pos], row, rtol=0, atol=1e-7)


def test_out_of_pages_evicts_and_readmits_inside_the_batcher(params):
    """The full group runs out (the window group cannot): the youngest stream
    is evicted, readmitted when pages free up, and every stream still holds
    the tokens it holds when served alone."""
    tight = BatchingConfig(page_size=4, num_pages=26, max_slots=3,
                           pages_per_slot=40)
    prompts = [_ids(n, n) for n in (30, 22, 18)]
    b = ContinuousBatcher(CFG, params, BCFG)
    sids = [b.submit(p, 24, rng_seed=i) for i, p in enumerate(prompts)]
    want = b.run()
    t = ContinuousBatcher(CFG, params, tight)
    tsids = [t.submit(p, 24, rng_seed=i) for i, p in enumerate(prompts)]
    got = t.run()
    t.pool.check_invariants()
    assert t.report()["evicted"] >= 1
    for a, c in zip(sids, tsids):
        np.testing.assert_array_equal(got[c], want[a])


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
    assert _check_stream(tap, 1, CFG, params, prompt, res[sid]) < TOL
    assert third in res


def test_batcher_tokens_equal_generate(params):
    b = ContinuousBatcher(CFG, params, BCFG)
    prompts = [_ids(n, n) for n in (5, 8, 13, 26)]
    temps = [0.0, 0.7, 0.0, 0.7]
    sids = [b.submit(p, 20, temperature=t, rng_seed=i)
            for i, (p, t) in enumerate(zip(prompts, temps))]
    res = b.run()
    for i, (sid, p, t) in enumerate(zip(sids, prompts, temps)):
        want = np.asarray(generate(CFG, params, p[None], 20, temperature=t,
                                   rng_key=jax.random.key(i)))[0]
        np.testing.assert_array_equal(res[sid], want)
    rep = b.report()
    assert rep["state_bytes"] == 0 and rep["window_rows_live"] == 0
    assert rep["routed_local"] == rep["routed_assignments"] > 0
    assert np.asarray(rep["expert_tokens"]).shape == (8, 8)


def test_the_window_counters_count_rows_inside_a_window(params):
    b = ContinuousBatcher(CFG, params, BCFG)
    b.submit(_ids(6, 1), 40, rng_seed=0)
    b.submit(_ids(30, 2), 40, rng_seed=1)
    b.step()
    rep = b.report()
    # lengths 7 and 31 after the step: min(length, window 10) each
    assert rep["window_rows_live"] == 7 + 10
    assert rep["window_rows_capacity"] == 48


# -- the named mistakes ---------------------------------------------------------

def _swapped(kind_from, kind_to, keep=1):
    """Every layer of one kind served as the other but the last ``keep``: a
    stack with no layer of a kind has no pool of that group to serve from."""
    def make(monkeypatch):
        mine = [i for i, t in enumerate(CFG.layer_types) if t == kind_from]
        return dataclasses.replace(CFG, layer_types=tuple(
            kind_to if i in mine[:-keep] else t
            for i, t in enumerate(CFG.layer_types)))
    return make


def _ring_place_mask(monkeypatch):
    """A ring row masked by where it lies instead of the position it holds."""
    def by_place(lengths, entries, page_size):
        rows = jnp.arange(entries * page_size, dtype=jnp.int32)
        return jnp.broadcast_to(rows, (lengths.shape[0], rows.shape[0]))

    monkeypatch.setattr(paged_kv, "ring_positions", by_place)
    return CFG


def _window_one_short(monkeypatch):
    return dataclasses.replace(CFG, sliding_window=9)


def _yarn_everywhere(monkeypatch):
    real = transformer.precompute_rope
    monkeypatch.setattr(hybrid, "precompute_rope",
                        lambda cfg, n, scaled=True: real(cfg, n))
    return CFG


def _yarn_nowhere(monkeypatch):
    return dataclasses.replace(CFG, rope_scaling=None)


def _no_attention_factor(monkeypatch):
    return dataclasses.replace(CFG, rope_scaling=CFG.rope_scaling[:5]
                               + (1.0,))


def _bf16_ring(monkeypatch):
    real = paged_kv.write_rows

    def rounded(pool, layer, table, lengths, k, v, ring=False):
        if ring:
            k = k.astype(jnp.bfloat16).astype(k.dtype)
            v = v.astype(jnp.bfloat16).astype(v.dtype)
        return real(pool, layer, table, lengths, k, v, ring)

    monkeypatch.setattr(paged_kv, "write_rows", rounded)
    return CFG


def _tied_head(monkeypatch):
    real = hybrid.unembed_hybrid
    monkeypatch.setattr(
        hybrid, "unembed_hybrid",
        lambda cfg, p, h: real(dataclasses.replace(
            cfg, tie_word_embeddings=True), p, h))
    return CFG


MISTAKES = {
    "window-layers-served-as-full": _swapped("sliding_attention",
                                             "attention"),
    "a-full-layer-served-as-window": _swapped("attention",
                                              "sliding_attention"),
    "ring-row-masked-by-its-place": _ring_place_mask,
    "window-one-key-short": _window_one_short,
    "yarn-on-sliding-layers-too": _yarn_everywhere,
    "yarn-on-no-layer": _yarn_nowhere,
    "no-attention-factor": _no_attention_factor,
    "bf16-keys-in-the-ring": _bf16_ring,
    "tied-head": _tied_head,
}


def _swap_params(cfg, params):
    """The same weights for a config whose layers changed kind: the stacks
    re-dealt in layer order."""
    if cfg.layer_types == CFG.layer_types:
        return params
    rows, seen = [], {"attention": 0, "sliding_attention": 0}
    for t in CFG.layer_types:
        stack = params["window" if t == "sliding_attention" else "attn"]
        rows.append({k: v[seen[t]] for k, v in stack.items()})
        seen[t] += 1
    out = {k: v for k, v in params.items() if k not in ("attn", "window")}
    for name, kind in (("attn", "attention"),
                       ("window", "sliding_attention")):
        mine = [r for r, t in zip(rows, cfg.layer_types) if t == kind]
        if mine:
            out[name] = {k: jnp.stack([r[k] for r in mine]) for k in mine[0]}
        elif name == "attn":
            out[name] = {k: v[:0] for k, v in params["attn"].items()}
    return out


@pytest.mark.parametrize("name", sorted(MISTAKES))
def test_a_named_mistake_fails(monkeypatch, params, name):
    """The comparison above is tight enough: the same prefill-then-decode
    through the batcher, with one thing wrong, misses the reference by at
    least twenty tolerances at some step."""
    cfg = MISTAKES[name](monkeypatch)
    p = _swap_params(cfg, params)
    prompt = _ids(23, 5)
    tap = LogitTap(monkeypatch, cfg)
    with jax.default_matmul_precision("highest"):
        b = ContinuousBatcher(cfg, p, BCFG)
        sid = b.submit(prompt, 40, rng_seed=0)
        toks = b.run()[sid]
    seq = np.concatenate([prompt, toks])
    want = ref_logits(CFG, params, seq)
    worst = max(rel_err(row, want[pos]) for pos, row in
                tap.of_slot(0).items() if pos < len(seq))
    assert worst > 20 * TOL, worst


# -- the share ----------------------------------------------------------------

@pytest.mark.parametrize("tokens", [7, 300, 301, 304])
def test_dense_and_grouped_paths_agree_with_no_shared_expert(tokens):
    """7: the dense path. 300 and 301: the grouped path over tokens padded
    to 304 (``moe.GROUPED_TOKEN_MULTIPLE``: a v5e compiles the whole walk
    wrongly at a count that is no multiple of 8), the padding routed to no
    expert and sliced off; 304: no padding."""
    cfg = tiny_mellum_config(experts_held=4, expert_offset=2)
    mp = make_params(tiny_mellum_config())["moe"][0]
    assert "shared_gate" not in mp
    mp = {**mp, **{k: mp[k][2:6] for k in ("w_gate", "w_up", "w_down")}}
    u = jax.random.normal(jax.random.key(6), (tokens, cfg.hidden_size))
    with jax.default_matmul_precision("highest"):
        idx, w = moe.route(cfg, mp["router"], u)
        dense = moe._experts_dense(cfg, mp, u, idx, w)
        grouped = moe._experts_grouped(cfg, mp, u, idx, w)
        out, counts = moe.moe_layer(cfg, mp, u)
    assert rel_err(grouped, np.asarray(dense)) < TOL
    np.testing.assert_array_equal(
        np.asarray(out), np.asarray(dense if tokens <= moe.DENSE_MAX_TOKENS
                                    else grouped))
    local = np.asarray(idx) - 2
    want = np.bincount(local[(local >= 0) & (local < 4)], minlength=4)
    np.testing.assert_array_equal(np.asarray(counts), want)


def test_renormalised_softmax_over_all_equals_softmax_over_the_chosen():
    """``norm_topk_prob``: softmax over all 64 logits, taken at the top 8 and
    renormalised, is the softmax over those 8 — what ``moe.route`` computes
    and the reference spells out."""
    cfg = tiny_mellum_config()
    r = jax.random.normal(jax.random.key(3), (29, cfg.hidden_size))
    w = jax.random.normal(jax.random.key(4), (cfg.hidden_size, 8)) * 0.5
    idx, got = moe.route(cfg, w, r)
    probs = jax.nn.softmax(r @ w, axis=-1)
    taken = jnp.take_along_axis(probs, idx, axis=-1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(
        taken / taken.sum(-1, keepdims=True)), rtol=2e-6)


# -- what refuses the family, by name --------------------------------------------

def _refusals():
    from edgellm_tpu.parallel import SplitConfig, SplitRuntime, \
        make_stage_mesh
    from edgellm_tpu.serve import disagg, recovery, speculative

    p = None     # no refusal below gets as far as touching parameters
    pc = PrefixCacheConfig()
    yield "prefix-sharing", lambda: PagedKVCache(
        CFG, num_pages=9, page_size=4, max_slots=2, pages_per_slot=4,
        prefix_cache=pc)
    yield "quantized-kv-tier", lambda: PagedKVCache(
        CFG, num_pages=9, page_size=4, max_slots=2, pages_per_slot=4,
        kv_codec="int8_per_channel")
    yield "bookkeeping-only-allocator", lambda: PagedKVCache(
        CFG, num_pages=9, page_size=4, max_slots=2, pages_per_slot=4,
        materialize=False)
    yield "checkpoint-dir", lambda: ContinuousBatcher(
        CFG, p, dataclasses.replace(BCFG, checkpoint_dir="/nonexistent"))
    yield "split-runtime-batcher", lambda: ContinuousBatcher(
        CFG, p, BCFG, split_runtime=object(), placed_params=object())
    yield "split-runtime", lambda: SplitRuntime(
        CFG, SplitConfig(cuts=(3,), hop_codecs=("fp16",)),
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
        CFG, num_pages=9, page_size=4, max_slots=2,
        pages_per_slot=4).state_dict()
    yield "survivable-generate", lambda: generate(
        CFG, p, _ids(4)[None], 2,
        recovery=types.SimpleNamespace())
    yield "decode-step-hook", lambda: transformer.decode_step(
        CFG, p, None, None, boundary_fn=lambda l, h: h)


REFUSALS = dict(_refusals())      # the calls are lambdas: nothing runs yet


@pytest.mark.parametrize("name", sorted(REFUSALS))
def test_a_mechanism_that_reads_full_history_refuses_the_family_by_name(name):
    """One worded refusal (``hybrid.refuse_window_ring``), raised where the
    mechanism is built or entered, before any weights are touched; the
    recurrent-state refusal still refuses exactly the family it refused."""
    with pytest.raises(WindowRingUnsupported) as e:
        REFUSALS[name]()
    msg = str(e.value)
    assert "'mellum'" in msg and "sliding-window layers keep a ring" in msg
    assert "no fallback" in msg and not isinstance(
        e.value, RecurrentStateUnsupported)


def test_the_two_refusals_refuse_their_own_family_only():
    granite = tiny_hybrid_config()
    hybrid.refuse_recurrent_state(CFG, "x")          # not mellum's
    hybrid.refuse_window_ring(granite, "x")          # not granite's
    with pytest.raises(RecurrentStateUnsupported, match="Mamba-2"):
        hybrid.refuse_beyond_kv_rows(granite, "x")
    with pytest.raises(WindowRingUnsupported, match="ring"):
        hybrid.refuse_beyond_kv_rows(CFG, "x")
    for name in ("tiny-qwen2", "tiny-neox", "tiny-llama"):
        hybrid.refuse_beyond_kv_rows(PRESETS[name], "x")
    assert granite.recurrent_state and granite.is_hybrid \
        and not granite.window_layers
    assert CFG.is_hybrid and not CFG.recurrent_state and CFG.window_layers


# -- hf_loader -------------------------------------------------------------------

def _hf(**over):
    import json

    with open(os.path.join(ROOT, "benchmark", "configs",
                           "mellum2-12b-a2.5b-pp4.json")) as f:
        published = json.load(f)
    return types.SimpleNamespace(**{**published, **over})


def test_hf_loader_maps_the_published_mellum_config():
    from edgellm_tpu.models.hf_loader import config_from_hf

    full = _hf(num_hidden_layers=28, layer_types=(
        ["sliding_attention"] * 3 + ["full_attention"]) * 7,
        mlp_layer_types=["sparse"] * 28)
    assert config_from_hf(full) == MELLUM2_12B_A2_5B
    cut = config_from_hf(_hf())
    assert cut.num_layers == 8 and cut.kv_layers == 2 \
        and cut.window_layers == 6 and cut.head_dim == 128


@pytest.mark.parametrize("over,match", [
    (dict(mlp_layer_types=["sparse"] * 7 + ["dense"]), "dense"),
    (dict(norm_topk_prob=False), "norm_topk_prob"),
    (dict(rope_parameters={"full_attention": {"rope_type": "default",
                                              "rope_theta": 5e5},
                           "sliding_attention": {"rope_type": "default",
                                                 "rope_theta": 5e5}}),
     "yarn"),
])
def test_hf_loader_refuses_a_mellum_it_does_not_know(over, match):
    from edgellm_tpu.models.hf_loader import config_from_hf

    with pytest.raises(ValueError, match=match):
        config_from_hf(_hf(**over))


def test_hf_loader_still_refuses_a_sliding_qwen2_by_name():
    from edgellm_tpu.models.hf_loader import config_from_hf

    q = types.SimpleNamespace(
        model_type="qwen2", vocab_size=256, hidden_size=64,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        intermediate_size=128, max_position_embeddings=512,
        rms_norm_eps=1e-6, rope_theta=1e6, tie_word_embeddings=True,
        use_sliding_window=True)
    with pytest.raises(ValueError, match="use_sliding_window"):
        config_from_hf(q)
    q.use_sliding_window = False
    assert config_from_hf(q).family == "qwen2"


# -- the normal path ------------------------------------------------------------

def test_run_py_serves_the_family_through_the_front_and_the_batcher(tmp_path,
                                                                    capsys):
    """``run.py`` serve -> ``ServeFront`` -> ``ContinuousBatcher`` -> the two
    page groups -> the paged step, by the preset's name and nothing else:
    prompts of 30 tokens are past the toy's window of 20."""
    import json

    from edgellm_tpu.run import main

    params = {"experiment": "serve",
              "serving": {"admission": {"max_queue_depth": 8},
                          "capacity_round": 16,
                          "soak": {"n_requests": 3, "arrival_rate": 2.0,
                                   "prompt_len": 30, "max_new_tokens": 12}},
              "batching": {"page_size": 4, "num_pages": 41, "max_slots": 2,
                           "pages_per_slot": 12}}
    assert main(["--params", json.dumps(params), "--model", "tiny-mellum",
                 "--output-dir", str(tmp_path / "out")]) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["outcomes"] == {"completed": 3} and line["mode"] == "batched"
    rep = json.load(open(tmp_path / "out" / "serve_report.json"))
    assert [len(t) for t in rep["tokens"]] == [12, 12, 12]
