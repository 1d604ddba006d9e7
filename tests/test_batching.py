"""Continuous batching + paged KV: allocator invariants, ragged parity,
mid-flight admit/evict, jit-miss-free steady state, checkpoint/restore.

The load-bearing claim everywhere: a stream's tokens through the paged
ragged step are BIT-IDENTICAL to running it alone through ``generate``
(per-step sampling keys depend only on (seed, step index); masked padding
contributes exactly 0 to softmax; pages store the same post-rotary values
the contiguous cache stores).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from edgellm_tpu.models import init_params, tiny_config
from edgellm_tpu.models.flash_attention import decode_attention
from edgellm_tpu.models import paged_kv
from edgellm_tpu.models.paged_kv import (OutOfPages, OutOfSlots, PagedKVCache,
                                         PagePool, init_pool, init_quant_pool,
                                         paged_decode_attention,
                                         paged_decode_step)
from edgellm_tpu.serve.batching import (BatchingConfig, ContinuousBatcher,
                                        _batched_sample,
                                        batched_step_cache_size)
from edgellm_tpu.serve.decode import _sample, generate
from edgellm_tpu.serve.recovery import CheckpointError

CFG = tiny_config("qwen2", num_layers=4, hidden_size=32, num_heads=4,
                  vocab_size=128)

# one shared geometry so every batcher test reuses the same compiled ragged
# step: span 32 = 4 pages x 8, the capacity generate() parity calls use too
BCFG = BatchingConfig(page_size=8, num_pages=17, max_slots=4,
                      pages_per_slot=4)


@pytest.fixture(scope="module")
def params():
    return init_params(CFG, jax.random.key(1))


def _prompt(n, seed=0):
    return np.random.default_rng(seed).integers(
        1, CFG.vocab_size, size=n).astype(np.int32)


def _solo(params, prompt, max_new, temp=0.0, seed=0):
    out = generate(CFG, params, jnp.asarray(prompt)[None], max_new,
                   capacity=BCFG.span, temperature=temp,
                   rng_key=jax.random.key(seed))
    return np.asarray(out)[0]


# ---------------------------------------------------------------------------
# allocator
# ---------------------------------------------------------------------------


def _bookkeeping(num_pages=9, page_size=4, max_slots=3, pages_per_slot=3):
    return PagedKVCache(CFG, num_pages=num_pages, page_size=page_size,
                        max_slots=max_slots, pages_per_slot=pages_per_slot,
                        materialize=False)


def test_pool_alloc_free_invariants():
    pool = _bookkeeping()
    s0 = pool.alloc_slot()
    pool.ensure(s0, 5)            # 2 pages
    pool.check_invariants()
    assert len(pool._slot_pages[s0]) == 2
    assert pool.num_free_pages == 8 - 2
    s1 = pool.alloc_slot()
    pool.ensure(s1, 12)           # 3 pages (the slot max)
    pool.check_invariants()
    pool.free_slot(s0)
    pool.check_invariants()
    assert pool.num_free_pages == 8 - 3
    # ensure() must allocate nothing when it cannot cover the growth
    s2 = pool.alloc_slot()
    pool.ensure(s2, 12)
    s3 = pool.alloc_slot()
    free_before = pool.num_free_pages
    with pytest.raises(OutOfPages):
        pool.ensure(s3, 12)       # needs 3, only 2 free
    assert pool.num_free_pages == free_before
    pool.check_invariants()
    with pytest.raises(OutOfSlots):
        pool.alloc_slot()
    with pytest.raises(ValueError):
        pool.ensure(s3, pool.span + 1)


def test_trash_page_never_allocated():
    pool = _bookkeeping()
    slots = [pool.alloc_slot() for _ in range(3)]
    for s in slots:
        pool.ensure(s, 8)
        assert 0 not in pool._slot_pages[s]
    pool.check_invariants()


def test_bookkeeping_only_mode_guards():
    pool = _bookkeeping()
    assert pool.pool is None
    for call in (lambda: pool.adopt(0, None, None, 1),
                 lambda: pool.gather_slot(0),
                 pool.defrag, pool.state_dict,
                 lambda: pool.load_state_dict({})):
        with pytest.raises(ValueError, match="materialize=False"):
            call()


def test_adopt_gather_roundtrip():
    pool = PagedKVCache(CFG, num_pages=9, page_size=4, max_slots=2,
                        pages_per_slot=3)
    rng = np.random.default_rng(3)
    n = 10
    k = rng.standard_normal(
        (CFG.num_layers, n, CFG.num_kv_heads, CFG.head_dim)).astype(np.float32)
    v = rng.standard_normal(k.shape).astype(np.float32)
    slot = pool.alloc_slot()
    pool.adopt(slot, jnp.asarray(k), jnp.asarray(v), n)
    pool.check_invariants()
    back = pool.gather_slot(slot)
    assert int(back["length"]) == n
    np.testing.assert_array_equal(back["k"], k)
    np.testing.assert_array_equal(back["v"], v)
    # the stored leaf is (L, P, ps, 2*KV*hd), a row its K lanes then its V
    # lanes; a row of layer l landed in layer l at its page and offset and
    # NOWHERE else: the (L, P, ps, KV, hd) oracle, written with the indices
    # the flat (layer, page, row) index replaces
    assert len(pool.pool) == 1
    assert pool.pool.kv.shape == (CFG.num_layers, 9, 4,
                                  2 * CFG.num_kv_heads * CFG.head_dim)
    assert (pool.pool.num_pages, pool.pool.page_size) == (9, 4)
    assert pool.pool.k_lanes == CFG.num_kv_heads * CFG.head_dim
    pos = np.arange(n)
    pages, offs = pool.page_table[slot, pos // 4], pos % 4
    for leaf, rows in zip(paged_kv.split_kv(pool.pool.kv), (k, v)):
        want = np.zeros((CFG.num_layers, 9, 4, CFG.num_kv_heads,
                         CFG.head_dim), np.float32)
        want[:, pages, offs] = rows
        np.testing.assert_array_equal(
            np.asarray(leaf).reshape(want.shape), want)


def test_parent_shaped_state_dict_loads():
    # a state_dict written before the row was stored lane-dense holds
    # (L, P, ps, KV, hd) leaves; it loads by a reshape, and state_dict()
    # still writes that form
    kw = dict(num_pages=9, page_size=4, max_slots=2, pages_per_slot=3)
    src = PagedKVCache(CFG, **kw)
    rng = np.random.default_rng(5)
    k = rng.standard_normal((CFG.num_layers, 7, CFG.num_kv_heads,
                             CFG.head_dim)).astype(np.float32)
    slot = src.alloc_slot()
    src.adopt(slot, jnp.asarray(k), jnp.asarray(-k), 7)
    state = src.state_dict()
    five_d = (CFG.num_layers, 9, 4, CFG.num_kv_heads, CFG.head_dim)
    assert state["k"].shape == state["v"].shape == five_d
    pos = np.arange(7)
    np.testing.assert_array_equal(
        state["k"][:, src.page_table[slot, pos // 4], pos % 4], k)
    twin = PagedKVCache(CFG, **kw)
    twin.load_state_dict(state)
    twin.check_invariants()
    assert twin.pool.kv.shape == src.pool.kv.shape
    np.testing.assert_array_equal(np.asarray(twin.pool.kv),
                                  np.asarray(src.pool.kv))
    back = twin.gather_slot(slot)
    np.testing.assert_array_equal(back["k"], k)
    np.testing.assert_array_equal(back["v"], -k)
    with pytest.raises(ValueError, match="shape mismatch"):
        twin.load_state_dict({**state, "k": state["k"][:, :-1]})


def test_defrag_preserves_content_and_compacts():
    pool = PagedKVCache(CFG, num_pages=13, page_size=4, max_slots=3,
                        pages_per_slot=4)
    rng = np.random.default_rng(5)
    shapes = {}
    for n in (7, 9, 6):
        k = rng.standard_normal((CFG.num_layers, n, CFG.num_kv_heads,
                                 CFG.head_dim)).astype(np.float32)
        v = rng.standard_normal(k.shape).astype(np.float32)
        slot = pool.alloc_slot()
        pool.adopt(slot, jnp.asarray(k), jnp.asarray(v), n)
        shapes[slot] = (k, v)
    pool.free_slot(1)             # hole in the middle of the pool
    del shapes[1]
    moved = pool.defrag()
    pool.check_invariants()
    assert moved > 0
    # allocated pages are now the low contiguous range, trash page fixed
    owned = sorted(p for pages in pool._slot_pages for p in pages)
    assert owned == list(range(1, len(owned) + 1))
    for slot, (k, v) in shapes.items():
        back = pool.gather_slot(slot)
        np.testing.assert_array_equal(back["k"], k)
        np.testing.assert_array_equal(back["v"], v)


def test_defrag_churn_page_moves_up_past_free_page():
    # regression: alloc/grow/free churn can leave an owned page whose
    # compacted destination is a HIGHER id currently on the free list
    # (here slot pages [[3], [1, 4]] with page 2 free: page 1's destination
    # is 2). The old->new map is then not invertible, and a naive inversion
    # gathered the free page's garbage into the destination — silently,
    # since check_invariants() only sees bookkeeping.
    # (Pages of this size go out in runs of two, [1, 2] [3, 4] and a short
    # [5], the rest of a run held ahead for the slot that broke it and taken
    # back, the oldest holder's first, once no other page is free.)
    pool = PagedKVCache(CFG, num_pages=6, page_size=4, max_slots=3,
                        pages_per_slot=2)
    assert pool.run_pages == 2
    rng = np.random.default_rng(11)

    def kv(n):
        k = rng.standard_normal((CFG.num_layers, n, CFG.num_kv_heads,
                                 CFG.head_dim)).astype(np.float32)
        return k, rng.standard_normal(k.shape).astype(np.float32)

    def fill(slot, n):
        k, v = kv(n)
        pool.adopt(slot, jnp.asarray(k), jnp.asarray(v), n)
        return k, v

    s0, s1, s2 = (pool.alloc_slot() for _ in range(3))
    fill(s1, 4)                       # run [1, 2]: page [1], 2 held ahead
    k0, v0 = fill(s0, 4)              # run [3, 4]: page [3], 4 held ahead
    fill(s2, 4)                       # no whole run left: page [5]
    fill(s2, 8)                       # nothing free but what is held: 2
    k1, v1 = fill(s1, 8)              # its own 2 is gone: s0's 4 -> [1, 4]
    assert pool._slot_pages[s2] == [5, 2]
    pool.free_slot(s2)                # free: 2, 5
    assert pool._slot_pages[s0] == [3]
    assert pool._slot_pages[s1] == [1, 4]
    pool.check_invariants()

    moved = pool.defrag()
    pool.check_invariants()
    assert moved > 0
    owned = sorted(p for pages in pool._slot_pages for p in pages)
    assert owned == list(range(1, len(owned) + 1))
    for slot, (k, v) in ((s0, (k0, v0)), (s1, (k1, v1))):
        back = pool.gather_slot(slot)
        np.testing.assert_array_equal(back["k"], k)
        np.testing.assert_array_equal(back["v"], v)


# ---------------------------------------------------------------------------
# ragged step parity
# ---------------------------------------------------------------------------


def test_ragged_mixed_lengths_bit_identical_to_generate(params):
    bat = ContinuousBatcher(CFG, params, BCFG)
    streams = [  # mixed prompt lengths, remaining tokens, temperatures
        dict(prompt=_prompt(5, 1), max_new=6, temp=0.0, seed=11),
        dict(prompt=_prompt(9, 2), max_new=4, temp=0.7, seed=22),
        dict(prompt=_prompt(13, 3), max_new=8, temp=1.1, seed=33),
    ]
    sids = [bat.submit(s["prompt"], s["max_new"], temperature=s["temp"],
                       rng_seed=s["seed"]) for s in streams]
    results = bat.run()
    for sid, s in zip(sids, streams):
        np.testing.assert_array_equal(
            results[sid], _solo(params, s["prompt"], s["max_new"],
                                s["temp"], s["seed"]))
    rep = bat.report()
    assert rep["finished"] == 3 and rep["evicted"] == 0
    # at most the warm-up compiles: the step, and the merge ahead of it that
    # feeds a slot the token still in flight
    assert rep["jit_misses"] <= 2


@pytest.mark.parametrize("read", [paged_kv.PAGE_GATHER, paged_kv.PAGE_WALK])
def test_report_counts_the_pages_a_page_walk_fetches(params, read):
    """``report()``'s two running sums, at a toy size: before every step, the
    pages under each slot's length with the row the step writes (an idle
    slot's one trash page) against slots x table entries; both stay 0 where
    the step was built on the page gather, which is what a CPU builds. The
    counting is the host's, so the walk's is checked by naming it the read
    of a batcher whose step still gathers."""
    bat = ContinuousBatcher(CFG, params, BCFG)
    assert bat.decode_read == paged_kv.PAGE_GATHER      # a cpu: the oracle
    assert bat.report()["decode_read"] == paged_kv.PAGE_GATHER
    bat.decode_read = read
    bat.submit(_prompt(7, 1), 12)      # 7 -> 18 positions: pages 1 -> 3
    bat.submit(_prompt(16, 2), 3)      # starts its third page at once
    walked = spanned = 0
    while True:
        before = bat.report()
        if not bat.step():
            break
        # the lengths the step was launched with: each running stream's
        # cache before this step's token, 0 for the two idle slots. The last
        # call launches nothing: it reads the step in flight
        after = bat.report()
        launched = after["steps"] - before["steps"]
        spanned += launched * BCFG.max_slots * BCFG.pages_per_slot
        assert (after["attend_pages_spanned"]
                - before["attend_pages_spanned"]) == (
                    launched * BCFG.max_slots * BCFG.pages_per_slot
                    if read == paged_kv.PAGE_WALK else 0)
        walked += after["attend_pages_walked"] - before["attend_pages_walked"]
    rep = bat.report()
    assert rep["finished"] == 2
    if read == paged_kv.PAGE_GATHER:
        assert rep["attend_pages_walked"] == rep["attend_pages_spanned"] == 0
        return
    # stream 1 decodes at cache lengths 7..17 (11 steps: its first token is
    # the prefill's), stream 2 at 16, 17 (2 steps); the other slots idle
    steps = rep["steps"]
    assert steps == 11
    lens1 = list(range(7, 18))
    # its slot is idle once its last token is launched: kept for one more
    # step, until that token is read, and handed to the step as a free one
    lens2 = [16, 17] + [0] * 9
    want = sum(n // 8 + 1 for n in lens1) + sum(n // 8 + 1 for n in lens2) \
        + 2 * steps                      # two slots never held a stream
    assert rep["attend_pages_walked"] == walked == want
    assert rep["attend_pages_spanned"] == spanned == steps * 16


def test_steady_state_is_jit_miss_free(params):
    # warm the geometry's executable...
    warm = ContinuousBatcher(CFG, params, BCFG)
    warm.submit(_prompt(4), 2)
    warm.run()
    # ...then a FRESH batcher with different streams never compiles again:
    # admit/evict/fill states are traced inputs, not trace constants
    bat = ContinuousBatcher(CFG, params, BCFG)
    before = batched_step_cache_size()
    for i, (n, m) in enumerate([(3, 5), (11, 3), (7, 7), (6, 4), (9, 2)]):
        bat.submit(_prompt(n, seed=i), m, temperature=0.5 * i, rng_seed=i)
    bat.run()
    assert batched_step_cache_size() == before
    assert bat.report()["jit_misses"] == 0


def test_eviction_under_pressure_still_bit_identical(params):
    # pool too small for all three streams at once: the youngest evicts
    # mid-flight, re-queues with its gathered prefix, and STILL matches solo
    tight = BatchingConfig(page_size=8, num_pages=8, max_slots=4,
                           pages_per_slot=4)  # 7 allocatable pages
    bat = ContinuousBatcher(CFG, params, tight)
    streams = [
        dict(prompt=_prompt(15, 7), max_new=8, temp=0.0, seed=1),
        dict(prompt=_prompt(14, 8), max_new=8, temp=0.9, seed=2),
        dict(prompt=_prompt(13, 9), max_new=8, temp=0.0, seed=3),
    ]
    sids = [bat.submit(s["prompt"], s["max_new"], temperature=s["temp"],
                       rng_seed=s["seed"]) for s in streams]
    results = bat.run()
    assert bat.report()["evicted"] > 0
    for sid, s in zip(sids, streams):
        np.testing.assert_array_equal(
            results[sid], _solo(params, s["prompt"], s["max_new"],
                                s["temp"], s["seed"]))


def test_explicit_midflight_evict_resumes_identically(params):
    bat = ContinuousBatcher(CFG, params, BCFG)
    p = _prompt(6, 4)
    sid = bat.submit(p, 8, temperature=0.8, rng_seed=9)
    for _ in range(3):
        bat.step()
    bat.evict(sid)
    assert bat._streams[sid].status == "waiting"
    results = bat.run()
    np.testing.assert_array_equal(results[sid],
                                  _solo(params, p, 8, 0.8, 9))
    assert bat._streams[sid].evictions == 1


def test_max_new_tokens_one_is_prefill_only(params):
    bat = ContinuousBatcher(CFG, params, BCFG)
    p = _prompt(5, 6)
    sid = bat.submit(p, 1)
    results = bat.run()
    np.testing.assert_array_equal(results[sid], _solo(params, p, 1))


def test_run_raises_when_no_stream_can_fit(params):
    # span covers the request, but the pool never has enough free pages
    wedged = BatchingConfig(page_size=8, num_pages=3, max_slots=2,
                            pages_per_slot=4)  # 2 allocatable pages
    bat = ContinuousBatcher(CFG, params, wedged)
    bat.submit(_prompt(20), 4)    # needs 3 pages just to admit
    with pytest.raises(OutOfPages):
        bat.run()


def test_submit_validation(params):
    bat = ContinuousBatcher(CFG, params, BCFG)
    with pytest.raises(ValueError):
        bat.submit(np.zeros((0,), np.int32), 4)
    with pytest.raises(ValueError):
        bat.submit(_prompt(4), 0)
    with pytest.raises(ValueError):
        bat.submit(_prompt(4), 4, temperature=-0.1)
    with pytest.raises(ValueError):
        bat.submit(_prompt(30), 8)  # 30 + 8 - 1 > span 32


def test_trash_page_stays_finite(params):
    bat = ContinuousBatcher(CFG, params, BCFG)
    bat.submit(_prompt(5), 6)     # slots 1-3 inactive: they write page 0
    bat.run()
    assert np.isfinite(np.asarray(bat.pool.pool.kv[:, 0])).all()


# ---------------------------------------------------------------------------
# checkpoint / restore
# ---------------------------------------------------------------------------


def test_checkpoint_restore_across_pool_geometry(params, tmp_path):
    p = _prompt(7, 10)
    bat = ContinuousBatcher(CFG, params, BCFG)
    sid = bat.submit(p, 8, temperature=0.6, rng_seed=42)
    for _ in range(4):
        bat.step()
    path = bat.checkpoint_stream(sid, str(tmp_path / "s.ckpt"))
    # restore into a DIFFERENT pool geometry: the payload is the contiguous
    # prefix, so any span that covers it works
    other = ContinuousBatcher(
        CFG, params, BatchingConfig(page_size=4, num_pages=17, max_slots=2,
                                    pages_per_slot=8))
    rid = other.restore_stream(path)
    results = other.run()
    np.testing.assert_array_equal(results[rid],
                                  _solo(params, p, 8, 0.6, 42))


def test_checkpoint_refuses_other_model(params, tmp_path):
    bat = ContinuousBatcher(CFG, params, BCFG)
    sid = bat.submit(_prompt(5), 4)
    bat.step()
    path = bat.checkpoint_stream(sid, str(tmp_path / "s.ckpt"))
    other_cfg = tiny_config("qwen2", num_layers=2, hidden_size=32,
                            num_heads=4, vocab_size=128)
    other = ContinuousBatcher(other_cfg, init_params(other_cfg,
                                                     jax.random.key(0)), BCFG)
    with pytest.raises(CheckpointError, match="model"):
        other.restore_stream(path)


# ---------------------------------------------------------------------------
# the paged attend: one page gather, then decode_attention
# ---------------------------------------------------------------------------


def _stored(pages, layer=0, layers=1):
    """One layer's (num_pages, page_size, KV, hd) K or V as layer ``layer``
    of a stored (layers, num_pages, page_size, KV*hd) leaf whose other
    layers hold garbage no attend of ``layer`` may read."""
    pn, ps = pages.shape[:2]
    leaf = np.full((layers, pn, ps, int(np.prod(pages.shape[2:]))), 1e4,
                   np.float32)
    leaf[layer] = np.asarray(pages, np.float32).reshape(pn, ps, -1)
    return jnp.asarray(leaf, pages.dtype)


def _kv_pool(k_leaf, v_leaf):
    """The fp pool of :func:`_stored` K and V leaves: a row its K lanes, then
    its V lanes, in the one leaf."""
    return PagePool(paged_kv.join_kv(k_leaf, v_leaf))


@pytest.mark.parametrize("layer,layers", [(0, 1), (1, 3), (2, 3)])
def test_paged_attention_matches_contiguous(layer, layers):
    # the page gather at a layer index, then the attend over the rows as
    # they lie, must agree with decode_attention over each slot's contiguous
    # (B, span, KV, hd) view, and be invariant to garbage beyond length
    rng = np.random.default_rng(11)
    b, h, kv, hd, pn, ps, pps = 3, 4, 2, 8, 7, 4, 2
    span = pps * ps
    q = jnp.asarray(rng.standard_normal((b, 1, h, hd)).astype(np.float32))
    kp = jnp.asarray(rng.standard_normal((pn, ps, kv, hd)).astype(np.float32))
    vp = jnp.asarray(rng.standard_normal(kp.shape).astype(np.float32))
    pt = jnp.asarray([[1, 2], [3, 4], [5, 6]], jnp.int32)
    lengths = jnp.asarray([3, 8, 5], jnp.int32)
    out = paged_decode_attention(
        q, _kv_pool(_stored(kp, layer, layers), _stored(vp, layer, layers)),
        layer, pt, lengths)
    idx = (np.asarray(pt)[:, :, None] * ps
           + np.arange(ps)[None, None, :]).reshape(b, span)
    kg = jnp.asarray(np.asarray(kp).reshape(pn * ps, kv, hd)[idx])
    vg = jnp.asarray(np.asarray(vp).reshape(pn * ps, kv, hd)[idx])
    ref = _ragged_decode_attention(q, kg, vg, lengths)
    # a head's scores sum its own hd products and exact zeros for the other
    # group's lanes; at 16 lanes the order of additions is decode_attention's
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    # scribble over every position past each slot's length: masked entries
    # contribute exactly 0, so the output must not change by a single bit
    kp2, vp2 = np.asarray(kp).copy(), np.asarray(vp).copy()
    for i in range(b):
        for pos in range(int(lengths[i]), span):
            page, off = np.asarray(pt)[i, pos // ps], pos % ps
            kp2[page, off] = 1e6 * (i + 1)
            vp2[page, off] = -1e6
    out2 = paged_decode_attention(
        q, _kv_pool(_stored(jnp.asarray(kp2), layer, layers),
                    _stored(jnp.asarray(vp2), layer, layers)),
        layer, pt, lengths)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(out2))


def _ragged_decode_attention(q, k, v, lengths):
    """The (B, span, KV, hd) oracle of the paged attend: each slot alone
    through the contiguous path's decode_attention at its own length."""
    return jnp.concatenate([
        decode_attention(q[i:i + 1], k[i:i + 1], v[i:i + 1], lengths[i])
        for i in range(q.shape[0])])


def _flat_row_gather(pages, page_table):
    """The fallback's fetch as it was before the page gather (one slice a
    ROW: 393,216 of them a layer at the benchmark's geometry, 11.9 ns each on
    a v5e whatever the row held). Kept as the oracle."""
    pn, ps = pages.shape[:2]
    b, pps = page_table.shape
    idx = (page_table[:, :, None] * ps
           + jnp.arange(ps)[None, None, :]).reshape(b, pps * ps)
    return pages.reshape(pn * ps, *pages.shape[2:])[idx]


def _ragged_paged_case(ps):
    """Five slots over pages 0..8 (0 the trash page): one that ends inside a
    page, one on a page edge, one at length 1, one whose table is all trash
    page (an inactive slot of the step: length 0 + the step's own row), one
    that repeats a page another slot holds (a shared prefix) and names it
    twice itself."""
    table = jnp.asarray([[1, 2, 3, 0], [4, 5, 0, 0], [6, 0, 0, 0],
                         [0, 0, 0, 0], [1, 7, 1, 8]], jnp.int32)
    lengths = jnp.asarray([2 * ps + 3, 2 * ps, 1, 1, 4 * ps], jnp.int32)
    return table, lengths


@pytest.mark.parametrize("kv,hd,ps", [(2, 64, 16), (2, 128, 16),
                                      (8, 128, 16), (2, 64, 8)])
def test_page_gather_equals_flat_row_gather_bitwise(kv, hd, ps, monkeypatch):
    # the K/V handed to the attend, and what comes out of it, must be the
    # flat-row gather's to the bit: same values, same order, trash-page rows
    # only under the length mask
    rng = np.random.default_rng(kv * hd + ps)
    pn, h = 11, 2 * kv
    pt, lengths = _ragged_paged_case(ps)
    b = pt.shape[0]
    q = jnp.asarray(rng.standard_normal((b, 1, h, hd)), jnp.bfloat16)
    kp = jnp.asarray(rng.standard_normal((pn, ps, kv, hd)), jnp.bfloat16)
    vp = jnp.asarray(rng.standard_normal((pn, ps, kv, hd)), jnp.bfloat16)
    handed = []
    attend = paged_kv.attend_rows

    def recording_attend(q_, k_, v_, lengths_):
        handed.append((k_, v_))
        return attend(q_, k_, v_, lengths_)

    monkeypatch.setattr(paged_kv, "attend_rows", recording_attend)
    # layer 1 of 2: the other layer's pages are garbage under the same ids
    out = paged_decode_attention(
        q, _kv_pool(_stored(kp, 1, 2), _stored(vp, 1, 2)), 1, pt, lengths)
    (kg, vg), = handed
    k_old, v_old = _flat_row_gather(kp, pt), _flat_row_gather(vp, pt)
    span = pt.shape[1] * ps
    assert k_old.shape == (b, span, kv, hd)
    assert kg.shape == vg.shape == (b, span, kv * hd)   # as stored
    np.testing.assert_array_equal(np.asarray(kg).reshape(k_old.shape),
                                  np.asarray(k_old))
    np.testing.assert_array_equal(np.asarray(vg).reshape(v_old.shape),
                                  np.asarray(v_old))
    # the attend over the rows as they lie against decode_attention over
    # the (KV, hd) view: the same products, zeros added for the other
    # groups' lanes (bf16 out: a reordered sum's tolerance)
    np.testing.assert_allclose(
        np.asarray(out, np.float32),
        np.asarray(_ragged_decode_attention(q, k_old, v_old, lengths),
                   np.float32),
        rtol=2e-2, atol=2e-2)
    # the all-trash slot reads the trash page's row 0 and nothing else
    np.testing.assert_allclose(
        np.asarray(out[3], np.float32),
        np.asarray(decode_attention(q[3:4], kp[None, 0], vp[None, 0], 1)[0],
                   np.float32), rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("tier", ["fp", "int8_per_channel",
                                  "int4_per_channel"])
def test_write_rows_then_read_span_at_a_layer_equal_the_5d_oracle(tier):
    # a step's rows written at layer l and the span read back at layer l
    # equal the same write and the flat-row read of the pool held as
    # (L, P, ps, KV, lanes): the row lands in layer l only, at
    # l*P*ps + page*ps + row, and the pages come from l*P + page
    from edgellm_tpu.models.flash_attention import (dequantize_kv_rows,
                                                    quantize_kv_rows)

    rng = np.random.default_rng(23)
    layers, pn, ps, kv, hd = 3, 11, 4, 2, 8
    pt, lengths = _ragged_paged_case(ps)
    lengths = lengths - 1                       # the row to be written
    b = pt.shape[0]
    k = jnp.asarray(rng.standard_normal((b, 1, kv, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, 1, kv, hd)), jnp.float32)
    if tier == "fp":
        pool = PagePool(jnp.asarray(rng.standard_normal(
            (layers, pn, ps, 2 * kv * hd)), jnp.float32))
        # ONE stored row a position: its K heads' lanes, then its V heads'
        stored = (jnp.concatenate([k[:, 0].reshape(b, -1),
                                   v[:, 0].reshape(b, -1)], -1),)
    else:
        pool = init_quant_pool(
            tiny_config("qwen2", num_layers=layers, hidden_size=kv * hd * 2,
                        num_heads=2 * kv, vocab_size=32), pn, ps, tier)
        pool = type(pool)(*(jnp.asarray(rng.integers(
            0, 100, a.shape), a.dtype) for a in pool))
        (qk, sk), (qv, sv) = (quantize_kv_rows(x[:, 0], tier)
                              for x in (k, v))
        stored = (qk, qv, sk, sv)
    before = [np.asarray(a) for a in pool]
    for layer in (0, 2, jnp.asarray(1, jnp.int32)):
        after = paged_kv.write_rows(pool, layer, pt, lengths, k, v)
        l = int(layer)
        page = np.asarray(pt)[np.arange(b), np.asarray(lengths) // ps]
        off = np.asarray(lengths) % ps
        live = page != 0        # the trash page takes duplicate writes
        assert len(after) == len(before) == len(stored)
        for a0, a1, rows in zip(before, after, stored):
            want = a0.copy()
            want[l, page[live], off[live]] = np.asarray(
                rows, a0.dtype).reshape(b, -1)[live]
            got = np.asarray(a1)
            np.testing.assert_array_equal(got[:, 1:], want[:, 1:])
            np.testing.assert_array_equal(np.delete(got, l, 0)[:, 0],
                                          np.delete(want, l, 0)[:, 0])
        kg, vg = paged_kv.read_span(after, layer, pt, jnp.float32)
        views = [np.asarray(a)[l].reshape(pn, ps, kv, -1) for a in (
            paged_kv.split_kv(after.kv) if tier == "fp" else after)]
        if tier == "fp":
            k_ref, v_ref = (_flat_row_gather(jnp.asarray(x), pt)
                            for x in views)
        else:
            k_ref, v_ref = (dequantize_kv_rows(
                _flat_row_gather(jnp.asarray(c), pt),
                _flat_row_gather(jnp.asarray(s_[..., 0]), pt), tier)
                for c, s_ in ((views[0], views[2]), (views[1], views[3])))
        np.testing.assert_array_equal(
            np.asarray(kg), np.asarray(k_ref).reshape(kg.shape))
        np.testing.assert_array_equal(
            np.asarray(vg), np.asarray(v_ref).reshape(vg.shape))


def _gathers(jaxpr):
    """Every ``gather`` equation of a jaxpr and of the jaxprs its equations
    carry (the layer scan's body, closed calls), with its scope path."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "gather":
            yield str(eqn.source_info.name_stack), eqn
        for val in eqn.params.values():
            for sub in (val if isinstance(val, (list, tuple)) else (val,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _gathers(sub)


@pytest.mark.parametrize("tier", ["fp", "int8_per_channel",
                                  "int4_per_channel"])
def test_decode_step_fetches_pool_by_page_not_by_row(params, tier):
    # the guard against the per-row gather coming back unseen: in the traced
    # step, every gather under attn.decode that reads a pool array takes one
    # whole page a slice — none reads the pool flattened to rows
    pn, ps, slots, pps = BCFG.num_pages, BCFG.page_size, BCFG.max_slots, 4
    kv, hd = CFG.num_kv_heads, CFG.head_dim
    table = jnp.zeros((slots, pps), jnp.int32)
    ints = jnp.zeros((slots,), jnp.int32)
    if tier == "fp":
        pool = init_pool(CFG, pn, ps)
        want = [ps * 2 * kv * hd]         # a page, its K and V lanes: ONE
    else:
        pool = init_quant_pool(CFG, pn, ps, tier)
        want = [ps * pool.k.shape[-1]] * 2 + [ps * kv] * 2  # codes, scales
    jaxpr = jax.make_jaxpr(lambda *a: paged_decode_step(CFG, *a))(
        params, pool, table, ints, ints)
    fetches = []
    for path, eqn in _gathers(jaxpr.jaxpr):
        shape = eqn.invars[0].aval.shape
        if "attn.decode" not in path or "paged_kv.write" in path:
            continue
        # whatever view of the pool is gathered, its leading axis counts the
        # PAGES of every layer (the flat (layer, page) index: no layer is
        # sliced out first) and one slice is everything a page holds
        assert shape[0] == CFG.num_layers * pn, \
            f"a pool not indexed by (layer, page): {shape}"
        assert tuple(eqn.params["slice_sizes"]) == (1, *shape[1:]), \
            f"a slice is not one whole page: {eqn.params['slice_sizes']}"
        fetches.append(int(np.prod(shape[1:])))
    assert sorted(fetches) == sorted(want)


def test_batched_sample_matches_single_row():
    rng = np.random.default_rng(13)
    logits = jnp.asarray(rng.standard_normal((4, 128)).astype(np.float32))
    keys = jnp.stack([jax.random.key(s) for s in (7, 8, 9, 10)])
    steps = jnp.asarray([0, 3, 5, 2], jnp.int32)
    temps = jnp.asarray([0.0, 0.7, 1.3, 0.0], jnp.float32)
    got = np.asarray(_batched_sample(logits, jax.random.key_data(keys),
                                     steps, temps))
    for i in range(4):
        want = _sample(logits[i:i + 1],
                       jax.random.fold_in(keys[i], steps[i]),
                       float(temps[i]))
        assert got[i] == int(np.asarray(want)[0])


# ---------------------------------------------------------------------------
# ServeFront integration
# ---------------------------------------------------------------------------


def test_drain_batched_front_matches_generate(params):
    from edgellm_tpu.serve import Request, ServeFront

    bat = ContinuousBatcher(CFG, params, BCFG)
    front = ServeFront(CFG, params, batcher=bat)
    reqs = [(_prompt(5, 20), 4, 0.0, 1), (_prompt(9, 21), 6, 0.8, 2),
            (_prompt(12, 22), 5, 0.0, 3)]
    for p, m, t, s in reqs:
        front.submit(Request(prompt_ids=p, max_new_tokens=m, temperature=t,
                             rng_seed=s))
    recs = front.drain_batched()
    assert len(recs) == 3
    by_prompt = {r.prompt_tokens: r for r in recs}
    for p, m, t, s in reqs:
        rec = by_prompt[len(p)]
        assert rec.outcome == "completed" and rec.backend == "batched"
        np.testing.assert_array_equal(rec.tokens[0],
                                      _solo(params, p, m, t, s))
    # the drain consumed the finished streams: nothing accumulates in the
    # batcher across drains on a long-lived server
    assert bat.results == {} and bat._streams == {}


def test_drain_batched_rejects_oversized_request_and_keeps_draining(params):
    from edgellm_tpu.serve import Request, ServeFront

    bat = ContinuousBatcher(CFG, params, BCFG)
    front = ServeFront(CFG, params, batcher=bat)
    good = (_prompt(5, 40), 4, 0.0, 7)
    front.submit(Request(prompt_ids=good[0], max_new_tokens=good[1],
                         temperature=good[2], rng_seed=good[3]))
    # prompt + granted tokens exceed the batcher's slot span (32): the drain
    # must record the rejection and keep serving the rest of the queue
    front.submit(Request(prompt_ids=_prompt(30, 41), max_new_tokens=8))
    recs = front.drain_batched()
    assert len(recs) == 2
    by_prompt = {r.prompt_tokens: r for r in recs}
    bad = by_prompt[30]
    assert bad.outcome == "rejected" and bad.reason == "exceeds_slot_span"
    ok = by_prompt[5]
    assert ok.outcome == "completed" and ok.backend == "batched"
    np.testing.assert_array_equal(
        ok.tokens[0], _solo(params, good[0], good[1], good[2], good[3]))
    assert bat.results == {} and bat._streams == {}


# ---------------------------------------------------------------------------
# split runtime: per-stage pools page the same way
# ---------------------------------------------------------------------------


def test_split_paged_decode_matches_generate_split(params):
    if len(jax.devices()) < 2:
        pytest.skip("needs >= 2 devices")
    from edgellm_tpu.parallel import SplitConfig, SplitRuntime, make_stage_mesh
    from edgellm_tpu.serve.decode import generate_split

    mesh = make_stage_mesh(2)
    rt = SplitRuntime(CFG, SplitConfig(cuts=(2,),
                                       hop_codecs=("int8_per_token",)), mesh)
    placed = rt.place_params(params)
    streams = [dict(prompt=_prompt(5, 30), max_new=5, temp=0.0, seed=11),
               dict(prompt=_prompt(9, 31), max_new=4, temp=0.7, seed=22)]
    ref = [np.asarray(generate_split(
        rt, placed, jnp.asarray(s["prompt"])[None], s["max_new"],
        capacity=32, temperature=s["temp"],
        rng_key=jax.random.key(s["seed"])))[0] for s in streams]

    ps, npg, ms, pps = 8, 9, 4, 4
    host = PagedKVCache(CFG, num_pages=npg, page_size=ps, max_slots=ms,
                        pages_per_slot=pps, materialize=False)
    pool = rt.init_paged_pool(npg, ps)
    state = {}
    for i, s in enumerate(streams):
        n = len(s["prompt"])
        logits, cache = rt.prefill_decode(placed,
                                          jnp.asarray(s["prompt"])[None], 32)
        key = jax.random.key(s["seed"])
        tok0 = int(_sample(logits[:, -1], jax.random.fold_in(key, 0),
                           s["temp"])[0])
        slot = host.alloc_slot()
        host.ensure(slot, n)
        pool = rt.adopt_paged(pool, cache, 0, host._flat_indices(slot, n), n)
        host.lengths[slot] = n
        host.check_invariants()
        state[slot] = dict(i=i, key=key, toks=[tok0], **s)
    while any(len(v["toks"]) < v["max_new"] for v in state.values()):
        tok_ids = np.zeros((ms,), np.int32)
        active = []
        for slot, v in state.items():
            if len(v["toks"]) >= v["max_new"]:
                continue
            host.ensure(slot, int(host.lengths[slot]) + 1)
            tok_ids[slot] = v["toks"][-1]
            active.append(slot)
        pt, lens = host.device_tables()
        logits, pool = rt.decode_step_paged(placed, pool, pt, lens,
                                            jnp.asarray(tok_ids))
        for slot in active:
            v = state[slot]
            tok = int(_sample(logits[slot][None],
                              jax.random.fold_in(v["key"], len(v["toks"])),
                              v["temp"])[0])
            v["toks"].append(tok)
            host.lengths[slot] = int(host.lengths[slot]) + 1
    for v in state.values():
        np.testing.assert_array_equal(np.asarray(v["toks"], np.int32),
                                      ref[v["i"]])


# ---------------------------------------------------------------------------
# split runtime: the stage's pool is carried through the step, and a write
# that must not happen (a dead unroll iteration, a fill / drain step of the
# µ-batch schedule, a padding layer) lands in the trash page
# ---------------------------------------------------------------------------


def _staged_oracle(rt, placed, pool, table, lengths, toks):
    """The step as the runtime made it before its pool was carried, kept
    here as the oracle: every layer runs over ITS slice of the stage's pool
    as a pool of one layer, and whatever must not be written is selected
    away whole — a padding layer's slice after the layer, a dead unroll
    iteration's pool after the stage. Nothing is routed anywhere, so the
    trash page holds the idle slots' writes only. Unpipelined: the µ-batch
    schedule must leave the same bits."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from edgellm_tpu.models.transformer import embed, precompute_rope, unembed
    from edgellm_tpu.parallel import split

    tree_map = jax.tree_util.tree_map

    def stage(local_layers, local_valid, hidden, pool_loc, cos_b, sin_b):
        lv = {k: v[0] for k, v in local_layers.items()}
        hidden = jax.lax.pcast(hidden, ("stage",), to="varying")

        def scan_body(h, xs):
            lp, ok, layer_pool = xs
            out, written = paged_kv.block_decode_paged(
                CFG, lp, h, cos_b, sin_b,
                tree_map(lambda a: a[None], layer_pool), 0, table, lengths)
            return jnp.where(ok, out, h), tree_map(
                lambda new, old: jnp.where(ok, new[0], old), written,
                layer_pool)

        def run_stage(h, pool, keep):
            h2, written = jax.lax.scan(scan_body, h,
                                       (lv, local_valid[0], pool))
            return h2, split.keep_carry(keep, written, pool)

        out, pool = split.run_pipeline_stages_carry(
            len(rt.bounds), rt.codecs, run_stage, hidden,
            tree_map(lambda a: a[0], pool_loc))
        return out, tree_map(lambda a: a[None], pool)

    @jax.jit
    def step(placed, pool, toks):
        cos, sin = precompute_rope(CFG, table.shape[1] * pool.page_size)
        out, pool = shard_map(
            stage, mesh=rt.mesh,
            in_specs=({k: rt._layer_pspec(k, v.ndim)
                       for k, v in placed["layers"].items()},
                      P("stage"), P(), P("stage"), P(), P()),
            out_specs=(P(), P("stage")), check_vma=False,
        )(placed["layers"], placed["layers_valid"],
          embed(placed, toks[:, None]), pool, cos[lengths], sin[lengths])
        return unembed(CFG, placed, out)[:, -1], pool

    table, lengths = jnp.asarray(table), jnp.asarray(lengths)
    return step(placed, pool, jnp.asarray(toks))


@pytest.mark.parametrize("tier,n_micro", [("fp", 1), ("fp", 2),
                                          ("int8_per_channel", 1)])
def test_split_paged_step_leaves_every_real_page_to_the_live_write(
        params, tier, n_micro):
    if len(jax.devices()) < 3:
        pytest.skip("needs >= 3 devices")
    from jax.sharding import NamedSharding, PartitionSpec as P

    from edgellm_tpu.parallel import (PipelineConfig, SplitConfig,
                                      SplitRuntime, make_stage_mesh)

    # stages of 2, 1 and 1 layers: stages 1 and 2 carry a padding layer
    rt = SplitRuntime(
        CFG, SplitConfig(cuts=(1, 2), hop_codecs=("int8_per_token",) * 2),
        make_stage_mesh(3), pipeline=PipelineConfig(num_microbatches=n_micro))
    assert rt.stage_size == 2 and [b - a for a, b in rt.bounds] == [2, 1, 1]
    placed = rt.place_params(params)
    ps, npg, ms, pps = (BCFG.page_size, BCFG.num_pages, BCFG.max_slots,
                        BCFG.pages_per_slot)
    # a pool with something in EVERY row, the trash page and the padding
    # layers' pages included: an untouched page is told from a zeroed one
    rng = np.random.default_rng(5)
    zero = rt.init_paged_pool(npg, ps, kv_codec=tier)

    def some(a):    # codes, a quantized tier's positive scales, or fp rows
        if a.dtype == jnp.int8:
            return rng.integers(-127, 128, a.shape).astype(a.dtype)
        if tier != "fp":
            return rng.uniform(0.01, 0.05, a.shape).astype(a.dtype)
        return rng.standard_normal(a.shape).astype(a.dtype)

    start = [some(a) for a in zero]
    staged = NamedSharding(rt.mesh, P("stage"))
    pool = type(zero)(*(jax.device_put(a, staged) for a in start))
    want = type(zero)(*(jax.device_put(a, staged) for a in start))
    # three streams at their own fill levels and an idle slot (row of zeros)
    table = np.zeros((ms, pps), np.int32)
    table[0], table[1, :2], table[3, :3] = [1, 2, 3, 4], [5, 6], [7, 8, 9]
    lengths = np.asarray([25, 9, 0, 16], np.int32)
    toks = _prompt(ms, 77)
    for _ in range(2):          # the second step reads what the first wrote
        ref, want = _staged_oracle(rt, placed, want, table, lengths, toks)
        logits, pool = rt.decode_step_paged(placed, pool, table, lengths,
                                            jnp.asarray(toks))
        live = lengths > 0
        np.testing.assert_array_equal(np.asarray(logits)[live],
                                      np.asarray(ref)[live])
        for g, w, was in zip(pool, want, start):
            g, w = np.asarray(g), np.asarray(w)
            # every real page of every stage, padding layers' too, bit for
            # bit: written once, by the iteration whose turn it was
            np.testing.assert_array_equal(g[:, :, 1:], w[:, :, 1:])
            # a padding layer touched no real page at all ...
            np.testing.assert_array_equal(g[1:, 1, 1:], was[1:, 1, 1:])
            # ... its write went to the trash page, like every dead
            # iteration's (the oracle's holds the idle slot's write only)
            assert not np.array_equal(g[1:, 1, 0], was[1:, 1, 0])
            np.testing.assert_array_equal(w[1:, 1, 0], was[1:, 1, 0])
            assert not np.array_equal(g[:, 0, 0], w[:, 0, 0])
        toks = np.asarray(jnp.argmax(logits, -1), np.int32)
        lengths = np.where(live, lengths + 1, 0).astype(np.int32)
