"""Prefix-sharing paged KV cache: radix index, refcounts, copy-on-write.

The load-bearing claim: prefix sharing is HOST-SIDE bookkeeping — it
changes which page-table rows point at which pages, never the traced
graph — so a prefix-enabled batcher's tokens are identical to the
non-shared path on a mixed trace while matched prompt prefixes cost zero
prefill compute. The graphlint contracts pin the jaxpr half
(``batching.prefix-disabled-identity``); these tests pin the executed
half plus every allocator invariant sharing touches: refcounted frees,
COW forks, defrag under sharing churn, LRU index eviction under page
pressure, and checkpoint/restore with shared pages in flight.
"""
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from edgellm_tpu.models import init_params, tiny_config
from edgellm_tpu.models.paged_kv import PagedKVCache, PrefixCacheConfig
from edgellm_tpu.serve.batching import BatchingConfig, ContinuousBatcher
from edgellm_tpu.serve.decode import generate

CFG = tiny_config("qwen2", num_layers=4, hidden_size=32, num_heads=4,
                  vocab_size=128)
# same geometry as tests/test_batching.py so the compiled ragged step is
# shared across the suite; prefix-enabled twins differ only in host state
BCFG = BatchingConfig(page_size=8, num_pages=17, max_slots=4,
                      pages_per_slot=4)
PCFG = PrefixCacheConfig(enabled=True, min_shared_block=1)

# pool-level tests use a 2-layer model: the allocator math is layer-count
# independent and the materialized pages stay tiny
CFG2 = tiny_config("qwen2", num_layers=2, hidden_size=32, num_heads=4,
                   vocab_size=128)
PROMPT = list(range(100, 110))     # 10 tokens = 2 full blocks + partial 2


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


def _seq(n, seed):
    r = np.random.default_rng(seed)
    shape = (CFG2.num_layers, n, CFG2.num_kv_heads, CFG2.head_dim)
    return (jnp.asarray(r.standard_normal(shape), jnp.float32),
            jnp.asarray(r.standard_normal(shape), jnp.float32))


def _pool(prefix=PCFG, **kw):
    return PagedKVCache(CFG2, num_pages=13, page_size=4, max_slots=3,
                        pages_per_slot=4, prefix_cache=prefix, **kw)


def _donor_pool(prefix=PCFG):
    """A pool whose slot 0 adopted PROMPT and published it to the index."""
    cache = _pool(prefix)
    s0 = cache.alloc_slot()
    k0, v0 = _seq(10, 0)
    cache.adopt(s0, k0, v0, 10)
    assert cache.register_prefix(s0, PROMPT) == 3
    cache.check_invariants()
    return cache, s0


# ---------------------------------------------------------------------------
# config + inert paths
# ---------------------------------------------------------------------------


def test_prefix_config_validation():
    with pytest.raises(ValueError, match="min_shared_block"):
        PrefixCacheConfig(min_shared_block=0)
    with pytest.raises(ValueError, match="max_index_pages"):
        PrefixCacheConfig(max_index_pages=-1)


def test_prefix_api_inert_without_index():
    # no prefix_cache at all, and enabled=False, behave identically: the
    # sharing API returns zeros and allocator state never changes
    for prefix in (None, PrefixCacheConfig(enabled=False)):
        pool = PagedKVCache(CFG2, num_pages=13, page_size=4, max_slots=3,
                            pages_per_slot=4, materialize=False,
                            prefix_cache=prefix)
        assert pool.prefix is None
        s = pool.alloc_slot()
        pool.ensure(s, 10)
        assert pool.register_prefix(s, PROMPT) == 0
        assert pool.probe_prefix(PROMPT) == {"tokens": 0, "pages": 0,
                                             "forks": 0}
        s1 = pool.alloc_slot()
        assert pool.share_prefix(s1, PROMPT) == 0
        assert pool.release_prefix() == 0
        pool.check_invariants()


# ---------------------------------------------------------------------------
# probe / share / COW
# ---------------------------------------------------------------------------


def test_probe_share_cow_and_unique_tokens():
    cache, s0 = _donor_pool()
    g0 = cache.gather_slot(s0)
    probe = cache.probe_prefix(PROMPT + [111, 112])
    assert probe == {"tokens": 10, "pages": 3, "forks": 1}
    s1 = cache.alloc_slot()
    # the batcher caps the claim at S-1 so one suffix token remains
    assert cache.share_prefix(s1, PROMPT + [111, 112], max_tokens=11) == 10
    cache.check_invariants()
    assert cache.prefix_counters["hits"] == 1
    assert cache.prefix_counters["saved_tokens"] == 10
    # suffix rows land in the shared partial page: it must COW-fork, and
    # the fork's device copy must carry the donor's matched rows
    k1, v1 = _seq(2, 1)
    cache.adopt_rows(s1, k1, v1, 10, 12)
    cache.check_invariants()
    assert cache.prefix_counters["cow_forks"] == 1
    g1 = cache.gather_slot(s1)
    np.testing.assert_array_equal(g1["k"][:, :10], g0["k"][:, :10])
    np.testing.assert_array_equal(g1["v"][:, :10], g0["v"][:, :10])
    np.testing.assert_array_equal(np.asarray(g1["k"][:, 10:12]),
                                  np.asarray(k1))
    # divergent tail registers under the matched chain without re-pinning
    cache.register_prefix(s1, PROMPT + [111, 112])
    cache.check_invariants()
    # unique coverage: 2 shared full pages (8) + donor partial (2) + the
    # sharer's forked partial covering rows 8..12 (4) = 14, not 10 + 12
    assert cache.unique_live_tokens == 14
    assert cache.live_tokens == 22
    assert cache.shared_pages >= 2


def _unique_by_walking_pages(cache):
    """``unique_live_tokens`` as it was before its short cut: per page, the
    largest coverage over every slot that holds it."""
    cover = np.zeros((cache.num_pages,), np.int64)
    for s in range(cache.max_slots):
        if cache.active[s]:
            n = int(cache.lengths[s])
            for j, p in enumerate(cache._slot_pages[s]):
                cover[p] = max(cover[p], min(cache.page_size,
                                             n - j * cache.page_size), 0)
    return int(cover.sum())


@pytest.mark.parametrize("shared", [False, True], ids=["unshared", "shared"])
def test_unique_live_tokens_short_cut_equals_the_page_walk(shared):
    """With no page held twice the property returns ``live_tokens`` without
    walking the pages (the walk ran twice a step on the batcher's commit
    clock); with a shared page it walks. Both equal the walk."""
    cache = _donor_pool()[0] if shared else _pool(None)
    if shared:
        s1 = cache.alloc_slot()
        assert cache.share_prefix(s1, PROMPT + [111, 112],
                                  max_tokens=11) == 10
    else:
        for n, seed in ((10, 0), (7, 1)):
            s = cache.alloc_slot()
            cache.adopt(s, *_seq(n, seed), n)
    cache.check_invariants()
    assert bool(cache.shared_pages) == shared
    assert cache.unique_live_tokens == _unique_by_walking_pages(cache)
    assert (cache.unique_live_tokens == cache.live_tokens) == (not shared)


def test_share_cap_lands_mid_partial_node():
    cache, _ = _donor_pool()
    assert cache.probe_prefix(PROMPT, max_tokens=9) == {
        "tokens": 9, "pages": 3, "forks": 1}
    s1 = cache.alloc_slot()
    # cap 9 = 2 full blocks + ONE token of the 2-token partial node
    assert cache.share_prefix(s1, PROMPT, max_tokens=9) == 9
    assert int(cache.lengths[s1]) == 9
    cache.check_invariants()
    k, v = _seq(1, 2)
    cache.adopt_rows(s1, k, v, 9, 10)
    cache.check_invariants()
    assert cache.prefix_counters["cow_forks"] == 1


def test_min_shared_block_gates_sharing():
    cache, _ = _donor_pool(
        PrefixCacheConfig(enabled=True, min_shared_block=12))
    assert cache.probe_prefix(PROMPT) == {"tokens": 0, "pages": 0,
                                          "forks": 0}
    s1 = cache.alloc_slot()
    assert cache.share_prefix(s1, PROMPT) == 0
    assert cache.prefix_counters["misses"] == 1
    # the miss must leave the slot untouched
    assert int(cache.lengths[s1]) == 0 and not cache._slot_pages[s1]
    cache.check_invariants()


def test_share_requires_fresh_slot():
    cache, s0 = _donor_pool()
    with pytest.raises(ValueError, match="fresh"):
        cache.share_prefix(s0, PROMPT)


# ---------------------------------------------------------------------------
# index cap + LRU eviction + pressure reclaim
# ---------------------------------------------------------------------------


def test_index_cap_evicts_lru_leaves():
    cache = _pool(PrefixCacheConfig(enabled=True, min_shared_block=1,
                                    max_index_pages=2))
    s0 = cache.alloc_slot()
    k0, v0 = _seq(10, 0)
    cache.adopt(s0, k0, v0, 10)
    # the cap stops registration at 2 nodes: the partial tail never pins
    # (its only evictable victim is the chain being registered)
    assert cache.register_prefix(s0, PROMPT) == 2
    assert cache.prefix.num_nodes == 2
    assert cache.probe_prefix(PROMPT)["tokens"] == 8
    cache.check_invariants()
    # a disjoint prompt evicts the donor chain leaf-first (LRU order)
    other = list(range(30, 38))
    s1 = cache.alloc_slot()
    k1, v1 = _seq(8, 1)
    cache.adopt(s1, k1, v1, 8)
    assert cache.register_prefix(s1, other) == 2
    cache.check_invariants()
    assert cache.prefix.num_nodes == 2
    assert cache.prefix_counters["index_evictions"] == 2
    assert cache.probe_prefix(PROMPT)["tokens"] == 0
    assert cache.probe_prefix(other)["tokens"] == 8


def test_pressure_reclaims_lru_index_pages_first():
    cache, s0 = _donor_pool()
    other = [int(t) for t in range(30, 40)]
    s1 = cache.alloc_slot()
    k1, v1 = _seq(10, 1)
    cache.adopt(s1, k1, v1, 10)
    assert cache.register_prefix(s1, other) == 3
    cache.free_slot(s0)
    cache.free_slot(s1)
    cache.check_invariants()
    # both chains now live only in the index (refcount 1 each); touch the
    # PROMPT chain so it is the recently-used one
    s = cache.alloc_slot()
    assert cache.share_prefix(s, PROMPT) == 10
    cache.free_slot(s)
    assert cache.index_pages == 6
    assert cache.reclaimable_index_pages == 6
    # demand 8 pages with 6 free: the allocator must reclaim exactly two
    # index-only pages, LRU-first — the untouched chain loses its tail
    sa = cache.alloc_slot()
    cache.ensure(sa, 16)
    sb = cache.alloc_slot()
    cache.ensure(sb, 16)
    cache.check_invariants()
    assert cache.prefix_counters["reclaimed_pages"] == 2
    assert cache.probe_prefix(PROMPT)["tokens"] == 10
    assert cache.probe_prefix(other)["tokens"] == 4
    # release everything: every page must come home
    cache.free_slot(sa)
    cache.free_slot(sb)
    cache.release_prefix()
    cache.check_invariants()
    assert cache.num_free_pages == 12


def test_release_prefix_path_drops_exclusive_suffix():
    cache, s0 = _donor_pool()
    cache.free_slot(s0)
    cache.check_invariants()
    assert cache.index_pages == 3
    assert cache.release_prefix(PROMPT) == 3
    cache.check_invariants()
    assert cache.probe_prefix(PROMPT)["tokens"] == 0
    assert cache.num_free_pages == 12


# ---------------------------------------------------------------------------
# defrag x sharing churn
# ---------------------------------------------------------------------------


def test_defrag_relocates_shared_pages_once_for_all_owners():
    cache, s0 = _donor_pool()
    g0 = cache.gather_slot(s0)
    s1 = cache.alloc_slot()
    cache.share_prefix(s1, PROMPT + [111, 112], max_tokens=11)
    k1, v1 = _seq(2, 1)
    cache.adopt_rows(s1, k1, v1, 10, 12)
    cache.register_prefix(s1, PROMPT + [111, 112])
    s2 = cache.alloc_slot()
    cache.share_prefix(s2, PROMPT, max_tokens=9)
    k2, v2 = _seq(1, 2)
    cache.adopt_rows(s2, k2, v2, 9, 10)
    cache.check_invariants()
    g1 = cache.gather_slot(s1)
    g2 = cache.gather_slot(s2)
    # a page referenced by three slots moves once; every owner's view is
    # byte-identical afterwards
    cache.defrag()
    cache.check_invariants()
    for slot, g in ((s0, g0), (s1, g1), (s2, g2)):
        got = cache.gather_slot(slot)
        np.testing.assert_array_equal(got["k"], g["k"])
        np.testing.assert_array_equal(got["v"], g["v"])
    # free the DONOR mid-churn: shared pages survive for the other owners,
    # and defragging across the freed hole keeps them byte-identical
    cache.free_slot(s0)
    cache.check_invariants()
    cache.defrag()
    cache.check_invariants()
    np.testing.assert_array_equal(cache.gather_slot(s1)["k"], g1["k"])
    np.testing.assert_array_equal(cache.gather_slot(s2)["k"], g2["k"])


# ---------------------------------------------------------------------------
# serialization
# ---------------------------------------------------------------------------


def _shared_state_dict():
    cache, s0 = _donor_pool()
    s1 = cache.alloc_slot()
    cache.share_prefix(s1, PROMPT + [111, 112], max_tokens=11)
    k1, v1 = _seq(2, 1)
    cache.adopt_rows(s1, k1, v1, 10, 12)
    cache.check_invariants()
    return cache, s0, s1, cache.state_dict()


def test_state_dict_roundtrips_refcounts_and_index():
    cache, s0, s1, sd = _shared_state_dict()
    cache2 = _pool()
    cache2.load_state_dict(sd)
    cache2.check_invariants()
    assert cache2.prefix.num_nodes == cache.prefix.num_nodes
    assert (cache2._refcount == cache._refcount).all()
    for slot in (s0, s1):
        np.testing.assert_array_equal(cache2.gather_slot(slot)["k"],
                                      cache.gather_slot(slot)["k"])
    # the restored index is live, not a husk: a new admit shares from it
    s2 = cache2.alloc_slot()
    assert cache2.share_prefix(s2, PROMPT) == 10
    cache2.check_invariants()


def test_sharing_checkpoint_restores_into_prefix_disabled_pool():
    cache, s0, s1, sd = _shared_state_dict()
    plain = _pool(prefix=None)
    plain.load_state_dict(sd)
    # the index is gone, so its holds must drop without double-freeing or
    # leaking — check_invariants cross-checks refcount == slot references
    plain.check_invariants()
    assert plain.prefix is None
    assert plain.index_pages == 0
    for slot in (s0, s1):
        np.testing.assert_array_equal(plain.gather_slot(slot)["k"],
                                      cache.gather_slot(slot)["k"])


# ---------------------------------------------------------------------------
# batched decode: token identity + reporting
# ---------------------------------------------------------------------------


def _mixed_trace(rng_seed=3):
    rng = np.random.default_rng(rng_seed)
    shared = rng.integers(1, CFG.vocab_size, size=8)
    prompts = [
        np.concatenate([shared, rng.integers(1, CFG.vocab_size, size=5)]),
        np.concatenate([shared, rng.integers(1, CFG.vocab_size, size=3)]),
        rng.integers(1, CFG.vocab_size, size=9),          # disjoint
        np.concatenate([shared, rng.integers(1, CFG.vocab_size, size=7)]),
    ]
    return [p.astype(np.int32) for p in prompts], [0.0, 0.7, 0.0, 1.1]


def test_batched_mixed_trace_token_identity(params):
    prompts, temps = _mixed_trace()

    def run(prefix_cache):
        bc = BatchingConfig(page_size=8, num_pages=17, max_slots=4,
                            pages_per_slot=4, prefix_cache=prefix_cache)
        b = ContinuousBatcher(CFG, params, bc)
        sids = [b.submit(p, 6, temperature=t, rng_seed=i)
                for i, (p, t) in enumerate(zip(prompts, temps))]
        res = b.run()
        b.pool.check_invariants()
        return {s: res[s].tolist() for s in sids}, b

    base, off_bat = run(None)
    got, on_bat = run(PCFG)
    assert got == base
    # the parity proved something: the shared prefix actually hit
    rep = on_bat.report()["prefix"]
    assert rep["hits"] >= 2 and rep["saved_tokens"] > 0
    assert rep["cow_forks"] >= 1
    # enabled=False must be indistinguishable from no config at all
    off, _ = run(PrefixCacheConfig(enabled=False))
    assert off == base
    # and both pin to solo generate through the greedy stream
    np.testing.assert_array_equal(np.asarray(base[0], np.int32),
                                  _solo(params, prompts[0], 6, 0.0, 0))
    # occupancy counts a shared page ONCE: sharing can only lower it
    assert (on_bat.report()["occupancy_mean"]
            <= off_bat.report()["occupancy_mean"] + 1e-9)


def test_checkpoint_restore_with_shared_pages(params, tmp_path):
    bc = BatchingConfig(page_size=8, num_pages=17, max_slots=4,
                        pages_per_slot=4, prefix_cache=PCFG)
    bat = ContinuousBatcher(CFG, params, bc)
    shared = _prompt(9, 21)
    pa = np.concatenate([shared, _prompt(3, 22)])
    pb = np.concatenate([shared, _prompt(2, 23)])
    sa = bat.submit(pa, 8, temperature=0.6, rng_seed=7)
    sb = bat.submit(pb, 8, temperature=0.0, rng_seed=8)
    for _ in range(3):
        bat.step()
    assert bat.pool.shared_pages >= 1
    path = bat.checkpoint_stream(sb, str(tmp_path / "b.ckpt"))
    # kill the stream mid-decode: its shared pages must survive for the
    # other holder — no double-free, no leak
    bat.discard(sb)
    bat.pool.check_invariants()
    res = bat.run()
    bat.pool.check_invariants()
    np.testing.assert_array_equal(res[sa], _solo(params, pa, 8, 0.6, 7))
    # restore into a FRESH prefix-enabled batcher: the payload is the
    # contiguous prefix, adopted privately, finishing bit-identically
    other = ContinuousBatcher(CFG, params, bc)
    rid = other.restore_stream(path)
    out = other.run()
    other.pool.check_invariants()
    np.testing.assert_array_equal(out[rid], _solo(params, pb, 8, 0.0, 8))
    # and into a prefix-DISABLED pool: no index state rides the checkpoint
    plain = ContinuousBatcher(CFG, params, BCFG)
    rid2 = plain.restore_stream(path)
    np.testing.assert_array_equal(plain.run()[rid2],
                                  _solo(params, pb, 8, 0.0, 8))


def test_split_mixed_trace_token_identity(params):
    if len(jax.devices()) < 2:
        pytest.skip("needs >= 2 devices")
    from edgellm_tpu.parallel import (SplitConfig, SplitRuntime,
                                      make_stage_mesh)

    mesh = make_stage_mesh(2)
    rt = SplitRuntime(CFG, SplitConfig(cuts=(2,),
                                       hop_codecs=("int8_per_token",)), mesh)
    placed = rt.place_params(params)
    prompts, temps = _mixed_trace(5)
    prompts, temps = prompts[:3], temps[:3]

    def run(prefix_cache):
        bc = BatchingConfig(page_size=8, num_pages=17, max_slots=4,
                            pages_per_slot=4, prefix_cache=prefix_cache)
        b = ContinuousBatcher(CFG, params, bc, split_runtime=rt,
                              placed_params=placed)
        sids = [b.submit(p, 5, temperature=t, rng_seed=i)
                for i, (p, t) in enumerate(zip(prompts, temps))]
        res = b.run()
        b.pool.check_invariants()
        return {s: res[s].tolist() for s in sids}, b

    base, _ = run(None)
    got, gb = run(PCFG)
    assert got == base
    assert gb.report()["prefix"]["hits"] >= 1


def test_front_report_carries_prefix_scoreboard(params):
    from edgellm_tpu.serve import Request, ServeFront

    bat = ContinuousBatcher(
        CFG, params, BatchingConfig(page_size=8, num_pages=17, max_slots=4,
                                    pages_per_slot=4, prefix_cache=PCFG))
    front = ServeFront(CFG, params, batcher=bat)
    shared = _prompt(9, 50)
    reqs = [(np.concatenate([shared, _prompt(3, 51)]), 4, 0.0, 1),
            (np.concatenate([shared, _prompt(2, 52)]), 4, 0.6, 2)]
    for p, m, t, s in reqs:
        front.submit(Request(prompt_ids=p, max_new_tokens=m, temperature=t,
                             rng_seed=s))
    recs = front.drain_batched()
    assert len(recs) == 2
    for (p, m, t, s), rec in zip(reqs, sorted(recs,
                                              key=lambda r: r.request_id)):
        assert rec.outcome == "completed"
        np.testing.assert_array_equal(rec.tokens[0],
                                      _solo(params, p, m, t, s))
    # the drain stamps the headline numbers into each record's plan and
    # the front-level report exposes the live scoreboard
    assert any(r.plan.get("prefix", {}).get("saved_tokens", 0) > 0
               for r in recs)
    rep = front.report()
    assert rep["prefix"]["hits"] >= 1
    assert 0.0 < rep["prefix"]["hit_rate"] <= 1.0
    # a front without a prefix-enabled batcher reports no such section
    assert "prefix" not in ServeFront(CFG, params).report()


# ---------------------------------------------------------------------------
# run.py params validation
# ---------------------------------------------------------------------------


def _prefix_params():
    root = os.path.join(os.path.dirname(__file__), "..")
    with open(os.path.join(root, "configs",
                           "split13_qwen_prefix.json")) as f:
        return json.load(f)


def test_params_validation_accepts_prefix_config():
    from edgellm_tpu.run import _validate_params_json

    _validate_params_json(_prefix_params())  # must not raise


@pytest.mark.parametrize("patch, msg", [
    ({"batching": None}, "rides the continuous batcher"),
    ({"prefix_cache": [1]}, "object of PrefixCacheConfig"),
    ({"prefix_cache": {"enabled": True, "page_size": 8}}, "unknown field"),
    ({"prefix_cache": {"enabled": 1}}, "must be a boolean"),
    ({"prefix_cache": {"min_shared_block": -1}}, "non-negative"),
    ({"prefix_cache": {"min_shared_block": True}}, "non-negative"),
    ({"prefix_cache": {"min_shared_block": 0}}, "min_shared_block"),
])
def test_params_validation_rejects_prefix_footguns(patch, msg):
    from edgellm_tpu.run import _validate_params_json

    p = _prefix_params()
    p.update(patch)
    if p.get("batching") is None:
        p.pop("batching", None)
    with pytest.raises(SystemExit, match=msg):
        _validate_params_json(p)


def test_params_validation_prefix_needs_serve():
    from edgellm_tpu.run import _validate_params_json

    with pytest.raises(SystemExit, match="experiment 'serve'"):
        _validate_params_json({"experiment": "relevance", "max_length": 64,
                               "stride": 32,
                               "prefix_cache": {"enabled": True}})


def test_soak_shared_prefix_len_validation():
    from edgellm_tpu.serve.soak import SoakConfig

    with pytest.raises(ValueError, match="shared_prefix_len"):
        SoakConfig(prompt_len=8, shared_prefix_len=9)
    assert SoakConfig(prompt_len=8, shared_prefix_len=8).shared_prefix_len \
        == 8
