"""A slot's pages lie in runs: ``PagedKVCache`` hands out and takes back RUNS
of ``run_pages`` adjacent pages, the unit ``flash_attention.paged_decode_walk``
fetches with one DMA (PERF.md §6 "PR 46").

The run's length G is nobody's choice: ``flash_attention.walk_run_pages`` reads
it off the bytes of one page, so the pools here get their G from their rows'
width and dtype, as the cells' do: 16 rows of 2 x 128 float32 lanes are 16 KB
(four to a run), the same in bf16 8 KB (eight), 2 x 256 float32 lanes 32 KB (a
page is its own run, and the allocator is the LIFO stack of single pages it
was before runs, id for id); and a table of three entries holds runs of two.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from edgellm_tpu.models import flash_attention, paged_kv, tiny_config
from edgellm_tpu.models.paged_kv import (OutOfPages, PagedKVCache,
                                         PrefixCacheConfig)

PAGE, SLOTS, PAGES_PER_SLOT = 16, 5, 16

#: G -> (hidden size at 4 heads of which 2 are KV heads, pool dtype, a slot's
#: table: 16 entries, or 3 where the table is what caps the run)
POOLS = {1: (512, jnp.float32, 16), 2: (256, jnp.bfloat16, 3),
         4: (256, jnp.float32, 16), 8: (256, jnp.bfloat16, 16)}


def _cache(g, num_pages=49, materialize=False, prefix=True, slots=SLOTS,
           pages_per_slot=None):
    hidden, dtype, table = POOLS[g]
    pages_per_slot = pages_per_slot or table
    cfg = tiny_config("qwen2", num_layers=1, hidden_size=hidden, num_heads=4,
                      vocab_size=64)
    cache = PagedKVCache(
        cfg, num_pages=num_pages, page_size=PAGE, max_slots=slots,
        pages_per_slot=pages_per_slot, dtype=dtype, materialize=materialize,
        prefix_cache=PrefixCacheConfig(enabled=True) if prefix else None)
    assert cache.run_pages == g
    return cache


def _runs(cache, slot):
    """Which whole groups of ``slot``'s table are runs, by the predicate the
    kernel is handed."""
    whole = len(cache._slot_pages[slot]) // cache.run_pages
    return flash_attention.page_runs(cache.page_table,
                                     cache.run_pages)[slot, :whole]


def _churn(cache, seed, steps, on_step=None, defrag=True):
    """A seeded mix of every operation that moves a page: slots come and go,
    grow by a few tokens or a prompt at a time, publish and map prefixes (of
    a three-token alphabet, so that later prompts hit), fork what they share
    before they write, and the pool is compacted now and then."""
    rng = np.random.default_rng(seed)
    prompts = {}
    for i in range(steps):
        op = rng.integers(0, 8)
        live = [s for s in range(cache.max_slots) if cache.active[s]]
        try:
            if op == 0 and len(live) < cache.max_slots:
                s = cache.alloc_slot()
                toks = rng.integers(0, 2, int(rng.integers(
                    1, min(5 * PAGE, cache.span))))
                prompts[s] = toks
                if rng.random() < 0.6:
                    cache.share_prefix(s, toks, max_tokens=len(toks) - 1)
            elif op in (1, 2, 3, 4) and live:
                s = int(rng.choice(live))
                grow = 1 if op < 4 else int(rng.integers(1, 4 * PAGE))
                n = min(int(cache.lengths[s]) + grow, cache.span)
                cache.ensure(s, n)
                cache.prepare_write(s, n)
                cache.lengths[s] = n
            elif op == 5 and live:
                s = int(rng.choice(live))
                toks = prompts.get(s, ())[:int(cache.lengths[s])]
                if len(toks):
                    cache.register_prefix(s, toks)
            elif op == 6 and live:
                cache.free_slot(int(rng.choice(live)))
            elif op == 7 and defrag and rng.random() < 0.2:
                cache.defrag()
        except OutOfPages:
            pass
        if on_step is not None:
            on_step(i)


@pytest.mark.parametrize("materialize", [False, True],
                         ids=["bookkeeping-only", "with-a-pool"])
@pytest.mark.parametrize("g", POOLS)
def test_invariants_hold_after_every_operation_of_a_churn(g, materialize):
    """``check_invariants`` (no page both held ahead and owned, every run's
    count right, nothing leaked) after each operation, and ``num_free_pages``
    reads what a stack of single pages would: every page no slot and no
    index node references, those held ahead for a slot included."""
    cache = _cache(g, materialize=materialize)

    def check(_):
        cache.check_invariants()
        assert cache.num_free_pages == \
            cache.num_pages - 1 - int(np.sum(cache._refcount > 0))

    _churn(cache, seed=g, steps=1500 if not materialize else 600,
           on_step=check, defrag=materialize)
    assert cache.prefix_counters["hits"] and cache.prefix_counters["cow_forks"]


@pytest.mark.parametrize("g", [4, 8])
@pytest.mark.parametrize("tokens", [1, PAGE, 3 * PAGE + 1, 8 * PAGE,
                                    11 * PAGE + 5])
def test_a_prompts_pages_are_runs_at_table_aligned_positions(g, tokens):
    """ONE ``ensure`` of a prompt, on a pool whose free pages a churn has
    scattered first: every whole group of the table is a run by construction
    (not by the order of a stack), and what the prompt does not cover of its
    last run is held ahead for it."""
    cache = _cache(g, num_pages=81, prefix=False)
    rng = np.random.default_rng(tokens)
    for s in [cache.alloc_slot() for _ in range(4)]:
        cache.ensure(s, int(rng.integers(1, 6 * PAGE)))
    cache.free_slot(2)
    cache.free_slot(0)
    slot = cache.alloc_slot()
    cache.ensure(slot, tokens)
    cache.check_invariants()
    pages = cache._slot_pages[slot]
    assert len(pages) == cache.pages_for(tokens)
    assert _runs(cache, slot).all()
    assert all((pages[j] - 1) % g == 0 for j in range(0, len(pages), g))
    assert len(cache._ahead.get(slot, ())) == -len(pages) % g


@pytest.mark.parametrize("g", [4, 8])
def test_slots_that_grow_in_lockstep_grow_into_the_runs_they_hold(g):
    """Every slot a page at a time, turn by turn (a saturated batch's decode
    steps): a slot that reaches a new group takes a whole run and holds the
    rest ahead, so its next growths cost nothing and its pages stay runs,
    where a stack of single pages would deal each run out across the slots."""
    cache = _cache(g, num_pages=1 + SLOTS * PAGES_PER_SLOT, prefix=False)
    slots = [cache.alloc_slot() for _ in range(SLOTS)]
    for n in range(1, PAGES_PER_SLOT + 1):
        for s in slots:
            free = cache.num_free_pages
            cache.ensure(s, n * PAGE)
            assert cache.num_free_pages == free - 1
            assert _runs(cache, s).all()
        cache.check_invariants()
    assert cache.num_free_pages == 0 and not cache._ahead


@pytest.mark.parametrize("g", [4, 8])
def test_a_freed_run_is_whole_again(g):
    """Slots freed in any order give their runs back whole (a count a run),
    pages held ahead included: the next prompt takes runs everywhere."""
    cache = _cache(g, num_pages=1 + SLOTS * PAGES_PER_SLOT, prefix=False)
    slots = [cache.alloc_slot() for _ in range(SLOTS)]
    for n in range(1, PAGES_PER_SLOT):           # lockstep, a page short
        for s in slots:
            cache.ensure(s, n * PAGE)
    for s in (3, 0, 4):
        cache.free_slot(s)
    cache.check_invariants()
    assert len(cache._whole) == 3 * PAGES_PER_SLOT // g
    s = cache.alloc_slot()
    cache.ensure(s, PAGES_PER_SLOT * PAGE)
    runs = _runs(cache, s)
    assert runs.all() and len(runs) == PAGES_PER_SLOT // g


def test_pages_held_ahead_are_taken_back_before_out_of_pages():
    """Held-ahead pages are free pages to everyone who asks: counted by
    ``num_free_pages``, taken (the farthest first) by another slot's
    ``ensure`` or fork once no other page is free, and only then
    ``OutOfPages``, which allocates nothing."""
    cache = _cache(4, num_pages=9, prefix=False, slots=3, pages_per_slot=8)
    a, b, c = (cache.alloc_slot() for _ in range(3))
    cache.ensure(a, 1)
    cache.ensure(b, 1)
    assert cache._ahead == {a: [4, 3, 2], b: [8, 7, 6]}
    assert cache.num_free_pages == 6 and not cache._whole
    cache.ensure(c, 4 * PAGE)                   # every other page is held
    assert cache._slot_pages[c] == [4, 3, 2, 8]
    assert cache._ahead == {b: [7, 6]} and cache.num_free_pages == 2
    cache.check_invariants()
    before = (cache.page_table.copy(), dict(cache._ahead))
    with pytest.raises(OutOfPages, match="needs 3 page"):
        cache.ensure(a, 4 * PAGE)
    assert (cache.page_table == before[0]).all() and cache._ahead == before[1]
    cache.ensure(a, 3 * PAGE)                   # the last two, b's
    assert cache._slot_pages[a] == [1, 7, 6] and not cache._ahead
    assert cache.num_free_pages == 0
    cache.check_invariants()
    cache.free_slot(c)
    cache.ensure(b, 2 * PAGE)
    assert cache._slot_pages[b][0] == 5 and cache.num_free_pages == 3
    cache.check_invariants()


@pytest.mark.parametrize("g", [4, 8])
def test_a_forked_page_costs_its_group_its_run_and_nothing_else(g):
    """A page is still the unit of sharing and forking: a published prompt's
    last page forks at the first write, its group is no run any more (the
    kernel sees that in the table), the pages held ahead of the page that
    was left behind go back, and the groups grown after it are runs again."""
    cache = _cache(g, num_pages=81, pages_per_slot=4 * g)
    toks = np.arange(2 * g * PAGE + 3) % 7
    s = cache.alloc_slot()
    cache.ensure(s, len(toks))
    cache.lengths[s] = len(toks)
    assert len(cache._ahead[s]) == g - 1
    cache.register_prefix(s, toks)
    cache.ensure(s, len(toks) + 1)
    pairs = cache.prepare_write(s, len(toks) + 1)
    assert len(pairs) == 1 and s not in cache._ahead
    cache.check_invariants()
    cache.lengths[s] = len(toks) + 1
    cache.ensure(s, 4 * g * PAGE)
    # (the forked page's own group is single pages: a run only if they
    # happen to lie adjacent, which the kernel sees in the table)
    runs = _runs(cache, s)
    assert runs[[0, 1, 3]].all() and len(runs) == 4
    assert pairs[0][1] == cache.page_table[s, 2 * g]
    # a second stream maps the published pages: shared ones are runs too
    t = cache.alloc_slot()
    assert cache.share_prefix(t, toks) == len(toks)
    assert _runs(cache, t).tolist() == [True, True]
    cache.check_invariants()


@pytest.mark.parametrize("g", POOLS)
def test_state_dict_round_trips_pages_held_ahead(g):
    """A snapshot taken mid-churn restores the same tables, the same free
    runs in the same order and the same pages held ahead: both caches then
    hand out the same pages."""
    cache, twin = (_cache(g, materialize=True) for _ in range(2))
    _churn(cache, seed=7, steps=120)
    for s in range(SLOTS):          # somebody holds ahead where runs are long
        if cache.active[s]:
            cache.free_slot(s)
    s = cache.alloc_slot()
    cache.ensure(s, 1)
    cache.lengths[s] = 1
    assert (g == 1) == (not cache._ahead)
    state = cache.state_dict()
    assert ("ahead" in state) == (g > 1)
    twin.load_state_dict(state)
    twin.check_invariants()
    assert twin._ahead == cache._ahead and twin._whole == cache._whole
    assert list(twin._broken) == list(cache._broken)
    assert twin._runs == cache._runs
    assert twin.num_free_pages == cache.num_free_pages
    for c in (cache, twin):
        _churn(c, seed=11, steps=80)
    assert (twin.page_table == cache.page_table).all()


@pytest.mark.parametrize("g", POOLS)
def test_the_bookkeeping_only_mode_hands_out_the_same_pages(g):
    """The allocator the split runtime shares (``materialize=False``: no
    pool to read a page's bytes off) has the same runs and deals the same
    pages as the one that holds the pool."""
    bare, full = _cache(g, materialize=False), _cache(g, materialize=True)
    assert full.pages_per_slot == POOLS[g][2]
    for cache in (bare, full):
        _churn(cache, seed=3, steps=250, defrag=False)
    assert full.pool is not None and bare.pool is None
    assert (bare.page_table == full.page_table).all()
    assert bare._ahead == full._ahead and bare._runs == full._runs
    assert paged_kv.walk_geometry(full.pool, POOLS[g][2]) == (32, g)


class _Stack:
    """The allocator before runs: ONE LIFO list of single pages."""

    def __init__(self, num_pages, slots):
        self.free = list(range(num_pages - 1, 0, -1))
        self.pages = [[] for _ in range(slots)]

    def ensure(self, slot, n_pages):
        need = n_pages - len(self.pages[slot])
        if need > len(self.free):
            raise OutOfPages
        for _ in range(max(need, 0)):
            self.pages[slot].append(self.free.pop())

    def free_slot(self, slot):
        self.free.extend(reversed(self.pages[slot]))
        self.pages[slot] = []


def test_a_run_of_one_page_hands_out_the_pages_a_stack_would():
    """Where a page is a fetch by itself (32 KB and up: four of the nine
    cells) the allocator is what it was: the page ids of a seeded churn of
    admissions, growths and evictions equal a LIFO stack's, id for id, the
    free list in the same order, and nothing is ever held ahead."""
    cache, stack = _cache(1, prefix=False), _Stack(49, SLOTS)
    rng = np.random.default_rng(5)
    for _ in range(2000):
        live = [s for s in range(SLOTS) if cache.active[s]]
        op = rng.integers(0, 4)
        if op == 0 and len(live) < SLOTS:
            cache.alloc_slot()
        elif op in (1, 2) and live:
            s = int(rng.choice(live))
            n = min(int(cache.lengths[s]) + int(rng.integers(1, 3 * PAGE)),
                    cache.span)
            try:
                stack.ensure(s, cache.pages_for(n))
            except OutOfPages:
                with pytest.raises(OutOfPages):
                    cache.ensure(s, n)
                continue
            cache.ensure(s, n)
            cache.lengths[s] = n
        elif op == 3 and live:
            s = int(rng.choice(live))
            cache.free_slot(s)
            stack.free_slot(s)
        assert cache._slot_pages == stack.pages
        assert cache._free_pages() == stack.free and not cache._ahead
    cache.check_invariants()


def test_the_length_of_a_run_is_read_off_a_pages_bytes():
    """One rule, in one place, for the allocator and the kernel: a page of
    32 KB or more goes alone; a smaller one in the smallest power of two of
    pages that makes a fetch of 64 KB, at most eight, no more than a slot's
    table holds; the cells' pages by their bytes."""
    rule = flash_attention.walk_run_pages
    kb = 1024
    assert [rule(b * kb, 128) for b in (1, 4, 8, 12, 16, 20, 31, 32, 64)] == \
        [8, 8, 8, 8, 4, 4, 4, 1, 1]
    assert [rule(256, pps) for pps in (1, 2, 3, 4, 7, 8, 128)] == \
        [1, 2, 2, 4, 4, 8, 8]
    # qwen2-0.5b / -1.5b rows of bf16, a mistral4 and a longcat latent row,
    # mellum's and granite's rows
    for lanes, want in ((2 * 2 * 64, 8), (2 * 2 * 128, 4), (384, 8),
                        (640, 4), (2 * 4 * 128, 1), (2 * 8 * 128, 1)):
        assert rule(16 * lanes * 2, 128) == want


def test_a_seeded_closed_loop_emits_the_same_tokens_on_the_walk(monkeypatch):
    """Through the batcher: a closed loop over a pool too small for its
    streams (evictions and readmissions) with prompts that share a published
    prefix (hits, forks), built on the page walk with runs of eight (the
    choice forced as a TPU would make it, the kernel interpreted) against
    the same service on the page gather: the same tokens, and the counters
    of the walk say that runs went as runs."""
    from jax.experimental.pallas import tpu as pltpu
    from edgellm_tpu.models.transformer import init_params
    from edgellm_tpu.serve import batching

    cfg = tiny_config("qwen2", num_layers=2, hidden_size=256, num_heads=4,
                      vocab_size=64)
    params = init_params(cfg, jax.random.key(0))
    bcfg = batching.BatchingConfig(
        page_size=8, num_pages=22, max_slots=3, pages_per_slot=12,
        prefix_cache=PrefixCacheConfig(enabled=True))
    rng = np.random.default_rng(2)
    shared = rng.integers(1, cfg.vocab_size, 27).astype(np.int32)
    prompts = [np.concatenate([shared, rng.integers(
        1, cfg.vocab_size, n).astype(np.int32)]) for n in (3, 9, 14, 1, 6)]
    step_jit, kernel = batching._batched_step_jit, \
        flash_attention.paged_decode_walk

    def serve():
        step_jit.clear_cache()
        b = batching.ContinuousBatcher(cfg, params, bcfg)
        assert b.pool.run_pages == 8
        sids = [b.submit(p, 40, rng_seed=i) for i, p in enumerate(prompts)]
        for _ in range(600):
            b.pool.check_invariants()
            if not b.step():
                break
        assert set(b.results) == set(sids)
        return b, [b.results[s] for s in sids]

    with jax.default_matmul_precision("highest"):
        gather, want = serve()
        monkeypatch.setattr(paged_kv, "_on_tpu", lambda: True)
        monkeypatch.setattr(
            flash_attention, "paged_decode_walk",
            lambda *a, **k: jax.block_until_ready(kernel(
                *a, **k, interpret=pltpu.InterpretParams())))

        def waited(*args):      # the interpreter's callbacks dispatch too
            return jax.block_until_ready(step_jit(*args))

        waited._cache_size = step_jit._cache_size
        monkeypatch.setattr(batching, "_batched_step_jit", waited)
        walker, got = serve()
    step_jit.clear_cache()
    for g, w in zip(got, want):
        assert np.array_equal(g, w), (g.tolist(), w.tolist())
    rep, oracle = walker.report(), gather.report()
    assert rep["decode_read"] == paged_kv.PAGE_WALK
    assert rep["evicted"] == oracle["evicted"] > 0
    assert rep["prefix"]["hits"] == oracle["prefix"]["hits"] > 0
    assert rep["prefix"]["cow_forks"] > 0
    walked, in_runs = rep["attend_pages_walked"], rep["attend_pages_in_runs"]
    assert 0 < in_runs < walked and in_runs % 8 == 0
    assert rep["attend_dmas"] == walked - in_runs // 8 * 7
    assert oracle["attend_pages_in_runs"] == oracle["attend_dmas"] == 0


@pytest.mark.parametrize("broken", ["no-group", "a-first-group", "a-later-group"])
def test_the_batchers_count_of_pages_in_runs_is_a_recount_of_its_table(
        monkeypatch, broken):
    """``ContinuousBatcher._pages_in_runs`` (the count behind the launch, of
    the slots that reach a whole group and the blocks they reach) against a
    page-by-page recount of the table the kernel is handed: a group counts
    where it is live whole, names adjacent pages and no group before it in
    its block of the walk is broken; idle slots and slots short of a group
    count nothing. And a batcher whose allocator deals other runs than its
    walk takes is refused."""
    from edgellm_tpu.models.transformer import init_params
    from edgellm_tpu.serve import batching

    monkeypatch.setattr(paged_kv, "_on_tpu", lambda: True)
    cfg = tiny_config("qwen2", num_layers=1, hidden_size=256, num_heads=4,
                      vocab_size=64)
    bcfg = batching.BatchingConfig(page_size=8, num_pages=200, max_slots=4,
                                   pages_per_slot=80)
    b = batching.ContinuousBatcher(
        cfg, init_params(cfg, jax.random.key(0)), bcfg)
    ppb, run = b.attend_walk
    assert (b.decode_read, run, b.pool.run_pages) == (paged_kv.PAGE_WALK, 8, 8)
    assert ppb % run == 0 and ppb < 80      # the longest slot takes blocks
    lengths = (70 * 8 + 3, 11, 9 * 8, 0)    # slot 3 stays idle
    for n in lengths[:3]:
        b.pool.ensure(b.pool.alloc_slot(), n)
    table = b.pool.page_table
    if broken != "no-group":
        g = 0 if broken == "a-first-group" else 2
        table[0, [g * run, g * run + 1]] = table[0, [g * run + 1, g * run]]
    reached = np.asarray(lengths) // 8 + 1
    want = 0
    for s, live in enumerate(reached):
        for blk in range(0, live, ppb):
            for at in range(blk, min(blk + ppb, live) - run + 1, run):
                ids = table[s, at:at + run]
                if not (np.diff(ids) == 1).all():
                    break               # the block's later groups go alone
                want += run
    assert b._pages_in_runs(reached, b.attend_walk) == want > 0
    assert b._pages_in_runs(np.ones(4, np.int64), b.attend_walk) == 0  # idle
    b.pool.run_pages = 4
    with pytest.raises(AssertionError, match="hands out runs of 4"):
        b._read_paths()


#: the benchmark's nine configurations (``benchmark/configs/*.json``; a test
#: must not read them): the lanes of a stored row of each leaf of the pool, a
#: slot's table, and the run. Every page is 16 rows of bfloat16. Eight are
#: what they were before the index walk; keye's was 1, read off its K/V leaf
#: alone (PERF.md §6 "PR 51").
CELL_RUNS = {
    "qwen2-0.5b": ((2 * 2 * 64,), 128, 8),
    "qwen2-1.5b-split4": ((2 * 2 * 128,), 128, 4),
    "granite-4.0-h-small-ep2": ((2 * 8 * 128,), 128, 1),
    "mellum2-12b-a2.5b-pp4": ((2 * 4 * 128,), 384, 1),
    "mistral-small-4-119b-ep4": ((384,), 768, 8),
    "trinity-mini-pp8": ((2 * 4 * 128,), 768, 1),
    "longcat-flash-chat-ep32": ((640,), 192, 4),
    "lfm2-8b-a1b-pp2": ((2 * 8 * 64,), 288, 1),
    "keye-vl-2.0-30b-a3b-ep4": ((2 * 4 * 128, 128), 1280, 8),
    # 16 x 1280: a latent leaf (20 KB a page: four to a run by itself) and
    # the index keys' (4 KB: eight), which sets the pool's runs
    "deepseek-v3.2-exp-ep16": ((640, 128), 1280, 8),
    # the full layers' group, deepseek's two leaves at 32 x 1280 ...
    "dots3-note-prev-ep8": ((640, 128), 1280, 8),
    # ... and the window layers' ring group: ONE leaf of 1152-lane latent
    # rows, a page of 36 KB a fetch by itself, 33 entries a ring
    "dots3-note-prev-ep8.rings": ((1152,), 33, 1),
}


@pytest.mark.parametrize("cell", sorted(CELL_RUNS))
def test_the_run_of_each_cells_pool_is_its_smallest_pages(cell):
    """``paged_kv.pool_run_pages``: the longest run any leaf's walk takes,
    which is the rule on the pool's smallest page; a leaf's own walk takes
    what ITS page says."""
    lanes, pps, want = CELL_RUNS[cell]
    pool = tuple(jax.ShapeDtypeStruct((1, 3, PAGE, n), jnp.bfloat16)
                 for n in lanes)
    assert paged_kv.pool_run_pages(pool, pps) == want
    assert want == flash_attention.walk_run_pages(
        PAGE * min(lanes) * 2, pps)
    own = [paged_kv.leaf_run_pages(leaf, pps) for leaf in pool]
    assert own == ([want] if len(lanes) == 1
                   else [{2 * 4 * 128: 1, 640: 4}[lanes[0]], 8])


def test_a_two_leaf_pool_at_the_keye_geometry_deals_runs_of_eight(
        monkeypatch):
    """An ``IndexedPagePool`` at the keye cell's geometry (32 slots x 1280
    pages of 16 rows, published widths, bfloat16; the leaves are shapes
    here, which is all an allocator reads): its runs are read off the
    index-key leaf (4 KB a page, eight to a run) where the K/V leaf's page
    (32 KB) is a fetch by itself. Prompts of 512 and 1024 pages are whole
    runs; slots that grow in lockstep take a run each and hold seven pages
    ahead, so that their pages do not interleave; the pool that fits 32 full
    slots of single pages fits them in runs; an evicted slot gives back its
    runs and what it held ahead, whole; the whole-cache snapshot stays
    refused by name."""
    import dataclasses

    from edgellm_tpu.models.configs import KEYE_VL_2_0_30B_A3B
    from edgellm_tpu.models.hybrid import IndexKeysUnsupported

    cfg = dataclasses.replace(KEYE_VL_2_0_30B_A3B, num_layers=1,
                              layer_types=("sparse_attention",))
    init_pool = paged_kv.init_pool
    monkeypatch.setattr(paged_kv, "init_pool", lambda *a, **k: jax.eval_shape(
        lambda: init_pool(*a, **k)))
    slots, pps = 32, 1280
    cache = PagedKVCache(cfg, num_pages=slots * pps + 1, page_size=PAGE,
                         max_slots=slots, pages_per_slot=pps,
                         dtype=jnp.bfloat16)
    pool = cache.pool
    assert isinstance(pool, paged_kv.IndexedPagePool)
    assert [a.shape[-1] for a in pool] == [1024, 128]
    assert paged_kv.page_leaf_bytes(cfg, PAGE, dtype=jnp.bfloat16) == 4096
    assert cache.run_pages == paged_kv.pool_run_pages(pool, pps) == 8
    assert paged_kv.walk_geometry(paged_kv.PagePool(pool.kv), pps) == (32, 1)
    assert paged_kv.index_walk_geometry(pool, pps) == (128, 8)

    def whole_groups(slot):
        return _runs(cache, slot).all()

    # admission: the cell's two prompts, 8192 and 16384 tokens
    for s in range(slots):
        assert cache.alloc_slot() == s
        tokens = (8192, 16384)[s % 2]
        cache.ensure(s, tokens)
        cache.lengths[s] = tokens
        assert whole_groups(s) and len(cache._slot_pages[s]) == tokens // PAGE
    assert not cache._ahead                     # whole runs: nothing held
    cache.check_invariants()
    # growth, every slot a page in lockstep: a run each, seven held ahead
    for s in range(slots):
        cache.lengths[s] += 1
        cache.ensure(s, int(cache.lengths[s]))
    assert all(len(cache._ahead[s]) == 7 for s in range(slots))
    heads = sorted(cache._slot_pages[s][-1] for s in range(slots))
    assert all((p - 1) % 8 == 0 for p in heads) and len(set(heads)) == slots
    free = cache.num_free_pages
    for step in range(1, 8):                    # the next seven are their own
        for s in range(slots):
            cache.lengths[s] += PAGE
            cache.ensure(s, int(cache.lengths[s]))
        assert cache.num_free_pages == free - step * slots
    assert not cache._ahead and all(whole_groups(s) for s in range(slots))
    cache.check_invariants()
    # an eviction mid-group gives the run back whole, what was held too
    cache.lengths[3] += PAGE
    cache.ensure(3, int(cache.lengths[3]))
    assert len(cache._ahead[3]) == 7 and not cache._broken
    held, whole = cache.num_free_pages, len(cache._whole)
    pages = len(cache._slot_pages[3])
    cache.free_slot(3)
    assert not cache._ahead and not cache._broken
    assert cache.num_free_pages == held + pages
    assert len(cache._whole) == whole + -(-pages // 8)
    # every slot to its full span: 32 x 160 runs are the pool's 5120
    assert cache.alloc_slot() == 3
    for s in range(slots):
        cache.ensure(s, pps * PAGE)
        cache.lengths[s] = pps * PAGE
        assert whole_groups(s)
    assert cache.num_free_pages == 0 and not cache._whole
    cache.check_invariants()
    for s in range(slots):
        cache.free_slot(s)
    assert len(cache._whole) == slots * pps // 8 and not cache._broken
    cache.check_invariants()
    with pytest.raises(IndexKeysUnsupported, match="state_dict"):
        cache.state_dict()
