"""The page walk against the page gather: ``flash_attention.paged_decode_walk``
in TPU-interpret mode on the CPU, held to ``paged_kv.read_span`` +
``paged_kv.attend_rows`` (the XLA path, which stays the oracle) at toy sizes.

What a chip would do with the kernel is ``tests/test_chip_compile.py``'s (it
compiles) and the benchmark's (it is timed); here is what it computes: the
same rows attended, whatever the table says about where they lie, and nothing
of a page or a row that no length covers.
"""
import dataclasses
import functools
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

from edgellm_tpu.models import flash_attention, hybrid, paged_kv
from edgellm_tpu.models import tiny_config
from edgellm_tpu.models.configs import (tiny_afmoe_config,
                                        tiny_hybrid_config,
                                        tiny_lfm2_moe_config,
                                        tiny_longcat_flash_config,
                                        tiny_mellum_config,
                                        tiny_mistral4_config)
from edgellm_tpu.models.transformer import init_params

PAGE, PAGES_PER_SLOT, LAYERS, LAYER = 16, 8, 3, 1
SPAN = PAGE * PAGES_PER_SLOT

#: (K lanes W = KV * hd of a row of 2 W, query heads, KV heads): the cells'
#: three shapes
GEOMETRIES = {"w128-h14-kv2": (128, 14, 2), "w256-h12-kv2": (256, 12, 2),
              "w512-h32-kv4": (512, 32, 4)}

#: name -> (lengths a slot, pages in scrambled pool order?, NaN in every page
#: no slot holds?). A length of 1 is an idle slot: its table row is the trash
#: page. Every table has a neighbour of another length beside the slot it is
#: named for, so that the pipeline crosses a slot boundary both ways.
TABLES = {
    "ends-on-a-page-edge": ((48, 5, 32), False, False),
    "ends-mid-page": ((37, 64, 3), False, False),
    "idle-slots-on-the-trash-page": ((1, 21, 1, 1), False, False),
    "full-span": ((SPAN, 17, SPAN), False, False),
    "scrambled-pool-order": ((100, 33, 1, 16, 77), True, False),
    "unfetched-pages-hold-nan": ((60, 1, 16, 90), True, True),
}


def _pool_and_table(width, lengths, scrambled, poisoned, dtype, seed):
    rng = np.random.default_rng(seed)
    slots = len(lengths)
    pages = slots * PAGES_PER_SLOT + 1
    k, v = (rng.standard_normal((LAYERS, pages, PAGE, width))
            .astype(np.float32) for _ in range(2))
    order = (rng.permutation(np.arange(1, pages)) if scrambled
             else np.arange(1, pages))
    table = np.zeros((slots, PAGES_PER_SLOT), np.int32)
    held = np.zeros((pages,), bool)
    taken = 0
    for i, n in enumerate(lengths):
        if n == 1:
            continue        # idle: every entry the trash page, length 0 + 1
        for j in range(-(-n // PAGE)):
            table[i, j] = order[taken]
            held[order[taken]] = True
            taken += 1
    held[0] = True          # the trash page is fetched, and finite
    if poisoned:
        k[:, ~held] = np.nan
        v[:, ~held] = np.nan
    pool = paged_kv.PagePool(paged_kv.join_kv(jnp.asarray(k, dtype),
                                              jnp.asarray(v, dtype)))
    return pool, jnp.asarray(table), held


_KERNEL = flash_attention.paged_decode_walk      # before any test patches it


def _interpreted(*args, **kwargs):
    """The kernel under the TPU interpreter, WAITED FOR: its host callbacks
    run JAX operations of their own, and deadlock against a main thread that
    has gone on to dispatch the next one."""
    return jax.block_until_ready(_KERNEL(
        *args, **kwargs, interpret=pltpu.InterpretParams()))


def _walk(q, pool, table, lengths, pages_per_block=None):
    """``paged_kv.attend_pages`` with the kernel interpreted."""
    hd = q.shape[-1]
    own, qz = paged_kv._group_lanes(q, pool.k_lanes // hd)
    out = _interpreted(
        qz, paged_kv._pages(pool.kv, 1),
        LAYER * pool.num_pages + table, lengths, scale=float(hd ** -0.5),
        pages_per_block=pages_per_block)
    return paged_kv._own_lanes(out, own)


def _gather(q, pool, table, lengths, held):
    """The oracle, over a pool whose unheld pages are made finite: 0 x NaN
    is what the gather's masked rows would make of them."""
    clean = paged_kv.PagePool(*(
        jnp.where(held[None, :, None, None], a, 0) for a in pool))
    kg, vg = paged_kv.read_span(clean, LAYER, table, q.dtype)
    return paged_kv.attend_rows(q, kg, vg, lengths)


@pytest.mark.parametrize("table", TABLES)
@pytest.mark.parametrize("geometry", GEOMETRIES)
def test_walk_equals_gather_float32(geometry, table):
    """Float32 rows: only the order of the float32 sums differs, so the two
    agree to rounding, on every table kind, with blocks of 2 pages so that
    most slots take several blocks and end inside one."""
    width, heads, kv = GEOMETRIES[geometry]
    lengths, scrambled, poisoned = TABLES[table]
    pool, tab, held = _pool_and_table(width, lengths, scrambled, poisoned,
                                      jnp.float32, seed=len(table))
    q = jnp.asarray(np.random.default_rng(1).standard_normal(
        (len(lengths), 1, heads, width // kv)), jnp.float32)
    lens = jnp.asarray(lengths, jnp.int32)
    got = _walk(q, pool, tab, lens, pages_per_block=2)
    want = _gather(q, pool, tab, lens, jnp.asarray(held))
    assert got.shape == want.shape and got.dtype == want.dtype
    assert bool(jnp.isfinite(got).all())
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("geometry", GEOMETRIES)
def test_walk_equals_gather_bfloat16_default_block(geometry):
    """The cells' dtype and the block :func:`paged_walk_pages_per_block`
    picks (a whole toy span in one block): equal to bf16 rounding of the
    probabilities, finite over a poisoned pool."""
    width, heads, kv = GEOMETRIES[geometry]
    lengths = (SPAN, 1, 37, 16, 90)
    pool, tab, held = _pool_and_table(width, lengths, True, True,
                                      jnp.bfloat16, seed=7)
    q = jnp.asarray(np.random.default_rng(2).standard_normal(
        (len(lengths), 1, heads, width // kv)), jnp.bfloat16)
    lens = jnp.asarray(lengths, jnp.int32)
    got = _walk(q, pool, tab, lens).astype(jnp.float32)
    want = _gather(q, pool, tab, lens, jnp.asarray(held)).astype(jnp.float32)
    assert bool(jnp.isfinite(got).all())
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-2)


@pytest.mark.parametrize("geometry", GEOMETRIES)
def test_float32_query_over_a_bfloat16_pool(geometry):
    """The split runtime's late stages: a float32 query meets bf16 rows in
    float32, in the kernel as in ``attend_rows``' einsums, so the two agree
    to the order of the float32 sums; a scrambled, NaN-poisoned pool, blocks
    of 2 pages and of the rule's size."""
    width, heads, kv = GEOMETRIES[geometry]
    lengths = (SPAN, 1, 37, 16, 90)
    pool, tab, held = _pool_and_table(width, lengths, True, True,
                                      jnp.bfloat16, seed=9)
    q = jnp.asarray(np.random.default_rng(3).standard_normal(
        (len(lengths), 1, heads, width // kv)), jnp.float32)
    lens = jnp.asarray(lengths, jnp.int32)
    want = _gather(q, pool, tab, lens, jnp.asarray(held))
    for ppb in (2, None):
        got = _walk(q, pool, tab, lens, pages_per_block=ppb)
        assert got.dtype == want.dtype == jnp.float32
        assert bool(jnp.isfinite(got).all())
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)


def test_a_row_as_wide_as_the_query_is_keys_and_values():
    """A row of the query's width (a latent row is both key and value): the
    weighted sum of the rows the scores were taken over, where a row twice
    as wide gives its second half."""
    pool, tab, held = _pool_and_table(128, (40, 1, 16), True, True,
                                      jnp.float32, seed=3)
    lens = jnp.asarray((40, 1, 16), jnp.int32)
    qz = jnp.asarray(np.random.default_rng(4).standard_normal((3, 5, 128)),
                     jnp.float32)
    latent = pool.kv[..., :128]
    pages = paged_kv._pages(latent, 1)
    got = _interpreted(qz, pages, LAYER * pool.num_pages + tab, lens,
                       scale=0.125, pages_per_block=2)
    rows = paged_kv._gather_pages(
        jnp.where(jnp.asarray(held)[None, :, None, None], latent, 0), LAYER,
        tab)
    want = paged_kv.attend_latent(qz, rows, lens, 64)   # 64 ** -0.5 = 0.125
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    with pytest.raises(ValueError, match="neither a query's 128"):
        _KERNEL(qz, paged_kv._pages(pool.kv[..., :192], 1), tab, lens,
                scale=0.125)


def test_read_path_is_read_off_the_pool(monkeypatch):
    """The walk for an fp pool of whole tiles on a TPU, the gather for
    everything else — no flag: the pool's type, its shape and the backend.
    A window layer's pool of rings is asked what every pool is asked: its
    table is a ring, which the walk takes (PR 40)."""
    cfg = tiny_config("qwen2", num_layers=2, hidden_size=256, num_heads=4,
                      vocab_size=64)                     # KV 2 x hd 64
    fp = paged_kv.init_pool(cfg, 9, 16, jnp.bfloat16)
    assert len(fp) == 1 and fp.kv.shape == (2, 9, 16, 2 * 128)
    k_lanes = fp.kv[..., :128]
    assert paged_kv.decode_read_path(
        paged_kv.LatentPool(k_lanes)) == paged_kv.PAGE_GATHER      # on a cpu
    quant = paged_kv.init_quant_pool(cfg, 9, 16, "int8_per_channel")
    # a row of 64 K lanes and 64 V lanes is one whole lane tile, of which
    # the kernel would slice half: whole tiles are asked of W, not of 2 W
    narrow = paged_kv.PagePool(k_lanes)
    short = paged_kv.PagePool(fp.kv[:, :, :8])
    # a window group's pools: 3 slots' rings of 4 pages and the trash page
    sliding = tiny_mellum_config(sliding_window=40, head_dim=64)
    rings = paged_kv.init_pool(sliding, 3 * 4 + 1, 16, jnp.bfloat16,
                               layers=sliding.window_layers)
    quant_rings = paged_kv.init_quant_pool(sliding, 3 * 4 + 1, 16,
                                           "int8_per_channel")
    part_rings = paged_kv.PagePool(rings.kv[..., :128])
    assert rings.kv.shape == (6, 13, 16, 2 * 128)
    assert paged_kv.decode_read_path(fp) == paged_kv.PAGE_GATHER  # on a cpu
    assert paged_kv.decode_read_path(rings) == paged_kv.PAGE_GATHER
    monkeypatch.setattr(paged_kv, "_on_tpu", lambda: True)
    assert paged_kv.decode_read_path(fp) == paged_kv.PAGE_WALK
    assert paged_kv.decode_read_path(
        paged_kv.PagePool(fp.kv[None])) == paged_kv.PAGE_WALK
    # a latent pool's one leaf is asked what a row's K lanes are asked
    assert paged_kv.decode_read_path(
        paged_kv.LatentPool(k_lanes)) == paged_kv.PAGE_WALK
    assert paged_kv.decode_read_path(rings) == paged_kv.PAGE_WALK
    for pool in (quant, narrow, short, quant_rings, part_rings,
                 paged_kv.LatentPool(k_lanes[..., :64]),
                 paged_kv.LatentPool(short.kv)):
        assert paged_kv.decode_read_path(pool) == paged_kv.PAGE_GATHER


@pytest.mark.parametrize("compute", ["float32", "bfloat16"])
def test_paged_step_on_the_walk_equals_the_step_on_the_gather(monkeypatch,
                                                              compute):
    """The whole ragged step built on the walk (the choice forced as a TPU
    would make it, the kernel interpreted) against the same step on the
    gather: logits and the written pool, float32 pages under either compute
    dtype (a bf16 query meets float32 rows in float32, as the einsums do)."""
    cfg = tiny_config("qwen2", num_layers=2, hidden_size=256, num_heads=4,
                      vocab_size=64)
    params = init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(5)
    pool = paged_kv.PagePool(*(
        jnp.asarray(rng.standard_normal(a.shape), a.dtype)
        for a in paged_kv.init_pool(cfg, 13, 8, jnp.float32)))
    table = jnp.asarray([[1, 2, 3, 0], [0, 0, 0, 0], [7, 5, 0, 0]], jnp.int32)
    lens = jnp.asarray([20, 0, 9], jnp.int32)
    toks = jnp.asarray([3, 0, 5], jnp.int32)
    step = functools.partial(paged_kv.paged_decode_step, cfg, params, pool,
                             table, lens, toks,
                             compute_dtype=jnp.dtype(compute))
    want, want_pool = step()
    monkeypatch.setattr(paged_kv, "_on_tpu", lambda: True)
    monkeypatch.setattr(
        flash_attention, "paged_decode_walk",
        functools.partial(flash_attention.paged_decode_walk,
                          interpret=pltpu.InterpretParams()))
    got, got_pool = jax.block_until_ready(step())
    tol = 1e-5 if compute == "float32" else 3e-2
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=tol)
    np.testing.assert_allclose(np.asarray(got_pool.kv),
                               np.asarray(want_pool.kv), atol=tol)


#: the toy stacks that keep recurrent state beside ONE K/V layer of one
#: whole lane tile of K lanes (2 KV heads of 64): the granite and lfm2 cells'
STATE_STACKS = {
    "granitemoehybrid": lambda: tiny_hybrid_config(hidden_size=256),
    "lfm2_moe": lambda: tiny_lfm2_moe_config(hidden_size=256),
}


@pytest.mark.parametrize("family", STATE_STACKS)
def test_hybrid_step_on_the_walk_equals_the_step_on_the_gather(monkeypatch,
                                                               family):
    """``paged_decode_step_hybrid`` of a stack with recurrent state, its K/V
    pool's one leaf handed over as a latent stack's is: the step built on the
    walk (the kernel interpreted) against the step on the gather, logits,
    the written pool and the state. Slot 1 is idle, slot 2 writes the first
    row of a new page; every page no table names holds NaN under the walk."""
    cfg = STATE_STACKS[family]()
    assert cfg.kv_row_lanes == 128 and cfg.kv_layers == 1
    params = init_params(cfg, jax.random.key(0))
    page = 8
    table = np.asarray([[1, 2, 3, 0], [0, 0, 0, 0], [7, 5, 0, 0]], np.int32)
    lens = np.asarray([2 * page + 4, 0, page], np.int32)
    rng = np.random.default_rng(13)
    pool = paged_kv.init_pool(cfg, 9, page)
    assert pool.kv.shape == (1, 9, page, 256)
    clean = jnp.asarray(rng.standard_normal(pool.kv.shape), jnp.float32)
    state = {leaf: jnp.asarray(rng.standard_normal(a.shape), a.dtype)
             for leaf, a in paged_kv.init_slot_state(cfg, 3).items()}
    held = np.zeros((9,), bool)
    held[np.unique(table)] = True
    dead = jnp.asarray(~held)[None, :, None, None]
    step = functools.partial(
        hybrid.paged_decode_step_hybrid, cfg, params, state=state,
        expert_tokens=jnp.zeros((cfg.expert_layers, cfg.local_experts),
                                jnp.int32),
        page_table=jnp.asarray(table), lengths=jnp.asarray(lens),
        token_ids=jnp.asarray([3, 0, 5], jnp.int32))
    with jax.default_matmul_precision("highest"):
        want, want_kv, want_state, want_cnt = step(pool=clean)
        monkeypatch.setattr(paged_kv, "_on_tpu", lambda: True)
        assert paged_kv.decode_read_path(pool) == paged_kv.PAGE_WALK
        monkeypatch.setattr(flash_attention, "paged_decode_walk",
                            _interpreted)
        got, got_kv, got_state, got_cnt = jax.block_until_ready(
            step(pool=jnp.where(dead, jnp.nan, clean)))
    assert bool(jnp.isfinite(got).all())
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    np.testing.assert_array_equal(np.asarray(got_cnt), np.asarray(want_cnt))
    np.testing.assert_allclose(np.asarray(jnp.where(dead, 0, got_kv)),
                               np.asarray(jnp.where(dead, 0, want_kv)),
                               atol=1e-5)
    for leaf in want_state:
        np.testing.assert_allclose(np.asarray(got_state[leaf]),
                                   np.asarray(want_state[leaf]), atol=1e-5)


#: name -> (rows a page, parameter / query dtype, pool dtype, logit
#: tolerance): a page is whole sublane tiles of its dtype either way, and
#: the second is the mistral4 cell's pairing
LATENT_STEPS = {"float32": (8, jnp.float32, jnp.float32, 1e-5),
                "bfloat16": (16, jnp.bfloat16, jnp.bfloat16, 2e-2)}


@pytest.mark.parametrize("poisoned", [False, True],
                         ids=["clean", "dead-pages-hold-nan"])
@pytest.mark.parametrize("compute", LATENT_STEPS)
def test_latent_step_on_the_walk_equals_the_step_on_the_gather(monkeypatch,
                                                                compute,
                                                                poisoned):
    """The whole hybrid step of a toy ``mistral4`` stack built on the walk
    (the choice forced as a TPU would make it, the kernel interpreted)
    against the same step on the gather: logits and the written one-leaf
    pool. Slot 1 is idle (length 0: its row goes to the trash page, which it
    attends), slot 2 writes the first row of a new page, slot 3 the last row
    of its last page. ``poisoned``: every page no table names holds NaN
    under the walk, which must fetch none of them; nothing of a fetched
    page's rows past a length (stale or another stream's) reaches the sum."""
    page, dtype, pool_dtype, tol = LATENT_STEPS[compute]
    cfg = tiny_mistral4_config(num_layers=2)
    params = init_params(cfg, jax.random.key(0), dtype=dtype)
    table = np.asarray([[1, 2, 3, 0], [0, 0, 0, 0], [7, 5, 0, 0],
                        [9, 4, 8, 6]], np.int32)
    lens = np.asarray([2 * page + 4, 0, page, 4 * page - 1], np.int32)
    rows = paged_kv.init_pool(cfg, 13, page, pool_dtype).rows
    assert rows.shape == (2, 13, page, 128)
    rng = np.random.default_rng(11)
    clean = jnp.asarray(rng.standard_normal(rows.shape), pool_dtype)
    held = np.zeros((13,), bool)
    held[np.unique(table)] = True               # the trash page among them
    step = functools.partial(
        hybrid.paged_decode_step_hybrid, cfg, params, state=None,
        expert_tokens=jnp.zeros((2, cfg.local_experts), jnp.int32),
        page_table=jnp.asarray(table), lengths=jnp.asarray(lens),
        token_ids=jnp.asarray([3, 0, 5, 7], jnp.int32))
    want, want_rows, *_ = step(pool=clean)
    monkeypatch.setattr(paged_kv, "_on_tpu", lambda: True)
    assert paged_kv.decode_read_path(
        paged_kv.LatentPool(clean)) == paged_kv.PAGE_WALK
    walks = []

    def walk(qz, pages, *args, **kwargs):
        walks.append((qz.shape[-1], pages.shape[-1]))
        return _interpreted(qz, pages, *args, **kwargs)

    monkeypatch.setattr(flash_attention, "paged_decode_walk", walk)
    dead = jnp.asarray(~held)[None, :, None, None]
    got, got_rows, *_ = jax.block_until_ready(step(
        pool=jnp.where(dead, jnp.nan, clean) if poisoned else clean))
    # a layer a walk, the row key and value both (as wide as the query)
    assert walks == [(128, 128)] * 2
    assert bool(jnp.isfinite(got).all())
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=tol)
    np.testing.assert_allclose(
        np.asarray(jnp.where(dead, 0, got_rows), np.float32),
        np.asarray(jnp.where(dead, 0, want_rows), np.float32), atol=tol)


@pytest.mark.parametrize("read", ["page-walk", "page-gather"])
@pytest.mark.parametrize("compute", LATENT_STEPS)
def test_a_640_lane_step_of_two_sublayers_equals_the_contiguous_step(
        monkeypatch, compute, read):
    """A toy ``longcat_flash`` layer whose cached row is FIVE lane tiles (520
    latent + 8 rotated lanes stored 640 wide, the published row's width)
    through the ragged step on either read, against the contiguous absorbed
    step a stream at a time: both sublayers' rows written (the first row of a
    new page, the last of the last page), the latent lanes carrying their
    rank scale, an idle slot between the streams."""
    page, dtype, pool_dtype, tol = LATENT_STEPS[compute]
    cfg = dataclasses.replace(tiny_longcat_flash_config(num_layers=1),
                              kv_lora_rank=520)
    assert (cfg.kv_row_lanes, cfg.latent_layers) == (640, 2)
    params = init_params(cfg, jax.random.key(0), dtype=dtype)
    table = np.asarray([[1, 2, 3, 0], [0, 0, 0, 0], [7, 5, 0, 0],
                        [9, 4, 8, 6]], np.int32)
    lens = np.asarray([2 * page + 4, 0, page, 4 * page - 1], np.int32)
    toks = np.asarray([3, 0, 5, 7], np.int32)
    rows = np.zeros((2, 13, page, 640), np.float32)
    want = {}
    for slot, n in enumerate(lens):
        if not n:
            continue
        ids = np.random.default_rng(slot).integers(1, 256, (1, n))
        _, cache = hybrid.prefill_hybrid(cfg, params, jnp.asarray(ids),
                                         4 * page)
        logits, after = hybrid.decode_step_hybrid(
            cfg, params, cache, jnp.asarray(toks[slot:slot + 1]))
        want[slot] = (np.asarray(logits[0]),
                      np.asarray(after.rows[:, 0, n], np.float32))
        pages = table[slot, :-(-n // page)]
        rows[:, pages] = np.asarray(cache.rows[:, 0], np.float32).reshape(
            2, 4, page, 640)[:, :len(pages)]
    if read == "page-walk":
        monkeypatch.setattr(paged_kv, "_on_tpu", lambda: True)
        monkeypatch.setattr(flash_attention, "paged_decode_walk",
                            _interpreted)
    pool = paged_kv.LatentPool(jnp.asarray(rows, pool_dtype))
    assert paged_kv.decode_read_path(pool) == (
        paged_kv.PAGE_WALK if read == "page-walk" else paged_kv.PAGE_GATHER)
    got, got_rows, _, counts = jax.block_until_ready(
        hybrid.paged_decode_step_hybrid(
            cfg, params, pool.rows, None,
            jnp.zeros((1, cfg.counted_experts), jnp.int32),
            jnp.asarray(table), jnp.asarray(lens), jnp.asarray(toks)))
    assert int(counts.sum()) == 3 * cfg.experts_per_tok    # the live slots'
    for slot, (logits, row) in want.items():
        n = lens[slot]
        np.testing.assert_allclose(np.asarray(got[slot]), logits, atol=tol)
        np.testing.assert_allclose(
            np.asarray(got_rows[:, table[slot, n // page], n % page],
                       np.float32), row, atol=tol)


# -- a window layer's ring (PR 40) --------------------------------------------

#: name -> (the ring's entries E, the window it serves, pages a block):
#: ``E = ceil((window - 1) / PAGE) + 1``, the most pages that many positions
#: touch; blocks that divide E, that leave a last block of one page, and the
#: rule's own (``ring_walk_pages_per_block``: the whole toy ring)
RINGS = {"block-divides-ring": (4, 40, 2), "block-leaves-one-page": (5, 64, 2),
         "the-rule's-block": (5, 64, None)}

#: name -> the named slot's length, of (E, window); it stands between a
#: neighbour whose ring has turned and one whose ring has not
RING_LENGTHS = {
    "one-row": lambda e, w: 1,
    "under-a-page": lambda e, w: 5,
    "under-the-window": lambda e, w: w - 3,
    "exactly-the-window": lambda e, w: w,
    "the-window-and-one": lambda e, w: w + 1,
    "ends-a-page": lambda e, w: (e + 2) * PAGE,
    "starts-a-page": lambda e, w: (e + 2) * PAGE + 1,
    "fills-the-ring-exactly": lambda e, w: e * PAGE,
    "several-laps": lambda e, w: 3 * e * PAGE + 7,
}


def _ring_pool(entries, window, lengths, idle, dtype, poisoned, seed,
               width=128):
    """A window group's pool (slot i's ring the pages ``1 + i*E ..``, as
    ``PagedKVCache._ring_of`` hands them out; an idle slot's row the trash
    page), its table, and which ring rows each slot attends by the oracle's
    own word. ``poisoned``: every row NO slot attends (rows of the lap
    before, rows not reached yet, rows past the newest, a whole page nobody
    names) holds NaN in K and inf in V; the trash page's row 0 stays finite,
    it is what an idle slot attends."""
    rng = np.random.default_rng(seed)
    slots = len(lengths)
    pages = slots * entries + 1
    k, v = (rng.standard_normal((LAYERS, pages, PAGE, width))
            .astype(np.float32) for _ in range(2))
    table = np.zeros((slots, entries), np.int32)
    for i in range(slots):
        if i not in idle:
            table[i] = 1 + i * entries + np.arange(entries)
    lens = jnp.asarray(lengths, jnp.int32)
    valid = np.asarray(paged_kv.window_valid(
        paged_kv.ring_positions(lens, entries, PAGE), lens, window))
    keep = np.zeros((pages, PAGE), bool)
    # (an idle slot names the trash page in every entry: unbuffered)
    np.logical_or.at(keep, table, valid.reshape(slots, entries, PAGE))
    if poisoned:
        k[:, ~keep] = np.nan
        v[:, ~keep] = np.inf
    pool = paged_kv.PagePool(paged_kv.join_kv(jnp.asarray(k, dtype),
                                              jnp.asarray(v, dtype)))
    return pool, jnp.asarray(table), lens, jnp.asarray(valid), keep


def _ring_walk(q, pool, table, lens, window, pages_per_block):
    """``paged_kv.attend_pages`` over a ring with the kernel interpreted."""
    hd = q.shape[-1]
    own, qz = paged_kv._group_lanes(q, pool.k_lanes // hd)
    out = _interpreted(
        qz, paged_kv._pages(pool.kv, 1),
        LAYER * pool.num_pages + table, lens, scale=float(hd ** -0.5),
        pages_per_block=pages_per_block, window=window)
    return paged_kv._own_lanes(out, own)


def _ring_gather(q, pool, table, lens, valid, keep):
    """The oracle: the parent's read of a ring, over a pool whose unattended
    rows are made finite (0 x NaN is what its masked rows would make)."""
    clean = paged_kv.PagePool(*(
        jnp.where(jnp.asarray(keep)[None, :, :, None], a, 0) for a in pool))
    kg, vg = paged_kv.read_span(clean, LAYER, table, q.dtype)
    return paged_kv.attend_rows(q, kg, vg, lens, valid)


def _ring_case(ring, length, dtype, poisoned):
    entries, window, ppb = RINGS[ring]
    n = RING_LENGTHS[length](entries, window)
    lengths = (2 * entries * PAGE + 3, n, window // 2, 1)
    pool, table, lens, valid, keep = _ring_pool(
        entries, window, lengths, {3}, dtype, poisoned, seed=len(length))
    q = jnp.asarray(np.random.default_rng(1).standard_normal(
        (len(lengths), 1, 4, 64)), dtype)
    got = _ring_walk(q, pool, table, lens, window, ppb)
    want = _ring_gather(q, pool, table, lens, valid, keep)
    assert got.shape == want.shape and got.dtype == want.dtype
    assert bool(jnp.isfinite(got).all())
    return np.asarray(got, np.float32), np.asarray(want, np.float32)


@pytest.mark.parametrize("length", RING_LENGTHS)
@pytest.mark.parametrize("ring", RINGS)
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_ring_walk_equals_gather(dtype, ring, length):
    """A ring's rows are attended by the POSITION each holds, as
    ``ring_positions`` + ``window_valid`` say, wherever in the ring it lies:
    a ring that has not turned (a prefix of its table, the pages past the
    newest never fetched), one that has (fetched whole, the newest page part
    new rows and part the lap before's), an idle slot on the trash page."""
    got, want = _ring_case(ring, length, jnp.dtype(dtype), poisoned=False)
    tol = 1e-5 if dtype == "float32" else 2e-2
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol)


@pytest.mark.parametrize("length", RING_LENGTHS)
@pytest.mark.parametrize("ring", RINGS)
def test_ring_walk_reads_nothing_outside_a_window(ring, length):
    """Every row no slot's window covers holds NaN (K) and inf (V) in the
    pool: rows of the lap before in fetched pages, rows not written yet,
    pages a young ring has not reached. None may reach the output, through a
    score or through 0 x inf in the weighted sum."""
    got, want = _ring_case(ring, length, jnp.float32, poisoned=True)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("state", ["turned", "not-turned"])
@pytest.mark.parametrize("entries,window", [(129, 2048), (65, 1024)])
def test_the_cells_rings_at_their_width(entries, window, state):
    """The mellum / trinity cells' rings as they are: 129 and 65 entries of
    16 bf16 rows of 512 K lanes (4 KV heads of 128) and 32 query heads, the
    rule's own block (65 pages: two blocks, one), every row outside a
    window NaN / inf. ``turned``: the named slot has lapped its ring and is
    fetched whole; ``not-turned``: it is a prefix of its table."""
    n = (2 * entries * PAGE + 5 if state == "turned"
         else (entries // 2) * PAGE + 3)
    lengths = (n, 1, window + 1)
    pool, table, lens, valid, keep = _ring_pool(
        entries, window, lengths, {1}, jnp.bfloat16, True, seed=entries,
        width=512)
    assert pool.kv.shape == (LAYERS, 3 * entries + 1, PAGE, 2 * 512)
    q = jnp.asarray(np.random.default_rng(2).standard_normal(
        (len(lengths), 1, 32, 128)), jnp.bfloat16)
    got = _ring_walk(q, pool, table, lens, window, None)
    want = _ring_gather(q, pool, table, lens, valid, keep)
    assert bool(jnp.isfinite(got).all())
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=2e-2)


def test_ring_rule_cuts_the_ring_into_equal_blocks():
    """``ring_walk_pages_per_block``: no ring ends on a block of one page.
    The cells' rings at 512-lane bf16 rows; a toy ring is one block."""
    rule = flash_attention.ring_walk_pages_per_block
    assert flash_attention.paged_walk_pages_per_block(16, 512, 2) == 32
    for entries in (129, 65, 33, 4, 1, 257, 48, 100):
        ppb = rule(entries, 16, 512, 2)
        blocks = -(-entries // ppb)
        assert entries - (blocks - 1) * ppb > ppb // 2, (entries, ppb)
    assert rule(4, 16, 128, 4) == 4


def _walk_jaxpr(row_lanes, **kwargs):
    """The text of a walk's jaxpr over pages of ``row_lanes``-lane bf16 rows
    and a 128-lane query: (all of it, the kernel's own arguments)."""
    shape = jax.ShapeDtypeStruct
    text = str(jax.make_jaxpr(
        lambda q, pages, ids, lens: flash_attention.paged_decode_walk(
            q, pages, ids, lens, scale=0.125, **kwargs))(
                shape((3, 4, 128), jnp.bfloat16),
                shape((40, 16, row_lanes), jnp.bfloat16),
                shape((3, 8), jnp.int32), shape((3,), jnp.int32)))
    kernel = text[text.index("pallas_call["):]
    kernel = kernel[kernel.index("jaxpr={ lambda ;"):]
    return text, kernel[:kernel.index(". let")]


@pytest.mark.parametrize("row_lanes,window", [(256, 0), (256, 40), (128, 0)],
                         ids=["k-then-v", "k-then-v-ring", "latent"])
def test_a_page_is_one_dma_whatever_its_row_holds(row_lanes, window):
    """The kernel holds ONE operand in HBM, one pair of VMEM buffers a row
    wide and one pair of DMA semaphores, and starts and waits for as many
    DMAs where a row is K then V as where it is key and value both: a page
    is one fetch (as two leaves it was two of each)."""
    text, args = _walk_jaxpr(row_lanes, window=window)
    ppb = (flash_attention.ring_walk_pages_per_block(8, 16, 128, 2) if window
           else flash_attention.paged_walk_pages_per_block(16, 128, 2))
    assert args.count("Ref<any>") == 1
    assert f"Ref<any>{{bf16[40,16,{row_lanes}]}}" in args
    assert args.count("Ref<vmem>") == 1
    assert f"Ref<vmem>{{bf16[2,{ppb},16,{row_lanes}]}}" in args
    assert args.count("dma_sem") == 1 and "dma_sem[2]" in args
    latent, _ = _walk_jaxpr(128, window=window)
    assert text.count("dma_start") == latent.count("dma_start") > 0
    assert text.count("dma_wait") == latent.count("dma_wait") > 0
    # a ring's body is another: the mask by position is in it
    assert (text == _walk_jaxpr(row_lanes)[0]) == (window == 0)


#: the toy stacks whose window layers keep rings, at rows of ONE lane tile (2
#: KV heads of 64) and a window of 12: a ring of 3 pages of 8 float32 rows
RING_STACKS = {
    "mellum": lambda: tiny_mellum_config(
        sliding_window=12, head_dim=64,
        layer_types=("sliding_attention", "attention", "sliding_attention")),
    "afmoe": lambda: tiny_afmoe_config(
        sliding_window=12, head_dim=64,
        layer_types=("sliding_attention", "sliding_attention", "attention")),
}


@pytest.mark.parametrize("family", RING_STACKS)
def test_window_step_on_the_walk_equals_the_step_on_the_gather(monkeypatch,
                                                               family):
    """The whole ``_batched_window_step_jit`` through the batcher, built on
    the walk (the choice forced as a TPU would make it, the kernel
    interpreted: the ring layers' walks and the full layer's) against the
    same service on the gather: a prompt longer than the ring beside one
    shorter than a page whose ring turns under way, an eviction and a
    readmission through ``adopt_window``; the same tokens, the same rows in
    both pools. ``report()`` names each read and the page counters add up."""
    from edgellm_tpu.serve import batching

    cfg = RING_STACKS[family]()
    params = init_params(cfg, jax.random.key(0))
    bcfg = batching.BatchingConfig(page_size=8, num_pages=41, max_slots=3,
                                   pages_per_slot=8)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, cfg.vocab_size, n).astype(np.int32)
               for n in (5, 29)]
    step_jit = batching._batched_window_step_jit

    def serve():
        step_jit.clear_cache()
        b = batching.ContinuousBatcher(cfg, params, bcfg)
        ring = b.pool.window_pages
        assert ring == 3
        sids = [b.submit(p, 26, rng_seed=i) for i, p in enumerate(prompts)]
        for i in range(200):
            if i == 9:
                b.evict(sids[1])
                b.pool.check_invariants()
            if not b.step():
                break
        assert set(b.results) == set(sids)
        return b, [b.results[s] for s in sids]

    with jax.default_matmul_precision("highest"):
        gather, want = serve()
        rep = gather.report()
        assert rep["decode_read"] == rep["window_read"] == paged_kv.PAGE_GATHER
        assert rep["window_pages_walked"] == rep["window_pages_spanned"] == 0
        monkeypatch.setattr(paged_kv, "_on_tpu", lambda: True)
        windows = []

        def walk(*args, window=0, **kwargs):
            windows.append(window)
            return _interpreted(*args, window=window, **kwargs)

        monkeypatch.setattr(flash_attention, "paged_decode_walk", walk)
        # the interpreter's callbacks must not meet a host that dispatches on
        fetched = []        # a step: the ring entries its lengths reach

        def waited(*args):
            fetched.append(int(np.minimum(np.asarray(args[7]) // 8 + 1,
                                          3).sum()))
            return jax.block_until_ready(step_jit(*args))

        waited._cache_size = step_jit._cache_size     # the jit-miss counter's
        monkeypatch.setattr(batching, "_batched_window_step_jit", waited)
        walker, got = serve()
    step_jit.clear_cache()
    # one trace: a walk a layer, the ring layers' with their window
    assert sorted(windows) == [0, 12, 12]
    rep = walker.report()
    assert rep["decode_read"] == rep["window_read"] == paged_kv.PAGE_WALK
    assert rep["evicted"] == 1
    walked, spanned = sum(fetched), len(fetched) * 3 * 3
    assert (rep["window_pages_walked"], rep["window_pages_spanned"]) == (
        walked, spanned)
    assert 0 < walked < spanned            # young rings fetch less than all
    assert 0 < rep["attend_pages_walked"] < rep["attend_pages_spanned"]
    for g, w in zip(got, want):
        assert np.array_equal(g, w), (g.tolist(), w.tolist())
    for mine, theirs in ((walker.pool.pool, gather.pool.pool),
                         (walker.pool.window_pool, gather.pool.window_pool)):
        for a, b in zip(mine, theirs):
            # page 0 is the trash page: idle slots' rows, in any order
            np.testing.assert_allclose(np.asarray(a[:, 1:]),
                                       np.asarray(b[:, 1:]), atol=1e-5)


# -- runs: a group of adjacent pages is one DMA (PERF.md §6 "PR 46") ---------

def _run_pool(tables, lengths, lanes, dtype, seed, entries=16):
    """A pool of 16-row pages of ``lanes`` lanes a row, NaN in every page no
    slot's LIVE entries name (the trash page is finite: an idle slot fetches
    it), and the (slots, ``entries``) table that ``tables`` spells out."""
    rng = np.random.default_rng(seed)
    table = np.zeros((len(tables), entries), np.int32)
    for i, ids in enumerate(tables):
        table[i, :len(ids)] = ids
    pages = int(table.max()) + 6
    rows = rng.standard_normal(
        (LAYERS * pages, PAGE, lanes)).astype(np.float32)
    held = np.zeros((pages,), bool)
    held[0] = True
    for i, n in enumerate(lengths):
        held[table[i, :max(-(-n // PAGE), 1)]] = True
    rows.reshape(LAYERS, pages, PAGE, lanes)[:, ~held] = np.nan
    return (jnp.asarray(rows, dtype), jnp.asarray(LAYER * pages + table),
            jnp.asarray(lengths, jnp.int32))


def _seq(first, n):
    return list(range(first, first + n))


#: name -> (tables, lengths): each slot's entries as page ids of the pool,
#: and the positions it attends. Runs of four are [1..4], [5..8], ...
RUN_TABLES = {
    "every-group-a-run": (
        [_seq(1, 12), _seq(21, 7), _seq(33, 16)], (12 * 16, 100, 256)),
    "no-group-a-run": (
        [[9, 3, 12, 7, 1, 14, 5, 2], [30, 28, 26, 24, 22], [40, 42, 41, 43]],
        (8 * 16 - 3, 70, 64)),
    "mixed": (
        [_seq(1, 4) + [9, 8, 7, 6] + _seq(11, 4) + [20, 21, 23, 22],
         [31, 32, 34, 33] + _seq(41, 8)], (256, 190)),
    # the table goes on adjacent, the length stops: the pages past it hold
    # NaN and must not be fetched with the run's first ones
    "a-run-ends-mid-group-at-the-last-live-page": (
        [_seq(1, 16), _seq(21, 16), _seq(41, 16)], (5 * 16 + 1, 16 * 6, 23)),
    "a-last-block-of-fewer-pages-than-a-run": (
        [_seq(1, 16), _seq(21, 16)], (9 * 16, 8 * 16 + 40)),
    "lengths-that-are-whole-runs": (
        [_seq(1, 16), _seq(21, 16), _seq(41, 16)], (64, 128, 256)),
    "an-idle-slot-on-the-trash-page": (
        [[], _seq(1, 8), [], []], (1, 128, 1, 1)),
}


@pytest.mark.parametrize("run", [2, 4, 8])
@pytest.mark.parametrize("table", RUN_TABLES)
def test_a_run_of_pages_is_fetched_to_the_same_bytes(table, run):
    """Runs of 2, 4 and 8 against the page-a-DMA body (``run_pages=1``), BIT
    FOR BIT: adjacent pages in HBM land adjacent in the buffer, so the dots
    see the same bytes in the same order, whatever the table says about
    which groups are runs and which of them lead their block; blocks of 8
    pages, so that slots take several."""
    tables, lengths = RUN_TABLES[table]
    pages, ids, lens = _run_pool(tables, lengths, 256, jnp.bfloat16, seed=run)
    qz = jnp.asarray(np.random.default_rng(5).standard_normal(
        (len(lengths), 6, 128)), jnp.bfloat16)
    want = _interpreted(qz, pages, ids, lens, scale=0.125, pages_per_block=8,
                        run_pages=1)
    got = _interpreted(qz, pages, ids, lens, scale=0.125, pages_per_block=8,
                       run_pages=run)
    assert bool(jnp.isfinite(want.astype(jnp.float32)).all())
    assert np.array_equal(np.asarray(got.astype(jnp.float32)),
                          np.asarray(want.astype(jnp.float32)))


@pytest.mark.parametrize("query", ["bfloat16", "float32"])
@pytest.mark.parametrize("row", ["k-then-v", "latent"])
def test_runs_under_either_row_and_either_query(row, query):
    """A row of 2 W lanes (K then V) and one of W (a latent row, key and
    value both), a bf16 query and a float32 one over bf16 pages (the split
    runtime's late stages): the run rule's own choice for the page (8 KB and
    4 KB: eight) against the page-a-DMA body bit for bit, and against
    ``attend_rows`` / ``attend_latent`` over the gathered rows to rounding."""
    tables, lengths = RUN_TABLES["mixed"]
    lanes = 256 if row == "k-then-v" else 128
    pages, ids, lens = _run_pool(tables, lengths, lanes, jnp.bfloat16, seed=3)
    qz = jnp.asarray(np.random.default_rng(6).standard_normal(
        (len(lengths), 6, 128)), jnp.dtype(query))
    assert flash_attention.walk_run_pages(PAGE * lanes * 2, 16) == 8
    want = _interpreted(qz, pages, ids, lens, scale=0.125, run_pages=1)
    got = _interpreted(qz, pages, ids, lens, scale=0.125)
    assert got.dtype == jnp.dtype(query)
    assert np.array_equal(np.asarray(got.astype(jnp.float32)),
                          np.asarray(want.astype(jnp.float32)))
    clean = jnp.nan_to_num(pages.astype(jnp.float32)).astype(pages.dtype)
    gathered = clean[ids].reshape(len(lengths), -1, lanes)
    if row == "latent":
        oracle = paged_kv.attend_latent(qz, gathered, lens, 64)
    else:
        scores = jnp.einsum("bhD,bcD->bhc", qz, gathered[..., :128],
                            preferred_element_type=jnp.float32) * 0.125
        valid = jnp.arange(gathered.shape[1])[None, :] < lens[:, None]
        probs = jax.nn.softmax(jnp.where(valid[:, None], scores,
                                         jnp.finfo(jnp.float32).min), -1)
        oracle = jnp.einsum("bhc,bcD->bhD", probs.astype(qz.dtype),
                            gathered[..., 128:],
                            preferred_element_type=jnp.float32
                            ).astype(qz.dtype)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(oracle, np.float32), atol=3e-2)


def test_the_hosts_count_of_runs_is_the_kernels_table():
    """``leading_runs`` is the table the kernel reads behind its lengths (made
    on the device, of the ids it is handed) and what the batcher counts
    ``attend_pages_in_runs`` by (in numpy, of the host's table): the same
    function, the same answer on a random table with runs planted in it: of
    each block, the groups from its first on that name adjacent pages, no
    further than its first group that does not. And a kernel handed that
    table fetches what the page-a-DMA body fetches, with NaN in every page a
    wrongly taken run would drag in."""
    rng = np.random.default_rng(8)
    slots, entries, run = 6, 16, 4
    table = rng.permutation(np.arange(1, 1 + slots * entries)).reshape(
        slots, entries).astype(np.int32)
    planted = rng.random((slots, entries // run)) < 0.6
    for s, g in zip(*np.nonzero(planted)):
        first = 200 + 8 * (s * entries + g)          # adjacent, and free
        table[s, g * run:(g + 1) * run] = np.arange(first, first + run)
    # near misses: adjacent but for the last, and adjacent going down
    table[0, :4], table[1, :4] = [900, 901, 902, 904], [913, 912, 911, 910]
    planted[0, 0] = planted[1, 0] = False
    host = flash_attention.page_runs(table, run)
    assert host.dtype == bool and (host == planted).all()
    for g, ppb in ((2, 8), (4, 8), (4, 16), (8, 8), (4, 12)):
        want = np.zeros((slots, -(-entries // ppb)), np.int32)
        runs = flash_attention.page_runs(table, g)
        for s in range(slots):
            for blk in range(want.shape[1]):
                for grp in runs[s, blk * (ppb // g):(blk + 1) * (ppb // g)]:
                    if not grp:
                        break
                    want[s, blk] += 1
        got = flash_attention.leading_runs(table, g, ppb)
        assert got.dtype == np.int32 and (got == want).all(), (g, ppb)
        on_device = flash_attention.leading_runs(jnp.asarray(table), g, ppb)
        assert on_device.dtype == jnp.int32
        assert (np.asarray(on_device) == want).all()
    # a count a block the KERNEL walks: a ring of 5 entries in blocks of 2
    # has a third block, of one entry and no whole group
    short = flash_attention.leading_runs(table[:, :5], 2, 2)
    assert short.shape == (slots, 3) and not short[:, 2].any()
    lengths = rng.integers(1, entries * PAGE, slots)
    pages, ids, lens = _run_pool(table.tolist(), lengths.tolist(), 256,
                                 jnp.bfloat16, seed=9)
    qz = jnp.asarray(rng.standard_normal((slots, 4, 128)), jnp.bfloat16)
    want = _interpreted(qz, pages, ids, lens, scale=0.125, run_pages=1)
    for ppb in (8, 16):
        got = _interpreted(qz, pages, ids, lens, scale=0.125, run_pages=run,
                           pages_per_block=ppb)
        same = _interpreted(qz, pages, ids, lens, scale=0.125, run_pages=1,
                            pages_per_block=ppb)
        assert bool(jnp.isfinite(got.astype(jnp.float32)).all())
        assert np.array_equal(np.asarray(got, np.float32),
                              np.asarray(same, np.float32))
    assert want.shape == got.shape


def _kernel_jaxpr(lanes, run_pages=None, window=0):
    """The kernel's own jaxpr (the ``pallas_call``'s body) over 16-row bf16
    pages of ``lanes`` lanes and a query half as wide."""
    shape = jax.ShapeDtypeStruct
    text = str(jax.make_jaxpr(
        lambda q, pages, ids, lens: flash_attention.paged_decode_walk(
            q, pages, ids, lens, scale=0.125, window=window,
            run_pages=run_pages))(
                shape((3, 4, lanes // 2), jnp.bfloat16),
                shape((40, 16, lanes), jnp.bfloat16),
                shape((3, 8), jnp.int32), shape((3,), jnp.int32)))
    kernel = text[text.index("pallas_call["):]
    return kernel[kernel.index("jaxpr={ lambda ;"):]


@pytest.mark.parametrize("window", [0, 40], ids=["prefix", "ring"])
def test_a_page_of_32_kb_traces_the_page_a_dma_body(window):
    """Where a page is a fetch by itself (32 KB: mellum's, trinity's and
    lfm2's full layers and every ring of the cells; granite's are 64 KB) the
    rule makes a run ONE page long and the kernel traces the body it traced
    before runs: the lengths alone in their array, a DMA a page, the issue
    loop unrolled by two. The same body as an 8 KB page's told ``run_pages=1``, and
    another than the one the rule gives that page."""
    assert flash_attention.walk_run_pages(16 * 1024 * 2, 8) == 1
    wide = _kernel_jaxpr(1024, window=window)
    narrow = _kernel_jaxpr(256, window=window)
    alone = _kernel_jaxpr(256, run_pages=1, window=window)

    def lengths(kernel):    # in SMEM: the page ids, the lengths, a parity
        args = kernel[:kernel.index(". let")]
        assert args.count("Ref<smem>") == 3
        return re.findall(r"Ref<smem>\{i32\[(\d+)\]\}", args)[0]

    # behind 3 slots' lengths, a count a block of each slot's 8 entries
    assert (lengths(wide), lengths(alone), lengths(narrow)) == ("3", "3", "6")
    assert wide == _kernel_jaxpr(1024, run_pages=1, window=window)
    # a page's DMA in the unrolled pair and in the odd one out, as before
    # runs; with them, a run's and its pages' a group and the tail's
    starts = [k.count("dma_start") for k in (wide, alone, narrow)]
    assert starts[0] == starts[1] < starts[2], starts
