"""The index walk: ``flash_attention.paged_index_walk`` scores a sparse
layer's index keys where they lie in the pool's second leaf, a run of adjacent
pages a DMA (PERF.md §6 "PR 51"), against the read it replaces on a TPU,
``sparse_attn.index_scores`` of ``paged_kv._gather_pages`` (the oracle, and
every other backend's read).

The kernel runs under the TPU interpreter (the backend here is the CPU),
WAITED FOR: its host callbacks deadlock against a main thread that keeps
dispatching. Every page no live table entry names, the rows of a slot's last
page past its length, and the trash page past an idle slot's one row hold NaN:
no live score may see them, and what the kernel writes past a length is 0.0.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

from edgellm_tpu.models import flash_attention, paged_kv, sparse_attn
from edgellm_tpu.models.configs import tiny_keye_vl2_config

_KERNEL = flash_attention.paged_index_walk       # before any test patches it

PAGE, LANES, HEADS, DI = 16, 128, 4, 64
RUN, PPB, ENTRIES = 4, 12, 36                    # three groups a block, three
BLOCK, SPAN = PPB * PAGE, ENTRIES * PAGE         # blocks a table
LAYERS, LAYER = 2, 1
PAGES = 1 + 2 * ENTRIES + 8
OTHER = 200                                      # the second live slot's rows

LENGTHS = [1, 15, 16, 17, BLOCK - 1, BLOCK, BLOCK + 1, SPAN]
TABLES = ["whole-runs", "broken-first", "broken-middle", "broken-last",
          "shuffled"]


def _interpreted(*args, **kwargs):
    return jax.block_until_ready(_KERNEL(
        *args, **kwargs, interpret=pltpu.InterpretParams()))


def _table(kind: str, rng) -> np.ndarray:
    """Three slots' tables: slot 0 full of pages laid out as ``kind`` says
    (runs of ``RUN`` adjacent pages at table-aligned groups, in a shuffled
    order of runs; one group of every block broken by a swap; or no two
    entries adjacent), slot 1 idle on the trash page, slot 2 in whole
    runs."""
    table = np.zeros((3, ENTRIES), np.int32)
    runs = 1 + RUN * rng.permutation(2 * ENTRIES // RUN)
    for slot, mine in ((0, runs[:ENTRIES // RUN]), (2, runs[ENTRIES // RUN:])):
        table[slot] = (mine[:, None] + np.arange(RUN)[None, :]).reshape(-1)
    if kind == "shuffled":
        table[0] = table[0, rng.permutation(ENTRIES)]
        assert not flash_attention.page_runs(table[:1], RUN).any()
    elif kind != "whole-runs":
        g = {"first": 0, "middle": 1, "last": 2}[kind.split("-")[1]]
        for blk in range(0, ENTRIES, PPB):
            at = blk + g * RUN
            table[0, [at, at + 1]] = table[0, [at + 1, at]]
    return table


def _case(kind: str, length: int, dtype, seed: int = 0):
    """(qi, wi, the clean leaf, the poisoned one, table, lengths): only what
    a length covers is finite in the poisoned leaf."""
    rng = np.random.default_rng(seed)
    table = _table(kind, rng)
    lengths = np.asarray([length, 1, OTHER], np.int32)
    clean = rng.standard_normal((LAYERS, PAGES, PAGE, LANES)).astype(
        np.float32)
    clean[..., DI:] = 0.0                        # an index key, then zeros
    covered = np.zeros((PAGES, PAGE), bool)
    for slot, n in enumerate(lengths):
        for p in range(n):
            covered[table[slot, p // PAGE], p % PAGE] = True
    dirty = np.where(covered[None, :, :, None], clean, np.nan)
    # every table entry past a slot's live pages names the trash page, as
    # the allocator leaves it
    for slot, n in enumerate(lengths):
        table[slot, -(-int(n) // PAGE):] = 0
    qi = rng.standard_normal((3, HEADS, DI)).astype(np.float32)
    wi = rng.standard_normal((3, HEADS)).astype(np.float32)
    return (jnp.asarray(qi, dtype), jnp.asarray(wi), jnp.asarray(clean, dtype),
            jnp.asarray(dirty, dtype), jnp.asarray(table),
            jnp.asarray(lengths))


def _walk(qi, wi, leaf, table, lengths, **kw):
    return _interpreted(
        sparse_attn._pad_query(qi, LANES), wi, paged_kv._pages(leaf, 1),
        (LAYER * PAGES + table).astype(jnp.int32), lengths,
        pages_per_block=PPB, **kw)


def _tolerance(want):
    """Float32 rounding of a sum of ``DI`` products and ``HEADS`` terms, by
    the largest score."""
    return float(jnp.abs(want).max()) * 1e-5


@pytest.mark.parametrize("kind", TABLES)
@pytest.mark.parametrize("length", LENGTHS)
def test_scores_equal_the_gathers_on_live_rows_and_nothing_dead_is_seen(
        kind, length):
    qi, wi, clean, dirty, table, lengths = _case(kind, length, jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = sparse_attn.index_scores(
            qi, wi, paged_kv._gather_pages(clean, LAYER, table))
    got = _walk(qi, wi, dirty, table, lengths, run_pages=RUN)
    assert got.shape == (3, SPAN) and got.dtype == jnp.float32
    live = np.arange(SPAN)[None, :] < np.asarray(lengths)[:, None]
    got, want = np.asarray(got), np.asarray(want)
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got[live], want[live],
                               atol=_tolerance(want[live]))
    assert (got[~live] == 0.0).all()
    # a run is the same bytes in the buffer as its pages one by one
    np.testing.assert_array_equal(
        got, np.asarray(_walk(qi, wi, dirty, table, lengths, run_pages=1)))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("topk", [8, 150, 400])
def test_the_selection_of_the_walks_scores_is_the_gathers(topk, dtype):
    """As a SELECTION (``selection_mask`` of both, ``topk`` under and over
    the slots' lengths): the same positions, but for those whose scores lie
    within float32 rounding of the k-th."""
    qi, wi, clean, dirty, table, lengths = _case("broken-middle", 333, dtype,
                                                 seed=topk)
    with jax.default_matmul_precision("highest"):
        want = sparse_attn.index_scores(
            qi, wi, paged_kv._gather_pages(clean, LAYER, table))
    got = _walk(qi, wi, dirty, table, lengths, run_pages=RUN)
    live = jnp.arange(SPAN)[None, :] < lengths[:, None]
    chosen = np.asarray(sparse_attn.selection_mask(got, live, topk))
    oracle = np.asarray(sparse_attn.selection_mask(want, live, topk))
    assert (chosen.sum(1) == np.minimum(np.asarray(lengths), topk)).all()
    tol = _tolerance(want)
    for slot in range(3):
        n = int(lengths[slot])
        if n <= topk:
            assert chosen[slot, :n].all() and not chosen[slot, n:].any()
            continue
        kth = np.sort(np.asarray(want)[slot, :n])[-topk]
        off = np.flatnonzero(chosen[slot] != oracle[slot])
        assert (np.abs(np.asarray(want)[slot, off] - kth) <= tol).all(), off


def test_a_score_of_minus_zero_is_written_plus_zero():
    """Every head's weight negative and every dot negative: relu leaves
    +0.0 times a negative weight, -0.0, which the kernel writes as +0.0 (so
    that equal scores compare equal bit for bit, as ``_weighted`` says)."""
    qi, wi, clean, dirty, table, lengths = _case("whole-runs", 40,
                                                 jnp.float32)
    keys = jnp.abs(dirty)
    qi, wi = -jnp.abs(qi), -jnp.abs(wi)
    got = np.asarray(_walk(qi, wi, keys, table, lengths, run_pages=RUN))
    assert (got == 0.0).all() and not np.signbit(got).any()


def test_the_steps_read_takes_the_walk_on_a_tpu_and_the_gather_elsewhere(
        monkeypatch):
    """``sparse_attn.index_scores_paged``: here the gather; with the choice
    forced as a TPU would make it, the kernel with the block and the run
    read off the leaf (``paged_kv.index_walk_geometry``) and the table of
    leading runs made of the page table."""
    cfg = tiny_keye_vl2_config()
    qi, wi, clean, dirty, table, lengths = _case("broken-first", 300,
                                                 jnp.float32)
    pool = paged_kv.IndexedPagePool(
        jnp.zeros((LAYERS, PAGES, PAGE, 2 * cfg.kv_row_lanes)), dirty)
    assert paged_kv.index_read_path(pool) == paged_kv.PAGE_GATHER
    with jax.default_matmul_precision("highest"):
        want = np.asarray(sparse_attn.index_scores_paged(
            qi, wi, pool._replace(ik=clean), LAYER, table, lengths))
    monkeypatch.setattr(paged_kv, "_on_tpu", lambda: True)
    assert paged_kv.index_read_path(pool) == paged_kv.INDEX_WALK
    assert paged_kv.index_walk_geometry(pool, ENTRIES) == (
        flash_attention.INDEX_WALK_BLOCK_ROWS // PAGE, 8)
    seen = {}

    def kernel(*args, **kwargs):
        seen.update(kwargs)
        return _interpreted(*args, **kwargs)

    monkeypatch.setattr(flash_attention, "paged_index_walk", kernel)
    got = np.asarray(sparse_attn.index_scores_paged(
        qi, wi, pool, LAYER, table, lengths))
    assert (seen["pages_per_block"], seen["run_pages"]) == (128, 8)
    np.testing.assert_array_equal(
        np.asarray(seen["lead"]),
        flash_attention.leading_runs(np.asarray(table), 8, 128))
    live = np.arange(SPAN)[None, :] < np.asarray(lengths)[:, None]
    assert np.isfinite(got).all() and (got[~live] == 0.0).all()
    np.testing.assert_allclose(got[live], want[live],
                               atol=_tolerance(want[live]))


def test_operands_that_do_not_fit_the_leaf_are_refused():
    qi, wi, clean, _, table, lengths = _case("whole-runs", 5, jnp.float32)
    pages = paged_kv._pages(clean, 1)
    with pytest.raises(ValueError, match="against index rows of 128 lanes"):
        _KERNEL(qi, wi, pages, table, lengths)          # a query not padded
    with pytest.raises(ValueError, match="against index rows of 128 lanes"):
        _KERNEL(sparse_attn._pad_query(qi, LANES), wi[:, :2], pages, table,
                lengths)
