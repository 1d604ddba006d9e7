"""Learned sparse attention: the indexer of a ``sparse_attention`` layer, its
selection, and the two forms the layer's attention takes. The walk
(``models/hybrid.py``) calls these; the pages are ``models/paged_kv.py``'s.

With ``x = rms(h; w1)``, H query / KV key-value heads of ``hd`` lanes as a GQA
layer has them (``q``, ``k`` normed per head, then rotated), and an INDEXER of
``Hi`` heads of ``di`` lanes::

    qI = x W_qI                       (Hi x di), rotated over its di lanes
    kI = ln(x W_kI; g, b)             (di): ONE index key a position, rotated
    wI = x W_w                        (Hi)
    I[t, s] = Hi^-1/2 di^-1/2 sum_j wI[t, j] relu(qI[t, j] . kI[s])   (s <= t)

in float32 from operands of the compute dtype. Position t attends ``S_t``, the
positions of the ``min(topk, t + 1)`` largest ``I[t, 0..t]``, a tie to the
earlier position: ``softmax over s in S_t of (q[t, a] . k[s, g] / sqrt(hd))``.
While a sequence holds no more than ``topk`` positions that is plain causal
GQA. The selection is EXACT (``jax.lax.top_k`` semantics, no approximate
k-th value and no per-block quota).

A position's index key is cached beside its K/V row, ``[kI | 0...]`` of
``cfg.index_row_lanes`` lanes (``paged_kv.IndexedPagePool``'s second leaf).

- **prefill** (:func:`attend_blocks`): blocks of ``QBLOCK`` query rows; a
  block scores the index keys it is handed, turns its rows' selections into
  a MASK and attends under it: no (S, S) tensor exists. Eight blocks share
  one traced body (:data:`BLOCKS_PER_BODY`): the compile of a 16k prompt is
  a quarter of the unrolled form's. The attend is the one
  :func:`sparse_prefill_path` names: on a TPU ONE kernel that walks the keys
  a block at a time under the mask with a running maximum and sum
  (``flash_attention.masked_attention``), so that a block's (H, QBLOCK, S)
  float32 scores never leave vector memory; everywhere else the XLA einsums
  that carry them through HBM, the kernel's oracle (PERF.md §6 "PR 53").
- **decode** (:func:`attention_decode_paged`): one query a slot. The slot's
  live index keys are scored (:func:`index_scores_paged`): on a TPU where
  they lie, by a page walk of the pool's index-key leaf that fetches a run
  of adjacent 4 KB pages with one DMA and scores a block while it is in
  vector memory (``flash_attention.paged_index_walk``; 0.21 ms a layer at 32
  slots of 8k-20k rows where the XLA page gather of every slot's whole span
  and the dot over the copy took 1.03, and a page a DMA 0.68: PERF.md §6
  "PR 51"); elsewhere by that gather and :func:`index_scores`, the kernel's
  oracle (``paged_kv.index_read_path``). The ``topk`` best are chosen; then
  the read the pool's K/V leaf takes (:func:`sparse_read_path`, split as
  ``paged_kv.PAGE_WALK`` and ``PAGE_GATHER`` are): on a TPU the MASKED WALK,
  the page walk of a GQA layer over every live page with the selection as a
  mask on its rows (``flash_attention.paged_decode_walk(keep=)``); elsewhere
  a ROW GATHER of the chosen K/V rows out of the pool (a row is ``2 KV hd``
  contiguous lanes of the one K/V leaf) attended as ``paged_kv.attend_rows``
  attends a span. On a v5e a gathered 2 KB row costs 31.6 ns and a walked
  one 2.9 (PERF.md §6 "PR 47"): the walk reads 7x the bytes at 32 slots of
  8k-20k rows and is still 0.7 ms a layer ahead, so the TPU has the one
  read; a pool deeper than ~11 x ``topk`` rows a slot, which no cell or
  sweep has run, is where a gather would have to be timed against it. A
  pool whose slots cannot pass ``topk`` positions skips all of it and
  attends its pages as a GQA layer does.

Scopes: ``attn.sparse`` (a layer's decode), within it ``attn.sparse.index``
(the indexer's projections and its score pass) and ``attn.sparse.select`` (the
top-k and the row ids); ``attn.sparse.prefill`` (the block form).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from . import flash_attention
from .configs import LANE_TILE, ModelConfig
from .flash_attention import QBLOCK
from .mla import rotate_rows  # (B, heads, lanes) by ONE table row a sequence
from .paged_kv import (INDEX_WALK, PAGE_WALK, IndexedPagePool, PagePool,
                       _apply_rotary_rows, _gather_pages, _pages, _rows,
                       attend_pages, attend_rows, decode_read_path,
                       head_norms, index_read_path, index_walk_geometry,
                       paged_decode_attention, split_kv, write_rows)
from .transformer import _layernorm, apply_rotary

#: the decode reads of a sparse layer, as ``ContinuousBatcher.report()``
#: names them (``sparse_read``)
MASKED_WALK = "pallas page walk, the selection a mask on its rows"
ROW_GATHER = "xla row gather of the selected rows"
EVERY_ROW = "every live row (no slot can pass index_topk)"
#: the attends of a sparse layer's PREFILL blocks (``sparse_prefill``)
MASKED_KERNEL = "pallas masked attention, a key block at a time"
XLA_BLOCKS = "xla blocks, float32 scores through HBM"


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def sparse_prefill_path(cfg: ModelConfig, dtype) -> str:
    """Which attend a sparse layer's prefill blocks are built with, read off
    what it is handed: :data:`MASKED_KERNEL`
    (``flash_attention.masked_attention``) on a TPU where the operands are
    bfloat16 or float32 and a head's key and value lanes are whole or half
    lane tiles (a latent layer's: ``mla.expand``'s, ``nope + rope`` and
    ``v_head_dim``); :data:`XLA_BLOCKS`, the kernel's oracle, everywhere
    else."""
    dv = cfg.v_head_dim if cfg.latent_layers else cfg.head_dim
    whole = cfg.head_dim % (LANE_TILE // 2) == 0 and dv % (LANE_TILE // 2) == 0
    known = jnp.dtype(dtype) in (jnp.dtype(jnp.bfloat16),
                                 jnp.dtype(jnp.float32))
    return MASKED_KERNEL if whole and known and _on_tpu() else XLA_BLOCKS


def sparse_read_path(cfg: ModelConfig, span: int, pool=None) -> str:
    """Which read a sparse layer's decode is built with, read off what it is
    handed: by the positions a slot can hold (``span``), :data:`EVERY_ROW`
    (the layer's plain page read) where none can pass ``cfg.index_topk``;
    else the selection and :data:`MASKED_WALK` where the pool's K/V leaf
    takes the page walk (``paged_kv.decode_read_path``: an fp pool of whole
    tiles on a TPU); else :data:`ROW_GATHER` (every other backend, part
    tiles; ``pool`` None)."""
    if span <= cfg.index_topk:
        return EVERY_ROW
    if pool is not None and decode_read_path(pool) == PAGE_WALK:
        return MASKED_WALK
    return ROW_GATHER


def index_rope(cfg: ModelConfig, n: int):
    """(cos, sin) (n, index_head_dim) float32: the plain table of
    ``cfg.rope_theta`` over the indexer's own lanes, half-split pairs."""
    di = cfg.index_head_dim
    inv = 1.0 / (cfg.rope_theta
                 ** (jnp.arange(0, di, 2, dtype=jnp.float32) / di))
    freqs = jnp.outer(jnp.arange(n, dtype=jnp.float32), inv)
    emb = jnp.concatenate([freqs, freqs], axis=-1)
    return jnp.cos(emb), jnp.sin(emb)


def project_index(cfg: ModelConfig, lp: dict, x, rotate, query=None):
    """x (..., D) normalised -> (qI (..., Hi, di) rotated; kI (...,
    index_row_lanes), the position's index key as it is cached: layer-normed,
    rotated, zeros after; wI (..., Hi) float32, the heads' weights times
    ``Hi^-1/2 di^-1/2``). ``rotate``: a function of (..., heads, di)
    arrays. ``query`` (None: ``x``): what ``W_qI`` projects, where that is
    not the layer's input (a sparse latent layer's q latent ``c_q``)."""
    hi, di = cfg.index_heads, cfg.index_head_dim
    qi = rotate(((x if query is None else query)
                 @ lp["wq_index"]).reshape(*x.shape[:-1], hi, di))
    ki = index_key(cfg, lp, x, rotate)
    wi = (x @ lp["w_index"]).astype(jnp.float32) * (hi * di) ** -0.5
    return qi, ki, wi


def index_key(cfg: ModelConfig, lp: dict, x, rotate):
    """:func:`project_index`'s kI alone: x (..., D) normalised -> (...,
    index_row_lanes), the position's index key as it is cached."""
    ki = _layernorm(x @ lp["wk_index"], lp["index_norm_scale"],
                    lp["index_norm_bias"], cfg.norm_eps)
    ki = rotate(ki[..., None, :])[..., 0, :]
    return jnp.pad(ki, ((0, 0),) * (ki.ndim - 1)
                   + ((0, cfg.index_row_lanes - cfg.index_head_dim),))


def _pad_query(qi, lanes: int):
    """qI (..., di) against a cached row's ``lanes``: zeros after."""
    return jnp.pad(qi, ((0, 0),) * (qi.ndim - 1)
                   + ((0, lanes - qi.shape[-1]),))


def _weighted(dots, wi):
    """relu and the heads' weighted sum: dots (B, Hi, ..., C) float32, wi
    broadcastable to it -> (B, ..., C). A score of -0.0 is made +0.0, so
    that equal scores compare equal bit for bit."""
    scores = jnp.sum(jax.nn.relu(dots) * wi, axis=1)
    return jnp.where(scores == 0.0, 0.0, scores)


def index_scores(qi, wi, ik_rows):
    """One query a sequence against index keys as cached: qi (B, Hi, di), wi
    (B, Hi) float32, ik_rows (B, C, lanes) -> I (B, C) float32."""
    dots = jnp.einsum("bhd,bcd->bhc", _pad_query(qi, ik_rows.shape[-1]),
                      ik_rows, preferred_element_type=jnp.float32)
    return _weighted(dots, wi[:, :, None])


def index_scores_paged(qi, wi, pool: IndexedPagePool, layer, page_table,
                       lengths):
    """:func:`index_scores` of each slot's index keys in layer ``layer`` of
    the pool, by the read the pool takes (``paged_kv.index_read_path``): qi
    (B, Hi, di), wi (B, Hi) float32, lengths (B,) the positions a slot
    scores -> (B, span) float32. On the index walk the keys are scored where
    they lie and a position past a slot's length reads 0.0; on the page
    gather every table entry is gathered and scored, and what lies past a
    length is whatever its page held. Either way the caller masks by
    length."""
    if index_read_path(pool) != INDEX_WALK:
        return index_scores(qi, wi, _gather_pages(pool.ik, layer, page_table))
    ppb, run = index_walk_geometry(pool, page_table.shape[1])
    table = page_table.astype(jnp.int32)
    # (of the TABLE: adjacency does not change with a layer's offset, so the
    # compiler merges the layers' equal expressions)
    lead = flash_attention.leading_runs(table, run, ppb) if run > 1 else None
    return flash_attention.paged_index_walk(
        _pad_query(qi, pool.ik.shape[-1]), wi, _pages(pool.ik, 1),
        layer * pool.num_pages + table, lengths.astype(jnp.int32),
        pages_per_block=ppb, run_pages=run, lead=lead)


def select(scores, lengths, k: int):
    """The rows a decode step attends: scores (B, C >= k) float32, lengths
    (B,) live positions a sequence (the newest included) -> (idx (B, k)
    int32, count (B,)): the ``count = min(lengths, k)`` best live positions
    first, best first, a tie to the earlier; what follows them is dead."""
    live = jnp.arange(scores.shape[1])[None, :] < lengths[:, None]
    _, idx = jax.lax.top_k(jnp.where(live, scores, -jnp.inf), k)
    return idx.astype(jnp.int32), jnp.minimum(lengths, k).astype(jnp.int32)


def _ordered(x):
    """float32 -> uint32 whose unsigned order is the floats' (-inf lowest)."""
    u = jax.lax.bitcast_convert_type(x, jnp.uint32)
    return jnp.where((u >> 31) == 1, ~u, u | jnp.uint32(0x80000000))


def kth_largest(s, k: int):
    """(``_ordered(s)``, the k-th largest of each row of it (..., 1)): s
    (..., C) float32. EXACT, and no sort: the answer's 32 bits are settled
    from the top, a counting pass over the row each (the largest T with at
    least k entries >= T). On a v5e 0.8 ms for a (512, 16384) block where
    ``jax.lax.top_k(s, 2048)`` takes 5.3, and 0.26 against 0.60 for the
    decode's (32, 20480) (PERF.md §6 "PR 47")."""
    u = _ordered(s)

    def bit(i, t):
        cand = t | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        enough = jnp.sum(u >= cand, axis=-1, keepdims=True,
                         dtype=jnp.int32) >= k
        return jnp.where(enough, cand, t)

    return u, jax.lax.fori_loop(
        0, 32, bit, jnp.zeros(s.shape[:-1] + (1,), jnp.uint32))


def selection_mask(scores, visible, k: int):
    """The same selection as a mask, a query a row: scores (..., C >= k)
    float32, visible (broadcastable to it) bool, the positions the query can
    see -> (..., C) bool, at most k true a row: the scores above the row's
    k-th largest visible score (:func:`kth_largest`), and of those equal to
    it the earliest, as many as still fit: ``jax.lax.top_k``'s set."""
    u, kth = kth_largest(jnp.where(visible, scores, -jnp.inf), k)
    above, ties = u > kth, u == kth
    room = k - jnp.sum(above, axis=-1, keepdims=True, dtype=jnp.int32)
    rank = jnp.cumsum(ties, axis=-1, dtype=jnp.int32)
    return visible & (above | (ties & (rank <= room)))


#: query blocks that share ONE traced body (a ``lax.map`` over them against
#: the keys the LAST of them can see): 32 blocks x 6 layers unrolled, each
#: with its counting loop, took the chip's compiler ~250 s a cold set-up of
#: the benchmark cell; eight a body cost a fifth more index and attention
#: arithmetic (a block reads up to 3584 keys it masks) and compile in a
#: quarter of that (PERF.md §6 "PR 47")
BLOCKS_PER_BODY = 8


def _by_group(a):
    """(B, n, KV, hd) -> (B KV, n, hd): the keys, or one query head a group,
    as :func:`flash_attention.masked_attention` takes them."""
    b, n, kv, hd = a.shape
    return jnp.swapaxes(a, 1, 2).reshape(b * kv, n, hd)


def _attend_block(cfg: ModelConfig, start, qb, k, v, qib, ik, wib,
                  select: bool, kernel: bool):
    """One block of query rows at positions ``start ..`` (``start`` may be
    traced) against EVERY key handed over: qb (B, Q, H, hd), k, v (B, C, KV,
    hd), or with ``kernel`` :func:`_by_group` (B KV, C, hd); qib (B, Q, Hi,
    lanes) padded, ik (B, C, lanes), wib (B, Q, Hi) -> (B, Q, H, hd). Keys
    past a row's own position are masked; with ``select`` the row's
    selection besides. ``kernel``: the attend is the masked kernel, else the
    XLA einsums over float32 scores (B, H, Q, C), its oracle."""
    b, n, h, hd = qb.shape
    kv = cfg.num_kv_heads
    seen = (jnp.arange(ik.shape[1])[None, :]
            <= start + jnp.arange(n)[:, None])[None]           # (1, Q, C)
    if select:
        with jax.named_scope("attn.sparse.index"):
            dots = jnp.einsum("bqhd,bcd->bhqc", qib, ik,
                              preferred_element_type=jnp.float32)
            index = _weighted(dots, jnp.moveaxis(wib, -1, 1)[..., None])
        with jax.named_scope("attn.sparse.select"):
            seen = selection_mask(index, seen, cfg.index_topk)  # (B, Q, C)
    qg = qb.reshape(b, n, kv, h // kv, hd)
    if kernel:
        out = flash_attention.masked_attention(
            jnp.moveaxis(qg, 1, 3).reshape(b * kv, h // kv, n, hd), k, v,
            seen, start, scale=1.0 / math.sqrt(hd))
        return jnp.moveaxis(out.reshape(b, kv, h // kv, n, hd), 3, 1
                            ).reshape(b, n, h, hd)
    scores = jnp.einsum("bqgrd,bcgd->bgrqc", qg, k,
                        preferred_element_type=jnp.float32)
    scores = jnp.where(seen[:, None, None], scores * (1.0 / math.sqrt(hd)),
                       jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bgrqc,bcgd->bqgrd", probs.astype(qb.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.astype(qb.dtype).reshape(b, n, h, hd)


def attend_blocks(cfg: ModelConfig, q, k, v, qi, ik, wi):
    """The prefill form: causal GQA attention in which query row t sees the
    ``cfg.index_topk`` positions its index scores select, by blocks of
    :data:`QBLOCK` query rows. q (B, S, H, hd), k, v (B, S, KV, hd) rotated;
    qi (B, S, Hi, di), ik (B, S, lanes), wi (B, S, Hi) from
    :func:`project_index` -> (B, S, H, hd). :data:`BLOCKS_PER_BODY` whole
    blocks at a time run as ONE traced body against the keys the last of
    them can see (a block's own later keys masked), the rows left over as a
    block of their own; a body whose keys all lie inside the first
    ``index_topk`` positions selects nothing (every visible position is
    attended), and a row that sees no more than ``index_topk`` selects them
    all. No (S, S) tensor exists: the widest is one block's index dots (B,
    Hi, QBLOCK, S), float32; its attention scores (B, H, QBLOCK, S) float32,
    1 GB at 32 heads and 16384 positions, exist on the XLA path alone
    (:func:`sparse_prefill_path`: the kernel takes its keys by KV group)."""
    s = q.shape[1]
    qi = _pad_query(qi, ik.shape[-1])
    kernel = sparse_prefill_path(cfg, q.dtype) == MASKED_KERNEL
    body = QBLOCK * BLOCKS_PER_BODY
    outs = []
    for start in range(0, s, body):
        stop = min(start + body, s)
        whole = (stop - start) // QBLOCK * QBLOCK
        keys = tuple(_by_group(a[:, :stop]) if kernel else a[:, :stop]
                     for a in (k, v))
        select = stop > cfg.index_topk

        def block(at, rows, n):
            cut = [jax.lax.dynamic_slice_in_dim(a, rows, n, axis=1)
                   for a in (q, qi, wi)]
            return _attend_block(cfg, at, cut[0], *keys, cut[1],
                                 ik[:, :stop], cut[2], select, kernel)

        if whole > QBLOCK:
            firsts = start + QBLOCK * jnp.arange(whole // QBLOCK)
            out = jax.lax.map(lambda at: block(at, at, QBLOCK), firsts)
            outs.append(jnp.moveaxis(out, 0, 1).reshape(
                q.shape[0], whole, *q.shape[2:]))
        elif whole:
            outs.append(block(start, start, QBLOCK))
        if start + whole < stop:
            outs.append(block(start + whole, start + whole,
                              stop - start - whole))
    return outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=1)


def _qkv(cfg: ModelConfig, lp: dict, x):
    """x (..., D) -> q (..., H, hd), k, v (..., KV, hd), q and k normed per
    head, nothing rotated."""
    hd = cfg.head_dim
    q = (x @ lp["wq"]).reshape(*x.shape[:-1], cfg.num_heads, hd)
    k = (x @ lp["wk"]).reshape(*x.shape[:-1], cfg.num_kv_heads, hd)
    v = (x @ lp["wv"]).reshape(*x.shape[:-1], cfg.num_kv_heads, hd)
    return (*head_norms(cfg, lp, q, k), v)


@jax.named_scope("attn.sparse.prefill")
def attention_full(cfg: ModelConfig, lp: dict, x, rope, rope_index):
    """A sparse layer over whole sequences: x (B, S, D) normalised, rope and
    rope_index (cos, sin) (S, hd) / (S, di) -> (out (B, S, D), k, v (B, S,
    KV, hd) rotated, index keys (B, S, index_row_lanes)): what a cache is
    filled from."""
    b, s, _ = x.shape
    q, k, v = _qkv(cfg, lp, x)
    q = apply_rotary(q, *rope, cfg.rotary_dim)
    k = apply_rotary(k, *rope, cfg.rotary_dim)
    qi, ik, wi = project_index(
        cfg, lp, x,
        lambda t: apply_rotary(t, *rope_index, cfg.index_head_dim))
    out = attend_blocks(cfg, q, k, v, qi, ik, wi)
    return out.reshape(b, s, -1) @ lp["wo"], k, v, ik


def selected_rows(scores, lengths, k: int, pool: IndexedPagePool, layer,
                  page_table):
    """(:func:`select`'s count (B,), the flat row ids (B, k) into the pool's
    leaves viewed (L*P*ps, lanes) of the positions it chose): position p of
    slot i is row ``layer*P*ps + page_table[i, p // ps]*ps + p % ps``; a dead
    entry names a row of the slot's own tail or of the trash page."""
    idx, count = select(scores, lengths, k)
    ps = pool.page_size
    page = jnp.take_along_axis(page_table, idx // ps, axis=1)
    return count, layer * (pool.num_pages * ps) + page * ps + idx % ps


def read_selected(cfg: ModelConfig, qi, wi, pool, layer, page_table, lengths,
                  *, every, walk, rows):
    """The decode of a sparse layer kind after its row is written: index ->
    select -> read, the read the pool takes (:func:`sparse_read_path`). qi (B,
    Hi, di), wi (B, Hi) from :func:`project_index`; ``pool`` holds the index
    keys as ``ik``; lengths (B,) each slot's positions BEFORE the one just
    written, as the layer was handed them. The layer kind brings its three
    reads: ``every()`` of every live row where no slot can pass
    ``index_topk``; ``walk(keep)`` the page walk under the selection, keep
    (B, span) bool; ``rows(chosen, count)`` of the rows gathered by the flat
    ids chosen (B, k), count (B,) of them live (:func:`selected_rows`)."""
    span = page_table.shape[1] * pool.page_size
    read = sparse_read_path(cfg, span, pool)
    if read == EVERY_ROW:
        return every()
    with jax.named_scope("attn.sparse.index"):
        scores = index_scores_paged(qi, wi, pool, layer, page_table,
                                    lengths + 1)
    if read == MASKED_WALK:
        with jax.named_scope("attn.sparse.select"):
            live = jnp.arange(span)[None, :] < (lengths + 1)[:, None]
            keep = selection_mask(scores, live, cfg.index_topk)
        return walk(keep)
    with jax.named_scope("attn.sparse.select"):
        count, chosen = selected_rows(scores, lengths + 1, cfg.index_topk,
                                      pool, layer, page_table)
    return rows(chosen, count)


@jax.named_scope("attn.sparse")
def attention_decode_paged(cfg: ModelConfig, lp: dict, x, rope, rope_index,
                           pool: IndexedPagePool, layer, page_table, lengths):
    """A sparse layer of the ragged step: x (B, D) normalised, rope /
    rope_index (cos, sin) (B, hd) / (B, di), each slot's own position's.
    Project, norm and rotate; write the slot's K/V row and its index key into
    its current page (``paged_kv.write``, one scatter a leaf); score the
    slot's index keys, choose, and attend the chosen K/V rows by the pool's
    read (:func:`read_selected`); ``W_o``.
    Returns (out (B, D), pool)."""
    b = x.shape[0]
    q, k, v = (t[:, None] for t in _qkv(cfg, lp, x))           # (B, 1, ., hd)
    q = _apply_rotary_rows(q, *rope, cfg.rotary_dim)
    k = _apply_rotary_rows(k, *rope, cfg.rotary_dim)
    with jax.named_scope("attn.sparse.index"):
        qi, ik, wi = project_index(cfg, lp, x, rotate_rows(*rope_index))
    pool = write_rows(pool, layer, page_table, lengths, k, v, index=ik)
    out = read_selected(
        cfg, qi, wi, pool, layer, page_table, lengths,
        every=lambda: paged_decode_attention(
            q, PagePool(pool.kv), layer, page_table, lengths + 1),
        walk=lambda keep: attend_pages(
            q, PagePool(pool.kv), layer, page_table, lengths + 1, keep=keep),
        rows=lambda chosen, count: attend_rows(
            q, *split_kv(_rows(pool.kv, 1)[chosen]), count))
    return out.reshape(b, -1) @ lp["wo"], pool


@jax.named_scope("attn.sparse")
def attention_decode_rows(cfg: ModelConfig, lp: dict, x, rope, rope_index,
                          k_all, v_all, ik_all, pos):
    """The same layer against ONE layer of a contiguous cache: x (B, D),
    rope / rope_index (cos, sin) (1, lanes) at ``pos``; k_all, v_all (B,
    capacity, KV, hd), ik_all (B, capacity, index_row_lanes) -> (out (B, D),
    the three with position ``pos`` written)."""
    b = x.shape[0]
    q, k, v = (t[:, None] for t in _qkv(cfg, lp, x))
    q = apply_rotary(q, *rope, cfg.rotary_dim)
    k = apply_rotary(k, *rope, cfg.rotary_dim)
    qi, ik, wi = project_index(cfg, lp, x, rotate_rows(*rope_index))
    k_all = jax.lax.dynamic_update_slice(k_all, k.astype(k_all.dtype),
                                         (0, pos, 0, 0))
    v_all = jax.lax.dynamic_update_slice(v_all, v.astype(v_all.dtype),
                                         (0, pos, 0, 0))
    ik_all = jax.lax.dynamic_update_slice(
        ik_all, ik[:, None].astype(ik_all.dtype), (0, pos, 0))
    capacity = k_all.shape[1]
    merged = (k_all.reshape(b, capacity, -1), v_all.reshape(b, capacity, -1))
    lengths = jnp.broadcast_to(pos + 1, (b,))
    if capacity > cfg.index_topk:
        idx, lengths = select(index_scores(qi, wi, ik_all), lengths,
                              cfg.index_topk)
        merged = tuple(jnp.take_along_axis(a, idx[:, :, None], axis=1)
                       for a in merged)
    out = attend_rows(q, *merged, lengths)
    return out.reshape(b, -1) @ lp["wo"], k_all, v_all, ik_all
