"""Sparse latent attention: a ``sparse_latent_attention`` layer, latent
attention (``models/mla.py``) whose query attends the positions an indexer
selects (``models/sparse_attn.py``). The walk (``models/hybrid.py``) calls
these; the pages are ``models/paged_kv.py``'s ``IndexedLatentPool``.

With ``x = rms(h; w1)``, ``c_q``, the heads' queries, the cached row ``[c |
k_rope]`` and the score scale as ``mla.py`` states them, and an INDEXER of
``Hi`` heads of ``di`` lanes::

    qI = c_q W_qI                     (Hi x di): from the q latent, not from x
    kI = ln(x W_kI; g, b)             (di): ONE index key a position
    wI = x W_w                        (Hi)

the FIRST ``qk_rope_head_dim`` lanes of every ``qI_j`` and of ``kI`` rotated
by the attention's own table in half-split pairs (``cfg.index_rope_lanes``;
the heads' rope lanes rotate in interleaved pairs), the rest left as they
are; ``I[t, s]``, the selection ``S_t`` and its ties as ``sparse_attn.py``
has them. Position t attends ``S_t`` alone: up to ``index_topk`` positions
that is plain causal latent attention.

Both forms are ABSORBED (multi-query attention of H heads over the rows as
cached): a row is key and value both, no per-head key or value of a cached
position exists, and a prefill of S positions at 128 heads never holds the
(S, H, nope + vd) it would rebuild.

- **prefill** (:func:`attention_full`): blocks of :data:`QUERY_ROWS` query
  rows, ``sparse_attn.BLOCKS_PER_BODY x QBLOCK`` rows a traced body against
  the rows the last of them can see. A block makes its own heads' queries
  and indexer queries from ``c_q`` (no (S, H, hd) query exists either),
  scores the index keys, turns its rows' selections into a mask
  (``sparse_attn.selection_mask``) and attends under it; ``W_kvb``'s V half
  and ``W_o`` close the block. The widest tensors are a block's scores (B,
  H, QUERY_ROWS, S) and index dots (B, Hi, QUERY_ROWS, S), float32: 537 and
  268 MB at 128 / 64 heads and 16384 positions.
- **decode** (:func:`attention_decode_paged`): one query a slot. The row and
  the index key are written (one scatter a leaf), the slot's live index keys
  scored where they lie (``sparse_attn.index_scores_paged``), the ``topk``
  best chosen, and the chosen rows read as ``sparse_attn.sparse_read_path``
  says of the pool: on a TPU the MASKED WALK of every live row
  (``paged_kv.attend_latent_pages(keep=)``), elsewhere a ROW GATHER of the
  chosen rows and ``paged_kv.attend_latent`` over them.

Scopes: ``attn.sparse_latent`` (a layer's decode) and
``attn.sparse_latent.prefill`` (the block form); within either the shared
``attn.sparse.index`` (the indexer's projections and its score pass) and
``attn.sparse.select`` keep their names.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import mla
from .configs import ModelConfig
from .flash_attention import QBLOCK
from .paged_kv import (IndexedLatentPool, _apply_rotary_rows, _rows,
                       attend_latent, attend_latent_pages,
                       latent_decode_attention, write_rows)
from .sparse_attn import (BLOCKS_PER_BODY, _pad_query, _weighted, index_key,
                          index_scores, project_index, read_selected, select,
                          selection_mask)
from .transformer import apply_rotary, deinterleave_pairs

#: query rows a prefill block attends at once: every head's queries of a row
#: share the rows they read (multi-query), so a block's dots have ``H x
#: QUERY_ROWS`` rows whatever this is, and it is set by the float32 scores a
#: block holds: (H, QUERY_ROWS, S) is 537 MB at 128 heads and 16384 positions
QUERY_ROWS = 64


def index_rotation_rows(cfg: ModelConfig, cos, sin):
    """The indexer's rotation of (B, heads, di) arrays by ONE table row a
    sequence (cos, sin (B, rope) or (1, rope)): the first
    ``cfg.index_rope_lanes`` lanes in half-split pairs, the rest as they
    are."""
    def rotate(t):
        return _apply_rotary_rows(t[:, None], cos, sin,
                                  cfg.index_rope_lanes)[:, 0]
    return rotate


def _attend(cfg: ModelConfig, lp: dict, q_rows, rows, seen):
    """Absorbed attention of queries over rows: q_rows (B, Q, H, lanes) from
    ``mla.absorb_query``, rows (B, C, lanes), seen (B or 1, Q, C) bool ->
    the layer's output (B, Q, D)."""
    b, n, h, lanes = q_rows.shape
    scores = jnp.einsum("bqhD,bcD->bhqc", q_rows, rows,
                        preferred_element_type=jnp.float32)
    scores = jnp.where(seen[:, None], scores * (cfg.head_dim ** -0.5),
                       jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(scores, axis=-1)
    ctx = jnp.einsum("bhqc,bcD->bqhD", probs.astype(q_rows.dtype), rows,
                     preferred_element_type=jnp.float32).astype(q_rows.dtype)
    return mla.unabsorb(cfg, lp, ctx.reshape(b * n, h, lanes)).reshape(
        b, n, -1)


def _attend_block(cfg: ModelConfig, lp: dict, start, c_q, x, rope, rows, ik,
                  select: bool):
    """One block of query rows at positions ``start ..`` (``start`` may be
    traced) against EVERY row handed over: c_q (B, Q, q_lora_rank), x (B, Q,
    D), rope (cos, sin) (Q, rope) the block's own rows of the table, rows
    (B, C, kv_row_lanes), ik (B, C, index_row_lanes) -> (B, Q, D). Rows past
    a query's own position are masked; with ``select`` its selection
    besides."""
    b, n, _ = c_q.shape
    at = start + jnp.arange(n)
    seen = (jnp.arange(rows.shape[1])[None, :] <= at[:, None])[None]
    if select:
        with jax.named_scope("attn.sparse.index"):
            qi, _, wi = project_index(
                cfg, lp, x,
                lambda t: apply_rotary(t, *rope, cfg.index_rope_lanes),
                query=c_q)
            dots = jnp.einsum("bqhd,bcd->bhqc", _pad_query(qi, ik.shape[-1]),
                              ik, preferred_element_type=jnp.float32)
            index = _weighted(dots, jnp.moveaxis(wi, -1, 1)[..., None])
        with jax.named_scope("attn.sparse.select"):
            seen = selection_mask(index, seen, cfg.index_topk)
    q = mla.head_queries(
        cfg, lp, c_q, jnp.broadcast_to(mla.query_scale(cfg, at), (b, n)))
    nope = cfg.qk_nope_head_dim
    q_rope = apply_rotary(deinterleave_pairs(q[..., nope:]), *rope,
                          cfg.rotary_dim)
    q_rows = mla.absorb_query(
        cfg, lp, q[..., :nope].reshape(b * n, cfg.num_heads, nope),
        q_rope.reshape(b * n, cfg.num_heads, -1))
    return _attend(cfg, lp, q_rows.reshape(b, n, cfg.num_heads, -1), rows,
                   seen)


@jax.named_scope("attn.sparse_latent.prefill")
def attention_full(cfg: ModelConfig, lp: dict, x, rope):
    """A sparse latent layer over whole sequences: x (B, S, D) normalised,
    rope (cos, sin) (S, rope) -> (out (B, S, D), rows (B, S, kv_row_lanes),
    index keys (B, S, index_row_lanes)): what a cache is filled from.
    ``BLOCKS_PER_BODY x QBLOCK`` rows at a time run as ONE traced body (a
    ``lax.map`` over blocks of :data:`QUERY_ROWS`) against the rows the last
    of them can see, the rows left over as a block of their own; a body
    whose rows all lie inside the first ``index_topk`` positions selects
    nothing, and a query that sees no more than ``index_topk`` selects them
    all."""
    b, s, _ = x.shape
    cos, sin = rope
    c_q = mla.query_latent(cfg, lp, x)
    rows = mla.latent_row(
        cfg, lp, x, lambda t: apply_rotary(t, cos, sin, cfg.rotary_dim))
    with jax.named_scope("attn.sparse.index"):
        # (every position's index key; a block makes its own queries)
        ik = index_key(cfg, lp, x, lambda t: apply_rotary(
            t, cos, sin, cfg.index_rope_lanes))
    body = QBLOCK * BLOCKS_PER_BODY
    outs = []
    for start in range(0, s, body):
        stop = min(start + body, s)
        whole = (stop - start) // QUERY_ROWS * QUERY_ROWS
        select = stop > cfg.index_topk

        def block(at, n, stop=stop, select=select):
            cut = [jax.lax.dynamic_slice_in_dim(a, at, n, axis=1)
                   for a in (c_q, x)]
            table = tuple(jax.lax.dynamic_slice_in_dim(t, at, n)
                          for t in rope)
            return _attend_block(cfg, lp, at, *cut, table, rows[:, :stop],
                                 ik[:, :stop], select)

        if whole > QUERY_ROWS:
            firsts = start + QUERY_ROWS * jnp.arange(whole // QUERY_ROWS)
            out = jax.lax.map(lambda at: block(at, QUERY_ROWS), firsts)
            outs.append(jnp.moveaxis(out, 0, 1).reshape(b, whole, -1))
        elif whole:
            outs.append(block(start, QUERY_ROWS))
        if start + whole < stop:
            outs.append(block(start + whole, stop - start - whole))
    out = outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=1)
    return out, rows, ik


@jax.named_scope("attn.sparse_latent")
def attention_decode_paged(cfg: ModelConfig, lp: dict, x, rope,
                           pool: IndexedLatentPool, layer, page_table,
                           lengths):
    """A sparse latent layer of the ragged step: x (B, D) normalised, rope
    (cos, sin) (B, rope), each slot's own position's. Project and rotate;
    write the slot's row and its index key into its current page
    (``paged_kv.write``, one scatter a leaf); score the slot's index keys,
    choose, and attend the chosen rows ABSORBED by the pool's read
    (``sparse_attn.read_selected``, the skeleton both sparse kinds share);
    ``W_kvb``'s V half and ``W_o``. Returns (out (B, D), pool)."""
    c_q = mla.query_latent(cfg, lp, x)
    q_nope, q_rope, row = mla.project(
        cfg, lp, x, mla.rotate_rows(*rope), mla.query_scale(cfg, lengths),
        c_q)
    with jax.named_scope("attn.sparse.index"):
        qi, ik, wi = project_index(
            cfg, lp, x, index_rotation_rows(cfg, *rope), query=c_q)
    pool = write_rows(pool, layer, page_table, lengths, row[:, None], None,
                      index=ik)
    q_rows = mla.absorb_query(cfg, lp, q_nope, q_rope)
    ctx = read_selected(
        cfg, qi, wi, pool, layer, page_table, lengths,
        every=lambda: latent_decode_attention(
            q_rows, pool, layer, page_table, lengths + 1, cfg.head_dim),
        walk=lambda keep: attend_latent_pages(
            q_rows, pool, layer, page_table, lengths + 1, cfg.head_dim,
            keep=keep),
        rows=lambda chosen, count: attend_latent(
            q_rows, _rows(pool.rows, 1)[chosen], count, cfg.head_dim))
    return mla.unabsorb(cfg, lp, ctx), pool


@jax.named_scope("attn.sparse_latent")
def attention_decode_rows(cfg: ModelConfig, lp: dict, x, rope, rows_all,
                          ik_all, pos):
    """The same layer against ONE layer of a contiguous cache: x (B, D), rope
    (cos, sin) (1, rope) at ``pos``; rows_all (B, capacity, kv_row_lanes),
    ik_all (B, capacity, index_row_lanes) -> (out (B, D), the two with
    position ``pos`` written)."""
    b = x.shape[0]
    c_q = mla.query_latent(cfg, lp, x)
    q_nope, q_rope, row = mla.project(
        cfg, lp, x, mla.rotate_rows(*rope),
        mla.query_scale(cfg, jnp.broadcast_to(pos, (b,))), c_q)
    qi, ik, wi = project_index(cfg, lp, x, index_rotation_rows(cfg, *rope),
                               query=c_q)
    rows_all = jax.lax.dynamic_update_slice(
        rows_all, row[:, None].astype(rows_all.dtype), (0, pos, 0))
    ik_all = jax.lax.dynamic_update_slice(
        ik_all, ik[:, None].astype(ik_all.dtype), (0, pos, 0))
    lengths = jnp.broadcast_to(pos + 1, (b,))
    attended = rows_all
    if rows_all.shape[1] > cfg.index_topk:
        idx, lengths = select(index_scores(qi, wi, ik_all), lengths,
                              cfg.index_topk)
        attended = jnp.take_along_axis(rows_all, idx[:, :, None], axis=1)
    ctx = attend_latent(mla.absorb_query(cfg, lp, q_nope, q_rope), attended,
                        lengths, cfg.head_dim)
    return mla.unabsorb(cfg, lp, ctx), rows_all, ik_all
