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

Every decode step is ABSORBED (multi-query attention of H heads over the
rows as cached): a row is key and value both and no per-head key or value of
a cached position exists. A prefill never holds a (S, H, nope + vd) tensor
for more than :data:`EXPANDED_HEADS` heads.

- **prefill** (:func:`attention_full`): blocks of :data:`QUERY_ROWS` query
  rows, ``sparse_attn.BLOCKS_PER_BODY x QBLOCK`` rows a traced body against
  the rows the last of them can see. A block makes its indexer queries from
  ``c_q``, scores the index keys and turns its rows' selections into a mask
  (``sparse_attn.selection_mask``, :func:`_block_mask`). The attend under
  the mask is the one ``sparse_attn.sparse_prefill_path`` names. On a TPU it
  is ``flash_attention.masked_attention``, which walks the keys a block at a
  time with a running maximum and sum, so that no (H, rows, S) float32
  score leaves vector memory, EXPANDED (:func:`_attend_expanded`): a body's
  rows in one call a group of heads under the blocks' masks, the group's
  keys and values rebuilt from the rows (``mla.expand``), ``W_o`` once a
  body: with the scores in vector memory the dots are what is left, and the
  expanded ones are 3.4x fewer. Everywhere else the XLA einsums over a
  block's float32 scores (B, H, QUERY_ROWS, S), ABSORBED as a decode step is
  (:func:`_attend_block`): the kernel's oracle. The widest tensor left on the
  kernel's path is a block's index dots (B, Hi, QUERY_ROWS, S) float32,
  which XLA fuses into their weighted sum (PERF.md section 6 "PR 53" has
  the timings of both).
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

import math

import jax
import jax.numpy as jnp

from . import flash_attention, mla
from .configs import ModelConfig
from .flash_attention import QBLOCK
from .paged_kv import (IndexedLatentPool, _apply_rotary_rows, _rows,
                       attend_latent, attend_latent_pages, gated,
                       latent_decode_attention, write_rows)
from .sparse_attn import (BLOCKS_PER_BODY, MASKED_KERNEL, _by_group,
                          _pad_query, _weighted, index_key, index_scores,
                          project_index, read_selected, select,
                          selection_mask, sparse_prefill_path)
from .transformer import apply_rotary, deinterleave_pairs

#: query rows a prefill block selects for (and, on the XLA path, attends) at
#: once. Set by the float32 index dots a block holds, (Hi, QUERY_ROWS, S):
#: 268 MB at 64 index heads and 16384 positions (the attention scores (H,
#: QUERY_ROWS, S), 537 MB at 128 heads, exist on the XLA path alone)
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


def _geo(cfg: ModelConfig):
    """The sizes ``mla.py`` is handed here: the sparse latent kind's."""
    return cfg.latent_geometry("sparse_latent_attention")


def _attend(cfg: ModelConfig, lp: dict, q_rows, rows, seen, gate=None):
    """Absorbed attention of queries over rows (the XLA path): q_rows (B, Q,
    H, lanes) from ``mla.absorb_query``, rows (B, C, lanes), seen (B or 1,
    Q, C) bool, gate (B, Q, H) or None (``mla.head_gate``) -> the layer's
    output (B, Q, D)."""
    b, n, h, lanes = q_rows.shape
    scores = jnp.einsum("bqhD,bcD->bhqc", q_rows, rows,
                        preferred_element_type=jnp.float32)
    scores = jnp.where(seen[:, None], scores * (cfg.head_dim ** -0.5),
                       jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(scores, axis=-1)
    ctx = jnp.einsum("bhqc,bcD->bqhD", probs.astype(q_rows.dtype), rows,
                     preferred_element_type=jnp.float32).astype(q_rows.dtype)
    return mla.unabsorb(
        _geo(cfg), lp, ctx.reshape(b * n, h, lanes),
        None if gate is None else gate.reshape(b * n, h)).reshape(b, n, -1)


def _block_mask(cfg: ModelConfig, lp: dict, start, c_q, x, rope, ik,
                select: bool):
    """What one block of query rows at positions ``start ..`` (``start`` may
    be traced) attends of EVERY position handed over: c_q (B, Q,
    q_lora_rank), x (B, Q, D), rope (cos, sin) (Q, rope) the block's own
    rows of the table, ik (B, C, index_row_lanes) -> (B or 1, Q, C) bool.
    Positions past a query's own are masked; with ``select`` its selection
    besides."""
    at = start + jnp.arange(c_q.shape[1])
    seen = (jnp.arange(ik.shape[1])[None, :] <= at[:, None])[None]
    if not select:
        return seen
    with jax.named_scope("attn.sparse.index"):
        qi, _, wi = project_index(
            cfg, lp, x,
            lambda t: apply_rotary(t, *rope, cfg.index_rope_lanes),
            query=c_q)
        dots = jnp.einsum("bqhd,bcd->bhqc", _pad_query(qi, ik.shape[-1]),
                          ik, preferred_element_type=jnp.float32)
        index = _weighted(dots, jnp.moveaxis(wi, -1, 1)[..., None])
    with jax.named_scope("attn.sparse.select"):
        return selection_mask(index, seen, cfg.index_topk)


def _head_queries(cfg: ModelConfig, lp: dict, start, c_q, rope, heads=None):
    """The heads' queries of rows at positions ``start ..``: c_q (B, Q,
    q_lora_rank) -> (q_nope (B, Q, H, nope), q_rope (B, Q, H, rope) rotated
    by the rows' own table rows), scaled as ``mla.project`` scales them;
    ``heads`` (first, count): those heads' alone."""
    b, n, _ = c_q.shape
    q = mla.head_queries(_geo(cfg), lp, c_q, jnp.broadcast_to(
        mla.query_scale(cfg, _geo(cfg), start + jnp.arange(n)), (b, n)),
        heads)
    nope = cfg.qk_nope_head_dim
    return q[..., :nope], apply_rotary(deinterleave_pairs(q[..., nope:]),
                                       *rope, cfg.rotary_dim)


def _attend_block(cfg: ModelConfig, lp: dict, start, c_q, x, rope, rows,
                  seen):
    """One block of query rows ABSORBED against every row handed over (the
    XLA path): the heads' queries made from c_q (B, Q, q_lora_rank) and
    folded through ``W_kvb``'s K half, rows (B, C, kv_row_lanes), seen (B or
    1, Q, C) from :func:`_block_mask`, x (B, Q, D) the rows' normalised
    input (what a gate reads) -> (B, Q, D)."""
    b, n, _ = c_q.shape
    q_nope, q_rope = _head_queries(cfg, lp, start, c_q, rope)
    q_rows = mla.absorb_query(
        _geo(cfg), lp, q_nope.reshape(b * n, cfg.num_heads, -1),
        q_rope.reshape(b * n, cfg.num_heads, -1))
    return _attend(cfg, lp, q_rows.reshape(b, n, cfg.num_heads, -1), rows,
                   seen, mla.head_gate(lp, x))


#: heads whose K and V :func:`_attend_expanded` rebuilds at a time: 16 heads'
#: (S, 192 + 128) are 168 MB at 16384 positions where all 128 would be 1.3 GB
EXPANDED_HEADS = 16


def _attend_expanded(cfg: ModelConfig, lp: dict, start: int, c_q, x, rope,
                     rows, seen):
    """A BODY's query rows EXPANDED against every row handed over, in the
    masked kernel: c_q (B, Q, q_lora_rank), x (B, Q, D) the rows' normalised
    input (what a gate reads), rope the rows' own table rows, rows (B, C,
    kv_row_lanes), seen (B or 1, Q, C) -> (B, Q, D).
    :data:`EXPANDED_HEADS` heads at a time (a ``lax.map``: one group's
    queries, keys and values live at once): their queries made from c_q,
    their keys and values rebuilt from the rows (``mla.expand``), a head a
    group of the kernel, one call over the body's rows."""
    b, n, _ = c_q.shape
    h = cfg.num_heads
    hg = math.gcd(h, EXPANDED_HEADS)
    seen = seen.astype(jnp.int8)        # once, not once a group of heads

    def group(first):
        q = jnp.concatenate(_head_queries(cfg, lp, start, c_q, rope,
                                          (first, hg)), axis=-1)
        k, v = mla.expand(_geo(cfg), lp, rows, (first, hg))
        out = flash_attention.masked_attention(
            _by_group(q)[:, None], _by_group(k), _by_group(v), seen, start,
            scale=cfg.head_dim ** -0.5)
        return out.reshape(b, hg, n, -1)

    outs = jax.lax.map(group, jnp.arange(0, h, hg))
    # (groups, B, hg, Q, vd) -> (B, Q, H vd)
    ctx = jnp.transpose(outs, (1, 3, 0, 2, 4)).reshape(b, n, -1)
    return gated(lp, x, ctx) @ lp["wo"]


def _joined(parts: list):
    """Blocks of rows (B, n_i, ...) as one (B, sum n_i, ...)."""
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)


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
    all. On the XLA path a block makes its mask and attends ABSORBED; where
    the masked kernel runs, the blocks make their masks alone and the body
    attends EXPANDED under them in one go."""
    b, s, _ = x.shape
    cos, sin = rope
    c_q = mla.query_latent(cfg, lp, x)
    rows = mla.latent_row(
        cfg, _geo(cfg), lp, x,
        lambda t: apply_rotary(t, cos, sin, cfg.rotary_dim))
    with jax.named_scope("attn.sparse.index"):
        # (every position's index key; a block makes its own queries)
        ik = index_key(cfg, lp, x, lambda t: apply_rotary(
            t, cos, sin, cfg.index_rope_lanes))
    kernel = sparse_prefill_path(cfg, x.dtype) == MASKED_KERNEL
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
            seen = _block_mask(cfg, lp, at, *cut, table, ik[:, :stop],
                               select)
            if kernel:
                return seen
            return _attend_block(cfg, lp, at, *cut, table, rows[:, :stop],
                                 seen)

        parts = []
        if whole > QUERY_ROWS:
            firsts = start + QUERY_ROWS * jnp.arange(whole // QUERY_ROWS)
            out = jax.lax.map(lambda at: block(at, QUERY_ROWS), firsts)
            parts.append(jnp.moveaxis(out, 0, 1).reshape(
                out.shape[1], whole, -1))
        elif whole:
            parts.append(block(start, QUERY_ROWS))
        if start + whole < stop:
            parts.append(block(start + whole, stop - start - whole))
        if kernel:
            outs.append(_attend_expanded(
                cfg, lp, start, c_q[:, start:stop], x[:, start:stop],
                (cos[start:stop], sin[start:stop]), rows[:, :stop],
                _joined(parts)))
        else:
            outs.extend(parts)
    return _joined(outs), rows, ik


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
    geo = _geo(cfg)
    q_nope, q_rope, row = mla.project(
        cfg, geo, lp, x, mla.rotate_rows(*rope),
        mla.query_scale(cfg, geo, lengths), c_q)
    with jax.named_scope("attn.sparse.index"):
        qi, ik, wi = project_index(
            cfg, lp, x, index_rotation_rows(cfg, *rope), query=c_q)
    pool = write_rows(pool, layer, page_table, lengths, row[:, None], None,
                      index=ik)
    q_rows = mla.absorb_query(geo, lp, q_nope, q_rope)
    ctx = read_selected(
        cfg, qi, wi, pool, layer, page_table, lengths,
        every=lambda: latent_decode_attention(
            q_rows, pool, layer, page_table, lengths + 1, cfg.head_dim),
        walk=lambda keep: attend_latent_pages(
            q_rows, pool, layer, page_table, lengths + 1, cfg.head_dim,
            keep=keep),
        rows=lambda chosen, count: attend_latent(
            q_rows, _rows(pool.rows, 1)[chosen], count, cfg.head_dim))
    return mla.unabsorb(geo, lp, ctx, mla.head_gate(lp, x)), pool


@jax.named_scope("attn.sparse_latent")
def attention_decode_rows(cfg: ModelConfig, lp: dict, x, rope, rows_all,
                          ik_all, pos):
    """The same layer against ONE layer of a contiguous cache: x (B, D), rope
    (cos, sin) (1, rope) at ``pos``; rows_all (B, capacity, kv_row_lanes),
    ik_all (B, capacity, index_row_lanes) -> (out (B, D), the two with
    position ``pos`` written)."""
    b = x.shape[0]
    c_q = mla.query_latent(cfg, lp, x)
    geo = _geo(cfg)
    q_nope, q_rope, row = mla.project(
        cfg, geo, lp, x, mla.rotate_rows(*rope),
        mla.query_scale(cfg, geo, jnp.broadcast_to(pos, (b,))), c_q)
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
    ctx = attend_latent(mla.absorb_query(geo, lp, q_nope, q_rope), attended,
                        lengths, cfg.head_dim)
    return (mla.unabsorb(geo, lp, ctx, mla.head_gate(lp, x)), rows_all,
            ik_all)
