"""Latent attention (MLA): the projections of a ``latent_attention`` layer
and the two forms its attention takes. The walk (``models/hybrid.py``) and
the page pool (``models/paged_kv.py``) call these; nothing here knows a
cache.

With ``x = rms(h; w1)`` and H heads of ``nope + rope`` query / key lanes and
``vd`` value lanes::

    c_q = rms(x W_qa; g_q)                  (q_lora_rank)
    q_h = c_q W_qb = [q_nope_h | q_rope_h]  (nope | rope)
    [c_kv | k_rope] = x W_kva               (kv_lora_rank | rope)
    c = rms(c_kv; g_kv)
    [k_nope_h | v_h] = c W_kvb^h            (nope | vd)

``q_rope_h`` and the ONE ``k_rope`` all heads share are rotated in
interleaved pairs (lanes 2i, 2i+1 by frequency i; stored de-interleaved,
evens then odds, on both sides, so every dot product is the pair rotation's).
The cached row of a position is ``[c | k_rope | 0...]`` (``cfg.kv_row_lanes``
lanes): what both forms read.

- **expanded** (:func:`expand`; forward and prefill): ``k_nope_h`` and ``v_h``
  rebuilt for every head from ``c``; ``score_h = q_h . [k_nope_h | k_rope]``.
- **absorbed** (:func:`absorb_query`, :func:`unabsorb`; every decode step):
  ``W_kvb``'s K half folded into the query, ``q~_h = W_kvb^{K,h} q_nope_h``
  (kv_lora_rank), so ``score_h = [q~_h | q_rope_h] . row`` is multi-query
  attention of H heads over the row as it is cached, and its V half applied
  after the weighted sum of rows: ``o_h = W_kvb^{V,h T} sum_j p_j c(j)``. No
  per-head key or value of a cached position exists.

Scores are scaled by ``(nope + rope)^-1/2`` (the attends' own), times
``softmax_mscale^2`` and the query position's ``1 + beta ln(1 + floor(pos /
original_max))``, both folded into the query (:func:`query_scale`).

Under ``cfg.rank_scales`` the whole query is also multiplied by
``sqrt(hidden / q_lora_rank)`` (folded into the same scale) and ``c`` by
``sqrt(hidden / kv_lora_rank)`` AFTER its norm, where the row is made
(:func:`project`): the cached row holds the scaled latent, so both forms read
it and ``k_rope`` does not carry it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .configs import LatentGeometry, ModelConfig
from .transformer import _rmsnorm, _rotate_half, deinterleave_pairs


def query_scale(cfg: ModelConfig, geo: LatentGeometry, positions):
    """What a query at ``positions`` (any shape, int) is multiplied by beside
    the attends' ``head_dim^-1/2``: float32, same shape."""
    scale = jnp.full(positions.shape,
                     cfg.softmax_mscale ** 2
                     * cfg.rank_scale(geo.q_lora_rank), jnp.float32)
    if not cfg.query_scale_beta:
        return scale
    original_max = cfg.rope_scaling[2]
    return scale * (1.0 + cfg.query_scale_beta * jnp.log1p(
        (positions // original_max).astype(jnp.float32)))


def rotate_rows(cos, sin):
    """The rotation of (B, heads, rope) arrays by ONE table row a sequence:
    cos, sin (B, rope), each slot's own position's, or (1, rope)."""
    def rotate(t):
        return (t * cos[:, None, :].astype(t.dtype)
                + _rotate_half(t) * sin[:, None, :].astype(t.dtype))
    return rotate


def plain_rope(geo: LatentGeometry, n: int):
    """(cos, sin) (n, rope) float32 of plain RoPE by the KIND's own theta
    over its rope lanes, ``transformer.precompute_rope``'s layout (emb =
    concat(freqs, freqs)): the table of a latent kind whose theta is not the
    stack's ``rope_theta`` (a window kind's)."""
    rot = geo.qk_rope_head_dim
    inv_freq = 1.0 / (geo.rope_theta
                      ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot))
    freqs = jnp.outer(jnp.arange(n, dtype=jnp.float32), inv_freq)
    emb = jnp.concatenate([freqs, freqs], axis=-1)
    return jnp.cos(emb), jnp.sin(emb)


def _pad_lanes(geo: LatentGeometry, x):
    """(..., kv_lora_rank + rope) -> (..., kv_row_lanes), zeros after."""
    pad = geo.kv_row_lanes - x.shape[-1]
    return jnp.pad(x, ((0, 0),) * (x.ndim - 1) + ((0, pad),))


def query_latent(cfg: ModelConfig, lp: dict, x):
    """x (..., D) normalised -> ``c_q`` (..., q_lora_rank), the normalised
    query latent: what :func:`project` makes the heads' queries from and a
    sparse latent layer's indexer its own (``models/sparse_mla.py``)."""
    return _rmsnorm(x @ lp["wq_a"], lp["q_norm"], cfg.norm_eps)


def project(cfg: ModelConfig, geo: LatentGeometry, lp: dict, x, rotate,
            scale, c_q=None):
    """x (..., D) normalised -> (q_nope (..., H, nope), q_rope (..., H, rope),
    row (..., kv_row_lanes)): the queries scaled by ``scale`` (...,) float32
    (:func:`query_scale`) and ``q_rope`` / the row's ``k_rope`` rotated by
    ``rotate`` (a function of (..., heads, rope) arrays, de-interleaved first
    here), the row as it is cached. ``c_q``: :func:`query_latent` of ``x``
    where the caller has made it already."""
    rope = geo.qk_rope_head_dim
    if c_q is None:
        c_q = query_latent(cfg, lp, x)
    q = head_queries(geo, lp, c_q, scale)
    row = latent_row(cfg, geo, lp, x, rotate)
    return (q[..., :-rope], rotate(deinterleave_pairs(q[..., -rope:])), row)


def head_queries(geo: LatentGeometry, lp: dict, c_q, scale, heads=None):
    """``c_q`` (..., q_lora_rank) -> the heads' queries (..., H, nope + rope)
    times ``scale`` (...,) float32, nothing rotated yet. ``heads`` (first,
    count): those heads alone (:func:`_of_heads`)."""
    wq, h = _of_heads(lp["wq_b"], geo.num_heads, heads)
    q = (c_q @ wq).reshape(*c_q.shape[:-1], h, geo.head_dim)
    return q * scale[..., None, None].astype(q.dtype)


def latent_row(cfg: ModelConfig, geo: LatentGeometry, lp: dict, x, rotate):
    """x (..., D) normalised -> the position's row as it is cached (...,
    kv_row_lanes): ``[c | k_rope | 0...]``, ``k_rope`` de-interleaved and
    rotated by ``rotate``."""
    rank = geo.kv_lora_rank
    kv = x @ lp["wkv_a"]
    c = _rmsnorm(kv[..., :rank], lp["kv_norm"], cfg.norm_eps)
    if cfg.rank_scales:
        c = c * jnp.asarray(cfg.rank_scale(rank), c.dtype)
    k_rope = rotate(deinterleave_pairs(kv[..., None, rank:]))[..., 0, :]
    return _pad_lanes(geo, jnp.concatenate([c, k_rope], axis=-1))


def _of_heads(w, h: int, heads):
    """A matrix whose columns are ``h`` heads' lanes, head-major (rank, h x
    lanes) -> (the columns of heads ``first .. first + count``, count) for
    ``heads`` = (first, count), ``first`` traced or not (a group of heads a
    turn of a ``lax.map``); (w, h) for None."""
    if heads is None:
        return w, h
    lanes = w.shape[1] // h
    return jax.lax.dynamic_slice_in_dim(
        w, heads[0] * lanes, heads[1] * lanes, axis=1), heads[1]


def _kvb(geo: LatentGeometry, lp: dict, heads=None):
    """``W_kvb`` (rank, H, nope + vd): K lanes first, then V. ``heads``: as
    :func:`_of_heads`."""
    w, h = _of_heads(lp["wkv_b"], geo.num_heads, heads)
    return w.reshape(geo.kv_lora_rank, h,
                     geo.qk_nope_head_dim + geo.v_head_dim)


def expand(geo: LatentGeometry, lp: dict, rows, heads=None):
    """Cached rows (B, S, kv_row_lanes) -> per-head (k (B, S, H, nope + rope),
    v (B, S, H, vd)): the expanded form's keys and values. ``heads`` (first,
    count): those heads' alone (:func:`_of_heads`)."""
    rank, rope = geo.kv_lora_rank, geo.qk_rope_head_dim
    kv = jnp.einsum("bsc,chn->bshn", rows[..., :rank], _kvb(geo, lp, heads))
    k_rope = jnp.broadcast_to(rows[..., None, rank:rank + rope],
                              (*kv.shape[:3], rope))
    nope = geo.qk_nope_head_dim
    return (jnp.concatenate([kv[..., :nope], k_rope], axis=-1),
            kv[..., nope:])


def absorb_query(geo: LatentGeometry, lp: dict, q_nope, q_rope):
    """(B, H, nope), (B, H, rope) -> the query over a cached row, (B, H,
    kv_row_lanes): ``[W_kvb^{K,h} q_nope_h | q_rope_h | 0...]``."""
    wk = _kvb(geo, lp)[..., :geo.qk_nope_head_dim]
    qc = jnp.einsum("bhn,chn->bhc", q_nope, wk,
                    preferred_element_type=jnp.float32).astype(q_nope.dtype)
    return _pad_lanes(geo, jnp.concatenate([qc, q_rope], axis=-1))


def head_gate(lp: dict, x):
    """``sigmoid(x W_g)`` (..., H), one gate a head, of the layer's
    normalised input x (..., D); None where the layer holds no gate."""
    return jax.nn.sigmoid(x @ lp["wg"]) if "wg" in lp else None


def unabsorb(geo: LatentGeometry, lp: dict, ctx, gate=None):
    """The weighted sums of cached rows (B, H, kv_row_lanes) -> the layer's
    output (B, D): ``W_kvb``'s V half on the latent lanes, then ``W_o``.
    ``gate`` (B, H) (:func:`head_gate`; None: no gate): a head's output times
    its gate ahead of ``W_o``. A head's scalar commutes with its V half, so
    it multiplies the ``v_head_dim`` lanes that come out, not the
    ``kv_lora_rank`` that go in."""
    wv = _kvb(geo, lp)[..., geo.qk_nope_head_dim:]
    out = jnp.einsum("bhc,chv->bhv", ctx[..., :geo.kv_lora_rank], wv)
    if gate is not None:
        out = out * gate[..., None].astype(out.dtype)
    return out.reshape(ctx.shape[0], -1) @ lp["wo"]
