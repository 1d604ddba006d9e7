"""A stack walked by layer kinds: the ``granitemoehybrid``, ``mellum``,
``mistral4``, ``afmoe``, ``longcat_flash``, ``lfm2_moe``, ``keye_vl2``,
``deepseek_v32`` and ``dots3_note`` families' forward and steps.

The one-block families ride one ``lax.scan`` over a pytree stacked along the
layer axis with K/V as the scanned state. Here a layer is a Mamba-2 mixer
(``models/mamba2.py``: a recurrent state and a convolution window a sequence)
or a position-free GQA attention layer (K/V rows, the paged pool), each then
the routed + shared expert layer (``models/moe.py``): params are PER KIND::

    params = {"embed": (V, D), "final_norm_scale": (D,),
              "mamba": {... stacked along the L_mamba mamba layers},
              "attn":  {... stacked along the L_attn attention layers},
              "moe":   [{...} for each of the L layers]}

A ``mellum`` stack (JetBrains Mellum 2) has no ``mamba`` entry, and beside
``attn`` (its full causal layers, rotated by the YaRN table) a third kind,
``window``: sliding layers stacked the same way, rotated by the plain table,
whose K/V live in a RING of pages (``paged_kv``: the window group); no shared
expert, an untied head (``lm_head``). A ``mistral4`` stack (Mistral Small 4)
has one kind, ``latent`` (``models/mla.py``): every layer caches ONE latent
row a position in the page pool (``paged_kv.LatentPool``); its prefill
attends EXPANDED (keys and values rebuilt per head from the rows) and every
decode step ABSORBED (multi-query attention over the rows as cached);
routed experts plus a shared one, an untied head. An ``afmoe``
stack (Arcee Trinity) is mellum's two kinds with the positions the other way
round (``window`` rotates, ``attn`` takes none: ``cfg.position_free``) and
leaves no other family holds, each applied where a layer has it (``paged_kv.
head_norms`` / ``gated`` / ``post_norm``): ``q_norm`` / ``k_norm`` per head
ahead of the rotation, a gate ``wg`` ahead of ``W_o``, a ``post_scale`` norm
on each sublayer's output; a leading dense layer's ``moe`` entry holds no
router and is a SwiGLU (:func:`_feed_forward`). A ``longcat_flash`` stack
(LongCat-Flash) walks SUBLAYERS: two ``latent`` rows and two dense ``moe``
entries a published layer, the first entry also the layer's routed experts
under ``shortcut``, whose result joins after the second (:func:`_shortcut`).
An ``lfm2_moe`` stack (LiquidAI LFM2) is granite's shape with another
recurrent kind, ``conv`` (``models/shortconv.py``: a gated short convolution
whose state is a window of rows, the state store's one leaf), beside ``attn``
layers that rotate and hold ``q_norm`` / ``k_norm``; its leading ``moe``
entries dense, the rest routed with a ``router_bias`` and no shared expert, a
tied head. What a stack's recurrent kinds keep for a sequence is
:func:`state_shapes`'s to say: the contiguous cache and the paged state store
hold those leaves and no other. A ``keye_vl2`` stack (Kwai Keye-VL 2.0's
language model) has one kind, ``sparse`` (``models/sparse_attn.py``): a
rotated, q/k-normed GQA layer that also caches an INDEX KEY a position (the
page pool's second leaf, ``paged_kv.IndexedPagePool``) and whose query
attends the ``index_topk`` positions its indexer scores highest; routed
experts alone, an untied head. A ``deepseek_v32`` stack (DeepSeek-V3.2-Exp)
has one kind, ``sparse_latent`` (``models/sparse_mla.py``): mistral4's latent
layer whose query attends the positions keye's indexer selects, its rows and
index keys the two leaves of a ``paged_kv.IndexedLatentPool``; afmoe's leading
dense entries, then routed experts chosen within the best expert groups
(``moe.route``) plus a shared one, an untied head. A ``dots3_note`` stack
(dots3-note-prev's language model) holds TWO latent kinds at sizes of their
own (``cfg.latent_geometry``): deepseek's ``sparse_latent`` (plain RoPE, one
routing group) and ``window_latent``, plain latent attention with no indexer
over a band of ``sliding_window`` keys, whose rows live in the window group's
RING (a one-leaf latent pool as wide as ITS row) and rotate by a table of its
own theta; both multiply their latents by the rank factors and gate every
head's output (``wg`` (D, H): ``mla.head_gate``) ahead of ``W_o``. Its ragged
step hands over an ``IndexedLatentPool`` and a ``window=`` group at once.

(the expert weights are a list, not a stack: a row sliced from a ``(L, E, D,
F)`` stack for a prefill's grouped products, whose operands must be whole
buffers, is a 226 MB copy) and the stack is walked by a static Python loop
over ``cfg.layer_types``: layer ``l`` takes entry ``l`` of ``moe`` and the
next row of its kind, so row ``j`` of the state store is updated in place.

Every layer: ``h += residual_multiplier * mixer(rms(h; w1))`` then ``h +=
residual_multiplier * (moe(u) + shared(u))``, ``u = rms(h; w2)``; ``h0 =
embed[ids] * embedding_multiplier``; logits ``= rms(h_L; w_f) @ embed.T /
logits_scaling``. Granite scores ``q k^T * attention_multiplier``: the
kernels and ``decode_attention`` all scale by ``1/sqrt(head_dim)``, so ``q``
is multiplied by ``attention_multiplier * sqrt(head_dim)`` once, ahead.

Not done, by name: boundary hooks and attention statistics (the sweep
drivers), the split runtime, speculation, prefix sharing, quantized KV tiers,
checkpoints: each needs a snapshot of the recurrent state that does not exist
(:func:`refuse_recurrent_state`), or reads "a slot's K/V = every position of
every layer": not a ring (:func:`refuse_window_ring`; the decode's page walk
takes one, masked by position), nor a latent row (:func:`refuse_latent_rows`), nor rows with an index key
beside them that a query reads a selection of (:func:`refuse_index_keys`);
all: :func:`refuse_beyond_kv_rows`."""
from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..lint import graph_contract
from .configs import ModelConfig
from . import mla, sparse_attn, sparse_mla
from .flash_attention import (MAX_BLOCKED_S, QBLOCK, causal_attention,
                              decode_attention, kernel_plan)
from .mamba2 import mamba2_prefill, mamba2_step
from .shortconv import shortconv_prefill, shortconv_step
from .moe import moe_layer
from .paged_kv import (INDEXED_POOLS, LatentPool, PagePool,
                       _attention_decode_latent,
                       _attention_decode_paged, _attention_decode_window,
                       _attention_decode_window_latent,
                       attend_latent, gated, head_norms, post_norm)
from .transformer import (_rmsnorm, apply_rotary, deinterleave_pairs, mlp,
                          precompute_rope)


class RecurrentStateUnsupported(ValueError):
    """A mechanism that keeps, copies or rolls back a sequence's state as K/V
    rows alone was asked to serve a family whose layers also keep recurrent
    state (Mamba-2's convolution window and SSM state, a short convolution's
    window)."""


def refuse_recurrent_state(cfg: ModelConfig, what: str) -> None:
    """Raise for a config with recurrent state: ``what`` names the mechanism
    refusing."""
    if cfg.recurrent_state:
        keeps = ("Mamba-2 layers keep recurrent state (a convolution window "
                 "and an SSM state per sequence)" if cfg.mamba_layers else
                 f"short-convolution layers keep recurrent state (a window "
                 f"of the last {cfg.conv_window - 1} rows per sequence)")
        raise RecurrentStateUnsupported(
            f"{what} does not support family {cfg.family!r}: its {keeps} "
            f"beside the K/V rows, and {what} has no snapshot of that "
            f"recurrent state; there is no fallback")


class WindowRingUnsupported(ValueError):
    """A mechanism that reads a sequence's K/V as every position of every
    layer was asked to serve a family whose window layers keep only a ring of
    the newest positions."""


def refuse_window_ring(cfg: ModelConfig, what: str) -> None:
    """Raise for a config with sliding-window layers: ``what`` names the
    mechanism refusing."""
    if cfg.window_layers:
        raise WindowRingUnsupported(
            f"{what} does not support family {cfg.family!r}: its "
            f"{cfg.window_layers} sliding-window layers keep a ring of the "
            f"newest {cfg.sliding_window} positions in a page group of their "
            f"own beside the full layers' pages, and {what} is written for "
            f"one kind of layer whose K/V rows are every position of every "
            f"layer; there is no fallback")


class LatentRowsUnsupported(ValueError):
    """A mechanism that moves a sequence's cache as K and V rows of ``KV x
    hd`` lanes was asked to serve a family whose layers cache one latent row
    a position."""


def refuse_latent_rows(cfg: ModelConfig, what: str) -> None:
    """Raise for a config with latent-attention layers: ``what`` names the
    mechanism refusing."""
    if cfg.latent_layers or cfg.window_latent_layers:
        raise LatentRowsUnsupported(
            f"{what} does not support family {cfg.family!r}: its "
            f"{cfg.latent_layers + cfg.window_latent_layers} "
            f"latent-attention layers cache ONE row a "
            f"position for all heads (a {cfg.kv_lora_rank}-lane latent and "
            f"{cfg.qk_rope_head_dim} rotated lanes, stored "
            f"{cfg.kv_row_lanes} wide in a one-leaf pool), and {what} is "
            f"written for K and V rows of num_kv_heads x head_dim lanes "
            f"each; there is no fallback")


class IndexKeysUnsupported(ValueError):
    """A mechanism that moves or reads a sequence's cache as K and V rows
    alone was asked to serve a family whose layers also keep an index key a
    position and attend the positions it selects."""


def refuse_index_keys(cfg: ModelConfig, what: str) -> None:
    """Raise for a config with sparse-attention layers: ``what`` names the
    mechanism refusing."""
    if cfg.sparse_layers:
        raise IndexKeysUnsupported(
            f"{what} does not support family {cfg.family!r}: its "
            f"{cfg.sparse_layers} sparse-attention layers keep an index key "
            f"a position ({cfg.index_head_dim} lanes, stored "
            f"{cfg.index_row_lanes} wide) in a second leaf of the page pool "
            f"beside the K/V rows and attend the {cfg.index_topk} positions "
            f"it selects, and {what} is written for a cache of K and V rows "
            f"alone that every query reads whole; there is no fallback")


def refuse_beyond_kv_rows(cfg: ModelConfig, what: str) -> None:
    """What a mechanism that handles plain per-layer K/V rows alone calls:
    the four refusals above, each in its own words."""
    refuse_recurrent_state(cfg, what)
    refuse_window_ring(cfg, what)
    refuse_latent_rows(cfg, what)
    refuse_index_keys(cfg, what)


class HybridCache(NamedTuple):
    """The contiguous decode cache of a stack with recurrent state.

    k, v: (L_attn, B, capacity, KV, hd); length: () int32; state: the
    leaves :func:`state_shapes` names for B sequences, float32."""

    k: jnp.ndarray
    v: jnp.ndarray
    length: jnp.ndarray
    state: dict

    @property
    def capacity(self) -> int:
        return self.k.shape[2]


class WindowCache(NamedTuple):
    """The contiguous decode cache of a stack with sliding-window layers.

    k, v: (L_attn, B, capacity, KV, hd), the full layers'; length: () int32;
    wk, wv: (L_window, B, capacity, KV, hd), EVERY position of the sliding
    layers (the contiguous path masks the band; only the paged pool keeps a
    ring)."""

    k: jnp.ndarray
    v: jnp.ndarray
    length: jnp.ndarray
    wk: jnp.ndarray
    wv: jnp.ndarray

    @property
    def capacity(self) -> int:
        return self.k.shape[2]


class SparseCache(NamedTuple):
    """The contiguous decode cache of a stack of sparse-attention layers.

    k, v: (L, B, capacity, KV, hd); length: () int32; index: (L, B, capacity,
    index_row_lanes), a position's index key as the page pool stores it."""

    k: jnp.ndarray
    v: jnp.ndarray
    length: jnp.ndarray
    index: jnp.ndarray

    @property
    def capacity(self) -> int:
        return self.k.shape[2]


class SparseLatentCache(NamedTuple):
    """The contiguous decode cache of a stack of sparse latent layers.

    rows: (L, B, capacity, kv_row_lanes), :class:`LatentCache`'s; length: ()
    int32; index: (L, B, capacity, index_row_lanes), :class:`SparseCache`'s."""

    rows: jnp.ndarray
    length: jnp.ndarray
    index: jnp.ndarray

    @property
    def capacity(self) -> int:
        return self.rows.shape[2]


class SparseLatentWindowCache(NamedTuple):
    """The contiguous decode cache of a stack of sparse latent layers beside
    window latent layers (``dots3_note``).

    rows, length, index: :class:`SparseLatentCache`'s, the full layers';
    wrows: (L_window, B, capacity, window_row_lanes), EVERY position of the
    window layers' latent rows (the contiguous path masks the band; only the
    paged pool keeps a ring)."""

    rows: jnp.ndarray
    length: jnp.ndarray
    index: jnp.ndarray
    wrows: jnp.ndarray

    @property
    def capacity(self) -> int:
        return self.rows.shape[2]


class LatentCache(NamedTuple):
    """The contiguous decode cache of a stack of latent-attention layers.

    rows: (L, B, capacity, kv_row_lanes), a position's ``[c | k_rope | 0...]``
    as the page pool stores it; length: () int32."""

    rows: jnp.ndarray
    length: jnp.ndarray

    @property
    def capacity(self) -> int:
        return self.rows.shape[2]


def state_shapes(cfg: ModelConfig, rows: int) -> dict:
    """{leaf: shape} of what the stack's recurrent kinds keep for ``rows``
    sequences or slots, a leaf stacked along its kind's layers: Mamba-2
    layers a convolution window ``conv`` and a matrix state ``ssm``; short
    convolutions their window, ``conv``, alone. Empty for a stack whose
    state is its K/V rows."""
    if cfg.mamba_layers:
        return {"conv": (cfg.mamba_layers, rows, cfg.mamba_d_conv - 1,
                         cfg.mamba_conv_dim),
                "ssm": (cfg.mamba_layers, rows, cfg.mamba_heads,
                        cfg.mamba_head_dim, cfg.mamba_d_state)}
    if cfg.conv_layers:
        return {"conv": (cfg.conv_layers, rows, cfg.conv_window - 1,
                         cfg.hidden_size)}
    return {}


def _rms(cfg, x, scale):
    return _rmsnorm(x, scale, cfg.norm_eps)


def _row(tree: dict, j: int) -> dict:
    return {k: v[j] for k, v in tree.items()}


def _kinds(cfg: ModelConfig):
    """(layer, kind, index among its kind) down the stack."""
    seen = {"mamba": 0, "conv": 0, "attention": 0, "sliding_attention": 0,
            "latent_attention": 0, "sparse_attention": 0,
            "sparse_latent_attention": 0, "sliding_latent_attention": 0}
    for layer, kind in enumerate(cfg.layer_types):
        yield layer, kind, seen[kind]
        seen[kind] += 1


def _qkv(cfg: ModelConfig, lp: dict, x):
    """x (B, S, D) -> q (B, S, H, hd) pre-scaled, k, v (B, S, KV, hd), q and k
    normed per head where the layer has the norms; no positions are applied."""
    b, s, _ = x.shape
    hd = cfg.head_dim
    q = (x @ lp["wq"]).reshape(b, s, cfg.num_heads, hd)
    k = (x @ lp["wk"]).reshape(b, s, cfg.num_kv_heads, hd)
    v = (x @ lp["wv"]).reshape(b, s, cfg.num_kv_heads, hd)
    q, k = head_norms(cfg, lp, q, k)
    return q * jnp.asarray(cfg.q_prescale, q.dtype), k, v


def _rotated(cfg: ModelConfig, qkv: tuple, rope):
    """:func:`_qkv`'s result with q and k rotated by ``rope`` (cos, sin) (S,
    rot); None applies no positions."""
    if rope is None:
        return qkv
    q, k, v = qkv
    return (apply_rotary(q, *rope, cfg.rotary_dim),
            apply_rotary(k, *rope, cfg.rotary_dim), v)


def _rope_tables(cfg: ModelConfig, n: int) -> dict:
    """{kind: (cos, sin) (n, rot) or None}: the table each attention kind
    rotates by — none for a kind in ``cfg.position_free``; else the scaled
    one (YaRN) on full layers and the plain one on sliding layers."""
    if cfg.latent_layers:  # the rope lanes' table (cfg.rotary_dim wide)
        # (a sparse latent layer's indexer rotates by it too)
        tables = {"sparse_latent_attention" if cfg.sparse_layers
                  else "latent_attention": precompute_rope(cfg, n)}
        if cfg.window_latent_layers:    # a table a kind: its own theta
            tables["sliding_latent_attention"] = mla.plain_rope(
                cfg.window_latent, n)
        return tables
    if cfg.sparse_layers:  # the heads' table, and the indexer's narrower one
        return {"sparse_attention": precompute_rope(cfg, n),
                "sparse_index": sparse_attn.index_rope(cfg, n)}
    free = cfg.position_free
    return {"attention": (None if "attention" in free
                          else precompute_rope(cfg, n)),
            "sliding_attention": (
                precompute_rope(cfg, n, scaled=False) if cfg.window_layers
                and "sliding_attention" not in free else None)}


def _attention_blocks(q, k, v, window: int):
    """Causal (``window`` 0) or banded GQA attention in plain XLA, a block of
    :data:`QBLOCK` query rows at a time against the keys that block can see:
    no (H, S, S) score tensor exists (32 x 4096^2 float32 would be 2.1 GB a
    layer), and a sliding layer's block reads at most ``QBLOCK + window - 1``
    keys. Position i attends j with ``i - window < j <= i``. q (B, S, H,
    hd), k (B, S, KV, hd), v (B, S, KV, vd) -> (B, S, H, vd); softmax in
    float32."""
    b, s, h, hd = q.shape
    kv = k.shape[2]
    outs = []
    for start in range(0, s, QBLOCK):
        stop = min(start + QBLOCK, s)
        lo = max(0, start - window + 1) if window else 0
        qb = q[:, start:stop].reshape(b, stop - start, kv, h // kv, hd)
        scores = jnp.einsum("bqgrd,bcgd->bgrqc", qb, k[:, lo:stop],
                            preferred_element_type=jnp.float32)
        scores = scores * (1.0 / float(hd) ** 0.5)
        qi = jnp.arange(start, stop)[:, None]
        kj = jnp.arange(lo, stop)[None, :]
        seen = kj <= qi
        if window:
            seen &= kj > qi - window
        scores = jnp.where(seen, scores, jnp.finfo(jnp.float32).min)
        probs = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bgrqc,bcgd->bqgrd", probs.astype(q.dtype),
                         v[:, lo:stop], preferred_element_type=jnp.float32)
        outs.append(out.astype(q.dtype).reshape(b, stop - start, h,
                                                v.shape[-1]))
    return outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=1)


def _attention_full(cfg: ModelConfig, lp: dict, x, rope=None,
                    window: int = 0):
    """Attention over whole sequences, causal or (``window`` > 0) banded ->
    (out (B, S, D), k, v). A stack with sliding layers, and any prompt past
    the kernels' envelope, goes by query blocks in plain XLA, its full
    layers too; otherwise the prefill kernel where it has a plan and
    ``jax.nn.dot_product_attention`` where not."""
    b, s, _ = x.shape
    q, k, v = _rotated(cfg, _qkv(cfg, lp, x), rope)
    if cfg.window_layers or s > MAX_BLOCKED_S:
        out = _attention_blocks(q, k, v, window)
    else:
        plan = kernel_plan(s, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
                           itemsize=jnp.dtype(x.dtype).itemsize)
        out = (causal_attention(q, k, v, plan=plan) if plan is not None
               else jax.nn.dot_product_attention(q, k, v, is_causal=True))
    return _attn_out(cfg, lp, x, out.reshape(b, s, -1)), k, v


def _attention_latent_full(cfg: ModelConfig, lp: dict, x, rope):
    """A latent layer over whole sequences, EXPANDED -> (out (B, S, D), rows
    (B, S, kv_row_lanes) as the cache takes them): keys and values rebuilt
    for every head from the rows (``mla.expand``), then causal attention by
    blocks of :data:`QBLOCK` query rows. The widest tensor is the last
    block's scores, (1, H, 1, QBLOCK, S) float32: 32 x 512 x 8192 x 4 B =
    537 MB at a 8192-token prompt, beside the expanded K and V of (S, H, 128)
    each, 67 MB a leaf in bf16."""
    b, s, _ = x.shape
    cos, sin = rope
    geo = cfg.latent_geometry("latent_attention")
    q_nope, q_rope, rows = mla.project(
        cfg, geo, lp, x, lambda t: apply_rotary(t, cos, sin, cfg.rotary_dim),
        jnp.broadcast_to(mla.query_scale(cfg, geo, jnp.arange(s)), (b, s)))
    with jax.named_scope("attn.latent.expand"):
        k, v = mla.expand(geo, lp, rows)
        out = _attention_blocks(jnp.concatenate([q_nope, q_rope], axis=-1),
                                k, v, 0)
    return out.reshape(b, s, -1) @ lp["wo"], rows


@jax.named_scope("attn.latent")
def _attention_latent_step(cfg: ModelConfig, lp: dict, x, rope, rows_all,
                           pos):
    """A latent layer's decode against ONE layer of a contiguous cache,
    ABSORBED: x (B, D), rope (cos, sin) (1, rot) at ``pos``, rows_all (B,
    capacity, kv_row_lanes) -> (out (B, D), rows_all with position ``pos``
    written)."""
    b = x.shape[0]
    geo = cfg.latent_geometry("latent_attention")
    q_nope, q_rope, row = mla.project(
        cfg, geo, lp, x, mla.rotate_rows(*rope),
        mla.query_scale(cfg, geo, jnp.broadcast_to(pos, (b,))))
    rows_all = jax.lax.dynamic_update_slice(
        rows_all, row[:, None].astype(rows_all.dtype), (0, pos, 0))
    ctx = attend_latent(mla.absorb_query(geo, lp, q_nope, q_rope), rows_all,
                        jnp.broadcast_to(pos + 1, (b,)), cfg.head_dim)
    return mla.unabsorb(geo, lp, ctx), rows_all


#: heads whose queries, keys and values a window latent layer's prefill
#: rebuilds at a time: 16 heads' (S, 256 + 256 + 128) are 336 MB at 16384
#: positions where all 64 would be 1.3 GB
BAND_HEADS = 16


@jax.named_scope("attn.window_latent.prefill")
def _attention_window_latent_full(cfg: ModelConfig, lp: dict, x, rope):
    """A window latent layer over whole sequences, EXPANDED under the band ->
    (out (B, S, D), rows (B, S, window_row_lanes) as the ring takes them):
    position t attends ``s`` with ``t - sliding_window < s <= t``.
    :data:`BAND_HEADS` heads at a time (a ``lax.map``: one group's queries,
    keys and values live at once), their queries made from ``c_q`` and their
    keys and values rebuilt from the rows (``mla.expand``), attended by
    blocks of :data:`QBLOCK` query rows against the ``QBLOCK + window - 1``
    keys the band lets them see (:func:`_attention_blocks`, plain XLA: timed
    alone on a v5e at the cell's widths against
    ``flash_attention.masked_attention`` under the band's mask, the blocks
    were ahead over the cell's two prompt lengths together and are the one
    attend kept: PERF.md section 6 "PR 54"); then the heads' gate and ``W_o``
    once."""
    b, s, _ = x.shape
    cos, sin = rope
    geo = cfg.latent_geometry("sliding_latent_attention")
    rot, nope = geo.qk_rope_head_dim, geo.qk_nope_head_dim

    def rotate(t):
        return apply_rotary(t, cos, sin, rot)

    c_q = mla.query_latent(cfg, lp, x)
    rows = mla.latent_row(cfg, geo, lp, x, rotate)
    scale = jnp.broadcast_to(mla.query_scale(cfg, geo, jnp.arange(s)), (b, s))
    hg = math.gcd(geo.num_heads, BAND_HEADS)

    def group(first):
        q = mla.head_queries(geo, lp, c_q, scale, (first, hg))
        q = jnp.concatenate(
            [q[..., :nope], rotate(deinterleave_pairs(q[..., nope:]))],
            axis=-1)
        k, v = mla.expand(geo, lp, rows, (first, hg))
        return _attention_blocks(q, k, v, cfg.sliding_window)

    outs = jax.lax.map(group, jnp.arange(0, geo.num_heads, hg))
    # (groups, B, S, hg, vd) -> (B, S, H vd)
    ctx = jnp.moveaxis(outs, 0, 2).reshape(b, s, -1)
    return gated(lp, x, ctx) @ lp["wo"], rows


#: a prefill whose routed layer would gather more than FFN_ROWS_MAX bytes of
#: token rows (tokens x experts_per_tok x hidden: 1.9 GB at 16384 x 8 x 7168
#: in bf16, beside as much again in products) takes its feed-forwards, the
#: dense ones too, FFN_CHUNK_ROWS bytes of such rows at a time; nothing at
#: or under it is touched (the widest before this rule: 512 MiB, 16384 x 8 x
#: 2048)
FFN_ROWS_MAX = 768 << 20
FFN_CHUNK_ROWS = 256 << 20


def _ffn_chunk(cfg: ModelConfig, u) -> int:
    """Tokens of u (T, D) a feed-forward takes at once (0: all of them), a
    power of two; see :data:`FFN_ROWS_MAX`."""
    row = cfg.experts_per_tok * u.shape[-1] * u.dtype.itemsize
    if u.shape[0] * row <= FFN_ROWS_MAX:
        return 0
    return 1 << (FFN_CHUNK_ROWS // row).bit_length() - 1


def _ffn(cfg: ModelConfig, mp: dict, h, active=None):
    """The feed-forward sublayer on h (..., D); returns (h, counts (Eh,)),
    counts None where the tokens went in chunks (a prefill's, which nobody
    reads)."""
    u = _rms(cfg, h, mp["ln2_scale"])
    u = u.reshape(-1, u.shape[-1])
    chunk = _ffn_chunk(cfg, u)
    if not chunk:
        out, counts = _feed_forward(cfg, mp, u, active)
        return h + cfg.residual_multiplier * out.reshape(h.shape), counts
    pad = -u.shape[0] % chunk
    chunks = jnp.pad(u, ((0, pad), (0, 0))).reshape(-1, chunk, u.shape[-1])
    out = jax.lax.map(lambda c: _feed_forward(cfg, mp, c)[0], chunks)
    out = out.reshape(-1, u.shape[-1])[:u.shape[0]]
    return h + cfg.residual_multiplier * out.reshape(h.shape), None


def _step_row(cfg: ModelConfig, kind: str, lp: dict, h, state: dict, j: int):
    """One recurrent layer's decode update against row ``j`` of a state
    store's leaves (L_kind, rows, ...) -> (state, out): the row is read,
    updated and written back in place. The read and the write stand under
    the update's scope (``ssm.step``, ``shortconv.conv``): XLA fuses them
    into it, and the fusion is named after the write."""
    if kind == "conv":
        with jax.named_scope("shortconv.conv"):
            window = state["conv"][j]
        out, window = shortconv_step(cfg, lp, _rms(cfg, h, lp["ln1_scale"]),
                                     window)
        with jax.named_scope("shortconv.conv"):
            return {"conv": state["conv"].at[j].set(window)}, out
    with jax.named_scope("ssm.step"):
        conv, ssm = state["conv"][j], state["ssm"][j]
    out, conv, ssm = mamba2_step(cfg, lp, _rms(cfg, h, lp["ln1_scale"]), conv,
                                 ssm)
    with jax.named_scope("ssm.step"):
        return {"conv": state["conv"].at[j].set(conv),
                "ssm": state["ssm"].at[j].set(ssm)}, out


def embed_hybrid(cfg: ModelConfig, params: dict, ids):
    h = jnp.take(params["embed"], ids, axis=0)
    return h * jnp.asarray(cfg.embedding_multiplier, h.dtype)


def unembed_hybrid(cfg: ModelConfig, params: dict, hidden):
    """(..., D) -> float32 logits (..., V) over the tied table, or over an
    untied ``lm_head`` (D, V)."""
    post = _rms(cfg, hidden, params["final_norm_scale"])
    if not cfg.tie_word_embeddings:
        return jnp.einsum("...d,dv->...v", post, params["lm_head"],
                          preferred_element_type=jnp.float32)
    logits = jnp.einsum("...d,vd->...v", post, params["embed"],
                        preferred_element_type=jnp.float32)
    return logits / cfg.logits_scaling


def _walk_full(cfg: ModelConfig, params: dict, ids, collect: bool):
    """Whole sequences through the stack. Returns (hidden (B, S, D), per-kind
    lists of what a decode cache is filled from when ``collect``)."""
    h, term = embed_hybrid(cfg, params, ids), None   # term: _shortcut's
    ks, vs, wks, wvs, lat, iks = [], [], [], [], [], []
    # (a window latent layer's rows ride in ``wks``, and ``wvs`` stays empty)
    state = {leaf: [] for leaf in state_shapes(cfg, 0)}
    rope = _rope_tables(cfg, ids.shape[1])
    for layer, kind, j in _kinds(cfg):
        if kind == "latent_attention":
            lp = _row(params["latent"], j)
            out, rows = _attention_latent_full(
                cfg, lp, _rms(cfg, h, lp["ln1_scale"]), rope[kind])
            if collect:
                lat.append(rows)
        elif kind == "sparse_latent_attention":
            lp = _row(params["sparse_latent"], j)
            out, rows, ik = sparse_mla.attention_full(
                cfg, lp, _rms(cfg, h, lp["ln1_scale"]), rope[kind])
            if collect:
                lat.append(rows)
                iks.append(ik)
        elif kind == "sliding_latent_attention":
            lp = _row(params["window_latent"], j)
            out, rows = _attention_window_latent_full(
                cfg, lp, _rms(cfg, h, lp["ln1_scale"]), rope[kind])
            if collect:
                wks.append(rows)
        elif kind == "sparse_attention":
            lp = _row(params["sparse"], j)
            out, k, v, ik = sparse_attn.attention_full(
                cfg, lp, _rms(cfg, h, lp["ln1_scale"]), rope[kind],
                rope["sparse_index"])
            if collect:
                ks.append(k)
                vs.append(v)
                iks.append(ik)
        elif kind == "mamba":
            lp = _row(params["mamba"], j)
            out, conv, ssm = mamba2_prefill(
                cfg, lp, _rms(cfg, h, lp["ln1_scale"]))
            if collect:
                state["conv"].append(conv)
                state["ssm"].append(ssm)
        elif kind == "conv":
            lp = _row(params["conv"], j)
            out, window = shortconv_prefill(
                cfg, lp, _rms(cfg, h, lp["ln1_scale"]))
            if collect:
                state["conv"].append(window)
        elif kind == "sliding_attention":
            lp = _row(params["window"], j)
            out, k, v = _attention_full(
                cfg, lp, _rms(cfg, h, lp["ln1_scale"]), rope[kind],
                cfg.sliding_window)
            if collect:
                wks.append(k)
                wvs.append(v)
        else:
            lp = _row(params["attn"], j)
            out, k, v = _attention_full(
                cfg, lp, _rms(cfg, h, lp["ln1_scale"]), rope[kind])
            if collect:
                ks.append(k)
                vs.append(v)
        h = h + cfg.residual_multiplier * out
        g, _ = _ffn(cfg, params["moe"][layer], h)
        h, term, _ = _shortcut(cfg, params["moe"][layer], h, g, term)
    return h, (ks, vs, state, wks, wvs, lat, iks)


def forward_hybrid(cfg: ModelConfig, params: dict, ids):
    """ids (B, S) -> float32 logits (B, S, V)."""
    h, _ = _walk_full(cfg, params, ids, collect=False)
    return unembed_hybrid(cfg, params, h)


def _stack(items: list, shape: tuple, dtype):
    return jnp.stack(items) if items else jnp.zeros(shape, dtype)


def prefill_hybrid(cfg: ModelConfig, params: dict, ids, capacity: int,
                   last_only: bool = False):
    """The prompt's forward that also fills the decode cache: (logits (B, S,
    V) float32 — (B, V) of the last position with ``last_only`` —, a
    :class:`HybridCache`, :class:`WindowCache`, :class:`LatentCache`,
    :class:`SparseCache`, :class:`SparseLatentCache` or
    :class:`SparseLatentWindowCache`)."""
    b, s = ids.shape
    if not 0 < s <= capacity:
        raise ValueError(f"prompt length {s} must be in [1, capacity="
                         f"{capacity}]")
    h, (ks, vs, state, wks, wvs, lat, iks) = _walk_full(cfg, params, ids,
                                                        collect=True)
    logits = unembed_hybrid(cfg, params, h[:, -1] if last_only else h)
    if lat:
        grow = ((0, 0), (0, 0), (0, capacity - s), (0, 0))
        rows, length = jnp.pad(jnp.stack(lat), grow), jnp.asarray(s, jnp.int32)
        if wks:
            return logits, SparseLatentWindowCache(
                rows, length, jnp.pad(jnp.stack(iks), grow),
                jnp.pad(jnp.stack(wks), grow))
        if iks:
            return logits, SparseLatentCache(
                rows, length, jnp.pad(jnp.stack(iks), grow))
        return logits, LatentCache(rows, length)
    kv_shape = (0, b, s, cfg.num_kv_heads, cfg.head_dim)
    pad = ((0, 0), (0, 0), (0, capacity - s), (0, 0), (0, 0))
    k = jnp.pad(_stack(ks, kv_shape, h.dtype), pad)
    v = jnp.pad(_stack(vs, kv_shape, h.dtype), pad)
    length = jnp.asarray(s, jnp.int32)
    if cfg.window_layers:
        return logits, WindowCache(k, v, length, jnp.pad(jnp.stack(wks), pad),
                                   jnp.pad(jnp.stack(wvs), pad))
    if iks:
        return logits, SparseCache(k, v, length,
                                   jnp.pad(jnp.stack(iks), pad[:-1]))
    return logits, HybridCache(k, v, length,
                               {leaf: jnp.stack(rows)
                                for leaf, rows in state.items()})


def decode_step_hybrid(cfg: ModelConfig, params: dict, cache, token_ids):
    """Append one position to every row of a contiguous cache: token ids (B,)
    or (B, 1) -> (logits (B, V) float32, updated cache)."""
    if token_ids.ndim == 2:
        token_ids = token_ids[:, 0]
    b = token_ids.shape[0]
    pos = cache.length
    h = embed_hybrid(cfg, params, token_ids)                  # (B, D)
    if isinstance(cache, (SparseLatentCache, SparseLatentWindowCache)):
        return _decode_step_sparse_latent(cfg, params, cache, h)
    if isinstance(cache, LatentCache):
        return _decode_step_latent(cfg, params, cache, h)
    if isinstance(cache, SparseCache):
        return _decode_step_sparse(cfg, params, cache, h)
    windowed = isinstance(cache, WindowCache)
    state = None if windowed else cache.state
    # the rows a kind's layers append to: full layers k / v, sliding wk / wv
    rows = {"attention": [cache.k, cache.v],
            "sliding_attention": [cache.wk, cache.wv] if windowed else None}
    rope = {kind: t and tuple(jax.lax.dynamic_slice_in_dim(x, pos, 1)
                              for x in t)
            for kind, t in _rope_tables(cfg, cache.capacity).items()}
    for layer, kind, j in _kinds(cfg):
        if kind in ("mamba", "conv"):
            state, out = _step_row(cfg, kind, _row(params[kind], j), h, state,
                                   j)
        else:
            sliding = kind == "sliding_attention"
            lp = _row(params["window" if sliding else "attn"], j)
            k_all, v_all = rows[kind]
            # x is kept: a layer with an output gate reads it a second time,
            # after the attend (:func:`_attn_out`)
            x = _rms(cfg, h, lp["ln1_scale"])
            q, k, v = _rotated(cfg, _qkv(cfg, lp, x[:, None]), rope[kind])
            kc = jax.lax.dynamic_update_slice(
                k_all[j], k.astype(k_all.dtype), (0, pos, 0, 0))
            vc = jax.lax.dynamic_update_slice(
                v_all[j], v.astype(v_all.dtype), (0, pos, 0, 0))
            out = _attn_out(cfg, lp, x, decode_attention(
                q, kc, vc, pos + 1,
                cfg.sliding_window if sliding else 0).reshape(b, -1))
            rows[kind] = [k_all.at[j].set(kc), v_all.at[j].set(vc)]
        h = h + cfg.residual_multiplier * out
        h, _ = _ffn(cfg, params["moe"][layer], h)
    logits = unembed_hybrid(cfg, params, h)
    if windowed:
        return logits, WindowCache(*rows["attention"], pos + 1,
                                   *rows["sliding_attention"])
    return logits, HybridCache(*rows["attention"], pos + 1, state)


def _decode_step_latent(cfg: ModelConfig, params: dict, cache: LatentCache,
                        h):
    """:func:`decode_step_hybrid` for a stack of latent layers: h (B, D) the
    embedded tokens."""
    pos, rows, term = cache.length, cache.rows, None
    rope = tuple(jax.lax.dynamic_slice_in_dim(x, pos, 1) for x in
                 _rope_tables(cfg, cache.capacity)["latent_attention"])
    for layer, _, j in _kinds(cfg):
        lp = _row(params["latent"], j)
        out, rows_j = _attention_latent_step(
            cfg, lp, _rms(cfg, h, lp["ln1_scale"]), rope, rows[j], pos)
        rows = rows.at[j].set(rows_j)
        h = h + cfg.residual_multiplier * out
        g, _ = _ffn(cfg, params["moe"][layer], h)
        h, term, _ = _shortcut(cfg, params["moe"][layer], h, g, term)
    return unembed_hybrid(cfg, params, h), LatentCache(rows, pos + 1)


def _decode_step_sparse(cfg: ModelConfig, params: dict, cache: SparseCache,
                        h):
    """:func:`decode_step_hybrid` for a stack of sparse-attention layers: h
    (B, D) the embedded tokens."""
    pos, (k, v, _, index) = cache.length, cache
    rope = {kind: tuple(jax.lax.dynamic_slice_in_dim(x, pos, 1) for x in t)
            for kind, t in _rope_tables(cfg, cache.capacity).items()}
    for layer, kind, j in _kinds(cfg):
        lp = _row(params["sparse"], j)
        out, k_j, v_j, index_j = sparse_attn.attention_decode_rows(
            cfg, lp, _rms(cfg, h, lp["ln1_scale"]), rope[kind],
            rope["sparse_index"], k[j], v[j], index[j], pos)
        k, v, index = (a.at[j].set(r) for a, r in
                       ((k, k_j), (v, v_j), (index, index_j)))
        h = h + cfg.residual_multiplier * out
        h, _ = _ffn(cfg, params["moe"][layer], h)
    return unembed_hybrid(cfg, params, h), SparseCache(k, v, pos + 1, index)


def _decode_step_sparse_latent(cfg: ModelConfig, params: dict, cache, h):
    """:func:`decode_step_hybrid` for a stack of sparse latent layers (a
    :class:`SparseLatentCache`), or of those beside window latent layers (a
    :class:`SparseLatentWindowCache`): h (B, D) the embedded tokens."""
    pos, rows, index = cache.length, cache.rows, cache.index
    wrows = getattr(cache, "wrows", None)
    rope = {kind: tuple(jax.lax.dynamic_slice_in_dim(x, pos, 1) for x in t)
            for kind, t in _rope_tables(cfg, cache.capacity).items()}
    for layer, kind, j in _kinds(cfg):
        if kind == "sliding_latent_attention":
            lp = _row(params["window_latent"], j)
            out, wrows_j = _attention_window_latent_step(
                cfg, lp, _rms(cfg, h, lp["ln1_scale"]), rope[kind], wrows[j],
                pos)
            wrows = wrows.at[j].set(wrows_j)
        else:
            lp = _row(params["sparse_latent"], j)
            out, rows_j, index_j = sparse_mla.attention_decode_rows(
                cfg, lp, _rms(cfg, h, lp["ln1_scale"]), rope[kind], rows[j],
                index[j], pos)
            rows, index = rows.at[j].set(rows_j), index.at[j].set(index_j)
        h = h + cfg.residual_multiplier * out
        h, _ = _ffn(cfg, params["moe"][layer], h)
    logits = unembed_hybrid(cfg, params, h)
    if wrows is None:
        return logits, SparseLatentCache(rows, pos + 1, index)
    return logits, SparseLatentWindowCache(rows, pos + 1, index, wrows)


@jax.named_scope("attn.window_latent")
def _attention_window_latent_step(cfg: ModelConfig, lp: dict, x, rope,
                                  rows_all, pos):
    """A window latent layer's decode against ONE layer of a contiguous
    cache, ABSORBED: x (B, D), rope (cos, sin) (1, rot) at ``pos``, rows_all
    (B, capacity, window_row_lanes) -> (out (B, D), rows_all with position
    ``pos`` written). The band is a mask over every cached position."""
    b = x.shape[0]
    geo = cfg.latent_geometry("sliding_latent_attention")
    q_nope, q_rope, row = mla.project(
        cfg, geo, lp, x, mla.rotate_rows(*rope),
        mla.query_scale(cfg, geo, jnp.broadcast_to(pos, (b,))))
    rows_all = jax.lax.dynamic_update_slice(
        rows_all, row[:, None].astype(rows_all.dtype), (0, pos, 0))
    at = jnp.arange(rows_all.shape[1])
    seen = (at <= pos) & (at > pos - cfg.sliding_window)
    ctx = attend_latent(mla.absorb_query(geo, lp, q_nope, q_rope), rows_all,
                        None, geo.head_dim, jnp.broadcast_to(seen, (
                            b, rows_all.shape[1])))
    return mla.unabsorb(geo, lp, ctx, mla.head_gate(lp, x)), rows_all


@graph_contract("paged.decode_step_window_latent", collectives={},
                donate=lambda ctx: ctx.get("donate_min", 4))
@graph_contract("paged.decode_step_sparse_latent", collectives={},
                donate=lambda ctx: ctx.get("donate_min", 3))
@graph_contract("paged.decode_step_sparse", collectives={},
                donate=lambda ctx: ctx.get("donate_min", 3))
@graph_contract("paged.decode_step_latent", collectives={},
                donate=lambda ctx: ctx.get("donate_min", 2))
@graph_contract("paged.decode_step_hybrid", collectives={},
                donate=lambda ctx: ctx.get("donate_min", 4))
@graph_contract("paged.decode_step_shortconv", collectives={},
                donate=lambda ctx: ctx.get("donate_min", 3))
@graph_contract("paged.decode_step_window", collectives={},
                donate=lambda ctx: ctx.get("donate_min", 3))
def paged_decode_step_hybrid(cfg: ModelConfig, params: dict, pool, state,
                             expert_tokens, page_table, lengths, token_ids,
                             window=None):
    """The ragged step for a hybrid stack: one position for EVERY slot.

    pool: the page pool's ONE leaf — (L_attn, num_pages, page_size, 2 * KV *
    hd), a ``paged_kv.PagePool``'s K-then-V rows, or for a stack of latent
    layers (``cfg.latent_layers``) a ``LatentPool``'s (L, num_pages,
    page_size, kv_row_lanes) — addressed by the
    static attention-layer number (paged_kv's flat index); state: the
    per-slot state store, :func:`state_shapes`'s leaves (L_kind, max_slots,
    ...) float32, None for a stack that keeps none; expert_tokens
    (L expert layers, Eh) int32, the count of assignments per held expert, which
    gains this step's over the slots with ``lengths > 0`` (a free slot runs
    token-0 math into the trash page and into its own dead state rows, and is
    not counted). Returns (logits (max_slots, V) float32, pool, state,
    expert_tokens).

    A stack with sliding layers also passes ``window`` = (win (L_window,
    window pool pages, page_size, 2 * KV * hd), the window group's leaf,
    window_table (max_slots, window_pages): each slot's ring) and gets win
    back as a fifth result; it has no recurrent layer. A stack of
    sparse-attention layers hands over its ``paged_kv.IndexedPagePool``
    WHOLE as ``pool`` (both leaves: the K/V rows and the index keys) and gets
    it back so, as a stack of sparse latent layers does its
    ``paged_kv.IndexedLatentPool``. A ``dots3_note`` stack does both at
    once: its ``IndexedLatentPool`` whole as ``pool`` and ``window`` = (the
    ring group's ONE leaf of latent rows (L_window, pages, page_size,
    window_row_lanes), its table)."""
    if token_ids.ndim == 2:
        token_ids = token_ids[:, 0]
    active = lengths > 0
    h = embed_hybrid(cfg, params, token_ids)                  # (B, D)
    counts, term = [], None
    # each slot's own row of the table its layer kind rotates by
    span = page_table.shape[1] * (
        pool.page_size if isinstance(pool, INDEXED_POOLS) else pool.shape[2])
    rope = {kind: t and (t[0][lengths], t[1][lengths])
            for kind, t in _rope_tables(cfg, span).items()}
    if window is not None:
        win, window_table = window
    for layer, kind, j in _kinds(cfg):
        if kind == "latent_attention":
            lp = _row(params["latent"], j)
            out, (pool,) = _attention_decode_latent(
                cfg, lp, _rms(cfg, h, lp["ln1_scale"]), *rope[kind],
                LatentPool(pool), j, page_table, lengths)
        elif kind == "sparse_latent_attention":
            lp = _row(params["sparse_latent"], j)
            out, pool = sparse_mla.attention_decode_paged(
                cfg, lp, _rms(cfg, h, lp["ln1_scale"]), rope[kind], pool, j,
                page_table, lengths)
        elif kind == "sparse_attention":
            lp = _row(params["sparse"], j)
            out, pool = sparse_attn.attention_decode_paged(
                cfg, lp, _rms(cfg, h, lp["ln1_scale"]), rope[kind],
                rope["sparse_index"], pool, j, page_table, lengths)
        elif kind in ("mamba", "conv"):
            state, out = _step_row(cfg, kind, _row(params[kind], j), h, state,
                                   j)
        elif kind == "sliding_latent_attention":
            lp = _row(params["window_latent"], j)
            out, (win,) = _attention_decode_window_latent(
                cfg, lp, _rms(cfg, h, lp["ln1_scale"]), *rope[kind],
                LatentPool(win), j, window_table, lengths)
        elif kind == "sliding_attention":
            lp = _row(params["window"], j)
            out, (win,) = _attention_decode_window(
                cfg, lp, _rms(cfg, h, lp["ln1_scale"])[:, None],
                *(rope[kind] or (None, None)), PagePool(win), j,
                window_table, lengths)
            out = out[:, 0]
        else:
            lp = _row(params["attn"], j)
            out, (pool,) = _attention_decode_paged(
                cfg, lp, _rms(cfg, h, lp["ln1_scale"])[:, None],
                *(rope[kind] or (None, None)),
                PagePool(pool), j, page_table, lengths)
            out = out[:, 0]
        h = h + cfg.residual_multiplier * out
        g, c = _ffn(cfg, params["moe"][layer], h, active)
        h, term, c = _shortcut(cfg, params["moe"][layer], h, g, term, c,
                               active)
        if c is not None:                      # a dense layer routes nothing
            counts.append(c)
    with jax.named_scope("unembed_sample"):
        logits = unembed_hybrid(cfg, params, h)
    out = (logits, pool, state, expert_tokens + jnp.stack(counts))
    return out if window is None else out + (win,)


def init_params_hybrid(cfg: ModelConfig, key: jax.Array,
                       dtype=jnp.float32) -> dict:
    """Random init for tests and smoke runs: normal std 0.02, norm scales
    one; the Mamba-2 scalars take ``mamba_ssm``'s initialisation so that the
    state matters (``A_log = log U[1, 16]``, ``dt_bias = softplus^-1(dt)``,
    ``dt`` log-uniform in [0.001, 0.1], ``D = 1``, the convolution uniform in
    +-1/sqrt(d_conv)). A kind the stack has no layer of has no entry, nor has
    an absent shared expert or a tied head. An ``afmoe`` stack's kinds also
    hold the per-head ``q_norm`` / ``k_norm``, the gate ``wg`` and
    ``post_scale``; its leading dense layers' ``moe`` entries a SwiGLU of
    ``intermediate_size`` and no router, its expert layers a float32
    ``router_bias`` drawn NONZERO (a checkpoint's is trained; at zero a path
    that dropped it would pass); a stack of sublayers: :func:`_sub_ffns`. An
    ``lfm2_moe`` stack's ``attn`` holds the two head norms alone; its
    ``conv`` kind's taps are uniform in +-1/sqrt(taps), as a depthwise
    convolution is initialised upstream, so that the window matters. A
    ``keye_vl2`` stack's ``sparse`` kind holds an attention layer's leaves
    with the two head norms, and the indexer's: ``wq_index``, ``wk_index``,
    the index key's LayerNorm (``index_norm_scale`` one, ``index_norm_bias``
    zero) and ``w_index``. A ``deepseek_v32`` stack's ``sparse_latent`` kind
    holds a latent layer's leaves and the same indexer's, ``wq_index`` off
    the q latent (q_lora_rank rows). A ``dots3_note`` stack's
    ``sparse_latent`` kind also holds the heads' gate ``wg`` (D, H), and its
    ``window_latent`` kind a latent layer's leaves at the window kind's own
    sizes (``cfg.window_latent``) with a gate of its own heads."""
    keys = iter(jax.random.split(key, 16 + 8 * len(cfg.layer_types)))

    def init(*shape):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * 0.02).astype(dtype)

    d, hd = cfg.hidden_size, cfg.head_dim
    lm, la, lt = cfg.mamba_layers, cfg.kv_layers, cfg.num_layers
    eh, f, fs = cfg.local_experts, cfg.expert_width, cfg.shared_width

    afmoe = cfg.family == "afmoe"
    head_normed = afmoe or cfg.family in ("lfm2_moe", "keye_vl2")

    def attention(n):
        return {
            "ln1_scale": jnp.ones((n, d), dtype),
            "wq": init(n, d, cfg.num_heads * hd),
            "wk": init(n, d, cfg.num_kv_heads * hd),
            "wv": init(n, d, cfg.num_kv_heads * hd),
            "wo": init(n, cfg.num_heads * hd, d),
            **({"q_norm": jnp.ones((n, hd), dtype),
                "k_norm": jnp.ones((n, hd), dtype)} if head_normed else {}),
            **({"wg": init(n, d, cfg.num_heads * hd),
                "post_scale": jnp.ones((n, d), dtype)} if afmoe else {}),
        }

    if lm:  # drawn first, as before the stack had kinds without it
        dt = jnp.exp(jax.random.uniform(
            next(keys), (lm, cfg.mamba_heads), jnp.float32,
            jnp.log(0.001), jnp.log(0.1)))
    params = {
        # the tied table at 0.02 / embedding_multiplier: h0 then starts at
        # the other matrices' std and a token's own row does not win every
        # logit by embedding_multiplier * |row|^2
        "embed": (init(cfg.vocab_size, d).astype(jnp.float32)
                  / cfg.embedding_multiplier).astype(dtype),
        "final_norm_scale": jnp.ones((d,), dtype),
    }
    if lm:
        nh, di, cd = cfg.mamba_heads, cfg.mamba_d_inner, cfg.mamba_conv_dim
        params["mamba"] = {
            "ln1_scale": jnp.ones((lm, d), dtype),
            "w_in": init(lm, d, di + cd + nh),
            "conv_w": jax.random.uniform(
                next(keys), (lm, cd, cfg.mamba_d_conv), jnp.float32,
                -cfg.mamba_d_conv ** -0.5, cfg.mamba_d_conv ** -0.5
            ).astype(dtype),
            "conv_b": init(lm, cd),
            "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype),
            "A_log": jnp.log(jax.random.uniform(
                next(keys), (lm, nh), jnp.float32, 1.0, 16.0)).astype(dtype),
            "D": jnp.ones((lm, nh), dtype),
            "norm_scale": jnp.ones((lm, di), dtype),
            "w_out": init(lm, di, d),
        }
    if cfg.conv_layers:
        lc, taps = cfg.conv_layers, cfg.conv_window
        params["conv"] = {
            "ln1_scale": jnp.ones((lc, d), dtype),
            "w_in": init(lc, d, 3 * d),
            "conv_w": jax.random.uniform(
                next(keys), (lc, d, taps), jnp.float32, -taps ** -0.5,
                taps ** -0.5).astype(dtype),
            "w_out": init(lc, d, d),
        }
    if cfg.latent_layers:
        n, h = cfg.latent_layers, cfg.num_heads
        rank = cfg.kv_lora_rank
        latent = {
            "ln1_scale": jnp.ones((n, d), dtype),
            "wq_a": init(n, d, cfg.q_lora_rank),
            "q_norm": jnp.ones((n, cfg.q_lora_rank), dtype),
            "wq_b": init(n, cfg.q_lora_rank, h * hd),
            "wkv_a": init(n, d, rank + cfg.qk_rope_head_dim),
            "kv_norm": jnp.ones((n, rank), dtype),
            "wkv_b": init(n, rank, h * (cfg.qk_nope_head_dim
                                        + cfg.v_head_dim)),
            "wo": init(n, h * cfg.v_head_dim, d),
        }
        if cfg.head_gate:   # a gate lane a head
            latent["wg"] = init(n, d, h)
        if cfg.sparse_layers:  # the indexer's leaves, its query's from c_q
            hi, di = cfg.index_heads, cfg.index_head_dim
            params["sparse_latent"] = {
                **latent,
                "wq_index": init(n, cfg.q_lora_rank, hi * di),
                "wk_index": init(n, d, di),
                "index_norm_scale": jnp.ones((n, di), dtype),
                "index_norm_bias": jnp.zeros((n, di), dtype),
                "w_index": init(n, d, hi),
            }
        else:
            params["latent"] = latent
    elif cfg.sparse_layers:
        n, hi, di = cfg.sparse_layers, cfg.index_heads, cfg.index_head_dim
        params["sparse"] = {
            **attention(n),
            "wq_index": init(n, d, hi * di), "wk_index": init(n, d, di),
            "index_norm_scale": jnp.ones((n, di), dtype),
            "index_norm_bias": jnp.zeros((n, di), dtype),
            "w_index": init(n, d, hi),
        }
    else:
        params["attn"] = attention(la)
    fd = cfg.intermediate_size
    norms = {"ln2_scale": jnp.ones((d,), dtype),
             **({"post_scale": jnp.ones((d,), dtype)} if afmoe else {})}
    params["moe"] = _sub_ffns(cfg, init, norms) if cfg.sublayers > 1 else [{
        **norms,
        "w_gate": init(d, fd), "w_up": init(d, fd), "w_down": init(fd, d),
    } if layer < cfg.num_dense_layers else {
        **norms,
        "router": init(d, cfg.num_experts),
        "w_gate": init(eh, d, f), "w_up": init(eh, d, f),
        "w_down": init(eh, f, d),
        **({"shared_gate": init(d, fs), "shared_up": init(d, fs),
            "shared_down": init(fs, d)} if fs else {}),
        **({"router_bias": init(cfg.num_experts).astype(jnp.float32)}
           if cfg.score_func == "sigmoid" else {}),
    } for layer in range(lt)]
    if cfg.window_latent_layers:
        n, g = cfg.window_latent_layers, cfg.window_latent
        params["window_latent"] = {
            "ln1_scale": jnp.ones((n, d), dtype),
            "wq_a": init(n, d, g.q_lora_rank),
            "q_norm": jnp.ones((n, g.q_lora_rank), dtype),
            "wq_b": init(n, g.q_lora_rank, g.num_heads * g.head_dim),
            "wkv_a": init(n, d, g.kv_lora_rank + g.qk_rope_head_dim),
            "kv_norm": jnp.ones((n, g.kv_lora_rank), dtype),
            "wkv_b": init(n, g.kv_lora_rank, g.num_heads * (
                g.qk_nope_head_dim + g.v_head_dim)),
            "wo": init(n, g.num_heads * g.v_head_dim, d),
            **({"wg": init(n, d, g.num_heads)} if cfg.head_gate else {}),
        }
    elif cfg.window_layers:
        params["window"] = attention(cfg.window_layers)
    if not cfg.tie_word_embeddings:
        params["lm_head"] = init(d, cfg.vocab_size)
    return params


def _attn_out(cfg: ModelConfig, lp: dict, x, ctx):
    """The attend's output ctx (..., H*hd) of the layer input x (..., D) ->
    the sublayer's: gated where the layer has a gate, through ``W_o``, normed
    where it has a post-norm."""
    return post_norm(cfg, lp, gated(lp, x, ctx) @ lp["wo"])


def _feed_forward(cfg: ModelConfig, mp: dict, u, active=None):
    """One layer's feed-forward on u (T, D) normalised -> (out (T, D), counts
    (Eh,)): the routed expert layer, or, where the entry holds no router (a
    leading dense layer), a SwiGLU of ``intermediate_size`` under the scope
    ``mlp`` and counts None; normed after where the entry holds
    ``post_scale``, inside the sublayer's own scope (PERF.md §6 "PR 32")."""
    if "router" not in mp:
        with jax.named_scope("mlp"):
            return post_norm(cfg, mp, mlp(cfg, mp, u)), None
    out, counts = moe_layer(cfg, mp, u, active)
    with jax.named_scope("moe.experts"):
        return post_norm(cfg, mp, out), counts


def _shortcut(cfg: ModelConfig, mp: dict, h, g, term, counts=None,
              active=None):
    """What follows :func:`_ffn` in the walk: h (..., D) the stream it read,
    g what it returned, ``term`` what the walk carries -> (h, term, counts).
    In a stack whose layers hold sublayers (``cfg.sublayers`` 2) the entry of
    a layer's FIRST sublayer also holds the layer's routed experts, under
    ``shortcut``: they read the normalised input the dense SwiGLU read (h's,
    not g's), and their result does not join here but is handed back as
    ``term``, which the walk carries past the next attention sublayer and
    its feed-forward; an entry without ``shortcut`` adds the ``term`` it is
    handed, at the layer's end. Every other family's entries hold none and
    are handed none: g goes on as it is."""
    if "shortcut" in mp:
        u = _rms(cfg, h, mp["ln2_scale"])   # _ffn's own u: one value compiled
        term, counts = moe_layer(cfg, mp["shortcut"],
                                 u.reshape(-1, u.shape[-1]), active)
        return g, term.reshape(h.shape), counts
    if term is not None:
        g = g + cfg.residual_multiplier * term
    return g, None, counts


def _sub_ffns(cfg: ModelConfig, init, norms: dict) -> list:
    """:func:`init_params_hybrid`'s ``moe`` list for a stack whose layers
    hold sublayers: an entry a SUBLAYER, each a dense SwiGLU of
    ``intermediate_size``; a layer's first also the routed layer, under
    ``shortcut`` (a router ``cfg.router_width`` wide, a float32
    ``router_bias`` drawn nonzero, the held experts)."""
    d, fd = cfg.hidden_size, cfg.intermediate_size
    eh, f = cfg.local_experts, cfg.expert_width

    def entry(first: bool) -> dict:
        dense = {**norms, "w_gate": init(d, fd), "w_up": init(d, fd),
                 "w_down": init(fd, d)}
        if not first:
            return dense
        return {**dense, "shortcut": {
            "router": init(d, cfg.router_width),
            "router_bias": init(cfg.router_width).astype(jnp.float32),
            "w_gate": init(eh, d, f), "w_up": init(eh, d, f),
            "w_down": init(eh, f, d)}}

    return [entry(i % cfg.sublayers == 0)
            for i in range(len(cfg.layer_types))]
