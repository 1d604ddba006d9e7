"""Page-table KV cache for continuous batching.

The monolithic :class:`~edgellm_tpu.models.transformer.KVCache` gives every
request a private ``(B, capacity)`` buffer sized for the worst case, so a
mixed-length request stream either pads every cache to the longest stream or
recompiles per shape — ROADMAP item 1's gap between "a compiled generate()"
and "a service". This module replaces the monolith with the paged layout of
*Ragged Paged Attention* (PAPERS.md): one shared pool of fixed-size pages,

    kv: (L, num_pages, page_size, 2 * KV * hd)

(a position's K lanes, then its V lanes, in ONE row of one leaf: a page is
one contiguous piece of HBM and the decode read fetches it with one DMA,
PERF.md §6 "PR 45") and a small host-side allocator that maps each stream (a
*slot*) to an ordered list of pages. Logical position ``p`` of slot ``i``
lives at ``page_table[i, p // page_size]`` offset ``p % page_size``. The page
table and per-slot lengths ride through the jitted step as traced int32
arrays, so ONE executable serves every admit/evict/fill configuration of a
given pool geometry — the continuous-batching scheduler
(``serve/batching.py``) admits and evicts mid-flight without a single retrace.

Conventions that keep the paged step bit-identical to the contiguous one:

- page 0 is the TRASH page: never allocated, written by inactive slots (their
  page-table rows are all zero). Its contents are garbage but always finite
  (inactive rows run real token-0 math), so masked attention positions
  contribute exactly 0 to every softmax.
- pages store POST-ROTARY keys at ``num_kv_heads`` width, the same values the
  contiguous cache stores; gathering a slot's pages in order reproduces that
  slot's contiguous cache prefix byte-for-byte.
- the per-slot RoPE row, attention mask, and sampling fold_in sequence match
  ``decode_step``/``generate`` exactly, and attention softmax is invariant to
  the amount of masked padding — so a slot's tokens are bit-identical to
  running it alone (``tests/test_batching.py`` asserts this, and the
  ``batching.decode-step-identity`` graphlint contract re-checks it on every
  lint run).

Donation: the jitted step and adopt/defrag helpers donate the pool buffers,
so the (L, num_pages, page_size) arrays update in place — the
``paged.decode_step`` graph contract asserts the aliasing survives lowering.

Stored shape and addressing — two halves of ONE mechanism (PERF.md §6
"PR 29"; tests/test_chip_compile.py holds the chip's compiler to it):

- a token's K (or V) for ALL its KV heads is one minor vector of
  ``KV * lanes`` (lanes = hd, or the packed code width of a quantized tier;
  on the fp tier the V vector follows the K vector in the same row),
  so a page of 16 bf16 rows is whole (8,128)(2,1) tiles, nothing is padded
  and the chip keeps the array row-major. With a ``(KV, hd)`` tail of
  (2, 64) the runtime stored the pool PAGES-minor, where nothing can scatter
  a row or gather a page: every layer of the step relaid its slice out and
  back, and the adopt relaid the whole pool (113 of 181 ms of device time).
- every device-side write and read goes through one flat index computed
  from (layer, page, row) over the pool viewed ``(L*P*ps, KV*lanes)`` (rows)
  or ``(L*P, ps, KV*lanes)`` (pages); nothing slices a leading layer axis,
  and the step's layer scan CARRIES the pool instead of stacking it.

Two page groups (PERF.md §6 "PR 30"): a stack with sliding-window layers
beside full ones keeps TWO pools of this shape and two tables a slot in one
:class:`PagedKVCache`. The full layers' group is the one described above and
grows with the stream. A window layer never needs more than its window, so
its group gives each slot a RING of ``cfg.window_pages(page_size)`` pages in
a pool of its own: position ``p`` lives in ring entry ``(p // page_size) %
window_pages``, the row a step writes overwrites one that has left the
window, and nothing is taken or freed while the stream grows. Both groups go
through the same :func:`write_rows` (``ring=True``), :func:`_gather_pages`
and :func:`attend_rows`; the ring's attend masks a row by the ABSOLUTE
position it holds (:func:`ring_positions`, :func:`window_valid`), and keys are
rotated before they are stored, so ring order does not matter to the softmax.

A latent row (PERF.md §6 "PR 32"): a stack of latent-attention layers
(``models/mla.py``) caches ONE row a position a layer for all its heads, ``[c
| k_rope]`` zero-padded to whole lane tiles (``cfg.kv_row_lanes``: 320 ->
384), in a pool of ONE leaf (:class:`LatentPool`) in the same page group, by
the same table and allocator as full layers' K/V: the surgery below runs over
the one leaf, :func:`write_rows` and :func:`_gather_pages` address it as they
do a K/V pool's, and :func:`attend_latent` / :func:`attend_latent_pages` ("PR
35") stand beside :func:`attend_rows` / :func:`attend_pages`.

Neither half helps alone. The lane-dense row with ``.at[:, dest]`` still
costs two whole-pool copies a leaf (the compiler moves L under the row
axis), and on the staged pool, whose (2, 128) tail was already compact and
whose adopt was in place, it would ADD two stage-pool copies a leaf; the flat
index cannot help a pool that lives pages-minor. Callers never see the
merge: adopts, gathers, checkpoints and migration hand over (S, KV, hd) rows.
"""
from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import Iterator, NamedTuple, Optional

import numpy as np
import jax
import jax.numpy as jnp

from ..lint import graph_contract
from . import flash_attention, mla
from .configs import LANE_TILE, ModelConfig
from .flash_attention import dequantize_kv_rows, quantize_kv_rows
from .transformer import (_cast_params, _layernorm, _rmsnorm, _rotate_half,
                          embed, mlp, precompute_rope, unembed)

#: slot id a page belongs to when it is on the free list
FREE = -1

#: owner sentinel for a page referenced by more than one holder (several
#: slots, or a slot plus the prefix index) — no single slot may write it
SHARED = -2


@dataclass(frozen=True)
class PrefixCacheConfig:
    """Knobs for prefix sharing over the paged pool.

    min_shared_block: minimum matched prefix length (tokens) before an admit
        takes the shared path — below it the index is consulted but the
        request prefills privately (tiny matches are not worth the COW fork
        their first decode write costs).
    max_index_pages: cap on the number of index NODES (each node pins one
        page); 0 = uncapped. At the cap, registration evicts LRU leaves
        first and gives up if every leaf is still live in some slot.
    """

    enabled: bool = True
    min_shared_block: int = 1
    max_index_pages: int = 0

    def __post_init__(self):
        if self.min_shared_block < 1:
            raise ValueError(f"min_shared_block must be >= 1, got "
                             f"{self.min_shared_block}")
        if self.max_index_pages < 0:
            raise ValueError(f"max_index_pages must be >= 0 (0 = uncapped), "
                             f"got {self.max_index_pages}")


class _PrefixNode:
    """One page's worth of a registered prompt prefix.

    A node maps one token-id block to the page holding its post-rotary K/V,
    valid only under this node's PATH (positions are absolute from 0, so
    the same block under a different parent chain is a different node).
    ``full`` nodes cover exactly ``page_size`` tokens and may have children;
    ``partial`` nodes cover the tail of a registered prompt (< page_size
    tokens) and are always leaves — a partial page cannot be extended
    in-place without invalidating sharers, which is exactly what
    :meth:`PagedKVCache.fork_page` (COW) exists to avoid.
    """

    __slots__ = ("tokens", "page", "full", "parent", "children", "partials",
                 "stamp")

    def __init__(self, tokens: tuple, page: int, full: bool,
                 parent: Optional["_PrefixNode"], stamp: int):
        self.tokens = tokens
        self.page = page
        self.full = full
        self.parent = parent
        self.children: dict[tuple, _PrefixNode] = {}
        self.partials: list[_PrefixNode] = []
        self.stamp = stamp

    @property
    def is_leaf(self) -> bool:
        return not self.children and not self.partials


class PrefixIndex:
    """Radix index over page-granular token blocks.

    Keyed by the token-id block itself (a python tuple — its hash IS the
    token-block hash; collisions are impossible by construction, unlike a
    rolling digest). Depth j in the trie is page j of a prompt: walking
    full-block children from the root matches ever-longer page-aligned
    prefixes, and each matched node names a pool page that already holds
    that block's K/V. LRU stamps order eviction; reclaiming always drops
    leaves first so interior nodes never strand unreachable holds.
    """

    def __init__(self, page_size: int):
        self.page_size = page_size
        self.root = _PrefixNode((), 0, True, None, 0)
        self._clock = 0
        self._count = 0

    # -- bookkeeping -------------------------------------------------------

    @property
    def num_nodes(self) -> int:
        return self._count

    def _tick(self) -> int:
        self._clock += 1
        return self._clock

    def touch(self, node: _PrefixNode) -> None:
        self._tick()
        # refresh the whole path: evicting an ancestor of a hot leaf would
        # orphan it, so LRU order must be path-monotone (parent >= child)
        while node is not None and node is not self.root:
            node.stamp = self._clock
            node = node.parent

    def iter_nodes(self) -> Iterator[_PrefixNode]:
        """Every node except the root, preorder (parents first)."""
        stack = [self.root]
        while stack:
            node = stack.pop()
            if node is not self.root:
                yield node
            stack.extend(node.partials)
            stack.extend(node.children.values())

    def leaves(self) -> list[_PrefixNode]:
        return [n for n in self.iter_nodes() if n.is_leaf]

    # -- match / insert / remove ------------------------------------------

    def match(self, tokens) -> list[tuple[_PrefixNode, int]]:
        """Longest page-aligned match of ``tokens`` against the index.

        Returns [(node, claimed_tokens), ...] along the match path: full
        interior blocks claim ``page_size`` tokens each; one final node may
        claim fewer — the longest-common-prefix row count of a partial leaf
        (or of a full block the request diverges inside). Claimed rows of
        the final page are valid for THIS request; rows past the claim are
        the donor's K/V, which per-slot length masking never reads."""
        ps = self.page_size
        out: list[tuple[_PrefixNode, int]] = []
        node = self.root
        j = 0
        while (j + 1) * ps <= len(tokens):
            key = tuple(int(t) for t in tokens[j * ps:(j + 1) * ps])
            child = node.children.get(key)
            if child is None:
                break
            out.append((child, ps))
            node = child
            j += 1
        rest = [int(t) for t in tokens[j * ps:]]
        best, best_m = None, 0
        for cand in list(node.partials) + list(node.children.values()):
            m = 0
            for a, b in zip(cand.tokens, rest):
                if a != b:
                    break
                m += 1
            if m > best_m:
                best, best_m = cand, m
        if best is not None and best_m > 0:
            out.append((best, best_m))
        return out

    def insert_full(self, parent: _PrefixNode, key: tuple,
                    page: int) -> _PrefixNode:
        node = _PrefixNode(key, page, True, parent, self._tick())
        parent.children[key] = node
        self._count += 1
        return node

    def insert_partial(self, parent: _PrefixNode, tokens: tuple,
                       page: int) -> _PrefixNode:
        node = _PrefixNode(tokens, page, False, parent, self._tick())
        parent.partials.append(node)
        self._count += 1
        return node

    def remove(self, node: _PrefixNode) -> None:
        """Detach a LEAF node (interior nodes must shed children first)."""
        assert node.is_leaf, "only leaves are removable"
        parent = node.parent
        if node.full:
            del parent.children[node.tokens]
        else:
            parent.partials.remove(node)
        node.parent = None
        self._count -= 1

    # -- serialization (checkpoint round-trip) ----------------------------

    def to_array(self) -> np.ndarray:
        """Flatten to one int64 array: per node (preorder)
        ``[depth, full, page, stamp, ntok, tok...]`` — the ndarray-friendly
        form :class:`~edgellm_tpu.serve.recovery.DecodeCheckpoint` stores."""
        rows: list[int] = []

        def walk(node: _PrefixNode, depth: int) -> None:
            for child in list(node.children.values()) + node.partials:
                rows.extend([depth, int(child.full), child.page, child.stamp,
                             len(child.tokens)])
                rows.extend(int(t) for t in child.tokens)
                walk(child, depth + 1)

        walk(self.root, 0)
        return np.asarray(rows, np.int64)

    def load_array(self, flat: np.ndarray) -> None:
        """Rebuild from :meth:`to_array` output (clears current contents)."""
        self.root = _PrefixNode((), 0, True, None, 0)
        self._count = 0
        flat = np.asarray(flat, np.int64)
        path = [self.root]  # path[d] = parent at depth d
        i = 0
        while i < flat.size:
            depth, full, page, stamp, ntok = (int(x) for x in flat[i:i + 5])
            tokens = tuple(int(t) for t in flat[i + 5:i + 5 + ntok])
            i += 5 + ntok
            parent = path[depth]
            if full:
                node = self.insert_full(parent, tokens, page)
            else:
                node = self.insert_partial(parent, tokens, page)
            node.stamp = stamp
            del path[depth + 1:]
            path.append(node)
        self._clock = max((n.stamp for n in self.iter_nodes()), default=0)


class OutOfPages(RuntimeError):
    """The pool has no free page for a slot that must grow — the scheduler's
    signal to evict (or refuse to admit) a stream."""


class OutOfSlots(RuntimeError):
    """Every slot of the compiled step shape is occupied."""


class KVTierMismatchError(ValueError):
    """A KV payload at one ``kv_codec`` tier was offered to a pool built at
    another. Every adoption surface — packed adopts, checkpoint restore,
    page migration — raises THIS type (never a transcode): silently
    requantizing or inflating would change page bytes under the bit-exact
    round-trip promise. ``offered``/``pool`` carry both tier names so
    callers can rebuild at the right tier."""

    def __init__(self, *, offered: str, pool: str, where: str,
                 detail: str = ""):
        self.offered = offered
        self.pool = pool
        self.where = where
        super().__init__(
            f"KV tier mismatch in {where}: payload is {offered!r}, pool is "
            f"{pool!r}; rebuild the pool at kv_codec={offered!r} "
            f"(at-rest transcoding is refused)"
            + (f" — {detail}" if detail else ""))


class PagePool(NamedTuple):
    """Device-side page pool: post-rotary K/V at ``num_kv_heads`` width, ONE
    leaf.

    kv: (..., num_pages, page_size, 2 * KV * hd): a position's row is its K
    for all its KV heads as one minor vector (lanes [0, W), W = KV * hd, head
    j in lanes [j*hd, (j+1)*hd)), then its V the same way (lanes [W, 2W)).
    So a page is ``2 * page_size * W`` contiguous items of whole lane tiles,
    which the chip stores row-major (module docstring: with a (KV, hd) tail
    it lived pages-minor and was copied around every write and read), a
    decode step writes one row a slot a layer, and the page walk fetches a
    page, keys and values, with one DMA (as two leaves it was two, and the
    walk is bound by how fast they are issued: PERF.md §6 "PR 45"). The
    leading axes are (L,) on one chip and (n_stages, stage_size) in the
    split runtime; the LAST of them is the layer axis that every flat index
    folds in (:func:`write_rows`, :func:`read_span`, :func:`adopt_at`),
    never slices. Page 0 is the reserved trash page (see module docstring).
    Callers never see the join: adopts, gathers, checkpoints and migration
    hand over K and V (:func:`join_kv`, :func:`split_kv`)."""

    kv: jnp.ndarray

    @property
    def num_pages(self) -> int:
        return self.kv.shape[-3]

    @property
    def page_size(self) -> int:
        return self.kv.shape[-2]

    @property
    def k_lanes(self) -> int:
        """W = KV * hd: where a row's V lanes start."""
        return self.kv.shape[-1] // 2


def join_kv(k, v):
    """K rows (..., W) and V rows (..., W) as a :class:`PagePool` stores
    them: (..., 2 W), K lanes then V lanes."""
    return jnp.concatenate([k, v], axis=-1)


def split_kv(rows):
    """:func:`join_kv` undone: (..., 2 W) -> (K (..., W), V (..., W))."""
    w = rows.shape[-1] // 2
    return rows[..., :w], rows[..., w:]


class LatentPool(NamedTuple):
    """Device-side page pool of a stack of latent-attention layers: ONE leaf.

    rows: (L, num_pages, page_size, cfg.kv_row_lanes): a position's
    normalised latent ``c``, then its post-rotary ``k_rope`` (shared by all
    heads), then zeros up to whole 128-lane tiles. Pages, table, trash page
    and flat index are :class:`PagePool`'s."""

    rows: jnp.ndarray

    @property
    def num_pages(self) -> int:
        return self.rows.shape[-3]

    @property
    def page_size(self) -> int:
        return self.rows.shape[-2]


class IndexedPagePool(NamedTuple):
    """Device-side page pool of a stack of sparse-attention layers
    (``models/sparse_attn.py``): TWO leaves that share one page table.

    kv: a :class:`PagePool`'s leaf, a position's K then V lanes; ik: (...,
    num_pages, page_size, cfg.index_row_lanes), the position's INDEX KEY (the
    one key the layer's indexer scores it by: ``index_head_dim`` lanes, then
    zeros up to whole 128-lane tiles). Row r of page p of layer l is one
    position in both, so pages, table, trash page, flat index and every piece
    of surgery below (written once over a pool's leaves) are
    :class:`PagePool`'s: an adopt, a gather, a fork or a defrag moves a
    position's K/V row and its index key together. A decode step reads each
    leaf by a walk of its own where it lies (:func:`attend_pages` the K/V
    rows, ``sparse_attn.index_scores_paged`` the index keys), and the index
    keys' page is the smaller: the one the length of the pool's runs is read
    off (:func:`page_leaf_bytes`)."""

    kv: jnp.ndarray
    ik: jnp.ndarray

    @property
    def num_pages(self) -> int:
        return self.kv.shape[-3]

    @property
    def page_size(self) -> int:
        return self.kv.shape[-2]

    @property
    def k_lanes(self) -> int:
        return self.kv.shape[-1] // 2


class IndexedLatentPool(NamedTuple):
    """Device-side page pool of a stack of sparse LATENT layers
    (``models/sparse_mla.py``): a :class:`LatentPool`'s leaf and an
    :class:`IndexedPagePool`'s second one under one page table.

    rows: (L, num_pages, page_size, cfg.kv_row_lanes), a position's ``[c |
    k_rope | 0...]``; ik: (L, num_pages, page_size, cfg.index_row_lanes), its
    index key. Row r of page p of layer l is one position in both, and the
    surgery written over a pool's leaves moves the two together. The walks
    are the two other pools': the latent rows' (key and value both, a block
    of twice a K/V walk's pages) and the index keys', whose page is the
    smaller and sets the pool's runs."""

    rows: jnp.ndarray
    ik: jnp.ndarray

    @property
    def num_pages(self) -> int:
        return self.rows.shape[-3]

    @property
    def page_size(self) -> int:
        return self.rows.shape[-2]


#: the pools whose first leaf is a latent row / that hold index keys
LATENT_POOLS = (LatentPool, IndexedLatentPool)
INDEXED_POOLS = (IndexedPagePool, IndexedLatentPool)
#: every pool of the fp tier (rows in the pool's own dtype, no scales)
FP_POOLS = (PagePool, IndexedPagePool, *LATENT_POOLS)


def init_pool(cfg: ModelConfig, num_pages: int, page_size: int,
              dtype=jnp.float32, layers: Optional[int] = None,
              lanes: int = 0):
    """An all-zero pool; ``num_pages`` INCLUDES the reserved trash page 0,
    so ``num_pages - 1`` pages are allocatable. ``layers``: how many layers
    it serves where that is not ``cfg.kv_layers`` (the window group's). A
    :class:`PagePool`, a :class:`LatentPool` for latent layers, an
    :class:`IndexedPagePool` for sparse-attention layers, an
    :class:`IndexedLatentPool` for layers that are both. ``lanes`` (> 0): a
    group whose rows are latent at a width of their own, whatever the main
    group holds (the window group of a stack whose window layers cache
    latent rows, ``cfg.window_row_lanes``): a one-leaf :class:`LatentPool` of
    rows that wide."""
    if num_pages < 2:
        raise ValueError(f"num_pages must be >= 2 (page 0 is reserved), "
                         f"got {num_pages}")
    if page_size < 1:
        raise ValueError(f"page_size must be >= 1, got {page_size}")
    rows = (cfg.kv_layers if layers is None else layers, num_pages,
            page_size)
    if lanes:
        return LatentPool(jnp.zeros(rows + (lanes,), dtype))
    if cfg.latent_layers and cfg.sparse_layers:
        return IndexedLatentPool(
            jnp.zeros(rows + (cfg.kv_row_lanes,), dtype),
            jnp.zeros(rows + (cfg.index_row_lanes,), dtype))
    if cfg.latent_layers:
        return LatentPool(jnp.zeros(rows + (cfg.kv_row_lanes,), dtype))
    kv = jnp.zeros(rows + (2 * cfg.kv_row_lanes,), dtype)
    if cfg.sparse_layers:
        return IndexedPagePool(
            kv, jnp.zeros(rows + (cfg.index_row_lanes,), dtype))
    return PagePool(kv)


# ---------------------------------------------------------------------------
# KV-at-rest compression: quantized page layouts. ROADMAP item 3 — the same
# per-channel shapes the wire codecs compress, applied to the pool so a fixed
# HBM budget holds 2-4x more live tokens. The "fp" tier IS the plain PagePool
# path above, untouched, so disabled builds trace the pre-quantization graph.
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class KVPageCodec:
    """One KV-at-rest storage tier. ``bits=0`` marks the uncompressed fp
    tier (codes are the pool dtype itself, no scales). Quantized tiers store
    ``code_lanes(hd)`` packed code bytes plus ONE fp32 absmax scale per
    (token row, KV head) — per-row, not per-page, because decode appends a
    single row via scatter and must not requantize its neighbours."""

    name: str
    bits: int
    code_dtype: object  # jnp dtype of the code arrays ("fp": pool dtype)

    @property
    def quantized(self) -> bool:
        return self.bits > 0

    def code_lanes(self, head_dim: int) -> int:
        """Last-axis width of a code row (int4 packs two lanes per byte)."""
        if self.bits == 4:
            if head_dim % 2:
                raise ValueError(f"int4 packing needs an even head_dim, "
                                 f"got {head_dim}")
            return head_dim // 2
        return head_dim

    def row_bytes(self, head_dim: int, dtype=jnp.float32) -> int:
        """HBM bytes per (token row, KV head) for K or V: codes + scale."""
        if not self.quantized:
            return head_dim * jnp.dtype(dtype).itemsize
        return self.code_lanes(head_dim) + 4  # packed codes + fp32 scale


KV_PAGE_CODECS = {
    "fp": KVPageCodec("fp", 0, None),
    "int8_per_channel": KVPageCodec("int8_per_channel", 8, jnp.int8),
    "int4_per_channel": KVPageCodec("int4_per_channel", 4, jnp.uint8),
}


def resolve_kv_codec(name: str) -> KVPageCodec:
    """Registry lookup that REFUSES unknown tier names (the run.py params
    validator and every constructor route through this)."""
    try:
        return KV_PAGE_CODECS[name]
    except KeyError:
        raise ValueError(f"unknown kv_codec {name!r}; available tiers: "
                         f"{sorted(KV_PAGE_CODECS)}") from None


class QuantPagePool(NamedTuple):
    """Quantized device pool: packed int codes + per-row fp32 scales.

    k, v: (..., num_pages, page_size, KV * hdc) codes — hdc = hd (int8) or
    hd/2 (packed int4, lane i paired with lane i + hd/2 WITHIN a head, the
    wire codecs' contiguous-half pairing); the KV heads' codes merge into
    one minor vector exactly as PagePool's rows do, so there is one row
    layout. k_scale, v_scale: (..., num_pages, page_size, KV) fp32 absmax
    scales, which also tell a quantized pool its KV. The leading axes, the
    page axis and the token axis match PagePool, so the page-table/flat-index
    math is tier-agnostic."""

    k: jnp.ndarray
    v: jnp.ndarray
    k_scale: jnp.ndarray
    v_scale: jnp.ndarray

    @property
    def num_pages(self) -> int:
        return self.k.shape[-3]

    @property
    def page_size(self) -> int:
        return self.k.shape[-2]


def init_quant_pool(cfg: ModelConfig, num_pages: int, page_size: int,
                    kv_codec: str) -> QuantPagePool:
    """All-zero quantized pool (same trash-page-0 convention as
    :func:`init_pool`; zero codes with zero scales dequantize to zeros)."""
    codec = resolve_kv_codec(kv_codec)
    if not codec.quantized:
        raise ValueError("init_quant_pool is for quantized tiers; "
                         "use init_pool for fp")
    if num_pages < 2:
        raise ValueError(f"num_pages must be >= 2 (page 0 is reserved), "
                         f"got {num_pages}")
    if page_size < 1:
        raise ValueError(f"page_size must be >= 1, got {page_size}")
    hdc = codec.code_lanes(cfg.head_dim)
    sshape = (cfg.num_layers, num_pages, page_size, cfg.num_kv_heads)
    cshape = sshape[:-1] + (cfg.num_kv_heads * hdc,)
    return QuantPagePool(jnp.zeros(cshape, codec.code_dtype),
                         jnp.zeros(cshape, codec.code_dtype),
                         jnp.zeros(sshape, jnp.float32),
                         jnp.zeros(sshape, jnp.float32))


def pool_tier(pool) -> str:
    """The ``kv_codec`` name of a pool (whole, staged or one layer's): the
    one place a tier is read from, its type and the width of its codes."""
    if isinstance(pool, FP_POOLS):
        return "fp"
    return next(c.name for c in KV_PAGE_CODECS.values()
                if c.quantized and pool.k.dtype == c.code_dtype)


def _k_lanes(pool) -> int:
    """Lanes of a K row (as many of a V row) of a K/V pool at its tier; of a
    latent pool's whole row."""
    return (pool.k_lanes if isinstance(pool, (PagePool, IndexedPagePool))
            else pool[0].shape[-1])


def kv_page_bytes(cfg: ModelConfig, page_size: int, kv_codec: str = "fp",
                  dtype=jnp.float32) -> int:
    """HBM bytes ONE page costs across all layers (K + V, codes + scales; a
    sparse-attention stack's index keys, as stored, with them) — the honest
    per-tier footprint the capacity accounting below divides by."""
    codec = resolve_kv_codec(kv_codec)
    if cfg.sparse_layers:
        if codec.quantized:
            from .hybrid import refuse_index_keys

            refuse_index_keys(cfg, f"the quantized KV tier kv_codec="
                                   f"{kv_codec!r}")
        # (a latent row is key and value both: one row, not K's then V's)
        return (cfg.kv_layers * page_size
                * ((1 if cfg.latent_layers else 2) * cfg.kv_row_lanes
                   + cfg.index_row_lanes)
                * jnp.dtype(dtype).itemsize)
    if cfg.latent_layers:
        if codec.quantized:
            from .hybrid import refuse_latent_rows

            refuse_latent_rows(cfg, f"the quantized KV tier kv_codec="
                                    f"{kv_codec!r}")
        return (cfg.kv_layers * page_size * cfg.kv_row_lanes
                * jnp.dtype(dtype).itemsize)
    return (2 * cfg.num_layers * page_size * cfg.num_kv_heads
            * codec.row_bytes(cfg.head_dim, dtype))


def num_pages_for_bytes(cfg: ModelConfig, pool_bytes: int, page_size: int,
                        kv_codec: str = "fp", dtype=jnp.float32) -> int:
    """Pages (trash page included) a fixed HBM budget buys at a tier — the
    pages-per-token admission math is unchanged, the pool just has MORE
    pages, which is exactly how quantization multiplies concurrency."""
    pages = int(pool_bytes) // kv_page_bytes(cfg, page_size, kv_codec, dtype)
    if pages < 2:
        raise ValueError(
            f"pool budget {pool_bytes} bytes buys {pages} {kv_codec} "
            f"page(s); need >= 2 (page 0 is reserved)")
    return pages


def page_leaf_bytes(cfg: ModelConfig, page_size: int, kv_codec: str = "fp",
                    dtype=jnp.float32) -> int:
    """HBM bytes of the SMALLEST page of one layer that a walk of the pool
    fetches (a position's K and V rows, or its latent row, ``page_size``
    times): what the length of the pool's runs is read off
    (``flash_attention.walk_run_pages``). Of an :class:`IndexedPagePool`
    the smaller of its two leaves' pages, each of which a walk of its own
    fetches: at the published widths the index keys' (4 KB of 16 bf16 rows,
    so eight go together) beside a K/V page that is a fetch by itself."""
    layers = cfg.kv_layers if cfg.latent_layers else cfg.num_layers
    page = kv_page_bytes(cfg, page_size, kv_codec, dtype) // layers
    if cfg.sparse_layers:
        index = page_size * cfg.index_row_lanes * jnp.dtype(dtype).itemsize
        page = min(page - index, index)
    return page


# ---------------------------------------------------------------------------
# Pool surgery: adopt a contiguous prefix, gather one back, copy pages for a
# COW fork, permute them for defrag. Each is written once over the pool's
# leaves; ``lead`` counts the axes before the page axis (1 for a chip's
# (L, ...) pool, 2 for the split runtime's (n_stages, stage_size, ...)), so
# the staged pool takes the same code. The LAST leading axis is the layer
# axis and is never sliced: it folds into the row (or page) axis, and the
# index of a row of layer l is ``l * P * ps + row`` (:func:`_every_layer`).
# The axes before it (the staged pool's sharded stage axis) stay sliced.
# Page moves are BYTE moves — codes and scales ride the same copy or
# permutation untouched, so a forked page is byte-identical to its original
# and defrag never requantizes. Only adopt (fp rows in) and gather (fp rows
# out) touch the codec; the *_packed pair moves raw codes + scales for the
# bit-exact checkpoint/eviction path. Whatever writes donates the pool, so
# surgery is in place.
# ---------------------------------------------------------------------------


def _rows(arr, lead: int):
    """A pool leaf viewed as token rows, its layer, page and row axes merged:
    (..., L*P*ps, width). A bitcast where a page is whole tiles."""
    sh = arr.shape
    return arr.reshape(*sh[:lead - 1], -1, sh[-1])


def _pages(arr, lead: int):
    """A pool leaf viewed as pages, its layer and page axes merged:
    (..., L*P, ps, width)."""
    sh = arr.shape
    return arr.reshape(*sh[:lead - 1], -1, *sh[lead + 1:])


def _every_layer(arr, lead: int, idx, pages: bool = False):
    """Per-layer indices ``idx`` (n,) — flat token rows
    (:meth:`PagedKVCache._flat_indices`; a layer holds P*ps of them) or,
    with ``pages``, page ids (a layer holds P) — as indices into the folded
    axis of :func:`_rows` / :func:`_pages` for EVERY layer: (L*n,),
    layer-major."""
    stride = arr.shape[lead] * (1 if pages else arr.shape[lead + 1])
    layers = jnp.arange(arr.shape[lead - 1], dtype=jnp.int32) * stride
    return (layers[:, None] + idx[None, :].astype(jnp.int32)).reshape(-1)


def _at(lead: int, idx):
    return (slice(None),) * (lead - 1) + (idx,)


def _merge_heads(x):
    """(..., KV, lanes) rows as a caller hands them over -> the stored
    (..., KV*lanes) minor vector."""
    return x.reshape(*x.shape[:-2], -1)


def _split_heads(x, kv: int):
    """The stored (..., KV*lanes) minor vector -> (..., KV, lanes)."""
    return x.reshape(*x.shape[:-1], kv, -1)


@jax.named_scope("paged_kv.adopt")
def _set_rows(pool, rows, dest, lead: int, head: Optional[int] = None):
    """Scatter ``rows`` — one (..., L, S, width) array a pool leaf, already
    in the leaf's stored form — into the flat token positions ``dest`` (S,)
    of every layer, over the leaf viewed (..., L*P*ps, width), which the chip
    runs in place. (``.at[:, dest]`` over (L, P*ps, width) makes its compiler
    move L under the row axis and copy the whole pool out and back, twice a
    leaf.)

    ``head`` (static) says where ``dest`` meets its first page boundary:
    ``dest[head:]`` starts a page and runs on in position order, as
    :meth:`PagedKVCache._flat_indices` lays a slot out. The whole pages in it
    then go a PAGE a scatter slice at ``l*P + page`` — whole tiles, stored by
    bytes — and only the rows before ``head`` and after the last whole page
    go a row a slice: a lane-dense bf16 row shares its packed sublane with
    its neighbour, so a row scatter costs 76-86 ns a row on a v5e where a
    (2, 128) row that was a tile of its own cost 12 (PERF.md §6 "PR 29": a
    1024-row adopt 3.8 ms by rows, 0.3 by pages). ``None``: every row by
    itself, whatever ``dest`` holds."""
    n = dest.shape[0]
    ps = pool[0].shape[lead + 1]
    head = n if head is None else min(head, n)
    whole = (n - head) // ps * ps               # rows that fill whole pages

    def apart(x, axis):                          # rows scattered one by one
        return jnp.concatenate(
            [jax.lax.slice_in_dim(x, 0, head, axis=axis),
             jax.lax.slice_in_dim(x, head + whole, n, axis=axis)], axis=axis)

    out = []
    for a, r in zip(pool, rows):
        r = r.astype(a.dtype)
        if whole:
            at = _every_layer(a, lead, dest[head:head + whole:ps] // ps,
                              pages=True)
            body = r[..., head:head + whole, :].reshape(
                *a.shape[:lead - 1], -1, ps, a.shape[-1])
            a = _pages(a, lead).at[_at(lead, at)].set(body).reshape(a.shape)
        if whole < n:
            at = _every_layer(a, lead, apart(dest, 0))
            a = _rows(a, lead).at[_at(lead, at)].set(
                apart(r, r.ndim - 2).reshape(*a.shape[:lead - 1], -1,
                                            a.shape[-1])).reshape(a.shape)
        out.append(a)
    return type(pool)(*out)


def page_head(dest, page_size: int) -> int:
    """The ``head`` of :func:`_set_rows` for a HOST array of flat token
    indices in position order: how many rows lie before the first page
    boundary (0 for an adopt from position 0)."""
    return int(-int(dest[0]) % page_size) if len(dest) else 0


def adopt_at(pool, k_seq, v_seq, dest, lead: int, head: Optional[int] = None,
             index=None):
    """Put contiguous (..., L, S, KV, hd) fp K/V rows at the flat token
    indices ``dest`` (S,) of every layer: stored as they are on the fp tier
    (the KV heads merged into one minor vector, K's then V's joined into the
    row: one scatter), quantized on append on the others ('writes quantize
    on append', the at-rest contract). Row ``dest[i]`` of layer ``l`` is row
    ``l*P*ps + dest[i]`` of the leaf viewed (..., L*P*ps, KV*lanes); with
    two leading axes the sharded stage axis stays sliced and only
    ``stage_size`` folds into the index. S and ``head`` (:func:`_set_rows`)
    are static per call (one executable per adopted length). ``index``: the
    (..., L, S, index_row_lanes) index keys of the same positions as stored,
    which an :class:`IndexedPagePool` takes into its second leaf by the same
    scatter (and no other pool takes)."""
    tier = pool_tier(pool)
    if isinstance(pool, IndexedPagePool) != (index is not None):
        raise ValueError(
            f"a {type(pool).__name__} adopts K/V rows "
            f"{'WITH' if index is None else 'without'} index keys")
    if tier == "fp":
        return _set_rows(
            pool, (join_kv(_merge_heads(k_seq), _merge_heads(v_seq)),)
            + (() if index is None else (index,)), dest, lead, head)
    qk, sk = quantize_kv_rows(k_seq, tier)
    qv, sv = quantize_kv_rows(v_seq, tier)
    return _set_rows(pool, (_merge_heads(qk), _merge_heads(qv), sk, sv),
                     dest, lead, head)


@functools.partial(jax.jit, static_argnames=("head",), donate_argnums=(0,))
def _adopt_impl(pool, k_seq, v_seq, dest, head: Optional[int] = None,
                index=None):
    return adopt_at(pool, k_seq, v_seq, dest, 1, head, index)


@functools.partial(jax.jit, static_argnames=("head",), donate_argnums=(0,))
def _adopt_latent_impl(pool, rows, dest, head: Optional[int] = None,
                       index=None):
    """:func:`_adopt_impl` for a :class:`LatentPool`: (L, S, kv_row_lanes)
    rows, as stored, at the flat token indices ``dest``; with them, into an
    :class:`IndexedLatentPool`'s second leaf, the positions' index keys
    ``index`` (L, S, index_row_lanes)."""
    if isinstance(pool, IndexedLatentPool) != (index is not None):
        raise ValueError(
            f"a {type(pool).__name__} adopts latent rows "
            f"{'WITH' if index is None else 'without'} index keys")
    return _set_rows(pool, (rows,) + (() if index is None else (index,)),
                     dest, 1, head)


@functools.partial(jax.jit, static_argnames=("lead", "head"),
                   donate_argnums=(0,))
def _adopt_packed_impl(pool, k_codes, v_codes, k_scale, v_scale, dest,
                       lead: int = 1, head: Optional[int] = None):
    """Scatter already-packed (..., S, KV, hdc) code rows and their scales
    (a checkpoint's payload) — no requantize, so restore is bit-exact by
    construction."""
    return _set_rows(pool, (_merge_heads(k_codes), _merge_heads(v_codes),
                            k_scale, v_scale), dest, lead, head)


def _get_rows(pool, idx, lead: int):
    """The rows at flat token indices ``idx`` (span,) of every layer as
    stored, a (..., L, span, width) array a leaf: one gather a leaf at
    ``l*P*ps + idx``."""
    out = []
    for a in pool:
        at = _every_layer(a, lead, idx)
        out.append(_rows(a, lead)[_at(lead, at)].reshape(
            *a.shape[:lead], idx.shape[0], a.shape[-1]))
    return out


@functools.partial(jax.jit, static_argnames=("lead",))
def _gather_packed_impl(pool, idx, lead: int = 1):
    """A quantized pool's rows at ``idx`` as stored — codes (..., L, span,
    KV, hdc), scales (..., L, span, KV): the checkpoint/eviction form,
    geometry-independent AND codec-lossless. NOT donated: the pool stays
    live."""
    kc, vc, ks, vs = _get_rows(pool, idx, lead)
    kv = ks.shape[-1]
    return _split_heads(kc, kv), _split_heads(vc, kv), ks, vs


@functools.partial(jax.jit, static_argnames=("lead", "kv"))
def _gather_impl(pool, idx, lead: int = 1, *, kv: int):
    """The rows at ``idx`` back as contiguous (..., L, span, KV, hd) K and V:
    byte-identical to what was adopted on the fp tier, DEQUANTIZED to fp32 on
    the others (the suffix-prefill compute path, which needs fp rows; lossy
    by exactly the tier's quantization error). ``kv`` is what an fp pool's
    merged row cannot say. An :class:`IndexedPagePool` hands back a third
    array, the rows' index keys as stored (..., L, span, index_row_lanes)."""
    tier = pool_tier(pool)
    rows = _get_rows(pool, idx, lead)
    # the fp tier: one gather of the joined rows, then K | V on lanes
    k, v, *scales = split_kv(rows[0]) if tier == "fp" else rows
    k, v = _split_heads(k, kv), _split_heads(v, kv)
    if tier == "fp":
        return (k, v, *rows[1:])
    return (dequantize_kv_rows(k, scales[0], tier),
            dequantize_kv_rows(v, scales[1], tier))


@jax.jit
def _gather_latent_impl(pool, idx):
    """A latent pool's rows at ``idx`` as stored, an array a leaf: [(L, span,
    kv_row_lanes)], and an :class:`IndexedLatentPool`'s index keys (L, span,
    index_row_lanes) after."""
    return _get_rows(pool, idx, 1)


@functools.partial(jax.jit, static_argnames=("lead",), donate_argnums=(0,))
def _permute_impl(pool, src, lead: int = 1):
    """new_pool[l, p] = old_pool[l, src[p]] — the defrag move, one gather of
    whole pages a leaf at ``l*P + src`` over the leaf viewed (..., L*P, ps,
    width)."""
    return type(pool)(*(
        _pages(a, lead)[_at(lead, _every_layer(a, lead, src, pages=True))]
        .reshape(a.shape) for a in pool))


@functools.partial(jax.jit, static_argnames=("lead",), donate_argnums=(0,))
def _copy_pages_impl(pool, src, dst, lead: int = 1):
    """COW fork: duplicate whole pages ``src`` (n,) into pages ``dst`` (n,)
    of every layer. The forking slot then writes its private copy; every
    other holder keeps reading the original bytes."""
    out = []
    for a in pool:
        pages = _pages(a, lead)
        at_src = _at(lead, _every_layer(a, lead, src, pages=True))
        at_dst = _at(lead, _every_layer(a, lead, dst, pages=True))
        out.append(pages.at[at_dst].set(pages[at_src]).reshape(a.shape))
    return type(pool)(*out)


# The per-slot state store of a hybrid stack (models/hybrid.py): a dict of
# the leaves ``hybrid.state_shapes`` names for the stack's recurrent kinds,
# each (L_kind, max_slots, ...) float32: a Mamba-2 stack's ``conv`` (L_mamba,
# max_slots, d_conv-1, conv_dim) and ``ssm`` (L_mamba, max_slots, H, P, N), a
# short-convolution stack's ``conv`` (L_conv, max_slots, taps-1, D) alone. Row
# j of a leaf is layer j's state for each slot. A slot's state has a fixed
# size and is overwritten every step, so there is nothing to page: one row a
# slot.


def init_slot_state(cfg: ModelConfig, max_slots: int) -> dict:
    from .hybrid import state_shapes

    return {leaf: jnp.zeros(shape, jnp.float32)
            for leaf, shape in state_shapes(cfg, max_slots).items()}


@functools.partial(jax.jit, donate_argnums=(0,))
@jax.named_scope("state.adopt")
def _state_set_impl(state, rows, slot):
    """Overwrite one slot's rows of every leaf with (L_kind, ...) state: a
    prefill's, a resumed stream's, or zeros when the slot is allocated."""
    return {leaf: a.at[:, slot].set(rows[leaf].astype(a.dtype))
            for leaf, a in state.items()}


@jax.jit
def _state_get_impl(state, slot):
    return {leaf: a[:, slot] for leaf, a in state.items()}


class PagedKVCache:
    """Host-side allocator + device pool for up to ``max_slots`` concurrent
    streams of up to ``pages_per_slot * page_size`` tokens each.

    The device state is ``self.pool`` (swapped wholesale after each donated
    step/adopt/defrag); the host state is the page table, per-slot lengths,
    the free list, and per-page ownership. ``device_tables()`` materializes
    the traced int32 inputs of the compiled step. All mutating methods keep
    :meth:`check_invariants` true: no page owned twice, no page leaked, the
    trash page never allocated.

    Pages go out and come back in RUNS of ``run_pages`` adjacent pages (as
    many as make a fetch of 64 KB where a page is under 32 KB, else one:
    ``flash_attention.walk_run_pages``), each
    placed at a table-aligned position, so that the page walk can take a
    group of a slot's entries with one DMA: a prompt's pages are runs by
    construction, a slot that grows into a new group takes a whole free run
    and holds the rest AHEAD (free pages to everyone who asks: counted by
    :attr:`num_free_pages`, taken back before :class:`OutOfPages`), and a run
    whose pages have all come back is whole again. A page stays the unit of
    sharing, forking and eviction; a shared or forked page that breaks a run
    costs that group its run (and the groups after it in its block of the
    walk theirs), which the kernel sees in the table. Where
    a page is a fetch by itself a run is one page, and this is the LIFO
    stack of single pages it was before runs.
    """

    def __init__(self, cfg: ModelConfig, *, num_pages: int, page_size: int,
                 max_slots: int, pages_per_slot: int, dtype=jnp.float32,
                 materialize: bool = True,
                 prefix_cache: Optional[PrefixCacheConfig] = None,
                 kv_codec: str = "fp"):
        if max_slots < 1:
            raise ValueError(f"max_slots must be >= 1, got {max_slots}")
        if pages_per_slot < 1:
            raise ValueError(
                f"pages_per_slot must be >= 1, got {pages_per_slot}")
        self.cfg = cfg
        if cfg.is_hybrid:
            from .hybrid import refuse_beyond_kv_rows

            if prefix_cache is not None and prefix_cache.enabled:
                refuse_beyond_kv_rows(cfg, "prefix sharing (PrefixIndex)")
            if resolve_kv_codec(kv_codec).quantized:
                refuse_beyond_kv_rows(
                    cfg, f"the quantized KV tier kv_codec={kv_codec!r}")
            if not materialize:
                refuse_beyond_kv_rows(
                    cfg, "a bookkeeping-only PagedKVCache (the split "
                         "runtime's allocator)")
        # KV-at-rest tier. Every page bookkeeping path below (alloc, COW,
        # refcounts, radix index, defrag permutation) is codec-agnostic — a
        # page is a page; only the device-pool surgery dispatches on tier.
        self.kv_codec = resolve_kv_codec(kv_codec).name
        # materialize=False: bookkeeping-only mode — the page table, free
        # list, and ownership machinery without a local device pool. The
        # split runtime uses this: its pools live per-stage on the mesh
        # (SplitRuntime.init_paged_pool), only the allocator is shared.
        if not materialize:
            self.pool = None
        elif self.kv_codec == "fp":
            self.pool = init_pool(cfg, num_pages, page_size, dtype)
        else:
            self.pool = init_quant_pool(cfg, num_pages, page_size,
                                        self.kv_codec)
        # the second kind of state: one fixed-size row a slot for every
        # recurrent layer of a hybrid stack, the leaves its kinds keep,
        # managed with the slot (zeroed at alloc, written at adopt, gathered
        # at eviction, dead once freed)
        self.state: Optional[dict] = (
            init_slot_state(cfg, max_slots) if cfg.recurrent_state else None)
        # the second page group: the sliding-window layers keep, for each
        # slot, a RING of ``window_pages`` pages in a pool of their own —
        # position p in entry (p // page_size) % window_pages — so they
        # neither take nor free a page however long the stream grows. The
        # rings are provisioned in full: slot s holds pages [1 + s * wp,
        # 1 + (s + 1) * wp) of the window pool for as long as it is active
        # (page 0 is that pool's trash page: a free slot's table row is 0).
        self.window_pages = (cfg.window_pages(page_size)
                             if cfg.window_layers else 0)
        # (a PagePool of K/V rows, or a LatentPool where the window layers
        # cache latent rows: told apart by the row's width alone)
        self.window_pool = None
        self.window_table: Optional[np.ndarray] = None
        if self.window_pages:
            self.window_pool = init_pool(
                cfg, max_slots * self.window_pages + 1, page_size, dtype,
                layers=cfg.window_layers, lanes=cfg.window_row_lanes)
            self.window_table = np.zeros((max_slots, self.window_pages),
                                         np.int32)
        self.page_size = page_size
        self.num_pages = num_pages
        self.max_slots = max_slots
        self.pages_per_slot = pages_per_slot
        self.page_table = np.zeros((max_slots, pages_per_slot), np.int32)
        self.lengths = np.zeros((max_slots,), np.int32)
        self.active = np.zeros((max_slots,), bool)
        # pages are handed out and taken back in RUNS of ``run_pages``
        # adjacent pages (run c: pages 1 + c*G .. c*G + G; page 0 stays the
        # trash page), the unit the page walk fetches with one DMA; G is
        # read off a page's bytes (the smaller leaf's, of a pool of two) and
        # is 1 where a page is a fetch by itself
        self.run_pages = flash_attention.walk_run_pages(
            page_leaf_bytes(cfg, page_size, self.kv_codec, dtype),
            pages_per_slot)
        # the free pages of each run; LIFO stacks of the runs that are WHOLE
        # (low pages first out — deterministic layouts) and, in the order
        # they broke, of those that are not; the pages a slot holds AHEAD of
        # its last page, the rest of the run it grew into (next page last):
        # nobody's yet, and free to whoever asks (num_free_pages counts them)
        self._runs: list[list[int]] = []
        self._whole: list[int] = []
        self._broken: dict[int, None] = {}
        self._ahead: dict[int, list[int]] = {}
        self._reset_free(range(num_pages - 1, 0, -1))
        self._slot_pages: list[list[int]] = [[] for _ in range(max_slots)]
        # page -> exclusive slot, or SHARED (>1 holder / index-held), or FREE
        self._owner = np.full((num_pages,), FREE, np.int32)
        # per-page reference counts: one per slot-table entry + one per
        # prefix-index node; a page returns to the free list ONLY at 0
        self._refcount = np.zeros((num_pages,), np.int32)
        self._index_holds = np.zeros((num_pages,), np.int32)
        self.prefix_cfg = prefix_cache
        self.prefix: Optional[PrefixIndex] = (
            PrefixIndex(page_size)
            if prefix_cache is not None and prefix_cache.enabled else None)
        # host counters; read lock-free by report scrapes (GIL-atomic ints)
        self.prefix_counters = {"hits": 0, "misses": 0, "saved_tokens": 0,
                                "cow_forks": 0, "index_evictions": 0,
                                "reclaimed_pages": 0}
        # migration-handoff holds: slots pinned while their pages are in
        # flight to another pool. free_slot refuses a held slot and defrag
        # defers wholesale (see hold_slot), so a _flat_indices snapshot
        # taken under a hold stays valid for the whole transfer.
        self._slot_holds = np.zeros((max_slots,), np.int32)
        self.deferred_defrags = 0

    # -- geometry ----------------------------------------------------------

    @property
    def span(self) -> int:
        """Max positions one slot can hold — the compiled attention width."""
        return self.pages_per_slot * self.page_size

    @property
    def num_free_pages(self) -> int:
        """Pages an :meth:`ensure` can get without evicting anything: no
        slot's and not the index's, those held ahead for a slot included."""
        return self._n_free

    @property
    def token_capacity(self) -> int:
        """Allocatable token positions (the trash page excluded)."""
        return (self.num_pages - 1) * self.page_size

    @property
    def live_tokens(self) -> int:
        return int(self.lengths[self.active].sum())

    @property
    def unique_live_tokens(self) -> int:
        """Live tokens counting each physical page ONCE: per page, the max
        coverage over every slot referencing it. Equals :attr:`live_tokens`
        when nothing is shared; under prefix sharing it is the honest
        occupancy numerator (summing per-slot lengths over-counts aliased
        pages — the ``report()`` occupancy bug this property fixes)."""
        if not self.shared_pages:
            # every page is one holder's: the walk below would add up the
            # slots' lengths, a page at a time, in Python, on the batcher's
            # commit clock with the chip idle (12 ms a step at 192 slots)
            return self.live_tokens
        cover = np.zeros((self.num_pages,), np.int64)
        for s in range(self.max_slots):
            if not self.active[s]:
                continue
            n = int(self.lengths[s])
            for j, p in enumerate(self._slot_pages[s]):
                c = min(self.page_size, n - j * self.page_size)
                if c > 0:
                    cover[p] = max(cover[p], c)
        return int(cover.sum())

    @property
    def shared_pages(self) -> int:
        """Pages with more than one holder (slots and/or the index)."""
        return int(np.sum(self._refcount > 1))

    @property
    def index_pages(self) -> int:
        """Pages pinned by at least one prefix-index node."""
        return int(np.sum(self._index_holds > 0))

    @property
    def reclaimable_index_pages(self) -> int:
        """Pages held ONLY by the index — :meth:`ensure` frees these
        LRU-first under pressure, so admission feasibility may count them
        as available."""
        return int(np.sum((self._refcount == 1) & (self._index_holds == 1)))

    def pages_for(self, n_tokens: int) -> int:
        return -(-n_tokens // self.page_size)

    # -- slot lifecycle ----------------------------------------------------

    def alloc_slot(self) -> int:
        """Claim the lowest free slot (deterministic admit order)."""
        for s in range(self.max_slots):
            if not self.active[s]:
                self.active[s] = True
                self.lengths[s] = 0
                if self.state is not None:
                    # a reused slot starts from zero, not from its last
                    # tenant's state
                    self.adopt_state(s, *(0.0 for _ in self.state))
                if self.window_table is not None:
                    self.window_table[s] = self._ring_of(s)
                return s
        raise OutOfSlots(f"all {self.max_slots} slots active")

    def ensure(self, slot: int, new_length: int) -> None:
        """Grow ``slot``'s page list to cover ``new_length`` positions,
        allocating pages from the free list: whole runs at the table's
        group boundaries, the rest of the last one held ahead for the slot's
        next growths. Under pool pressure, pages held
        ONLY by the prefix index (refcount would drop to 0) are reclaimed
        LRU-first before giving up. Raises :class:`OutOfPages` (allocating
        nothing) when the pool still cannot cover the growth."""
        if not self.active[slot]:
            raise ValueError(f"slot {slot} is not active")
        if new_length > self.span:
            raise ValueError(f"length {new_length} exceeds slot span "
                             f"{self.span}")
        pages = self._slot_pages[slot]
        need = self.pages_for(new_length) - len(pages)
        if need <= 0:
            return
        if need > self._n_free:
            self._reclaim_index_pages(need - self._n_free)
        if need > self._n_free:
            raise OutOfPages(
                f"slot {slot} needs {need} page(s), {self._n_free} free")
        # a group of the table that starts here takes a whole run, and what
        # the growth does not cover of it is held ahead for the next ones;
        # where no run is whole, single pages of broken ones
        held = self._ahead.pop(slot, [])
        for _ in range(need):
            if held:
                p = held.pop()
            elif len(pages) % self.run_pages == 0 and self._whole:
                c = self._whole.pop()
                held, self._runs[c] = self._runs[c], []
                held.sort(reverse=True)
                p = held.pop()
            else:
                p = self._take()
            self._owner[p] = slot
            self._refcount[p] = 1
            self.page_table[slot, len(pages)] = p
            pages.append(p)
        self._n_free -= need
        if held:
            self._ahead[slot] = held

    # -- the free pages, by run ----------------------------------------------

    def _reset_free(self, pages) -> None:
        """Every page of ``pages`` free, in that order; nothing held ahead."""
        self._runs = [[] for _ in range(
            -(-(self.num_pages - 1) // self.run_pages))]
        self._whole, self._broken, self._ahead = [], {}, {}
        for p in pages:
            self._put(int(p))
        self._n_free = sum(map(len, self._runs))

    def _put(self, p: int) -> None:
        """Page ``p`` is free again; its run is whole again where it was the
        last one out (a count a run: nothing is sorted or searched)."""
        c = (p - 1) // self.run_pages
        run = self._runs[c]
        run.append(p)
        if len(run) == self.run_pages:
            self._broken.pop(c, None)
            self._whole.append(c)
        elif len(run) == 1:
            self._broken[c] = None

    def _take(self) -> int:
        """ONE free page: of the run that broke last, else off a whole run
        (its lowest), else the farthest page another slot holds ahead. The
        caller has seen that ``_n_free`` allows it, and counts it."""
        if self._broken:
            c = next(reversed(self._broken))
            run = self._runs[c]
            p = run.pop()
            if not run:
                del self._broken[c]
            return p
        if self._whole:
            c = self._whole.pop()
            run = self._runs[c]
            run.sort(reverse=True)
            if len(run) > 1:
                self._broken[c] = None
            return run.pop()
        slot = next(iter(self._ahead))
        held = self._ahead[slot]
        p = held.pop(0)
        if not held:
            del self._ahead[slot]
        return p

    def _drop_ahead(self, slot: int) -> None:
        """What ``slot`` holds ahead goes back to its run."""
        for p in self._ahead.pop(slot, ()):
            self._put(p)

    def _free_pages(self) -> list:
        """The free pages no slot holds ahead, in an order that
        :meth:`_reset_free` turns back into the same runs and stacks: the
        broken runs as they broke, then the whole ones as they stack."""
        return [p for c in (*self._broken, *self._whole)
                for p in self._runs[c]]

    def free_slot(self, slot: int) -> None:
        """Release a slot; each of its pages drops one reference and returns
        to the free list only at refcount 0 (reverse allocation order, so the
        free list stays LIFO-deterministic), after what it held ahead.
        Shared pages survive for their other holders. The page contents are
        left stale — masked attention never reads past a slot's length."""
        if not self.active[slot]:
            raise ValueError(f"slot {slot} is not active")
        if self._slot_holds[slot]:
            raise ValueError(
                f"slot {slot} is held for an in-flight migration "
                f"({int(self._slot_holds[slot])} hold(s)); release the hold "
                f"before freeing")
        self._drop_ahead(slot)
        for p in reversed(self._slot_pages[slot]):
            self._release_ref(p)
        self._slot_pages[slot] = []
        self.page_table[slot] = 0
        if self.window_table is not None:
            self.window_table[slot] = 0  # stale ring rows stay masked
        self.lengths[slot] = 0
        self.active[slot] = False

    # -- migration-handoff holds -------------------------------------------

    def hold_slot(self, slot: int) -> None:
        """Pin ``slot`` for an in-flight page handoff: while at least one
        hold is out, :meth:`free_slot` refuses the slot and :meth:`defrag`
        defers entirely (returns 0 and bumps ``deferred_defrags``) — nothing
        may move or recycle the pages a migration's flat-index snapshot
        references, so the transfer can retry/hedge against stable source
        bytes. Prefix-index pins are refcounts and survive regardless."""
        if not self.active[slot]:
            raise ValueError(f"slot {slot} is not active")
        self._slot_holds[slot] += 1

    def release_slot_hold(self, slot: int) -> None:
        """Drop one migration hold on ``slot`` (see :meth:`hold_slot`)."""
        if self._slot_holds[slot] <= 0:
            raise ValueError(f"slot {slot} has no outstanding hold")
        self._slot_holds[slot] -= 1

    @property
    def held_slots(self) -> list:
        return [s for s in range(self.max_slots) if self._slot_holds[s] > 0]

    # -- reference counting / prefix sharing -------------------------------

    def _release_ref(self, p: int) -> None:
        """Drop one reference to page ``p``; free it at refcount 0."""
        assert self._refcount[p] > 0, f"refcount underflow on page {p}"
        self._refcount[p] -= 1
        if self._refcount[p] == 0:
            self._owner[p] = FREE
            self._put(p)
            self._n_free += 1
        else:
            self._recompute_owner(p)

    def _recompute_owner(self, p: int) -> None:
        """Keep the owner sentinel precise after a reference change: the
        single referencing slot when exclusive, SHARED otherwise."""
        if self._refcount[p] == 0:
            self._owner[p] = FREE
            return
        holders = [s for s in range(self.max_slots)
                   if p in self._slot_pages[s]]
        if len(holders) == 1 and self._index_holds[p] == 0:
            self._owner[p] = holders[0]
        else:
            self._owner[p] = SHARED

    def _drop_index_hold(self, p: int) -> None:
        assert self._index_holds[p] > 0
        self._index_holds[p] -= 1
        self._release_ref(p)

    def _add_index_hold(self, p: int) -> None:
        self._index_holds[p] += 1
        self._refcount[p] += 1
        self._owner[p] = SHARED

    def _evict_index_leaf(self, node) -> None:
        self.prefix.remove(node)
        self.prefix_counters["index_evictions"] += 1
        self._drop_index_hold(node.page)

    def _reclaim_index_pages(self, want: int) -> int:
        """Free up to ``want`` pages by evicting LRU index leaves whose page
        is held ONLY by the index (refcount 1 → dropping the hold frees it).
        Repeats so a freed leaf exposes its now-leaf parent. Returns the
        number of pages actually freed."""
        if self.prefix is None:
            return 0
        freed = 0
        while freed < want:
            candidates = [n for n in self.prefix.leaves()
                          if self._refcount[n.page] == 1]
            if not candidates:
                break
            victim = min(candidates, key=lambda n: (n.stamp, n.page))
            self._evict_index_leaf(victim)
            freed += 1
        self.prefix_counters["reclaimed_pages"] += freed
        return freed

    def probe_prefix(self, tokens, max_tokens: Optional[int] = None) -> dict:
        """Dry-run :meth:`share_prefix`: what WOULD an admit reuse?
        Returns {"tokens": claimable prefix length, "pages": index pages a
        match would map, "forks": COW forks the suffix write would trigger
        (1 when the match ends mid-page)} — the admit feasibility check uses
        this to count pages the slot will NOT need from the free list."""
        if self.prefix is None:
            return {"tokens": 0, "pages": 0, "forks": 0}
        limit = (len(tokens) if max_tokens is None
                 else min(int(max_tokens), len(tokens)))
        claimed, pages = 0, 0
        for node, claim in self.prefix.match(tokens):
            take = min(claim, limit - claimed)
            if take <= 0:
                break
            claimed += take
            pages += 1
        if claimed < (self.prefix_cfg.min_shared_block
                      if self.prefix_cfg else 1):
            return {"tokens": 0, "pages": 0, "forks": 0}
        return {"tokens": claimed, "pages": pages,
                "forks": 1 if claimed % self.page_size else 0}

    def share_prefix(self, slot: int, tokens,
                     max_tokens: Optional[int] = None) -> int:
        """Map the longest indexed prefix of ``tokens`` into a FRESH slot's
        page table with zero data movement: each matched index page gains one
        reference and lands in the slot's next table row; the slot's length
        becomes the claimed token count. ``max_tokens`` caps the claim (the
        batcher passes S-1 so at least one suffix token remains to produce
        the first sampled logits). Returns the claimed length (0 = miss or
        below ``min_shared_block`` — the slot is untouched)."""
        if self.prefix is None:
            return 0
        if not self.active[slot] or self._slot_pages[slot]:
            raise ValueError(
                f"share_prefix needs a fresh active slot; slot {slot} "
                f"already owns {len(self._slot_pages[slot])} page(s)")
        limit = (len(tokens) if max_tokens is None
                 else min(int(max_tokens), len(tokens)))
        matched = self.prefix.match(tokens)
        claimed = 0
        mapped: list = []
        for node, claim in matched:
            take = min(claim, limit - claimed)
            if take <= 0:
                break
            claimed += take
            mapped.append(node)
        if claimed < (self.prefix_cfg.min_shared_block
                      if self.prefix_cfg else 1):
            self.prefix_counters["misses"] += 1
            return 0
        for node in mapped:
            p = node.page
            self._refcount[p] += 1
            self._owner[p] = SHARED
            self.page_table[slot, len(self._slot_pages[slot])] = p
            self._slot_pages[slot].append(p)
            self.prefix.touch(node)
        self.lengths[slot] = claimed
        self.prefix_counters["hits"] += 1
        self.prefix_counters["saved_tokens"] += claimed
        return claimed

    def _index_make_room(self, protect: set) -> bool:
        """Honor ``max_index_pages``: evict LRU leaves (never ``protect``,
        the registration path in flight) until a node fits. False = every
        evictable leaf is protected, caller should stop registering."""
        cap = self.prefix_cfg.max_index_pages if self.prefix_cfg else 0
        if cap <= 0:
            return True
        while self.prefix.num_nodes >= cap:
            candidates = [n for n in self.prefix.leaves()
                          if n not in protect]
            if not candidates:
                return False
            self._evict_index_leaf(
                min(candidates, key=lambda n: (n.stamp, n.page)))
        return True

    def register_prefix(self, slot: int, tokens) -> int:
        """Publish ``slot``'s prompt pages into the index so later admits can
        share them: one full-block node per fully-covered page, plus one
        partial leaf for the tail. Blocks already indexed (a donor's, or the
        shared pages this very slot mapped) are LRU-touched, not re-pinned.
        Newly indexed pages gain an index reference — the slot's own first
        decode write into its partial page will COW-fork, leaving the
        registered bytes immutable. Returns the number of nodes added."""
        if self.prefix is None:
            return 0
        ps = self.page_size
        pages = self._slot_pages[slot]
        node = self.prefix.root
        added = 0
        walked: set = set()
        j = 0
        while (j + 1) * ps <= len(tokens) and j < len(pages):
            key = tuple(int(t) for t in tokens[j * ps:(j + 1) * ps])
            child = node.children.get(key)
            if child is None:
                if not self._index_make_room(walked):
                    return added
                child = self.prefix.insert_full(node, key, pages[j])
                self._add_index_hold(pages[j])
                added += 1
            else:
                self.prefix.touch(child)
            walked.add(child)
            node = child
            j += 1
        tail = tuple(int(t) for t in tokens[j * ps:])
        if tail and j < len(pages):
            for cand in node.partials:
                if cand.tokens == tail:
                    self.prefix.touch(cand)
                    return added
            if not self._index_make_room(walked):
                return added
            self.prefix.insert_partial(node, tail, pages[j])
            self._add_index_hold(pages[j])
            added += 1
        return added

    def release_prefix(self, tokens=None) -> int:
        """Drop index pins: the whole index (``tokens=None``) or the deepest
        exclusive suffix of one registered path. Pages whose refcount hits 0
        return to the free list. Returns the number of nodes released."""
        if self.prefix is None:
            return 0
        if tokens is None:
            dropped = 0
            while True:
                leaves = self.prefix.leaves()
                if not leaves:
                    break
                for leaf in leaves:
                    self._evict_index_leaf(leaf)
                    dropped += 1
            return dropped
        chain = [node for node, _ in self.prefix.match(tokens)]
        dropped = 0
        for node in reversed(chain):
            if not node.is_leaf:
                break
            self._evict_index_leaf(node)
            dropped += 1
        return dropped

    # -- copy-on-write -----------------------------------------------------

    def fork_page(self, slot: int, page_index: int) -> tuple[int, int]:
        """COW: give ``slot`` a private copy-slot for its ``page_index``-th
        page. Allocates a fresh page, repoints the slot's table row, and
        drops one reference on the shared original (every other holder keeps
        it). Returns (old_page, new_page) — the DEVICE copy is the caller's
        job (:meth:`ensure_writable` does it for a materialized pool; the
        split batcher routes the pair through the runtime's per-stage
        pools)."""
        old = self._slot_pages[slot][page_index]
        assert self._refcount[old] > 1, \
            f"fork_page on exclusively-owned page {old}"
        if not self._n_free:
            self._reclaim_index_pages(1)
        if not self._n_free:
            raise OutOfPages(
                f"COW fork for slot {slot} needs a free page, 0 free")
        if page_index == len(self._slot_pages[slot]) - 1:
            # what is held ahead follows the page that is left behind
            self._drop_ahead(slot)
        new = self._take()
        self._n_free -= 1
        self._refcount[new] = 1
        self._owner[new] = slot
        self._refcount[old] -= 1
        self._recompute_owner(old)
        self._slot_pages[slot][page_index] = new
        self.page_table[slot, page_index] = new
        self.prefix_counters["cow_forks"] += 1
        return old, new

    def prepare_write(self, slot: int, new_length: int,
                      start: Optional[int] = None) -> list:
        """Fork every SHARED page the write range
        ``[lengths[slot], new_length)`` touches (bookkeeping only; ``start``
        overrides the range's left edge — :meth:`adopt` rewrites from 0).
        Returns the (old, new) copy list the device pools must apply before
        any row in the range is written."""
        left = int(self.lengths[slot]) if start is None else int(start)
        start = left // self.page_size
        stop = min(self.pages_for(new_length), len(self._slot_pages[slot]))
        forks = [j for j in range(start, stop)
                 if self._refcount[self._slot_pages[slot][j]] > 1]
        # all-or-nothing: a fork that fails MID-loop would leave earlier
        # forks' table rows pointing at pages whose device copy never ran
        if len(forks) > self._n_free:
            self._reclaim_index_pages(len(forks) - self._n_free)
        if len(forks) > self._n_free:
            raise OutOfPages(
                f"slot {slot} needs {len(forks)} COW fork(s), "
                f"{self._n_free} page(s) free")
        return [self.fork_page(slot, j) for j in forks]

    def ensure_writable(self, slot: int, new_length: int) -> list:
        """:meth:`ensure` + COW: after this, every page covering
        ``[lengths[slot], new_length)`` is exclusively owned by ``slot`` and
        safe to write in place. On a materialized pool the page copies run
        here; bookkeeping-only callers (the split batcher) get the (old, new)
        pairs back and must apply them to their own per-stage pools."""
        self.ensure(slot, new_length)
        pairs = self.prepare_write(slot, new_length)
        if pairs and self.pool is not None:
            src = jnp.asarray([o for o, _ in pairs], jnp.int32)
            dst = jnp.asarray([n for _, n in pairs], jnp.int32)
            self.pool = _copy_pages_impl(self.pool, src, dst)
        return pairs

    def device_tables(self, idle=()) -> tuple[jnp.ndarray, jnp.ndarray]:
        """(page_table (max_slots, pages_per_slot), lengths (max_slots,)) as
        device int32 arrays — the traced inputs of the compiled step. The
        ``idle`` slots (active, but riding no step) go out as a free slot
        does: table row 0 and length 0, so the step's write for them lands
        on the trash page and not past their last row. What is uploaded is
        a COPY: the caller goes on growing, freeing and counting while the
        step it launched has yet to run, and an upload may alias its host
        array (the CPU's does) or read it later."""
        table, lengths = self.page_table.copy(), self.lengths.copy()
        if len(idle):
            table[idle] = 0
            lengths[idle] = 0
        return jnp.asarray(table), jnp.asarray(lengths, jnp.int32)

    def device_window_table(self, idle=()) -> jnp.ndarray:
        """(max_slots, window_pages) int32: each slot's ring in the window
        pool, the third traced table of a step with sliding layers; a copy,
        the ``idle`` slots' rows 0, as :meth:`device_tables`."""
        table = self.window_table.copy()
        if len(idle):
            table[idle] = 0
        return jnp.asarray(table)

    # -- the window group's ring -------------------------------------------

    def _ring_of(self, slot: int) -> np.ndarray:
        """The window pool pages that are ``slot``'s ring, entry by entry."""
        wp = self.window_pages
        return 1 + slot * wp + np.arange(wp, dtype=np.int32)

    def window_ring_start(self, length: int) -> int:
        """The first position a ring still holds once ``length`` positions
        are written: the start of the oldest of the ``window_pages`` pages
        that end with the newest position's. (Its first rows may have left
        the window already; the attend masks by position.)"""
        return max(0, ((length - 1) // self.page_size - self.window_pages
                       + 1) * self.page_size)

    def _ring_indices(self, slot: int, start: int, stop: int) -> np.ndarray:
        """Flat row indices, in the window pool, of positions [start, stop)
        of ``slot``: position p at ring entry (p // ps) % window_pages."""
        pos = np.arange(start, stop)
        entry = (pos // self.page_size) % self.window_pages
        return (self.window_table[slot, entry] * self.page_size
                + pos % self.page_size).astype(np.int32)

    @property
    def window_rows_capacity(self) -> int:
        """Rows the rings of all slots hold, a window layer."""
        return self.max_slots * self.window_pages * self.page_size

    @property
    def latent_rows_capacity(self) -> int:
        """Rows the pages can hold, a latent layer (0: no latent layer)."""
        return self.token_capacity if self.cfg.latent_layers else 0

    @property
    def latent_rows_live(self) -> int:
        """Latent rows of live streams, a latent layer."""
        return self.live_tokens if self.cfg.latent_layers else 0

    @property
    def kv_row_bytes(self) -> int:
        """Device bytes ONE position keeps in ONE layer of the page pool, as
        stored (every leaf, codes and scales, lane padding included); 0 for
        a bookkeeping-only cache."""
        if self.pool is None:
            return 0
        return sum(a.shape[-1] * a.dtype.itemsize for a in self.pool)

    @property
    def window_rows_live(self) -> int:
        """Ring rows inside some stream's window, a window layer."""
        return int(np.minimum(self.lengths[self.active],
                              self.cfg.sliding_window).sum())

    def adopt_window(self, slot: int, wk_seq, wv_seq, length: int) -> None:
        """Write the sliding layers' (L_window, n, KV, hd) post-rotary K/V of
        positions ``[window_ring_start(length), length)`` — the tail of a
        prefill, or an evicted stream's gathered ring — at their ring places.
        Whole pages go a page a scatter slice, as :meth:`adopt`'s. A ring of
        LATENT rows takes them as ``wk_seq`` (L_window, n, window_row_lanes),
        as stored, and ``wv_seq`` None (:func:`write_rows`' convention)."""
        start = self.window_ring_start(length)
        if wk_seq.shape[1] != length - start:
            raise ValueError(
                f"adopt_window takes positions [{start}, {length}) of a "
                f"{length}-position stream, got {wk_seq.shape[1]} rows")
        if isinstance(self.window_pool, LatentPool) != (wv_seq is None):
            raise ValueError(
                f"a ring of {type(self.window_pool).__name__} rows adopts "
                f"{'latent rows alone' if wv_seq is not None else 'K and V'}")
        dest = jnp.asarray(self._ring_indices(slot, start, length))
        if wv_seq is None:
            self.window_pool = _adopt_latent_impl(
                self.window_pool, jnp.asarray(wk_seq), dest, head=0)
            return
        self.window_pool = _adopt_impl(self.window_pool, jnp.asarray(wk_seq),
                                       jnp.asarray(wv_seq), dest, head=0)

    def gather_window(self, slot: int) -> dict:
        """``slot``'s ring as host arrays {"wk", "wv"}: (L_window, n, KV, hd)
        rows of positions ``[window_ring_start(length), length)`` in position
        order — what an eviction keeps beside :meth:`gather_slot`'s rows, and
        what :meth:`adopt_window` takes back. A ring of latent rows:
        {"wrows": (L_window, n, window_row_lanes)}, bytes as stored (the
        main group's payload already holds a "rows")."""
        if not self.window_pages:
            return {}
        n = int(self.lengths[slot])
        start = self.window_ring_start(n)
        idx = jnp.asarray(self._ring_indices(slot, start, max(n, 1)))
        if isinstance(self.window_pool, LatentPool):
            (rows,) = _gather_latent_impl(self.window_pool, idx)
            return {"wrows": np.asarray(rows)[:, :n - start]}
        k, v = _gather_impl(self.window_pool, idx,
                            kv=self.cfg.num_kv_heads)
        return {"wk": np.asarray(k)[:, :n - start],
                "wv": np.asarray(v)[:, :n - start]}

    # -- data movement -----------------------------------------------------

    def _require_pool(self, what: str) -> None:
        if self.pool is None:
            raise ValueError(f"{what} needs a materialized pool; this cache "
                             f"was built with materialize=False "
                             f"(bookkeeping-only)")

    def _refuse_window(self, what: str) -> None:
        """Refuse a mechanism that reads the pool as K and V of every
        position (the name is older than the latent rows)."""
        from .hybrid import (refuse_index_keys, refuse_latent_rows,
                             refuse_window_ring)

        refuse_window_ring(self.cfg, what)
        refuse_latent_rows(self.cfg, what)
        refuse_index_keys(self.cfg, what)

    def _refuse_index(self, what: str) -> None:
        """Refuse a mechanism that moves a range of a slot's K/V rows and
        knows no index key."""
        from .hybrid import refuse_index_keys

        refuse_index_keys(self.cfg, what)

    def _flat_indices(self, slot: int, n: int) -> np.ndarray:
        pos = np.arange(n)
        return (self.page_table[slot, pos // self.page_size]
                * self.page_size + pos % self.page_size).astype(np.int32)

    def adopt(self, slot: int, k_seq, v_seq, length: int,
              index=None) -> None:
        """Write a contiguous (L, length, KV, hd) post-rotary K/V prefix
        (a prefill's cache, or a restored checkpoint) into ``slot``'s pages
        and set its length. Allocates pages as needed; any shared page in
        the range is COW-forked first (no device copy — every row the fork
        exposes is overwritten here, and rows past ``length`` stay masked).
        ``index``: the positions' (L, length, index_row_lanes) index keys, as
        stored, where the pool keeps them (:class:`IndexedPagePool`)."""
        self._require_pool("adopt")
        self.ensure(slot, length)
        self.prepare_write(slot, length, start=0)
        dest = jnp.asarray(self._flat_indices(slot, length))
        self.pool = _adopt_impl(
            self.pool, jnp.asarray(k_seq), jnp.asarray(v_seq), dest, head=0,
            index=None if index is None else jnp.asarray(index))
        self.lengths[slot] = length

    def adopt_latent(self, slot: int, rows, length: int,
                     index=None) -> None:
        """:meth:`adopt` for a :class:`LatentPool`: a contiguous (L, length,
        kv_row_lanes) prefix of latent rows as stored (a prefill's cache, or
        an evicted stream's gathered rows) into ``slot``'s pages; ``index``:
        the positions' index keys where the pool keeps them
        (:class:`IndexedLatentPool`), as :meth:`adopt`'s."""
        self._require_pool("adopt_latent")
        self.ensure(slot, length)
        self.prepare_write(slot, length, start=0)
        dest = jnp.asarray(self._flat_indices(slot, length))
        self.pool = _adopt_latent_impl(
            self.pool, jnp.asarray(rows), dest, head=0,
            index=None if index is None else jnp.asarray(index))
        self.lengths[slot] = length

    def adopt_rows(self, slot: int, k_seq, v_seq,
                   start: int, stop: int) -> None:
        """Suffix variant of :meth:`adopt`: write (L, stop-start, KV, hd)
        post-rotary K/V into rows ``[start, stop)`` of ``slot`` — the
        prefix-sharing admit path lands ONLY the unmatched suffix here, the
        shared rows below ``start`` stay aliased. ``start`` must equal the
        slot's current length (the shared-prefix claim)."""
        self._require_pool("adopt_rows")
        self._refuse_index("adopt_rows (a prefix hit's suffix)")
        if start != int(self.lengths[slot]):
            raise ValueError(f"adopt_rows start {start} != slot {slot} "
                             f"length {int(self.lengths[slot])}")
        self.ensure_writable(slot, stop)
        dest = jnp.asarray(self._flat_indices(slot, stop)[start:])
        self.pool = _adopt_impl(self.pool, jnp.asarray(k_seq),
                                jnp.asarray(v_seq), dest,
                                head=-start % self.page_size)
        self.lengths[slot] = stop

    def adopt_packed(self, slot: int, k_codes, v_codes, k_scale, v_scale,
                     length: int) -> None:
        """Write already-packed (L, length, KV, hdc) codes + (L, length, KV)
        scales into ``slot`` — the restore/readmit path for quantized
        checkpoints. No requantize happens, so the pool bytes equal the
        gathered bytes exactly, across any pool geometry."""
        self._require_pool("adopt_packed")
        if self.kv_codec == "fp":
            raise KVTierMismatchError(
                offered="quantized", pool=self.kv_codec,
                where="adopt_packed",
                detail="packed payloads are for quantized tiers; fp pools "
                       "adopt fp rows via adopt()")
        self.ensure(slot, length)
        self.prepare_write(slot, length, start=0)
        dest = jnp.asarray(self._flat_indices(slot, length))
        self.pool = _adopt_packed_impl(
            self.pool, jnp.asarray(k_codes), jnp.asarray(v_codes),
            jnp.asarray(k_scale), jnp.asarray(v_scale), dest, head=0)
        self.lengths[slot] = length

    @property
    def state_bytes(self) -> int:
        """Device bytes of the per-slot recurrent state store (0 for a
        family whose state is its pages)."""
        return sum(self.state_leaf_bytes.values())

    @property
    def state_leaf_bytes(self) -> dict:
        """{leaf: device bytes} of the state store."""
        return {leaf: int(a.nbytes) for leaf, a in (self.state or {}).items()}

    def adopt_state(self, slot: int, *rows) -> None:
        """Overwrite ``slot``'s recurrent state with ``rows``: (L_kind, ...)
        for each leaf the store holds, in its order (a Mamba-2 stack's
        ``conv``, ``ssm``; a short-convolution stack's ``conv``) — a
        prefill's, or an evicted stream's gathered rows (scalars broadcast:
        zeros at allocation)."""
        if self.state is None:
            raise ValueError(f"family {self.cfg.family!r} keeps no recurrent "
                             f"state")
        if not self.active[slot]:
            raise ValueError(f"slot {slot} is not active")
        if len(rows) != len(self.state):
            raise ValueError(f"the state store holds {list(self.state)}, "
                             f"got {len(rows)} leaves")
        self.state = _state_set_impl(
            self.state,
            {leaf: jnp.asarray(a, jnp.float32)
             for leaf, a in zip(self.state, rows)},
            jnp.asarray(slot, jnp.int32))

    def gather_state(self, slot: int) -> dict:
        """``slot``'s recurrent state as host arrays, {leaf: (L_kind, ...)}:
        what an eviction keeps beside :meth:`gather_slot`'s K/V rows."""
        if self.state is None:
            return {}
        return {leaf: np.asarray(a) for leaf, a in _state_get_impl(
            self.state, jnp.asarray(slot, jnp.int32)).items()}

    def gather_slot(self, slot: int) -> dict:
        """Read ``slot``'s K/V back as the contiguous host state dict the
        recovery checkpoint stores: {"k": (L, length, KV, hd), "v": ...,
        "length"} — byte-identical to the contiguous cache prefix on the fp
        tier; on quantized tiers the rows come back DEQUANTIZED to fp32
        (the suffix-prefill compute path — use :meth:`gather_slot_packed`
        when the bytes themselves must survive). A latent stack's payload is
        {"rows": (L, length, kv_row_lanes), "length"}, bytes as stored; a
        sparse-attention stack's adds "index": (L, length, index_row_lanes),
        what :meth:`adopt` takes back as ``index``."""
        self._require_pool("gather_slot")
        n = int(self.lengths[slot])
        idx = jnp.asarray(self._flat_indices(slot, max(n, 1)))
        if isinstance(self.pool, LATENT_POOLS):
            # a latent stack's rows as stored, what adopt_latent takes back
            # (its index keys with them, where the pool keeps any)
            rows, *index = _gather_latent_impl(self.pool, idx)
            return {"rows": np.asarray(rows)[:, :n],
                    **{"index": np.asarray(a)[:, :n] for a in index},
                    "length": np.asarray(n, np.int32)}
        k, v, *index = _gather_impl(self.pool, idx, kv=self.cfg.num_kv_heads)
        return {"k": np.asarray(k)[:, :n], "v": np.asarray(v)[:, :n],
                # a sparse-attention stack's index keys, as stored
                **{"index": np.asarray(a)[:, :n] for a in index},
                "length": np.asarray(n, np.int32)}

    def gather_slot_packed(self, slot: int) -> dict:
        """Quantized-tier eviction/checkpoint form: {"k_codes", "v_codes",
        "k_scale", "v_scale", "length"} host arrays — raw pool bytes, so
        gather -> adopt_packed round-trips bit-exactly by construction."""
        self._require_pool("gather_slot_packed")
        if self.kv_codec == "fp":
            raise ValueError("gather_slot_packed is for quantized tiers; "
                             "fp pools use gather_slot()")
        n = int(self.lengths[slot])
        idx = jnp.asarray(self._flat_indices(slot, max(n, 1)))
        kc, vc, ks, vs = _gather_packed_impl(self.pool, idx)
        return {"k_codes": np.asarray(kc)[:, :n],
                "v_codes": np.asarray(vc)[:, :n],
                "k_scale": np.asarray(ks)[:, :n],
                "v_scale": np.asarray(vs)[:, :n],
                "length": np.asarray(n, np.int32)}

    def _check_row_range(self, slot: int, start: int, stop: int) -> None:
        if not 0 <= start < stop <= int(self.lengths[slot]):
            raise ValueError(
                f"row range [{start}, {stop}) out of slot {slot}'s "
                f"length {int(self.lengths[slot])}")

    def gather_slot_rows(self, slot: int, start: int, stop: int) -> dict:
        """Row range ``[start, stop)`` of :meth:`gather_slot` — the per-page
        migration chunk (a handoff seals, ships, and verifies one page at a
        time; under :meth:`hold_slot` the flat indices stay stable across
        the whole ranged walk)."""
        self._require_pool("gather_slot_rows")
        self._refuse_index("gather_slot_rows (a migration's page chunk)")
        self._check_row_range(slot, start, stop)
        idx = jnp.asarray(self._flat_indices(slot, stop)[start:])
        k, v = _gather_impl(self.pool, idx, kv=self.cfg.num_kv_heads)
        return {"k": np.asarray(k), "v": np.asarray(v)}

    def gather_slot_rows_packed(self, slot: int, start: int,
                                stop: int) -> dict:
        """Row range ``[start, stop)`` of :meth:`gather_slot_packed` — raw
        pool bytes for one migrated page, so the packed adopt on the far
        side is a byte move."""
        self._require_pool("gather_slot_rows_packed")
        if self.kv_codec == "fp":
            raise ValueError("gather_slot_rows_packed is for quantized "
                             "tiers; fp pools use gather_slot_rows()")
        self._check_row_range(slot, start, stop)
        idx = jnp.asarray(self._flat_indices(slot, stop)[start:])
        kc, vc, ks, vs = _gather_packed_impl(self.pool, idx)
        return {"k_codes": np.asarray(kc), "v_codes": np.asarray(vc),
                "k_scale": np.asarray(ks), "v_scale": np.asarray(vs)}

    def defrag(self) -> int:
        """Compact allocated pages to the low end of the pool (slot order,
        trash page fixed at 0) and rebuild the free list above them. Returns
        the number of pages that moved. One donated device gather; page
        tables are rewritten to match, so every slot's logical content is
        unchanged. Deferred (returns 0) while any slot holds a migration
        pin — a compaction would invalidate the in-flight transfer's
        flat-index snapshot."""
        self._require_pool("defrag")
        if self._slot_holds.any():
            self.deferred_defrags += 1
            return 0
        # src (new -> old) must be a TRUE permutation: after alloc/grow/free
        # churn an owned page's compacted destination can be a currently-free
        # page with a HIGHER id (e.g. slot pages [[4],[2],[1]] with page 3
        # free), so inverting an old->new map would collide with the free
        # page's identity entry and gather garbage into the destination.
        # Place referenced pages at their destinations first, then spread the
        # leftover old pages over the remaining destinations. A SHARED page
        # gets its destination on FIRST encounter and every later holder —
        # other slots' table rows, index nodes — repoints to that same id,
        # so it moves exactly once.
        src = np.zeros((self.num_pages,), np.int32)  # new -> old; src[0] = 0
        new_of: dict = {}
        moved = 0
        nxt = 1

        def place(p: int) -> int:
            nonlocal moved, nxt
            if p in new_of:
                return new_of[p]
            src[nxt] = p
            if p != nxt:
                moved += 1
            new_of[p] = nxt
            nxt += 1
            return new_of[p]

        for s in range(self.max_slots):
            pages = self._slot_pages[s]
            for j, p in enumerate(pages):
                pages[j] = place(p)
                self.page_table[s, j] = pages[j]
        if self.prefix is not None:
            for node in self.prefix.iter_nodes():
                node.page = place(node.page)
        placed = set(int(x) for x in src[:nxt])
        src[nxt:] = [p for p in range(1, self.num_pages) if p not in placed]
        # bookkeeping arrays ride the same permutation (free pages carry
        # FREE/0/0, so the gather is correct for the whole range).
        self._owner = self._owner[src].copy()
        self._refcount = self._refcount[src].copy()
        self._index_holds = self._index_holds[src].copy()
        self._reset_free(range(self.num_pages - 1, nxt - 1, -1))
        if moved:
            self.pool = _permute_impl(self.pool, jnp.asarray(src))
        return moved

    # -- serialization -----------------------------------------------------

    def state_dict(self) -> dict:
        """Whole-cache snapshot as host numpy arrays — the checkpoint form.
        (Per-slot checkpoints use :meth:`gather_slot` instead, which is
        geometry-independent.)"""
        self._require_pool("state_dict")
        self._refuse_window("state_dict (the whole-cache snapshot)")
        # the K/V (code) leaves in the form callers hand rows over in,
        # (L, P, ps, KV, lanes) — what every earlier checkpoint holds; the
        # stored row merges the last two axes, a free reshape either way
        kv = self.cfg.num_kv_heads
        # (the fp leaf is split on the host: no second pool on the device)
        k, v = (_split_heads(a, kv) for a in (
            split_kv(np.asarray(self.pool.kv)) if self.kv_codec == "fp"
            else map(np.asarray, self.pool[:2])))
        if self.kv_codec == "fp":
            # pre-quantization key set, unchanged: old checkpoints and fp
            # pools stay mutually loadable
            state = {"k": k, "v": v}
        else:
            state = {"kv_codec": self.kv_codec, "k_codes": k, "v_codes": v,
                     "k_scale": np.asarray(self.pool.k_scale),
                     "v_scale": np.asarray(self.pool.v_scale)}
        state.update({"page_table": self.page_table.copy(),
                      "lengths": self.lengths.copy(),
                      "active": self.active.copy(),
                      "free": np.asarray(self._free_pages(), np.int32),
                      "refcount": self._refcount.copy(),
                      "index_holds": self._index_holds.copy()})
        if self.run_pages > 1:
            # a row a slot, the next page first, 0 where nothing is held
            ahead = np.zeros((self.max_slots, self.run_pages - 1), np.int32)
            for s, held in self._ahead.items():
                ahead[s, :len(held)] = held[::-1]
            state["ahead"] = ahead
        if self.prefix is not None:
            state["prefix_index"] = self.prefix.to_array()
        for leaf, a in (self.state or {}).items():
            state["state_" + leaf] = np.asarray(a)
        return state

    def load_state_dict(self, state: dict) -> None:
        """Restore :meth:`state_dict` output bit-exactly (same geometry).
        Refcounts, index holds, and the serialized radix index round-trip
        when present; pre-sharing checkpoints (no ``refcount`` key) derive
        exclusive refcounts from the slot tables, so restore never
        double-frees or leaks a page either way."""
        self._require_pool("load_state_dict")
        self._refuse_window("load_state_dict (the whole-cache snapshot)")
        ck = state.get("kv_codec", "fp")
        if ck != self.kv_codec:
            # REFUSAL, not transcode: silently requantizing (or inflating)
            # a whole pool would change every page's bytes under checkpoints
            # that promise bit-exact round-trips — the caller must build a
            # cache at the checkpoint's tier instead.
            raise KVTierMismatchError(offered=ck, pool=self.kv_codec,
                                      where="load_state_dict")
        names = (("k", "v") if self.kv_codec == "fp"
                 else ("k_codes", "v_codes", "k_scale", "v_scale"))
        kv = self.cfg.num_kv_heads
        lanes = _k_lanes(self.pool)
        want = self.pool[0].shape[:-1] + (kv, lanes // kv)
        if state[names[0]].shape != want:
            raise ValueError(
                f"pool shape mismatch: checkpoint {state[names[0]].shape} "
                f"vs cache {want}")
        if self.kv_codec == "fp":    # joined on the host, uploaded once
            self.pool = PagePool(jnp.asarray(np.concatenate(
                [_merge_heads(np.asarray(state[n])) for n in names], -1)))
        else:
            self.pool = type(self.pool)(*(
                jnp.asarray(state[n]).reshape(a.shape)
                for n, a in zip(names, self.pool)))
        if self.state is not None:
            for leaf, a in self.state.items():
                if state["state_" + leaf].shape != a.shape:
                    raise ValueError(
                        f"state store shape mismatch: checkpoint {leaf} "
                        f"{state['state_' + leaf].shape} vs {a.shape}")
            self.state = {leaf: jnp.asarray(state["state_" + leaf])
                          for leaf in self.state}
        self.page_table = np.asarray(state["page_table"], np.int32).copy()
        self.lengths = np.asarray(state["lengths"], np.int32).copy()
        self.active = np.asarray(state["active"], bool).copy()
        self._reset_free(state["free"])
        for s, held in enumerate(state.get("ahead", ())):
            if held[0]:
                self._ahead[s] = [int(p) for p in held[held > 0][::-1]]
        self._slot_pages = [[] for _ in range(self.max_slots)]
        for s in range(self.max_slots):
            if not self.active[s]:
                continue
            n = self.pages_for(int(self.lengths[s]))
            self._slot_pages[s] = [int(p) for p in self.page_table[s, :n]]
        if "refcount" in state:
            self._refcount = np.asarray(state["refcount"], np.int32).copy()
            self._index_holds = np.asarray(state["index_holds"],
                                           np.int32).copy()
        else:
            self._refcount = np.zeros((self.num_pages,), np.int32)
            self._index_holds = np.zeros((self.num_pages,), np.int32)
            for pages in self._slot_pages:
                for p in pages:
                    self._refcount[p] += 1
        if self.prefix is not None:
            self.prefix = PrefixIndex(self.page_size)
            if state.get("prefix_index") is not None:
                self.prefix.load_array(np.asarray(state["prefix_index"]))
        elif self._index_holds.any():
            # sharing-era checkpoint restored into a prefix-disabled cache:
            # the index is gone, so its holds must not pin (or leak) pages.
            for p in np.nonzero(self._index_holds)[0]:
                self._refcount[p] -= self._index_holds[p]
                self._index_holds[p] = 0
                if self._refcount[p] == 0:
                    self._put(int(p))
        self._n_free = self.num_pages - 1 - int(np.sum(self._refcount > 0))
        self._owner = np.full((self.num_pages,), FREE, np.int32)
        for p in range(1, self.num_pages):
            if self._refcount[p] > 0:
                self._recompute_owner(p)

    # -- invariants --------------------------------------------------------

    def check_invariants(self) -> None:
        """Raise AssertionError on any aliasing/leak/ownership/refcount
        violation — the test suite calls this after every mutation."""
        assert (self.state is not None) == self.cfg.recurrent_state, \
            "a state store exists exactly for a family with recurrent state"
        assert bool(self.window_pages) == bool(self.cfg.window_layers), \
            "a window group exists exactly for a stack with sliding layers"
        if self.window_pages:
            wp = self.window_pages
            assert wp == self.cfg.window_pages(self.page_size)
            assert wp * self.page_size >= \
                self.cfg.sliding_window + self.page_size - 1, \
                "a ring must hold a whole window wherever it starts in a page"
            assert self.window_pool[0].shape[:3] == (
                self.cfg.window_layers, self.max_slots * wp + 1,
                self.page_size), \
                f"window pool {self.window_pool[0].shape}: a ring of {wp} " \
                f"pages a slot and the trash page, however long streams grow"
            assert isinstance(self.window_pool, LatentPool) == bool(
                self.cfg.window_latent_layers) and (
                not self.cfg.window_latent_layers
                or self.window_pool.rows.shape[-1]
                == self.cfg.window_row_lanes), \
                "a ring holds latent rows (at the window kind's own width) " \
                "exactly for window layers that cache them"
            assert self.pool is None or \
                self.pool[0].shape[0] == self.cfg.kv_layers, \
                "the page pool holds the full attention layers only"
            for s in range(self.max_slots):
                want = self._ring_of(s) if self.active[s] else 0
                assert (self.window_table[s] == want).all(), \
                    f"slot {s}'s ring {self.window_table[s]} != {want}"
            live = self.window_table[self.window_table > 0]
            assert len(live) == len(set(live.tolist())), \
                "a window page in two rings"
        assert isinstance(self.pool, LATENT_POOLS) == bool(
            self.cfg.latent_layers and self.pool is not None), \
            "a pool of latent rows exists exactly for a stack of latent layers"
        assert isinstance(self.pool, INDEXED_POOLS) == bool(
            self.cfg.sparse_layers and self.pool is not None), \
            "a pool holds index keys exactly for a stack of sparse layers"
        if self.cfg.latent_layers and self.pool is not None:
            want = (self.cfg.kv_layers, self.num_pages, self.page_size,
                    self.cfg.kv_row_lanes)
            assert self.pool.rows.shape == want, \
                f"latent pool {self.pool.rows.shape} != {want}"
            assert self.cfg.kv_row_lanes % LANE_TILE == 0 and \
                self.cfg.kv_row_lanes >= self.cfg.kv_lora_rank + \
                self.cfg.qk_rope_head_dim, \
                "a latent row is whole lane tiles that hold c and k_rope"
        if self.state is not None:
            from .hybrid import state_shapes

            want = state_shapes(self.cfg, self.max_slots)
            have = {leaf: a.shape for leaf, a in self.state.items()}
            assert have == want, f"state store shapes {have} != {want}"
            assert all(a.dtype == jnp.float32 for a in self.state.values()), \
                "recurrent state must stay float32"
            if self.pool is not None:
                assert self.pool[0].shape[0] == self.cfg.kv_layers, \
                    "the page pool holds the attention layers only"
        free = self._free_pages()
        assert 0 not in free, "trash page 0 on the free list"
        assert self._owner[0] == FREE, "trash page 0 owned by a slot"
        assert self._refcount[0] == 0, "trash page 0 referenced"
        # ground truth: refcount == slot-table references + index holds.
        expect = np.zeros((self.num_pages,), np.int32)
        holders: list = [[] for _ in range(self.num_pages)]
        for s, pages in enumerate(self._slot_pages):
            assert len(pages) == len(set(pages)), \
                f"slot {s} references a page twice: {pages}"
            for p in pages:
                expect[p] += 1
                holders[p].append(s)
        index_holds = np.zeros((self.num_pages,), np.int32)
        if self.prefix is not None:
            for node in self.prefix.iter_nodes():
                index_holds[node.page] += 1
                if node.full:
                    assert len(node.tokens) == self.page_size, \
                        f"full index node with {len(node.tokens)} tokens"
                else:
                    assert 0 < len(node.tokens) < self.page_size, \
                        f"partial index node with {len(node.tokens)} tokens"
                    assert not node.children and not node.partials, \
                        "partial index node has children"
        expect += index_holds
        assert (self._index_holds == index_holds).all(), \
            f"index holds drifted: {self._index_holds} vs {index_holds}"
        assert (self._refcount == expect).all(), \
            f"refcounts drifted: {self._refcount} vs {expect}"
        referenced = set(int(p) for p in np.nonzero(expect)[0])
        assert len(free) == len(set(free)), "page twice on the free list"
        assert not (referenced & set(free)), \
            "page both referenced and free"
        # the runs: every free page in its own run's list, a run among the
        # whole ones exactly when all its pages are free, among the broken
        # ones exactly when some are
        g = self.run_pages
        if self.pool is not None and self.kv_codec == "fp":
            assert g == pool_run_pages(self.pool, self.pages_per_slot), \
                "a run is as long as the walk's rule makes it for the " \
                "pool's smallest page"
        assert sum(map(len, self._runs)) == len(free), \
            "a run with free pages is neither whole nor broken"
        whole = set(self._whole)
        assert len(self._whole) == len(whole), "a run stacked twice"
        for c, run in enumerate(self._runs):
            assert all((p - 1) // g == c for p in run), \
                f"run {c} lists another run's page: {run}"
            assert (c in whole) == (len(run) == g), \
                f"run {c} has {len(run)} of {g} pages free, whole or not"
            assert (c in self._broken) == (0 < len(run) < g), \
                f"run {c} has {len(run)} of {g} pages free, broken or not"
        # held ahead: the pages that follow a slot's last one in its run,
        # nobody's (free to whoever asks) and on no free list
        ahead = [p for held in self._ahead.values() for p in held]
        assert len(ahead) == len(set(ahead)), "page held ahead twice"
        assert not (set(ahead) & (referenced | set(free))), \
            "page both held ahead and owned or free"
        for s, held in self._ahead.items():
            pages = self._slot_pages[s]
            assert self.active[s] and pages and 0 < len(held) < g, \
                f"slot {s} holds {held} ahead of {pages}"
            assert held == list(range(pages[-1] + len(held), pages[-1], -1)) \
                and (held[0] - 1) // g == (pages[-1] - 1) // g, \
                f"slot {s} holds {held} ahead: not what follows {pages[-1]} " \
                f"in its run"
            assert (len(pages) - 1) % g + 1 + len(held) <= g, \
                f"slot {s} holds {held} ahead past its table's group"
        assert self._n_free == len(free) + len(ahead), \
            f"num_free_pages {self._n_free} != {len(free)} + {len(ahead)}"
        assert referenced | set(free) | set(ahead) == \
            set(range(1, self.num_pages)), \
            "page leaked (neither referenced nor free)"
        for p in range(1, self.num_pages):
            if expect[p] == 0:
                assert self._owner[p] == FREE, f"free page {p} has an owner"
            elif expect[p] == 1 and len(holders[p]) == 1:
                assert self._owner[p] == holders[p][0], \
                    f"exclusive page {p} owner {self._owner[p]} != " \
                    f"slot {holders[p][0]}"
            else:
                assert self._owner[p] == SHARED, \
                    f"shared page {p} owner {self._owner[p]} != SHARED"
        for s in range(self.max_slots):
            if self.active[s]:
                assert len(self._slot_pages[s]) * self.page_size >= \
                    self.lengths[s], f"slot {s} pages do not cover its length"
                for j, p in enumerate(self._slot_pages[s]):
                    assert self.page_table[s, j] == p
            else:
                assert not self._slot_pages[s], f"inactive slot {s} owns pages"
                assert (self.page_table[s] == 0).all()
                assert self.lengths[s] == 0
                assert self._slot_holds[s] == 0, \
                    f"inactive slot {s} carries a migration hold"

    def prefix_report(self) -> dict:
        """Host-side sharing stats for ``ContinuousBatcher.report()`` and
        the obs gauges. Cheap — no device sync."""
        c = self.prefix_counters
        total = c["hits"] + c["misses"]
        return {"enabled": self.prefix is not None,
                "hits": c["hits"], "misses": c["misses"],
                "hit_rate": (c["hits"] / total) if total else 0.0,
                "saved_tokens": c["saved_tokens"],
                "cow_forks": c["cow_forks"],
                "index_evictions": c["index_evictions"],
                "reclaimed_pages": c["reclaimed_pages"],
                "shared_pages": int(self.shared_pages),
                "index_pages": int(self.index_pages),
                "index_nodes": (self.prefix.num_nodes
                                if self.prefix is not None else 0)}


# ---------------------------------------------------------------------------
# the ragged decode step: one position for EVERY slot, per-slot positions,
# one compiled executable per pool geometry.
# ---------------------------------------------------------------------------


def _apply_rotary_rows(x: jnp.ndarray, cos_b: jnp.ndarray,
                       sin_b: jnp.ndarray, rot: int) -> jnp.ndarray:
    """``apply_rotary`` with a PER-SLOT table row: x (B, 1, H, hd), cos/sin
    (B, rot) gathered at each slot's own position. Elementwise ops and
    values match the contiguous path's single sliced row exactly."""
    c = cos_b[:, None, None, :].astype(x.dtype)
    s = sin_b[:, None, None, :].astype(x.dtype)
    if rot == x.shape[-1]:
        return x * c + _rotate_half(x) * s
    x_rot, x_pass = x[..., :rot], x[..., rot:]
    x_rot = x_rot * c + _rotate_half(x_rot) * s
    return jnp.concatenate([x_rot, x_pass], axis=-1)


@jax.named_scope("paged_kv.write")
def write_rows(pool, layer, page_table, lengths, k, v, ring: bool = False,
               index=None):
    """The pool with a step's new K/V rows in layer ``layer``: pool leaves
    (L, P, ps, KV*lanes) WITH their layer axis, ``layer`` a traced or static
    index, k, v (B, 1, KV, hd) post-rotary, slot i's row at position
    ``lengths[i]`` of its page list. The fp tier stores the cast row (its KV
    heads merged into one minor vector, K's then V's: one row, ONE scatter a
    layer); a quantized tier quantizes ON APPEND and scatters codes + the
    row's own scales — neighbouring rows are untouched, which is why scales
    are per row and not per page.

    One scatter a leaf at the flat index ``layer*P*ps + page*ps + row`` over
    the leaf viewed (L*P*ps, KV*lanes): the carried pool is updated where it
    lies, no layer is sliced out of it and none is put back. The only code
    that knows where in a pool a decode step's row goes.

    A :class:`LatentPool` takes its ONE row a slot as ``k`` (B, 1,
    kv_row_lanes), as stored, and ``v`` None. An :class:`IndexedPagePool`
    also takes the position's index key, ``index`` (B, index_row_lanes) as
    stored, into its second leaf at the same flat index.

    ``ring`` (static): the table is a window layer's RING of pages, and
    position p lives in entry ``(p // ps) % entries``: the row written
    overwrites one that has left the window."""
    tier = pool_tier(pool)
    if tier == "fp":     # ONE row: K lanes then V lanes, or the latent row
        stored = (k[:, 0] if v is None else join_kv(
            _merge_heads(k[:, 0]), _merge_heads(v[:, 0])),)
        if index is not None:
            stored += (index,)
    else:
        qk, sk = quantize_kv_rows(k[:, 0], tier)  # (B,KV,hdc), (B,KV)
        qv, sv = quantize_kv_rows(v[:, 0], tier)
        stored = (qk, qv, sk, sv)
    ps = pool.page_size
    # slot i's new token lands in its (length // page_size)-th page at offset
    # length % page_size; inactive slots (all-zero table rows) land in the
    # trash page, where duplicate scatter indices are harmless garbage
    base = layer * (pool.num_pages * ps)
    slots = jnp.arange(k.shape[0])
    entry = lengths // ps
    if ring:
        entry = entry % page_table.shape[1]
    dest = base + page_table[slots, entry] * ps + lengths % ps  # (B,)
    return type(pool)(*(
        _rows(a, 1).at[dest].set(
            r.astype(a.dtype).reshape(-1, a.shape[-1])).reshape(a.shape)
        for a, r in zip(pool, stored)))


def _gather_pages(leaf, layer, page_table):
    """Each slot's pages out of layer ``layer`` of a pool leaf (L, P, ps,
    width), in table order: page_table (B, pages_per_slot) -> (B, span,
    width); trash-page rows of an unallocated tail come along and stay under
    the caller's length mask.

    One gather slice is one whole PAGE at ``layer*P + page`` of the leaf
    viewed (L*P, ps, width). What the forms cost on a v5e (PERF.md §6 "PR
    27", "PR 29"; one layer's K or V at 192 slots x 128 pages of 16 rows):
    a ROW a slice 4.4-4.65 ms (11.9 ns a row whatever it held); a page a
    slice of the (KV, hd) = (2, 64) pool 0.85 ms, by PADDED bytes (a row was
    half a lane tile) plus a relayout of the layer's slice in and out of the
    pages-minor array around it; a page a slice of the lane-dense pool is
    whole (8,128)(2,1) tiles, read where they lie: 0.46-0.63 ms for its true
    200 MB (0.62-0.80 where most of the table is the trash page). Same values
    in the same order as the row gather (tests/test_batching.py keeps it as
    the oracle, and guards the traced step against its return)."""
    b, pps = page_table.shape
    at = layer * leaf.shape[1] + page_table
    return _pages(leaf, 1)[at].reshape(b, pps * leaf.shape[2],
                                       leaf.shape[-1])


def read_span(pool, layer, page_table, dtype):
    """Each slot's whole span of K and V out of layer ``layer`` of the pool,
    in page table order, AS STORED: ((B, span, KV*hd), same) in ``dtype``,
    head j in lanes [j*hd, (j+1)*hd). The fp tier gathers pages once, K and
    V lanes together (:func:`_gather_pages`, pages at ``layer*P +
    page_table``), and splits the copy on lanes; a quantized
    tier gathers codes and scales a page a slice, THEN dequantizes —
    elementwise per row, so exactly equal to dequantizing the whole pool
    first (the numerical-equivalence contract the lint layer executes).
    Trash-page rows come along under the caller's length mask."""
    tier = pool_tier(pool)
    if tier == "fp":
        return split_kv(_gather_pages(pool.kv, layer, page_table))
    kv = pool.k_scale.shape[-1]
    return tuple(
        _merge_heads(dequantize_kv_rows(
            _split_heads(_gather_pages(codes, layer, page_table), kv),
            _gather_pages(scales, layer, page_table), tier, dtype))
        for codes, scales in ((pool.k, pool.k_scale),
                              (pool.v, pool.v_scale)))


def ring_positions(lengths, entries: int, page_size: int):
    """The absolute position each row of a slot's ring holds, in table order:
    lengths (B,) counts a slot's positions INCLUDING the newest, ``t =
    lengths - 1``; ring entry j holds page ``q = T - ((T - j) mod entries)``
    of the stream, ``T = t // page_size`` — the latest page number congruent
    to j — and its row o position ``q * page_size + o``: (B, entries *
    page_size) int32. Negative where the stream has not reached the entry
    yet; past ``t`` in the newest page's rows that are not written yet (they
    still hold the page ``entries`` back, which has left every window)."""
    last_page = (lengths - 1) // page_size                       # (B,)
    j = jnp.arange(entries, dtype=jnp.int32)
    page = last_page[:, None] - (last_page[:, None] - j[None, :]) % entries
    pos = page[:, :, None] * page_size + jnp.arange(page_size,
                                                    dtype=jnp.int32)
    return pos.reshape(lengths.shape[0], entries * page_size)


def window_valid(pos, lengths, window: int):
    """Which ring rows the newest position ``t = lengths - 1`` attends: the
    ``window`` positions up to and including itself, by the ABSOLUTE position
    a row holds (``t - window < pos <= t``, ``pos >= 0``), never by where in
    the ring it lies."""
    t = lengths[:, None] - 1
    return (pos >= 0) & (pos <= t) & (pos > t - window)


def _group_lanes(q, kv: int):
    """Each query head in ITS KV group's lanes of a row-wide query, zero
    elsewhere: q (B, 1, H, hd) -> (own (H, KV) bool, head h's group; qz (B, H,
    KV*hd)). Head j*rep+g attends KV group j, as everywhere."""
    b, _, h, hd = q.shape
    own = (jnp.arange(h)[:, None] // (h // kv)) == jnp.arange(kv)[None, :]
    qz = jnp.where(own[None, :, :, None], q.reshape(b, h, 1, hd), 0)
    return own, qz.reshape(b, h, kv * hd)


def _own_lanes(out, own):
    """What a head keeps of a row-wide weighted sum, its own group's lanes:
    out (B, H, KV*hd) -> (B, 1, H, hd)."""
    b, h, _ = out.shape
    out = jnp.where(own[None, :, :, None], out.reshape(b, h, own.shape[1], -1),
                    0)
    return out.sum(axis=2).reshape(b, 1, h, -1)


def attend_rows(q, k_rows, v_rows, lengths, valid=None):
    """Single-position GQA attention against rows as the pool stores them:
    q (B, 1, H, hd); k_rows, v_rows (B, span, KV*hd), head j of a row in
    lanes [j*hd, (j+1)*hd); lengths (B,) valid positions a slot — or
    ``valid`` (B, span) bool, the rows attended, where they are not a prefix
    (a window layer's ring, :func:`window_valid`). Returns (B, 1, H, hd) in
    q's dtype; softmax in fp32.

    The rows are read AS THEY LIE: splitting the (KV*hd) lanes into
    (KV, hd) for ``decode_attention``'s per-group einsum is a real
    lane-splitting copy of every gathered K and V on the chip (100 MB read,
    200 written a layer at hd 64). Instead each query head is placed in ITS
    group's lanes of a (B, H, KV*hd) query that is zero elsewhere
    (:func:`_group_lanes`), so the scores are one dot over the whole row —
    exact, the added products are zeros — and PV yields every group's lanes
    for every head, of which a head keeps its own. KV-fold the MXU work of a
    step that is bound by the K/V read."""
    hd = q.shape[-1]
    own, qz = _group_lanes(q, k_rows.shape[-1] // hd)
    scores = jnp.einsum("bhD,bcD->bhc", qz, k_rows,
                        preferred_element_type=jnp.float32)
    scores = scores * (1.0 / np.sqrt(hd))
    if valid is None:
        # ragged: row i masks at its own lengths[i]
        valid = jnp.arange(k_rows.shape[1])[None, :] < lengths[:, None]
    scores = jnp.where(valid[:, None, :], scores, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhc,bcD->bhD", probs.astype(q.dtype), v_rows,
                     preferred_element_type=jnp.float32).astype(q.dtype)
    return _own_lanes(out, own)


#: the two decode reads of a layer whose pages hold a prefix, as
#: ``ContinuousBatcher.report()`` and ``chip_smoke.py`` name them
PAGE_WALK, PAGE_GATHER = "pallas page walk", "xla page gather"


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def decode_read_path(pool) -> str:
    """Which read a decode step's attention layer is built with, read off
    what it is handed: :data:`PAGE_WALK` (:func:`attend_pages`; a
    :class:`LatentPool`: :func:`attend_latent_pages`) for an fp pool on a
    TPU, where a page is whole tiles (a row's K lanes and its V lanes each
    whole lane tiles, a page of whole sublane tiles: what the kernel's page
    DMAs and lane slices need), whether its pages
    hold a prefix or a window layer's ring (the walk masks a ring's rows by
    the position each holds); :data:`PAGE_GATHER` (the oracle) for a
    quantized tier, part tiles and every other backend. ``pool`` may be
    whole, staged or one layer's. No width is gated out: on a v5e the walk
    is ahead at 128 to 1024 lanes (PERF.md §6 "PR 33", "PR 35"; a ring of 65
    or 129 pages: "PR 40")."""
    # (an IndexedPagePool: of its K/V leaf, the one a walk would fetch)
    if not isinstance(pool, FP_POOLS) or not _on_tpu():
        return PAGE_GATHER
    # what the kernel slices on lanes (a row's K part, its V part as wide,
    # or the whole latent row) and on sublanes (a page)
    return (PAGE_WALK if _whole_tiles(pool[0], _k_lanes(pool))
            else PAGE_GATHER)


def _whole_tiles(leaf, lanes: int) -> bool:
    """Whether a walk's kernel can slice ``leaf``: ``lanes`` whole lane
    tiles, a page whole sublane tiles of the leaf's dtype."""
    sublanes = 32 // jnp.dtype(leaf.dtype).itemsize
    return lanes % LANE_TILE == 0 and leaf.shape[-2] % sublanes == 0


def walk_geometry(pool, pages_per_slot: int) -> tuple:
    """(pages a block, pages a run) of the walk over a prefix's table of
    ``pages_per_slot`` entries into ``pool`` (whole, staged or one layer's).
    The block: the kernel's own rule for a K/V row, and TWICE that for a
    latent row, which is half as wide at the same lanes: the same VMEM in the
    two buffers. The run, the pages the kernel takes with ONE DMA where the
    groups that lead a block name adjacent pages: the rule on a page's bytes
    (``flash_attention.walk_run_pages``, the length of the runs
    :class:`PagedKVCache` hands out) cut to what divides a block."""
    leaf = pool[0]
    ppb = flash_attention.paged_walk_pages_per_block(
        leaf.shape[-2], _k_lanes(pool), leaf.dtype.itemsize)
    if isinstance(pool, LATENT_POOLS):
        ppb *= 2
    return ppb, math.gcd(ppb, leaf_run_pages(leaf, pages_per_slot))


def leaf_run_pages(leaf, pages_per_slot: int) -> int:
    """``flash_attention.walk_run_pages`` of ONE leaf (whole, staged or one
    layer's): the pages of it a walk would take with one DMA, by its own
    page's bytes."""
    return flash_attention.walk_run_pages(
        leaf.shape[-2] * leaf.shape[-1] * leaf.dtype.itemsize, pages_per_slot)


def pool_run_pages(pool, pages_per_slot: int) -> int:
    """The runs an fp pool's pages have to lie in for every walk of it: the
    longest any of its leaves asks for, which is its smallest page's
    (:func:`page_leaf_bytes` says the same of a configuration). A walk takes
    of them what ITS leaf's rule says (:func:`walk_geometry`,
    :func:`index_walk_geometry`): a run is table-aligned and a power of two,
    so a shorter one lies inside it."""
    return max(leaf_run_pages(leaf, pages_per_slot) for leaf in pool)


#: the read of a sparse layer's index keys that scores them where they lie,
#: as ``ContinuousBatcher.report()`` names it (``index_read``; the other is
#: :data:`PAGE_GATHER`)
INDEX_WALK = "pallas index page walk"


def index_read_path(pool) -> str:
    """Which read a sparse layer's decode takes of its slots' index keys,
    read off what it is handed as :func:`decode_read_path` reads the K/V
    read: :data:`INDEX_WALK` (``flash_attention.paged_index_walk``) for an
    fp :class:`IndexedPagePool` on a TPU whose index-key pages are whole
    tiles; :data:`PAGE_GATHER` (:func:`_gather_pages` +
    ``sparse_attn.index_scores``, the oracle) for part tiles and every other
    backend."""
    if not isinstance(pool, INDEXED_POOLS) or not _on_tpu():
        return PAGE_GATHER
    return (INDEX_WALK if _whole_tiles(pool.ik, pool.ik.shape[-1])
            else PAGE_GATHER)


def index_walk_geometry(pool: IndexedPagePool, pages_per_slot: int) -> tuple:
    """(pages a block, pages a run) of the index walk over a table of
    ``pages_per_slot`` entries, read off the index-key leaf as
    :func:`walk_geometry` reads the K/V walk's off its leaf: the ONE reading
    for the kernel's caller, its table of leading runs and the host's count."""
    ik = pool.ik
    ppb = flash_attention.index_walk_pages_per_block(
        ik.shape[-2], ik.shape[-1], ik.dtype.itemsize)
    return ppb, math.gcd(ppb, leaf_run_pages(ik, pages_per_slot))


def _walk_runs(pool, page_table, lead=None) -> dict:
    """What ``flash_attention.paged_decode_walk`` is told of a prefix's
    ``page_table`` into ``pool``: :func:`walk_geometry`'s block and run (the
    ONE reading of the rule off this leaf: the kernel, its table of leading
    runs and the host's counters of it all take it from there) and, where
    pages go in runs, that table (``flash_attention.leading_runs``), made
    here of the table's ids unless the caller brings it (``lead``)."""
    ppb, run = walk_geometry(pool, page_table.shape[1])
    if run == 1:
        lead = None
    elif lead is None:
        lead = flash_attention.leading_runs(page_table.astype(jnp.int32),
                                            run, ppb)
    return {"pages_per_block": ppb, "run_pages": run, "lead": lead}


def walk_lead(pool, page_table):
    """The page walk's table of leading runs over a prefix's ``page_table``
    into ``pool`` (None where a page goes alone or the read is the gather):
    made of the TABLE, which every layer of a step shares, and not of a
    layer's page ids. A step that SCANS its layers makes it once, before the
    scan, and hands it down (``lead``): made in the scan's body it is made
    again every layer, 6 µs a layer on a v5e, 0.15 ms of a 24-layer step (the
    compiler sinks it into the loop, it does not hoist it: PERF.md §6 "PR
    46"). A step that walks its layers one by one leaves it to the attends,
    whose equal expressions the compiler merges."""
    if decode_read_path(pool) != PAGE_WALK:
        return None
    return _walk_runs(pool, page_table)["lead"]


def attend_pages(q, pool: PagePool, layer, page_table, lengths,
                 window: int = 0, lead=None, keep=None):
    """:func:`read_span` + :func:`attend_rows` without the span: q (B, 1, H,
    hd) against each slot's LIVE pages of layer ``layer`` of an fp pool (L,
    P, ps, 2*KV*hd), read out of the pool where they lie by ONE kernel
    (``flash_attention.paged_decode_walk``: a DMA a page, keys and values
    together, ``ceil(lengths[i] / ps)`` pages a slot, one for an idle slot's
    trash page). The leaf goes in whole, viewed (L*P, ps, 2*KV*hd) — a
    bitcast of the carried pool — with the page ids ``layer*P +
    page_table``; the same query in its groups' lanes, float32 scores and
    softmax, and the same rows attended as :func:`attend_rows`, in a
    blockwise order of the float32 sums. Returns (B, 1, H, hd) in q's dtype.

    ``window`` (static, > 0): ``page_table`` is a window layer's ring, of
    which the kernel fetches the entries the stream has reached (all of them
    once the ring has turned) and attends the rows :func:`window_valid` says
    of :func:`ring_positions`, by two scalars a slot.

    ``keep`` (B, span) bool: of a slot's live positions, those the query
    attends (a sparse-attention layer's selection): the walk fetches every
    live page, and a row ``keep`` leaves out takes no part."""
    hd = q.shape[-1]
    own, qz = _group_lanes(q, pool.k_lanes // hd)
    ids = (layer * pool.num_pages + page_table).astype(jnp.int32)
    out = flash_attention.paged_decode_walk(
        qz, _pages(pool.kv, 1), ids, lengths.astype(jnp.int32),
        scale=float(1.0 / np.sqrt(hd)), window=window,
        # (a ring's pages are a fetch each in every configuration that has
        # rings; the kernel reads the rule off the operand for itself)
        **({} if window else _walk_runs(pool, page_table, lead)),
        **({} if keep is None else {"keep": keep}))
    return _own_lanes(out, own)


def attend_latent(q_rows, rows, lengths, head_dim: int, valid=None):
    """Single-position multi-query attention of H heads over latent rows as
    the pool stores them (the ABSORBED form, ``models/mla.py``): q_rows (B,
    H, lanes) from ``mla.absorb_query``; rows (B, span, lanes), the SAME
    array keys and values (a row's latent lanes are both); lengths (B,)
    valid positions a slot, or ``valid`` (B, span) bool, the rows attended,
    where they are not a prefix (a window layer's ring or band). Returns the
    weighted sums of rows (B, H, lanes)
    in q's dtype, which ``mla.unabsorb`` takes; scores times
    ``head_dim^-1/2`` (the query / key head's width, what the expanded form
    divides by), softmax in fp32. No (B, span, H, ...) tensor exists: the
    widest are the (B, H, span) scores."""
    scores = jnp.einsum("bhD,bcD->bhc", q_rows, rows,
                        preferred_element_type=jnp.float32)
    scores = scores * (1.0 / np.sqrt(head_dim))
    if valid is None:
        valid = jnp.arange(rows.shape[1])[None, :] < lengths[:, None]
    scores = jnp.where(valid[:, None, :], scores, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhc,bcD->bhD", probs.astype(q_rows.dtype), rows,
                      preferred_element_type=jnp.float32
                      ).astype(q_rows.dtype)


@jax.named_scope("attn.latent")
def _attention_decode_latent(cfg: ModelConfig, lp: dict, x, cos_b, sin_b,
                             pool: LatentPool, layer, page_table, lengths):
    """A latent-attention layer of the ragged step, ABSORBED: x (B, D)
    normalised; project and rotate each slot at ITS position, write its new
    row into its current page (``paged_kv.write``), attend each slot's rows
    as they lie in its pages (:func:`latent_decode_attention`), then the V
    half of ``W_kvb`` and ``W_o``. Returns (out (B, D), pool)."""
    geo = cfg.latent_geometry("latent_attention")
    q_nope, q_rope, row = mla.project(cfg, geo, lp, x,
                                      mla.rotate_rows(cos_b, sin_b),
                                      mla.query_scale(cfg, geo, lengths))
    pool = write_rows(pool, layer, page_table, lengths, row[:, None], None)
    q_rows = mla.absorb_query(geo, lp, q_nope, q_rope)
    ctx = latent_decode_attention(q_rows, pool, layer, page_table,
                                  lengths + 1, cfg.head_dim)
    return mla.unabsorb(geo, lp, ctx), pool


def paged_decode_attention(q, pool, layer, page_table, lengths,
                           window: int = 0, lead=None):
    """Ragged single-position attention against layer ``layer`` of a pool:
    q (B, 1, H, hd) per slot; page_table (B, pages_per_slot) int32 names each
    slot's pages in logical order (0 = the trash page for unallocated tails);
    lengths (B,) int32 counts each slot's valid positions INCLUDING the one
    this step wrote. Returns (B, 1, H, hd) in q's dtype; softmax in fp32.

    Where :func:`decode_read_path` says so, one kernel that walks each
    slot's live pages (:func:`attend_pages`). Otherwise one XLA page gather
    (:func:`read_span`) and :func:`attend_rows` over its output as it lies:
    trash-page garbage lands only in masked positions, where softmax of
    ``finfo.min`` contributes exactly 0.

    ``window`` (static, > 0): ``page_table`` is a window layer's ring
    (:func:`write_rows`) and a row is attended by the position it holds, on
    either read: the walk takes the ring as it takes a prefix's table.
    ``lead``: :func:`walk_lead` of the pool and the table, where the caller
    has made it already (a step that scans its layers)."""
    s1, h, hd = q.shape[1:]
    if s1 != 1:
        raise ValueError(f"paged decode is q_len=1 only, got q_len={s1}")
    tier = pool_tier(pool)
    lanes = KV_PAGE_CODECS[tier].code_lanes(hd)
    kv, rest = divmod(_k_lanes(pool), lanes)
    if rest or (tier != "fp" and kv != pool.k_scale.shape[-1]):
        raise ValueError(f"row width {_k_lanes(pool)} does not match q "
                         f"head_dim {hd} for tier {tier!r}")
    if h % kv:
        raise ValueError(f"ragged GQA: H={h}, KV={kv}")
    if decode_read_path(pool) == PAGE_WALK:
        return attend_pages(q, pool, layer, page_table, lengths, window,
                            lead)
    kg, vg = read_span(pool, layer, page_table, q.dtype)
    if not window:
        return attend_rows(q, kg, vg, lengths)
    return attend_rows(q, kg, vg, lengths, window_valid(
        ring_positions(lengths, page_table.shape[1], pool.page_size),
        lengths, window))


def _decode_paged(cfg: ModelConfig, lp: dict, x: jnp.ndarray, cos_b, sin_b,
                  pool, layer, page_table, lengths,
                  tp_axis: Optional[str] = None, window: int = 0,
                  write_table=None, lead=None):
    """The paged twin of ``transformer._attention_decode`` (``window`` static,
    > 0: a sliding layer over its ring; see the two scoped entries below):
    project the (B, 1, D) hidden, rotate each slot at ITS position (``cos_b``
    None: a position-free layer, nothing is rotated), write the new K/V row
    into each slot's current page, then ragged-attend against the slot's
    pages. ``pool`` is the WHOLE (L, num_pages, page_size, ...) pool, at
    whichever tier, and ``layer`` the index this layer's rows and pages are
    addressed under; on a quantized tier the current token attends its OWN
    quantized K/V, consistent with what every later step will read. What the
    layer's leaves add: :func:`head_norms`, :func:`gated`, :func:`post_norm`.
    ``write_table`` (None: ``page_table``; a Python-level default): the table
    the new row is WRITTEN through, where a caller must keep some slots' rows
    out of their pages (the split runtime's dead unroll iterations and padding
    layers: the trash page) while the read still gathers the real ones.
    ``lead``: :func:`paged_decode_attention`'s."""
    b, s1, d = x.shape
    hd = cfg.head_dim
    h, kv = lp["wq"].shape[-1] // hd, lp["wk"].shape[-1] // hd
    q = (x @ lp["wq"]).reshape(b, s1, h, hd)
    k = (x @ lp["wk"]).reshape(b, s1, kv, hd)
    v = (x @ lp["wv"]).reshape(b, s1, kv, hd)
    if "bq" in lp:
        q = q + lp["bq"].reshape(h, hd)
        k = k + lp["bk"].reshape(kv, hd)
        v = v + lp["bv"].reshape(kv, hd)
    q, k = head_norms(cfg, lp, q, k)
    if cos_b is None:  # a position-free layer: no table was handed in
        q = q * jnp.asarray(cfg.q_prescale, q.dtype)
    else:
        q = _apply_rotary_rows(q, cos_b, sin_b, cfg.rotary_dim)
        k = _apply_rotary_rows(k, cos_b, sin_b, cfg.rotary_dim)
    write_table = page_table if write_table is None else write_table
    pool = (write_rows(pool, layer, write_table, lengths, k, v, ring=True)
            if window else write_rows(pool, layer, write_table, lengths, k, v))
    out = paged_decode_attention(q, pool, layer, page_table, lengths + 1,
                                 window, lead)
    out = gated(lp, x, out.reshape(b, s1, h * hd)) @ lp["wo"]
    if tp_axis is not None:
        out = jax.lax.psum(out, tp_axis)
    if "bo" in lp:
        out = out + lp["bo"]
    return post_norm(cfg, lp, out), pool


@jax.named_scope("attn.decode")
def _attention_decode_paged(cfg: ModelConfig, lp: dict, x: jnp.ndarray,
                            cos_b, sin_b, pool, layer, page_table, lengths,
                            tp_axis: Optional[str] = None, write_table=None,
                            lead=None):
    """:func:`_decode_paged` for a layer whose pages hold every position."""
    return _decode_paged(cfg, lp, x, cos_b, sin_b, pool, layer, page_table,
                         lengths, tp_axis, write_table=write_table, lead=lead)


@jax.named_scope("attn.window")
def _attention_decode_window(cfg: ModelConfig, lp: dict, x: jnp.ndarray,
                             cos_b, sin_b, pool, layer, window_table,
                             lengths):
    """:func:`_decode_paged` for a sliding-window layer: ``pool`` and
    ``window_table`` are the window group's, the table a ring."""
    return _decode_paged(cfg, lp, x, cos_b, sin_b, pool, layer, window_table,
                         lengths, window=cfg.sliding_window)


def block_decode_paged(cfg: ModelConfig, lp: dict, hidden: jnp.ndarray,
                       cos_b, sin_b, pool, layer, page_table, lengths,
                       tp_axis: Optional[str] = None, write_table=None,
                       lead=None):
    """The paged twin of ``transformer.block_decode`` for one layer:
    same norm/residual/MLP structure, paged attention core over layer
    ``layer`` of the whole pool (``write_table``, ``lead``:
    :func:`_decode_paged`)."""
    if cfg.family == "gpt_neox":
        attn_in = _layernorm(hidden, lp["ln1_scale"], lp["ln1_bias"],
                             cfg.norm_eps)
        attn_out, pool = _attention_decode_paged(
            cfg, lp, attn_in, cos_b, sin_b, pool, layer, page_table, lengths,
            tp_axis, write_table, lead)
        mlp_in = _layernorm(hidden, lp["ln2_scale"], lp["ln2_bias"],
                            cfg.norm_eps)
        return hidden + attn_out + mlp(cfg, lp, mlp_in, tp_axis), pool
    attn_in = _rmsnorm(hidden, lp["ln1_scale"], cfg.norm_eps)
    attn_out, pool = _attention_decode_paged(
        cfg, lp, attn_in, cos_b, sin_b, pool, layer, page_table, lengths,
        tp_axis, write_table, lead)
    hidden = hidden + attn_out
    mlp_in = _rmsnorm(hidden, lp["ln2_scale"], cfg.norm_eps)
    return hidden + mlp(cfg, lp, mlp_in, tp_axis), pool


@graph_contract("paged.decode_step", collectives={},
                donate=lambda ctx: ctx.get("donate_min", 1))
@graph_contract("paged.decode_step_quant", collectives={},
                donate=lambda ctx: ctx.get("donate_min", 4))
def paged_decode_step(cfg: ModelConfig, params: dict, pool,
                      page_table: jnp.ndarray, lengths: jnp.ndarray,
                      token_ids: jnp.ndarray, *,
                      compute_dtype: Optional[jnp.dtype] = None):
    """Append one position to EVERY slot of a paged pool in one pass.

    pool: a :class:`PagePool` or :class:`QuantPagePool` with leading axis
    (L,); page_table (max_slots, pages_per_slot) and lengths (max_slots,)
    are TRACED — one executable per pool geometry and tier serves every
    admit/evict/fill state. token_ids: (max_slots,) int32 (inactive slots
    pass any valid token; their writes land in the trash page). Returns
    (logits (max_slots, V) fp32, pool).

    Per-slot positions: the RoPE row, the page write offset, and the
    attention mask all index by each slot's own ``lengths[i]`` — the ragged
    generalization of ``decode_step``'s single ``cache.length``; per-slot
    math is bit-identical to the contiguous path (see module docstring).
    The layer scan CARRIES the pool pytree (the fp tier's one leaf kv; a
    quantized tier's k, v, k_scale, v_scale) beside the hidden state and
    scans the layer index:
    the donated pool is updated where it lies, layer after layer, and is
    never sliced into ``xs`` nor stacked back out of ``ys`` (which cost a
    relayout of every layer's slice in and out, PERF.md §6 "PR 29").
    """
    params = _cast_params(params, compute_dtype)
    if token_ids.ndim == 1:
        token_ids = token_ids[:, None]
    hidden = embed(params, token_ids)  # (B, 1, D)
    span = page_table.shape[1] * pool.page_size  # pages_per_slot * page_size
    cos, sin = precompute_rope(cfg, span)
    cos_b = cos[lengths]  # (B, rot) — each slot's own row
    sin_b = sin[lengths]

    lead = walk_lead(pool, page_table)    # once a step, not once a layer

    def body(carry, xs):
        (h, pool), (lp, layer) = carry, xs
        return block_decode_paged(cfg, lp, h, cos_b, sin_b, pool, layer,
                                  page_table, lengths, lead=lead), None

    layers = jnp.arange(pool[0].shape[0], dtype=jnp.int32)
    (hidden, pool), _ = jax.lax.scan(body, (hidden, pool),
                                     (params["layers"], layers))
    with jax.named_scope("unembed_sample"):
        logits = unembed(cfg, params, hidden)[:, -1]  # (B, V) fp32
    return logits, pool


# -- the latent layers' decode read ------------------------------------------
# Below the K/V step, so that no line of it moves: a Pallas kernel's body
# carries its call stack's line numbers into the compile-cache key (PERF.md
# §6 "PR 32"), and the five cells that hold no latent row keep theirs.

def attend_latent_pages(q_rows, pool: LatentPool, layer, page_table, lengths,
                        head_dim: int, keep=None):
    """:func:`_gather_pages` + :func:`attend_latent` without the span: q_rows
    (B, H, lanes) against each slot's LIVE pages of layer ``layer`` of the
    one-leaf pool, read where they lie by the kernel :func:`attend_pages`
    hands a K/V pool's leaf (``flash_attention.paged_decode_walk``; a row as
    wide as the query is key and value both, a DMA a page). The leaf goes
    in whole, viewed (L*P, ps, lanes) — a bitcast of the carried pool — with
    the page ids ``layer*P + page_table``; the same rows attended, float32
    scores and softmax, probabilities in q's dtype before the weighted sum,
    in a blockwise order of the float32 sums. Returns (B, H, lanes).

    A block holds TWICE the pages of a K/V walk's, whose row of the same
    lanes is twice as wide: the same VMEM in the two buffers, 64 page DMAs in
    flight at 16-row pages. On a v5e at the mistral4 cell's shape 256 / 512 /
    1024 / 1536 / 2048 rows a block take 1.86 / 1.40 / 1.25 / 1.21 / 1.23
    ms a layer (an all-idle batch 0.076 at 512, 0.101 at 1024, 0.157 at
    2048; PERF.md §6 "PR 35"). ``keep``: :func:`attend_pages`'s, a sparse
    latent layer's selection as a mask on the walk's rows."""
    pages = _pages(pool.rows, 1)
    ids = (layer * pool.num_pages + page_table).astype(jnp.int32)
    return flash_attention.paged_decode_walk(
        q_rows, pages, ids, lengths.astype(jnp.int32),
        scale=float(1.0 / np.sqrt(head_dim)),
        **_walk_runs(pool, page_table),
        **({} if keep is None else {"keep": keep}))


def latent_decode_attention(q_rows, pool: LatentPool, layer, page_table,
                            lengths, head_dim: int):
    """:func:`paged_decode_attention` for latent rows: the absorbed query
    ``q_rows`` (B, H, lanes) of every slot against the rows of its pages of
    layer ``layer``, ``lengths`` (B,) counting the one this step wrote; the
    weighted sums of rows (B, H, lanes) in q's dtype. Where
    :func:`decode_read_path` says so, the page walk; otherwise one page
    gather and :func:`attend_latent` over its output as it lies."""
    if decode_read_path(pool) == PAGE_WALK:
        return attend_latent_pages(q_rows, pool, layer, page_table, lengths,
                                   head_dim)
    return attend_latent(q_rows, _gather_pages(pool.rows, layer, page_table),
                         lengths, head_dim)


# -- what a layer's own leaves add around the attend (down here: the lines
# above keep their numbers, PERF.md section 6 "PR 32") ------------------------

def head_norms(cfg: ModelConfig, lp: dict, q, k):
    """q (..., H, hd), k (..., KV, hd) normed over each head's lanes where
    the layer holds ``q_norm`` / ``k_norm`` (hd,): before any rotation."""
    if "q_norm" not in lp:
        return q, k
    return (_rmsnorm(q, lp["q_norm"], cfg.norm_eps),
            _rmsnorm(k, lp["k_norm"], cfg.norm_eps))


def gated(lp: dict, x, ctx):
    """The attend's output ctx (..., H*hd) times ``sigmoid(x W_g)`` where the
    layer holds an output gate ``wg``: ahead of ``W_o``. ``wg`` (D, H*hd): a
    gate a lane; (D, H): a gate a HEAD, broadcast over the head's lanes."""
    if "wg" not in lp:
        return ctx
    gate = jax.nn.sigmoid(x @ lp["wg"])
    if gate.shape[-1] != ctx.shape[-1]:
        heads = gate.shape[-1]
        return (ctx.reshape(*ctx.shape[:-1], heads, -1)
                * gate[..., None]).reshape(ctx.shape)
    return ctx * gate


def post_norm(cfg: ModelConfig, lp: dict, out):
    """The sublayer's output normed where the layer holds ``post_scale``
    (D,): before it joins the residual stream."""
    if "post_scale" not in lp:
        return out
    return _rmsnorm(out, lp["post_scale"], cfg.norm_eps)


# -- a window layer whose ring holds LATENT rows (down here: the lines above
# keep their numbers) ---------------------------------------------------------

def latent_ring_attention(q_rows, pool: LatentPool, layer, window_table,
                          lengths, head_dim: int, window: int):
    """:func:`latent_decode_attention` for a RING of latent rows: the
    absorbed query ``q_rows`` (B, H, lanes) of every slot against the rows of
    its ring of layer ``layer`` that hold positions ``(t - window, t]``, ``t
    = lengths - 1`` (``lengths`` counts the one this step wrote); the
    weighted sums of rows (B, H, lanes). Where :func:`decode_read_path` says
    so of the ring's pool, the page walk under ``window=`` (a row is key and
    value both, masked by the position it holds inside the kernel: the two
    things ``flash_attention.paged_decode_walk`` took one at a time before);
    otherwise one page gather of the rings and :func:`attend_latent` under
    :func:`window_valid`."""
    if decode_read_path(pool) == PAGE_WALK:
        ids = (layer * pool.num_pages + window_table).astype(jnp.int32)
        return flash_attention.paged_decode_walk(
            q_rows, _pages(pool.rows, 1), ids, lengths.astype(jnp.int32),
            scale=float(1.0 / np.sqrt(head_dim)), window=window)
    return attend_latent(
        q_rows, _gather_pages(pool.rows, layer, window_table), lengths,
        head_dim, window_valid(
            ring_positions(lengths, window_table.shape[1], pool.page_size),
            lengths, window))


@jax.named_scope("attn.window_latent")
def _attention_decode_window_latent(cfg: ModelConfig, lp: dict, x, cos_b,
                                    sin_b, pool: LatentPool, layer,
                                    window_table, lengths):
    """A window layer of latent rows in the ragged step, ABSORBED: x (B, D)
    normalised; project and rotate each slot at ITS position by the window
    kind's own sizes and table (``cfg.latent_geometry``), write its new row
    into its ring (the scope ``attn.window_latent.write``: the ring's write
    is this layer's own, not the growing pool's ``paged_kv.write``), attend
    the ring's rows inside the band (:func:`latent_ring_attention`), then the
    V half of ``W_kvb``, the heads' gate and ``W_o``. Returns (out (B, D),
    pool)."""
    geo = cfg.latent_geometry("sliding_latent_attention")
    q_nope, q_rope, row = mla.project(cfg, geo, lp, x,
                                      mla.rotate_rows(cos_b, sin_b),
                                      mla.query_scale(cfg, geo, lengths))
    with jax.named_scope("attn.window_latent.write"):
        pool = write_rows.__wrapped__(pool, layer, window_table, lengths,
                                      row[:, None], None, ring=True)
    q_rows = mla.absorb_query(geo, lp, q_nope, q_rope)
    ctx = latent_ring_attention(q_rows, pool, layer, window_table,
                                lengths + 1, geo.head_dim,
                                cfg.sliding_window)
    return mla.unabsorb(geo, lp, ctx, mla.head_gate(lp, x)), pool
