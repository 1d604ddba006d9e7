"""Model architecture configs for the supported causal-LM families.

The reference hardcodes two HuggingFace checkpoints — ``EleutherAI/pythia-70m``
(``/root/reference/Experiments/Pythia-70M/pythia_model.py:25``) and
``Qwen/Qwen2-0.5B`` (``Experiments/Qwen2-0.5B/qwen_layer_wise.py:17``).  Here the
architecture is an explicit config so any GPT-NeoX- or Qwen2-family size runs,
including the Qwen2-1.5B 3-hop target (BASELINE.json configs[4]) and tiny
randomly-initialized variants used by the test suite (the environment has no
network access to pull pretrained weights).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional


#: lanes of one tile of the chip's minor dimension: what a stored latent row
#: is padded to whole multiples of (``ModelConfig.kv_row_lanes``)
LANE_TILE = 128


@dataclasses.dataclass(frozen=True)
class LatentGeometry:
    """The sizes of ONE kind of latent-attention layer, what ``models/mla.py``
    is handed for the kind it serves (``ModelConfig.latent_geometry``): a
    stack may hold two kinds of latent layer at different sizes. The names
    are ``ModelConfig``'s flat fields', which stay the full kind's."""

    num_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    rope_theta: float

    @property
    def head_dim(self) -> int:
        """A query / key head's lanes: what the scores are scaled by."""
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def kv_row_lanes(self) -> int:
        """Lanes of the kind's ONE stored row a position, ``[c | k_rope]``
        zero-padded to whole 128-lane tiles (``ModelConfig.kv_row_lanes``)."""
        return -(-(self.kv_lora_rank + self.qk_rope_head_dim)
                 // LANE_TILE) * LANE_TILE


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    """Architecture hyperparameters for one causal LM.

    ``family`` selects the block wiring:
      - ``"gpt_neox"``: parallel-residual blocks, LayerNorm (+bias), fused GELU MLP,
        partial rotary (``rotary_pct``), biases on all linears. Pythia models.
      - ``"qwen2"``: sequential-residual blocks, RMSNorm, SwiGLU MLP, full rotary,
        QKV biases but bias-free o/gate/up/down projections, grouped-query attention.
      - ``"llama"``: identical wiring to qwen2 with no biases anywhere
        (Llama-2/3 models; beyond the reference's two families).
      - ``"granitemoehybrid"``: two kinds of layer in one stack
        (``layer_types``: Mamba-2 mixers and position-free GQA attention),
        every layer's feed-forward a routed expert layer plus a shared
        expert, four scalar multipliers (IBM Granite 4.0-H). The serving
        path only; see ``models/hybrid.py``.
      - ``"mellum"``: the same walk over ``layer_types`` with a third kind,
        ``"sliding_attention"`` (a banded GQA layer that keeps a ring of
        pages) beside ``"attention"`` (full causal, HF's ``full_attention``);
        an explicit head width (``H x hd != hidden_size``), RoPE with YaRN
        on the full layers only, every feed-forward a routed expert layer
        with no shared expert, an untied head (JetBrains Mellum 2).
      - ``"mistral4"``: the same walk with a fourth kind,
        ``"latent_attention"`` (MLA): queries through a low-rank bottleneck,
        keys and values rebuilt per head from ONE cached latent row a
        position (``kv_lora_rank`` normalised lanes + ``qk_rope_head_dim``
        rotated lanes shared by all heads), interleaved rotary pairs on the
        rope lanes only, YaRN with its softmax ``mscale`` and a per-position
        query scale; routed experts plus a shared one on every layer, an
        untied head (Mistral Small 4).
      - ``"afmoe"``: mellum's two kinds (``"sliding_attention"`` rings
        beside ``"attention"``) with positions BY KIND the other way round
        (the window layers rotate, plain RoPE; the full layers take none:
        ``position_free``), a per-head RMSNorm on q and k ahead of the
        rotation, a sigmoid gate on the attend's output ahead of ``W_o``,
        a norm after each sublayer as well as before it, the first
        ``num_dense_layers`` feed-forwards a dense SwiGLU of
        ``intermediate_size`` and the rest routed experts plus a shared
        one, routed by ``score_func`` ``"sigmoid"`` (a per-expert selection
        bias, ``route_scale``), ``h0 = embed * sqrt(d)``, an untied head
        (Arcee Trinity).
      - ``"longcat_flash"``: a layer of two (``sublayers``) latent-attention
        sublayers, each followed by a dense SwiGLU of ``intermediate_size``,
        with ONE routed layer on a shortcut: it reads the first dense
        SwiGLU's normalised input and its result joins the residual stream
        at the layer's end (``layer_types`` lists the SUBLAYERS, two a
        published layer). The router scores by the softmax over ALL its
        outputs (``score_func`` ``"softmax_all"``: a selection bias, weights
        the scores as they are times ``route_scale``), ``num_experts`` routed
        experts and, after them, ``zero_experts`` identity experts that hold
        no weights (``E(u) = u``); the latent query and the normalised latent
        are multiplied by ``sqrt(hidden / rank)`` (``rank_scales``); plain
        RoPE, an untied head (Meituan LongCat-Flash).
      - ``"lfm2_moe"``: granite's walk with another recurrent kind,
        ``"conv"`` (``models/shortconv.py``: a gated depthwise convolution of
        ``conv_window`` taps whose whole memory of a sequence is the last
        ``conv_window - 1`` rows of a product, no matrix state), beside
        ``"attention"`` layers that ROTATE (plain RoPE) after a per-head
        RMSNorm on q and k; the first ``num_dense_layers`` feed-forwards a
        dense SwiGLU, the rest routed by ``"sigmoid"`` scores with a
        selection bias and no shared expert, the weights normalised over
        ``+ 1e-6`` (``route_norm_eps``); a tied head (LiquidAI LFM2).
      - ``"keye_vl2"``: one kind, ``"sparse_attention"``
        (``models/sparse_attn.py``): a rotated, q/k-normed GQA layer whose
        query attends the ``index_topk`` positions a learned INDEXER scores
        highest (``index_heads`` query heads of ``index_head_dim`` against
        ONE index key a position, which the page pool keeps in a second leaf
        beside the K/V rows), every position while there are no more than
        that; every feed-forward routed by the softmax over the chosen
        logits, no shared expert, an untied head (Kwai Keye-VL 2.0's
        language model).
      - ``"deepseek_v32"``: one kind, ``"sparse_latent_attention"``
        (``models/sparse_mla.py``): mistral4's latent layer (one cached row
        a position, interleaved rotary pairs on the rope lanes, YaRN with
        its softmax ``mscale``) whose query attends the ``index_topk``
        positions keye's INDEXER selects: here the indexer's query comes
        from the q latent ``c_q`` and only the first ``qk_rope_head_dim``
        lanes of its heads and key rotate, by the attention's table; the
        pool keeps the latent row and the index key in two leaves under one
        table. The first ``num_dense_layers`` feed-forwards a dense SwiGLU,
        the rest routed by ``"sigmoid"`` scores with a selection bias, the
        experts in ``route_groups`` groups of which a token may choose
        within the ``route_groups_kept`` best, plus a shared expert; an
        untied head (DeepSeek-V3.2-Exp).
      - ``"dots3_note"``: two kinds of latent layer in one stack.
        ``"sparse_latent_attention"`` layers as deepseek_v32's (the flat
        latent fields, plain RoPE by ``rope_theta``) beside
        ``"sliding_latent_attention"`` layers: plain latent attention at
        sizes OF THEIR OWN (``window_latent``: heads, ranks, head widths and
        a theta that all differ), no indexer, banded over
        ``sliding_window`` keys, whose cached latent rows live in a RING of
        pages (the window group, ``window_row_lanes`` wide). Both kinds
        multiply their latents by the rank factors (``rank_scales``, each at
        its own ranks) and gate every head's output by ``sigmoid(x W_g)``
        ahead of ``W_o`` (``head_gate``: one gate lane a head). A leading
        dense layer, then ``"sigmoid"`` routing with a selection bias in one
        group plus a shared expert; an untied head (dots3-note-prev's
        language model).

    The fields after ``rope_scaling`` exist for those nine families and
    default to "absent", so the three one-block families hash and trace as
    before.
    """

    family: str
    vocab_size: int
    hidden_size: int
    num_layers: int
    num_heads: int
    num_kv_heads: int
    intermediate_size: int
    max_position_embeddings: int
    norm_eps: float
    rope_theta: float = 10000.0
    rotary_pct: float = 1.0
    tie_word_embeddings: bool = False
    #: RoPE frequency rescaling, or None for vanilla RoPE. Tuple forms,
    #: hashable for the frozen config: ("llama3", factor, low_freq_factor,
    #: high_freq_factor, original_max_position_embeddings) or ("yarn",
    #: factor, original_max_position_embeddings, beta_fast, beta_slow,
    #: attention_factor). In a stack with window layers it is the FULL
    #: layers' table; the window layers rotate by the plain one (Mellum's
    #: ``rope_parameters`` by layer kind).
    rope_scaling: Optional[tuple] = None
    #: per-layer mixer kind, ``"mamba"``, ``"conv"``, ``"attention"``,
    #: ``"sliding_attention"``, ``"latent_attention"``, ``"sparse_attention"``,
    #: ``"sparse_latent_attention"`` or ``"sliding_latent_attention"``; empty
    #: = every layer is the family's one block
    layer_types: tuple = ()
    #: width of one attention head where the model states it; 0 = the
    #: derived ``hidden_size // num_heads``
    explicit_head_dim: int = 0
    #: keys a ``"sliding_attention"`` (or ``"sliding_latent_attention"``)
    #: layer attends, itself among them:
    #: position i sees j with ``i - sliding_window < j <= i``
    sliding_window: int = 0
    #: routed experts: ``num_experts`` is the ROUTER's width (the published
    #: count), ``experts_held`` how many of them this chip computes, starting
    #: at ``expert_offset`` (expert parallelism: the rest live elsewhere and
    #: nothing here stands in for them). 0 held = all of them.
    num_experts: int = 0
    experts_per_tok: int = 0
    expert_width: int = 0
    shared_width: int = 0
    experts_held: int = 0
    expert_offset: int = 0
    #: Mamba-2 mixer: heads x head width = d_inner; B/C are ``n_groups`` x
    #: ``d_state``; prefill runs in chunks of ``mamba_chunk`` positions
    mamba_heads: int = 0
    mamba_head_dim: int = 0
    mamba_d_state: int = 0
    mamba_d_conv: int = 0
    mamba_n_groups: int = 1
    mamba_chunk: int = 256
    #: Granite's scalars: h0 = embed * embedding_multiplier; every sublayer's
    #: output * residual_multiplier; logits / logits_scaling; attention
    #: scores * attention_multiplier (None = 1/sqrt(head_dim))
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0
    attention_multiplier: Optional[float] = None
    #: no positional encoding of any kind in the attention layers
    nope: bool = False
    #: latent attention: the query bottleneck, the cached latent's width, the
    #: rotated lanes of a head (of ``head_dim``, the rest are position-free)
    #: and a value head's width
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    #: scores are multiplied by ``softmax_mscale ** 2`` (YaRN's ``0.1 *
    #: mscale_all_dim * ln(factor) + 1``) and by ``1 + query_scale_beta *
    #: ln(1 + floor(pos / original_max_position_embeddings))`` at query
    #: position ``pos``
    softmax_mscale: float = 1.0
    query_scale_beta: float = 0.0
    #: the feed-forward kind by layer: the first ``num_dense_layers`` are a
    #: dense SwiGLU of ``intermediate_size``, the rest routed expert layers
    num_dense_layers: int = 0
    #: the router's scores (``models/moe.route``): ``"softmax"`` over the
    #: chosen logits, or ``"sigmoid"`` of every logit, the top-k taken of
    #: score + a per-expert selection bias, the weights the chosen scores
    #: alone, normalised to sum 1, times ``route_scale``; or
    #: ``"softmax_all"``, the softmax over EVERY output, chosen the same way,
    #: the weights the chosen scores as they are times ``route_scale``
    score_func: str = "softmax"
    route_scale: float = 1.0
    #: identity experts: router outputs ``[num_experts, num_experts +
    #: zero_experts)`` that hold no weights and hand their input back
    #: (``models/moe.py``); they belong to no chip's share, so every token's
    #: are computed where the token is
    zero_experts: int = 0
    #: a latent layer's whole query times ``sqrt(hidden_size / q_lora_rank)``
    #: and its normalised latent times ``sqrt(hidden_size / kv_lora_rank)``
    #: where the row is made (``models/mla.py``)
    rank_scales: bool = False
    #: taps of a ``"conv"`` layer's depthwise causal convolution (HF's
    #: ``conv_L_cache``): a sequence keeps ``conv_window - 1`` rows a layer
    conv_window: int = 0
    #: a ``"sparse_attention"`` layer's indexer: ``index_heads`` query heads
    #: of ``index_head_dim`` lanes score ONE index key a position, and the
    #: layer's query attends the ``index_topk`` highest (HF's ``sa_config``)
    index_heads: int = 0
    index_head_dim: int = 0
    index_topk: int = 0
    #: how the published rotation splits its ``rotary_dim / 2`` frequencies
    #: among three position streams (HF's ``rope_scaling.mrope_section``);
    #: text positions are equal in the three, so the program rotates by the
    #: plain table and only checks the sum
    mrope_section: tuple = ()
    #: group-limited routing (``models/moe.route``): the router's outputs in
    #: ``route_groups`` equal groups, ranked by the sum of their two largest
    #: biased scores; a token's experts are the top k within the
    #: ``route_groups_kept`` best groups (HF's ``n_group`` / ``topk_group``).
    #: 1 and 1: every expert stands, and nothing of it is traced
    route_groups: int = 1
    route_groups_kept: int = 1
    #: the sizes of the ``"sliding_latent_attention"`` layers, which are not
    #: the flat latent fields' (those stay the full kind's); None: no such
    #: layer
    window_latent: Optional[LatentGeometry] = None
    #: every latent layer holds an output gate ``wg`` (D, heads): a head's
    #: attend output times ``sigmoid(x W_g)_h`` ahead of ``W_o``
    head_gate: bool = False

    @property
    def head_dim(self) -> int:
        return self.explicit_head_dim or self.hidden_size // self.num_heads

    @property
    def qk_nope_head_dim(self) -> int:
        """A latent layer's position-free lanes of a query / key head."""
        return self.head_dim - self.qk_rope_head_dim

    @property
    def latent_layers(self) -> int:
        """Layers whose cached row is a latent (one row a position for all
        heads), not per-head K and V, IN THE POOL THAT GROWS with the
        stream: what says the main page group's pool is a latent one. The
        layers whose latent rows live in a ring: ``window_latent_layers``."""
        return sum(1 for t in self.layer_types
                   if t in ("latent_attention", "sparse_latent_attention"))

    @property
    def window_latent_layers(self) -> int:
        """Layers whose cached row is a latent kept in a RING of pages (the
        window group's): counted by ``window_layers`` too."""
        return sum(1 for t in self.layer_types
                   if t == "sliding_latent_attention")

    def latent_geometry(self, kind: str) -> LatentGeometry:
        """The sizes of the latent layers of ``kind``: what ``models/mla.py``
        asks in place of the flat fields. The window kind's are
        ``window_latent``; every other latent kind's the flat fields."""
        if kind == "sliding_latent_attention":
            return self.window_latent
        return LatentGeometry(
            self.num_heads, self.q_lora_rank, self.kv_lora_rank,
            self.qk_nope_head_dim, self.qk_rope_head_dim, self.v_head_dim,
            self.rope_theta)

    def rank_scale(self, rank: int) -> float:
        """What a latent of ``rank`` lanes is multiplied by under
        ``rank_scales``: ``sqrt(hidden_size / rank)``; else 1."""
        return math.sqrt(self.hidden_size / rank) if self.rank_scales else 1.0

    @property
    def sparse_layers(self) -> int:
        """Layers whose query attends the positions an indexer selects, and
        which keep an index key a position beside the K/V (or latent) row."""
        return sum(1 for t in self.layer_types
                   if t in ("sparse_attention", "sparse_latent_attention"))

    @property
    def index_rope_lanes(self) -> int:
        """The LEADING lanes of an indexer head (and of the index key) that
        rotate: all of them by a table of their own
        (``sparse_attn.index_rope``), or, where the layer is latent, the
        first ``qk_rope_head_dim`` by the attention's table."""
        return (self.qk_rope_head_dim if self.latent_layers
                else self.index_head_dim)

    @property
    def index_row_lanes(self) -> int:
        """Lanes of ONE stored row of the page pool's index-key leaf (0: no
        such leaf): a position's ``index_head_dim`` key lanes zero-padded to
        whole 128-lane tiles (64 -> 128). At 64 lanes a page of 16 bf16 rows
        is half an (16, 128) tile: the chip's compiler pads it to 128 lanes
        anyway or keeps the leaf pages-minor and copies it around every row
        write (``kv_row_lanes``' reason); two positions a row would halve
        the bytes but make a step's row write a read-modify-write of its
        neighbour's lanes."""
        if not self.sparse_layers:
            return 0
        return -(-self.index_head_dim // LANE_TILE) * LANE_TILE

    @property
    def kv_row_lanes(self) -> int:
        """Lanes of ONE stored row of a page-pool leaf, the one place the
        pool's row width is asked: all KV heads' K (or V) of a position, or a
        latent layer's ``[c (kv_lora_rank) | k_rope (qk_rope_head_dim)]``
        zero-padded to whole 128-lane tiles (320 -> 384: at 320 the chip's
        compiler keeps the pool pages-minor and copies it whole around every
        write, as it does a 64-lane second leaf; PERF.md section 6 "PR 32")."""
        if self.latent_layers:
            return -(-(self.kv_lora_rank + self.qk_rope_head_dim)
                     // LANE_TILE) * LANE_TILE
        return self.num_kv_heads * self.head_dim

    @property
    def window_row_lanes(self) -> int:
        """Lanes of ONE stored row of the WINDOW group's pool where its
        layers cache latent rows (0: they keep K/V rows, ``2 *
        kv_row_lanes`` wide, or there is no window group): the window kind's
        ``[c | k_rope]`` padded as ``kv_row_lanes`` pads the full kind's
        (1088 -> 1152)."""
        if not self.window_latent_layers:
            return 0
        return self.window_latent.kv_row_lanes

    @property
    def is_hybrid(self) -> bool:
        """Walked by layer kinds (``models/hybrid.py``), with params held per
        kind and a routed expert layer after every mixer."""
        return self.family in ("granitemoehybrid", "mellum", "mistral4",
                               "afmoe", "longcat_flash", "lfm2_moe",
                               "keye_vl2", "deepseek_v32", "dots3_note")

    @property
    def expert_layers(self) -> int:
        """Layers whose feed-forward is routed experts: all but the leading
        dense ones (published layers, not sublayers)."""
        return self.num_layers - self.num_dense_layers

    @property
    def sublayers(self) -> int:
        """Attention sublayers a published layer, each followed by a dense
        SwiGLU: past 1 ``layer_types`` lists the sublayers and a layer's ONE
        routed layer rides a shortcut beside them (``hybrid._shortcut``)."""
        return 2 if self.family == "longcat_flash" else 1

    @property
    def router_width(self) -> int:
        """The router's outputs: the routed experts, then the identity
        ones."""
        return self.num_experts + self.zero_experts

    @property
    def counted_experts(self) -> int:
        """Columns of the step's assignment counter a layer: one a held
        expert, then one for all the identity experts where the router has
        any."""
        return self.local_experts + (1 if self.zero_experts else 0)

    @property
    def q_rank_scale(self) -> float:
        """What a (full) latent layer's whole query is multiplied by."""
        return self.rank_scale(self.q_lora_rank)

    @property
    def kv_rank_scale(self) -> float:
        """What a (full) latent layer's normalised latent is multiplied by."""
        return self.rank_scale(self.kv_lora_rank)

    @property
    def position_free(self) -> tuple:
        """The attention kinds that take no positions at all: every kind
        under ``nope``; an afmoe stack's full layers, whose window layers
        rotate."""
        if self.nope:
            return ("attention", "sliding_attention")
        return ("attention",) if self.family == "afmoe" else ()

    @property
    def route_norm_eps(self) -> float:
        """What a ``"sigmoid"`` router adds to the sum of the chosen scores
        it normalises by: the family's own constant, not a setting."""
        return 1e-6 if self.family == "lfm2_moe" else 1e-20

    @property
    def recurrent_state(self) -> bool:
        """Keeps a sequence's state as more than K/V rows (a Mamba-2 layer's
        convolution window and SSM state, a short convolution's window): a
        property of the layer kinds, which ``hybrid.state_shapes`` sizes and
        ``hybrid.refuse_recurrent_state`` refuses by."""
        return any(t in ("mamba", "conv") for t in self.layer_types)

    @property
    def window_layers(self) -> int:
        """Layers that keep a RING of pages: the sliding ones, whether their
        rows are K/V or a latent (``window_latent_layers``)."""
        return sum(1 for t in self.layer_types
                   if t in ("sliding_attention", "sliding_latent_attention"))

    def window_pages(self, page_size: int) -> int:
        """Pages a slot's ring holds in each window layer: the most that
        ``sliding_window`` consecutive positions can touch,
        ``ceil((window - 1) / page_size) + 1`` — ``window // page_size + 1``
        where the page size divides the window (65 at 1024 / 16)."""
        return -(-(self.sliding_window - 1) // page_size) + 1

    @property
    def kv_layers(self) -> int:
        """Layers that keep pages that grow with the stream: all of them, or
        the attention ones (per-head K/V rows, or latent rows)."""
        if not self.layer_types:
            return self.num_layers
        return sum(1 for t in self.layer_types
                   if t in ("attention", "latent_attention",
                            "sparse_attention", "sparse_latent_attention"))

    @property
    def mamba_layers(self) -> int:
        return sum(1 for t in self.layer_types if t == "mamba")

    @property
    def conv_layers(self) -> int:
        """Gated short-convolution layers (``models/shortconv.py``)."""
        return sum(1 for t in self.layer_types if t == "conv")

    @property
    def mamba_d_inner(self) -> int:
        return self.mamba_heads * self.mamba_head_dim

    @property
    def mamba_conv_dim(self) -> int:
        """Width the causal convolution runs over: x, then B and C."""
        return (self.mamba_d_inner
                + 2 * self.mamba_n_groups * self.mamba_d_state)

    @property
    def local_experts(self) -> int:
        return self.experts_held or self.num_experts

    @property
    def q_prescale(self) -> float:
        """What ``q`` is multiplied by so that the attention paths' own
        ``1/sqrt(head_dim)`` comes out as ``attention_multiplier``."""
        if self.attention_multiplier is None:
            return 1.0
        return float(self.attention_multiplier) * float(self.head_dim) ** 0.5

    @property
    def rotary_dim(self) -> int:
        return self.qk_rope_head_dim or int(self.head_dim * self.rotary_pct)

    @property
    def qkv_bias(self) -> bool:
        return self.family in ("gpt_neox", "qwen2")

    def __post_init__(self):
        if self.family not in ("gpt_neox", "qwen2", "llama",
                               "granitemoehybrid", "mellum", "mistral4",
                               "afmoe", "longcat_flash", "lfm2_moe",
                               "keye_vl2", "deepseek_v32", "dots3_note"):
            raise ValueError(f"unknown family: {self.family}")
        if self.is_hybrid:
            self._check_hybrid()
        elif (self.layer_types or self.num_experts or self.mamba_heads
              or self.explicit_head_dim or self.sliding_window
              or self.kv_lora_rank or self.num_dense_layers
              or self.score_func != "softmax" or self.zero_experts
              or self.rank_scales or self.conv_window or self.index_topk
              or self.index_heads or self.index_head_dim
              or self.mrope_section or self.route_groups != 1
              or self.route_groups_kept != 1 or self.window_latent
              or self.head_gate):
            raise ValueError(
                f"layer_types / experts / mamba / head width / window / "
                f"latent / dense-layer / routing / short-convolution / "
                f"indexer / head-gate fields belong to the granitemoehybrid, "
                f"mellum, mistral4, afmoe, longcat_flash, lfm2_moe, keye_vl2, "
                f"deepseek_v32 and dots3_note families, not {self.family!r}")
        if not self.explicit_head_dim and self.hidden_size % self.num_heads:
            raise ValueError("num_heads must evenly divide hidden_size")
        if self.num_heads % self.num_kv_heads:
            raise ValueError("num_kv_heads must evenly divide num_heads")

    def _check_hybrid(self):
        kinds = {"granitemoehybrid": ("mamba", "attention"),
                 "mellum": ("attention", "sliding_attention"),
                 "mistral4": ("latent_attention",),
                 "afmoe": ("attention", "sliding_attention"),
                 "longcat_flash": ("latent_attention",),
                 "lfm2_moe": ("conv", "attention"),
                 "keye_vl2": ("sparse_attention",),
                 "deepseek_v32": ("sparse_latent_attention",),
                 "dots3_note": ("sparse_latent_attention",
                                "sliding_latent_attention")}[self.family]
        if len(self.layer_types) != self.num_layers * self.sublayers or any(
                t not in kinds for t in self.layer_types):
            raise ValueError(
                f"layer_types must name one of {kinds} for each of the "
                f"{self.num_layers} layers x {self.sublayers} sublayer(s) "
                f"of family {self.family!r}, got {self.layer_types!r}")
        if self.sublayers > 1 and (self.num_dense_layers
                                   or self.shared_width):
            raise ValueError(
                "a layer of sublayers holds a dense SwiGLU after each and "
                "its routed layer on a shortcut: no leading dense layer, no "
                "shared expert")
        if self.zero_experts < 0 or (self.zero_experts
                                     and self.score_func != "softmax_all"):
            raise ValueError(
                "identity experts (zero_experts) are routed by score_func "
                "'softmax_all' (unnormalised weights: a token's compute "
                "varies with what it chose)")
        if self.rank_scales and not self.latent_layers:
            raise ValueError("rank_scales belongs to latent_attention layers")
        if self.window_layers and self.sliding_window < 1:
            raise ValueError("a sliding_attention layer needs sliding_window "
                             ">= 1")
        w = self.window_latent
        if bool(self.window_latent_layers) != (w is not None) or (
                w is not None and (
                    min(w.num_heads, w.q_lora_rank, w.kv_lora_rank,
                        w.qk_nope_head_dim, w.v_head_dim) < 1
                    or w.qk_rope_head_dim < 2 or w.qk_rope_head_dim % 2)):
            raise ValueError(
                "window_latent (heads, ranks, nope, an even rope, a value "
                "width, all >= 1) belongs to sliding_latent_attention "
                "layers, and those need it")
        if self.head_gate and not (self.latent_layers
                                   or self.window_latent_layers):
            raise ValueError("head_gate belongs to latent layers")
        if bool(self.latent_layers) != bool(self.kv_lora_rank):
            raise ValueError("latent ranks belong to latent_attention layers, "
                             "and those need them")
        if self.latent_layers and (
                min(self.q_lora_rank, self.kv_lora_rank, self.v_head_dim) < 1
                or not 0 < self.qk_rope_head_dim < self.head_dim
                or self.qk_rope_head_dim % 2):
            raise ValueError(
                "a latent_attention layer needs q_lora_rank, kv_lora_rank, "
                "v_head_dim >= 1 and an even qk_rope_head_dim inside the "
                "explicit head_dim (= qk_nope_head_dim + qk_rope_head_dim)")
        if not 0 < self.experts_per_tok <= self.router_width:
            raise ValueError("experts_per_tok must be in [1, num_experts + "
                             "zero_experts]")
        if not (0 <= self.expert_offset
                and self.expert_offset + self.local_experts
                <= self.num_experts):
            raise ValueError(
                f"experts held [{self.expert_offset}, "
                f"{self.expert_offset + self.local_experts}) lie outside the "
                f"router's {self.num_experts}")
        if not 0 <= self.num_dense_layers < self.num_layers:
            raise ValueError("num_dense_layers must leave at least one "
                             "expert layer")
        if self.score_func not in ("softmax", "sigmoid", "softmax_all"):
            raise ValueError(f"unknown score_func: {self.score_func!r}")
        if self.expert_width < 1 or self.shared_width < 0:
            raise ValueError("expert_width must be >= 1 and shared_width "
                             ">= 0 (0: no shared expert)")
        if self.mamba_layers and min(
                self.mamba_heads, self.mamba_head_dim, self.mamba_d_state,
                self.mamba_d_conv - 1, self.mamba_chunk) < 1:
            raise ValueError("a mamba layer needs heads, head width, d_state, "
                             "d_conv >= 2 and a chunk length")
        if self.mamba_heads % self.mamba_n_groups:
            raise ValueError("mamba_n_groups must evenly divide mamba_heads")
        if bool(self.conv_layers) != bool(self.conv_window) or (
                self.conv_layers and self.conv_window < 2):
            raise ValueError("conv_window (>= 2 taps) belongs to conv layers, "
                             "and those need it")
        indexer = (self.index_heads, self.index_head_dim, self.index_topk)
        if bool(self.sparse_layers) != bool(any(indexer)) or (
                self.sparse_layers and (min(indexer) < 1
                                        or self.index_head_dim % 2)):
            raise ValueError(
                "index_heads, index_head_dim (even) and index_topk (all >= "
                "1) belong to sparse_attention layers, and those need them")
        if self.sparse_layers and self.latent_layers \
                and self.index_head_dim <= self.qk_rope_head_dim:
            raise ValueError(
                "a sparse_latent_attention layer's indexer rotates the first "
                "qk_rope_head_dim lanes of an index_head_dim that is wider")
        groups, kept = self.route_groups, self.route_groups_kept
        if (groups, kept) != (1, 1) and (
                self.score_func == "softmax" or not 1 <= kept <= groups
                or self.router_width % groups
                or self.router_width // groups < 2
                or kept * (self.router_width // groups)
                < self.experts_per_tok):
            raise ValueError(
                f"route_groups {groups} / route_groups_kept {kept}: the "
                f"router's {self.router_width} outputs must split into "
                f"equal groups of at least two (a group is ranked by its "
                f"two best biased scores), the kept groups must hold "
                f"experts_per_tok {self.experts_per_tok}, and the scores "
                f"are 'sigmoid' or 'softmax_all'")
        if self.mrope_section and (
                not self.sparse_layers
                or sum(self.mrope_section) * 2 != self.rotary_dim):
            raise ValueError(
                f"mrope_section {self.mrope_section!r} must split the "
                f"{self.rotary_dim // 2} rotary frequencies of a "
                f"sparse_attention stack's head (it sums to "
                f"{sum(self.mrope_section)})")


# EleutherAI/pythia-70m — facts per SURVEY.md section 2.1 (6 layers, d=512, 8 heads,
# FFN 2048 GELU, vocab 50304, LayerNorm, rotary_pct 0.25, window 2048).
PYTHIA_70M = ModelConfig(
    family="gpt_neox",
    vocab_size=50304,
    hidden_size=512,
    num_layers=6,
    num_heads=8,
    num_kv_heads=8,
    intermediate_size=2048,
    max_position_embeddings=2048,
    norm_eps=1e-5,
    rope_theta=10000.0,
    rotary_pct=0.25,
)

# Qwen/Qwen2-0.5B — 24 layers, d=896, 14 q heads / 2 kv heads (GQA), FFN 4864,
# vocab 151936, RMSNorm eps 1e-6 (SURVEY.md section 2.1 / notebook module dumps).
QWEN2_0_5B = ModelConfig(
    family="qwen2",
    vocab_size=151936,
    hidden_size=896,
    num_layers=24,
    num_heads=14,
    num_kv_heads=2,
    intermediate_size=4864,
    max_position_embeddings=131072,
    norm_eps=1e-6,
    rope_theta=1000000.0,
    tie_word_embeddings=True,
)

# Qwen/Qwen2-1.5B — the 3-device multi-hop split target (BASELINE.json configs[4]).
QWEN2_1_5B = ModelConfig(
    family="qwen2",
    vocab_size=151936,
    hidden_size=1536,
    num_layers=28,
    num_heads=12,
    num_kv_heads=2,
    intermediate_size=8960,
    max_position_embeddings=131072,
    norm_eps=1e-6,
    rope_theta=1000000.0,
    tie_word_embeddings=True,
)

# meta-llama/Llama-3.2-1B — beyond-parity family (edge-sized Llama). Ships
# llama3 RoPE rescaling (factor 32 over an 8192-token original window).
LLAMA_3_2_1B = ModelConfig(
    family="llama",
    vocab_size=128256,
    hidden_size=2048,
    num_layers=16,
    num_heads=32,
    num_kv_heads=8,
    intermediate_size=8192,
    max_position_embeddings=131072,
    norm_eps=1e-5,
    rope_theta=500000.0,
    tie_word_embeddings=True,
    rope_scaling=("llama3", 32.0, 1.0, 4.0, 8192),
)

# ibm-granite/granite-4.0-h-small (32B-A9B, 2025-10) — config.json: 40 layers
# in a period of ten (five Mamba-2, one NoPE GQA attention, four Mamba-2), d
# 4096, 72 routed experts of width 768 top-10 plus a shared expert of 1536 on
# every layer, tied 100352-row table. Whole: all 72 experts held.
_GRANITE_PERIOD = ("mamba",) * 5 + ("attention",) + ("mamba",) * 4
GRANITE_4_0_H_SMALL = ModelConfig(
    family="granitemoehybrid",
    vocab_size=100352,
    hidden_size=4096,
    num_layers=40,
    num_heads=32,
    num_kv_heads=8,
    intermediate_size=768,
    max_position_embeddings=131072,
    norm_eps=1e-5,
    tie_word_embeddings=True,
    layer_types=_GRANITE_PERIOD * 4,
    num_experts=72,
    experts_per_tok=10,
    expert_width=768,
    shared_width=1536,
    mamba_heads=128,
    mamba_head_dim=64,
    mamba_d_state=128,
    mamba_d_conv=4,
    mamba_n_groups=1,
    mamba_chunk=256,
    embedding_multiplier=12.0,
    residual_multiplier=0.22,
    logits_scaling=16.0,
    attention_multiplier=0.0078125,
    nope=True,
)


# JetBrains/Mellum2-12B-A2.5B-Instruct (2026-05) — config.json: 28 layers in
# a period of four (three sliding-window layers of 1024 keys, one full), d
# 2304, 32 query / 4 KV heads of 128 (H x hd = 4096), 64 routed experts of
# width 896 top-8 with no shared expert on every layer, untied 98304-row
# head; RoPE theta 500000, YaRN (factor 16 over 8192) on the full layers only.
_MELLUM_PERIOD = ("sliding_attention",) * 3 + ("attention",)
MELLUM2_12B_A2_5B = ModelConfig(
    family="mellum",
    vocab_size=98304,
    hidden_size=2304,
    num_layers=28,
    num_heads=32,
    num_kv_heads=4,
    intermediate_size=7168,   # the published dense width; no layer is dense
    max_position_embeddings=131072,
    norm_eps=1e-6,
    rope_theta=500000.0,
    tie_word_embeddings=False,
    rope_scaling=("yarn", 16.0, 8192, 32.0, 1.0, 1.2772588722239782),
    layer_types=_MELLUM_PERIOD * 7,
    explicit_head_dim=128,
    sliding_window=1024,
    num_experts=64,
    experts_per_tok=8,
    expert_width=896,
)


# mistralai/Mistral-Small-4-119B-2603 (119B-A6.5B, 2026-03) — config.json
# (``model_type`` ``mistral4``): 36 layers, every one latent attention (32
# heads: queries through a 1024-wide bottleneck, a cached row of 256 latent +
# 64 rotated lanes, value heads of 128) and 128 routed experts of width 2048
# top-4 plus one shared expert of 2048; YaRN (factor 128 over 8192, mscale =
# mscale_all_dim = 1: cos/sin unscaled, the softmax scale times m^2) and the
# per-position query scale (``llama_4_scaling_beta`` 0.1); untied 131072-row
# head.
MISTRAL_SMALL_4_119B = ModelConfig(
    family="mistral4",
    vocab_size=131072,
    hidden_size=4096,
    num_layers=36,
    num_heads=32,
    num_kv_heads=32,
    intermediate_size=12288,  # the published dense width; no layer is dense
    max_position_embeddings=1048576,
    norm_eps=1e-6,
    rope_theta=10000.0,
    tie_word_embeddings=False,
    rope_scaling=("yarn", 128.0, 8192, 32.0, 1.0, 1.0),
    layer_types=("latent_attention",) * 36,
    explicit_head_dim=128,
    num_experts=128,
    experts_per_tok=4,
    expert_width=2048,
    shared_width=2048,
    q_lora_rank=1024,
    kv_lora_rank=256,
    qk_rope_head_dim=64,
    v_head_dim=128,
    softmax_mscale=0.1 * math.log(128.0) + 1.0,
    query_scale_beta=0.1,
)


# arcee-ai/Trinity-Mini (26B-A3B, 2025-12) — config.json (``model_type``
# ``afmoe``): 32 layers in a period of four (three sliding-window layers of
# 2048 keys, rotated; one full, position-free), d 2048, 32 query / 4 KV heads
# of 128, q and k normed per head, a sigmoid gate on the attend's output, four
# norms a layer; two leading dense layers of width 6144, then 128 routed
# experts of width 1024 top-8 by sigmoid scores with a selection bias (weights
# normalised, times 2.826) plus one shared expert; ``mup_enabled``: the
# embedding times sqrt(d); untied 200192-row head.
TRINITY_MINI = ModelConfig(
    family="afmoe",
    vocab_size=200192,
    hidden_size=2048,
    num_layers=32,
    num_heads=32,
    num_kv_heads=4,
    intermediate_size=6144,
    max_position_embeddings=131072,
    norm_eps=1e-5,
    rope_theta=10000.0,
    tie_word_embeddings=False,
    layer_types=_MELLUM_PERIOD * 8,
    explicit_head_dim=128,
    sliding_window=2048,
    num_experts=128,
    experts_per_tok=8,
    expert_width=1024,
    shared_width=1024,
    embedding_multiplier=math.sqrt(2048.0),
    num_dense_layers=2,
    score_func="sigmoid",
    route_scale=2.826,
)


# meituan-longcat/LongCat-Flash-Chat (560B, 18.6-31.3B active, 2025-09) —
# config.json (``model_type`` ``longcat_flash``): 28 layers, each two latent
# attention sublayers (64 heads: queries through a 1536-wide bottleneck, a
# cached row of 512 latent + 64 rotated lanes, value heads of 128; both rank
# scales) and two dense SwiGLUs of 12288 with one routed layer on a shortcut:
# 512 experts of width 2048 + 256 identity experts, top-12 of the 768 by the
# whole softmax + a selection bias, weights unnormalised times 6; plain RoPE
# theta 1e7; untied 131072-row head.
LONGCAT_FLASH_CHAT = ModelConfig(
    family="longcat_flash",
    vocab_size=131072,
    hidden_size=6144,
    num_layers=28,
    num_heads=64,
    num_kv_heads=64,
    intermediate_size=12288,
    max_position_embeddings=131072,
    norm_eps=1e-5,
    rope_theta=10000000.0,
    tie_word_embeddings=False,
    layer_types=("latent_attention",) * 56,
    explicit_head_dim=192,
    num_experts=512,
    experts_per_tok=12,
    expert_width=2048,
    q_lora_rank=1536,
    kv_lora_rank=512,
    qk_rope_head_dim=64,
    v_head_dim=128,
    score_func="softmax_all",
    route_scale=6.0,
    zero_experts=256,
    rank_scales=True,
)


# LiquidAI/LFM2-8B-A1B (8.3B-A1.5B, 2025-10) — config.json (``model_type``
# ``lfm2_moe``): 24 layers, 18 gated short convolutions of 3 taps beside 6
# rotated GQA layers (32 query / 8 KV heads of 64, q and k normed per head),
# d 2048; two leading dense layers of width 7168, then 32 routed experts of
# width 1792 top-4 by sigmoid scores with a selection bias, weights
# normalised, no shared expert; tied 65536-row table.
_LFM2_LAYERS = tuple(
    "attention" if layer in (2, 6, 10, 14, 18, 21) else "conv"
    for layer in range(24))
LFM2_8B_A1B = ModelConfig(
    family="lfm2_moe",
    vocab_size=65536,
    hidden_size=2048,
    num_layers=24,
    num_heads=32,
    num_kv_heads=8,
    intermediate_size=7168,
    max_position_embeddings=128000,
    norm_eps=1e-5,
    rope_theta=1000000.0,
    tie_word_embeddings=True,
    layer_types=_LFM2_LAYERS,
    num_experts=32,
    experts_per_tok=4,
    expert_width=1792,
    num_dense_layers=2,
    score_func="sigmoid",
    conv_window=3,
)


# Kwai-Keye/Keye-VL-2.0-30B-A3B (30B-A3B, 2026-06) — config.json
# (``model_type`` ``KeyeVL2``), the language model: 48 layers, every one a
# sparse-attention layer (32 query / 4 KV heads of 128, q and k normed per
# head, rotated over all 128 lanes by theta 1e7 in three ``mrope_section``
# streams that text makes equal; an indexer of 16 heads of 64 lanes against
# one index key a position, the query attends the top 2048) and 128 routed
# experts of width 768 top-8 by the softmax over the chosen, none shared;
# untied 151936-row head. The vision tower is not built.
KEYE_VL_2_0_30B_A3B = ModelConfig(
    family="keye_vl2",
    vocab_size=151936,
    hidden_size=2048,
    num_layers=48,
    num_heads=32,
    num_kv_heads=4,
    intermediate_size=6144,  # the published dense width; no layer is dense
    max_position_embeddings=262144,
    norm_eps=1e-6,
    rope_theta=10000000.0,
    tie_word_embeddings=False,
    layer_types=("sparse_attention",) * 48,
    explicit_head_dim=128,
    num_experts=128,
    experts_per_tok=8,
    expert_width=768,
    index_heads=16,
    index_head_dim=64,
    index_topk=2048,
    mrope_section=(16, 24, 24),
)


# deepseek-ai/DeepSeek-V3.2-Exp (671B-A37B, 2025-09) — config.json
# (``model_type`` ``deepseek_v32``): 61 layers, every one latent attention
# (128 heads: queries through a 1536-wide bottleneck, a cached row of 512
# latent + 64 rotated lanes, value heads of 128; YaRN factor 40 over 4096,
# mscale = mscale_all_dim = 1) whose query attends the 2048 positions an
# indexer of 64 heads of 128 lanes selects (its query from the q latent, its
# first 64 lanes rotated); three leading dense layers of width 18432, then 256
# routed experts of width 2048 top-8 by sigmoid scores with a selection bias,
# chosen within the 4 best of 8 groups, weights normalised times 2.5, plus one
# shared expert; untied 129280-row head. The multi-token-prediction module is
# not built.
DEEPSEEK_V3_2_EXP = ModelConfig(
    family="deepseek_v32",
    vocab_size=129280,
    hidden_size=7168,
    num_layers=61,
    num_heads=128,
    num_kv_heads=128,
    intermediate_size=18432,
    max_position_embeddings=163840,
    norm_eps=1e-6,
    rope_theta=10000.0,
    tie_word_embeddings=False,
    rope_scaling=("yarn", 40.0, 4096, 32.0, 1.0, 1.0),
    layer_types=("sparse_latent_attention",) * 61,
    explicit_head_dim=192,
    num_experts=256,
    experts_per_tok=8,
    expert_width=2048,
    shared_width=2048,
    q_lora_rank=1536,
    kv_lora_rank=512,
    qk_rope_head_dim=64,
    v_head_dim=128,
    softmax_mscale=0.1 * math.log(40.0) + 1.0,
    num_dense_layers=3,
    score_func="sigmoid",
    route_scale=2.5,
    index_heads=64,
    index_head_dim=128,
    index_topk=2048,
    route_groups=8,
    route_groups_kept=4,
)


# dots-studio/dots3-note-prev (288B-A17B, 2026-08) — config.json
# (``model_type`` ``dots3_note``), the language model: 46 layers, 13 full
# (deepseek_v32's sparse latent layer: 128 heads, queries through a 1024-wide
# bottleneck, a cached row of 512 latent + 64 rotated lanes, value heads of
# 128, plain RoPE theta 8e7, an indexer of 64 heads of 128 lanes choosing
# 2048) and 33 sliding (plain latent attention at sizes of its own: 64 heads,
# both ranks 1024, 192 + 64 lanes a head, theta 5e4, a band of 513 keys, the
# rows in a ring); both rank factors and a head-wise output gate on every
# layer; one leading dense layer of width 13824, then 256 routed experts of
# width 1536 top-8 by sigmoid scores with a selection bias (one group), plus
# a shared expert; untied 152064-row head. The towers and the
# multi-token-prediction module are not built.
_DOTS3_LAYERS = ("sparse_latent_attention",) * 2 + (
    ("sliding_latent_attention",) * 3 + ("sparse_latent_attention",)) * 11
DOTS3_NOTE_PREV = ModelConfig(
    family="dots3_note",
    vocab_size=152064,
    hidden_size=5120,
    num_layers=46,
    num_heads=128,
    num_kv_heads=128,
    intermediate_size=13824,
    max_position_embeddings=524288,
    norm_eps=1e-5,
    rope_theta=80000000.0,
    tie_word_embeddings=False,
    layer_types=_DOTS3_LAYERS,
    explicit_head_dim=192,
    sliding_window=513,
    num_experts=256,
    experts_per_tok=8,
    expert_width=1536,
    shared_width=1536,
    q_lora_rank=1024,
    kv_lora_rank=512,
    qk_rope_head_dim=64,
    v_head_dim=128,
    num_dense_layers=1,
    score_func="sigmoid",
    route_scale=1.0,
    rank_scales=True,
    index_heads=64,
    index_head_dim=128,
    index_topk=2048,
    window_latent=LatentGeometry(
        num_heads=64, q_lora_rank=1024, kv_lora_rank=1024,
        qk_nope_head_dim=192, qk_rope_head_dim=64, v_head_dim=128,
        rope_theta=50000.0),
    head_gate=True,
)


def tiny_dots3_note_config(*, layer_types: tuple = (
        "sparse_latent_attention", "sparse_latent_attention",
        "sliding_latent_attention", "sliding_latent_attention",
        "sliding_latent_attention"),
                           index_topk: int = 8, sliding_window: int = 21,
                           num_dense_layers: int = 1, hidden_size: int = 48,
                           vocab_size: int = 256, num_experts: int = 16,
                           experts_per_tok: int = 3, experts_held: int = 0,
                           expert_offset: int = 0, rank_scales: bool = True,
                           window_latent: LatentGeometry = LatentGeometry(
                               num_heads=2, q_lora_rank=20, kv_lora_rank=136,
                               qk_nope_head_dim=24, qk_rope_head_dim=8,
                               v_head_dim=16, rope_theta=500.0),
                           max_position_embeddings: int = 512
                           ) -> ModelConfig:
    """A small dots3_note for tests: every mechanism of the published
    model's first stage (the leading dense layer, two full sparse latent
    layers of 4 heads with rows of 16 + 8 = 24 lanes (stored 128) beside
    index keys of 16 lanes, then three window layers of 2 heads with rows of
    136 + 8 = 144 lanes (stored 256: the two groups' rows differ in width as
    the published 640 and 1152 do), head widths, ranks and a theta of their
    own, a band the test
    prompts pass and a ring they turn; both rank factors off 1, a gate lane
    a head on both kinds, sigmoid routing in one group with a shared expert,
    an untied head) at toy widths."""
    return ModelConfig(
        family="dots3_note", vocab_size=vocab_size, hidden_size=hidden_size,
        num_layers=len(layer_types), num_heads=4, num_kv_heads=4,
        intermediate_size=96,
        max_position_embeddings=max_position_embeddings, norm_eps=1e-5,
        rope_theta=10000.0, tie_word_embeddings=False,
        layer_types=tuple(layer_types), explicit_head_dim=24,
        sliding_window=sliding_window, num_experts=num_experts,
        experts_per_tok=experts_per_tok, expert_width=32, shared_width=40,
        experts_held=experts_held, expert_offset=expert_offset,
        q_lora_rank=20, kv_lora_rank=16, qk_rope_head_dim=8, v_head_dim=16,
        num_dense_layers=num_dense_layers, score_func="sigmoid",
        route_scale=1.0, rank_scales=rank_scales, index_heads=3,
        index_head_dim=16, index_topk=index_topk,
        window_latent=window_latent, head_gate=True)


def tiny_deepseek_v32_config(*, num_layers: int = 3, index_topk: int = 8,
                             num_dense_layers: int = 1,
                             hidden_size: int = 48, num_heads: int = 4,
                             index_heads: int = 3, index_head_dim: int = 16,
                             vocab_size: int = 256, num_experts: int = 16,
                             experts_per_tok: int = 3, route_groups: int = 4,
                             route_groups_kept: int = 2,
                             experts_held: int = 0, expert_offset: int = 0,
                             original_max: int = 16,
                             max_position_embeddings: int = 512
                             ) -> ModelConfig:
    """A small deepseek_v32 for tests: every mechanism of the published
    model (a leading dense layer, latent rows of 16 + 8 = 24 lanes stored
    padded beside index keys of 16 lanes whose first 8 rotate, an indexer fed
    by the 20-wide q latent whose ``index_topk`` the test prompts pass, YaRN
    with ``mscale`` on the softmax stepping inside 100 positions, sigmoid
    routing over 4 groups of 4 of which 2 are kept, a route scale, a shared
    expert, an untied head) at toy widths."""
    return ModelConfig(
        family="deepseek_v32", vocab_size=vocab_size,
        hidden_size=hidden_size, num_layers=num_layers, num_heads=num_heads,
        num_kv_heads=num_heads, intermediate_size=96,
        max_position_embeddings=max_position_embeddings, norm_eps=1e-6,
        rope_theta=10000.0, tie_word_embeddings=False,
        rope_scaling=("yarn", 8.0, original_max, 32.0, 1.0, 1.0),
        layer_types=("sparse_latent_attention",) * num_layers,
        explicit_head_dim=24, num_experts=num_experts,
        experts_per_tok=experts_per_tok, expert_width=32, shared_width=40,
        experts_held=experts_held, expert_offset=expert_offset,
        q_lora_rank=20, kv_lora_rank=16, qk_rope_head_dim=8, v_head_dim=16,
        softmax_mscale=0.1 * math.log(8.0) + 1.0,
        num_dense_layers=num_dense_layers, score_func="sigmoid",
        route_scale=2.5, index_heads=index_heads,
        index_head_dim=index_head_dim, index_topk=index_topk,
        route_groups=route_groups, route_groups_kept=route_groups_kept)


def tiny_keye_vl2_config(*, num_layers: int = 2, index_topk: int = 8,
                         hidden_size: int = 48, num_heads: int = 4,
                         num_kv_heads: int = 2, head_dim: int = 16,
                         index_heads: int = 3, index_head_dim: int = 8,
                         vocab_size: int = 256, num_experts: int = 8,
                         experts_per_tok: int = 3, experts_held: int = 0,
                         expert_offset: int = 0,
                         max_position_embeddings: int = 512) -> ModelConfig:
    """A small keye_vl2 for tests: every mechanism of the published language
    model (sparse-attention layers alone, ``H x hd`` = 64 against a hidden
    size of 48, q/k norms, a three-stream ``mrope_section`` over the head's 8
    frequencies, an indexer of fewer and narrower heads than the attention's
    whose ``index_topk`` the test prompts pass, top-k routing by the softmax
    over the chosen with none shared, an untied head) at toy widths."""
    return ModelConfig(
        family="keye_vl2", vocab_size=vocab_size, hidden_size=hidden_size,
        num_layers=num_layers, num_heads=num_heads,
        num_kv_heads=num_kv_heads, intermediate_size=96,
        max_position_embeddings=max_position_embeddings, norm_eps=1e-6,
        rope_theta=10000000.0, tie_word_embeddings=False,
        layer_types=("sparse_attention",) * num_layers,
        explicit_head_dim=head_dim, num_experts=num_experts,
        experts_per_tok=experts_per_tok, expert_width=32,
        experts_held=experts_held, expert_offset=expert_offset,
        index_heads=index_heads, index_head_dim=index_head_dim,
        index_topk=index_topk,
        mrope_section=(head_dim // 8, head_dim // 8 + head_dim // 16,
                       head_dim // 2 - 2 * (head_dim // 8) - head_dim // 16))


def tiny_lfm2_moe_config(*, layer_types: tuple = ("conv", "conv", "attention",
                                                  "conv", "conv", "conv"),
                         num_dense_layers: int = 2, conv_window: int = 3,
                         hidden_size: int = 48, num_heads: int = 4,
                         num_kv_heads: int = 2, vocab_size: int = 256,
                         num_experts: int = 8, experts_per_tok: int = 3,
                         experts_held: int = 0, expert_offset: int = 0,
                         max_position_embeddings: int = 512) -> ModelConfig:
    """A small lfm2_moe for tests: every mechanism of the published model
    (short convolutions 3:1 beside a rotated, q/k-normed GQA layer in the
    published order, two leading dense layers, sigmoid routing with a
    selection bias and no shared expert, a tied head) at toy widths; the
    taps settable."""
    return ModelConfig(
        family="lfm2_moe", vocab_size=vocab_size, hidden_size=hidden_size,
        num_layers=len(layer_types), num_heads=num_heads,
        num_kv_heads=num_kv_heads, intermediate_size=96,
        max_position_embeddings=max_position_embeddings, norm_eps=1e-5,
        rope_theta=1000000.0, tie_word_embeddings=True,
        layer_types=tuple(layer_types), num_experts=num_experts,
        experts_per_tok=experts_per_tok, expert_width=32,
        experts_held=experts_held, expert_offset=expert_offset,
        num_dense_layers=num_dense_layers, score_func="sigmoid",
        conv_window=conv_window)


def tiny_longcat_flash_config(*, num_layers: int = 2, hidden_size: int = 48,
                              num_heads: int = 4, vocab_size: int = 256,
                              num_experts: int = 8, zero_experts: int = 4,
                              experts_per_tok: int = 5,
                              experts_held: int = 0, expert_offset: int = 0,
                              max_position_embeddings: int = 512
                              ) -> ModelConfig:
    """A small longcat_flash for tests, every ratio of the published model
    kept: two latent sublayers a layer, identity experts half the routed
    count, a top-k (5) larger than a held share of 2 or 4, rope lanes (8) <
    a head's 24, a cached row (16 + 8 = 24 lanes) that needs padding, both
    rank scales off 1 (sqrt(48 / 20), sqrt(48 / 16)), a dense width over the
    experts', an untied head."""
    return ModelConfig(
        family="longcat_flash", vocab_size=vocab_size,
        hidden_size=hidden_size, num_layers=num_layers, num_heads=num_heads,
        num_kv_heads=num_heads, intermediate_size=96,
        max_position_embeddings=max_position_embeddings, norm_eps=1e-5,
        rope_theta=10000.0, tie_word_embeddings=False,
        layer_types=("latent_attention",) * (2 * num_layers),
        explicit_head_dim=24, num_experts=num_experts,
        experts_per_tok=experts_per_tok, expert_width=32,
        experts_held=experts_held, expert_offset=expert_offset,
        q_lora_rank=20, kv_lora_rank=16, qk_rope_head_dim=8, v_head_dim=16,
        score_func="softmax_all", route_scale=6.0,
        zero_experts=zero_experts, rank_scales=True)


def tiny_afmoe_config(*, layer_types: tuple = (("sliding_attention",)
                                               + _MELLUM_PERIOD),
                      num_dense_layers: int = 1, sliding_window: int = 20,
                      hidden_size: int = 48, num_heads: int = 4,
                      num_kv_heads: int = 2, head_dim: int = 16,
                      vocab_size: int = 256, num_experts: int = 8,
                      experts_per_tok: int = 3, experts_held: int = 0,
                      expert_offset: int = 0,
                      max_position_embeddings: int = 512) -> ModelConfig:
    """A small afmoe for tests: every mechanism of the published model (a
    leading dense layer ahead of one whole period of expert layers, window
    layers that rotate beside a full one that does not, a window shorter than
    the test prompts, ``H x hd`` = 64 against a hidden size of 48, sigmoid
    routing with a route scale and a shared expert, the embedding times
    sqrt(d), an untied head) at toy widths."""
    return ModelConfig(
        family="afmoe", vocab_size=vocab_size, hidden_size=hidden_size,
        num_layers=len(layer_types), num_heads=num_heads,
        num_kv_heads=num_kv_heads, intermediate_size=96,
        max_position_embeddings=max_position_embeddings, norm_eps=1e-5,
        rope_theta=10000.0, tie_word_embeddings=False,
        layer_types=tuple(layer_types), explicit_head_dim=head_dim,
        sliding_window=sliding_window, num_experts=num_experts,
        experts_per_tok=experts_per_tok, expert_width=32, shared_width=32,
        experts_held=experts_held, expert_offset=expert_offset,
        embedding_multiplier=math.sqrt(float(hidden_size)),
        num_dense_layers=num_dense_layers, score_func="sigmoid",
        route_scale=2.826)


def tiny_mistral4_config(*, num_layers: int = 3, hidden_size: int = 48,
                         num_heads: int = 4, vocab_size: int = 256,
                         num_experts: int = 8, experts_per_tok: int = 3,
                         experts_held: int = 0, expert_offset: int = 0,
                         original_max: int = 16,
                         max_position_embeddings: int = 512) -> ModelConfig:
    """A small mistral4 for tests, every ratio of the published model kept:
    rope lanes (8) < a head's 24, ``H x head`` = 96 against a hidden size of
    48, the cached latent (16 + 8 = 24 lanes, stored padded) far under ``H x
    (head + v)`` = 160, a query bottleneck, YaRN with ``mscale`` on the
    softmax and the query scale both stepping inside 100 positions
    (``original_max`` 16), top-k of routed experts plus a shared one, an
    untied head."""
    return ModelConfig(
        family="mistral4", vocab_size=vocab_size, hidden_size=hidden_size,
        num_layers=num_layers, num_heads=num_heads, num_kv_heads=num_heads,
        intermediate_size=32,
        max_position_embeddings=max_position_embeddings, norm_eps=1e-6,
        rope_theta=10000.0, tie_word_embeddings=False,
        rope_scaling=("yarn", 8.0, original_max, 32.0, 1.0, 1.0),
        layer_types=("latent_attention",) * num_layers,
        explicit_head_dim=24, num_experts=num_experts,
        experts_per_tok=experts_per_tok, expert_width=32, shared_width=40,
        experts_held=experts_held, expert_offset=expert_offset,
        q_lora_rank=20, kv_lora_rank=16, qk_rope_head_dim=8, v_head_dim=16,
        softmax_mscale=0.1 * math.log(8.0) + 1.0, query_scale_beta=0.1)


def tiny_mellum_config(*, layer_types: tuple = _MELLUM_PERIOD * 2,
                       sliding_window: int = 20, hidden_size: int = 48,
                       num_heads: int = 4, num_kv_heads: int = 2,
                       head_dim: int = 16, vocab_size: int = 256,
                       num_experts: int = 8, experts_per_tok: int = 3,
                       experts_held: int = 0, expert_offset: int = 0,
                       max_position_embeddings: int = 512) -> ModelConfig:
    """A small mellum for tests: every mechanism of the published model (both
    layer kinds in the 3:1 pattern, a window shorter than the test prompts,
    ``H x hd`` = 64 against a hidden size of 48, YaRN on the full layers only
    with an original length the tests decode past, top-k of routed experts
    with none shared, an untied head) at toy widths."""
    return ModelConfig(
        family="mellum", vocab_size=vocab_size, hidden_size=hidden_size,
        num_layers=len(layer_types), num_heads=num_heads,
        num_kv_heads=num_kv_heads, intermediate_size=32,
        max_position_embeddings=max_position_embeddings, norm_eps=1e-6,
        rope_theta=10000.0, tie_word_embeddings=False,
        rope_scaling=("yarn", 4.0, 32, 32.0, 1.0, 0.1 * math.log(4.0) + 1.0),
        layer_types=tuple(layer_types), explicit_head_dim=head_dim,
        sliding_window=sliding_window, num_experts=num_experts,
        experts_per_tok=experts_per_tok, expert_width=32,
        experts_held=experts_held, expert_offset=expert_offset)


def tiny_hybrid_config(*, layer_types: tuple = ("mamba", "mamba", "attention",
                                                "mamba"),
                       hidden_size: int = 64, num_heads: int = 4,
                       num_kv_heads: int = 2, vocab_size: int = 256,
                       num_experts: int = 8, experts_per_tok: int = 3,
                       experts_held: int = 0, expert_offset: int = 0,
                       mamba_chunk: int = 8) -> ModelConfig:
    """A small granitemoehybrid for tests: every mechanism of the published
    model (both layer kinds, routed + shared experts, the four multipliers,
    NoPE, a non-default attention scale) at toy widths."""
    return ModelConfig(
        family="granitemoehybrid", vocab_size=vocab_size,
        hidden_size=hidden_size, num_layers=len(layer_types),
        num_heads=num_heads, num_kv_heads=num_kv_heads,
        intermediate_size=32, max_position_embeddings=512, norm_eps=1e-5,
        tie_word_embeddings=True, layer_types=tuple(layer_types),
        num_experts=num_experts, experts_per_tok=experts_per_tok,
        expert_width=32, shared_width=48, experts_held=experts_held,
        expert_offset=expert_offset, mamba_heads=8, mamba_head_dim=16,
        mamba_d_state=16, mamba_d_conv=4, mamba_n_groups=1,
        mamba_chunk=mamba_chunk, embedding_multiplier=12.0,
        residual_multiplier=0.22, logits_scaling=16.0,
        attention_multiplier=1.0 / 32.0, nope=True)


def tiny_config(family: str, *, num_layers: int = 4, hidden_size: int = 64,
                num_heads: int = 4, num_kv_heads: int | None = None,
                vocab_size: int = 256, intermediate_size: int | None = None) -> ModelConfig:
    """Small random-init config for tests (no pretrained weights in this environment)."""
    if family == "granitemoehybrid":
        return tiny_hybrid_config()
    if family == "mellum":
        return tiny_mellum_config()
    if family == "mistral4":
        return tiny_mistral4_config()
    if family == "afmoe":
        return tiny_afmoe_config()
    if family == "longcat_flash":
        return tiny_longcat_flash_config()
    if family == "lfm2_moe":
        return tiny_lfm2_moe_config()
    if family == "keye_vl2":
        return tiny_keye_vl2_config()
    if family == "deepseek_v32":
        return tiny_deepseek_v32_config()
    if family == "dots3_note":
        return tiny_dots3_note_config()
    if num_kv_heads is None:
        num_kv_heads = 2 if family in ("qwen2", "llama") else num_heads
    if intermediate_size is None:
        intermediate_size = hidden_size * 4
    return ModelConfig(
        family=family,
        vocab_size=vocab_size,
        hidden_size=hidden_size,
        num_layers=num_layers,
        num_heads=num_heads,
        num_kv_heads=num_kv_heads,
        intermediate_size=intermediate_size,
        max_position_embeddings=512,
        norm_eps=1e-5 if family == "gpt_neox" else 1e-6,
        rope_theta=10000.0 if family == "gpt_neox" else 1000000.0,
        rotary_pct=0.25 if family == "gpt_neox" else 1.0,
        tie_word_embeddings=family in ("qwen2", "llama"),
    )


PRESETS = {
    "pythia-70m": PYTHIA_70M,
    "qwen2-0.5b": QWEN2_0_5B,
    "qwen2-1.5b": QWEN2_1_5B,
    "llama-3.2-1b": LLAMA_3_2_1B,
    "granite-4.0-h-small": GRANITE_4_0_H_SMALL,
    "mellum2-12b-a2.5b": MELLUM2_12B_A2_5B,
    "mistral-small-4-119b": MISTRAL_SMALL_4_119B,
    "trinity-mini": TRINITY_MINI,
    "longcat-flash-chat": LONGCAT_FLASH_CHAT,
    "lfm2-8b-a1b": LFM2_8B_A1B,
    "keye-vl-2.0-30b-a3b": KEYE_VL_2_0_30B_A3B,
    "deepseek-v3.2-exp": DEEPSEEK_V3_2_EXP,
    "dots3-note-prev": DOTS3_NOTE_PREV,
    # CI/smoke-scale variants (random init, no pretrained weights needed)
    "tiny-neox": tiny_config("gpt_neox"),
    "tiny-qwen2": tiny_config("qwen2", num_layers=6),
    "tiny-llama": tiny_config("llama", num_layers=6),
    "tiny-granite-hybrid": tiny_hybrid_config(),
    "tiny-mellum": tiny_mellum_config(),
    "tiny-mistral4": tiny_mistral4_config(),
    "tiny-afmoe": tiny_afmoe_config(),
    "tiny-longcat-flash": tiny_longcat_flash_config(),
    "tiny-lfm2-moe": tiny_lfm2_moe_config(),
    "tiny-keye-vl2": tiny_keye_vl2_config(),
    "tiny-deepseek-v32": tiny_deepseek_v32_config(),
    "tiny-dots3-note": tiny_dots3_note_config(),
}
