"""Functional transformer core shared by the GPT-NeoX (Pythia) and Qwen2 families.

TPU-first re-design of the reference's layer-wise model wrappers
(``/root/reference/Experiments/Pythia-70M/pythia_model.py:153-206`` and
``Experiments/Qwen2-0.5B/qwen_layer_wise.py:42-104``):

- Parameters are a plain pytree with **all layers stacked along a leading axis**, so
  the layer loop is a single ``lax.scan`` (one traced block, fast compiles, and the
  stack shards naturally along a pipeline-stage mesh axis).
- The reference runs a *second* full model with eager attention just to obtain
  attention maps for importance scoring (``last_row_exp.py:66-70``,
  ``Qwen2-0.5B/main.py:132-134``). Here the same forward can capture reduced
  attention statistics (per-head column means and last rows) in one pass — the
  only quantities the importance metrics actually consume — so no second model and
  no O(S^2) attention-map materialization on the hot path.
- A ``boundary_fn(layer_idx, hidden) -> hidden`` hook reproduces the reference's
  in-place edit of the hidden state after ``layer_of_interest``; in the split
  runtime the same hook is where the activation is quantized, packed, and sent
  across the mesh (``edgellm_tpu.parallel``).
- ``run_layers`` exposes a statically-sliced segment of the stack so sweep drivers
  can resume from a cached boundary activation instead of recomputing the prefix
  (the reference recomputes the full forward for every method x layer x ratio
  combination — ``Qwen2-0.5B/main.py:170-178``).

Everything is jit-safe: no data-dependent Python control flow, static shapes.
"""
from __future__ import annotations

import math
from typing import Callable, NamedTuple, Optional

import numpy as np
import jax
import jax.numpy as jnp

from .configs import ModelConfig
from ..lint import graph_contract


class AttnStats(NamedTuple):
    """Per-layer reduced attention statistics (enough for every importance metric).

    col_mean: (L, B, H, S) — mean over the query axis of the post-softmax attention
        map, i.e. average attention *received* by each key position per head
        (the "column-wise mean" of README.md:63-67).
    last_row: (L, B, H, S) — final query row of the attention map per head.
    """

    col_mean: jnp.ndarray
    last_row: jnp.ndarray


def precompute_rope(cfg: ModelConfig, seq_len: int,
                    scaled: bool = True) -> tuple[jnp.ndarray, jnp.ndarray]:
    """cos/sin tables, fp32, HF convention: emb = concat(freqs, freqs).
    ``scaled=False`` is the plain table whatever ``cfg.rope_scaling`` says:
    what the window layers of a stack with two layer kinds rotate by."""
    rot = cfg.rotary_dim
    inv_freq = 1.0 / (cfg.rope_theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot))
    factor = 1.0
    if scaled and cfg.rope_scaling is not None:
        if cfg.rope_scaling[0] == "yarn":
            inv_freq, factor = _yarn_scale_freqs(inv_freq, cfg.rope_theta,
                                                 cfg.rope_scaling)
        else:
            inv_freq = _llama3_scale_freqs(inv_freq, cfg.rope_scaling)
    pos = jnp.arange(seq_len, dtype=jnp.float32)
    freqs = jnp.outer(pos, inv_freq)  # (S, rot/2)
    emb = jnp.concatenate([freqs, freqs], axis=-1)  # (S, rot)
    if factor != 1.0:
        return jnp.cos(emb) * factor, jnp.sin(emb) * factor
    return jnp.cos(emb), jnp.sin(emb)


def yarn_band(rot: int, theta: float, scaling: tuple) -> tuple[int, int]:
    """(low, high): the rotary pairs between which YaRN ramps from kept to
    interpolated frequencies. ``dim(r) = rot ln(orig / (2 pi r)) / (2 ln
    theta)`` is the pair that turns ``r`` times over the original length;
    ``low = floor(dim(beta_fast))``, ``high = ceil(dim(beta_slow))``, clipped
    to the table (transformers' ``find_correction_range``, ``truncate``)."""
    _, _, orig, beta_fast, beta_slow, _ = scaling

    def dim(r):
        return rot * math.log(orig / (r * 2 * math.pi)) / (2 * math.log(theta))

    return (max(math.floor(dim(beta_fast)), 0),
            min(math.ceil(dim(beta_slow)), rot - 1))


def _yarn_scale_freqs(inv_freq: jnp.ndarray, theta: float, scaling: tuple):
    """YaRN (transformers' ``_compute_yarn_parameters``): pair ``d`` keeps its
    frequency below ``low``, takes ``1/factor`` of it above ``high`` and a
    linear blend between. ``scaling`` = ("yarn", factor, original_max_position
    _embeddings, beta_fast, beta_slow, attention_factor). Returns (scaled
    inv_freq, attention_factor): cos and sin are both multiplied by it."""
    _, factor, _, _, _, attention_factor = scaling
    low, high = yarn_band(2 * inv_freq.shape[0], theta, scaling)
    if low == high:
        high += 0.001  # transformers: no singularity
    ramp = jnp.clip((jnp.arange(inv_freq.shape[0], dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    return (inv_freq / factor * ramp + inv_freq * (1.0 - ramp),
            float(attention_factor))


def _llama3_scale_freqs(inv_freq: jnp.ndarray, scaling: tuple) -> jnp.ndarray:
    """Llama-3.x RoPE frequency rescaling (transformers'
    ``_compute_llama3_parameters``): long-wavelength components are slowed by
    ``factor``, short ones kept, with a smooth ramp between the two cutoff
    wavelengths. ``scaling`` = ("llama3", factor, low_freq_factor,
    high_freq_factor, original_max_position_embeddings)."""
    kind, factor, low_ff, high_ff, orig = scaling
    if kind != "llama3":
        raise ValueError(f"unsupported rope_scaling type {kind!r}")
    low_wavelen = orig / low_ff
    high_wavelen = orig / high_ff
    wavelen = 2.0 * jnp.pi / inv_freq
    smooth = (orig / wavelen - low_ff) / (high_ff - low_ff)
    smoothed = (1.0 - smooth) * inv_freq / factor + smooth * inv_freq
    scaled = jnp.where(wavelen > low_wavelen, inv_freq / factor,
                       jnp.where(wavelen < high_wavelen, inv_freq, smoothed))
    return scaled


def _rotate_half(x):
    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)


def deinterleave_pairs(x):
    """(..., 2n) lanes (x0, x1, x2, ...) -> (x0, x2, ..., x1, x3, ...): after
    it, the half-split rotation of :func:`apply_rotary` IS the rotation of
    the interleaved pairs (2i, 2i+1) by frequency i (``rope_interleave``).
    Applied to queries and keys alike, so their dot products are the pair
    rotation's."""
    return jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)


def apply_rotary(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray, rot: int) -> jnp.ndarray:
    """Apply rotary embedding to the first ``rot`` dims of the head dimension.

    x: (B, S, H, hd); cos/sin: (S, rot). Partial rotary (rot < hd) is the GPT-NeoX
    ``rotary_pct`` path; Qwen2 uses rot == hd.
    """
    c = cos[None, :, None, :].astype(x.dtype)
    s = sin[None, :, None, :].astype(x.dtype)
    if rot == x.shape[-1]:
        return x * c + _rotate_half(x) * s
    x_rot, x_pass = x[..., :rot], x[..., rot:]
    x_rot = x_rot * c + _rotate_half(x_rot) * s
    return jnp.concatenate([x_rot, x_pass], axis=-1)


def _layernorm(x, scale, bias, eps):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    out = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (out * scale + bias).astype(x.dtype)


def _rmsnorm(x, scale, eps):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return ((xf * jax.lax.rsqrt(var + eps)).astype(x.dtype)) * scale


def _norm(cfg: ModelConfig, x, scale, bias):
    if cfg.family == "gpt_neox":
        return _layernorm(x, scale, bias, cfg.norm_eps)
    return _rmsnorm(x, scale, cfg.norm_eps)


def _stats_block_size(s: int, requested: Optional[int]) -> int:
    """Query-block length for the streaming stats path. ``None`` auto-picks the
    largest sublane-friendly divisor of S; explicit sizes must divide S; 0 (or
    a full-length block) selects the single-block path, which is exactly the
    old full-probs formulation."""
    if requested is not None:
        if requested == 0:
            return s
        if s % requested:
            raise ValueError(f"stats_block {requested} must divide seq len {s}")
        return requested
    for q in (128, 64, 32, 16, 8):
        if s % q == 0 and q < s:
            return q
    return s


def attention(cfg: ModelConfig, lp: dict, x: jnp.ndarray, cos, sin,
              capture_stats: bool,
              tp_axis: Optional[str] = None,
              stats_block: Optional[int] = None,
              return_kv: bool = False):
    """Eager-math attention (explicit softmax) with optional reduced-stat capture.

    The explicit-softmax formulation is what lets importance statistics fall out of
    the same pass (the constraint the reference hit with SDPA at
    ``last_row_exp.py:93-95``). XLA fuses the mask+softmax chain; the matmuls hit
    the MXU with fp32 accumulation.

    The stats path STREAMS query blocks (``stats_block`` rows at a time): each
    block's probabilities are materialized at (B, H, q_blk, S), its column sum
    accumulated, and the block discarded — peak memory drops S/q_blk-fold vs
    the (B, H, S, S) tensor while every importance statistic (per-head column
    means + last rows) stays exact. The softmax math per query row is identical
    to the full-probs formulation (rows are complete — no online rescaling), so
    ``stats_block=0`` (single block) IS the old path and serves as the oracle
    in tests. This is SURVEY section 7 hard-part #1 solved at the memory level.

    Head counts derive from the *weight shapes*, not the config, so the same code
    runs a tensor-parallel shard: with q/k/v columns split head-contiguously
    along ``tp_axis``, each device attends over its local heads and the row-split
    output projection's partial product is ``psum``-reduced across the axis
    (Megatron-style column/row pairing, expressed as a shard_map collective).
    """
    b, s, d = x.shape
    hd = cfg.head_dim
    h, kv = lp["wq"].shape[-1] // hd, lp["wk"].shape[-1] // hd  # local heads

    q = (x @ lp["wq"]).reshape(b, s, h, hd)
    k = (x @ lp["wk"]).reshape(b, s, kv, hd)
    v = (x @ lp["wv"]).reshape(b, s, kv, hd)
    if "bq" in lp:
        q = q + lp["bq"].reshape(h, hd)
        k = k + lp["bk"].reshape(kv, hd)
        v = v + lp["bv"].reshape(kv, hd)

    q = apply_rotary(q, cos, sin, cfg.rotary_dim)
    k = apply_rotary(k, cos, sin, cfg.rotary_dim)
    # the cacheable K/V: post-rotary, PRE-GQA-repeat (the cache stores
    # num_kv_heads — decode_attention re-broadcasts per query group)
    cache_kv = (k, v) if return_kv else None

    def project_out(out, stats):
        """The shared output epilogue: row-split projection, tp reduction,
        bias — one copy for the kernel, XLA, and blocked-scan paths."""
        out = out.reshape(b, s, h * hd) @ lp["wo"]
        if tp_axis is not None:
            out = jax.lax.psum(out, tp_axis)
        if "bo" in lp:
            out = out + lp["bo"]
        return (out, stats, cache_kv) if return_kv else (out, stats)

    from .flash_attention import (causal_attention, causal_attention_stats,
                                  kernel_plan)

    attn_plan = kernel_plan(s, h, kv, hd,
                            itemsize=jnp.dtype(x.dtype).itemsize)
    use_kernel = attn_plan is not None
    if not capture_stats:
        # Hot path. On TPU at S <= 1024 the whole-S Pallas kernel (one
        # (batch, head) score matrix per grid step, entirely in VMEM) measures
        # ~2.4x XLA's fused attention at the flagship's hd=64 shapes and
        # ~3.4x at qwen2-1.5b's hd=128; longer sequences (S=2048, the
        # reference's own Pythia window) and wider rows (llama-1b) take the
        # query-blocked / head-group-split kernel (models/flash_attention.py);
        # shapes outside both envelopes use XLA's fused path (flash-style
        # schedule, no O(S^2) HBM probs, native GQA). This is the analogue of
        # the reference's
        # SDPA instance for quantized forwards (pythia_model.py:25) while the
        # stats branch below replaces its second, eager-attention model
        # (last_row_exp.py:68).
        if use_kernel:
            return project_out(causal_attention(q, k, v, plan=attn_plan), None)
        return project_out(
            jax.nn.dot_product_attention(q, k, v, is_causal=True), None)

    if stats_block is None and use_kernel:
        # fused stats capture: col_sum and last_row read directly off the
        # in-VMEM probability matrix (the blocked-scan path below stays as
        # the portable implementation and, at stats_block=0, the oracle)
        out, stats = causal_attention_stats(q, k, v, plan=attn_plan)
        return project_out(out, stats)

    rep = h // kv
    if rep > 1:  # grouped-query attention: repeat KV heads
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)

    q_blk = _stats_block_size(s, stats_block)
    inv_scale = 1.0 / jnp.sqrt(jnp.asarray(hd, jnp.float32))
    neg_inf = jnp.finfo(jnp.float32).min
    key_pos = jnp.arange(s)

    def scores_of(q_rows, row_pos):
        sc = jnp.einsum("bqhd,bthd->bhqt", q_rows, k,
                        preferred_element_type=jnp.float32) * inv_scale
        mask = row_pos[:, None] >= key_pos[None, :]
        return jnp.where(mask[None, None], sc, neg_inf)

    if q_blk == s:  # single block == the full-probs formulation (oracle path)
        probs = jax.nn.softmax(scores_of(q, key_pos), axis=-1)  # (B, H, S, S)
        out = jnp.einsum("bhqt,bthd->bqhd", probs.astype(x.dtype), v,
                         preferred_element_type=jnp.float32).astype(x.dtype)
        col_sum = jnp.sum(probs, axis=2)
        last_row = probs[:, :, -1, :]
    else:
        q_blocks = q.reshape(b, s // q_blk, q_blk, h, hd).transpose(1, 0, 2, 3, 4)

        def body(col_acc, xs):
            q_rows, blk = xs
            rows = blk * q_blk + jnp.arange(q_blk)
            probs_blk = jax.nn.softmax(scores_of(q_rows, rows), axis=-1)
            out_blk = jnp.einsum("bhqt,bthd->bqhd", probs_blk.astype(x.dtype), v,
                                 preferred_element_type=jnp.float32
                                 ).astype(x.dtype)
            return col_acc + jnp.sum(probs_blk, axis=2), out_blk

        col_sum, outs = jax.lax.scan(
            body, jnp.zeros((b, h, s), jnp.float32),
            (q_blocks, jnp.arange(s // q_blk)))
        out = outs.transpose(1, 0, 2, 3, 4).reshape(b, s, h, hd)
        # the final causal row sees every key — one O(S) softmax, no mask
        last_row = jax.nn.softmax(
            jnp.einsum("bhd,bthd->bht", q[:, -1], k,
                       preferred_element_type=jnp.float32) * inv_scale, axis=-1)

    return project_out(out, (col_sum / s, last_row))  # stats (B, H, S) each


@jax.named_scope("mlp")
def mlp(cfg: ModelConfig, lp: dict, x: jnp.ndarray,
        tp_axis: Optional[str] = None) -> jnp.ndarray:
    """MLP; with ``tp_axis`` set, the hidden (F) axis is column-split per device
    and the row-split down-projection is ``psum``-reduced (biases that live on
    the model axis — ``b_in`` — are local; output biases are added post-psum)."""
    if cfg.family == "gpt_neox":
        hidden = x @ lp["w_in"] + lp["b_in"]
        hidden = jax.nn.gelu(hidden, approximate=False)
        out = hidden @ lp["w_out"]
        if tp_axis is not None:
            out = jax.lax.psum(out, tp_axis)
        return out + lp["b_out"]
    out = (jax.nn.silu(x @ lp["w_gate"]) * (x @ lp["w_up"])) @ lp["w_down"]
    if tp_axis is not None:
        out = jax.lax.psum(out, tp_axis)
    return out


def block(cfg: ModelConfig, lp: dict, hidden: jnp.ndarray, cos, sin,
          capture_stats: bool,
          tp_axis: Optional[str] = None,
          stats_block: Optional[int] = None,
          return_kv: bool = False):
    """One decoder block. GPT-NeoX: parallel residual; Qwen2: sequential.
    With ``return_kv`` the post-rotary per-layer K/V ride along (the prefill
    path fills the decode cache from them); returns (hidden, stats[, (k, v)]).
    """
    if cfg.family == "gpt_neox":
        attn_in = _layernorm(hidden, lp["ln1_scale"], lp["ln1_bias"], cfg.norm_eps)
        attn_out, stats, *kv = attention(cfg, lp, attn_in, cos, sin, capture_stats,
                                         tp_axis, stats_block, return_kv)
        mlp_in = _layernorm(hidden, lp["ln2_scale"], lp["ln2_bias"], cfg.norm_eps)
        out = hidden + attn_out + mlp(cfg, lp, mlp_in, tp_axis)
        return (out, stats, kv[0]) if return_kv else (out, stats)
    attn_in = _rmsnorm(hidden, lp["ln1_scale"], cfg.norm_eps)
    attn_out, stats, *kv = attention(cfg, lp, attn_in, cos, sin, capture_stats,
                                     tp_axis, stats_block, return_kv)
    hidden = hidden + attn_out
    mlp_in = _rmsnorm(hidden, lp["ln2_scale"], cfg.norm_eps)
    out = hidden + mlp(cfg, lp, mlp_in, tp_axis)
    return (out, stats, kv[0]) if return_kv else (out, stats)


def embed(params: dict, input_ids: jnp.ndarray) -> jnp.ndarray:
    return jnp.take(params["embed"], input_ids, axis=0)


def unembed(cfg: ModelConfig, params: dict, hidden: jnp.ndarray) -> jnp.ndarray:
    """Final norm + LM head -> fp32 logits."""
    post = _norm(cfg, hidden, params["final_norm_scale"], params.get("final_norm_bias", 0.0))
    head = params["embed"].T if cfg.tie_word_embeddings else params["lm_head"]
    return jnp.einsum("bsd,dv->bsv", post, head, preferred_element_type=jnp.float32)


def _slice_layers(layers: dict, start: int, stop: int) -> dict:
    return {k: v[start:stop] for k, v in layers.items()}


def run_layers(cfg: ModelConfig, params: dict, hidden: jnp.ndarray, *,
               start: int = 0, stop: Optional[int] = None,
               boundary_fn: Optional[Callable] = None,
               capture_stats: bool = False,
               collect_hidden: bool = False,
               collect_kv: bool = False,
               stats_block: Optional[int] = None):
    """Run decoder layers [start, stop) over ``hidden`` via one lax.scan.

    start/stop are static (jit caches one executable per segment); ``boundary_fn``
    receives the *global* layer index and the post-block hidden state — the same
    interception point as the reference's ``if i == layer_of_interest`` edit
    (``qwen_layer_wise.py:54``), but jit-safe.

    Returns (hidden, aux) where aux holds optional per-layer stats/hiddens and,
    with ``collect_kv``, the stacked post-rotary K/V the decode cache is
    prefilled from (aux["kv"] = (k, v), each (L, B, S, KV, hd)).
    """
    stop = cfg.num_layers if stop is None else stop
    if not (0 <= start <= stop <= cfg.num_layers):
        raise ValueError(
            f"layer segment [{start}, {stop}) out of range for {cfg.num_layers} layers")
    seq_len = hidden.shape[1]
    cos, sin = precompute_rope(cfg, seq_len)
    layer_stack = _slice_layers(params["layers"], start, stop)
    idxs = jnp.arange(start, stop)

    def body(h, xs):
        lp, idx = xs
        h, stats, *kv = block(cfg, lp, h, cos, sin, capture_stats,
                              stats_block=stats_block, return_kv=collect_kv)
        if boundary_fn is not None:
            h = boundary_fn(idx, h)
        out = (stats if capture_stats else None, h if collect_hidden else None,
               kv[0] if collect_kv else None)
        return h, out

    hidden, (stats, hiddens, kvs) = jax.lax.scan(body, hidden, (layer_stack, idxs))
    aux = {}
    if capture_stats:
        aux["stats"] = AttnStats(col_mean=stats[0], last_row=stats[1])
    if collect_hidden:
        aux["hiddens"] = hiddens  # (L, B, S, D), post-boundary_fn
    if collect_kv:
        aux["kv"] = kvs  # ((L, B, S, KV, hd), (L, B, S, KV, hd))
    return hidden, aux


def _cast_params(params: dict, compute_dtype) -> dict:
    """Cast floating params to ``compute_dtype``; None = keep as stored, so a
    bfloat16 pytree runs the MXU's native bf16 path end-to-end (NLL math stays
    fp32 regardless: ``unembed`` requests fp32 logits and ``_masked_ce``
    upcasts)."""
    if compute_dtype is None:
        return params
    return jax.tree_util.tree_map(
        lambda a: a.astype(compute_dtype)
        if jnp.issubdtype(a.dtype, jnp.floating) else a, params)


def forward(cfg: ModelConfig, params: dict, input_ids: jnp.ndarray, *,
            boundary_fn: Optional[Callable] = None,
            capture_stats: bool = False,
            collect_hidden: bool = False,
            compute_dtype: Optional[jnp.dtype] = None,
            stats_block: Optional[int] = None):
    """Full forward: ids -> logits (fp32), optionally with attention stats/hiddens.

    Mirrors the reference's manual loop (embed -> rotary -> layers -> final norm ->
    head -> logits; ``qwen_layer_wise.py:78-104``) as one jit-compiled function.
    """
    params = _cast_params(params, compute_dtype)
    if cfg.is_hybrid:
        from .hybrid import forward_hybrid, refuse_beyond_kv_rows

        if boundary_fn is not None or capture_stats or collect_hidden:
            refuse_beyond_kv_rows(
                cfg, "forward() with a boundary hook, attention statistics "
                     "or collected hiddens (the sweep drivers)")
        return forward_hybrid(cfg, params, input_ids), {}
    hidden = embed(params, input_ids)
    hidden, aux = run_layers(cfg, params, hidden, boundary_fn=boundary_fn,
                             capture_stats=capture_stats,
                             collect_hidden=collect_hidden,
                             stats_block=stats_block)
    logits = unembed(cfg, params, hidden)
    return logits, aux


def run_layers_from_ids(cfg: ModelConfig, params: dict, input_ids: jnp.ndarray, *,
                        capture_stats: bool = False,
                        compute_dtype: Optional[jnp.dtype] = None,
                        stats_block: Optional[int] = None):
    """Prefix pass for sweep drivers: embed -> all layers, collecting every
    post-block hidden state, WITHOUT the final norm/unembed (suffix runs redo the
    tail from a cached boundary activation, so logits here would be dead compute).

    Compute dtype follows the params pytree (pass fp32 params for reference-exact
    math; bf16 params keep the sweep on the MXU's native bf16 path) unless
    ``compute_dtype`` overrides it.
    """
    params = _cast_params(params, compute_dtype)
    hidden = embed(params, input_ids)
    return run_layers(cfg, params, hidden, capture_stats=capture_stats,
                      collect_hidden=True, stats_block=stats_block)


# ---------------------------------------------------------------------------
# KV-cached incremental decode: prefill fills the cache for the prompt, then
# decode_step appends ONE position per call — O(1) work per emitted token
# instead of the O(S) full re-forward the evaluation entry points do.
# ---------------------------------------------------------------------------


class KVCache(NamedTuple):
    """Per-layer key/value cache for incremental decode.

    k, v: (L, B, capacity, KV, hd) — post-rotary keys/values, stored at
        ``num_kv_heads`` (GQA caches the grouped heads; the decode attention
        re-broadcasts them per query group). The leading layer axis matches
        the stacked-parameter convention, so the cache rides the same
        ``lax.scan`` as the layer stack.
    length: () int32 — number of valid positions, i.e. the next write slot.
        Dynamic under jit: one executable serves every fill level of a given
        (batch, capacity) shape.
    """

    k: jnp.ndarray
    v: jnp.ndarray
    length: jnp.ndarray

    @property
    def capacity(self) -> int:
        return self.k.shape[2]


def init_cache(cfg: ModelConfig, batch: int, capacity: int,
               dtype=jnp.float32) -> KVCache:
    """An empty cache for ``batch`` sequences of up to ``capacity`` tokens."""
    shape = (cfg.num_layers, batch, capacity, cfg.num_kv_heads, cfg.head_dim)
    return KVCache(jnp.zeros(shape, dtype), jnp.zeros(shape, dtype),
                   jnp.zeros((), jnp.int32))


def cache_state_dict(cache) -> dict:
    """Snapshot a decode cache as host numpy arrays keyed ``k``/``v``/
    ``length`` — the serializable form the recovery checkpoint stores. Takes
    either a :class:`KVCache` or the split runtime's ``{"k","v","length"}``
    dict (both carry the position offset in ``length``)."""
    if isinstance(cache, dict):
        k, v, length = cache["k"], cache["v"], cache["length"]
    else:
        k, v, length = cache.k, cache.v, cache.length
    return {"k": np.asarray(k), "v": np.asarray(v),
            "length": np.asarray(length, np.int32)}


def cache_from_state_dict(state: dict) -> dict:
    """Rehydrate :func:`cache_state_dict` output to the on-device
    ``{"k","v","length"}`` cache dict every decode runtime consumes (wrap in
    :class:`KVCache` for the raw ``decode_step`` entry point)."""
    return {"k": jnp.asarray(state["k"]), "v": jnp.asarray(state["v"]),
            "length": jnp.asarray(state["length"], jnp.int32)}


@graph_contract("transformer.prefill", collectives={})
def prefill(cfg: ModelConfig, params: dict, input_ids: jnp.ndarray,
            capacity: int, *,
            boundary_fn: Optional[Callable] = None,
            compute_dtype: Optional[jnp.dtype] = None):
    """Full forward over the prompt that also fills the decode cache.

    Returns (logits (B, S, V) fp32, KVCache with length = S). ``capacity`` is
    static — it fixes the cache buffers' shape, so every later ``decode_step``
    reuses one executable regardless of how full the cache is.
    """
    s = input_ids.shape[1]
    if not 0 < s <= capacity:
        raise ValueError(f"prompt length {s} must be in [1, capacity={capacity}]")
    params = _cast_params(params, compute_dtype)
    if cfg.is_hybrid:
        from .hybrid import prefill_hybrid, refuse_beyond_kv_rows

        if boundary_fn is not None:
            refuse_beyond_kv_rows(cfg, "prefill() with a boundary hook")
        return prefill_hybrid(cfg, params, input_ids, capacity)
    hidden = embed(params, input_ids)
    hidden, aux = run_layers(cfg, params, hidden, boundary_fn=boundary_fn,
                             collect_kv=True)
    logits = unembed(cfg, params, hidden)
    k, v = aux["kv"]  # (L, B, S, KV, hd) each
    pad = ((0, 0), (0, 0), (0, capacity - s), (0, 0), (0, 0))
    return logits, KVCache(jnp.pad(k, pad), jnp.pad(v, pad),
                           jnp.asarray(s, jnp.int32))


def _attention_decode(cfg: ModelConfig, lp: dict, x: jnp.ndarray,
                      cos_t, sin_t, k_cache, v_cache, pos,
                      tp_axis: Optional[str] = None):
    """One layer's attention for a single decode position: project the (B, 1, D)
    hidden, rotate at ``pos``, write the new K/V into the cache, then attend
    q_len=1 against the length-masked cache. Returns (out, k_cache, v_cache)."""
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
    q = apply_rotary(q, cos_t, sin_t, cfg.rotary_dim)
    k = apply_rotary(k, cos_t, sin_t, cfg.rotary_dim)
    k_cache = jax.lax.dynamic_update_slice(
        k_cache, k.astype(k_cache.dtype), (0, pos, 0, 0))
    v_cache = jax.lax.dynamic_update_slice(
        v_cache, v.astype(v_cache.dtype), (0, pos, 0, 0))

    from .flash_attention import decode_attention

    out = decode_attention(q, k_cache, v_cache, pos + 1)
    out = out.reshape(b, s1, h * hd) @ lp["wo"]
    if tp_axis is not None:
        out = jax.lax.psum(out, tp_axis)
    if "bo" in lp:
        out = out + lp["bo"]
    return out, k_cache, v_cache


def block_decode(cfg: ModelConfig, lp: dict, hidden: jnp.ndarray,
                 cos_t, sin_t, k_cache, v_cache, pos,
                 tp_axis: Optional[str] = None):
    """The cache-carrying twin of :func:`block` for one decode position.
    ``k_cache``/``v_cache`` are this layer's (B, capacity, KV, hd) buffers;
    ``pos`` is the (traced) position being written. Returns
    (hidden, k_cache, v_cache)."""
    if cfg.family == "gpt_neox":
        attn_in = _layernorm(hidden, lp["ln1_scale"], lp["ln1_bias"], cfg.norm_eps)
        attn_out, k_cache, v_cache = _attention_decode(
            cfg, lp, attn_in, cos_t, sin_t, k_cache, v_cache, pos, tp_axis)
        mlp_in = _layernorm(hidden, lp["ln2_scale"], lp["ln2_bias"], cfg.norm_eps)
        return (hidden + attn_out + mlp(cfg, lp, mlp_in, tp_axis),
                k_cache, v_cache)
    attn_in = _rmsnorm(hidden, lp["ln1_scale"], cfg.norm_eps)
    attn_out, k_cache, v_cache = _attention_decode(
        cfg, lp, attn_in, cos_t, sin_t, k_cache, v_cache, pos, tp_axis)
    hidden = hidden + attn_out
    mlp_in = _rmsnorm(hidden, lp["ln2_scale"], cfg.norm_eps)
    return hidden + mlp(cfg, lp, mlp_in, tp_axis), k_cache, v_cache


def _attention_verify(cfg: ModelConfig, lp: dict, x: jnp.ndarray,
                      cos_t, sin_t, k_cache, v_cache, pos,
                      tp_axis: Optional[str] = None):
    """The K-position twin of :func:`_attention_decode` for speculative
    verify: project the (B, K, D) hidden block, rotate at positions
    ``pos .. pos+K-1`` (``cos_t``/``sin_t`` are the (K, rot) row slices),
    write all K new K/V rows into the cache at ``pos``, then attend q_len=K
    causally against the cache. Returns (out, k_cache, v_cache)."""
    b, kq, d = x.shape
    hd = cfg.head_dim
    h, kv = lp["wq"].shape[-1] // hd, lp["wk"].shape[-1] // hd
    q = (x @ lp["wq"]).reshape(b, kq, h, hd)
    k = (x @ lp["wk"]).reshape(b, kq, kv, hd)
    v = (x @ lp["wv"]).reshape(b, kq, kv, hd)
    if "bq" in lp:
        q = q + lp["bq"].reshape(h, hd)
        k = k + lp["bk"].reshape(kv, hd)
        v = v + lp["bv"].reshape(kv, hd)
    q = apply_rotary(q, cos_t, sin_t, cfg.rotary_dim)
    k = apply_rotary(k, cos_t, sin_t, cfg.rotary_dim)
    k_cache = jax.lax.dynamic_update_slice(
        k_cache, k.astype(k_cache.dtype), (0, pos, 0, 0))
    v_cache = jax.lax.dynamic_update_slice(
        v_cache, v.astype(v_cache.dtype), (0, pos, 0, 0))

    from .flash_attention import verify_attention

    out = verify_attention(q, k_cache, v_cache, pos)
    out = out.reshape(b, kq, h * hd) @ lp["wo"]
    if tp_axis is not None:
        out = jax.lax.psum(out, tp_axis)
    if "bo" in lp:
        out = out + lp["bo"]
    return out, k_cache, v_cache


def block_verify(cfg: ModelConfig, lp: dict, hidden: jnp.ndarray,
                 cos_t, sin_t, k_cache, v_cache, pos,
                 tp_axis: Optional[str] = None):
    """The cache-carrying twin of :func:`block_decode` for a K-position
    speculative-verify block. ``hidden`` is (B, K, D); ``pos`` is the (traced)
    first position being written. Returns (hidden, k_cache, v_cache)."""
    if cfg.family == "gpt_neox":
        attn_in = _layernorm(hidden, lp["ln1_scale"], lp["ln1_bias"], cfg.norm_eps)
        attn_out, k_cache, v_cache = _attention_verify(
            cfg, lp, attn_in, cos_t, sin_t, k_cache, v_cache, pos, tp_axis)
        mlp_in = _layernorm(hidden, lp["ln2_scale"], lp["ln2_bias"], cfg.norm_eps)
        return (hidden + attn_out + mlp(cfg, lp, mlp_in, tp_axis),
                k_cache, v_cache)
    attn_in = _rmsnorm(hidden, lp["ln1_scale"], cfg.norm_eps)
    attn_out, k_cache, v_cache = _attention_verify(
        cfg, lp, attn_in, cos_t, sin_t, k_cache, v_cache, pos, tp_axis)
    hidden = hidden + attn_out
    mlp_in = _rmsnorm(hidden, lp["ln2_scale"], cfg.norm_eps)
    return hidden + mlp(cfg, lp, mlp_in, tp_axis), k_cache, v_cache


@graph_contract("transformer.decode_step", collectives={})
def decode_step(cfg: ModelConfig, params: dict, cache: KVCache,
                token_ids: jnp.ndarray, *,
                boundary_fn: Optional[Callable] = None,
                compute_dtype: Optional[jnp.dtype] = None):
    """Append one position: (B,) or (B, 1) token ids -> (logits (B, V) fp32,
    updated cache). The RoPE tables are built for the full capacity and the
    current row is dynamically sliced at ``cache.length``, so the same
    machinery (partial rotary, llama3 scaling) applies at a position offset
    without retracing; jit this per (batch, capacity) shape and every emitted
    token reuses the one executable.
    """
    params = _cast_params(params, compute_dtype)
    if cfg.is_hybrid:
        from .hybrid import decode_step_hybrid, refuse_beyond_kv_rows

        if boundary_fn is not None:
            refuse_beyond_kv_rows(cfg, "decode_step() with a boundary hook")
        return decode_step_hybrid(cfg, params, cache, token_ids)
    if token_ids.ndim == 1:
        token_ids = token_ids[:, None]
    hidden = embed(params, token_ids)  # (B, 1, D)
    pos = cache.length
    cos, sin = precompute_rope(cfg, cache.capacity)
    cos_t = jax.lax.dynamic_slice_in_dim(cos, pos, 1)
    sin_t = jax.lax.dynamic_slice_in_dim(sin, pos, 1)
    idxs = jnp.arange(cfg.num_layers)

    def body(h, xs):
        lp, kc, vc, idx = xs
        h, kc, vc = block_decode(cfg, lp, h, cos_t, sin_t, kc, vc, pos)
        if boundary_fn is not None:
            h = boundary_fn(idx, h)
        return h, (kc, vc)

    hidden, (k_new, v_new) = jax.lax.scan(
        body, hidden, (params["layers"], cache.k, cache.v, idxs))
    logits = unembed(cfg, params, hidden)[:, -1]  # (B, V) fp32
    return logits, KVCache(k_new, v_new, pos + 1)


def nll_from_logits(logits: jnp.ndarray, target_ids: jnp.ndarray,
                    per_example: bool = False) -> jnp.ndarray:
    """Shifted cross-entropy with -100 masking — the reference's NLL definition
    (``qwen_layer_wise.py:28-40``): logits[:, :-1] vs targets[:, 1:], mean over
    valid positions (over the whole batch, or per row for the batched-over-ratios
    scheme of ``pythia_model.py:36-54``).
    """
    return _masked_ce(logits[:, :-1, :], target_ids[:, 1:], per_example)


def _masked_ce(logits: jnp.ndarray, targets: jnp.ndarray,
               per_example: bool) -> jnp.ndarray:
    """Mean cross-entropy over positions where ``targets != -100``; logits and
    targets are already shift-aligned (logits[b, i] predicts targets[b, i])."""
    valid = targets != -100
    safe_targets = jnp.where(valid, targets, 0)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    tok_nll = -jnp.take_along_axis(logp, safe_targets[..., None], axis=-1)[..., 0]
    tok_nll = jnp.where(valid, tok_nll, 0.0)
    axes = (1,) if per_example else None
    return jnp.sum(tok_nll, axis=axes) / jnp.maximum(jnp.sum(valid, axis=axes), 1)


def _vocab_block_size(v: int, target: int = 8192) -> int:
    """Largest divisor of ``v`` at most ``target`` via the smallest block
    count; ``v`` itself when the vocab is small or has no useful divisor."""
    if v <= 2 * target:
        return v
    for nb in range(2, 129):
        if v % nb == 0 and v // nb <= target:
            return v // nb
    return v


def nll_tail(cfg: ModelConfig, params: dict, hidden: jnp.ndarray,
             target_ids: jnp.ndarray, tail: int,
             per_example: bool = False,
             vocab_block: Optional[int] = None) -> jnp.ndarray:
    """``nll_from_logits(unembed(cfg, params, hidden), target_ids)`` with the
    unembed restricted to the ``tail`` scoring positions.

    The sliding-window recipe masks every target outside the last ``trg_len``
    positions to -100 (``Qwen2-0.5B/main.py:152-156``), so with stride 32 only
    ~6% of a 512-token window is ever scored — yet the full-vocab unembed
    (151k columns for Qwen2) dominates suffix FLOPs. Valid targets occupy the
    last ``trg_len`` positions; their (shifted) logits come from hidden positions
    ``[S - trg_len - 1, S - 2]``, so unembedding the last ``min(tail, S-1)``
    pre-final positions is exact whenever ``tail >= trg_len``. ``tail`` must be
    static (one executable per distinct tail length).

    Large vocabularies stream: the head is processed in ``vocab_block``-column
    blocks with an online logsumexp and in-block target-logit gather, so the
    (rows, V) fp32 logits tensor — 9.6 GB for a ratio-vmapped 128-window
    Qwen2 group — never materializes. Same FLOPs on the MXU, a fraction of
    the HBM traffic. ``vocab_block=None`` auto-picks a divisor of V (~8k);
    ``0`` forces the single-block path, which is exactly the old
    full-logits formulation (the oracle in tests)."""
    s = hidden.shape[1]
    tail = min(int(tail), s - 1)
    h = hidden[:, s - 1 - tail: s - 1]
    tgt = target_ids[:, s - tail:]
    vb = (_vocab_block_size(cfg.vocab_size) if vocab_block is None
          else (cfg.vocab_size if vocab_block == 0 else vocab_block))
    if vb >= cfg.vocab_size:
        return _masked_ce(unembed(cfg, params, h), tgt, per_example)
    if cfg.vocab_size % vb:
        raise ValueError(f"vocab_block {vb} must divide vocab {cfg.vocab_size}")
    return _blocked_ce(cfg, params, h, tgt, per_example, vb)


def _blocked_ce(cfg: ModelConfig, params: dict, hidden: jnp.ndarray,
                targets: jnp.ndarray, per_example: bool, vb: int) -> jnp.ndarray:
    """Streaming cross-entropy: final norm -> per-block partial logits ->
    online (max, sumexp, target-logit) accumulation. The head tensor is
    re-viewed blockwise in its OWN layout (no transpose copy of the 272 MB
    embedding for tied heads)."""
    b, t, d = hidden.shape
    post = _norm(cfg, hidden, params["final_norm_scale"],
                 params.get("final_norm_bias", 0.0)).reshape(b * t, d)
    n = b * t
    tgt = targets.reshape(n)
    valid = tgt != -100
    safe_tgt = jnp.where(valid, tgt, 0)
    nb = cfg.vocab_size // vb
    if cfg.tie_word_embeddings:
        emb = params["embed"]  # (V, D): block rows, no transpose copy

        def piece_of(i):
            blk = jax.lax.dynamic_slice_in_dim(emb, i * vb, vb, axis=0)
            return jnp.einsum("nd,vd->nv", post, blk,
                              preferred_element_type=jnp.float32)
    else:
        head = params["lm_head"]  # (D, V): block columns in place

        def piece_of(i):
            blk = jax.lax.dynamic_slice_in_dim(head, i * vb, vb, axis=1)
            return jnp.einsum("nd,dv->nv", post, blk,
                              preferred_element_type=jnp.float32)

    init = (jnp.full((n,), -jnp.inf, jnp.float32),  # running max
            jnp.zeros((n,), jnp.float32),           # running sum of exp
            jnp.zeros((n,), jnp.float32))           # target logit

    def body(carry, i):
        m, s_acc, t_logit = carry
        piece = piece_of(i)  # (N, vb) fp32, one block's logits
        local_max = jnp.max(piece, axis=-1)
        m_new = jnp.maximum(m, local_max)
        s_acc = (s_acc * jnp.exp(m - m_new)
                 + jnp.sum(jnp.exp(piece - m_new[:, None]), axis=-1))
        local = safe_tgt - i * vb
        in_blk = (local >= 0) & (local < vb)
        val = jnp.take_along_axis(
            piece, jnp.clip(local, 0, vb - 1)[:, None], axis=1)[:, 0]
        t_logit = jnp.where(in_blk, val, t_logit)
        return (m_new, s_acc, t_logit), None

    (m, s_acc, t_logit), _ = jax.lax.scan(body, init, jnp.arange(nb))
    tok_nll = jnp.where(valid, jnp.log(s_acc) + m - t_logit, 0.0)
    tok_nll = tok_nll.reshape(b, t)
    valid = valid.reshape(b, t)
    axes = (1,) if per_example else None
    return jnp.sum(tok_nll, axis=axes) / jnp.maximum(jnp.sum(valid, axis=axes), 1)


def init_params(cfg: ModelConfig, key: jax.Array, dtype=jnp.float32) -> dict:
    """Random init (tests/bench only — environment has no pretrained checkpoints)."""
    if cfg.is_hybrid:
        from .hybrid import init_params_hybrid

        return init_params_hybrid(cfg, key, dtype)
    keys = iter(jax.random.split(key, 32))
    init = lambda *shape: (jax.random.normal(next(keys), shape, jnp.float32) * 0.02).astype(dtype)
    L, D, F = cfg.num_layers, cfg.hidden_size, cfg.intermediate_size
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    layers = {
        "ln1_scale": jnp.ones((L, D), dtype),
        "ln2_scale": jnp.ones((L, D), dtype),
        "wq": init(L, D, H * hd), "wk": init(L, D, KV * hd), "wv": init(L, D, KV * hd),
        "wo": init(L, H * hd, D),
    }
    if cfg.qkv_bias:
        layers.update({
            "bq": jnp.zeros((L, H * hd), dtype), "bk": jnp.zeros((L, KV * hd), dtype),
            "bv": jnp.zeros((L, KV * hd), dtype),
        })
    if cfg.family == "gpt_neox":
        layers.update({
            "ln1_bias": jnp.zeros((L, D), dtype), "ln2_bias": jnp.zeros((L, D), dtype),
            "bo": jnp.zeros((L, D), dtype),
            "w_in": init(L, D, F), "b_in": jnp.zeros((L, F), dtype),
            "w_out": init(L, F, D), "b_out": jnp.zeros((L, D), dtype),
        })
    else:
        layers.update({
            "w_gate": init(L, D, F), "w_up": init(L, D, F), "w_down": init(L, F, D),
        })
    params = {
        "embed": init(cfg.vocab_size, D),
        "layers": layers,
        "final_norm_scale": jnp.ones((D,), dtype),
    }
    if cfg.family == "gpt_neox":
        params["final_norm_bias"] = jnp.zeros((D,), dtype)
    if not cfg.tie_word_embeddings:
        params["lm_head"] = init(D, cfg.vocab_size)
    return params
