"""A stack of two layer kinds: the ``granitemoehybrid`` family's forward,
prefill and decode steps.

The one-block families ride one ``lax.scan`` over a pytree stacked along the
layer axis with K/V as the scanned state. Here a layer is a Mamba-2 mixer
(``models/mamba2.py``: a fixed-size recurrent state and a convolution window
per sequence) or a position-free GQA attention layer (K/V rows, the paged
pool), each followed by the routed + shared expert layer (``models/moe.py``),
so parameters are held PER KIND::

    params = {"embed": (V, D), "final_norm_scale": (D,),
              "mamba": {... stacked along the L_mamba mamba layers},
              "attn":  {... stacked along the L_attn attention layers},
              "moe":   [{...} for each of the L layers]}

(the expert weights are a list, one entry a layer, and not a stack: the
grouped products of a prefill are a kernel call whose operands must be whole
buffers, and a row sliced from a ``(L, E, D, F)`` stack is a 226 MB copy a
tensor a layer, all ten alive at once at the published sizes) and the stack
is walked by a static Python loop over ``cfg.layer_types``: layer ``l`` takes
entry ``l`` of ``moe`` and the next row of its own kind. Every
layer is traced once per executable (ten for the benchmark's one period),
which buys XLA a free hand with each layer's state: row ``j`` of the
``(L_mamba, slots, ...)`` state store is read, updated and written in place,
with no loop-carried copy of the whole store.

Every layer: ``h += residual_multiplier * mixer(rms(h; w1))`` then
``h += residual_multiplier * (moe(u) + shared(u))``, ``u = rms(h; w2)``;
``h0 = embed[ids] * embedding_multiplier``; logits ``= rms(h_L; w_f) @
embed.T / logits_scaling``. Attention has no rotary of any kind and scores
``q k^T * attention_multiplier``: the kernels and ``decode_attention`` all
scale by ``1/sqrt(head_dim)``, so ``q`` is multiplied by
``attention_multiplier * sqrt(head_dim)`` once, ahead of them.

What this module does not do, by name, because each needs a snapshot of the
recurrent state that does not exist yet: boundary hooks and attention
statistics (the sweep drivers), the split runtime, speculation, prefix
sharing, quantized KV tiers, checkpoints. :func:`refuse_recurrent_state` is
the one place the refusal is worded.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..lint import graph_contract
from .configs import ModelConfig
from .flash_attention import causal_attention, decode_attention, kernel_plan
from .mamba2 import mamba2_prefill, mamba2_step
from .moe import moe_layer
from .paged_kv import PagePool, _attention_decode_paged
from .transformer import _rmsnorm


class RecurrentStateUnsupported(ValueError):
    """A mechanism that keeps, copies or rolls back a sequence's state as K/V
    rows alone was asked to serve a family whose layers also keep recurrent
    state (Mamba-2's convolution window and SSM state)."""


def refuse_recurrent_state(cfg: ModelConfig, what: str) -> None:
    """Raise for a hybrid config: ``what`` names the mechanism refusing."""
    if cfg.is_hybrid:
        raise RecurrentStateUnsupported(
            f"{what} does not support family {cfg.family!r}: its Mamba-2 "
            f"layers keep recurrent state (a convolution window and an SSM "
            f"state per sequence) beside the K/V rows, and {what} has no "
            f"snapshot of that recurrent state; there is no fallback")


class HybridCache(NamedTuple):
    """The contiguous decode cache of a hybrid stack.

    k, v: (L_attn, B, capacity, KV, hd); length: () int32;
    conv: (L_mamba, B, d_conv-1, conv_dim) float32;
    ssm: (L_mamba, B, H, P, N) float32."""

    k: jnp.ndarray
    v: jnp.ndarray
    length: jnp.ndarray
    conv: jnp.ndarray
    ssm: jnp.ndarray

    @property
    def capacity(self) -> int:
        return self.k.shape[2]


def state_shapes(cfg: ModelConfig, rows: int) -> tuple:
    """Shapes of (conv, ssm) for ``rows`` sequences or slots."""
    return ((cfg.mamba_layers, rows, cfg.mamba_d_conv - 1,
             cfg.mamba_conv_dim),
            (cfg.mamba_layers, rows, cfg.mamba_heads, cfg.mamba_head_dim,
             cfg.mamba_d_state))


def _rms(cfg, x, scale):
    return _rmsnorm(x, scale, cfg.norm_eps)


def _row(tree: dict, j: int) -> dict:
    return {k: v[j] for k, v in tree.items()}


def _kinds(cfg: ModelConfig):
    """(layer, kind, index among its kind) down the stack."""
    seen = {"mamba": 0, "attention": 0}
    for layer, kind in enumerate(cfg.layer_types):
        yield layer, kind, seen[kind]
        seen[kind] += 1


def _qkv(cfg: ModelConfig, lp: dict, x):
    """x (B, S, D) -> q (B, S, H, hd) pre-scaled, k, v (B, S, KV, hd); no
    positions are applied."""
    b, s, _ = x.shape
    hd = cfg.head_dim
    q = (x @ lp["wq"]).reshape(b, s, cfg.num_heads, hd)
    k = (x @ lp["wk"]).reshape(b, s, cfg.num_kv_heads, hd)
    v = (x @ lp["wv"]).reshape(b, s, cfg.num_kv_heads, hd)
    return q * jnp.asarray(cfg.q_prescale, q.dtype), k, v


def _attention_full(cfg: ModelConfig, lp: dict, x):
    """Causal attention over whole sequences -> (out (B, S, D), k, v)."""
    b, s, _ = x.shape
    q, k, v = _qkv(cfg, lp, x)
    plan = kernel_plan(s, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
                       itemsize=jnp.dtype(x.dtype).itemsize)
    out = (causal_attention(q, k, v, plan=plan) if plan is not None
           else jax.nn.dot_product_attention(q, k, v, is_causal=True))
    return out.reshape(b, s, -1) @ lp["wo"], k, v


def _ffn(cfg: ModelConfig, mp: dict, h, active=None):
    """The expert sublayer on h (..., D); returns (h, counts (Eh,))."""
    u = _rms(cfg, h, mp["ln2_scale"])
    out, counts = moe_layer(cfg, mp, u.reshape(-1, u.shape[-1]), active)
    return h + cfg.residual_multiplier * out.reshape(h.shape), counts


def _step_row(cfg: ModelConfig, lp: dict, h, conv_all, ssm_all, j: int):
    """One mamba layer's decode update against row ``j`` of a state store
    (L_mamba, rows, ...): the row is read, updated and written back in place.
    The read and the write stand under ``ssm.step`` with the update: XLA fuses
    them into it, and the fusion is named after the write."""
    with jax.named_scope("ssm.step"):
        conv, ssm = conv_all[j], ssm_all[j]
    out, conv, ssm = mamba2_step(cfg, lp, _rms(cfg, h, lp["ln1_scale"]), conv,
                                 ssm)
    with jax.named_scope("ssm.step"):
        return conv_all.at[j].set(conv), ssm_all.at[j].set(ssm), out


def embed_hybrid(cfg: ModelConfig, params: dict, ids):
    h = jnp.take(params["embed"], ids, axis=0)
    return h * jnp.asarray(cfg.embedding_multiplier, h.dtype)


def unembed_hybrid(cfg: ModelConfig, params: dict, hidden):
    """(..., D) -> float32 logits (..., V) over the tied table."""
    post = _rms(cfg, hidden, params["final_norm_scale"])
    logits = jnp.einsum("...d,vd->...v", post, params["embed"],
                        preferred_element_type=jnp.float32)
    return logits / cfg.logits_scaling


def _walk_full(cfg: ModelConfig, params: dict, ids, collect: bool):
    """Whole sequences through the stack. Returns (hidden (B, S, D), per-kind
    lists of what a decode cache is filled from when ``collect``)."""
    h = embed_hybrid(cfg, params, ids)
    ks, vs, convs, ssms = [], [], [], []
    for layer, kind, j in _kinds(cfg):
        if kind == "mamba":
            lp = _row(params["mamba"], j)
            out, conv, ssm = mamba2_prefill(
                cfg, lp, _rms(cfg, h, lp["ln1_scale"]))
            if collect:
                convs.append(conv)
                ssms.append(ssm)
        else:
            lp = _row(params["attn"], j)
            out, k, v = _attention_full(
                cfg, lp, _rms(cfg, h, lp["ln1_scale"]))
            if collect:
                ks.append(k)
                vs.append(v)
        h = h + cfg.residual_multiplier * out
        h, _ = _ffn(cfg, params["moe"][layer], h)
    return h, (ks, vs, convs, ssms)


def forward_hybrid(cfg: ModelConfig, params: dict, ids):
    """ids (B, S) -> float32 logits (B, S, V)."""
    h, _ = _walk_full(cfg, params, ids, collect=False)
    return unembed_hybrid(cfg, params, h)


def _stack(items: list, shape: tuple, dtype):
    return jnp.stack(items) if items else jnp.zeros(shape, dtype)


def prefill_hybrid(cfg: ModelConfig, params: dict, ids, capacity: int,
                   last_only: bool = False):
    """The prompt's forward that also fills the decode cache: (logits
    (B, S, V) float32 — (B, V) of the last position with ``last_only`` —,
    :class:`HybridCache` with length S)."""
    b, s = ids.shape
    if not 0 < s <= capacity:
        raise ValueError(f"prompt length {s} must be in [1, capacity="
                         f"{capacity}]")
    h, (ks, vs, convs, ssms) = _walk_full(cfg, params, ids, collect=True)
    logits = unembed_hybrid(cfg, params, h[:, -1] if last_only else h)
    kv_shape = (0, b, s, cfg.num_kv_heads, cfg.head_dim)
    conv_shape, ssm_shape = state_shapes(cfg, b)
    pad = ((0, 0), (0, 0), (0, capacity - s), (0, 0), (0, 0))
    return logits, HybridCache(
        jnp.pad(_stack(ks, kv_shape, h.dtype), pad),
        jnp.pad(_stack(vs, kv_shape, h.dtype), pad),
        jnp.asarray(s, jnp.int32),
        _stack(convs, conv_shape, jnp.float32),
        _stack(ssms, ssm_shape, jnp.float32))


def decode_step_hybrid(cfg: ModelConfig, params: dict, cache: HybridCache,
                       token_ids):
    """Append one position to every row of a contiguous cache: token ids (B,)
    or (B, 1) -> (logits (B, V) float32, updated cache)."""
    if token_ids.ndim == 2:
        token_ids = token_ids[:, 0]
    b = token_ids.shape[0]
    pos = cache.length
    h = embed_hybrid(cfg, params, token_ids)                  # (B, D)
    k_all, v_all, conv_all, ssm_all = (cache.k, cache.v, cache.conv,
                                       cache.ssm)
    for layer, kind, j in _kinds(cfg):
        if kind == "mamba":
            lp = _row(params["mamba"], j)
            conv_all, ssm_all, out = _step_row(cfg, lp, h, conv_all, ssm_all,
                                               j)
        else:
            lp = _row(params["attn"], j)
            q, k, v = _qkv(cfg, lp, _rms(cfg, h, lp["ln1_scale"])[:, None])
            kc = jax.lax.dynamic_update_slice(
                k_all[j], k.astype(k_all.dtype), (0, pos, 0, 0))
            vc = jax.lax.dynamic_update_slice(
                v_all[j], v.astype(v_all.dtype), (0, pos, 0, 0))
            out = decode_attention(q, kc, vc, pos + 1).reshape(b, -1) \
                @ lp["wo"]
            k_all, v_all = k_all.at[j].set(kc), v_all.at[j].set(vc)
        h = h + cfg.residual_multiplier * out
        h, _ = _ffn(cfg, params["moe"][layer], h)
    return (unembed_hybrid(cfg, params, h),
            HybridCache(k_all, v_all, pos + 1, conv_all, ssm_all))


@graph_contract("paged.decode_step_hybrid", collectives={},
                donate=lambda ctx: ctx.get("donate_min", 5))
def paged_decode_step_hybrid(cfg: ModelConfig, params: dict, pool_k, pool_v,
                             conv_all, ssm_all, expert_tokens, page_table,
                             lengths, token_ids):
    """The ragged step for a hybrid stack: one position for EVERY slot.

    pool_k/pool_v: (L_attn, num_pages, page_size, KV * hd), addressed by the
    static attention-layer number (paged_kv's flat index); conv_all / ssm_all:
    the per-slot state store, (L_mamba, max_slots, ...) float32; expert_tokens
    (L, Eh) int32, the running count of assignments per held expert, which
    gains this step's over the slots with ``lengths > 0`` (a free slot runs
    token-0 math into the trash page and into its own dead state rows, and is
    not counted). Returns (logits (max_slots, V) float32, pool_k, pool_v,
    conv_all, ssm_all, expert_tokens)."""
    if token_ids.ndim == 2:
        token_ids = token_ids[:, 0]
    active = lengths > 0
    h = embed_hybrid(cfg, params, token_ids)                  # (B, D)
    counts = []
    for layer, kind, j in _kinds(cfg):
        if kind == "mamba":
            lp = _row(params["mamba"], j)
            conv_all, ssm_all, out = _step_row(cfg, lp, h, conv_all, ssm_all,
                                               j)
        else:
            lp = _row(params["attn"], j)
            out, (pool_k, pool_v) = _attention_decode_paged(
                cfg, lp, _rms(cfg, h, lp["ln1_scale"])[:, None], None, None,
                PagePool(pool_k, pool_v), j, page_table, lengths)
            out = out[:, 0]
        h = h + cfg.residual_multiplier * out
        h, c = _ffn(cfg, params["moe"][layer], h, active)
        counts.append(c)
    with jax.named_scope("unembed_sample"):
        logits = unembed_hybrid(cfg, params, h)
    return (logits, pool_k, pool_v, conv_all, ssm_all,
            expert_tokens + jnp.stack(counts))


def init_params_hybrid(cfg: ModelConfig, key: jax.Array,
                       dtype=jnp.float32) -> dict:
    """Random init for tests and smoke runs: normal std 0.02, norm scales
    one; the Mamba-2 scalars take ``mamba_ssm``'s initialisation so that the
    state matters (``A_log = log U[1, 16]``, ``dt_bias = softplus^-1(dt)``
    with ``dt`` log-uniform in [0.001, 0.1], ``D = 1``, the convolution
    uniform in +-1/sqrt(d_conv))."""
    keys = iter(jax.random.split(key, 16 + 8 * cfg.num_layers))

    def init(*shape):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * 0.02).astype(dtype)

    d, hd = cfg.hidden_size, cfg.head_dim
    lm, la, lt = cfg.mamba_layers, cfg.kv_layers, cfg.num_layers
    nh, di, cd = cfg.mamba_heads, cfg.mamba_d_inner, cfg.mamba_conv_dim
    eh, f, fs = cfg.local_experts, cfg.expert_width, cfg.shared_width
    dt = jnp.exp(jax.random.uniform(next(keys), (lm, nh), jnp.float32,
                                    jnp.log(0.001), jnp.log(0.1)))
    return {
        # the tied table at 0.02 / embedding_multiplier: h0 then starts at
        # the other matrices' std and a token's own row does not win every
        # logit by embedding_multiplier * |row|^2
        "embed": (init(cfg.vocab_size, d).astype(jnp.float32)
                  / cfg.embedding_multiplier).astype(dtype),
        "final_norm_scale": jnp.ones((d,), dtype),
        "mamba": {
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
        },
        "attn": {
            "ln1_scale": jnp.ones((la, d), dtype),
            "wq": init(la, d, cfg.num_heads * hd),
            "wk": init(la, d, cfg.num_kv_heads * hd),
            "wv": init(la, d, cfg.num_kv_heads * hd),
            "wo": init(la, cfg.num_heads * hd, d),
        },
        "moe": [{
            "ln2_scale": jnp.ones((d,), dtype),
            "router": init(d, cfg.num_experts),
            "w_gate": init(eh, d, f), "w_up": init(eh, d, f),
            "w_down": init(eh, f, d),
            "shared_gate": init(d, fs), "shared_up": init(d, fs),
            "shared_down": init(fs, d),
        } for _ in range(lt)],
    }
