"""The plain reference: the Qwen2 forward in straightforward ``jax.numpy`` and
float32 at ``default_matmul_precision("highest")`` — no kernels, no cache, no
batching, nothing imported from the program. It follows the published
architecture (sequential residual, RMSNorm, rotary in the HF half-rotation
convention, grouped-query attention with q/k/v biases, SwiGLU, tied unembed).

For a split configuration the reference applies each hop codec's published
round trip (quantize, dequantize) to the hidden state after its cut layer.

``control=True`` is the same forward with every matmul operand rounded
through float8 (e4m3, scaled per token / per output channel): the nearest
precision below the bfloat16 the configurations state. It exists to show that
the comparison that decides ``correct`` fails when the arithmetic is cheaper
than stated; the benchmark's own runs never take it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .rooflines import head_dim

F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


def _f8(x, axis):
    """Round ``x`` through scaled float8 along ``axis`` (absmax scaling)."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / F8_MAX, 1.0)
    return (x / scale).astype(F8).astype(jnp.float32) * scale


def _mm(x, w, control):
    """x (..., K) @ w (K, N) in float32; the control rounds both operands."""
    if control:
        x, w = _f8(x, -1), _f8(w, 0)
    return x @ w


def _rmsnorm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def _rope(m, seq_len):
    hd = head_dim(m)
    inv = 1.0 / (m["rope_theta"] ** (jnp.arange(0, hd, 2, dtype=jnp.float32)
                                     / hd))
    freqs = jnp.outer(jnp.arange(seq_len, dtype=jnp.float32), inv)
    emb = jnp.concatenate([freqs, freqs], axis=-1)
    return jnp.cos(emb), jnp.sin(emb)


def _rotate(x, cos, sin):
    half = x.shape[-1] // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos[:, None, :] + rot * sin[:, None, :]


# -- hop codecs, as published in the configuration (per-token scales) --------

def _hop_int8_per_token(h):
    """Affine int8 over each token's own [min, max]."""
    mn = jnp.min(h, axis=-1, keepdims=True)
    mx = jnp.max(h, axis=-1, keepdims=True)
    scale = (mx - mn) * jnp.float32(1.0 / 255.0)
    safe = jnp.where(scale > 0, scale, 1.0)
    zp = jnp.round(-128.0 - mn / safe)
    q = jnp.clip(jnp.round(h / safe) + zp, -128, 127)
    return jnp.where(scale > 0, (q - zp) * safe, mn)


def _hop_int4_per_token(h):
    """Symmetric int4 on each token's own max-abs."""
    amax = jnp.max(jnp.abs(h), axis=-1, keepdims=True)
    safe = jnp.where(amax > 0, amax, 1.0)
    codes = jnp.round(jnp.clip(h / safe * 7.0, -8.0, 7.0))
    return codes / 7.0 * safe


HOP_CODECS = {"int8_per_token": _hop_int8_per_token,
              "int4_per_token": _hop_int4_per_token}


def _layer(m, lp, h, cos, sin, control):
    s = h.shape[0]
    hd = head_dim(m)
    nh, nkv = m["num_attention_heads"], m["num_key_value_heads"]
    x = _rmsnorm(h, lp["ln1_scale"], m["rms_norm_eps"])
    q = (_mm(x, lp["wq"], control) + lp["bq"]).reshape(s, nh, hd)
    k = (_mm(x, lp["wk"], control) + lp["bk"]).reshape(s, nkv, hd)
    v = (_mm(x, lp["wv"], control) + lp["bv"]).reshape(s, nkv, hd)
    q, k = _rotate(q, cos, sin), _rotate(k, cos, sin)
    if control:
        q, k, v = _f8(q, -1), _f8(k, -1), _f8(v, -1)
    k = jnp.repeat(k, nh // nkv, axis=1)
    v = jnp.repeat(v, nh // nkv, axis=1)
    scores = jnp.einsum("qhd,thd->hqt", q, k) / jnp.sqrt(jnp.float32(hd))
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), axis=-1)
    attn = jnp.einsum("hqt,thd->qhd", probs, v).reshape(s, nh * hd)
    h = h + _mm(attn, lp["wo"], control)
    x = _rmsnorm(h, lp["ln2_scale"], m["rms_norm_eps"])
    gate = jax.nn.silu(_mm(x, lp["w_gate"], control))
    return h + _mm(gate * _mm(x, lp["w_up"], control), lp["w_down"], control)


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


def _hidden(m, weights, ids, hops, control):
    """ids (S,) -> final hidden (S, D) in float32."""
    cos, sin = _rope(m, ids.shape[0])
    h = weights["embed"][ids].astype(jnp.float32)
    cuts = jnp.asarray([c for c, _ in hops], jnp.int32)

    def body(h, xs):
        lp, idx = xs
        h = _layer(m, _f32(lp), h, cos, sin, control)
        for j, (_, codec) in enumerate(hops):
            h = jnp.where(idx == cuts[j], HOP_CODECS[codec](h), h)
        return h, None

    h, _ = jax.lax.scan(
        body, h, (weights["layers"], jnp.arange(m["num_hidden_layers"])))
    return _rmsnorm(h, weights["final_norm_scale"].astype(jnp.float32),
                    m["rms_norm_eps"])


def _logits(m, weights, hidden, control):
    head = (weights["embed"].T if m["tie_word_embeddings"]
            else weights["lm_head"]).astype(jnp.float32)
    return _mm(hidden, head, control)


def logit_gaps(model_key, weights, ids, start, served, *, hops=(),
               with_control=False):
    """For one sequence ``ids`` (S,), padded at its end, whose served tokens
    ``served`` (N,) were produced at positions ``start .. start+N-1``: the gap,
    at each of those positions, by which the served token's reference logit
    lies below the reference's best. With ``with_control`` also the gap of the
    token the float8 forward puts first. Entries past the real count are the
    caller's to mask. Returns (gaps (N,), control_gaps (N,) or None)."""
    return _logit_gaps(model_key, weights, ids, start, served, tuple(hops),
                       bool(with_control))


@functools.partial(jax.jit, static_argnames=("model_key", "hops",
                                             "with_control"))
def _logit_gaps(model_key, weights, ids, start, served, hops, with_control):
    m = dict(model_key)
    n = served.shape[0]
    with jax.default_matmul_precision("highest"):
        hid = jax.lax.dynamic_slice_in_dim(
            _hidden(m, weights, ids, hops, False), start, n)
        ref = _logits(m, weights, hid, False)
        best = jnp.max(ref, axis=-1)
        gaps = best - jnp.take_along_axis(ref, served[:, None], axis=-1)[:, 0]
        if not with_control:
            return gaps, None
        hid_c = jax.lax.dynamic_slice_in_dim(
            _hidden(m, weights, ids, hops, True), start, n)
        first = jnp.argmax(_logits(m, weights, hid_c, True), axis=-1)
        control = best - jnp.take_along_axis(ref, first[:, None], axis=-1)[:, 0]
        return gaps, control


def model_key(m: dict) -> tuple:
    """The model's sizes as a hashable static argument."""
    return tuple(sorted((k, v) for k, v in m.items()
                        if isinstance(v, (int, float, bool))))
