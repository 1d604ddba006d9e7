"""The plain reference for the ``lfm2_moe`` family (LiquidAI LFM2):
straightforward ``jax.numpy`` in float32 at ``default_matmul_precision
("highest")`` — no kernels, no cache, no pages, no window kept between
positions, no batching, nothing imported from the program. ``m`` is the
configuration file's dict (the published ``config.json`` keys) and
``weights`` the benchmark's own seeded arrays in the layout the system under
test takes (``benchmark/architectures/lfm2_moe.py``).

With ``h`` the hidden state (S, D), ``rms(x; w) = x * rsqrt(mean(x^2) + eps)
* w`` (``eps = norm_eps``) and ``rms_hd`` the same over each head's ``hd =
D / H`` lanes, one layer ``l`` of kind ``layer_types[l]``, two norms a layer
and none after a sublayer:

- ``u = rms(h; g_op)``. A ``conv`` layer (``L = conv_L_cache`` taps, no
  bias): ``[B | C | x] = u W_in`` (D x 3D, chunks in that order); ``z = B *
  x``; ``c_t = sum_{j < L} w[:, j] * z_{t-(L-1)+j}`` with ``z_s = 0`` for ``s
  < 0`` (a depthwise causal convolution, tap ``L-1`` on the current position,
  no activation); ``h += (C * c) W_out``. A ``full_attention`` layer: ``q =
  rms_hd(u Wq; g_q)`` (H x hd), ``k = rms_hd(u Wk; g_k)``, ``v = u Wv`` (KV x
  hd), no bias; q and k then rotated (HF's ``x cos + rotate_half(x) sin`` over
  ``concat(freqs, freqs)``, ``inv_freq_d = theta^(-2d / hd)``, all ``hd``
  lanes, unscaled); head ``j * (H / KV) + g`` attends KV group ``j``; position
  ``i`` attends ``j <= i``; scores ``q k^T / sqrt(hd)``, softmax in float32;
  ``h += (P v) Wo``. No gate, no window.
- ``u = rms(h; g_ffn)``. A dense layer (``l < num_dense_layers``): ``h +=
  (silu(u W1) * (u W3)) W2`` of width ``intermediate_size``. An expert layer:
  ``p = sigmoid(u Wr)`` over ALL ``num_experts``; the chosen ``S`` = the top
  ``num_experts_per_tok`` of ``p + b`` (``b`` the per-expert selection bias,
  which no weight sees); ``w_e = routed_scaling_factor * p_e / (sum_{e in S}
  p_e + 1e-6)``; ``h += sum_{e in S} w_e (silu(u Wg_e) * (u Wu_e)) Wd_e``; no
  shared expert, no token dropped.
- ``h0 = embed[ids]``; logits ``= rms(h_L; g_f) @ embed.T`` (the tied head;
  ``g_f`` is the norm the checkpoint calls ``embedding_norm``).

Departures from the published description: none beyond the configuration
file's ``assumed`` (the depth is cut; weights are seeded, not trained).

Each layer is one jitted call with that layer's weights upcast inside it (its
experts one at a time, inside the loop over them); attention runs a block of
query rows at a time and the head a slice of the vocabulary at a time, so the
float32 copies that live beside the served system are one layer's projections,
one expert, one (H, 256, S) block of scores and one 8k-row slice of the
table, at 4608 positions.

``control=True`` rounds every matmul operand, and what a layer would cache
(q, k, v; the convolution's input ``z``), through scaled float8 (e4m3): the
nearest precision below the bfloat16 the configuration states.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

F8 = jnp.float8_e4m3fn
F8_MAX = 448.0
#: query rows attended at a time, and table rows multiplied at a time
QUERY_BLOCK = 256
VOCAB_BLOCK = 8192


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


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


def model_key(m: dict) -> tuple:
    """What the reference reads of a configuration, hashable."""
    share = m.get("share", {})
    for key, want in (("conv_bias", False), ("use_expert_bias", True),
                      ("norm_topk_prob", True), ("rope_scaling", None)):
        if m.get(key, want) != want:
            raise ValueError(f"the lfm2_moe reference knows {key}={want!r} "
                             f"alone, got {m[key]!r}")
    if not m.get("tie_word_embeddings", True):
        raise ValueError("the lfm2_moe reference knows the tied head")
    return tuple(sorted({
        "heads": m["num_attention_heads"], "kv_heads": m["num_key_value_heads"],
        "head_dim": m["hidden_size"] // m["num_attention_heads"],
        "eps": m["norm_eps"], "layer_types": tuple(m["layer_types"]),
        "taps": m["conv_L_cache"], "theta": float(m["rope_theta"]),
        "dense_layers": m["num_dense_layers"],
        "experts": m["num_experts"],
        "held": share.get("experts_held", m["num_experts"]),
        "offset": share.get("expert_offset", 0),
        "top_k": m["num_experts_per_tok"],
        "route_scale": float(m["routed_scaling_factor"]),
    }.items()))


def rope_table(k: dict, s: int):
    """(cos, sin), each (S, hd): ``concat(freqs, freqs)``, unscaled."""
    hd = k["head_dim"]
    inv_freq = k["theta"] ** (-2.0 * jnp.arange(hd // 2, dtype=jnp.float32)
                              / hd)
    freqs = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq
    emb = jnp.concatenate([freqs, freqs], axis=-1)
    return jnp.cos(emb), jnp.sin(emb)


def _rotate(x, cos, sin):
    """x (S, heads, hd); HF's rotate_half."""
    half = x.shape[-1] // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos[:, None, :] + rot * sin[:, None, :]


def _short_conv(k, lp, u, control):
    s, d = u.shape
    taps = k["taps"]
    bcx = _mm(u, lp["w_in"], control)
    gate_b, gate_c, x = bcx[:, :d], bcx[:, d:2 * d], bcx[:, 2 * d:]
    z = gate_b * x
    if control:
        z = _f8(z, -1)
    padded = jnp.concatenate([jnp.zeros((taps - 1, d), z.dtype), z])
    conv = jnp.zeros_like(z)
    for j in range(taps):       # tap j reads position t - (taps - 1) + j
        conv = conv + lp["conv_w"][:, j] * padded[j:j + s]
    return _mm(gate_c * conv, lp["w_out"], control)


def _attention(k, lp, u, control):
    s = u.shape[0]
    nh, nkv, hd = k["heads"], k["kv_heads"], k["head_dim"]
    q = _rms(_mm(u, lp["wq"], control).reshape(s, nh, hd), lp["q_norm"],
             k["eps"])
    kk = _rms(_mm(u, lp["wk"], control).reshape(s, nkv, hd), lp["k_norm"],
              k["eps"])
    v = _mm(u, lp["wv"], control).reshape(s, nkv, hd)
    cos, sin = rope_table(k, s)
    q, kk = _rotate(q, cos, sin), _rotate(kk, cos, sin)
    if control:
        q, kk, v = _f8(q, -1), _f8(kk, -1), _f8(v, -1)
    # head j * (H / KV) + g attends KV group j: (S, KV, H / KV, hd)
    q = q.reshape(s, nkv, nh // nkv, hd)
    qb = min(QUERY_BLOCK, s)
    if s % qb:
        raise ValueError(f"the reference attends {qb} query rows at a time; "
                         f"pad {s} positions to a multiple")
    cols = jnp.arange(s)[None, :]

    def block(i):
        rows = i * qb + jnp.arange(qb)[:, None]
        qi = jax.lax.dynamic_slice_in_dim(q, i * qb, qb)
        scores = jnp.einsum("qjgd,tjd->jgqt", qi, kk) / math.sqrt(hd)
        probs = jax.nn.softmax(
            jnp.where((cols <= rows)[None, None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("jgqt,tjd->qjgd", probs, v).reshape(qb, nh * hd)

    out = jax.lax.map(block, jnp.arange(s // qb)).reshape(s, nh * hd)
    return _mm(out, lp["wo"], control)


def _swiglu(u, wg, wu, wd, control):
    return _mm(jax.nn.silu(_mm(u, wg, control)) * _mm(u, wu, control), wd,
               control)


def _moe(k, mp, u, control):
    p = jax.nn.sigmoid(_mm(u, mp["router"], control))         # (S, E)
    # the bias takes part in the choice and in nothing else
    _, idx = jax.lax.top_k(p + mp["router_bias"], k["top_k"])
    chosen = jnp.take_along_axis(p, idx, axis=-1)
    w = k["route_scale"] * chosen / (
        jnp.sum(chosen, axis=-1, keepdims=True) + 1e-6)
    local = idx - k["offset"]
    held = (local >= 0) & (local < k["held"])
    # (S, held): the weight of each held expert for each token, 0 if unrouted
    combine = jnp.sum(jax.nn.one_hot(jnp.where(held, local, k["held"]),
                                     k["held"]) * w[..., None], axis=1)
    uq = _f8(u, -1) if control else u

    def expert(acc, xs):
        wg, wu, wd, c = xs          # one expert upcast at a time
        wg, wu, wd = _f32((wg, wu, wd))
        if control:
            wg, wu = _f8(wg, 0), _f8(wu, 0)
        hid = jax.nn.silu(uq @ wg) * (uq @ wu)
        return acc + c[:, None] * _mm(hid, wd, control), None

    routed, _ = jax.lax.scan(
        expert, jnp.zeros_like(u),
        (mp["w_gate"], mp["w_up"], mp["w_down"], combine.T))
    return routed


@functools.partial(jax.jit, static_argnames=("key", "kind", "control"))
def _layer(key, kind, lp, mp, h, control):
    k = dict(key)
    experts = ("w_gate", "w_up", "w_down")
    lp = _f32(lp)
    dense = "router" not in mp
    mp = {name: a if name in experts and not dense else _f32(a)
          for name, a in mp.items()}
    with jax.default_matmul_precision("highest"):
        u = _rms(h, lp["ln1_scale"], k["eps"])
        h = h + (_short_conv(k, lp, u, control) if kind == "conv"
                 else _attention(k, lp, u, control))
        u = _rms(h, mp["ln2_scale"], k["eps"])
        return h + (_swiglu(u, mp["w_gate"], mp["w_up"], mp["w_down"],
                            control) if dense else _moe(k, mp, u, control))


def _row(tree, j):
    return {name: a[j] for name, a in tree.items()}


def hidden(key, weights, ids, control=False):
    """ids (S,) -> the last layer's hidden state (S, D), float32."""
    k = dict(key)
    h = weights["embed"][ids].astype(jnp.float32)
    stacks = {"conv": "conv", "full_attention": "attn"}
    seen = dict.fromkeys(stacks, 0)
    for layer, kind in enumerate(k["layer_types"]):
        mp = weights["moe"][layer]
        if ("router" in mp) != (layer >= k["dense_layers"]):
            raise ValueError(f"layer {layer}: the first {k['dense_layers']} "
                             f"feed-forwards are dense, the rest routed")
        h = _layer(key, kind, _row(weights[stacks[kind]], seen[kind]), mp, h,
                   control)
        seen[kind] += 1
    return h


def _table_blocks(v: int):
    return [(c, min(c + VOCAB_BLOCK, v)) for c in range(0, v, VOCAB_BLOCK)]


@functools.partial(jax.jit, static_argnames=("key", "control"))
def _logits(key, weights, hid, control):
    k = dict(key)
    table = weights["embed"]
    with jax.default_matmul_precision("highest"):
        post = _rms(hid, weights["final_norm_scale"].astype(jnp.float32),
                    k["eps"])
        return jnp.concatenate(
            [_mm(post, table[a:b].astype(jnp.float32).T, control)
             for a, b in _table_blocks(table.shape[0])], axis=-1)


def logits(key, weights, ids, control=False):
    """ids (S,) -> float32 logits (S, V) of the whole forward. (The tests'
    entry; :func:`logit_gaps` never holds (S, V).)"""
    return _logits(key, weights, hidden(key, weights, ids, control), control)


@functools.partial(jax.jit, static_argnames=("key", "with_control"))
def _gaps(key, weights, hid, hid_control, served, with_control):
    """Rows of the last hidden state -> (gap of the served token under the
    reference's best, gap of the control's first choice), a slice of the
    vocabulary at a time: running maxima, never the (N, V) logits."""
    k = dict(key)
    table = weights["embed"]
    n = served.shape[0]
    scale = weights["final_norm_scale"].astype(jnp.float32)
    neg = jnp.full((n,), -jnp.inf)
    best, at_served, c_best, ref_at_c = neg, neg, neg, neg
    with jax.default_matmul_precision("highest"):
        post = _rms(hid, scale, k["eps"])
        post_c = _rms(hid_control, scale, k["eps"]) if with_control else None
        for a, b in _table_blocks(table.shape[0]):
            w = table[a:b].astype(jnp.float32).T               # (D, block)
            ref = post @ w                                     # (N, block)
            best = jnp.maximum(best, ref.max(axis=-1))
            inside = (served >= a) & (served < b)
            got = jnp.take_along_axis(
                ref, jnp.clip(served - a, 0, b - a - 1)[:, None], axis=-1)
            at_served = jnp.where(inside, got[:, 0], at_served)
            if with_control:
                ctl = _mm(post_c, w, True)
                first = jnp.argmax(ctl, axis=-1)
                top = ctl.max(axis=-1)
                here = jnp.take_along_axis(ref, first[:, None], axis=-1)[:, 0]
                ref_at_c = jnp.where(top > c_best, here, ref_at_c)
                c_best = jnp.maximum(c_best, top)
    return best - at_served, (best - ref_at_c) if with_control else None


def logit_gaps(key, weights, ids, start, served, *, with_control=False):
    """``benchmark/reference.py``'s result for this family: for one sequence
    ``ids`` (S,), padded at its end, whose served tokens ``served`` (N,) were
    produced at positions ``start .. start+N-1``: the gap by which the served
    token's reference logit lies below the reference's best; with
    ``with_control`` also the gap of the token the float8 forward puts
    first. Returns (gaps (N,), control_gaps (N,) or None)."""
    n = served.shape[0]

    def rows(control):
        return jax.lax.dynamic_slice_in_dim(
            hidden(key, weights, ids, control), start, n)

    hid = rows(False)
    return _gaps(key, weights, hid, rows(True) if with_control else hid,
                 served, with_control)
