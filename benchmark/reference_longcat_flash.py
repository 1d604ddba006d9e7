"""The plain reference for the ``longcat_flash`` family (Meituan
LongCat-Flash): straightforward ``jax.numpy`` in float32 at
``default_matmul_precision("highest")`` — no kernels, no cache, no pages, no
absorption, no batching, nothing imported from the program. ``m`` is the
configuration file's dict (the published ``config.json`` keys) and ``weights``
the benchmark's own seeded arrays in the layout the system under test takes
(``benchmark/architectures/longcat_flash.py``).

One published layer ``l`` holds two attention sublayers ``A0, A1`` (rows
``2l``, ``2l + 1`` of ``weights["latent"]``, their input norms ``n0, n1``
there as ``ln1_scale``), two dense SwiGLUs ``M0, M1`` with their input norms
``p0, p1`` (entries ``2l``, ``2l + 1`` of ``weights["moe"]``) and ONE routed
layer ``R`` (``weights["moe"][2l]["shortcut"]``), literally::

    h  = h + A0(rms(h; n0))
    u0 = rms(h; p0)
    s  = R(u0)                      # the shortcut: kept aside
    h  = h + M0(u0)
    h  = h + A1(rms(h; n1))
    h  = h + M1(rms(h; p1))
    h  = h + s

``rms(x; w) = x * rsqrt(mean(x^2) + eps) * w``; no bias anywhere.

- ``A`` is latent attention in its EXPANDED form at every position — keys
  and values rebuilt for every head from the latent — never the absorbed one
  the program's decode step runs: ``c_q = rms(x W_qa; g_q)``; ``q_h = c_q
  W_qb * sqrt(hidden / q_lora_rank)`` = ``[q_nope_h | q_rope_h]`` (the WHOLE
  query carries the rank scale); ``[c_kv | k_rope] = x W_kva``; ``c =
  rms(c_kv; g_kv) * sqrt(hidden / kv_lora_rank)`` (after the norm, so
  ``k_nope`` and ``v`` carry it and ``k_rope`` does not); ``[k_nope_h | v_h]
  = c W_kvb^h``. ``q_rope_h`` and the one ``k_rope`` all heads share are
  rotated in INTERLEAVED pairs, lanes ``2i, 2i+1`` by the angle ``p
  theta^(-2i / rope)``: plain RoPE, no scaling. ``score_h(i, j) = (nope +
  rope)^-1/2 (q_nope_h(i) . k_nope_h(j) + q_rope_h(i) . k_rope(j))`` for ``j
  <= i``; softmax in float32; ``A = concat_h(P_h v_h) W_o``.
- ``M(u) = (silu(u Wg) * (u Wu)) Wd`` at ``ffn_hidden_size``.
- ``R(u)``: ``logits = u W_r`` over ALL ``router_experts + zero_experts``
  outputs (no bias term); ``p = softmax(logits)`` over all of them; the
  chosen ``moe_topk`` = ``top_k(p + b)``, ``b`` a correction bias no weight
  sees; ``w_e = routed_scaling_factor * p_e``, NOT renormalised over the
  chosen; ``R(u) = sum over chosen e of w_e E_e(u)`` with ``E_e`` a SwiGLU at
  ``expert_ffn_hidden_size`` for ``e < router_experts`` and the IDENTITY,
  ``E_e(u) = u``, for the rest.
- ``h0 = embed[ids]``; logits ``= rms(h_L; w_f) @ lm_head`` (untied).

Departures from the published model, all in the configuration file: the
depth; the vocabulary slice; of the routed experts only the share's
``[expert_offset, + experts_held)`` contribute (the other chips' part of the
sum is left out, as the program's share leaves it out) while EVERY identity
expert does (they hold no weights and belong to no chip); weights are seeded,
not trained; what the file lists under ``assumed``.

Every sublayer is one jitted call with its weights upcast inside it (a dense
SwiGLU a block of its width at a time, the experts one at a time inside the
loop over them); attention runs a block of query rows at a time and the head a slice
of the vocabulary at a time, so the float32 copies that live beside the
served system are one sublayer's projections, its expanded K and V, a 4096-
column block of a dense matrix (100 MB at the published widths), one (H, 256,
S) block of scores and one 8k-column slice of the head.

``control=True`` rounds every matmul operand through scaled float8 (e4m3):
the nearest precision below the bfloat16 the configuration states.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

F8 = jnp.float8_e4m3fn
F8_MAX = 448.0
#: query rows attended at a time, and head columns multiplied at a time
QUERY_BLOCK = 256
VOCAB_BLOCK = 8192
#: columns of a feed-forward's width multiplied at a time
FFN_BLOCK = 4096


def _f8(x, axis):
    """Round ``x`` through scaled float8 along ``axis`` (absmax scaling)."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / F8_MAX, 1.0)
    return (x / scale).astype(F8).astype(jnp.float32) * scale


def _mm(x, w, control):
    """x (..., K) @ w (K, N), ``w`` upcast here, in float32; the control
    rounds both operands."""
    w = w.astype(jnp.float32)
    if control:
        x, w = _f8(x, -1), _f8(w, 0)
    return x @ w


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale.astype(jnp.float32)


def model_key(m: dict) -> tuple:
    """What the reference reads of a configuration, hashable."""
    share = m.get("share", {})
    if m["attention_method"] != "MLA" or m["zero_expert_type"] != "identity":
        raise ValueError("the longcat_flash reference knows latent attention "
                         "and identity zero-experts")
    if m.get("rope_scaling") or m.get("norm_topk_prob", False) \
            or m.get("router_bias", False) or m.get("attention_bias", False):
        raise ValueError("the longcat_flash reference knows plain RoPE, "
                         "unnormalised top-k weights and no bias terms")
    d = m["hidden_size"]
    return tuple(sorted({
        "layers": m["num_layers"], "heads": m["num_attention_heads"],
        "nope": m["qk_nope_head_dim"], "rope": m["qk_rope_head_dim"],
        "vd": m["v_head_dim"], "rank": m["kv_lora_rank"],
        "eps": m["rms_norm_eps"], "theta": float(m["rope_theta"]),
        # the config gives the two rank scales as booleans; their form is
        # sqrt(hidden / rank) (the file's ``assumed``)
        "q_scale": (math.sqrt(d / m["q_lora_rank"])
                    if m["mla_scale_q_lora"] else 1.0),
        "kv_scale": (math.sqrt(d / m["kv_lora_rank"])
                     if m["mla_scale_kv_lora"] else 1.0),
        "experts": share.get("router_experts", m["n_routed_experts"]),
        "zero": m["zero_expert_num"],
        "held": share.get("experts_held", m["n_routed_experts"]),
        "offset": share.get("expert_offset", 0),
        "top_k": m["moe_topk"],
        "routed_scale": float(m["routed_scaling_factor"]),
    }.items()))


def rope_table(k: dict, s: int):
    """(cos, sin), each (S, rope / 2): one angle a pair, plain RoPE."""
    rot = k["rope"]
    inv_freq = k["theta"] ** (
        -2.0 * jnp.arange(rot // 2, dtype=jnp.float32) / rot)
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq
    return jnp.cos(angle), jnp.sin(angle)


def _rotate_pairs(x, cos, sin):
    """x (S, heads, rope): lanes (2i, 2i+1) rotated by pair i's angle, in
    place."""
    even, odd = x[..., 0::2], x[..., 1::2]
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.stack([even * c - odd * s, odd * c + even * s],
                     axis=-1).reshape(x.shape)


def _attention(k, lp, x, control):
    s = x.shape[0]
    nh, nope, rot, vd, rank = (k["heads"], k["nope"], k["rope"], k["vd"],
                               k["rank"])
    c_q = _rms(_mm(x, lp["wq_a"], control), lp["q_norm"], k["eps"])
    q = _mm(c_q, lp["wq_b"], control).reshape(s, nh, nope + rot)
    q = q * k["q_scale"]                       # nope and rope lanes alike
    kv = _mm(x, lp["wkv_a"], control)
    c = _rms(kv[:, :rank], lp["kv_norm"], k["eps"]) * k["kv_scale"]
    cos, sin = rope_table(k, s)
    q_rope = _rotate_pairs(q[..., nope:], cos, sin)
    k_rope = _rotate_pairs(kv[:, None, rank:], cos, sin)        # (S, 1, rope)
    kvb = _mm(c, lp["wkv_b"], control).reshape(s, nh, nope + vd)
    q = jnp.concatenate([q[..., :nope], q_rope], axis=-1)
    kk = jnp.concatenate([kvb[..., :nope],
                          jnp.broadcast_to(k_rope, (s, nh, rot))], axis=-1)
    v = kvb[..., nope:]
    if control:
        q, kk, v = _f8(q, -1), _f8(kk, -1), _f8(v, -1)
    qb = min(QUERY_BLOCK, s)
    if s % qb:
        raise ValueError(f"the reference attends {qb} query rows at a time; "
                         f"pad {s} positions to a multiple")
    cols = jnp.arange(s)[None, :]
    scale = (nope + rot) ** -0.5

    def block(i):
        rows = i * qb + jnp.arange(qb)[:, None]
        qi = jax.lax.dynamic_slice_in_dim(q, i * qb, qb)
        scores = jnp.einsum("qhd,thd->hqt", qi, kk) * scale
        probs = jax.nn.softmax(jnp.where((cols <= rows)[None], scores,
                                         -jnp.inf), axis=-1)
        return jnp.einsum("hqt,thd->qhd", probs, v).reshape(qb, nh * vd)

    out = jax.lax.map(block, jnp.arange(s // qb)).reshape(s, nh * vd)
    return _mm(out, lp["wo"], control)


def _swiglu(u, wg, wu, wd, control):
    """``(silu(u Wg) * (u Wu)) Wd``, :data:`FFN_BLOCK` columns of the width
    at a time (the sum over the width in blocks), so that the float32 copies
    of a dense SwiGLU's matrices are 100 MB each and not 302."""
    out = 0.0
    for a in range(0, wg.shape[1], FFN_BLOCK):
        b = min(a + FFN_BLOCK, wg.shape[1])
        hidden = (jax.nn.silu(_mm(u, wg[:, a:b], control))
                  * _mm(u, wu[:, a:b], control))
        out = out + _mm(hidden, wd[a:b], control)
    return out


def route(k, rp, u, control=False):
    """u (S, D) -> (chosen ids (S, top_k) over all ``experts + zero``
    outputs, weights (S, top_k))."""
    p = jax.nn.softmax(_mm(u, rp["router"], control), axis=-1)  # all of them
    _, idx = jax.lax.top_k(p + rp["router_bias"].astype(jnp.float32),
                           k["top_k"])
    # the scores as they are, times the factor: NOT renormalised
    return idx, jnp.take_along_axis(p, idx, axis=-1) * k["routed_scale"]


def routed(k, rp, u, control=False):
    """``R(u)``: this share's part of it (module docstring)."""
    idx, w = route(k, rp, u, control)
    local = idx - k["offset"]
    held = (idx < k["experts"]) & (local >= 0) & (local < k["held"])
    # (S, held): the weight of each held expert for each token, 0 if unrouted
    combine = jnp.sum(jax.nn.one_hot(jnp.where(held, local, k["held"]),
                                     k["held"]) * w[..., None], axis=1)

    def expert(acc, xs):
        wg, wu, wd, c = xs          # one expert upcast at a time
        return acc + c[:, None] * _swiglu(u, wg, wu, wd, control), None

    out, _ = jax.lax.scan(
        expert, jnp.zeros_like(u),
        (rp["w_gate"], rp["w_up"], rp["w_down"], combine.T))
    # E_e(u) = u for every chosen e >= experts: no weights, no multiplies
    for j in range(k["top_k"]):
        zero = idx[:, j] >= k["experts"]
        out = out + jnp.where(zero, w[:, j], 0.0)[:, None] * u
    return out


@functools.partial(jax.jit, static_argnames=("key", "control"))
def _attn_sublayer(key, lp, h, control):
    k = dict(key)
    with jax.default_matmul_precision("highest"):
        return h + _attention(k, lp, _rms(h, lp["ln1_scale"], k["eps"]),
                              control)


@functools.partial(jax.jit, static_argnames=("key", "control"))
def _ffn_sublayer(key, mp, h, control):
    """-> (h + M(u), R(u) or None), ``u = rms(h; p)``."""
    k = dict(key)
    with jax.default_matmul_precision("highest"):
        u = _rms(h, mp["ln2_scale"], k["eps"])
        s = routed(k, mp["shortcut"], u, control) if "shortcut" in mp \
            else None
        return h + _swiglu(u, mp["w_gate"], mp["w_up"], mp["w_down"],
                           control), s


def _row(tree, j):
    return {name: a[j] for name, a in tree.items()}


def hidden(key, weights, ids, control=False):
    """ids (S,) -> the last layer's hidden state (S, D), float32."""
    k = dict(key)
    h = weights["embed"][ids].astype(jnp.float32)
    for layer in range(k["layers"]):
        a0, a1 = 2 * layer, 2 * layer + 1
        h = _attn_sublayer(key, _row(weights["latent"], a0), h, control)
        h, s = _ffn_sublayer(key, weights["moe"][a0], h, control)
        h = _attn_sublayer(key, _row(weights["latent"], a1), h, control)
        h, _ = _ffn_sublayer(key, weights["moe"][a1], h, control)
        h = h + s
    return h


def _head_blocks(v: int):
    return [(c, min(c + VOCAB_BLOCK, v)) for c in range(0, v, VOCAB_BLOCK)]


@functools.partial(jax.jit, static_argnames=("key", "control"))
def _logits(key, weights, hid, control):
    k = dict(key)
    head = weights["lm_head"]
    with jax.default_matmul_precision("highest"):
        post = _rms(hid, weights["final_norm_scale"], k["eps"])
        return jnp.concatenate(
            [_mm(post, head[:, a:b], control)
             for a, b in _head_blocks(head.shape[1])], axis=-1)


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
    head = weights["lm_head"]
    n = served.shape[0]
    scale = weights["final_norm_scale"]
    neg = jnp.full((n,), -jnp.inf)
    best, at_served, c_best, ref_at_c = neg, neg, neg, neg
    with jax.default_matmul_precision("highest"):
        post = _rms(hid, scale, k["eps"])
        post_c = _rms(hid_control, scale, k["eps"]) if with_control else None
        for a, b in _head_blocks(head.shape[1]):
            w = head[:, a:b].astype(jnp.float32)
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
