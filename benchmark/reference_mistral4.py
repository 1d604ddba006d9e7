"""The plain reference for the ``mistral4`` family (Mistral Small 4):
straightforward ``jax.numpy`` in float32 at ``default_matmul_precision
("highest")`` — no kernels, no cache, no pages, no absorption, no batching,
nothing imported from the program. ``m`` is the configuration file's dict (the
published ``config.json`` keys) and ``weights`` the benchmark's own seeded
arrays in the layout the system under test takes (``benchmark/architectures/
mistral4.py``).

Attention is the EXPANDED form at every position — keys and values rebuilt
for every head from the latent — never the absorbed one the program's decode
step runs, so the comparison is what checks the absorption. With ``x = rms(h;
w1)``, ``rms(x; w) = x * rsqrt(mean(x^2) + eps) * w``, H heads, one layer:

- ``c_q = rms(x W_qa; g_q)`` (q_lora_rank); ``q_h = c_q W_qb`` = ``[q_nope_h
  (nope) | q_rope_h (rope)]``; ``[c_kv (kv_lora_rank) | k_rope (rope)] = x
  W_kva``; ``c = rms(c_kv; g_kv)``; ``[k_nope_h (nope) | v_h (vd)] = c
  W_kvb^h``; no bias anywhere.
- ``q_rope_h`` and the one ``k_rope`` all heads share are rotated in
  INTERLEAVED pairs (``rope_interleave``): lanes ``2i, 2i+1`` by the angle
  ``p inv_freq'_i``, in place. YaRN (transformers' ``_compute_yarn_parameters``,
  ``truncate``): ``inv_freq_i = theta^(-2i / rope)``, ``dim(r) = rope ln(orig /
  (2 pi r)) / (2 ln theta)``, ``low = max(floor(dim(beta_fast)), 0)``, ``high =
  min(ceil(dim(beta_slow)), rope - 1)``, ``ramp_i = clip((i - low) / (high -
  low), 0, 1)``, ``inv_freq'_i = inv_freq_i / factor * ramp_i + inv_freq_i (1 -
  ramp_i)``; cos and sin both times ``ms(mscale) / ms(mscale_all_dim)``, ``ms(t)
  = 0.1 t ln(factor) + 1`` (1 at the published ``mscale = mscale_all_dim``).
- ``score_h(i, j) = s a(i) (q_nope_h(i) . k_nope_h(j) + q_rope_h(i) .
  k_rope(j))`` for ``j <= i``; ``s = (nope + rope)^-1/2 ms(mscale_all_dim)^2``;
  ``a(i) = 1 + llama_4_scaling_beta ln(1 + floor(i / orig))``; softmax in
  float32; ``h += concat_h(P_h v_h) W_o``.
- ``u = rms(h; w2)``; router logits ``u W_r`` over ALL ``n_routed_experts``;
  the top ``num_experts_per_tok``; weights = softmax over all, taken at the
  chosen, renormalised to sum 1 (``norm_topk_prob``), times
  ``routed_scaling_factor``; ``h += sum_e w_e (silu(u Wg_e) * (u Wu_e)) Wd_e +
  (silu(u Sg) * (u Su)) Sd``: the held experts' part (``share``) and the
  shared expert on every token. No token dropped.
- ``h0 = embed[ids]``; logits ``= rms(h_L; w_f) @ lm_head`` (untied).

Departures from the published model, all in the configuration file: the depth,
the experts held, the vocabulary slice; weights are seeded, not trained; no
vision tower; what the file lists under ``assumed``.

Each layer is one jitted call with that layer's weights upcast inside it (its
experts one at a time, inside the loop over them); attention runs a block of
query rows at a time and the head a slice of the vocabulary at a time, so the
float32 copies that live beside the served system are one layer's projections,
the expanded K and V of one layer, one expert, one (H, 256, S) block of scores
and one 8k-column slice of the head, at 12288 positions.

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
    rope = m["rope_parameters"]
    if rope.get("rope_type", rope.get("type")) != "yarn" \
            or not m["rope_interleave"]:
        raise ValueError("the mistral4 reference knows YaRN over interleaved "
                         "rotary pairs")
    if m["first_k_dense_replace"] or m["n_group"] != 1 or \
            m["topk_group"] != 1 or not m["norm_topk_prob"]:
        raise ValueError("the mistral4 reference knows routed layers only, "
                         "one routing group, a renormalised top-k")
    return tuple(sorted({
        "layers": m["num_hidden_layers"], "heads": m["num_attention_heads"],
        "nope": m["qk_nope_head_dim"], "rope": m["qk_rope_head_dim"],
        "vd": m["v_head_dim"], "rank": m["kv_lora_rank"],
        "eps": m["rms_norm_eps"],
        "experts": m["n_routed_experts"],
        "held": share.get("experts_held", m["n_routed_experts"]),
        "offset": share.get("expert_offset", 0),
        "top_k": m["num_experts_per_tok"],
        "routed_scale": float(m["routed_scaling_factor"]),
        "theta": float(rope["rope_theta"]), "factor": float(rope["factor"]),
        "orig": rope["original_max_position_embeddings"],
        "beta_fast": float(rope["beta_fast"]),
        "beta_slow": float(rope["beta_slow"]),
        "mscale": float(rope["mscale"]),
        "mscale_all_dim": float(rope["mscale_all_dim"]),
        "query_beta": float(rope["llama_4_scaling_beta"]),
    }.items()))


def _ms(k: dict, t: float) -> float:
    return 0.1 * t * math.log(k["factor"]) + 1.0 if k["factor"] > 1 else 1.0


def yarn_band(k: dict) -> tuple:
    """(low, high) of the docstring's formulas, as Python numbers."""
    rot = k["rope"]

    def dim(r):
        return rot * math.log(k["orig"] / (2 * math.pi * r)) / (
            2 * math.log(k["theta"]))

    return (max(math.floor(dim(k["beta_fast"])), 0),
            min(math.ceil(dim(k["beta_slow"])), rot - 1))


def inv_freq(k: dict):
    """(rope / 2,) float32 rotation frequencies."""
    rot = k["rope"]
    i = jnp.arange(rot // 2, dtype=jnp.float32)
    base = k["theta"] ** (-2.0 * i / rot)
    low, high = yarn_band(k)
    if low == high:
        high += 0.001
    ramp = jnp.clip((i - low) / (high - low), 0.0, 1.0)
    return base / k["factor"] * ramp + base * (1.0 - ramp)


def rope_table(k: dict, s: int):
    """(cos, sin), each (S, rope / 2): one angle a pair."""
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq(k)
    factor = _ms(k, k["mscale"]) / _ms(k, k["mscale_all_dim"])
    return jnp.cos(angle) * factor, jnp.sin(angle) * factor


def _rotate_pairs(x, cos, sin):
    """x (S, heads, rope): lanes (2i, 2i+1) rotated by pair i's angle, in
    place."""
    even, odd = x[..., 0::2], x[..., 1::2]
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.stack([even * c - odd * s, odd * c + even * s],
                     axis=-1).reshape(x.shape)


def softmax_scale(k: dict) -> float:
    return (k["nope"] + k["rope"]) ** -0.5 * _ms(k, k["mscale_all_dim"]) ** 2


def query_scale(k: dict, s: int):
    """a(i), (S,) float32."""
    pos = jnp.arange(s)
    return 1.0 + k["query_beta"] * jnp.log1p(
        (pos // k["orig"]).astype(jnp.float32))


def _attention(k, lp, x, control):
    s = x.shape[0]
    nh, nope, rot, vd, rank = (k["heads"], k["nope"], k["rope"], k["vd"],
                               k["rank"])
    c_q = _rms(_mm(x, lp["wq_a"], control), lp["q_norm"], k["eps"])
    q = _mm(c_q, lp["wq_b"], control).reshape(s, nh, nope + rot)
    kv = _mm(x, lp["wkv_a"], control)
    c = _rms(kv[:, :rank], lp["kv_norm"], k["eps"])
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
    scale = softmax_scale(k) * query_scale(k, s)                # (S,)

    def block(i):
        rows = i * qb + jnp.arange(qb)[:, None]
        qi = jax.lax.dynamic_slice_in_dim(q, i * qb, qb)
        si = jax.lax.dynamic_slice_in_dim(scale, i * qb, qb)
        scores = jnp.einsum("qhd,thd->hqt", qi, kk) * si[None, :, None]
        probs = jax.nn.softmax(jnp.where((cols <= rows)[None], scores,
                                         -jnp.inf), axis=-1)
        return jnp.einsum("hqt,thd->qhd", probs, v).reshape(qb, nh * vd)

    out = jax.lax.map(block, jnp.arange(s // qb)).reshape(s, nh * vd)
    return _mm(out, lp["wo"], control)


def _swiglu(u, wg, wu, wd, control):
    return _mm(jax.nn.silu(_mm(u, wg, control)) * _mm(u, wu, control), wd,
               control)


def _moe(k, mp, u, control):
    r = _mm(u, mp["router"], control)                         # (S, E)
    _, idx = jax.lax.top_k(r, k["top_k"])
    # softmax over ALL experts, taken at the chosen, renormalised to sum 1
    p = jnp.take_along_axis(jax.nn.softmax(r, axis=-1), idx, axis=-1)
    w = p / jnp.sum(p, axis=-1, keepdims=True) * k["routed_scale"]
    local = idx - k["offset"]
    held = (local >= 0) & (local < k["held"])
    # (S, held): the weight of each held expert for each token, 0 if unrouted
    combine = jnp.sum(jax.nn.one_hot(jnp.where(held, local, k["held"]),
                                     k["held"]) * w[..., None], axis=1)

    def expert(acc, xs):
        wg, wu, wd, c = xs          # one expert upcast at a time
        return acc + c[:, None] * _swiglu(u, *_f32((wg, wu, wd)),
                                          control), None

    routed, _ = jax.lax.scan(
        expert, jnp.zeros_like(u),
        (mp["w_gate"], mp["w_up"], mp["w_down"], combine.T))
    return routed + _swiglu(u, mp["shared_gate"], mp["shared_up"],
                            mp["shared_down"], control)


@functools.partial(jax.jit, static_argnames=("key", "control"))
def _layer(key, lp, mp, h, control):
    k = dict(key)
    experts = ("w_gate", "w_up", "w_down")
    lp = _f32(lp)
    mp = {name: a if name in experts else _f32(a) for name, a in mp.items()}
    with jax.default_matmul_precision("highest"):
        h = h + _attention(k, lp, _rms(h, lp["ln1_scale"], k["eps"]),
                           control)
        return h + _moe(k, mp, _rms(h, mp["ln2_scale"], k["eps"]), control)


def _row(tree, j):
    return {name: a[j] for name, a in tree.items()}


def hidden(key, weights, ids, control=False):
    """ids (S,) -> the last layer's hidden state (S, D), float32."""
    k = dict(key)
    h = weights["embed"][ids].astype(jnp.float32)
    for layer in range(k["layers"]):
        h = _layer(key, _row(weights["latent"], layer),
                   weights["moe"][layer], h, control)
    return h


def _head_blocks(v: int):
    return [(c, min(c + VOCAB_BLOCK, v)) for c in range(0, v, VOCAB_BLOCK)]


@functools.partial(jax.jit, static_argnames=("key", "control"))
def _logits(key, weights, hid, control):
    k = dict(key)
    head = weights["lm_head"]
    with jax.default_matmul_precision("highest"):
        post = _rms(hid, weights["final_norm_scale"].astype(jnp.float32),
                    k["eps"])
        return jnp.concatenate(
            [_mm(post, head[:, a:b].astype(jnp.float32), control)
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
    scale = weights["final_norm_scale"].astype(jnp.float32)
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
