"""The plain reference for the ``KeyeVL2`` language model (Kwai Keye-VL 2.0):
straightforward ``jax.numpy`` in float32 at ``default_matmul_precision
("highest")`` — no kernels, no cache, no pages, no batching, nothing imported
from the program. ``m`` is the configuration file's dict (the published
``config.json`` keys) and ``weights`` the benchmark's own seeded arrays in the
layout the system under test takes (``benchmark/architectures/KeyeVL2.py``).

With ``h`` the hidden state (S, D), ``rms(x; w) = x * rsqrt(mean(x^2) + eps)
* w`` (``eps = rms_norm_eps``), ``rms_hd`` the same over each head's ``hd``
lanes and ``ln(x; g, b)`` a LayerNorm with scale and bias, every layer (no
bias on any projection):

- ``u = rms(h; g_1)``. ``q = rms_hd(u Wq; g_q)`` (H x hd), ``k = rms_hd(u Wk;
  g_k)``, ``v = u Wv`` (KV x hd). q and k rotated over all ``hd`` lanes
  (``x cos + rotate_half(x) sin`` over ``concat(freqs, freqs)``, ``inv_freq_d
  = theta^(-2d / hd)``) by THREE position streams: the ``hd / 2`` frequencies
  are cut into ``mrope_section`` chunks and chunk ``i`` takes its angle from
  stream ``i mod 3`` (:func:`mrope_table`, HF's
  ``apply_multimodal_rotary_pos_emb``); text puts the same position in all
  three.
- the indexer (``sa_config``): ``qI = u WqI`` (Hi x di), ``kI = ln(u WkI)``
  (di, ONE key a position), ``wI = u Ww`` (Hi); qI and kI rotated over their
  ``di`` lanes by the same theta (stream 0). ``I[t, s] = Hi^-1/2 di^-1/2
  sum_j wI[t, j] relu(qI[t, j] . kI[s])`` for ``s <= t``. ``S_t`` = the
  positions of ``jax.lax.top_k(I[t, :t+1], min(topk, t + 1))``, literally, a
  row at a time (in blocks of query rows).
- head ``j * (H / KV) + g`` attends KV group ``j`` over ``S_t``: scores ``q
  k^T / sqrt(hd)``, softmax in float32; ``h += (P v) Wo``.
- ``u = rms(h; g_2)``; ``logits = u Wr`` over ALL ``num_experts``; the chosen
  ``S`` = their top ``num_experts_per_tok``; ``w = softmax(logits[S])``
  (``norm_topk_prob``); ``h += sum_{e in S} w_e (silu(u Wg_e) * (u Wu_e))
  Wd_e`` over the experts this share holds; no shared expert.
- ``h0 = embed[ids]``; logits ``= rms(h_L; g_f) @ lm_head`` (untied).

Departures from the published description: none beyond the configuration
file's ``assumed``.

Each layer is one jitted call with that layer's weights upcast inside it (its
experts one at a time); attention runs :data:`QUERY_BLOCK` query rows at a
time (the block's index scores (Hi, Q, S), its ``top_k``, its selection as a
mask made of the chosen ids, its attention scores (H, Q, S)) and the head a
slice of the vocabulary at a time.

``control=True`` rounds every matmul operand, and what a layer would cache
or score by (q, k, v; qI, kI), through scaled float8 (e4m3): the nearest
precision below the bfloat16 the configuration states.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

F8 = jnp.float8_e4m3fn
F8_MAX = 448.0
#: query rows attended at a time, and head columns multiplied at a time
QUERY_BLOCK = 128
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


def _ln(x, scale, bias, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * scale + bias


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


def model_key(m: dict) -> tuple:
    """What the reference reads of a configuration, hashable."""
    share = m.get("share", {})
    sa = m["sa_config"]
    for key, want in (("use_sliding_window", False), ("norm_topk_prob", True),
                      ("decoder_sparse_step", 1), ("mlp_only_layers", []),
                      ("attention_bias", False),
                      ("tie_word_embeddings", False)):
        if m.get(key, want) != want:
            raise ValueError(f"the KeyeVL2 reference knows {key}={want!r} "
                             f"alone, got {m[key]!r}")
    if sa["indexer_num_kv_heads"] != 1:
        raise ValueError("the KeyeVL2 reference knows one index key a "
                         "position")
    section = tuple(m["rope_scaling"]["mrope_section"])
    if 2 * sum(section) != m["head_dim"]:
        raise ValueError(f"mrope_section {section} does not split the "
                         f"{m['head_dim'] // 2} frequencies of a head")
    return tuple(sorted({
        "heads": m["num_attention_heads"],
        "kv_heads": m["num_key_value_heads"], "head_dim": m["head_dim"],
        "eps": m["rms_norm_eps"], "layers": m["num_hidden_layers"],
        "theta": float(m["rope_theta"]), "mrope_section": section,
        "index_heads": sa["indexer_num_heads"],
        "index_dim": sa["indexer_head_dim"], "topk": sa["topk"],
        "experts": share.get("router_experts", m["num_experts"]),
        "held": share.get("experts_held", m["num_experts"]),
        "offset": share.get("expert_offset", 0),
        "top_k": m["num_experts_per_tok"],
    }.items()))


def _angles(theta: float, dim: int, positions):
    """positions (..., S) -> ``concat(freqs, freqs)`` (..., S, dim)."""
    inv_freq = theta ** (-2.0 * jnp.arange(dim // 2, dtype=jnp.float32) / dim)
    freqs = positions.astype(jnp.float32)[..., None] * inv_freq
    return jnp.concatenate([freqs, freqs], axis=-1)


def mrope_table(k: dict, positions):
    """(cos, sin), each (S, hd), from THREE position streams ``positions``
    (3, S): the streams' tables cut on lanes into ``mrope_section * 2``
    chunks, chunk ``i`` taken from stream ``i mod 3`` (HF's
    ``apply_multimodal_rotary_pos_emb``)."""
    emb = _angles(k["theta"], k["head_dim"], positions)        # (3, S, hd)
    out = []
    for table in (jnp.cos(emb), jnp.sin(emb)):
        chunks, at = [], 0
        for i, width in enumerate(k["mrope_section"] * 2):
            chunks.append(table[i % 3, :, at:at + width])
            at += width
        out.append(jnp.concatenate(chunks, axis=-1))
    return tuple(out)


def text_positions(s: int):
    """A text sequence's three position streams, (3, S): all the same."""
    return jnp.broadcast_to(jnp.arange(s), (3, s))


def _rotate(x, cos, sin):
    """x (S, heads, lanes); HF's rotate_half."""
    half = x.shape[-1] // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos[:, None, :] + rot * sin[:, None, :]


def index_scores(k, lp, u, control):
    """u (S, D) normalised -> (qI (S, Hi, di), kI (S, di), wI (S, Hi)) of the
    indexer, rotated and (the weights) scaled."""
    s = u.shape[0]
    hi, di = k["index_heads"], k["index_dim"]
    qi = _mm(u, lp["wq_index"], control).reshape(s, hi, di)
    ki = _ln(_mm(u, lp["wk_index"], control), lp["index_norm_scale"],
             lp["index_norm_bias"], k["eps"])
    emb = _angles(k["theta"], di, text_positions(s)[0])
    qi = _rotate(qi, jnp.cos(emb), jnp.sin(emb))
    ki = _rotate(ki[:, None], jnp.cos(emb), jnp.sin(emb))[:, 0]
    wi = _mm(u, lp["w_index"], control) * (hi * di) ** -0.5
    if control:
        qi, ki = _f8(qi, -1), _f8(ki, -1)
    return qi, ki, wi


def selected(k, scores, rows):
    """The selection of a block of query rows, literally: scores (Q, S)
    float32 over every position, rows (Q, 1) the queries' positions -> (Q, S)
    bool, true at the positions of ``jax.lax.top_k`` of each row's visible
    scores (``min(topk, t + 1)`` of them: what it chooses among the
    invisible, where a row sees fewer than ``topk``, is dropped)."""
    q, s = scores.shape
    visible = jnp.arange(s)[None, :] <= rows
    _, idx = jax.lax.top_k(jnp.where(visible, scores, -jnp.inf),
                           min(k["topk"], s))
    chosen = jnp.zeros((q, s), bool).at[jnp.arange(q)[:, None], idx].set(True)
    return chosen & visible


def _attention(k, lp, u, control):
    s = u.shape[0]
    nh, nkv, hd = k["heads"], k["kv_heads"], k["head_dim"]
    q = _rms(_mm(u, lp["wq"], control).reshape(s, nh, hd), lp["q_norm"],
             k["eps"])
    kk = _rms(_mm(u, lp["wk"], control).reshape(s, nkv, hd), lp["k_norm"],
              k["eps"])
    v = _mm(u, lp["wv"], control).reshape(s, nkv, hd)
    cos, sin = mrope_table(k, text_positions(s))
    q, kk = _rotate(q, cos, sin), _rotate(kk, cos, sin)
    if control:
        q, kk, v = _f8(q, -1), _f8(kk, -1), _f8(v, -1)
    qi, ki, wi = index_scores(k, lp, u, control)
    # head j * (H / KV) + g attends KV group j: (S, KV, H / KV, hd)
    q = q.reshape(s, nkv, nh // nkv, hd)
    qb = min(QUERY_BLOCK, s)
    if s % qb:
        raise ValueError(f"the reference attends {qb} query rows at a time; "
                         f"pad {s} positions to a multiple")

    def block(i):
        rows = i * qb + jnp.arange(qb)[:, None]
        sl = functools.partial(jax.lax.dynamic_slice_in_dim,
                               start_index=i * qb, slice_size=qb)
        dots = jnp.einsum("qjd,td->jqt", sl(qi), ki)           # (Hi, Q, S)
        index = jnp.sum(jax.nn.relu(dots) * sl(wi).T[:, :, None], axis=0)
        seen = selected(k, index, rows)
        scores = jnp.einsum("qjgd,tjd->jgqt", sl(q), kk) / math.sqrt(hd)
        probs = jax.nn.softmax(
            jnp.where(seen[None, None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("jgqt,tjd->qjgd", probs, v).reshape(qb, nh * hd)

    out = jax.lax.map(block, jnp.arange(s // qb)).reshape(s, nh * hd)
    return _mm(out, lp["wo"], control)


def _moe(k, mp, u, control):
    logits = _mm(u, mp["router"], control)                     # (S, E)
    vals, idx = jax.lax.top_k(logits, k["top_k"])
    w = jax.nn.softmax(vals, axis=-1)
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


@functools.partial(jax.jit, static_argnames=("key", "control"))
def _layer(key, lp, mp, h, control):
    k = dict(key)
    experts = ("w_gate", "w_up", "w_down")
    lp = _f32(lp)
    mp = {name: a if name in experts else _f32(a) for name, a in mp.items()}
    with jax.default_matmul_precision("highest"):
        h = h + _attention(k, lp, _rms(h, lp["ln1_scale"], k["eps"]), control)
        return h + _moe(k, mp, _rms(h, mp["ln2_scale"], k["eps"]), control)


def hidden(key, weights, ids, control=False):
    """ids (S,) -> the last layer's hidden state (S, D), float32."""
    k = dict(key)
    h = weights["embed"][ids].astype(jnp.float32)
    for layer in range(k["layers"]):
        lp = {name: a[layer] for name, a in weights["sparse"].items()}
        h = _layer(key, lp, weights["moe"][layer], h, control)
    return h


def _head_blocks(v: int):
    return [(c, min(c + VOCAB_BLOCK, v)) for c in range(0, v, VOCAB_BLOCK)]


@functools.partial(jax.jit, static_argnames=("key", "control"))
def _logits(key, weights, hid, control):
    k = dict(key)
    head = weights["lm_head"]                                  # (D, V)
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
            w = head[:, a:b].astype(jnp.float32)               # (D, block)
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
