"""The plain reference for the ``mellum`` family (JetBrains Mellum 2):
straightforward ``jax.numpy`` in float32 at ``default_matmul_precision
("highest")`` — no kernels, no cache, no pages, no ring, no batching, nothing
imported from the program. ``m`` is the configuration file's dict (the
published ``config.json`` keys) and ``weights`` the benchmark's own seeded
arrays in the layout the system under test takes (``benchmark/architectures/
mellum.py``).

With ``x`` the hidden state (S, D) and ``rms(x; w) = x * rsqrt(mean(x^2) +
eps) * w``, one layer ``l`` of kind ``layer_types[l]``:

- ``a = rms(h; w1)``; ``q = a Wq`` (H x hd), ``k = a Wk``, ``v = a Wv`` (KV x
  hd), no bias; ``q``, ``k`` rotated by the table OF THIS LAYER'S KIND (HF's
  ``x cos + rotate_half(x) sin`` over ``concat(freqs, freqs)``); head ``j * (H
  / KV) + g`` attends KV group ``j``; position ``i`` attends ``j <= i`` on a
  ``full_attention`` layer and ``i - sliding_window < j <= i`` on a
  ``sliding_attention`` layer (``sliding_window`` keys, itself among them);
  scores ``q k^T / sqrt(hd)``, softmax in float32; ``h += (P v) Wo``.
- tables: ``inv_freq_d = theta^(-2d / hd)``, ``d = 0 .. hd/2 - 1``. Sliding:
  ``cos(p inv_freq)``, ``sin(p inv_freq)``. Full (YaRN, transformers'
  ``_compute_yarn_parameters``, ``truncate``): ``dim(r) = hd ln(orig / (2 pi
  r)) / (2 ln theta)``, ``low = max(floor(dim(beta_fast)), 0)``, ``high =
  min(ceil(dim(beta_slow)), hd - 1)``, ``ramp_d = clip((d - low) / (high -
  low), 0, 1)``, ``inv_freq'_d = inv_freq_d / factor * ramp_d + inv_freq_d *
  (1 - ramp_d)``; cos and sin both times ``attention_factor``.
- ``u = rms(h; w2)``; router logits ``u Wr`` over ALL ``num_experts``; the top
  ``num_experts_per_tok``; weights = softmax over all, taken at the chosen,
  renormalised to sum 1 (``norm_topk_prob``) — computed here literally so,
  and equal to the softmax over the chosen logits; ``h += sum_e w_e (silu(u
  Wg_e) * (u Wu_e)) Wd_e``. No shared expert, no token dropped.
- ``h0 = embed[ids]``; logits ``= rms(h_L; w_f) @ lm_head`` (untied).

Departures from the published model, all in the configuration file: the depth
(two periods of four); weights are seeded, not trained; no per-head q/k norm
(the published config names none) and no MTP head (no key describes one).

Each layer is one jitted call with that layer's weights upcast inside it (its
experts one at a time, inside the loop over them); attention runs a block of
query rows at a time and the head a slice of the vocabulary at a time, so the
float32 copies that live beside the served system are one layer's projections,
one expert, one (H, 512, S) block of scores and one 12k-column slice of the
head, at 6144 positions.

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
QUERY_BLOCK = 512
VOCAB_BLOCK = 12288


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
    full = m["rope_parameters"]["full_attention"]
    sliding = m["rope_parameters"]["sliding_attention"]
    if full["rope_type"] != "yarn" or sliding["rope_type"] != "default":
        raise ValueError("the mellum reference knows YaRN on full layers and "
                         "plain RoPE on sliding ones")
    return tuple(sorted({
        "heads": m["num_attention_heads"], "kv_heads": m["num_key_value_heads"],
        "head_dim": m["head_dim"], "eps": m["rms_norm_eps"],
        "layer_types": tuple(m["layer_types"]),
        "window": m["sliding_window"],
        "experts": m["num_experts"],
        "held": share.get("experts_held", m["num_experts"]),
        "offset": share.get("expert_offset", 0),
        "top_k": m["num_experts_per_tok"],
        "theta_sliding": float(sliding["rope_theta"]),
        "theta": float(full["rope_theta"]), "factor": float(full["factor"]),
        "orig": full["original_max_position_embeddings"],
        "beta_fast": float(full["beta_fast"]),
        "beta_slow": float(full["beta_slow"]),
        "attention_factor": float(full["attention_factor"]),
    }.items()))


def yarn_band(k: dict) -> tuple:
    """(low, high) of the issue's formulas, as Python numbers."""
    hd = k["head_dim"]

    def dim(r):
        return hd * math.log(k["orig"] / (2 * math.pi * r)) / (
            2 * math.log(k["theta"]))

    return (max(math.floor(dim(k["beta_fast"])), 0),
            min(math.ceil(dim(k["beta_slow"])), hd - 1))


def inv_freq(k: dict, kind: str):
    """(hd / 2,) float32 rotation frequencies of a layer kind."""
    hd = k["head_dim"]
    d = jnp.arange(hd // 2, dtype=jnp.float32)
    if kind == "sliding_attention":
        return k["theta_sliding"] ** (-2.0 * d / hd)
    base = k["theta"] ** (-2.0 * d / hd)
    low, high = yarn_band(k)
    if low == high:
        high += 0.001
    ramp = jnp.clip((d - low) / (high - low), 0.0, 1.0)
    return base / k["factor"] * ramp + base * (1.0 - ramp)


def rope_table(k: dict, kind: str, s: int):
    """(cos, sin), each (S, hd): ``concat(freqs, freqs)``, times the YaRN
    attention factor on a full layer."""
    freqs = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq(k, kind)
    emb = jnp.concatenate([freqs, freqs], axis=-1)
    scale = 1.0 if kind == "sliding_attention" else k["attention_factor"]
    return jnp.cos(emb) * scale, jnp.sin(emb) * scale


def _rotate(x, cos, sin):
    """x (S, heads, hd); HF's rotate_half."""
    half = x.shape[-1] // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos[:, None, :] + rot * sin[:, None, :]


def _attention(k, kind, lp, u, control):
    s = u.shape[0]
    nh, nkv, hd = k["heads"], k["kv_heads"], k["head_dim"]
    q = _mm(u, lp["wq"], control).reshape(s, nh, hd)
    kk = _mm(u, lp["wk"], control).reshape(s, nkv, hd)
    v = _mm(u, lp["wv"], control).reshape(s, nkv, hd)
    cos, sin = rope_table(k, kind, s)
    q, kk = _rotate(q, cos, sin), _rotate(kk, cos, sin)
    if control:
        q, kk, v = _f8(q, -1), _f8(kk, -1), _f8(v, -1)
    kk = jnp.repeat(kk, nh // nkv, axis=1)
    v = jnp.repeat(v, nh // nkv, axis=1)
    qb = min(QUERY_BLOCK, s)
    if s % qb:
        raise ValueError(f"the reference attends {qb} query rows at a time; "
                         f"pad {s} positions to a multiple")
    cols = jnp.arange(s)[None, :]

    def block(i):
        rows = i * qb + jnp.arange(qb)[:, None]
        qi = jax.lax.dynamic_slice_in_dim(q, i * qb, qb)
        scores = jnp.einsum("qhd,thd->hqt", qi, kk) / math.sqrt(hd)
        seen = cols <= rows
        if kind == "sliding_attention":
            seen &= cols > rows - k["window"]
        probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf),
                               axis=-1)
        return jnp.einsum("hqt,thd->qhd", probs, v).reshape(qb, nh * hd)

    out = jax.lax.map(block, jnp.arange(s // qb)).reshape(s, nh * hd)
    return _mm(out, lp["wo"], control)


def _moe(k, mp, u, control):
    r = _mm(u, mp["router"], control)                         # (S, E)
    _, idx = jax.lax.top_k(r, k["top_k"])
    # softmax over ALL experts, taken at the chosen, renormalised to sum 1
    p = jnp.take_along_axis(jax.nn.softmax(r, axis=-1), idx, axis=-1)
    w = p / jnp.sum(p, axis=-1, keepdims=True)
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
    mp = {name: a if name in experts else _f32(a) for name, a in mp.items()}
    with jax.default_matmul_precision("highest"):
        h = h + _attention(k, kind, lp, _rms(h, lp["ln1_scale"], k["eps"]),
                           control)
        return h + _moe(k, mp, _rms(h, mp["ln2_scale"], k["eps"]), control)


def _row(tree, j):
    return {name: a[j] for name, a in tree.items()}


def hidden(key, weights, ids, control=False):
    """ids (S,) -> the last layer's hidden state (S, D), float32."""
    k = dict(key)
    h = weights["embed"][ids].astype(jnp.float32)
    stacks = {"full_attention": "attn", "sliding_attention": "window"}
    seen = dict.fromkeys(stacks, 0)
    for layer, kind in enumerate(k["layer_types"]):
        h = _layer(key, kind, _row(weights[stacks[kind]], seen[kind]),
                   weights["moe"][layer], h, control)
        seen[kind] += 1
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
