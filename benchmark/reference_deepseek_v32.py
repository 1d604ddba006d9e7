"""The plain reference for the ``deepseek_v32`` family (DeepSeek-V3.2-Exp):
straightforward ``jax.numpy`` in float32 at ``default_matmul_precision
("highest")`` — no kernels, no cache, no pages, no absorption, no batching,
nothing imported from the program. ``m`` is the configuration file's dict (the
published ``config.json`` keys) and ``weights`` the benchmark's own seeded
arrays in the layout the system under test takes
(``benchmark/architectures/deepseek_v32.py``).

Attention is the EXPANDED form under an explicit ``top_k`` mask at every
position — keys and values rebuilt for every head from the latent, the
selection a mask made of ``jax.lax.top_k``'s ids — never the absorbed read of
chosen rows the program runs, so the comparison is what checks the
absorption, the pages and the selection. With ``x = rms(h; w1)``, ``rms(x; w)
= x * rsqrt(mean(x^2) + eps) * w``, ``ln`` a LayerNorm with scale and bias, H
heads, one layer (no bias on any projection):

- ``c_q = rms(x W_qa; g_q)`` (q_lora_rank); ``q_h = c_q W_qb`` = ``[q_nope_h
  (nope) | q_rope_h (rope)]``; ``[c_kv (kv_lora_rank) | k_rope (rope)] = x
  W_kva``; ``c = rms(c_kv; g_kv)``; ``[k_nope_h (nope) | v_h (vd)] = c
  W_kvb^h``.
- ``q_rope_h`` and the one ``k_rope`` all heads share are rotated in
  INTERLEAVED pairs: lanes ``2i, 2i+1`` by the angle ``p inv_freq'_i``. YaRN
  (transformers' ``_compute_yarn_parameters``, ``truncate``): ``inv_freq_i =
  theta^(-2i / rope)``, ``dim(r) = rope ln(orig / (2 pi r)) / (2 ln theta)``,
  ``low = max(floor(dim(beta_fast)), 0)``, ``high = min(ceil(dim(beta_slow)),
  rope - 1)``, ``ramp_i = clip((i - low) / (high - low), 0, 1)``,
  ``inv_freq'_i = inv_freq_i / factor * ramp_i + inv_freq_i (1 - ramp_i)``;
  ``mscale = mscale_all_dim``, so cos and sin carry no factor.
- the indexer: ``qI = c_q W_qI`` (Hi x di: from the q latent), ``kI = ln(x
  W_kI; g, b)`` (di, ONE key a position), ``wI = x W_w`` (Hi); the FIRST
  ``rope`` lanes of every ``qI_j`` and of ``kI`` rotated by the same angles
  in HALF-SPLIT pairs (lane ``i`` with lane ``i + rope / 2``), the other
  lanes left. ``I[t, s] = Hi^-1/2 di^-1/2 sum_j wI[t, j] relu(qI[t, j] .
  kI[s])`` for ``s <= t``. ``S_t`` = the positions of ``jax.lax.top_k(I[t,
  :t+1], min(topk, t + 1))``, literally, a row at a time.
- ``score_h(t, s) = sc (q_nope_h(t) . k_nope_h(s) + q_rope_h(t) . k_rope(s))``
  for ``s in S_t``; ``sc = (nope + rope)^-1/2 (0.1 mscale_all_dim ln(factor)
  + 1)^2``; softmax in float32 over ``S_t``; ``h += concat_h(P_h v_h) W_o``.
- ``u = rms(h; w2)``. The first ``first_k_dense_replace`` layers: ``h +=
  (silu(u Wg) * (u Wu)) Wd``. The rest: ``p = sigmoid(u W_r)`` over ALL
  ``n_routed_experts``; ``n_group`` groups of equal size in order, a group's
  score the sum of its two largest ``p + b``; the ``topk_group`` best groups
  kept; the top ``num_experts_per_tok`` of ``p + b`` among their experts;
  weights ``routed_scaling_factor * p_e / (sum of the chosen p + 1e-20)``;
  ``h += sum_e w_e (silu(u Wg_e) * (u Wu_e)) Wd_e + (silu(u Sg) * (u Su))
  Sd``: the held experts' part (``share``) and the shared expert on every
  token. No token dropped.
- ``h0 = embed[ids]``; logits ``= rms(h_L; w_f) @ lm_head`` (untied).

Departures from the published model, all in the configuration file: the depth,
the leading dense layers, the experts held, the vocabulary slice; weights are
seeded, not trained; no multi-token-prediction module; what the file lists
under ``assumed``.

It runs BESIDE the served system at 20480 positions: a layer's attention is
one jitted call that walks :data:`HEAD_GROUP` heads at a time (their queries
made from ``c_q``, their keys and values from ``c``, a block of
:data:`QUERY_BLOCK` query rows at a time, their part of ``W_o`` added up), so
no (S, H, hd) query, key or value exists; the selection is made once a layer
as a mask (S, S) of booleans (419 MB at 20480 positions: a scatter of the
chosen ids a block of query rows, not one a block a group of heads); the
dense SwiGLU goes :data:`WIDTH_BLOCK` columns at a
time, the experts one at a time (every held expert over every token: 16 x
20480 rows where 640 are routed, 29 TFLOP a layer that a gather would save
and a plain reference does not), the head a slice of the vocabulary at a
time.

``control=True`` rounds every matmul operand, and what a layer would cache
or score by (q, k, v; qI, kI), through scaled float8 (e4m3), the scale a
block's where the operand goes in blocks: the nearest precision below the
bfloat16 the configuration states.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

F8 = jnp.float8_e4m3fn
F8_MAX = 448.0
#: query rows attended at a time, heads walked at a time, columns of the
#: dense SwiGLU and of the head multiplied at a time
QUERY_BLOCK = 128
HEAD_GROUP = 8
WIDTH_BLOCK = 2048
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
    rope = m["rope_scaling"]
    if rope.get("rope_type", rope.get("type")) != "yarn" \
            or float(rope["mscale"]) != float(rope["mscale_all_dim"]):
        raise ValueError("the deepseek_v32 reference knows YaRN with mscale "
                         "= mscale_all_dim (cos and sin unscaled)")
    for key, want in (("scoring_func", "sigmoid"),
                      ("topk_method", "noaux_tc"), ("norm_topk_prob", True),
                      ("n_shared_experts", 1), ("moe_layer_freq", 1),
                      ("attention_bias", False),
                      ("tie_word_embeddings", False)):
        if m.get(key, want) != want:
            raise ValueError(f"the deepseek_v32 reference knows {key}="
                             f"{want!r} alone, got {m[key]!r}")
    return tuple(sorted({
        "layers": m["num_hidden_layers"],
        "dense_layers": m["first_k_dense_replace"],
        "heads": m["num_attention_heads"],
        "nope": m["qk_nope_head_dim"], "rope": m["qk_rope_head_dim"],
        "vd": m["v_head_dim"], "rank": m["kv_lora_rank"],
        "eps": m["rms_norm_eps"],
        "index_heads": m["index_n_heads"], "index_dim": m["index_head_dim"],
        "topk": m["index_topk"],
        "experts": share.get("router_experts", m["n_routed_experts"]),
        "held": share.get("experts_held", m["n_routed_experts"]),
        "offset": share.get("expert_offset", 0),
        "top_k": m["num_experts_per_tok"],
        "groups": m["n_group"], "groups_kept": m["topk_group"],
        "routed_scale": float(m["routed_scaling_factor"]),
        "theta": float(m["rope_theta"]), "factor": float(rope["factor"]),
        "orig": rope["original_max_position_embeddings"],
        "beta_fast": float(rope["beta_fast"]),
        "beta_slow": float(rope["beta_slow"]),
        "mscale_all_dim": float(rope["mscale_all_dim"]),
    }.items()))


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
    return jnp.cos(angle), jnp.sin(angle)


def _rotate_pairs(x, cos, sin):
    """x (S, heads, rope): lanes (2i, 2i+1) rotated by pair i's angle, in
    place (the attention's rope lanes)."""
    even, odd = x[..., 0::2], x[..., 1::2]
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.stack([even * c - odd * s, odd * c + even * s],
                     axis=-1).reshape(x.shape)


def _rotate_first_half_split(x, cos, sin):
    """x (S, heads, di): of its FIRST ``rope = 2 * cos.shape[-1]`` lanes,
    lane i and lane i + rope / 2 rotated by pair i's angle; the lanes after
    them left as they are (the indexer's)."""
    half = cos.shape[-1]
    a, b, rest = x[..., :half], x[..., half:2 * half], x[..., 2 * half:]
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([a * c - b * s, b * c + a * s, rest], axis=-1)


def softmax_scale(k: dict) -> float:
    m = (0.1 * k["mscale_all_dim"] * math.log(k["factor"]) + 1.0
         if k["factor"] > 1 else 1.0)
    return (k["nope"] + k["rope"]) ** -0.5 * m ** 2


def selected(k, scores, rows):
    """The selection of a block of query rows, literally: scores (Q, S)
    float32 over every position, rows (Q, 1) the queries' positions -> the
    ids (Q, min(topk, S)) of ``jax.lax.top_k`` of each row's visible scores
    (what it chooses among the invisible, where a row sees fewer than
    ``topk``, the mask drops: :func:`chosen_mask`)."""
    s = scores.shape[1]
    visible = jnp.arange(s)[None, :] <= rows
    return jax.lax.top_k(jnp.where(visible, scores, -jnp.inf),
                         min(k["topk"], s))[1]


def chosen_mask(idx, rows, s: int):
    """ids (Q, n) and the queries' positions (Q, 1) -> (Q, S) bool: true at
    the chosen positions a query can see."""
    q = idx.shape[0]
    chosen = jnp.zeros((q, s), bool).at[jnp.arange(q)[:, None], idx].set(True)
    return chosen & (jnp.arange(s)[None, :] <= rows)


def _blocks(s: int):
    qb = min(QUERY_BLOCK, s)
    if s % qb:
        raise ValueError(f"the reference attends {qb} query rows at a time; "
                         f"pad {s} positions to a multiple")
    return qb


def selection(k, lp, x, c_q, control, newest: bool = False):
    """x (S, D) normalised, c_q (S, q_lora_rank) -> (S, S) bool: the
    positions each position's query attends (:func:`selected`'s ids as
    :func:`chosen_mask` marks them, a block of query rows at a time; made
    once a layer, which every group of heads then reads). ``newest`` (a
    test's broken path): the newest ``topk`` positions in their place."""
    s = x.shape[0]
    hi, di = k["index_heads"], k["index_dim"]
    qb = _blocks(s)
    if newest:
        at = jnp.arange(s)
        return (at[None, :] <= at[:, None]) & (
            at[None, :] > at[:, None] - min(k["topk"], s))
    cos, sin = rope_table(k, s)
    ki = _ln(_mm(x, lp["wk_index"], control), lp["index_norm_scale"],
             lp["index_norm_bias"], k["eps"])
    ki = _rotate_first_half_split(ki[:, None], cos, sin)[:, 0]
    wi = _mm(x, lp["w_index"], control) * (hi * di) ** -0.5
    if control:
        ki = _f8(ki, -1)

    def block(i):
        sl = functools.partial(jax.lax.dynamic_slice_in_dim,
                               start_index=i * qb, slice_size=qb)
        qi = _mm(sl(c_q), lp["wq_index"], control).reshape(qb, hi, di)
        qi = _rotate_first_half_split(qi, sl(cos), sl(sin))
        if control:
            qi = _f8(qi, -1)
        dots = jnp.einsum("qjd,td->jqt", qi, ki)               # (Hi, Q, S)
        index = jnp.sum(jax.nn.relu(dots) * sl(wi).T[:, :, None], axis=0)
        rows = i * qb + jnp.arange(qb)[:, None]
        return chosen_mask(selected(k, index, rows), rows, s)

    return jax.lax.map(block, jnp.arange(s // qb)).reshape(s, s)


def _attention(k, lp, x, control, newest=False):
    s = x.shape[0]
    nh, nope, rot, vd, rank = (k["heads"], k["nope"], k["rope"], k["vd"],
                               k["rank"])
    hg = math.gcd(HEAD_GROUP, nh)
    qb = _blocks(s)
    c_q = _rms(_mm(x, lp["wq_a"], control), lp["q_norm"], k["eps"])
    kv = _mm(x, lp["wkv_a"], control)
    c = _rms(kv[:, :rank], lp["kv_norm"], k["eps"])
    cos, sin = rope_table(k, s)
    k_rope = _rotate_pairs(kv[:, None, rank:], cos, sin)[:, 0]  # (S, rope)
    if control:
        k_rope = _f8(k_rope, -1)
    seen_all = selection(k, lp, x, c_q, control, newest)       # (S, S) bool
    scale = softmax_scale(k)
    # a group of heads at a time: their columns of W_qb and W_kvb, their rows
    # of W_o
    wqb = lp["wq_b"].reshape(-1, nh // hg, hg, nope + rot)
    wkvb = lp["wkv_b"].reshape(rank, nh // hg, hg, nope + vd)
    wo = lp["wo"].reshape(nh // hg, hg * vd, -1)

    def group(acc, g):
        kvb = _mm(c, wkvb[:, g].reshape(rank, -1), control).reshape(
            s, hg, nope + vd)
        k_nope, v = kvb[..., :nope], kvb[..., nope:]
        if control:
            k_nope, v = _f8(k_nope, -1), _f8(v, -1)

        def block(i):
            sl = functools.partial(jax.lax.dynamic_slice_in_dim,
                                   start_index=i * qb, slice_size=qb)
            q = _mm(sl(c_q), wqb[:, g].reshape(-1, hg * (nope + rot)),
                    control).reshape(qb, hg, nope + rot)
            q_nope = q[..., :nope]
            q_rope = _rotate_pairs(q[..., nope:], sl(cos), sl(sin))
            if control:
                q_nope, q_rope = _f8(q_nope, -1), _f8(q_rope, -1)
            scores = (jnp.einsum("qhd,thd->hqt", q_nope, k_nope)
                      + jnp.einsum("qhd,td->hqt", q_rope, k_rope)) * scale
            seen = sl(seen_all)
            probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf),
                                   axis=-1)
            return jnp.einsum("hqt,thd->qhd", probs, v).reshape(qb, hg * vd)

        out = jax.lax.map(block, jnp.arange(s // qb)).reshape(s, hg * vd)
        return acc + _mm(out, wo[g], control), None

    acc, _ = jax.lax.scan(group, jnp.zeros_like(x), jnp.arange(nh // hg))
    return acc


def _swiglu(u, wg, wu, wd, control):
    """A SwiGLU of any width, :data:`WIDTH_BLOCK` columns at a time, each
    block upcast where it is used."""
    f = wg.shape[1]
    fb = math.gcd(WIDTH_BLOCK, f)

    def block(acc, i):
        cut = functools.partial(jax.lax.dynamic_slice_in_dim,
                                start_index=i * fb, slice_size=fb)
        hid = jax.nn.silu(_mm(u, _f32(cut(wg, axis=1)), control)) * _mm(
            u, _f32(cut(wu, axis=1)), control)
        return acc + _mm(hid, _f32(cut(wd, axis=0)), control), None

    acc, _ = jax.lax.scan(block, jnp.zeros_like(u), jnp.arange(f // fb))
    return acc


def route(k, mp, u, control, ungrouped: bool = False):
    """u (S, D) -> (ids (S, top_k) over the published width, weights (S,
    top_k)). ``ungrouped`` (a test's broken path): the top k of every
    expert, no group left out."""
    p = jax.nn.sigmoid(_mm(u, mp["router"], control))         # (S, E)
    biased = p + mp["router_bias"]
    if not ungrouped:
        s, e = p.shape
        groups = biased.reshape(s, k["groups"], e // k["groups"])
        rank = jnp.sum(jax.lax.top_k(groups, 2)[0], axis=-1)  # (S, G)
        _, best = jax.lax.top_k(rank, k["groups_kept"])
        kept = jnp.zeros((s, k["groups"]), bool).at[
            jnp.arange(s)[:, None], best].set(True)
        biased = jnp.where(kept[:, :, None], groups, -jnp.inf).reshape(s, e)
    _, idx = jax.lax.top_k(biased, k["top_k"])
    chosen = jnp.take_along_axis(p, idx, axis=-1)
    return idx, chosen / (jnp.sum(chosen, axis=-1, keepdims=True)
                          + 1e-20) * k["routed_scale"]


def _moe(k, mp, u, control, ungrouped=False):
    idx, w = route(k, mp, u, control, ungrouped)
    local = idx - k["offset"]
    held = (local >= 0) & (local < k["held"])
    # (S, held): the weight of each held expert for each token, 0 if unrouted
    combine = jnp.sum(jax.nn.one_hot(jnp.where(held, local, k["held"]),
                                     k["held"]) * w[..., None], axis=1)

    def expert(acc, xs):
        wg, wu, wd, c = xs          # one expert upcast at a time
        return acc + c[:, None] * _swiglu(u, wg, wu, wd, control), None

    routed, _ = jax.lax.scan(
        expert, jnp.zeros_like(u),
        (mp["w_gate"], mp["w_up"], mp["w_down"], combine.T))
    return routed + _swiglu(u, mp["shared_gate"], mp["shared_up"],
                            mp["shared_down"], control)


_WIDE = ("w_gate", "w_up", "w_down", "shared_gate", "shared_up",
         "shared_down")


@functools.partial(jax.jit, static_argnames=("key", "control", "newest"),
                   donate_argnums=(2,))
def _attend(key, lp, h, control, newest):
    k = dict(key)
    lp = _f32(lp)
    with jax.default_matmul_precision("highest"):
        return h + _attention(k, lp, _rms(h, lp["ln1_scale"], k["eps"]),
                              control, newest)


@functools.partial(jax.jit, static_argnames=("key", "control", "ungrouped"),
                   donate_argnums=(2,))
def _feed(key, mp, h, control, ungrouped):
    k = dict(key)
    mp = {name: a if name in _WIDE else _f32(a) for name, a in mp.items()}
    with jax.default_matmul_precision("highest"):
        u = _rms(h, mp["ln2_scale"], k["eps"])
        if "router" not in mp:
            return h + _swiglu(u, mp["w_gate"], mp["w_up"], mp["w_down"],
                               control)
        return h + _moe(k, mp, u, control, ungrouped)


def _row(tree, j):
    return {name: a[j] for name, a in tree.items()}


def hidden(key, weights, ids, control=False, *, newest=False,
           ungrouped=False):
    """ids (S,) -> the last layer's hidden state (S, D), float32. ``newest``
    / ``ungrouped``: the tests' two broken paths (:func:`selection`,
    :func:`route`)."""
    k = dict(key)
    h = weights["embed"][ids].astype(jnp.float32)
    for layer in range(k["layers"]):
        h = _attend(key, _row(weights["sparse_latent"], layer), h, control,
                    newest)
        h = _feed(key, weights["moe"][layer], h, control, ungrouped)
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


def logits(key, weights, ids, control=False, **broken):
    """ids (S,) -> float32 logits (S, V) of the whole forward. (The tests'
    entry; :func:`logit_gaps` never holds (S, V).)"""
    return _logits(key, weights, hidden(key, weights, ids, control, **broken),
                   control)


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
