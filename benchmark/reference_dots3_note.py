"""The plain reference for the ``dots3_note`` family (dots3-note-prev's
language model): straightforward ``jax.numpy`` in float32 at
``default_matmul_precision("highest")`` — no kernels, no cache, no pages, no
ring, no absorption, no batching, nothing imported from the program and
nothing from another family's reference. ``m`` is the configuration file's
dict (the published ``config.json`` keys) and ``weights`` the benchmark's own
seeded arrays in the layout the system under test takes
(``benchmark/architectures/dots3_note.py``).

Two kinds of layer in one stack (``layer_types``), each latent attention at
SIZES OF ITS OWN; both EXPANDED here (keys and values rebuilt for every head
from the latent), the full kind under an explicit ``top_k`` mask and the
window kind under an explicit band, never the absorbed read of chosen rows or
of a ring that the program runs: the comparison is what checks the
absorption, the pages, the ring and the selection. With ``x = rms(h; w1)``,
``rms(x; w) = x * rsqrt(mean(x^2) + eps) * w``, ``ln`` a LayerNorm with scale
and bias, d the hidden size, and for a kind its H heads, ranks ``r_q``,
``r_kv``, head lanes ``nope``, ``rope``, ``vd`` and its ``theta`` (no bias on
any projection):

- ``c_q = rms(x W_qa; g_q) * sqrt(d / r_q)``; ``q_h = c_q W_qb^h`` = ``[q_nope_h
  (nope) | q_rope_h (rope)]``; ``[c_kv (r_kv) | k_rope (rope)] = x W_kva``;
  ``c = rms(c_kv; g_kv) * sqrt(d / r_kv)``; ``[k_nope_h (nope) | v_h (vd)] =
  c W_kvb^h``. The two factors are ``apply_mla_qkv_lora_rescale`` (false:
  both 1), at the KIND's own ranks.
- ``q_rope_h`` and the one ``k_rope`` all heads share are rotated in
  INTERLEAVED pairs: lanes ``2i, 2i+1`` by the angle ``p theta^(-2i / rope)``,
  plain RoPE (``rope_scaling`` null) by the KIND's theta.
- ``score_h(t, s) = (nope + rope)^-1/2 (q_nope_h(t) . k_nope_h(s) + q_rope_h(t)
  . k_rope(s))`` over the positions ``A_t`` the kind attends; softmax in
  float32 over ``A_t``; ``o_h = sum p v_h(s)``.
- the gate (``attention_gate_type`` ``"headwise"``): ``g = sigmoid(x W_g)``,
  ``W_g`` (d, H), no bias; ``h += concat_h(g_h o_h) W_o``.
- a FULL layer (``full_attention``: the flat keys' sizes, ``rope_theta``):
  ``A_t = S_t``, an indexer's selection. ``qI = c_q W_qI`` (Hi x di: from the
  RESCALED q latent), ``kI = ln(x W_kI; g, b)`` (di, ONE key a position),
  ``wI = x W_w`` (Hi); the FIRST ``rope`` lanes of every ``qI_j`` and of
  ``kI`` rotated by the layer's own angles in HALF-SPLIT pairs (lane ``i``
  with lane ``i + rope / 2``), the other lanes left. ``I[t, s] = Hi^-1/2
  di^-1/2 sum_j wI[t, j] relu(qI[t, j] . kI[s])`` for ``s <= t``. ``S_t`` =
  the positions of ``jax.lax.top_k(I[t, :t+1], min(topk, t + 1))``,
  literally, a row at a time.
- a WINDOW layer (``sliding_attention``: the ``swa_*`` keys' sizes,
  ``swa_rope_theta``): no indexer; ``A_t = {s : t - sliding_window_size < s
  <= t}``, the query's own position among the ``sliding_window_size``.
- ``u = rms(h; w2)``. The first ``first_k_dense_replace`` layers: ``h +=
  (silu(u Wg) * (u Wu)) Wd``. The rest: ``p = sigmoid(u W_r)`` over ALL
  ``n_routed_experts``; the top ``num_experts_per_tok`` of ``p + b`` over all
  of them (one group); weights ``routed_scaling_factor * p_e / (sum of the
  chosen p + 1e-20)``; ``h += sum_e w_e (silu(u Wg_e) * (u Wu_e)) Wd_e +
  (silu(u Sg) * (u Su)) Sd``: the held experts' part (``share``) and the
  shared expert on every token. No token dropped.
- ``h0 = embed[ids]``; logits ``= rms(h_L; w_f) @ lm_head`` (untied).

Departures from the published model, all in the configuration file: the depth
and its ``layer_types``, the experts held, the vocabulary slice; weights are
seeded, not trained; no towers and no multi-token-prediction module; what the
file lists under ``assumed``.

It runs BESIDE the served system at 20480 positions: a layer's attention is
one jitted call that walks :data:`HEAD_GROUP` heads at a time (their queries
made from ``c_q``, their keys and values from ``c``, a block of
:data:`QUERY_BLOCK` query rows at a time, their part of ``W_o`` added up), so
no (S, H, hd) query, key or value exists; what a layer attends is made once
as a mask (S, S) of booleans (419 MB at 20480 positions); the dense SwiGLU
goes :data:`WIDTH_BLOCK` columns at a time, the experts one at a time (every
held expert over every token), the head a slice of the vocabulary at a time.

``control=True`` rounds every matmul operand, and what a layer would cache
or score by (q, k, v; qI, kI), through scaled float8 (e4m3), the scale a
block's where the operand goes in blocks: the nearest precision below the
bfloat16 the configuration states.

``broken`` (the tests' wrong paths, each a name): ``"every_row"`` a window
layer attends every position it can see; ``"no_full_gate"`` /
``"no_window_gate"`` a kind's gate dropped; ``"no_rescale"`` both rank
factors 1; ``"one_theta"`` the window layers rotate by the full layers'
theta; ``"newest"`` the newest ``topk`` positions in place of the chosen.
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
BROKEN = ("every_row", "no_full_gate", "no_window_gate", "no_rescale",
          "one_theta", "newest")
#: the published names of the two kinds -> (the weights' entry, short name)
KINDS = {"full_attention": "sparse_latent",
         "sliding_attention": "window_latent"}


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


def _sizes(m: dict, prefix: str) -> tuple:
    """One kind's sizes off the published keys (``prefix`` "" the full
    kind's flat keys, "swa_" the window kind's), hashable."""
    return tuple(sorted({
        "heads": m[prefix + "num_attention_heads"],
        "r_q": m[prefix + "q_lora_rank"], "r_kv": m[prefix + "kv_lora_rank"],
        "nope": m[prefix + "qk_nope_head_dim"],
        "rope": m[prefix + "qk_rope_head_dim"],
        "vd": m[prefix + "v_head_dim"],
        "theta": float(m[prefix + "rope_theta"]),
    }.items()))


def model_key(m: dict) -> tuple:
    """What the reference reads of a configuration, hashable."""
    share = m.get("share", {})
    for key, want in (("rope_scaling", None), ("scoring_func", "sigmoid"),
                      ("topk_method", "noaux_tc"), ("norm_topk_prob", True),
                      ("n_shared_experts", 1), ("moe_layer_freq", 1),
                      ("attention_bias", False),
                      ("attention_gate_type", "headwise"),
                      ("swa_attention_gate_type", "headwise"),
                      ("tie_word_embeddings", False)):
        if m.get(key, want) != want:
            raise ValueError(f"the dots3_note reference knows {key}="
                             f"{want!r} alone, got {m[key]!r}")
    if any(t not in KINDS for t in m["layer_types"]) \
            or len(m["layer_types"]) != m["num_hidden_layers"]:
        raise ValueError(f"layer_types must name one of {sorted(KINDS)} for "
                         f"each of the {m['num_hidden_layers']} layers")
    return tuple(sorted({
        "hidden": m["hidden_size"],
        "layer_types": tuple(m["layer_types"]),
        "dense_layers": m["first_k_dense_replace"],
        "full": _sizes(m, ""), "window": _sizes(m, "swa_"),
        "band": m["sliding_window_size"],
        "rescale": bool(m["apply_mla_qkv_lora_rescale"]),
        "eps": m["rms_norm_eps"],
        "index_heads": m["index_n_heads"], "index_dim": m["index_head_dim"],
        "topk": m["index_topk"],
        "experts": share.get("router_experts", m["n_routed_experts"]),
        "held": share.get("experts_held", m["n_routed_experts"]),
        "offset": share.get("expert_offset", 0),
        "top_k": m["num_experts_per_tok"],
        "routed_scale": float(m["routed_scaling_factor"]),
    }.items()))


def rope_table(g: dict, s: int):
    """(cos, sin), each (S, rope / 2): one angle a pair, plain RoPE by the
    kind's theta."""
    rot = g["rope"]
    i = jnp.arange(rot // 2, dtype=jnp.float32)
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * (
        g["theta"] ** (-2.0 * i / rot))
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


def softmax_scale(g: dict) -> float:
    return (g["nope"] + g["rope"]) ** -0.5


def rank_factor(k: dict, rank: int, broken=()) -> float:
    """``sqrt(hidden / rank)`` under ``apply_mla_qkv_lora_rescale``."""
    if not k["rescale"] or "no_rescale" in broken:
        return 1.0
    return math.sqrt(k["hidden"] / rank)


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


def band(k, s: int, broken=()):
    """(S, S) bool: what a window layer's positions attend, the
    ``sliding_window_size`` newest a query can see, itself among them."""
    at = jnp.arange(s)
    seen = at[None, :] <= at[:, None]
    if "every_row" in broken:
        return seen
    return seen & (at[None, :] > at[:, None] - k["band"])


def selection(k, g, lp, x, c_q, control, broken=()):
    """x (S, D) normalised, c_q (S, r_q) rescaled -> (S, S) bool: the
    positions each position's query attends in a FULL layer
    (:func:`selected`'s ids as :func:`chosen_mask` marks them, a block of
    query rows at a time; made once a layer, which every group of heads then
    reads)."""
    s = x.shape[0]
    hi, di = k["index_heads"], k["index_dim"]
    qb = _blocks(s)
    if "newest" in broken:
        at = jnp.arange(s)
        return (at[None, :] <= at[:, None]) & (
            at[None, :] > at[:, None] - min(k["topk"], s))
    cos, sin = rope_table(g, s)
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


def _attention(k, kind, lp, x, control, broken=()):
    """One layer's attention of ``kind`` (a published name): x (S, D)
    normalised -> (S, D), gate and ``W_o`` applied."""
    s = x.shape[0]
    full = kind == "full_attention"
    g = dict(k["full" if full else "window"])
    nh, nope, rot, vd, rank = (g["heads"], g["nope"], g["rope"], g["vd"],
                               g["r_kv"])
    hg = math.gcd(HEAD_GROUP, nh)
    qb = _blocks(s)
    c_q = _rms(_mm(x, lp["wq_a"], control), lp["q_norm"], k["eps"]) \
        * rank_factor(k, g["r_q"], broken)
    kv = _mm(x, lp["wkv_a"], control)
    c = _rms(kv[:, :rank], lp["kv_norm"], k["eps"]) \
        * rank_factor(k, rank, broken)
    turned = dict(g, theta=dict(k["full"])["theta"]) \
        if not full and "one_theta" in broken else g
    cos, sin = rope_table(turned, s)
    k_rope = _rotate_pairs(kv[:, None, rank:], cos, sin)[:, 0]  # (S, rope)
    if control:
        k_rope = _f8(k_rope, -1)
    seen_all = (selection(k, g, lp, x, c_q, control, broken) if full
                else band(k, s, broken))                        # (S, S) bool
    scale = softmax_scale(g)
    if ("no_full_gate" if full else "no_window_gate") in broken:
        gate = jnp.ones((s, nh), jnp.float32)
    else:
        gate = jax.nn.sigmoid(_mm(x, lp["wg"], control))        # (S, H)
    # a group of heads at a time: their columns of W_qb and W_kvb, their
    # gates, their rows of W_o
    wqb = lp["wq_b"].reshape(-1, nh // hg, hg, nope + rot)
    wkvb = lp["wkv_b"].reshape(rank, nh // hg, hg, nope + vd)
    wo = lp["wo"].reshape(nh // hg, hg * vd, -1)
    gates = gate.reshape(s, nh // hg, hg)

    def group(acc, i):
        kvb = _mm(c, wkvb[:, i].reshape(rank, -1), control).reshape(
            s, hg, nope + vd)
        k_nope, v = kvb[..., :nope], kvb[..., nope:]
        if control:
            k_nope, v = _f8(k_nope, -1), _f8(v, -1)

        def block(j):
            sl = functools.partial(jax.lax.dynamic_slice_in_dim,
                                   start_index=j * qb, slice_size=qb)
            q = _mm(sl(c_q), wqb[:, i].reshape(-1, hg * (nope + rot)),
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
            return jnp.einsum("hqt,thd->qhd", probs, v)

        out = jax.lax.map(block, jnp.arange(s // qb)).reshape(s, hg, vd)
        out = (out * gates[:, i, :, None]).reshape(s, hg * vd)
        return acc + _mm(out, wo[i], control), None

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


def route(k, mp, u, control):
    """u (S, D) -> (ids (S, top_k) over the published width, weights (S,
    top_k)): the top k of ``p + b`` over every expert, one group."""
    p = jax.nn.sigmoid(_mm(u, mp["router"], control))         # (S, E)
    _, idx = jax.lax.top_k(p + mp["router_bias"], k["top_k"])
    chosen = jnp.take_along_axis(p, idx, axis=-1)
    return idx, chosen / (jnp.sum(chosen, axis=-1, keepdims=True)
                          + 1e-20) * k["routed_scale"]


def _moe(k, mp, u, control):
    idx, w = route(k, mp, u, control)
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


@functools.partial(jax.jit,
                   static_argnames=("key", "kind", "control", "broken"),
                   donate_argnums=(3,))
def _attend(key, kind, lp, h, control, broken):
    k = dict(key)
    lp = _f32(lp)
    with jax.default_matmul_precision("highest"):
        return h + _attention(k, kind, lp,
                              _rms(h, lp["ln1_scale"], k["eps"]), control,
                              broken)


@functools.partial(jax.jit, static_argnames=("key", "control"),
                   donate_argnums=(2,))
def _feed(key, mp, h, control):
    k = dict(key)
    mp = {name: a if name in _WIDE else _f32(a) for name, a in mp.items()}
    with jax.default_matmul_precision("highest"):
        u = _rms(h, mp["ln2_scale"], k["eps"])
        if "router" not in mp:
            return h + _swiglu(u, mp["w_gate"], mp["w_up"], mp["w_down"],
                               control)
        return h + _moe(k, mp, u, control)


def _row(tree, j):
    return {name: a[j] for name, a in tree.items()}


def hidden(key, weights, ids, control=False, *, broken=()):
    """ids (S,) -> the last layer's hidden state (S, D), float32.
    ``broken``: names of :data:`BROKEN`, the tests' wrong paths."""
    k = dict(key)
    broken = tuple(broken)
    if set(broken) - set(BROKEN):
        raise ValueError(f"unknown broken path in {broken!r}: {BROKEN}")
    h = weights["embed"][ids].astype(jnp.float32)
    seen = dict.fromkeys(KINDS, 0)
    for layer, kind in enumerate(k["layer_types"]):
        lp = _row(weights[KINDS[kind]], seen[kind])
        seen[kind] += 1
        h = _attend(key, kind, lp, h, control, broken)
        h = _feed(key, weights["moe"][layer], h, control)
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


def logits(key, weights, ids, control=False, *, broken=()):
    """ids (S,) -> float32 logits (S, V) of the whole forward. (The tests'
    entry; :func:`logit_gaps` never holds (S, V).)"""
    return _logits(key, weights,
                   hidden(key, weights, ids, control, broken=broken), control)


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


def logit_gaps(key, weights, ids, start, served, *, with_control=False,
               broken=()):
    """``benchmark/reference.py``'s result for this family: for one sequence
    ``ids`` (S,), padded at its end, whose served tokens ``served`` (N,) were
    produced at positions ``start .. start+N-1``: the gap by which the served
    token's reference logit lies below the reference's best; with
    ``with_control`` also the gap of the token the float8 forward puts
    first. Returns (gaps (N,), control_gaps (N,) or None)."""
    n = served.shape[0]

    def rows(control):
        return jax.lax.dynamic_slice_in_dim(
            hidden(key, weights, ids, control, broken=broken), start, n)

    hid = rows(False)
    return _gaps(key, weights, hid, rows(True) if with_control else hid,
                 served, with_control)
