"""The plain reference for the ``granitemoehybrid`` family (IBM Granite 4.0-H):
straightforward ``jax.numpy`` in float32 at ``default_matmul_precision
("highest")`` — no kernels, no cache, no chunks, no batching, nothing imported
from the program. ``m`` is the configuration file's dict (the published
``config.json`` keys) and ``weights`` the benchmark's own seeded arrays in the
layout the system under test takes (``benchmark/architectures/
granitemoehybrid.py``).

With ``x`` the hidden state (S, D) and ``rms(x; w) = x * rsqrt(mean(x^2) +
eps) * w``:

- ``h0 = embed[ids] * embedding_multiplier``; logits ``= rms(h_L; w_f) @
  embed.T / logits_scaling`` (tied table).
- every layer: ``h += residual_multiplier * mixer(rms(h; w1))``, then ``h +=
  residual_multiplier * (moe(u) + shared(u))`` with ``u = rms(h; w2)``.
- attention (NoPE): q/k/v projections without bias and WITHOUT rotary of any
  kind; causal ``softmax(q k^T * attention_multiplier) v``; ``Wo``.
- mamba (Mamba-2): ``[z | xBC | dt] = u W_in``; ``xBC = silu(causal depthwise
  conv1d(xBC; w_c) + b_c)`` -> x, B, C; ``dt = softplus(dt + dt_bias)``, ``A =
  -exp(A_log)``; per head the LITERAL per-token recurrence ``H_t = exp(dt_t A)
  H_{t-1} + dt_t x_t (x) B_t``, ``y_t = H_t C_t + D x_t`` (a ``lax.scan`` over
  positions); ``rms(y * silu(z); w_n)`` over each of the ``n_groups`` groups;
  ``W_out``.
- moe: router logits over ALL ``share.router_experts`` experts, the top
  ``num_experts_per_tok``, weights = softmax over those; an expert is ``(silu(u
  W_g) * (u W_u)) W_d``; shared: the same SwiGLU at ``shared_intermediate_size``
  on every token.

The share is given to the reference as it is to the program: only the experts
``[share.expert_offset, + num_local_experts)`` are computed (what the absent
ones would add is left out, and that partial result goes on to the next
layer), and the table is the vocabulary's slice.

Departures from the published model, all listed in the configuration file:
the depth (one period), the experts held, the vocabulary slice; weights are
seeded, not trained.

Each layer is one jitted call with that layer's weights upcast inside it (its
experts one at a time, inside the loop over them), so the float32 copies of
one layer's mixer and one expert live beside the served system.

``control=True`` rounds every matmul operand through scaled float8 (e4m3):
the nearest precision below the bfloat16 the configuration states.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

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


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def model_key(m: dict) -> tuple:
    """What the reference reads of a configuration, hashable."""
    share = m.get("share", {})
    return tuple(sorted({
        "hidden_size": m["hidden_size"],
        "heads": m["num_attention_heads"], "kv_heads": m["num_key_value_heads"],
        "eps": m["rms_norm_eps"], "layer_types": tuple(m["layer_types"]),
        "experts": share.get("router_experts", m["num_local_experts"]),
        "held": m["num_local_experts"],
        "offset": share.get("expert_offset", 0),
        "top_k": m["num_experts_per_tok"],
        "m_heads": m["mamba_n_heads"], "m_head": m["mamba_d_head"],
        "m_state": m["mamba_d_state"], "m_conv": m["mamba_d_conv"],
        "m_groups": m["mamba_n_groups"],
        "emb_mult": float(m["embedding_multiplier"]),
        "res_mult": float(m["residual_multiplier"]),
        "att_mult": float(m["attention_multiplier"]),
        "logit_div": float(m["logits_scaling"]),
    }.items()))


def _attention(k, lp, u, control):
    s = u.shape[0]
    nh, nkv = k["heads"], k["kv_heads"]
    hd = k["hidden_size"] // nh
    q = _mm(u, lp["wq"], control).reshape(s, nh, hd)
    kk = _mm(u, lp["wk"], control).reshape(s, nkv, hd)
    v = _mm(u, lp["wv"], control).reshape(s, nkv, hd)
    if control:
        q, kk, v = _f8(q, -1), _f8(kk, -1), _f8(v, -1)
    kk = jnp.repeat(kk, nh // nkv, axis=1)
    v = jnp.repeat(v, nh // nkv, axis=1)
    scores = jnp.einsum("qhd,thd->hqt", q, kk) * k["att_mult"]
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), axis=-1)
    out = jnp.einsum("hqt,thd->qhd", probs, v).reshape(s, nh * hd)
    return _mm(out, lp["wo"], control)


def _mamba(k, lp, u, control):
    s = u.shape[0]
    nh, p, n = k["m_heads"], k["m_head"], k["m_state"]
    g, kc = k["m_groups"], k["m_conv"]
    di = nh * p
    zxbcdt = _mm(u, lp["w_in"], control)
    z = zxbcdt[:, :di]
    xbc = zxbcdt[:, di:2 * di + 2 * g * n]
    dt = zxbcdt[:, 2 * di + 2 * g * n:]
    # causal depthwise conv: tap kc-1 is the current position, tap 0 the oldest
    padded = jnp.concatenate([jnp.zeros((kc - 1, xbc.shape[1])), xbc])
    conv = sum(padded[j:j + s] * lp["conv_w"][:, j] for j in range(kc))
    xbc = jax.nn.silu(conv + lp["conv_b"])
    x = xbc[:, :di].reshape(s, nh, p)
    bm = jnp.repeat(xbc[:, di:di + g * n].reshape(s, g, n), nh // g, axis=1)
    cm = jnp.repeat(xbc[:, di + g * n:].reshape(s, g, n), nh // g, axis=1)
    dt = jax.nn.softplus(dt + lp["dt_bias"])                  # (S, H)
    a = -jnp.exp(lp["A_log"])                                 # (H,)

    def step(h, xs):
        x_t, b_t, c_t, dt_t = xs
        h = (jnp.exp(dt_t * a)[:, None, None] * h
             + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        return h, jnp.sum(h * c_t[:, None, :], axis=-1)

    _, y = jax.lax.scan(step, jnp.zeros((nh, p, n)), (x, bm, cm, dt))
    y = (y + lp["D"][:, None] * x).reshape(s, di)
    v = (y * jax.nn.silu(z)).reshape(s, g, di // g)
    v = v * jax.lax.rsqrt(jnp.mean(v * v, axis=-1, keepdims=True) + k["eps"])
    return _mm(v.reshape(s, di) * lp["norm_scale"], lp["w_out"], control)


def _moe(k, mp, u, control):
    r = _mm(u, mp["router"], control)                         # (S, E)
    vals, idx = jax.lax.top_k(r, k["top_k"])
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
    shared = _mm(jax.nn.silu(_mm(u, mp["shared_gate"], control))
                 * _mm(u, mp["shared_up"], control), mp["shared_down"],
                 control)
    return routed + shared


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


@functools.partial(jax.jit, static_argnames=("key", "kind", "control"))
def _layer(key, kind, lp, mp, h, control):
    k = dict(key)
    experts = ("w_gate", "w_up", "w_down")
    lp = _f32(lp)
    mp = {name: a if name in experts else _f32(a) for name, a in mp.items()}
    with jax.default_matmul_precision("highest"):
        mixer = _mamba if kind == "mamba" else _attention
        h = h + k["res_mult"] * mixer(
            k, lp, _rms(h, lp["ln1_scale"], k["eps"]), control)
        return h + k["res_mult"] * _moe(
            k, mp, _rms(h, mp["ln2_scale"], k["eps"]), control)


def _row(tree, j):
    return {name: a[j] for name, a in tree.items()}


def hidden(key, weights, ids, control=False):
    """ids (S,) -> the last layer's hidden state (S, D), float32."""
    k = dict(key)
    h = weights["embed"][ids].astype(jnp.float32) * k["emb_mult"]
    seen = {"mamba": 0, "attention": 0}
    for layer, kind in enumerate(k["layer_types"]):
        stack = weights["mamba" if kind == "mamba" else "attn"]
        h = _layer(key, kind, _row(stack, seen[kind]),
                   weights["moe"][layer], h, control)
        seen[kind] += 1
    return h


@functools.partial(jax.jit, static_argnames=("key", "control"))
def _logits(key, weights, hid, control):
    k = dict(key)
    with jax.default_matmul_precision("highest"):
        post = _rms(hid, weights["final_norm_scale"].astype(jnp.float32),
                    k["eps"])
        return _mm(post, weights["embed"].astype(jnp.float32).T,
                   control) / k["logit_div"]


def logits(key, weights, ids, control=False):
    """ids (S,) -> float32 logits (S, V) of the whole forward."""
    return _logits(key, weights, hidden(key, weights, ids, control), control)


def logit_gaps(key, weights, ids, start, served, *, with_control=False):
    """``benchmark/reference.py``'s result for this family: for one sequence
    ``ids`` (S,), padded at its end, whose served tokens ``served`` (N,) were
    produced at positions ``start .. start+N-1``: the gap by which the served
    token's reference logit lies below the reference's best; with
    ``with_control`` also the gap of the token the float8 forward puts
    first. Returns (gaps (N,), control_gaps (N,) or None)."""
    n = served.shape[0]

    def at_served(control):
        hid = jax.lax.dynamic_slice_in_dim(
            hidden(key, weights, ids, control), start, n)
        return _logits(key, weights, hid, control)

    ref = at_served(False)
    best = jnp.max(ref, axis=-1)
    gaps = best - jnp.take_along_axis(ref, served[:, None], axis=-1)[:, 0]
    if not with_control:
        return gaps, None
    first = jnp.argmax(at_served(True), axis=-1)
    return gaps, best - jnp.take_along_axis(ref, first[:, None], axis=-1)[:, 0]
