"""The routed expert layer of the families walked by layer kinds
(``models/hybrid.py``), told which experts it holds.

The router keeps its published width: logits over all ``cfg.router_width``
outputs, the top ``cfg.experts_per_tok`` of them, weights by
``cfg.score_func`` (:func:`route`). The chip computes the experts
``[cfg.expert_offset, + cfg.local_experts)`` for the tokens routed to them,
the shared expert on every token where the family has one, and every
token's IDENTITY experts (ids from ``cfg.num_experts`` up: no weights, they
belong to no chip's share; :func:`_with_identity`). No token is dropped and
there is no capacity factor. What the absent experts would have added is
left out: their chip's part (expert parallelism without its all-to-all).

Two ways through the held experts, chosen by the static token count:

- up to :data:`DENSE_MAX_TOKENS` tokens (the decode step): every held expert
  over every token, the combine weight (zero where a token was not routed to
  an expert) applied before the down projection, which then contracts over
  experts and width at once: all the held weights are read either way and
  the layer is bound by those bytes, not by the wasted multiplies;
- more tokens (a prefill): assignments sorted by expert and the three
  grouped products gate / up / down over the held groups
  (:func:`_grouped_products`); the rows of assignments to absent (and
  identity) experts sort last, belong to no group and are selected out of
  the result. The tokens are padded to a multiple of 8 first
  (:data:`GROUPED_TOKEN_MULTIPLE`). The products are one tiled kernel on a
  TPU where the widths are whole lane tiles (``models/grouped_matmul.py``)
  and three ``jax.lax.ragged_dot``, the oracle, everywhere else:
  :func:`grouped_product` says which, same operands, same float32
  accumulation, same rounding of each product.

Scopes: ``moe.route``, ``moe.experts`` (``moe.experts.grouped`` within it:
the prefill's grouped products), ``moe.shared``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import grouped_matmul
from .configs import ModelConfig

#: token counts up to this take the dense path: where dense multiplies
#: (tokens x held x 3DF) stop hiding under the held experts' weight bytes on
#: a v5e (197 TFLOP/s against 819 GB/s: ~240 tokens a byte-bound pass)
DENSE_MAX_TOKENS = 256
#: the grouped path pads its tokens to a multiple of this: on a v5e the walk
#: compiled whole (embed to logits under one jit) comes out 0.5-5% off at a
#: token count that is not (257, 300, 324, 420: PERF.md §6 "PR 30"; eager, or
#: the layer compiled alone, or any multiple of 8, is exact to 2e-7)
GROUPED_TOKEN_MULTIPLE = 8


def route(cfg: ModelConfig, router_w: jnp.ndarray, u: jnp.ndarray,
          bias: jnp.ndarray | None = None):
    """u (T, D) -> (expert ids (T, k) int32 over the PUBLISHED width,
    weights (T, k) float32: softmax over the chosen k logits).

    ``cfg.score_func`` ``"sigmoid"``: scores ``p = sigmoid(logits)``, the
    top k taken of ``p + bias`` ((E,) float32, a per-expert SELECTION bias
    that no weight sees), weights ``route_scale * p_e / (sum of the chosen p
    + cfg.route_norm_eps)``; ``"softmax_all"``: ``p = softmax(logits)`` over
    every output, chosen the same way, weights ``route_scale * p_e``; all of
    it float32. With ``cfg.route_groups`` > 1 the top k are taken within the
    ``cfg.route_groups_kept`` best groups (:func:`_group_limited`)."""
    with jax.named_scope("moe.route"):
        logits = jnp.einsum("td,de->te", u, router_w,
                            preferred_element_type=jnp.float32)
        if cfg.score_func != "softmax":
            p = (jax.nn.sigmoid if cfg.score_func == "sigmoid"
                 else jax.nn.softmax)(logits)
            biased = p + bias.astype(jnp.float32)
            if cfg.route_groups > 1:
                biased = _group_limited(cfg, biased)
            _, idx = jax.lax.top_k(biased, cfg.experts_per_tok)
            # the chosen scores by a one-hot product, exact, and not by
            # take_along_axis, whose gather leaves the scope's path behind
            chosen = jnp.einsum("tke,te->tk", jax.nn.one_hot(
                idx, p.shape[-1], dtype=jnp.float32), p)
            if cfg.score_func == "sigmoid":
                chosen = chosen / (jnp.sum(chosen, axis=-1, keepdims=True)
                                   + cfg.route_norm_eps)
            return idx.astype(jnp.int32), chosen * cfg.route_scale
        vals, idx = jax.lax.top_k(logits, cfg.experts_per_tok)
        return idx.astype(jnp.int32), jax.nn.softmax(vals, axis=-1)


def _group_limited(cfg: ModelConfig, biased):
    """Group-limited selection: biased scores (T, E) float32, the E outputs
    in ``cfg.route_groups`` equal groups in order -> the same with every
    output outside the ``cfg.route_groups_kept`` best groups at -inf. A
    group's rank is the sum of its two largest biased scores, a tie to the
    earlier group; the kept groups hold at least ``experts_per_tok``
    outputs, so the top k that follows never takes a masked one."""
    t, e = biased.shape
    groups = biased.reshape(t, cfg.route_groups, e // cfg.route_groups)
    rank = jnp.sum(jax.lax.top_k(groups, 2)[0], axis=-1)        # (T, G)
    _, best = jax.lax.top_k(rank, cfg.route_groups_kept)
    kept = jnp.any(best[:, :, None] == jnp.arange(cfg.route_groups),
                   axis=1)                                      # (T, G)
    return jnp.where(kept[:, :, None], groups, -jnp.inf).reshape(t, e)


def _local(cfg: ModelConfig, idx):
    """Published expert ids -> (index among the held experts, held?)."""
    local = idx - cfg.expert_offset
    return local, (local >= 0) & (local < cfg.local_experts)


def _assignments(cfg: ModelConfig, local, held):
    """Assignments per held expert (Eh,) int32 among those ``held`` marks."""
    eh = cfg.local_experts
    return jnp.sum(
        jax.nn.one_hot(jnp.where(held, local, eh), eh + 1, dtype=jnp.int32),
        axis=tuple(range(held.ndim)))[:eh]


def _experts_dense(cfg: ModelConfig, mp: dict, u, idx, weights):
    eh = cfg.local_experts
    local, held = _local(cfg, idx)
    # (T, Eh): a token's weight for each held expert, 0 where not routed
    combine = jnp.sum(
        jax.nn.one_hot(jnp.where(held, local, eh), eh, dtype=jnp.float32)
        * weights[..., None], axis=1)
    gate = jnp.einsum("td,edf->tef", u, mp["w_gate"])
    up = jnp.einsum("td,edf->tef", u, mp["w_up"])
    hidden = (jax.nn.silu(gate) * up
              * combine[..., None].astype(u.dtype))          # (T, Eh, F)
    return jnp.einsum("tef,efd->td", hidden, mp["w_down"])


def grouped_product(cfg: ModelConfig) -> str:
    """The path a prefill's grouped products take in this process, by the
    expert layer's widths (``ContinuousBatcher.report()`` names it)."""
    return grouped_matmul.grouped_product_path(cfg.hidden_size,
                                               cfg.expert_width)


def _grouped_products(mp: dict, rows, sizes):
    """rows (M, D) sorted by held expert, ``sizes`` (Eh,) rows a group ->
    ``(silu(rows @ w_gate[g]) * (rows @ w_up[g])) @ w_down[g]`` (M, D). What
    a row of no group holds is not defined on either path."""
    d, f = mp["w_gate"].shape[1:]
    with jax.named_scope("moe.experts.grouped"):
        if (grouped_matmul.grouped_product_path(d, f)
                == grouped_matmul.PALLAS_GROUPED):
            hidden = grouped_matmul.grouped_swiglu(rows, mp["w_gate"],
                                                   mp["w_up"], sizes)
            return grouped_matmul.grouped_matmul(hidden, mp["w_down"], sizes)
        gate = jax.lax.ragged_dot(rows, mp["w_gate"], sizes)
        up = jax.lax.ragged_dot(rows, mp["w_up"], sizes)
        return jax.lax.ragged_dot(jax.nn.silu(gate) * up, mp["w_down"], sizes)


def _experts_grouped(cfg: ModelConfig, mp: dict, u, idx, weights):
    t, k = idx.shape
    pad = -t % GROUPED_TOKEN_MULTIPLE
    if pad:
        # padding tokens are routed to no expert (id -1): their assignments
        # sort last with the absent experts', belong to no group and are
        # selected out like them
        rows = ((0, pad), (0, 0))
        return _experts_grouped(
            cfg, mp, jnp.pad(u, rows), jnp.pad(idx, rows, constant_values=-1),
            jnp.pad(weights, rows))[:t]
    eh = cfg.local_experts
    local, held = _local(cfg, idx)
    group = jnp.where(held, local, eh).reshape(-1)          # absent: last
    order = jnp.argsort(group, stable=True)
    sizes = _assignments(cfg, local, held)
    out = _grouped_products(mp, u[order // k], sizes)        # (T*k, D)
    # the sort undone on the products as they come (half the bytes of their
    # float32 weighting: PERF.md section 6 "PR 37"), a token's k rows together
    out = out[jnp.argsort(order)].reshape(t, k, -1)
    # rows past the last held group belong to no product, and what a grouped
    # product leaves in them is not defined (a TPU leaves NaN): they are
    # selected out, not multiplied by a zero weight
    out = jnp.where(held[..., None],
                    out.astype(jnp.float32) * weights[..., None], 0.0)
    return jnp.sum(out, axis=1).astype(u.dtype)


def moe_layer(cfg: ModelConfig, mp: dict, u: jnp.ndarray,
              active: jnp.ndarray | None = None):
    """u (T, D) normalised -> (the held experts' routed part + the shared
    expert's, if any (+ the identity experts': :func:`_with_identity`) (T, D),
    assignments a held expert (Eh,) int32 over the rows ``active`` marks)."""
    idx, weights = route(cfg, mp["router"], u, mp.get("router_bias"))
    with jax.named_scope("moe.experts"):
        experts = (_experts_dense if u.shape[0] <= DENSE_MAX_TOKENS
                   else _experts_grouped)
        routed = experts(cfg, mp, u, idx, weights)
        local, held = _local(cfg, idx)
        if active is not None:
            held = held & active[:, None]
        counts = _assignments(cfg, local, held)
        if cfg.zero_experts:
            routed, counts = _with_identity(cfg, u, idx, weights, active,
                                            routed, counts)
    if not cfg.shared_width:
        return routed, counts
    with jax.named_scope("moe.shared"):
        shared = (jax.nn.silu(u @ mp["shared_gate"])
                  * (u @ mp["shared_up"])) @ mp["shared_down"]
    return routed + shared, counts


def _with_identity(cfg: ModelConfig, u, idx, weights, active, routed,
                   counts):
    """The identity experts' part of a routed layer: ``E_e(u) = u`` for every
    chosen id ``e >= cfg.num_experts``, so a token gains ``u`` times the sum
    of its weights for them, in float32, rounded once with the held experts'
    sum. No group, no product and no "absent" selection sees them
    (:func:`_local` calls them not held). Returns (routed (T, D), counts
    (Eh + 1,): the assignments to identity ids over the rows ``active``
    marks, appended)."""
    zero = idx >= cfg.num_experts
    share = jnp.sum(jnp.where(zero, weights, 0.0), axis=-1, keepdims=True)
    routed = (routed.astype(jnp.float32)
              + u.astype(jnp.float32) * share).astype(u.dtype)
    if active is not None:
        zero = zero & active[:, None]
    return routed, jnp.concatenate(
        [counts, jnp.sum(zero, dtype=jnp.int32)[None]])
