"""What ``closed_loop_arch`` needs of the ``dots3_note`` family
(dots3-note-prev's language model: sparse latent layers whose query attends
the positions an indexer selects, beside WINDOW latent layers at sizes of
their own whose rows live in a ring; rank-rescaled latents and a head-wise
output gate on both; a leading dense SwiGLU, then experts routed by sigmoid
scores plus a shared one; an untied head): the seeded weights, the served
system built from the configuration file's keys, and the plain reference.

Weights are made on the device ONE LEAF PER JITTED CALL, the table first
while the device is empty (a leaf's float32 twin lives for the call). How
each leaf is seeded is in the configuration's ``assumed`` and ``seeding``.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmark import reference_dots3_note as reference
from benchmark.weights import DTYPES, seed_key

#: every matrix (a toy's file may widen them, ``seeding.matrix_std``) and,
#: added to one, every norm scale; the index key's LayerNorm bias at this
#: std around zero; the router, the gates ``wg``, the indexer's head weights
#: ``w_index`` and the router's selection bias are seeded by the
#: configuration's ``seeding`` (``router_logit_std`` / ``gate_logit_std`` /
#: ``index_weight_std``, each over ``sqrt(hidden)`` so that the logits have
#: that std at any width; ``router_bias_std`` as it is); so are the table
#: (``embed_std``) and what each KIND's g_q lies around
#: (:func:`q_norm_centre`, from ``attn_logit_std``)
STD = 0.02
#: the weights' entry of a published kind, and its keys' prefix
KINDS = {"full_attention": ("sparse_latent", ""),
         "sliding_attention": ("window_latent", "swa_")}


def share(config: dict) -> dict:
    return config.get("share", {})


def model_config(config: dict):
    """The program's own reading of the published keys
    (``hf_loader.config_from_hf``, which takes them as attributes), told the
    router's published width and which experts are held here where the file
    gives a share."""
    import dataclasses
    import types

    from edgellm_tpu.models.hf_loader import config_from_hf

    cfg = config_from_hf(types.SimpleNamespace(**config))
    if "router_experts" not in share(config):
        return cfg
    return dataclasses.replace(
        cfg, num_experts=share(config)["router_experts"],
        experts_held=config["n_routed_experts"],
        expert_offset=share(config).get("expert_offset", 0))


def build_batcher(config: dict, weights: dict):
    from edgellm_tpu.serve.batching import BatchingConfig, ContinuousBatcher

    s = config["serving"]
    bcfg = BatchingConfig(page_size=s["page_size"], num_pages=s["num_pages"],
                          max_slots=s["max_slots"],
                          pages_per_slot=s["pages_per_slot"],
                          cache_dtype=jnp.dtype(config["torch_dtype"]))
    return ContinuousBatcher(model_config(config), weights, bcfg)


# -- weights -----------------------------------------------------------------

@functools.partial(jax.jit,
                   static_argnames=("shape", "dtype", "std", "centre"))
def _leaf(key, shape, dtype, std, centre):
    x = jax.random.normal(key, shape, jnp.float32) * std
    return (centre + x).astype(dtype)


def _latent_plan(config: dict, kind: str) -> list:
    at, pre = KINDS[kind]
    n = config["layer_types"].count(kind)
    if not n:
        return []
    d, h = config["hidden_size"], config[pre + "num_attention_heads"]
    nope, rope, vd = (config[pre + "qk_nope_head_dim"],
                      config[pre + "qk_rope_head_dim"],
                      config[pre + "v_head_dim"])
    rq, rkv = config[pre + "q_lora_rank"], config[pre + "kv_lora_rank"]
    return [((at, "ln1_scale"), (n, d), "scale"),
            ((at, "wq_a"), (n, d, rq), "matrix"),
            ((at, "q_norm"), (n, rq), "q_scale." + kind),
            ((at, "wq_b"), (n, rq, h * (nope + rope)), "matrix"),
            ((at, "wkv_a"), (n, d, rkv + rope), "matrix"),
            ((at, "kv_norm"), (n, rkv), "scale"),
            ((at, "wkv_b"), (n, rkv, h * (nope + vd)), "matrix"),
            ((at, "wo"), (n, h * vd, d), "matrix"),
            ((at, "wg"), (n, d, h), "gate")]


def weight_plan(config: dict) -> list:
    """[(path, shape, how)] in the order the leaves are made: the table and
    the head first, the two kinds' stacks, the feed-forwards a layer at a
    time after them."""
    d, v, n = (config["hidden_size"], config["vocab_size"],
               config["num_hidden_layers"])
    full = config["layer_types"].count("full_attention")
    rq = config["q_lora_rank"]
    hi, di = config["index_n_heads"], config["index_head_dim"]
    eh, f = config["n_routed_experts"], config["moe_intermediate_size"]
    fd = config["intermediate_size"]
    e = share(config).get("router_experts", eh)
    at = "sparse_latent"
    plan = [(("embed",), (v, d), "embed"),
            (("lm_head",), (d, v), "normal"),
            (("final_norm_scale",), (d,), "scale"),
            *_latent_plan(config, "full_attention"),
            ((at, "wq_index"), (full, rq, hi * di), "matrix"),
            ((at, "wk_index"), (full, d, di), "matrix"),
            ((at, "index_norm_scale"), (full, di), "scale"),
            ((at, "index_norm_bias"), (full, di), "normal"),
            ((at, "w_index"), (full, d, hi), "index_weight"),
            *_latent_plan(config, "sliding_attention")]
    for layer in range(n):
        plan.append((("moe", layer, "ln2_scale"), (d,), "scale"))
        if layer < config["first_k_dense_replace"]:
            plan += [(("moe", layer, "w_gate"), (d, fd), "matrix"),
                     (("moe", layer, "w_up"), (d, fd), "matrix"),
                     (("moe", layer, "w_down"), (fd, d), "matrix")]
            continue
        plan += [(("moe", layer, "router"), (d, e), "router"),
                 (("moe", layer, "router_bias"), (e,), "router_bias"),
                 (("moe", layer, "w_gate"), (eh, d, f), "matrix"),
                 (("moe", layer, "w_up"), (eh, d, f), "matrix"),
                 (("moe", layer, "w_down"), (eh, f, d), "matrix"),
                 (("moe", layer, "shared_gate"), (d, f), "matrix"),
                 (("moe", layer, "shared_up"), (d, f), "matrix"),
                 (("moe", layer, "shared_down"), (f, d), "matrix")]
    return plan


def q_norm_centre(config: dict, matrix_std: float, kind: str) -> float:
    """What a KIND's ``g_q`` lies around so that a head's attention logits
    have the std ``seeding.attn_logit_std`` (1: the file gives none), worked
    for each kind under ITS rank factors. ``c_q`` leaves its norm with unit
    lanes times ``g_q`` times ``f_q = sqrt(d / r_q)``, so a query lane has
    the std ``g_q f_q m sqrt(r_q)`` (m the matrices' std); a ``k_nope`` lane
    ``f_kv m sqrt(r_kv)`` (the rescaled latent through ``W_kvb``), a
    ``k_rope`` lane ``m sqrt(hidden)`` (``x W_kva``, not normed, not
    rescaled); the logit is ``(nope + rope)^-1/2`` times the ``nope + rope``
    products' sum."""
    want = config["seeding"].get("attn_logit_std")
    if want is None:
        return 1.0
    k = dict(reference.model_key(config))
    g = dict(k["full" if kind == "full_attention" else "window"])
    lane = (matrix_std * math.sqrt(g["r_q"])
            * reference.rank_factor(k, g["r_q"]))
    keys = matrix_std * math.sqrt(
        g["nope"] * g["r_kv"] * reference.rank_factor(k, g["r_kv"]) ** 2
        + g["rope"] * config["hidden_size"])
    return want / (reference.softmax_scale(g) * lane * keys)


def make_weights(config: dict, seed: int) -> dict:
    # a program that does not know the family says so here, at once, and not
    # after 8 GB of weights
    model_config(config)
    dtype = DTYPES[config["torch_dtype"]]
    seeding = config["seeding"]
    wide = math.sqrt(config["hidden_size"])
    matrix = seeding.get("matrix_std", STD)
    stds = {"normal": STD, "scale": STD,
            "embed": seeding.get("embed_std", STD), "matrix": matrix,
            "router": seeding["router_logit_std"] / wide,
            "router_bias": seeding["router_bias_std"],
            "gate": seeding["gate_logit_std"] / wide,
            "index_weight": seeding["index_weight_std"] / wide}
    centres = {"scale": 1.0}
    for kind in KINDS:
        stds["q_scale." + kind] = STD
        centres["q_scale." + kind] = q_norm_centre(config, matrix, kind)
    root = seed_key(seed)
    out = {at: {} for kind, (at, _) in KINDS.items()
           if kind in config["layer_types"]}
    out["moe"] = [{} for _ in range(config["num_hidden_layers"])]
    for i, (path, shape, how) in enumerate(weight_plan(config)):
        node = out
        for part in path[:-1]:
            node = node[part]
        node[path[-1]] = _leaf(
            jax.random.fold_in(root, i), shape,
            # (the selection bias is float32 whatever the weights are)
            jnp.float32 if how == "router_bias" else dtype,
            stds[how], centres.get(how, 0.0))
    return out


# -- the reference -------------------------------------------------------------

def logit_gaps(config: dict, weights: dict, ids, start, served, *,
               with_control: bool = False):
    return reference.logit_gaps(reference.model_key(config), weights, ids,
                                start, served, with_control=with_control)
