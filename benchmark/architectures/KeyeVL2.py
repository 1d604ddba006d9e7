"""What ``closed_loop_arch`` needs of the ``KeyeVL2`` family (Kwai Keye-VL
2.0's language model: rotated, q/k-normed GQA layers whose query attends the
positions a learned indexer selects, an index key a position cached beside
the K/V row; every feed-forward routed experts by the softmax over the
chosen, none shared; an untied head): the seeded weights, the served system
built from the configuration file's keys, and the plain reference.

Weights are made on the device ONE LEAF PER JITTED CALL, the table first
while the device is empty (a leaf's float32 twin lives for the call: 0.3 GB
for a 37984 x 2048 table or a layer's 32 gate matrices). How each leaf is
seeded is in the configuration's ``assumed`` and ``seeding``.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmark import reference_keye_vl2 as reference
from benchmark.weights import DTYPES, seed_key

#: every matrix (a toy's file may widen them, ``seeding.matrix_std``) and,
#: added to one, every norm scale; the index key's LayerNorm bias at this
#: std around zero; the router and the indexer's head weights ``w_index`` are
#: seeded by the configuration's ``seeding`` (``router_logit_std`` /
#: ``index_weight_std``, each over ``sqrt(hidden)`` so that the logits have
#: that std at any width); so are the table (``embed_std``) and what g_q and
#: g_k lie around (the root of ``attn_logit_std``), where the file gives
#: them: without them every position of a long context ends in the same
#: hidden state and a greedy answer is one token repeated (the file's
#: ``assumed`` has the arithmetic)
STD = 0.02


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
        experts_held=config["num_experts"],
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


def weight_plan(config: dict) -> list:
    """[(path, shape, how)] in the order the leaves are made: the table and
    the head first, the expert layers a layer at a time after the stack."""
    d, v, n = (config["hidden_size"], config["vocab_size"],
               config["num_hidden_layers"])
    h, kv = config["num_attention_heads"], config["num_key_value_heads"]
    hd, sa = config["head_dim"], config["sa_config"]
    hi, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    eh, f = config["num_experts"], config["moe_intermediate_size"]
    e = share(config).get("router_experts", eh)
    plan = [(("embed",), (v, d), "embed"),
            (("lm_head",), (d, v), "normal"),
            (("final_norm_scale",), (d,), "scale"),
            (("sparse", "ln1_scale"), (n, d), "scale"),
            (("sparse", "q_norm"), (n, hd), "qk_scale"),
            (("sparse", "k_norm"), (n, hd), "qk_scale"),
            (("sparse", "wq"), (n, d, h * hd), "matrix"),
            (("sparse", "wk"), (n, d, kv * hd), "matrix"),
            (("sparse", "wv"), (n, d, kv * hd), "matrix"),
            (("sparse", "wo"), (n, h * hd, d), "matrix"),
            (("sparse", "wq_index"), (n, d, hi * di), "matrix"),
            (("sparse", "wk_index"), (n, d, di), "matrix"),
            (("sparse", "index_norm_scale"), (n, di), "scale"),
            (("sparse", "index_norm_bias"), (n, di), "normal"),
            (("sparse", "w_index"), (n, d, hi), "index_weight")]
    for layer in range(n):
        plan += [(("moe", layer, "ln2_scale"), (d,), "scale"),
                 (("moe", layer, "router"), (d, e), "router"),
                 (("moe", layer, "w_gate"), (eh, d, f), "matrix"),
                 (("moe", layer, "w_up"), (eh, d, f), "matrix"),
                 (("moe", layer, "w_down"), (eh, f, d), "matrix")]
    return plan


def make_weights(config: dict, seed: int) -> dict:
    # a program that does not know the family says so here, at once, and not
    # after 2.4 GB of weights
    model_config(config)
    dtype = DTYPES[config["torch_dtype"]]
    seeding = config["seeding"]
    wide = math.sqrt(config["hidden_size"])
    stds = {"normal": STD, "scale": STD, "qk_scale": STD,
            "embed": seeding.get("embed_std", STD),
            "matrix": seeding.get("matrix_std", STD),
            "router": seeding["router_logit_std"] / wide,
            "index_weight": seeding["index_weight_std"] / wide}
    # g_q and g_k each lie around the root of the std the attention's logits
    # are to have (q and k leave their norms with unit lanes, so q . k /
    # sqrt(head_dim) has the std g_q g_k)
    centres = {"scale": 1.0,
               "qk_scale": math.sqrt(seeding.get("attn_logit_std", 1.0))}
    root = seed_key(seed)
    out = {"sparse": {},
           "moe": [{} for _ in range(config["num_hidden_layers"])]}
    for i, (path, shape, how) in enumerate(weight_plan(config)):
        node = out
        for part in path[:-1]:
            node = node[part]
        node[path[-1]] = _leaf(jax.random.fold_in(root, i), shape, dtype,
                               stds[how], centres.get(how, 0.0))
    return out


# -- the reference -------------------------------------------------------------

def logit_gaps(config: dict, weights: dict, ids, start, served, *,
               with_control: bool = False):
    return reference.logit_gaps(reference.model_key(config), weights, ids,
                                start, served, with_control=with_control)
