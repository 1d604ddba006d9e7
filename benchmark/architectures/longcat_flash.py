"""What ``closed_loop_arch`` needs of the ``longcat_flash`` family (Meituan
LongCat-Flash: a layer of two latent-attention sublayers and two dense
SwiGLUs with one routed layer on a shortcut beside them, routed + identity
experts chosen by the whole softmax's scores plus a selection bias, an untied
head): the seeded weights, the served system built from the configuration
file's keys, and the plain reference.

Weights are made on the device ONE LEAF PER JITTED CALL, the table and the
head first while the device is empty (a leaf's float32 twin lives for the
call: 2.4 GB for the eight sublayers' ``wo``, 0.8 GB for a layer's 16 held
gate matrices). How each leaf is seeded is in the configuration's ``assumed``
and ``seeding``.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmark import reference_longcat_flash as reference
from benchmark.weights import DTYPES, seed_key

#: every matrix and, added to one, every norm scale; the router and its
#: selection bias are seeded by the configuration's ``seeding`` (the router
#: ``router_logit_std / sqrt(hidden)`` so that its logits have that std at any
#: width, the bias ``router_bias_std`` in score units, NONZERO: a checkpoint's
#: is steered in training, and at zero a path that drops it would pass)
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

@functools.partial(jax.jit, static_argnames=("shape", "dtype", "std", "one"))
def _leaf(key, shape, dtype, std, one):
    x = jax.random.normal(key, shape, jnp.float32) * std
    return ((1.0 + x) if one else x).astype(dtype)


def weight_plan(config: dict) -> list:
    """[(path, shape, how)] in the order the leaves are made: the table and
    the head first, the feed-forwards a SUBLAYER at a time after the latent
    stack (two sublayers a published layer; a layer's first entry also holds
    its routed layer, under ``shortcut``)."""
    d, v = config["hidden_size"], config["vocab_size"]
    n = 2 * config["num_layers"]
    h = config["num_attention_heads"]
    nope, rope = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    vd, qr, rank = (config["v_head_dim"], config["q_lora_rank"],
                    config["kv_lora_rank"])
    fd, f = config["ffn_hidden_size"], config["expert_ffn_hidden_size"]
    eh = config["n_routed_experts"]
    e = share(config).get("router_experts", eh) + config["zero_expert_num"]
    plan = [(("embed",), (v, d), "normal"),
            (("lm_head",), (d, v), "normal"),
            (("final_norm_scale",), (d,), "scale"),
            (("latent", "ln1_scale"), (n, d), "scale"),
            (("latent", "wq_a"), (n, d, qr), "normal"),
            (("latent", "q_norm"), (n, qr), "scale"),
            (("latent", "wq_b"), (n, qr, h * (nope + rope)), "normal"),
            (("latent", "wkv_a"), (n, d, rank + rope), "normal"),
            (("latent", "kv_norm"), (n, rank), "scale"),
            (("latent", "wkv_b"), (n, rank, h * (nope + vd)), "normal"),
            (("latent", "wo"), (n, h * vd, d), "normal")]
    for sub in range(n):
        plan += [(("moe", sub, "ln2_scale"), (d,), "scale"),
                 (("moe", sub, "w_gate"), (d, fd), "normal"),
                 (("moe", sub, "w_up"), (d, fd), "normal"),
                 (("moe", sub, "w_down"), (fd, d), "normal")]
        if sub % 2 == 0:
            plan += [(("moe", sub, "shortcut", "router"), (d, e), "router"),
                     (("moe", sub, "shortcut", "router_bias"), (e,), "bias"),
                     (("moe", sub, "shortcut", "w_gate"), (eh, d, f),
                      "normal"),
                     (("moe", sub, "shortcut", "w_up"), (eh, d, f), "normal"),
                     (("moe", sub, "shortcut", "w_down"), (eh, f, d),
                      "normal")]
    return plan


def make_weights(config: dict, seed: int) -> dict:
    # a program that does not know the family says so here, at once, and not
    # after 10 GB of weights
    model_config(config)
    dtype = DTYPES[config["torch_dtype"]]
    seeding = config["seeding"]
    stds = {"normal": STD, "scale": STD,
            "router": seeding["router_logit_std"] / math.sqrt(
                config["hidden_size"]),
            "bias": seeding["router_bias_std"]}
    root = seed_key(seed)
    out = {"latent": {}, "moe": [
        {"shortcut": {}} if sub % 2 == 0 else {}
        for sub in range(2 * config["num_layers"])]}
    for i, (path, shape, how) in enumerate(weight_plan(config)):
        node = out
        for part in path[:-1]:
            node = node[part]
        node[path[-1]] = _leaf(
            jax.random.fold_in(root, i), shape,
            jnp.float32 if how == "bias" else dtype, stds[how],
            how == "scale")
    return out


# -- the reference -------------------------------------------------------------

def logit_gaps(config: dict, weights: dict, ids, start, served, *,
               with_control: bool = False):
    return reference.logit_gaps(reference.model_key(config), weights, ids,
                                start, served, with_control=with_control)
