"""What ``closed_loop_arch`` needs of the ``afmoe`` family (Arcee Trinity:
sliding-window layers that rotate beside position-free full ones, per-head q/k
norms, a gated attention, four norms a layer, a leading dense layer, sigmoid
routing with a selection bias and a shared expert, the embedding times
sqrt(d), an untied head): the seeded weights, the served system built from the
configuration file's keys, and the plain reference.

Weights are made on the device ONE LEAF PER JITTED CALL, the table and the
head first while the device is empty (a leaf's float32 twin lives for the
call: 1.6 GB for a 200192 x 2048 table, 1.1 GB for a layer's 128 gate
matrices). How each leaf is seeded is in the configuration's ``assumed`` and
``seeding``.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmark import reference_afmoe as reference
from benchmark.weights import DTYPES, seed_key

#: every matrix and, added to one, every norm scale; the router and its
#: selection bias are seeded by the configuration's ``seeding`` (the router
#: ``router_logit_std / sqrt(hidden)`` so that its logits have that std at any
#: width, the bias ``router_bias_std`` in score units, NONZERO: a checkpoint's
#: is trained, and at zero a path that drops it would pass)
STD = 0.02


def share(config: dict) -> dict:
    return config.get("share", {})


def model_config(config: dict):
    """The program's own reading of the published keys
    (``hf_loader.config_from_hf``, which takes them as attributes), told
    which experts are held here where the file gives a share."""
    import dataclasses
    import types

    from edgellm_tpu.models.hf_loader import config_from_hf

    return dataclasses.replace(
        config_from_hf(types.SimpleNamespace(**config)),
        experts_held=share(config).get("experts_held", 0),
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
    the head first, the feed-forwards a layer at a time after the stacks."""
    d, v = config["hidden_size"], config["vocab_size"]
    kinds = config["layer_types"]
    h, kv = config["num_attention_heads"], config["num_key_value_heads"]
    hd = config["head_dim"]
    e, f = config["num_experts"], config["moe_intermediate_size"]
    fd, fs = config["intermediate_size"], f * config["num_shared_experts"]
    eh = share(config).get("experts_held", e)
    plan = [(("embed",), (v, d), "embed"),
            (("lm_head",), (d, v), "normal"),
            (("final_norm_scale",), (d,), "scale")]
    for stack, kind in (("attn", "full_attention"),
                        ("window", "sliding_attention")):
        n = kinds.count(kind)
        plan += [((stack, "ln1_scale"), (n, d), "scale"),
                 ((stack, "post_scale"), (n, d), "scale"),
                 ((stack, "q_norm"), (n, hd), "scale"),
                 ((stack, "k_norm"), (n, hd), "scale"),
                 ((stack, "wq"), (n, d, h * hd), "normal"),
                 ((stack, "wk"), (n, d, kv * hd), "normal"),
                 ((stack, "wv"), (n, d, kv * hd), "normal"),
                 ((stack, "wg"), (n, d, h * hd), "normal"),
                 ((stack, "wo"), (n, h * hd, d), "normal")]
    for layer in range(len(kinds)):
        plan += [(("moe", layer, "ln2_scale"), (d,), "scale"),
                 (("moe", layer, "post_scale"), (d,), "scale")]
        if layer < config["num_dense_layers"]:
            plan += [(("moe", layer, "w_gate"), (d, fd), "normal"),
                     (("moe", layer, "w_up"), (d, fd), "normal"),
                     (("moe", layer, "w_down"), (fd, d), "normal")]
            continue
        plan += [(("moe", layer, "router"), (d, e), "router"),
                 (("moe", layer, "router_bias"), (e,), "bias"),
                 (("moe", layer, "w_gate"), (eh, d, f), "normal"),
                 (("moe", layer, "w_up"), (eh, d, f), "normal"),
                 (("moe", layer, "w_down"), (eh, f, d), "normal")]
        if fs:
            plan += [(("moe", layer, "shared_gate"), (d, fs), "normal"),
                     (("moe", layer, "shared_up"), (d, fs), "normal"),
                     (("moe", layer, "shared_down"), (fs, d), "normal")]
    return plan


def make_weights(config: dict, seed: int) -> dict:
    # a program that does not know the family says so here, at once, and not
    # after 8.5 GB of weights
    model_config(config)
    dtype = DTYPES[config["torch_dtype"]]
    root_d = math.sqrt(config["hidden_size"])
    seeding = config["seeding"]
    stds = {"normal": STD, "scale": STD, "embed": STD / root_d,
            "router": seeding["router_logit_std"] / root_d,
            "bias": seeding["router_bias_std"]}
    root = seed_key(seed)
    out = {"attn": {}, "window": {},
           "moe": [{} for _ in config["layer_types"]]}
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
