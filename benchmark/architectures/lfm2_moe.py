"""What ``closed_loop_arch`` needs of the ``lfm2_moe`` family (LiquidAI LFM2:
gated short convolutions that keep a window of rows a sequence, 3:1 beside
rotated GQA layers with per-head q/k norms, two leading dense layers, then
experts routed by sigmoid scores with a selection bias and none shared, a
tied head): the seeded weights, the served system built from the
configuration file's keys, and the plain reference.

Weights are made on the device ONE LEAF PER JITTED CALL, the table first
while the device is empty (a leaf's float32 twin lives for the call: 0.5 GB
for a 65536 x 2048 table or a layer's 32 gate matrices). How each leaf is
seeded is in the configuration's ``assumed`` and ``seeding``.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmark import reference_lfm2_moe as reference
from benchmark.weights import DTYPES, seed_key

#: every matrix (a toy's file may widen them, ``seeding.matrix_std``: at 0.02
#: a projection of width 48 shrinks its input sevenfold where one of width
#: 2048 keeps it, the layers add nothing beside the token's own row of the
#: tied table, and every stream repeats its last token) and, added to one,
#: every norm scale; the table stays at 0.02; the taps, the router and its
#: selection bias are seeded by the configuration's ``seeding`` (the taps
#: ``conv_tap_std``, the scale a depthwise convolution is initialised at
#: upstream, so that the window matters; the router ``router_logit_std /
#: sqrt(hidden)`` so that its logits have that std at any width; the bias
#: ``router_bias_std`` in score units, NONZERO: a checkpoint's is trained,
#: and at zero a path that drops it would pass)
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
    """[(path, shape, how)] in the order the leaves are made: the table
    first, the feed-forwards a layer at a time after the stacks."""
    d, v = config["hidden_size"], config["vocab_size"]
    kinds = config["layer_types"]
    h, kv = config["num_attention_heads"], config["num_key_value_heads"]
    hd = d // h
    e, f = config["num_experts"], config["moe_intermediate_size"]
    fd, taps = config["intermediate_size"], config["conv_L_cache"]
    eh = share(config).get("experts_held", e)
    nc, na = kinds.count("conv"), kinds.count("full_attention")
    plan = [(("embed",), (v, d), "normal"),
            (("final_norm_scale",), (d,), "scale"),
            (("conv", "ln1_scale"), (nc, d), "scale"),
            (("conv", "w_in"), (nc, d, 3 * d), "matrix"),
            (("conv", "conv_w"), (nc, d, taps), "taps"),
            (("conv", "w_out"), (nc, d, d), "matrix"),
            (("attn", "ln1_scale"), (na, d), "scale"),
            (("attn", "q_norm"), (na, hd), "scale"),
            (("attn", "k_norm"), (na, hd), "scale"),
            (("attn", "wq"), (na, d, h * hd), "matrix"),
            (("attn", "wk"), (na, d, kv * hd), "matrix"),
            (("attn", "wv"), (na, d, kv * hd), "matrix"),
            (("attn", "wo"), (na, h * hd, d), "matrix")]
    for layer in range(len(kinds)):
        plan.append((("moe", layer, "ln2_scale"), (d,), "scale"))
        if layer < config["num_dense_layers"]:
            plan += [(("moe", layer, "w_gate"), (d, fd), "matrix"),
                     (("moe", layer, "w_up"), (d, fd), "matrix"),
                     (("moe", layer, "w_down"), (fd, d), "matrix")]
            continue
        plan += [(("moe", layer, "router"), (d, e), "router"),
                 (("moe", layer, "router_bias"), (e,), "bias"),
                 (("moe", layer, "w_gate"), (eh, d, f), "matrix"),
                 (("moe", layer, "w_up"), (eh, d, f), "matrix"),
                 (("moe", layer, "w_down"), (eh, f, d), "matrix")]
    return plan


def make_weights(config: dict, seed: int) -> dict:
    # a program that does not know the family says so here, at once, and not
    # after 7.9 GB of weights
    model_config(config)
    dtype = DTYPES[config["torch_dtype"]]
    seeding = config["seeding"]
    stds = {"normal": STD, "scale": STD,
            "matrix": seeding.get("matrix_std", STD),
            "taps": seeding["conv_tap_std"],
            "router": (seeding["router_logit_std"]
                       / math.sqrt(config["hidden_size"])),
            "bias": seeding["router_bias_std"]}
    root = seed_key(seed)
    out = {"conv": {}, "attn": {},
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
