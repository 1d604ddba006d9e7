"""What ``closed_loop_arch`` needs of the ``mellum`` family (JetBrains Mellum
2: sliding-window layers beside full ones, YaRN on the full layers only,
routed experts with none shared, an untied head): the seeded weights, the
served system built from the configuration file's keys, and the plain
reference.

Weights are made on the device ONE LEAF PER JITTED CALL, the table and the
head first while the device is empty (a leaf's float32 twin lives for the
call: 0.9 GB for a 98304 x 2304 table, 0.5 GB for a layer's 64 gate
matrices). They are seeded normal std 0.02 and norm scales 1 + that, the
router ``ROUTER_STD`` so that the top-k margins are not all near-ties (see the
configuration's ``assumed``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark import reference_mellum as reference
from benchmark.weights import DTYPES, seed_key

#: router logits are ROUTER_STD * sqrt(hidden) * N(0, 1): 2.4 at d 2304
ROUTER_STD = 0.05
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

    if config["rope_theta"] != \
            config["rope_parameters"]["full_attention"]["rope_theta"]:
        raise ValueError("the top-level rope_theta the harness reads must be "
                         "rope_parameters' own")
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

@functools.partial(jax.jit, static_argnames=("shape", "dtype", "how"))
def _leaf(key, shape, dtype, how):
    x = jax.random.normal(key, shape, jnp.float32) * (
        ROUTER_STD if how == "router" else STD)
    if how == "scale":
        x = 1.0 + x
    return x.astype(dtype)


def weight_plan(config: dict) -> list:
    """[(path, shape, how)] in the order the leaves are made: the table and
    the head first, the experts a layer at a time after the stacks."""
    d, v = config["hidden_size"], config["vocab_size"]
    kinds = config["layer_types"]
    h, kv = config["num_attention_heads"], config["num_key_value_heads"]
    hd = config["head_dim"]
    e, f = config["num_experts"], config["moe_intermediate_size"]
    eh = share(config).get("experts_held", e)
    plan = [(("embed",), (v, d), "normal"),
            (("lm_head",), (d, v), "normal"),
            (("final_norm_scale",), (d,), "scale")]
    for stack, kind in (("attn", "full_attention"),
                        ("window", "sliding_attention")):
        n = kinds.count(kind)
        plan += [((stack, "ln1_scale"), (n, d), "scale"),
                 ((stack, "wq"), (n, d, h * hd), "normal"),
                 ((stack, "wk"), (n, d, kv * hd), "normal"),
                 ((stack, "wv"), (n, d, kv * hd), "normal"),
                 ((stack, "wo"), (n, h * hd, d), "normal")]
    for layer in range(len(kinds)):
        plan += [(("moe", layer, "ln2_scale"), (d,), "scale"),
                 (("moe", layer, "router"), (d, e), "router"),
                 (("moe", layer, "w_gate"), (eh, d, f), "normal"),
                 (("moe", layer, "w_up"), (eh, d, f), "normal"),
                 (("moe", layer, "w_down"), (eh, f, d), "normal")]
    return plan


def make_weights(config: dict, seed: int) -> dict:
    # a program that does not know the family says so here, at once, and not
    # after 7.6 GB of weights
    model_config(config)
    dtype = DTYPES[config["torch_dtype"]]
    root = seed_key(seed)
    out = {"attn": {}, "window": {},
           "moe": [{} for _ in config["layer_types"]]}
    for i, (path, shape, how) in enumerate(weight_plan(config)):
        node = out
        for part in path[:-1]:
            node = node[part]
        node[path[-1]] = _leaf(jax.random.fold_in(root, i), shape, dtype, how)
    return out


# -- the reference -------------------------------------------------------------

def logit_gaps(config: dict, weights: dict, ids, start, served, *,
               with_control: bool = False):
    return reference.logit_gaps(reference.model_key(config), weights, ids,
                                start, served, with_control=with_control)
