"""What ``closed_loop_arch`` needs of the ``mistral4`` family (Mistral Small
4: latent attention with one cached row a position, routed experts plus a
shared one, an untied head): the seeded weights, the served system built from
the configuration file's keys, and the plain reference.

Weights are made on the device ONE LEAF PER JITTED CALL, the table and the
head first while the device is empty (a leaf's float32 twin lives for the
call: 0.8 GB for a layer's 32 held gate matrices). They are seeded normal std
0.02 and norm scales 1 + that, the router ``ROUTER_STD`` so that the top-k
margins are not all near-ties (see the configuration's ``assumed``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark import reference_mistral4 as reference
from benchmark.weights import DTYPES, seed_key

#: router logits are ROUTER_STD * sqrt(hidden) * N(0, 1): 3.2 at d 4096
ROUTER_STD = 0.05
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

    if config["rope_theta"] != config["rope_parameters"]["rope_theta"]:
        raise ValueError("the top-level rope_theta the harness reads must be "
                         "rope_parameters' own")
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

@functools.partial(jax.jit, static_argnames=("shape", "dtype", "how"))
def _leaf(key, shape, dtype, how):
    x = jax.random.normal(key, shape, jnp.float32) * (
        ROUTER_STD if how == "router" else STD)
    if how == "scale":
        x = 1.0 + x
    return x.astype(dtype)


def weight_plan(config: dict) -> list:
    """[(path, shape, how)] in the order the leaves are made: the table and
    the head first, the experts a layer at a time after the stack."""
    d, v, n = (config["hidden_size"], config["vocab_size"],
               config["num_hidden_layers"])
    h = config["num_attention_heads"]
    nope, rope = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    vd, qr, rank = (config["v_head_dim"], config["q_lora_rank"],
                    config["kv_lora_rank"])
    eh, f = config["n_routed_experts"], config["moe_intermediate_size"]
    e = share(config).get("router_experts", eh)
    fs = config["n_shared_experts"] * f
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
    for layer in range(n):
        plan += [(("moe", layer, "ln2_scale"), (d,), "scale"),
                 (("moe", layer, "router"), (d, e), "router"),
                 (("moe", layer, "w_gate"), (eh, d, f), "normal"),
                 (("moe", layer, "w_up"), (eh, d, f), "normal"),
                 (("moe", layer, "w_down"), (eh, f, d), "normal"),
                 (("moe", layer, "shared_gate"), (d, fs), "normal"),
                 (("moe", layer, "shared_up"), (d, fs), "normal"),
                 (("moe", layer, "shared_down"), (fs, d), "normal")]
    return plan


def make_weights(config: dict, seed: int) -> dict:
    # a program that does not know the family says so here, at once, and not
    # after 7.4 GB of weights
    model_config(config)
    dtype = DTYPES[config["torch_dtype"]]
    root = seed_key(seed)
    out = {"latent": {},
           "moe": [{} for _ in range(config["num_hidden_layers"])]}
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
