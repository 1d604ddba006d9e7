"""What ``closed_loop_arch`` needs of the ``granitemoehybrid`` family (IBM
Granite 4.0-H: Mamba-2 and NoPE attention layers, routed + shared experts):
the seeded weights, the served system built from the configuration file's
keys, and the plain reference. A later architecture adds a file like this one
under its ``model_type``.

Weights are made on the device ONE LEAF PER JITTED CALL, the stacked Mamba
projections first while the device is empty: a single call for the whole tree
(``weights._make``) would hold a float32 twin of the 9 GB of experts. They are
seeded normal std 0.02 and norm scales 1 + that, except (see the
configuration's ``assumed``): the tied table takes 0.02 / embedding_multiplier,
the Mamba-2 scalars and convolution take
``mamba_ssm``'s initialisation so that the recurrent state matters, and the
router takes ``ROUTER_STD`` so that the top-k margins are not all near-ties.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmark import reference_granitemoehybrid as reference
from benchmark.weights import DTYPES, seed_key

#: router logits are ROUTER_STD * sqrt(hidden) * N(0, 1): 3.2 at d 4096, so
#: the tenth of 72 sits ~0.2 above the eleventh and weighs a few percent
ROUTER_STD = 0.05
STD = 0.02


def share(config: dict) -> dict:
    return config.get("share", {})


def model_config(config: dict):
    from edgellm_tpu.models.configs import ModelConfig

    return ModelConfig(
        family=config["model_type"], vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        num_layers=config["num_hidden_layers"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        intermediate_size=config["intermediate_size"],
        max_position_embeddings=config["max_position_embeddings"],
        norm_eps=config["rms_norm_eps"], rope_theta=config["rope_theta"],
        tie_word_embeddings=config["tie_word_embeddings"],
        layer_types=tuple(config["layer_types"]),
        num_experts=share(config).get("router_experts",
                                      config["num_local_experts"]),
        experts_per_tok=config["num_experts_per_tok"],
        expert_width=config["intermediate_size"],
        shared_width=config["shared_intermediate_size"],
        experts_held=config["num_local_experts"],
        expert_offset=share(config).get("expert_offset", 0),
        mamba_heads=config["mamba_n_heads"],
        mamba_head_dim=config["mamba_d_head"],
        mamba_d_state=config["mamba_d_state"],
        mamba_d_conv=config["mamba_d_conv"],
        mamba_n_groups=config["mamba_n_groups"],
        mamba_chunk=config["mamba_chunk_size"],
        embedding_multiplier=float(config["embedding_multiplier"]),
        residual_multiplier=float(config["residual_multiplier"]),
        logits_scaling=float(config["logits_scaling"]),
        attention_multiplier=float(config["attention_multiplier"]),
        nope=config["position_embedding_type"] == "nope")


def build_batcher(config: dict, weights: dict):
    from edgellm_tpu.serve.batching import BatchingConfig, ContinuousBatcher

    s = config["serving"]
    bcfg = BatchingConfig(page_size=s["page_size"], num_pages=s["num_pages"],
                          max_slots=s["max_slots"],
                          pages_per_slot=s["pages_per_slot"],
                          cache_dtype=jnp.dtype(config["torch_dtype"]))
    return ContinuousBatcher(model_config(config), weights, bcfg)


# -- weights -----------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("shape", "dtype", "how", "std"))
def _leaf(key, shape, dtype, how, std=STD):
    if how == "conv":        # mamba_ssm's Conv1d default: U(+-1/sqrt(d_conv))
        bound = shape[-1] ** -0.5
        x = jax.random.uniform(key, shape, jnp.float32, -bound, bound)
    elif how == "a_log":     # A = -U[1, 16]
        x = jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
    elif how == "dt_bias":   # softplus^-1 of dt log-uniform in [1e-3, 1e-1]
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                        math.log(1e-3), math.log(1e-1)))
        x = dt + jnp.log(-jnp.expm1(-dt))
    elif how == "one":
        x = jnp.ones(shape, jnp.float32)
    else:
        x = jax.random.normal(key, shape, jnp.float32) * (
            ROUTER_STD if how == "router" else std)
        if how == "scale":
            x = 1.0 + x
    return x.astype(dtype)


def weight_plan(config: dict) -> list:
    """[(path, shape, how)] in the order the leaves are made: the largest
    stacked leaves first, the experts a layer at a time after them."""
    d, v = config["hidden_size"], config["vocab_size"]
    kinds = config["layer_types"]
    lm, la, lt = kinds.count("mamba"), kinds.count("attention"), len(kinds)
    nh, p = config["mamba_n_heads"], config["mamba_d_head"]
    gn = config["mamba_n_groups"] * config["mamba_d_state"]
    di, kc = nh * p, config["mamba_d_conv"]
    cd = di + 2 * gn
    hd = d // config["num_attention_heads"]
    h, kv = config["num_attention_heads"], config["num_key_value_heads"]
    e = share(config).get("router_experts", config["num_local_experts"])
    eh, f = config["num_local_experts"], config["intermediate_size"]
    fs = config["shared_intermediate_size"]
    plan = [
        (("mamba", "w_in"), (lm, d, di + cd + nh), "normal"),
        (("mamba", "w_out"), (lm, di, d), "normal"),
        (("embed",), (v, d), "normal"),
        (("final_norm_scale",), (d,), "scale"),
        (("mamba", "ln1_scale"), (lm, d), "scale"),
        (("mamba", "conv_w"), (lm, cd, kc), "conv"),
        (("mamba", "conv_b"), (lm, cd), "normal"),
        (("mamba", "dt_bias"), (lm, nh), "dt_bias"),
        (("mamba", "A_log"), (lm, nh), "a_log"),
        (("mamba", "D"), (lm, nh), "one"),
        (("mamba", "norm_scale"), (lm, di), "scale"),
        (("attn", "ln1_scale"), (la, d), "scale"),
        (("attn", "wq"), (la, d, h * hd), "normal"),
        (("attn", "wk"), (la, d, kv * hd), "normal"),
        (("attn", "wv"), (la, d, kv * hd), "normal"),
        (("attn", "wo"), (la, h * hd, d), "normal"),
    ]
    for layer in range(lt):
        plan += [
            (("moe", layer, "ln2_scale"), (d,), "scale"),
            (("moe", layer, "router"), (d, e), "router"),
            (("moe", layer, "w_gate"), (eh, d, f), "normal"),
            (("moe", layer, "w_up"), (eh, d, f), "normal"),
            (("moe", layer, "w_down"), (eh, f, d), "normal"),
            (("moe", layer, "shared_gate"), (d, fs), "normal"),
            (("moe", layer, "shared_up"), (d, fs), "normal"),
            (("moe", layer, "shared_down"), (fs, d), "normal"),
        ]
    return plan


def make_weights(config: dict, seed: int) -> dict:
    # a program that does not know the family says so here, at once, and not
    # after 9 GB of weights
    model_config(config)
    dtype = DTYPES[config["torch_dtype"]]
    root = seed_key(seed)
    lt = len(config["layer_types"])
    out = {"mamba": {}, "attn": {}, "moe": [{} for _ in range(lt)]}
    for i, (path, shape, how) in enumerate(weight_plan(config)):
        node = out
        for part in path[:-1]:
            node = node[part]
        # the tied table at STD / embedding_multiplier: h0 then starts at the
        # std the other matrices have, and a token's own row does not win
        # every logit by embedding_multiplier * |row|^2
        std = (STD / float(config["embedding_multiplier"])
               if path == ("embed",) else STD)
        node[path[-1]] = _leaf(jax.random.fold_in(root, i), shape, dtype, how,
                               std)
    return out


# -- the reference -------------------------------------------------------------

def logit_gaps(config: dict, weights: dict, ids, start, served, *,
               with_control: bool = False):
    return reference.logit_gaps(reference.model_key(config), weights, ids,
                                start, served, with_control=with_control)
