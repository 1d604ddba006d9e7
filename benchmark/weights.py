"""Seeded random weights, made on the device in one jitted call, in the type
the configuration serves them in and in the stacked layout the system under
test takes as its input (every layer along a leading axis).

The benchmark makes them, hands them to the program and gives the same arrays
to the plain reference: nothing here comes from the program.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .rooflines import head_dim

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def seed_key(seed: int) -> jax.Array:
    """A key from any non-negative whole number (seeds past 2**31 fold their
    high bits in rather than overflow an int32)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


def weight_shapes(m: dict) -> dict:
    L, D, F = m["num_hidden_layers"], m["hidden_size"], m["intermediate_size"]
    H, KV, hd = m["num_attention_heads"], m["num_key_value_heads"], head_dim(m)
    shapes = {
        "embed": (m["vocab_size"], D),
        "final_norm_scale": (D,),
        "layers": {
            "ln1_scale": (L, D), "ln2_scale": (L, D),
            "wq": (L, D, H * hd), "wk": (L, D, KV * hd), "wv": (L, D, KV * hd),
            "wo": (L, H * hd, D),
            "bq": (L, H * hd), "bk": (L, KV * hd), "bv": (L, KV * hd),
            "w_gate": (L, D, F), "w_up": (L, D, F), "w_down": (L, F, D),
        },
    }
    if not m["tie_word_embeddings"]:
        shapes["lm_head"] = (D, m["vocab_size"])
    return shapes


@functools.partial(jax.jit, static_argnames=("shapes_key", "dtype"))
def _make(key, shapes_key, dtype):
    paths = dict(shapes_key)
    keys = jax.random.split(key, len(paths))
    flat = {}
    for k, (path, shape) in zip(keys, sorted(paths.items())):
        x = jax.random.normal(k, shape, jnp.float32) * 0.02
        if path.endswith("_scale"):
            x = 1.0 + x  # norms near one, biases near zero: both paths live
        flat[path] = x.astype(dtype)
    return flat


def make_weights(m: dict, seed: int, dtype: str = "bfloat16") -> dict:
    shapes = weight_shapes(m)
    flat = {f"layers/{k}": v for k, v in shapes["layers"].items()}
    flat.update({k: v for k, v in shapes.items() if k != "layers"})
    made = _make(seed_key(seed), tuple(sorted(flat.items())), DTYPES[dtype])
    out = {k: v for k, v in made.items() if not k.startswith("layers/")}
    out["layers"] = {k.split("/", 1)[1]: v for k, v in made.items()
                     if k.startswith("layers/")}
    return out
