"""Bytes and operations a call must move or perform, from shapes alone.

``m`` is the ``model`` object of a configuration file: the keys of the
published ``config.json`` (Qwen2 family: sequential residual, RMSNorm, SwiGLU,
grouped-query attention, q/k/v biases, tied embeddings where the file says).
A multiply-add is 2 operations; matrix multiplications only.
"""
from __future__ import annotations


ITEMSIZE = {"bfloat16": 2, "float32": 4}


def head_dim(m: dict) -> int:
    return m["hidden_size"] // m["num_attention_heads"]


def layer_param_count(m: dict) -> int:
    d, f, hd = m["hidden_size"], m["intermediate_size"], head_dim(m)
    h, kv = m["num_attention_heads"], m["num_key_value_heads"]
    attn = d * h * hd + 2 * d * kv * hd + h * hd * d   # wq, wk, wv, wo
    bias = h * hd + 2 * kv * hd                        # bq, bk, bv
    return attn + bias + 3 * d * f + 2 * d             # + SwiGLU + 2 norms


def param_count(m: dict) -> int:
    n = m["num_hidden_layers"] * layer_param_count(m)
    n += m["vocab_size"] * m["hidden_size"] + m["hidden_size"]  # embed, norm
    if not m["tie_word_embeddings"]:
        n += m["vocab_size"] * m["hidden_size"]
    return n


def kv_bytes_per_token(m: dict, itemsize: int) -> int:
    """K and V rows of one position through every layer."""
    return (m["num_hidden_layers"] * 2 * m["num_key_value_heads"]
            * head_dim(m) * itemsize)


def decode_step_bytes(m: dict, live_tokens: float, slots: int,
                      itemsize: int) -> float:
    """The least one decode step must move through HBM: every weight once
    (the tied table is read whole by the unembed; the embed gathers ``slots``
    rows of it), the K/V rows of every live position once, and one new K/V
    row written per slot."""
    weights = param_count(m) * itemsize
    gather = slots * m["hidden_size"] * itemsize
    kv = kv_bytes_per_token(m, itemsize)
    return weights + gather + live_tokens * kv + slots * kv


def layer_flops_per_token(m: dict, seq_len: int) -> float:
    """One decoder block, per token, at sequence length ``seq_len``: the
    projections, the MLP, and QK^T + PV counted at the full S that a dense
    causal softmax executes."""
    d, hd = m["hidden_size"], head_dim(m)
    h, kv = m["num_attention_heads"], m["num_key_value_heads"]
    proj = 2 * (d * h * hd + 2 * d * kv * hd + h * hd * d)
    mlp = 2 * 3 * d * m["intermediate_size"]
    attn = 2 * 2 * seq_len * h * hd
    return float(proj + mlp + attn)


def unembed_flops_per_position(m: dict) -> float:
    return float(2 * m["hidden_size"] * m["vocab_size"])


def prefill_flops(m: dict, seq_len: int) -> float:
    """One prompt of ``seq_len`` tokens: every layer over every position and
    the unembed of the last one."""
    return (m["num_hidden_layers"] * layer_flops_per_token(m, seq_len)
            * seq_len + unembed_flops_per_position(m))
