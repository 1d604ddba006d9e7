"""Parameters a configuration of the ``afmoe`` family holds and bytes a decode
step of it must move, from shapes alone. ``c`` is a configuration file's dict:
the published ``config.json`` keys (``share.experts_held`` where fewer than
``num_experts`` are held). Every byte count is a floor (each byte once,
nothing for activations, intermediates or the copies a page gather makes), so
a share of the HBM peak computed from one cannot pass 100%.
"""
from __future__ import annotations

from benchmark.rooflines import ITEMSIZE

#: the ``moe.*`` scopes of an expert layer
MOE_SCOPES = ("moe.route", "moe.experts", "moe.shared")


def _layers(c: dict) -> tuple:
    """(full layers, sliding layers, dense layers, expert layers)."""
    kinds = c["layer_types"]
    dense = c["num_dense_layers"]
    return (kinds.count("full_attention"), kinds.count("sliding_attention"),
            dense, len(kinds) - dense)


def attention_params(c: dict) -> int:
    """wq, wo and the gate wg at H x hd wide, wk and wv at KV x hd, the two
    per-head norm scales; no bias."""
    d, hd = c["hidden_size"], c["head_dim"]
    h, kv = c["num_attention_heads"], c["num_key_value_heads"]
    return 3 * d * h * hd + 2 * d * kv * hd + 2 * hd


def norm_params(c: dict) -> int:
    """The four norms of a layer."""
    return 4 * c["hidden_size"]


def expert_ffn_params(c: dict) -> int:
    """The router over its published width, its selection bias, the held
    experts and the shared expert (SwiGLU: gate, up, down)."""
    d, f = c["hidden_size"], c["moe_intermediate_size"]
    held = c.get("share", {}).get("experts_held", c["num_experts"])
    return (d * c["num_experts"] + c["num_experts"] + 3 * held * d * f
            + 3 * d * f * c["num_shared_experts"])


def expert_layer_params(c: dict) -> int:
    return attention_params(c) + norm_params(c) + expert_ffn_params(c)


def dense_layer_params(c: dict) -> int:
    return (attention_params(c) + norm_params(c)
            + 3 * c["hidden_size"] * c["intermediate_size"])


def param_count(c: dict) -> int:
    """Every layer, the table, the untied head and the final norm."""
    d = c["hidden_size"]
    _, _, dense, expert = _layers(c)
    return (dense * dense_layer_params(c) + expert * expert_layer_params(c)
            + 2 * c["vocab_size"] * d + d)


def kv_row_bytes(c: dict, itemsize: int) -> int:
    """K and V of one position of ONE layer."""
    return 2 * c["num_key_value_heads"] * c["head_dim"] * itemsize


def pool_bytes(c: dict) -> tuple:
    """(the full group's pool, the ring group's) as the serving geometry
    sizes them: ``num_pages`` pages a full layer; ``max_slots`` rings of
    ``ceil((window - 1) / page_size) + 1`` pages and the trash page a sliding
    layer."""
    s = c["serving"]
    page = s["page_size"] * kv_row_bytes(c, ITEMSIZE[c["torch_dtype"]])
    full, sliding, _, _ = _layers(c)
    ring = -(-(c["sliding_window"] - 1) // s["page_size"]) + 1
    return (full * s["num_pages"] * page,
            sliding * (s["max_slots"] * ring + 1) * page)


def experts_step_bytes(c: dict) -> float:
    """``moe.*``: the router, bias, held experts and shared expert of every
    expert layer, read once (at 96 tokens x 8 of 128 every expert is hit)."""
    itemsize = ITEMSIZE[c["torch_dtype"]]
    return float(_layers(c)[3] * expert_ffn_params(c) * itemsize)


def step_bytes(c: dict, live_tokens: float, live_window_rows: float,
               slots: float) -> float:
    """The whole step: every held weight once (of the table only the rows the
    embed gathers, one a slot; the untied head whole), the full layers' live
    K/V rows once, the sliding layers' once and window-capped, and one new
    row a slot a layer written."""
    itemsize = ITEMSIZE[c["torch_dtype"]]
    d = c["hidden_size"]
    full, sliding, _, _ = _layers(c)
    row = kv_row_bytes(c, itemsize)
    weights = (param_count(c) - c["vocab_size"] * d + slots * d) * itemsize
    return (weights + full * live_tokens * row
            + sliding * live_window_rows * row
            + slots * len(c["layer_types"]) * row)
