"""Bytes a decode step of the ``mellum`` family must move, from shapes alone.
``c`` is a configuration file's dict: the published ``config.json`` keys
(``share.experts_held`` where fewer than ``num_experts`` are held). Every
count is a floor (each byte once, nothing for activations, intermediates or
the copies a page gather makes), so a share of the HBM peak computed from one
cannot pass 100%.
"""
from __future__ import annotations

from benchmark.rooflines import ITEMSIZE


def _layers(c: dict) -> tuple:
    """(full layers, sliding layers)."""
    kinds = c["layer_types"]
    return kinds.count("full_attention"), kinds.count("sliding_attention")


def attention_layer_params(c: dict) -> int:
    """wq and wo at H x hd (NOT hidden_size) wide, wk and wv at KV x hd, the
    input norm; no bias."""
    d, hd = c["hidden_size"], c["head_dim"]
    h, kv = c["num_attention_heads"], c["num_key_value_heads"]
    return 2 * d * h * hd + 2 * d * kv * hd + d


def moe_layer_params(c: dict) -> int:
    """The router over its published width, the held experts (SwiGLU: gate,
    up, down), the input norm; no shared expert."""
    d = c["hidden_size"]
    held = c.get("share", {}).get("experts_held", c["num_experts"])
    return d * c["num_experts"] + 3 * held * d * c["moe_intermediate_size"] + d


def param_count(c: dict) -> int:
    """Every layer, the table, the untied head and the final norm."""
    d = c["hidden_size"]
    return (len(c["layer_types"])
            * (attention_layer_params(c) + moe_layer_params(c))
            + 2 * c["vocab_size"] * d + d)


def kv_row_bytes(c: dict, itemsize: int) -> int:
    """K and V of one position of ONE layer."""
    return 2 * c["num_key_value_heads"] * c["head_dim"] * itemsize


def full_rows_bytes(c: dict, live_tokens: float) -> float:
    """The full layers' live rows, each read once: every position of every
    live stream, in every full layer."""
    itemsize = ITEMSIZE[c["torch_dtype"]]
    return _layers(c)[0] * live_tokens * kv_row_bytes(c, itemsize)


def window_rows_bytes(c: dict, live_window_rows: float) -> float:
    """The sliding layers' live rows, each read once: ``live_window_rows`` is
    the sum over live streams of min(length, sliding_window), a layer."""
    itemsize = ITEMSIZE[c["torch_dtype"]]
    return _layers(c)[1] * live_window_rows * kv_row_bytes(c, itemsize)


def step_bytes(c: dict, live_tokens: float, live_window_rows: float,
               slots: float) -> float:
    """The whole step: every held weight once (of the table only the rows
    the embed gathers, one a slot; the untied head whole), the live K/V rows
    of the full layers once and of the sliding layers once, window-capped,
    and one new row a slot a layer written."""
    itemsize = ITEMSIZE[c["torch_dtype"]]
    d = c["hidden_size"]
    weights = (param_count(c) - c["vocab_size"] * d + slots * d) * itemsize
    written = slots * len(c["layer_types"]) * kv_row_bytes(c, itemsize)
    return (weights + full_rows_bytes(c, live_tokens)
            + window_rows_bytes(c, live_window_rows) + written)


# -- what the readers share ---------------------------------------------------

def window_rows(record: dict):
    """(live ring rows a sliding layer, the rings' capacity) from
    ``report()`` at the window's two edges, the mean of the two; None where
    the program has no such counter."""
    r0, r1 = record["report0"], record["report1"]
    if not r1.get("window_rows_capacity"):
        return None
    return (0.5 * (r0.get("window_rows_live", r1["window_rows_live"])
                   + r1["window_rows_live"]), r1["window_rows_capacity"])
