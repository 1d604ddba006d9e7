"""Bytes and operations a decode step of the ``mistral4`` family must move,
from shapes alone. ``c`` is a configuration file's dict: the published
``config.json`` keys (``n_routed_experts`` the experts HELD here and
``share.router_experts`` the router's width where they differ). Every count
is a floor (each byte once, nothing for activations, intermediates or the
copies a page gather makes), so a share of a peak computed from one cannot
pass 100%.
"""
from __future__ import annotations

from benchmark.rooflines import ITEMSIZE


def attention_layer_params(c: dict) -> int:
    """W_qa, its norm, W_qb over H x (nope + rope); W_kva over the latent and
    the rope lanes, the latent's norm, W_kvb over H x (nope + vd); W_o from
    H x vd; the input norm; no bias."""
    d, h = c["hidden_size"], c["num_attention_heads"]
    qr, rank = c["q_lora_rank"], c["kv_lora_rank"]
    nope, rope, vd = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                      c["v_head_dim"])
    return (d * qr + qr + qr * h * (nope + rope) + d * (rank + rope) + rank
            + rank * h * (nope + vd) + h * vd * d + d)


def moe_layer_params(c: dict) -> int:
    """The router over its published width, the held experts and the shared
    expert (SwiGLU: gate, up, down), the input norm."""
    d, f = c["hidden_size"], c["moe_intermediate_size"]
    held = c["n_routed_experts"]
    router = c.get("share", {}).get("router_experts", held)
    return (d * router + 3 * held * d * f
            + 3 * d * f * c["n_shared_experts"] + d)


def param_count(c: dict) -> int:
    """Every layer, the table, the untied head and the final norm."""
    d = c["hidden_size"]
    return (c["num_hidden_layers"]
            * (attention_layer_params(c) + moe_layer_params(c))
            + 2 * c["vocab_size"] * d + d)


def latent_rows_bytes(c: dict, live_rows: float, row_bytes: float) -> float:
    """The live latent rows of every layer, each read once at its STORED
    width (``row_bytes``: the program's ``kv_row_bytes``, padding included)."""
    return c["num_hidden_layers"] * live_rows * row_bytes


def latent_attend_flops(c: dict, live_rows: float) -> float:
    """The absorbed attend's multiply-adds over the live rows, as operations:
    every head's score over the latent and the rope lanes, and its weighted
    sum over the latent lanes."""
    lanes = 2 * c["kv_lora_rank"] + c["qk_rope_head_dim"]
    return (2.0 * c["num_hidden_layers"] * live_rows
            * c["num_attention_heads"] * lanes)


def step_bytes(c: dict, live_rows: float, row_bytes: float,
               slots: float) -> float:
    """The whole step: every held weight once (of the table only the rows
    the embed gathers, one a slot; the untied head whole), the live latent
    rows once, and one new row a slot a layer written."""
    itemsize = ITEMSIZE[c["torch_dtype"]]
    d = c["hidden_size"]
    weights = (param_count(c) - c["vocab_size"] * d + slots * d) * itemsize
    written = slots * c["num_hidden_layers"] * row_bytes
    return weights + latent_rows_bytes(c, live_rows, row_bytes) + written


# -- what the readers share ---------------------------------------------------

def latent_rows(record: dict):
    """(live latent rows a layer, the pages' capacity in rows, a row's stored
    bytes) from ``report()`` at the window's two edges, the live rows the
    mean of the two; None where the program has no such counter."""
    r0, r1 = record["report0"], record["report1"]
    if not r1.get("latent_rows_capacity") or not r1.get("kv_row_bytes"):
        return None
    return (0.5 * (r0.get("latent_rows_live", r1["latent_rows_live"])
                   + r1["latent_rows_live"]),
            r1["latent_rows_capacity"], r1["kv_row_bytes"])


def attend_floor_ms(record: dict, live_rows: float, row_bytes: float):
    """The least milliseconds the absorbed attend of a step needs on this
    chip: the larger of its rows' bytes at the HBM peak and its
    multiply-adds at the bf16 peak."""
    from benchmark.peaks import peak

    c, kind = record["config"], record["device_kind"]
    return 1e3 * max(
        latent_rows_bytes(c, live_rows, row_bytes) / peak(kind, "hbm_bytes_s"),
        latent_attend_flops(c, live_rows) / peak(kind, "bf16_flops"))
