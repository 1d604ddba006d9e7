"""Bytes and operations a decode step of the ``longcat_flash`` family must
move, from shapes alone. ``c`` is a configuration file's dict: the published
``config.json`` keys (``n_routed_experts`` the experts HELD here and
``share.router_experts`` the routed experts the router scores where they
differ; the router is ``zero_expert_num`` outputs wider than that). A
published layer (``num_layers`` of them) is TWO attention sublayers, two
dense SwiGLUs and one routed layer; a position caches one latent row a
SUBLAYER. Every count is a floor (each byte once, nothing for activations,
intermediates or the copies a page gather makes), so a share of a peak
computed from one cannot pass 100%.
"""
from __future__ import annotations

from benchmark.rooflines import ITEMSIZE

#: the ``moe.*`` scopes of the routed layer (the family has no shared expert)
MOE_SCOPES = ("moe.route", "moe.experts")
#: attention sublayers a published layer
SUBLAYERS = 2


def attention_sublayer_params(c: dict) -> int:
    """W_qa, its norm, W_qb over H x (nope + rope); W_kva over the latent and
    the rope lanes, the latent's norm, W_kvb over H x (nope + vd); W_o from
    H x vd; no bias. (The input norm is counted with the layer's four.)"""
    d, h = c["hidden_size"], c["num_attention_heads"]
    qr, rank = c["q_lora_rank"], c["kv_lora_rank"]
    nope, rope, vd = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                      c["v_head_dim"])
    return (d * qr + qr + qr * h * (nope + rope) + d * (rank + rope) + rank
            + rank * h * (nope + vd) + h * vd * d)


def dense_ffn_params(c: dict) -> int:
    """One dense SwiGLU: gate, up, down."""
    return 3 * c["hidden_size"] * c["ffn_hidden_size"]


def routed_params(c: dict) -> int:
    """The router over all its outputs (routed + identity), its selection
    bias, the held experts (SwiGLU: gate, up, down). The identity experts
    hold nothing."""
    d, f = c["hidden_size"], c["expert_ffn_hidden_size"]
    held = c["n_routed_experts"]
    width = (c.get("share", {}).get("router_experts", held)
             + c["zero_expert_num"])
    return d * width + width + 3 * held * d * f


def layer_params(c: dict) -> int:
    """A published layer: two attention sublayers, two dense SwiGLUs, the
    routed layer, four norms."""
    return (SUBLAYERS * (attention_sublayer_params(c) + dense_ffn_params(c))
            + routed_params(c) + 4 * c["hidden_size"])


def param_count(c: dict) -> int:
    """Every layer, the table, the untied head and the final norm."""
    d = c["hidden_size"]
    return c["num_layers"] * layer_params(c) + 2 * c["vocab_size"] * d + d


def latent_rows_bytes(c: dict, live_rows: float, row_bytes: float) -> float:
    """The live latent rows of every SUBLAYER, each read once at its STORED
    width (``row_bytes``: the program's ``kv_row_bytes``, padding included)."""
    return SUBLAYERS * c["num_layers"] * live_rows * row_bytes


def latent_attend_flops(c: dict, live_rows: float) -> float:
    """The absorbed attend's multiply-adds over the live rows, as operations:
    every head's score over the latent and the rope lanes, and its weighted
    sum over the latent lanes, a sublayer."""
    lanes = 2 * c["kv_lora_rank"] + c["qk_rope_head_dim"]
    return (2.0 * SUBLAYERS * c["num_layers"] * live_rows
            * c["num_attention_heads"] * lanes)


def experts_step_bytes(c: dict) -> float:
    """``moe.*``: the router, bias and held experts of every published
    layer, read once."""
    return float(c["num_layers"] * routed_params(c)
                 * ITEMSIZE[c["torch_dtype"]])


def step_bytes(c: dict, live_rows: float, row_bytes: float,
               slots: float) -> float:
    """The whole step: every held weight once (of the table only the rows
    the embed gathers, one a slot; the untied head whole), the live latent
    rows of every sublayer once, and one new row a slot a sublayer written."""
    itemsize = ITEMSIZE[c["torch_dtype"]]
    d = c["hidden_size"]
    weights = (param_count(c) - c["vocab_size"] * d + slots * d) * itemsize
    written = slots * SUBLAYERS * c["num_layers"] * row_bytes
    return weights + latent_rows_bytes(c, live_rows, row_bytes) + written


def attend_floor_ms(record: dict, live_rows: float, row_bytes: float):
    """The least milliseconds the absorbed attends of a step need on this
    chip: the larger of their rows' bytes at the HBM peak and their
    multiply-adds at the bf16 peak."""
    from benchmark.peaks import peak

    c, kind = record["config"], record["device_kind"]
    return 1e3 * max(
        latent_rows_bytes(c, live_rows, row_bytes) / peak(kind, "hbm_bytes_s"),
        latent_attend_flops(c, live_rows) / peak(kind, "bf16_flops"))
