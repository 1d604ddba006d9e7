"""Bytes a decode step of the ``granitemoehybrid`` family must move, from
shapes alone. ``c`` is a configuration file's dict: the published
``config.json`` keys, with ``num_local_experts`` the experts HELD here and
``share.router_experts`` the router's width. Every count is a floor (each
byte once, nothing for activations or intermediates), so a share of the HBM
peak computed from one cannot pass 100%.
"""
from __future__ import annotations

from benchmark.rooflines import ITEMSIZE

STATE_ITEMSIZE = 4      # recurrent state and window are float32 (``assumed``)
#: the expert layer's scopes: router, held experts, shared expert
MOE_SCOPES = ("moe.route", "moe.experts", "moe.shared")


def _dims(c: dict) -> dict:
    nh, p = c["mamba_n_heads"], c["mamba_d_head"]
    gn = c["mamba_n_groups"] * c["mamba_d_state"]
    return {"d": c["hidden_size"], "di": nh * p, "cd": nh * p + 2 * gn,
            "nh": nh, "p": p, "n": c["mamba_d_state"],
            "kc": c["mamba_d_conv"],
            "hd": c["hidden_size"] // c["num_attention_heads"],
            "lm": c["layer_types"].count("mamba"),
            "la": c["layer_types"].count("attention"),
            "lt": len(c["layer_types"])}


def mamba_layer_params(c: dict) -> int:
    """One Mamba-2 mixer: in and out projections, convolution, the per-head
    scalars, the gated norm and the layer's input norm."""
    k = _dims(c)
    return (k["d"] * (k["di"] + k["cd"] + k["nh"]) + k["di"] * k["d"]
            + k["cd"] * k["kc"] + k["cd"] + 3 * k["nh"] + k["di"] + k["d"])


def attention_layer_params(c: dict) -> int:
    k = _dims(c)
    h, kv = c["num_attention_heads"], c["num_key_value_heads"]
    return 2 * k["d"] * h * k["hd"] + 2 * k["d"] * kv * k["hd"] + k["d"]


def moe_layer_params(c: dict) -> int:
    """One layer's feed-forward as held here: the router over its published
    width, the held experts, the shared expert, the input norm."""
    d = c["hidden_size"]
    e = c.get("share", {}).get("router_experts", c["num_local_experts"])
    return (d * e + 3 * c["num_local_experts"] * d * c["intermediate_size"]
            + 3 * d * c["shared_intermediate_size"] + d)


def param_count(c: dict) -> int:
    k = _dims(c)
    return (k["lm"] * mamba_layer_params(c)
            + k["la"] * attention_layer_params(c)
            + k["lt"] * moe_layer_params(c)
            + c["vocab_size"] * k["d"] + k["d"])


def state_bytes_per_slot(c: dict) -> int:
    """One slot's recurrent state and convolution window over the mamba
    layers."""
    k = _dims(c)
    return k["lm"] * STATE_ITEMSIZE * (k["nh"] * k["p"] * k["n"]
                                       + (k["kc"] - 1) * k["cd"])


def kv_bytes_per_token(c: dict, itemsize: int) -> int:
    k = _dims(c)
    return k["la"] * 2 * c["num_key_value_heads"] * k["hd"] * itemsize


def ssm_step_bytes(c: dict, live_slots: float) -> float:
    """``ssm.step``: every live slot's state read once and written once."""
    return 2.0 * live_slots * state_bytes_per_slot(c)


def moe_step_bytes(c: dict) -> float:
    """``moe.*``: every layer's router, held experts and shared expert read
    once (at 60 tokens x 10 of 72 every held expert is hit)."""
    itemsize = ITEMSIZE[c["torch_dtype"]]
    return float(_dims(c)["lt"] * (moe_layer_params(c) - c["hidden_size"])
                 * itemsize)


def hybrid_step_bytes(c: dict, live_tokens: float, live_slots: float
                      ) -> float:
    """The whole step: every held weight once (the tied table is read whole
    by the unembed), the live slots' state read and written, the live K/V
    rows of the attention layers read once and one new row a slot written."""
    itemsize = ITEMSIZE[c["torch_dtype"]]
    kv = kv_bytes_per_token(c, itemsize)
    return (param_count(c) * itemsize + ssm_step_bytes(c, live_slots)
            + live_tokens * kv + live_slots * kv)


# -- what the readers share ---------------------------------------------------

def live_slots(record: dict):
    """Mean slots in use over the window's steps, from ``report()``."""
    r0, r1 = record["report0"], record["report1"]
    n = r1["steps"] - r0["steps"]
    if not n:
        return None
    util = (r1["slot_util_mean"] * r1["steps"]
            - r0["slot_util_mean"] * r0["steps"]) / n
    return util * record["config"]["serving"]["max_slots"]


def scope_ms(record: dict, scopes: tuple, per_span: str):
    """Device self milliseconds of the named scopes per ``per_span`` span of
    the traced window (the program table of ``program_trace``), or None where
    the trace has no such span or the program no such scope."""
    from benchmark import program_trace

    table = program_trace.table_for(record)
    n = program_trace.span_count(table, per_span) if table else 0
    found = [table["scopes"][s] for s in scopes if s in table["scopes"]] \
        if n else []
    if not found:
        return None
    return 1e3 * sum(found) / n


def hbm_share(record: dict, need_bytes: float, ms: float):
    """``need_bytes`` at the chip's HBM peak as a share (%) of ``ms``."""
    from benchmark.peaks import peak

    floor_ms = 1e3 * need_bytes / peak(record["device_kind"], "hbm_bytes_s")
    return 100.0 * floor_ms / ms
