"""Parameters a configuration of the ``deepseek_v32`` family holds and bytes
and operations a decode step of it must move, from shapes alone. ``c`` is a
configuration file's dict: the published ``config.json`` keys
(``n_routed_experts`` the experts held, ``share.router_experts`` the router's
published width, ``first_k_dense_replace`` the leading dense layers). Every
count is a floor (each byte once, nothing for activations, intermediates or
the copies a gather makes; each multiply-add once), so a share of a peak
computed from one cannot pass 100%.

What a sparse latent layer's decode must read is NOT a slot's cache: it is
the slot's live INDEX KEYS at their stored width (every one is scored) and
the ``min(length, topk)`` latent rows the selection NAMES, whichever read
fetches them (a walk of every live row under the selection's mask reads
more, and is held to the same need). Both come from the program's counters
(``report()``'s ``index_rows_scored`` and ``sparse_rows_attended``, rows a
sparse layer, additive), a mean a step of the window
(``rooflines_keye_vl2.rows_a_step``).

The shares are of the DECODE STEP's own device time
(``rooflines_lfm2_moe.scope_ms_in_step``: only operations that ran inside the
step executable's runs count): a prefill runs its indexer, its selection and
its experts under the same scopes.
"""
from __future__ import annotations

from benchmark.rooflines import ITEMSIZE
from benchmark.rooflines_keye_vl2 import VMEM_BYTES

LANE_TILE = 128
#: a sparse latent layer's decode: the scope, the two shared scopes within
#: it, and the row writes (``paged_kv.write`` stands under
#: ``attn.sparse_latent`` in this family's step and nowhere else in it)
SPARSE_LATENT_SCOPES = ("attn.sparse_latent", "attn.sparse.index",
                        "attn.sparse.select", "paged_kv.write")
MOE_SCOPES = ("moe.route", "moe.experts", "moe.shared")


def _lanes(n: int) -> int:
    return -(-n // LANE_TILE) * LANE_TILE


def attention_params(c: dict) -> int:
    """``W_qa``, ``W_qb``, ``W_kva``, ``W_kvb``, ``W_o`` and the two latent
    norms; no bias."""
    d, h = c["hidden_size"], c["num_attention_heads"]
    nope, rope, vd = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                      c["v_head_dim"])
    rq, rkv = c["q_lora_rank"], c["kv_lora_rank"]
    return (d * rq + rq * h * (nope + rope) + d * (rkv + rope)
            + rkv * h * (nope + vd) + h * vd * d + rq + rkv)


def indexer_params(c: dict) -> int:
    """``W_qI`` (r_q x Hi di: off the q latent), ``W_kI`` (D x di), ``W_w``
    (D x Hi) and the index key's LayerNorm (scale and bias)."""
    d, hi, di = c["hidden_size"], c["index_n_heads"], c["index_head_dim"]
    return c["q_lora_rank"] * hi * di + d * di + d * hi + 2 * di


def expert_ffn_params(c: dict) -> int:
    """The router over its published width with its selection bias, the
    held experts and the shared one (SwiGLU: gate, up, down)."""
    d, f = c["hidden_size"], c["moe_intermediate_size"]
    width = c.get("share", {}).get("router_experts", c["n_routed_experts"])
    return (d * width + width
            + 3 * (c["n_routed_experts"] + c["n_shared_experts"]) * d * f)


def dense_ffn_params(c: dict) -> int:
    return 3 * c["hidden_size"] * c["intermediate_size"]


def expert_layers(c: dict) -> int:
    return c["num_hidden_layers"] - c["first_k_dense_replace"]


def param_count(c: dict) -> int:
    """Every layer's attention, indexer and two norms, the leading dense
    SwiGLUs, the expert layers, the table and the untied head, the final
    norm."""
    d, n = c["hidden_size"], c["num_hidden_layers"]
    return (n * (attention_params(c) + indexer_params(c) + 2 * d)
            + c["first_k_dense_replace"] * dense_ffn_params(c)
            + expert_layers(c) * expert_ffn_params(c)
            + 2 * c["vocab_size"] * d + d)


def latent_row_bytes(c: dict, itemsize: int) -> int:
    """The latent row of one position of one layer AS STORED: ``[c | k_rope]``
    rounded up to whole 128-lane tiles."""
    return _lanes(c["kv_lora_rank"] + c["qk_rope_head_dim"]) * itemsize


def index_row_bytes(c: dict, itemsize: int) -> int:
    """The index key of one position of one layer as stored."""
    return _lanes(c["index_head_dim"]) * itemsize


def pool_bytes(c: dict) -> int:
    """The page pool as the serving geometry sizes it, both leaves."""
    s, itemsize = c["serving"], ITEMSIZE[c["torch_dtype"]]
    return (c["num_hidden_layers"] * s["num_pages"] * s["page_size"]
            * (latent_row_bytes(c, itemsize) + index_row_bytes(c, itemsize)))


def pool_step_bytes(c: dict, scored: float, attended: float,
                    slots: float) -> float:
    """What one step's sparse latent layers move of the page pool, every
    layer: ``scored`` index keys (rows a layer) read at their stored width,
    ``attended`` latent rows read, a row of each a live slot written."""
    itemsize = ITEMSIZE[c["torch_dtype"]]
    row, ik = latent_row_bytes(c, itemsize), index_row_bytes(c, itemsize)
    return float(c["num_hidden_layers"]
                 * (scored * ik + attended * row + slots * (row + ik)))


def sparse_latent_step_need(c: dict, scored: float, attended: float,
                            slots: float) -> tuple:
    """(bytes, multiply-add operations) of ``attn.sparse_latent`` in one step
    over every layer: the scope's weights once; the pool's rows
    (:func:`pool_step_bytes`); the indexer's ``Hi x di`` multiply-adds a
    scored row and the absorbed attend's ``H x (2 r_kv + rope)`` (the score
    over the latent and the rope lanes, the weighted sum over the latent) an
    attended row, two operations each; the projections' a slot."""
    layers = c["num_hidden_layers"]
    weights = attention_params(c) + indexer_params(c)
    need = (layers * weights * ITEMSIZE[c["torch_dtype"]]
            + pool_step_bytes(c, scored, attended, slots))
    ops = layers * 2.0 * (
        scored * c["index_n_heads"] * c["index_head_dim"]
        + attended * c["num_attention_heads"]
        * (2 * c["kv_lora_rank"] + c["qk_rope_head_dim"])
        + slots * weights)
    return float(need), float(ops)


def experts_step_bytes(c: dict) -> float:
    """``moe.*`` in the step: the router, the held experts and the shared
    expert of every expert layer, read once (16 tokens x 8 of 256 over 16
    held: half an assignment an expert, and under ``moe.DENSE_MAX_TOKENS``
    the dense path reads every held expert whatever was routed to it)."""
    return float(expert_layers(c) * expert_ffn_params(c)
                 * ITEMSIZE[c["torch_dtype"]])


def experts_in_scope_bytes(c: dict, device_kind: str) -> float:
    """:func:`experts_step_bytes` less what the chip's vector memory can hold
    ahead of each expert layer's products
    (``rooflines_keye_vl2.experts_in_scope_bytes``' rule: the compiler may
    stage an operand there under the attention before it, and the scopes'
    time then does not hold those bytes' traffic)."""
    if device_kind not in VMEM_BYTES:
        raise KeyError(f"no vector memory size for device_kind "
                       f"{device_kind!r} (known: {sorted(VMEM_BYTES)})")
    ahead = expert_layers(c) * VMEM_BYTES[device_kind]
    return max(experts_step_bytes(c) - ahead, 0.0)


def step_bytes(c: dict, scored: float, attended: float,
               slots: float) -> float:
    """The whole step: every held weight once (table rows aside: the embed
    reads a row a slot, counted as nothing) and what the sparse latent
    layers read and write of the pool."""
    held = ((param_count(c) - c["vocab_size"] * c["hidden_size"])
            * ITEMSIZE[c["torch_dtype"]])
    return float(held + pool_step_bytes(c, scored, attended, slots))
