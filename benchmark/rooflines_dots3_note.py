"""Parameters a configuration of the ``dots3_note`` family holds and bytes and
operations a decode step of it must move, from shapes alone. ``c`` is a
configuration file's dict: the published ``config.json`` keys
(``layer_types`` the kinds down the stack, the flat latent keys the FULL
kind's sizes and the ``swa_*`` keys the WINDOW kind's, ``n_routed_experts``
the experts held, ``share.router_experts`` the router's published width,
``first_k_dense_replace`` the leading dense layers). Layers are counted BY
KIND: the two kinds differ in every size. Every count is a floor (each byte
once, nothing for activations, intermediates or the copies a gather makes;
each multiply-add once), so a share of a peak computed from one cannot pass
100%.

What a FULL layer's decode must read is the slot's live INDEX KEYS at their
stored width (every one is scored) and the ``min(length, topk)`` latent rows
the selection NAMES (the program's counters ``index_rows_scored`` and
``sparse_rows_attended``, rows a sparse layer, additive). What a WINDOW
layer's decode must read is the ring rows INSIDE THE BAND, ``min(length,
sliding_window_size)`` a live stream, at their stored width, whichever read
fetches them (a walk or a gather of the whole ring reads more, and is held to
the same need): ``report()``'s ``window_rows_live``, the mean of the window's
two edges.

The shares are of the DECODE STEP's own device time
(``rooflines_lfm2_moe.scope_ms_in_step``: only operations that ran inside the
step executable's runs count): a prefill runs its indexer, its selection and
its experts under the same scopes.
"""
from __future__ import annotations

from benchmark.rooflines import ITEMSIZE
from benchmark.rooflines_keye_vl2 import VMEM_BYTES

LANE_TILE = 128
#: a full layer's decode: the scope, the two shared scopes within it, and
#: its row writes (``paged_kv.write`` stands under ``attn.sparse_latent`` in
#: this family's step and nowhere else: the ring's write has a scope of its
#: own)
SPARSE_LATENT_SCOPES = ("attn.sparse_latent", "attn.sparse.index",
                        "attn.sparse.select", "paged_kv.write")
#: a window layer's decode, the ring's row write within it
WINDOW_LATENT_SCOPES = ("attn.window_latent", "attn.window_latent.write")
MOE_SCOPES = ("moe.route", "moe.experts", "moe.shared")


def _lanes(n: int) -> int:
    return -(-n // LANE_TILE) * LANE_TILE


def layers(c: dict) -> tuple:
    """(full layers, window layers) of the stack."""
    kinds = c["layer_types"]
    return (sum(1 for t in kinds if t == "full_attention"),
            sum(1 for t in kinds if t == "sliding_attention"))


def attention_params(c: dict, prefix: str = "") -> int:
    """One layer's ``W_qa``, ``W_qb``, ``W_kva``, ``W_kvb``, ``W_o``, the
    head-wise gate ``W_g`` and the two latent norms, at the kind's own sizes
    (``prefix`` "" the full kind's keys, "swa_" the window kind's); no
    bias."""
    d, h = c["hidden_size"], c[prefix + "num_attention_heads"]
    nope, rope, vd = (c[prefix + "qk_nope_head_dim"],
                      c[prefix + "qk_rope_head_dim"], c[prefix + "v_head_dim"])
    rq, rkv = c[prefix + "q_lora_rank"], c[prefix + "kv_lora_rank"]
    return (d * rq + rq * h * (nope + rope) + d * (rkv + rope)
            + rkv * h * (nope + vd) + h * vd * d + d * h + rq + rkv)


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
    """Every full layer's attention and indexer, every window layer's
    attention, two norms a layer, the leading dense SwiGLUs, the expert
    layers, the table and the untied head, the final norm."""
    d, n = c["hidden_size"], c["num_hidden_layers"]
    full, window = layers(c)
    return (full * (attention_params(c) + indexer_params(c))
            + window * attention_params(c, "swa_") + n * 2 * d
            + c["first_k_dense_replace"] * dense_ffn_params(c)
            + expert_layers(c) * expert_ffn_params(c)
            + 2 * c["vocab_size"] * d + d)


def latent_row_bytes(c: dict, itemsize: int, prefix: str = "") -> int:
    """A kind's latent row of one position of one layer AS STORED: ``[c |
    k_rope]`` rounded up to whole 128-lane tiles (640 / 1152 lanes)."""
    return _lanes(c[prefix + "kv_lora_rank"]
                  + c[prefix + "qk_rope_head_dim"]) * itemsize


def index_row_bytes(c: dict, itemsize: int) -> int:
    """The index key of one position of one layer as stored."""
    return _lanes(c["index_head_dim"]) * itemsize


def pool_bytes(c: dict) -> int:
    """Both page groups as the serving geometry sizes them: the full
    layers' two leaves, and the window layers' rings (a ring of ``ceil((W -
    1) / ps) + 1`` pages a slot a layer, and the trash page)."""
    s, itemsize = c["serving"], ITEMSIZE[c["torch_dtype"]]
    full, window = layers(c)
    ps = s["page_size"]
    ring = -(-(c["sliding_window_size"] - 1) // ps) + 1
    return (full * s["num_pages"] * ps
            * (latent_row_bytes(c, itemsize) + index_row_bytes(c, itemsize))
            + window * (s["max_slots"] * ring + 1) * ps
            * latent_row_bytes(c, itemsize, "swa_"))


def full_pool_step_bytes(c: dict, scored: float, attended: float,
                         slots: float) -> float:
    """What one step's FULL layers move of the page pool: ``scored`` index
    keys (rows a layer) read at their stored width, ``attended`` latent rows
    read, a row of each a live slot written."""
    itemsize = ITEMSIZE[c["torch_dtype"]]
    row, ik = latent_row_bytes(c, itemsize), index_row_bytes(c, itemsize)
    return float(layers(c)[0]
                 * (scored * ik + attended * row + slots * (row + ik)))


def ring_step_bytes(c: dict, ring_rows: float, slots: float) -> float:
    """What one step's WINDOW layers move of their rings: ``ring_rows`` rows
    inside the band (a layer) read, a row a live slot written."""
    row = latent_row_bytes(c, ITEMSIZE[c["torch_dtype"]], "swa_")
    return float(layers(c)[1] * (ring_rows + slots) * row)


def sparse_latent_step_need(c: dict, scored: float, attended: float,
                            slots: float) -> tuple:
    """(bytes, multiply-add operations) of ``attn.sparse_latent`` in one step
    over the FULL layers: the scope's weights once (the gate among them);
    the pool's rows (:func:`full_pool_step_bytes`); the indexer's ``Hi x di``
    multiply-adds a scored row and the absorbed attend's ``H x (2 r_kv +
    rope)`` an attended row, two operations each; the projections' a slot."""
    full = layers(c)[0]
    weights = attention_params(c) + indexer_params(c)
    need = (full * weights * ITEMSIZE[c["torch_dtype"]]
            + full_pool_step_bytes(c, scored, attended, slots))
    ops = full * 2.0 * (
        scored * c["index_n_heads"] * c["index_head_dim"]
        + attended * c["num_attention_heads"]
        * (2 * c["kv_lora_rank"] + c["qk_rope_head_dim"])
        + slots * weights)
    return float(need), float(ops)


def window_latent_step_need(c: dict, ring_rows: float, slots: float) -> tuple:
    """(bytes, multiply-add operations) of ``attn.window_latent`` in one step
    over the WINDOW layers: the scope's weights once; the ring rows inside
    the band at their stored width and a row a live slot written
    (:func:`ring_step_bytes`); the absorbed attend's ``Hs x (2 r_kv +
    rope)`` multiply-adds a ring row, two operations each, and the
    projections' a slot: the same work whichever read implements it."""
    window = layers(c)[1]
    weights = attention_params(c, "swa_")
    need = (window * weights * ITEMSIZE[c["torch_dtype"]]
            + ring_step_bytes(c, ring_rows, slots))
    ops = window * 2.0 * (
        ring_rows * c["swa_num_attention_heads"]
        * (2 * c["swa_kv_lora_rank"] + c["swa_qk_rope_head_dim"])
        + slots * weights)
    return float(need), float(ops)


def experts_step_bytes(c: dict) -> float:
    """``moe.*`` in the step: the router, the held experts and the shared
    expert of every expert layer, read once (32 tokens x 8 of 256 over 32
    held: one assignment an expert, and under ``moe.DENSE_MAX_TOKENS`` the
    dense path reads every held expert whatever was routed to it)."""
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


def step_bytes(c: dict, scored: float, attended: float, ring_rows: float,
               slots: float) -> float:
    """The whole step: every held weight once (table rows aside: the embed
    reads a row a slot, counted as nothing) and what both page groups' layers
    read and write."""
    held = ((param_count(c) - c["vocab_size"] * c["hidden_size"])
            * ITEMSIZE[c["torch_dtype"]])
    return float(held + full_pool_step_bytes(c, scored, attended, slots)
                 + ring_step_bytes(c, ring_rows, slots))
