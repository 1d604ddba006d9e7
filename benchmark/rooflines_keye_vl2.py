"""Parameters a configuration of the ``KeyeVL2`` family holds and bytes and
operations a decode step of it must move, from shapes alone. ``c`` is a
configuration file's dict: the published ``config.json`` keys (``num_experts``
the experts held, ``share.router_experts`` the router's published width).
Every count is a floor (each byte once, nothing for activations,
intermediates or the copies a gather makes; each multiply-add once), so a
share of a peak computed from one cannot pass 100%.

What a sparse layer's decode must read is NOT a slot's cache: it is the
slot's live INDEX KEYS at their stored width (every one is scored) and the
``min(length, topk)`` K/V rows the selection names. Both come from the
program's counters (``report()``'s ``index_rows_scored`` and
``sparse_rows_attended``, rows a sparse layer, additive), a mean a step of
the window.

The shares are of the DECODE STEP's own device time
(``rooflines_lfm2_moe.scope_ms_in_step``: only operations that ran inside the
step executable's runs count): a prefill runs its indexer, its selection and
its experts under the same scopes.
"""
from __future__ import annotations

from benchmark.rooflines import ITEMSIZE

LANE_TILE = 128
#: a sparse layer's decode: the scope, the two within it, and the row writes
#: (``paged_kv.write`` stands under ``attn.sparse`` in this family's step and
#: nowhere else in it)
SPARSE_SCOPES = ("attn.sparse", "attn.sparse.index", "attn.sparse.select",
                 "paged_kv.write")
INDEX_SCOPES = ("attn.sparse.index",)
SELECT_SCOPES = ("attn.sparse.select",)
MOE_SCOPES = ("moe.route", "moe.experts")


def attention_params(c: dict) -> int:
    """wq and wo at H x hd wide, wk and wv at KV x hd, the two per-head norm
    scales; no bias."""
    d, hd = c["hidden_size"], c["head_dim"]
    h, kv = c["num_attention_heads"], c["num_key_value_heads"]
    return 2 * d * h * hd + 2 * d * kv * hd + 2 * hd


def indexer_params(c: dict) -> int:
    """``WqI`` (D x Hi di), ``WkI`` (D x di), ``Ww`` (D x Hi) and the index
    key's LayerNorm (scale and bias)."""
    d, sa = c["hidden_size"], c["sa_config"]
    hi, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    return d * hi * di + d * di + d * hi + 2 * di


def expert_ffn_params(c: dict) -> int:
    """The router over its published width and the held experts (SwiGLU:
    gate, up, down); no shared expert, no bias."""
    d, f = c["hidden_size"], c["moe_intermediate_size"]
    width = c.get("share", {}).get("router_experts", c["num_experts"])
    return d * width + 3 * c["num_experts"] * d * f


def layer_params(c: dict) -> int:
    """A layer whole: attention, the indexer, the expert layer, two norms."""
    return (attention_params(c) + indexer_params(c) + expert_ffn_params(c)
            + 2 * c["hidden_size"])


def param_count(c: dict) -> int:
    """Every layer, the table and the untied head, the final norm."""
    d = c["hidden_size"]
    return (c["num_hidden_layers"] * layer_params(c)
            + 2 * c["vocab_size"] * d + d)


def kv_row_bytes(c: dict, itemsize: int) -> int:
    """K and V of one position of one layer."""
    return 2 * c["num_key_value_heads"] * c["head_dim"] * itemsize


def index_row_bytes(c: dict, itemsize: int) -> int:
    """The index key of one position of one layer AS STORED: its lanes
    rounded up to whole 128-lane tiles."""
    di = c["sa_config"]["indexer_head_dim"]
    return -(-di // LANE_TILE) * LANE_TILE * itemsize


def pool_bytes(c: dict) -> int:
    """The page pool as the serving geometry sizes it, both leaves."""
    s, itemsize = c["serving"], ITEMSIZE[c["torch_dtype"]]
    return (c["num_hidden_layers"] * s["num_pages"] * s["page_size"]
            * (kv_row_bytes(c, itemsize) + index_row_bytes(c, itemsize)))


def pool_step_bytes(c: dict, scored: float, attended: float,
                    slots: float) -> float:
    """What one step's sparse layers move of the page pool, every layer:
    ``scored`` index keys (rows a layer) read at their stored width,
    ``attended`` K/V rows read, a row of each a live slot written."""
    itemsize = ITEMSIZE[c["torch_dtype"]]
    kv, ik = kv_row_bytes(c, itemsize), index_row_bytes(c, itemsize)
    return float(c["num_hidden_layers"]
                 * (scored * ik + attended * kv + slots * (kv + ik)))


def sparse_step_need(c: dict, scored: float, attended: float,
                     slots: float) -> tuple:
    """(bytes, multiply-add operations) of ``attn.sparse`` in one step over
    every layer: the scope's weights once; the pool's rows
    (:func:`pool_step_bytes`); the indexer's ``Hi x di``
    multiply-adds a scored row and the attend's ``2 x H x hd`` (scores, then
    the weighted sum) an attended row, two operations each."""
    sa, layers = c["sa_config"], c["num_hidden_layers"]
    need = (layers * (attention_params(c) + indexer_params(c))
            * ITEMSIZE[c["torch_dtype"]]
            + pool_step_bytes(c, scored, attended, slots))
    ops = layers * 2.0 * (
        scored * sa["indexer_num_heads"] * sa["indexer_head_dim"]
        + attended * 2 * c["num_attention_heads"] * c["head_dim"]
        + slots * (attention_params(c) + indexer_params(c)))
    return float(need), float(ops)


def experts_step_bytes(c: dict) -> float:
    """``moe.*`` in the step: the router and held experts of every layer,
    read once (32 tokens x 8 of 128 over 32 held: 2 tokens an expert, every
    expert hit in most steps, and the dense path reads them all)."""
    return float(c["num_hidden_layers"] * expert_ffn_params(c)
                 * ITEMSIZE[c["torch_dtype"]])


#: on-chip vector memory a core, by device kind (v5e: 128 MiB): what the
#: compiler can fill AHEAD of a scope with that scope's operands
VMEM_BYTES = {"TPU v5 lite": 128 * 2 ** 20}


def experts_in_scope_bytes(c: dict, device_kind: str) -> float:
    """The least of :func:`experts_step_bytes` that crosses HBM INSIDE the
    ``moe.*`` scopes' own time: all of it less what the chip's vector memory
    can hold ahead of each layer's expert products. The v5e compiler stages
    an operand in that memory by an asynchronous copy that starts under the
    operations ahead (in this family's step, each layer's ``w_gate``, 100.7
    of the layer's 302.3 MB, under the sparse attention before it: the
    compiled step's ``copy-start`` / ``copy-done`` pairs into memory space 1,
    PERF.md §6 "PR 47"), so the scopes' time does not hold those bytes'
    traffic; it cannot stage more than the memory holds a layer."""
    if device_kind not in VMEM_BYTES:
        raise KeyError(f"no vector memory size for device_kind "
                       f"{device_kind!r} (known: {sorted(VMEM_BYTES)})")
    ahead = c["num_hidden_layers"] * VMEM_BYTES[device_kind]
    return max(experts_step_bytes(c) - ahead, 0.0)


def step_bytes(c: dict, scored: float, attended: float,
               slots: float) -> float:
    """The whole step: every held weight once (table rows aside: the embed
    reads a row a slot, counted as nothing) and what the sparse layers
    read and write of the pool."""
    held = ((param_count(c) - c["vocab_size"] * c["hidden_size"])
            * ITEMSIZE[c["torch_dtype"]])
    return float(held + pool_step_bytes(c, scored, attended, slots))


# -- what the readers share ---------------------------------------------------

def rows_a_step(record: dict):
    """(index keys scored, K/V rows attended, live rows) a sparse layer a
    step, means over the window's steps, from ``report()``'s additive
    counters; None where the program has no such counters or the window no
    step."""
    r0, r1 = record["report0"], record["report1"]
    keys = ("index_rows_scored", "sparse_rows_attended", "sparse_rows_live")
    steps = r1.get("steps", 0) - r0.get("steps", 0)
    if not steps or any(k not in r0 or k not in r1 for k in keys):
        return None
    return tuple((r1[k] - r0[k]) / steps for k in keys)


def peak_share(record: dict, need_bytes: float, ops: float, ms: float):
    """The longer of ``need_bytes`` at the chip's HBM peak and ``ops`` at
    its bf16 peak, as a share (%) of ``ms``."""
    from benchmark.peaks import peak

    kind = record["device_kind"]
    floor_s = max(need_bytes / peak(kind, "hbm_bytes_s"),
                  ops / peak(kind, "bf16_flops"))
    return 100.0 * 1e3 * floor_s / ms
