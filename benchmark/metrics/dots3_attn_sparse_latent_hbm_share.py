"""The least time a step's FULL (sparse latent) layers need (the scope's
weights once, the heads' gate among them; the index keys the indexer scored at
their stored width, the latent rows the selection NAMED, a row of each a live
slot written, all at the HBM peak; or the indexer's and the absorbed attend's
multiply-adds at the bf16 peak where that is longer:
``rooflines_dots3_note.sparse_latent_step_need``, the two full layers
counted, not the stack's five) as a share of the ``attn.sparse_latent``
scopes' device time inside the step executable
(``attn_sparse_latent_dev_ms``). A floor: it cannot pass 100%. None where the
program has no such scope or counter."""
from benchmark.rooflines_dots3_note import (SPARSE_LATENT_SCOPES,
                                            sparse_latent_step_need)
from benchmark.rooflines_granitemoehybrid import live_slots
from benchmark.rooflines_keye_vl2 import peak_share, rows_a_step
from benchmark.rooflines_lfm2_moe import step_scope_ms


def read(record: dict):
    ms = step_scope_ms(record, SPARSE_LATENT_SCOPES)
    rows, slots = rows_a_step(record), live_slots(record)
    if ms is None or rows is None or slots is None:
        return None
    need, ops = sparse_latent_step_need(record["config"], rows[0], rows[1],
                                        slots)
    return peak_share(record, need, ops, ms)
