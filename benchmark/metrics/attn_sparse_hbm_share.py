"""The least time a step's sparse attention needs (the scope's weights once,
the index keys the indexer scored at their stored width, the K/V rows the
selection named, a row of each a live slot written, all at the HBM peak; or
the indexer's and the attend's multiply-adds at the bf16 peak where that is
longer: ``rooflines_keye_vl2.sparse_step_need``) as a share of the
``attn.sparse`` scopes' device time inside the step executable. The rows are
the program's counters (``index_rows_scored``, ``sparse_rows_attended``), a
mean a step. A floor: it cannot pass 100%. None where the program has no
such scope or counter."""
from benchmark.rooflines_granitemoehybrid import live_slots
from benchmark.rooflines_keye_vl2 import (SPARSE_SCOPES, peak_share,
                                          rows_a_step, sparse_step_need)
from benchmark.rooflines_lfm2_moe import step_scope_ms


def read(record: dict):
    ms = step_scope_ms(record, SPARSE_SCOPES)
    rows, slots = rows_a_step(record), live_slots(record)
    if ms is None or rows is None or slots is None:
        return None
    need, ops = sparse_step_need(record["config"], rows[0], rows[1], slots)
    return peak_share(record, need, ops, ms)
