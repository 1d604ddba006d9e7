"""Device time per decode step under the ``attn.sparse`` scope and what
stands within it (``attn.sparse.index``, ``attn.sparse.select``, the row
writes' ``paged_kv.write``): every sparse-attention layer's projections,
norms and rotation, both row writes, the indexer's score pass over the live
index keys, the selection, the selected rows' read and attend, ``W_o``. Only
operations that ran inside the step executable count
(``rooflines_lfm2_moe.step_scope_ms``): a prefill scores and selects under
the same inner scopes. None where the program has no such scope."""
from benchmark.rooflines_keye_vl2 import SPARSE_SCOPES
from benchmark.rooflines_lfm2_moe import step_scope_ms


def read(record: dict):
    return step_scope_ms(record, SPARSE_SCOPES)
