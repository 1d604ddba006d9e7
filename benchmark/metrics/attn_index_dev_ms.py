"""Device time per decode step under the ``attn.sparse.index`` scope: every
sparse layer's indexer, its three projections, the index key's LayerNorm and
rotation, and the score pass over each slot's live index keys read through
its pages. Inside the step executable only
(``rooflines_lfm2_moe.step_scope_ms``). None where the program has no such
scope."""
from benchmark.rooflines_keye_vl2 import INDEX_SCOPES
from benchmark.rooflines_lfm2_moe import step_scope_ms


def read(record: dict):
    return step_scope_ms(record, INDEX_SCOPES)
