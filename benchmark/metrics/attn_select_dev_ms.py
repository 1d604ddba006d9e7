"""Device time per decode step under the ``attn.sparse.select`` scope: every
sparse layer's exact top-k of a slot's live index scores and what turns the
chosen positions into row ids of the pool. Inside the step executable only
(``rooflines_lfm2_moe.step_scope_ms``). None where the program has no such
scope."""
from benchmark.rooflines_keye_vl2 import SELECT_SCOPES
from benchmark.rooflines_lfm2_moe import step_scope_ms


def read(record: dict):
    return step_scope_ms(record, SELECT_SCOPES)
