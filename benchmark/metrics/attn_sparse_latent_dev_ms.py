"""Device time per decode step under the ``attn.sparse_latent`` scope and
what stands within it (the shared ``attn.sparse.index`` and
``attn.sparse.select``, the row writes' ``paged_kv.write``): every sparse
latent layer's projections, norms and rotations, both row writes, the
indexer's score pass over the live index keys, the selection, the absorbed
read of the chosen latent rows, ``W_kvb``'s V half and ``W_o``. Only
operations that ran inside the step executable count
(``rooflines_lfm2_moe.step_scope_ms``): a prefill scores and selects under
the same inner scopes. None where the program has no such scope."""
from benchmark.rooflines_deepseek_v32 import SPARSE_LATENT_SCOPES
from benchmark.rooflines_lfm2_moe import step_scope_ms


def read(record: dict):
    return step_scope_ms(record, SPARSE_LATENT_SCOPES)
