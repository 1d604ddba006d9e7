"""Device time per decode step under the ``attn.latent`` scope: every latent
layer's down- and up-projections, rotation, the absorption of ``W_kvb``'s K
half into the query, the gather of each slot's pages of latent rows, the
attend over them, the V up-projection and the output projection (the row's
write is ``paged_kv.write``'s). From the program table's scope sums /
``batch.step`` spans; None where the program has no such scope."""
from benchmark.program_trace import STEP_SPAN
from benchmark.rooflines_granitemoehybrid import scope_ms


def read(record: dict):
    return scope_ms(record, ("attn.latent",), STEP_SPAN)
