"""The least time the absorbed attends of a step need on the chip — the LIVE
latent rows of every SUBLAYER (two a published layer) read once at their
stored width at the HBM peak, or the attends' multiply-adds at the bf16 peak
where that is the larger (``rooflines_longcat_flash.attend_floor_ms``) — as a
share of the ``attn.latent`` scope's device time per step. The scope holds
the projections too, so it reads low by design. A floor: it cannot pass 100%.
None where the program has no latent counters or no such scope.
(``attn_latent_hbm_share`` counts one latent row a ``num_hidden_layers``,
half of this family's: this cell reports this reader instead.)"""
from benchmark.program_trace import STEP_SPAN
from benchmark.rooflines_granitemoehybrid import scope_ms
from benchmark.rooflines_longcat_flash import attend_floor_ms
from benchmark.rooflines_mistral4 import latent_rows


def read(record: dict):
    ms = scope_ms(record, ("attn.latent",), STEP_SPAN)
    rows = latent_rows(record)
    if ms is None or rows is None:
        return None
    return 100.0 * attend_floor_ms(record, rows[0], rows[2]) / ms
