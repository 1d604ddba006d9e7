"""The least time the absorbed attend of a step needs on the chip — the LIVE
latent rows of every layer read once at their stored width at the HBM peak,
or the attend's multiply-adds at the bf16 peak where that is the larger
(``rooflines_mistral4.attend_floor_ms``) — as a share of the ``attn.latent``
scope's device time per step. Reads low by design: the gather moves every
slot's whole span and not its live rows, writes the gathered copy and the
attend reads it twice, and the scope holds the projections too. A floor: it
cannot pass 100%."""
from benchmark.program_trace import STEP_SPAN
from benchmark.rooflines_granitemoehybrid import scope_ms
from benchmark.rooflines_mistral4 import attend_floor_ms, latent_rows


def read(record: dict):
    ms = scope_ms(record, ("attn.latent",), STEP_SPAN)
    rows = latent_rows(record)
    if ms is None or rows is None:
        return None
    return 100.0 * attend_floor_ms(record, rows[0], rows[2]) / ms
