"""The least time the full layers' LIVE rows need at the HBM peak, each read
once (``rooflines_mellum.full_rows_bytes``), as a share of the ``attn.decode``
scope's device time per step. Reads low where the gather moves every slot's
whole span and not its live rows, writes the gathered copy and the attend
reads it again; the scope holds the full layers' projections too."""
from benchmark.program_trace import STEP_SPAN
from benchmark.rooflines_granitemoehybrid import hbm_share, scope_ms
from benchmark.rooflines_mellum import full_rows_bytes, window_rows


def read(record: dict):
    ms = scope_ms(record, ("attn.decode",), STEP_SPAN)
    if ms is None or window_rows(record) is None:
        return None
    live = record["pool_live_share"] * record["token_capacity"]
    return hbm_share(record, full_rows_bytes(record["config"], live), ms)
