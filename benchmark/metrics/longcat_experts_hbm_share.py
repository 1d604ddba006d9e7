"""The least time the routed layers' routers, selection biases and held
experts need at the HBM peak, read once
(``rooflines_longcat_flash.experts_step_bytes``: 4 published layers), as a
share of the ``moe.*`` scopes' device time per step (which holds the identity
part, and what the traced window's prefills spend under those scopes too, so
this reads low by their part). A floor. None where the program has no such
scope."""
from benchmark.program_trace import STEP_SPAN
from benchmark.rooflines_granitemoehybrid import hbm_share, scope_ms
from benchmark.rooflines_longcat_flash import MOE_SCOPES, experts_step_bytes


def read(record: dict):
    ms = scope_ms(record, MOE_SCOPES, STEP_SPAN)
    if ms is None:
        return None
    return hbm_share(record, experts_step_bytes(record["config"]), ms)
