"""The least time the expert layers' routers, selection biases, held experts
and shared experts need at the HBM peak, read once
(``rooflines_afmoe.experts_step_bytes``), as a share of the ``moe.*`` scopes'
device time per step (which holds what the traced window's prefills spend
under those scopes too, so this reads low by their part). None where the
program has no such scope."""
from benchmark.program_trace import STEP_SPAN
from benchmark.rooflines_afmoe import MOE_SCOPES, experts_step_bytes
from benchmark.rooflines_granitemoehybrid import hbm_share, scope_ms


def read(record: dict):
    ms = scope_ms(record, MOE_SCOPES, STEP_SPAN)
    if ms is None:
        return None
    return hbm_share(record, experts_step_bytes(record["config"]), ms)
