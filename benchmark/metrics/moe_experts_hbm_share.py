"""The least time every layer's router, held experts and shared expert need
at the HBM peak, read once (``rooflines_granitemoehybrid.moe_step_bytes``), as
a share of the ``moe.*`` scopes' device time per step (which holds what the
traced window's prefills spend under those scopes too, so this reads low by
their part)."""
from benchmark.program_trace import STEP_SPAN
from benchmark.rooflines_granitemoehybrid import (MOE_SCOPES, hbm_share,
                                                  moe_step_bytes, scope_ms)


def read(record: dict):
    ms = scope_ms(record, MOE_SCOPES, STEP_SPAN)
    if ms is None:
        return None
    return hbm_share(record, moe_step_bytes(record["config"]), ms)
