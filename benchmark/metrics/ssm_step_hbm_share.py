"""The least time the live slots' recurrent state needs at the HBM peak (read
once, written once: ``rooflines_granitemoehybrid.ssm_step_bytes``) as a share
of the ``ssm.step`` scope's device time per step."""
from benchmark.program_trace import STEP_SPAN
from benchmark.rooflines_granitemoehybrid import (hbm_share, live_slots,
                                                  scope_ms, ssm_step_bytes)


def read(record: dict):
    ms = scope_ms(record, ("ssm.step",), STEP_SPAN)
    slots = live_slots(record)
    if ms is None or slots is None:
        return None
    return hbm_share(record, ssm_step_bytes(record["config"], slots), ms)
