"""The least time the short convolutions' bytes need at the HBM peak (every
conv mixer's weights read once, the live slots' windows read once and written
once: ``rooflines_lfm2_moe.shortconv_step_bytes``) as a share of the
``shortconv.*`` scopes' device time inside the step executable. A floor: it
cannot pass 100%. None where the program has no such scope."""
from benchmark.rooflines_granitemoehybrid import hbm_share, live_slots
from benchmark.rooflines_lfm2_moe import (SHORTCONV_SCOPES,
                                          shortconv_step_bytes, step_scope_ms)


def read(record: dict):
    ms = step_scope_ms(record, SHORTCONV_SCOPES)
    slots = live_slots(record)
    if ms is None or slots is None:
        return None
    return hbm_share(record, shortconv_step_bytes(record["config"], slots),
                     ms)
