"""The least time a step's window latent attention needs (the scope's
weights once, the ring rows inside the band at their stored width, a row a
live slot written, all at the HBM peak; or the absorbed attend's
multiply-adds at the bf16 peak where that is longer:
``rooflines_dots3_note.window_latent_step_need``) as a share of the
``attn.window_latent`` scopes' device time inside the step executable
(``attn_window_latent_dev_ms``). The kernels' roofline: the same need
whichever read fetches the ring. The rows are ``report()``'s
``window_rows_live`` (min(length, sliding_window) over the live streams), the
mean of the window's two edges. A floor: it cannot pass 100%. None where the
program has no such scope or counter."""
from benchmark.rooflines_dots3_note import (WINDOW_LATENT_SCOPES,
                                            window_latent_step_need)
from benchmark.rooflines_granitemoehybrid import live_slots
from benchmark.rooflines_keye_vl2 import peak_share
from benchmark.rooflines_lfm2_moe import step_scope_ms
from benchmark.rooflines_mellum import window_rows


def read(record: dict):
    ms = step_scope_ms(record, WINDOW_LATENT_SCOPES)
    rows, slots = window_rows(record), live_slots(record)
    if ms is None or rows is None or slots is None:
        return None
    need, ops = window_latent_step_need(record["config"], rows[0], slots)
    return peak_share(record, need, ops, ms)
