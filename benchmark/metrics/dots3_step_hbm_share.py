"""The least time a decode step's bytes need at the HBM peak (every held
weight once, the head whole; the index keys the indexers scored and the
latent rows the selections named, once; the ring rows inside the band, once;
a row of each leaf a slot a layer written:
``rooflines_dots3_note.step_bytes``) as a share of the step executable's
device time. A floor: it cannot pass 100%. None where the run has no trace or
the program no such counters."""
from benchmark.rooflines_dots3_note import step_bytes
from benchmark.rooflines_granitemoehybrid import hbm_share, live_slots
from benchmark.rooflines_keye_vl2 import rows_a_step
from benchmark.rooflines_mellum import window_rows
from benchmark.trace_reduce import step_runs_seconds


def read(record: dict):
    step = step_runs_seconds(record)
    rows, ring = rows_a_step(record), window_rows(record)
    slots = live_slots(record)
    if step is None or rows is None or ring is None or slots is None:
        return None
    return hbm_share(record, step_bytes(record["config"], rows[0], rows[1],
                                        ring[0], slots),
                     1e3 * step[1] / step[0])
