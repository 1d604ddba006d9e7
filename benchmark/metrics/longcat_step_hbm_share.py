"""The least time a decode step's bytes need at the HBM peak (every held
weight once, of the table a row a slot, the head whole; the live latent rows
of the 8 sublayers once at their stored width; a row a slot a sublayer
written: ``rooflines_longcat_flash.step_bytes``) as a share of the step
executable's device time. A floor: it cannot pass 100%. None where the program
has no latent counters or the run no trace."""
from benchmark.rooflines_granitemoehybrid import hbm_share, live_slots
from benchmark.rooflines_longcat_flash import step_bytes
from benchmark.rooflines_mistral4 import latent_rows
from benchmark.trace_reduce import step_runs_seconds


def read(record: dict):
    step = step_runs_seconds(record)
    slots = live_slots(record)
    rows = latent_rows(record)
    if step is None or slots is None or rows is None:
        return None
    need = step_bytes(record["config"], rows[0], rows[2], slots)
    return hbm_share(record, need, 1e3 * step[1] / step[0])
