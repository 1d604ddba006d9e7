"""The least time a decode step's bytes need at the HBM peak (every held
weight once, of the table a row a slot, the head whole; the full layer's live
K/V rows and the rings' live rows once; a row a slot a layer written:
``rooflines_afmoe.step_bytes``) as a share of the step executable's device
time. A floor: it cannot pass 100%. None where the program has no window
counters or the run no trace."""
from benchmark.rooflines_afmoe import step_bytes
from benchmark.rooflines_granitemoehybrid import hbm_share, live_slots
from benchmark.rooflines_mellum import window_rows
from benchmark.trace_reduce import step_runs_seconds


def read(record: dict):
    step = step_runs_seconds(record)
    slots = live_slots(record)
    rows = window_rows(record)
    if step is None or slots is None or rows is None:
        return None
    live = record["pool_live_share"] * record["token_capacity"]
    need = step_bytes(record["config"], live, rows[0], slots)
    return hbm_share(record, need, 1e3 * step[1] / step[0])
