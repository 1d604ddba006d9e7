"""The least time a decode step's bytes need at the HBM peak (every held
weight once, the tied table whole; the live slots' windows read and written;
the attention layers' live K/V rows once and a row a slot a layer written:
``rooflines_lfm2_moe.step_bytes``) as a share of the step executable's device
time. A floor: it cannot pass 100%. None where the run has no trace."""
from benchmark.rooflines_granitemoehybrid import hbm_share, live_slots
from benchmark.rooflines_lfm2_moe import step_bytes
from benchmark.trace_reduce import step_runs_seconds


def read(record: dict):
    step = step_runs_seconds(record)
    slots = live_slots(record)
    if step is None or slots is None:
        return None
    live = record["pool_live_share"] * record["token_capacity"]
    return hbm_share(record, step_bytes(record["config"], live, slots),
                     1e3 * step[1] / step[0])
