"""The least time a hybrid decode step's bytes need at the HBM peak (every
held weight once, the live slots' recurrent state twice, live K/V once:
``rooflines_granitemoehybrid.hybrid_step_bytes``) as a share of the step
executable's device time."""
from benchmark.rooflines_granitemoehybrid import (hbm_share,
                                                  hybrid_step_bytes,
                                                  live_slots)
from benchmark.trace_reduce import step_runs_seconds


def read(record: dict):
    step = step_runs_seconds(record)
    slots = live_slots(record)
    if step is None or slots is None or "layer_types" not in record["config"]:
        return None
    live = record["pool_live_share"] * record["token_capacity"]
    need = hybrid_step_bytes(record["config"], live, slots)
    return hbm_share(record, need, 1e3 * step[1] / step[0])
