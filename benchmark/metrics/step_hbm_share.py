"""The least time the decode step's bytes need at the HBM peak (weights
once, live K/V once, one new row per slot: ``rooflines.decode_step_bytes``;
spread over the cell's chips) as a share of the step's device time."""
from benchmark.peaks import peak
from benchmark.rooflines import ITEMSIZE, decode_step_bytes
from benchmark.trace_reduce import step_runs_seconds


def read(record: dict):
    step = step_runs_seconds(record)
    if step is None:
        return None
    live = record["pool_live_share"] * record["token_capacity"]
    need = decode_step_bytes(record["model"], live,
                             record["config"]["serving"]["max_slots"],
                             ITEMSIZE[record["config"]["torch_dtype"]])
    floor_s = need / (peak(record["device_kind"], "hbm_bytes_s")
                      * record["trace"]["chips"])
    return 100.0 * floor_s / (step[1] / step[0])
