"""Entry to return of ``ContinuousBatcher.step()`` per decode step, over the
window (the batcher's ``step_wall_s``): ``gap_mean_ms`` less this is the
benchmark's own loop, and this is the sum of the six ``host_*_ms``."""
from benchmark.program_trace import ms_per


def read(record: dict):
    return ms_per(record, "step_wall_s", "steps")
