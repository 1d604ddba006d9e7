"""Host time per decode step in ``batch.step.grow`` (the batcher's ``grow_s``
clock over the window): page growth for every running slot, and the evictions
it forces."""
from benchmark.program_trace import ms_per


def read(record: dict):
    return ms_per(record, "grow_s", "steps")
