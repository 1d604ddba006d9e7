"""Host time per decode step in ``batch.step.sync`` (the batcher's ``sync_s``
clock over the window): waiting for the sampled tokens, while the device runs
the step and what was queued ahead of it."""
from benchmark.program_trace import ms_per


def read(record: dict):
    return ms_per(record, "sync_s", "steps")
