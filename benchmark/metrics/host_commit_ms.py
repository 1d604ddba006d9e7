"""Host time per decode step in ``batch.step.commit`` (the batcher's
``commit_s`` clock over the window): appending the tokens, retiring finished
streams, the occupancy sums."""
from benchmark.program_trace import ms_per


def read(record: dict):
    return ms_per(record, "commit_s", "steps")
