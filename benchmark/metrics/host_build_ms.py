"""Host time per decode step in ``batch.step.build`` (the batcher's ``build_s``
clock over the window): building the step's inputs: per-slot token, step
index, temperature and PRNG key, and the page table."""
from benchmark.program_trace import ms_per


def read(record: dict):
    return ms_per(record, "build_s", "steps")
