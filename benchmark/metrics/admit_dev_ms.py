"""Device time of every executable that is not the decode step (prefills, the
adopt scatters, the pool's layout copies, the per-slot key executables) per
``batch.admit`` span in the traced window; mean over the cell's chips."""
from benchmark import program_trace


def read(record: dict):
    return program_trace.admit_dev_ms(record)
