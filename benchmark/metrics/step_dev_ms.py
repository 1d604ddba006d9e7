"""Device time of the decode step's executable per run of it, from the
trace's "XLA Modules" line (mean over the cell's chips)."""
from benchmark.trace_reduce import step_runs_seconds


def read(record: dict):
    step = step_runs_seconds(record)
    return None if step is None else 1e3 * step[1] / step[0]
