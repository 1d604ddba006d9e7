"""Device time in collective-permute operations per decode step, all cuts
together, from the trace (self time, mean over the cell's chips)."""
from benchmark.trace_reduce import step_runs_seconds


def read(record: dict):
    step = step_runs_seconds(record)
    if step is None or not record["config"].get("split"):
        return None
    hop = sum(t for name, t in record["trace"]["ops"].items()
              if "collective-permute" in name)
    return 1e3 * hop / step[0]
