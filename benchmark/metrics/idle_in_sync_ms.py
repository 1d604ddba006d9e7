"""Device-idle time per decode step while ``batch.step.sync`` was the innermost
open program span (the host waits for the tokens and the device still has
nothing to run), from the traced window; mean over the cell's chips."""
from benchmark.program_trace import idle_ms_per_step


def read(record: dict):
    idle = idle_ms_per_step(record)
    return idle[0] if idle else None
