"""Device-idle time per decode step outside ``batch.step.sync``: the device
waits for the host (admission, building inputs, launch, commit, the
benchmark's own loop), from the traced window; mean over the cell's chips."""
from benchmark.program_trace import idle_ms_per_step


def read(record: dict):
    idle = idle_ms_per_step(record)
    return idle[1] if idle else None
