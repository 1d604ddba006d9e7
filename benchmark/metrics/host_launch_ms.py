"""Host time per decode step in ``batch.step.launch`` (the batcher's
``launch_s`` clock over the window): host-to-device copies of the inputs,
stacking the keys, and the step's dispatch."""
from benchmark.program_trace import ms_per


def read(record: dict):
    return ms_per(record, "launch_s", "steps")
