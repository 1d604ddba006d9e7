"""The caller's time between two consecutive launched steps, per step: from
one ``step()``'s return to the next one's entry (``between_s`` / ``steps``).
No program clock holds it: it is the loop that drives the batcher, here the
benchmark's own (stamping tokens, replacing finished requests). With
``step_wall_ms`` it is the window over its steps in a closed loop."""
from benchmark.program_trace import ms_per


def read(record: dict):
    return ms_per(record, "between_s", "steps")
