"""Mean wall of a launched step that admitted at least one stream
(``admit_step_wall_s`` / ``admit_steps`` over the window): what every running
stream waits when a prefill runs inline."""
from benchmark.program_trace import ms_per


def read(record: dict):
    return ms_per(record, "admit_step_wall_s", "admit_steps")
